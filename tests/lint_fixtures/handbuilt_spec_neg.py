"""Fixture: specs resolved through the declarative layout — the
sanctioned pattern (and a bare `P(...)` call where P is NOT the
PartitionSpec alias)."""
import jax


def sharded_fn(f, mesh, lay):
    return jax.shard_map(
        f, mesh=mesh,
        in_specs=lay.specs("data", "grad"),
        out_specs=lay.replicated(),
    )


def named(mesh, lay):
    return jax.sharding.NamedSharding(mesh, lay.row_vector())


def P(x):
    """A local helper that merely shares the short name."""
    return x


def not_a_spec():
    return P(3)
