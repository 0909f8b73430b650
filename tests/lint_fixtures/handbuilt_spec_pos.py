"""Fixture: hand-built PartitionSpec in the backend layer — specs must
resolve through backend.layout (SpecLayout) by operand name."""
import jax

P = jax.sharding.PartitionSpec


def sharded_fn(f, mesh):
    return jax.shard_map(
        f, mesh=mesh,
        in_specs=P(None),                        # LINT: handbuilt-partition-spec
        out_specs=jax.sharding.PartitionSpec(),  # LINT: handbuilt-partition-spec
    )


def named(mesh, row_axes):
    return jax.sharding.NamedSharding(mesh, P(row_axes, None))  # LINT: handbuilt-partition-spec


# Alias bypasses must not be bypasses (review finding): import aliases
# and assigned aliases of any name count as PartitionSpec.
from jax.sharding import PartitionSpec as PS  # noqa: E402

Spec = jax.sharding.PartitionSpec
Chained = Spec


def alias_forms(mesh, row_axes):
    a = PS(None)                 # LINT: handbuilt-partition-spec
    b = Spec(row_axes)           # LINT: handbuilt-partition-spec
    c = Chained()                # LINT: handbuilt-partition-spec
    return a, b, c
