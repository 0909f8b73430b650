"""What every job kind shares: the program's TrainConfig from a cell's files."""

from __future__ import annotations

# What a TPU resolves by default, forced in a rehearsal so that the same
# kernels run (interpreted) and the same what-ran checks hold.
REHEARSAL_OVERRIDES = dict(hist_impl="pallas", hist_subtraction="on",
                           predict_impl="pallas")


def fold_seed(seed: int) -> int:
    """`--seed` may exceed 32 signed bits; TrainConfig.seed feeds device
    PRNG keys, which may not."""
    return int(seed) % (2 ** 31 - 1)


def train_config(cell: dict, seed: int, rehearse: bool, control: dict):
    """configs/<config>.json "train_config" <- workloads/<cell>.json
    "overrides" <- a control run's --set, in that order."""
    from ddt_tpu.config import TrainConfig

    fields = dict(cell["config"]["train_config"])
    fields.update(cell["overrides"])
    if rehearse:
        fields.update(REHEARSAL_OVERRIDES)
    fields.update(control)
    return TrainConfig(seed=fold_seed(seed), **fields)


def scaled_shapes(cell: dict, rehearse: bool) -> dict:
    """The configuration's shapes; a rehearsal takes 1/100 of the rows and
    what the config's "rehearse" block replaces (fewer trees)."""
    shapes = dict(cell["config"]["shapes"])
    if rehearse:
        shapes["rows"] = max(1000, shapes["rows"] // 100)
        shapes.update(cell["config"].get("rehearse", {}))
    return shapes
