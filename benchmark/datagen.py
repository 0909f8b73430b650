"""Inputs from `--seed`: the same seed gives the same inputs.

Kept here, not imported, so that no later PR to the program can change what
the benchmark feeds it. Originals (listed in PERF.md, Open questions, for a
later PR to delete or to import from here): `ddt_tpu/bench.py _predict_setup`
(random full trees, uniform bins).

Rows are made as BINS directly: quantile binning makes any continuous
feature's bins uniform, and drawing floats first and binning them costs ten
times the set-up at the sizes that fill a chip. Blocks of rows are drawn by a
few threads, each block from its own child of the seed, so the bytes do not
depend on how many threads ran.
"""

from __future__ import annotations

import numpy as np


BLOCK_ROWS = 1 << 18          # rows a block; fixed, it is part of the data
THREADS = 6


def uniform_bins(rows: int, n_features: int, n_bins: int,
                 seed: int) -> np.ndarray:
    """Uniform random bins, uint8 [R, F], in 0 .. n_bins-1."""
    from concurrent.futures import ThreadPoolExecutor

    out = np.empty((rows, n_features), np.uint8)
    flat = out.reshape(-1)
    per = BLOCK_ROWS * n_features
    per += -per % 8
    n_blocks = -(-flat.size // per)

    def fill(i: int) -> None:
        rng = np.random.default_rng(np.random.SeedSequence([seed, 2, i]))
        dst = flat[i * per:(i + 1) * per]
        words = rng.integers(0, 2 ** 64 - 1, size=-(-dst.size // 8),
                             dtype=np.uint64, endpoint=True)
        # a byte is uniform on 0..255; folding the top values down keeps
        # every bin populated (bin 0 twice as often, at 255 bins, which no
        # kernel's time depends on)
        np.mod(words.view(np.uint8)[:dst.size], n_bins, out=dst)

    with ThreadPoolExecutor(THREADS) as ex:
        list(ex.map(fill, range(n_blocks)))
    return out


def random_full_trees(n_trees: int, depth: int, n_features: int, n_bins: int,
                      seed: int) -> dict:
    """Node tables of `n_trees` full trees (every internal node splits, every
    node of the last level is a leaf), heap layout [T, 2^(depth+1)-1]."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 3]))
    n_nodes = 2 ** (depth + 1) - 1
    is_leaf = np.zeros((n_trees, n_nodes), bool)
    is_leaf[:, n_nodes // 2:] = True
    return {
        "feature": rng.integers(0, n_features, size=(n_trees, n_nodes),
                                dtype=np.int32),
        "threshold_bin": rng.integers(0, n_bins - 1, size=(n_trees, n_nodes),
                                      dtype=np.int32),
        "is_leaf": is_leaf,
        "leaf_value": rng.standard_normal(
            (n_trees, n_nodes)).astype(np.float32),
    }
