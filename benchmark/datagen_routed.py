"""Inputs of a ROUTED ensemble from `--seed`: a click log's rows as bins, and
full trees whose nodes take all three routes (`reference_routed.py`). The
same seed gives the same inputs; nothing here is imported from the program.

The schema is the public Criteo click logs': 13 integer columns with missing
values, then 26 categorical columns. Rows are made as BINS directly, as
`datagen.uniform_bins` makes them and for its reason (drawing floats and
categories first and binning them costs ten times the set-up):

    numeric column j   the NaN bin (the top bin) with a fixed share
                       MISSING_SHARE[j], else uniform over the other bins,
                       which is what quantile binning makes of the values
                       that are there;
    categorical        a power law over frequency ranks,
                       floor((n_bins - 1) u^3) for uniform u, which is what
                       the program's own synthetic click log draws and
                       frequency binning keeps (rank 0 the most frequent
                       category); no NaN bin: a missing category is a
                       category of its own.

Every cell of the batch is ONE lookup of a 16-bit uniform draw in its
column's table of 65,536 bins, so a column's distribution is exact to
1/65,536 and the draw costs two random bytes a cell. Blocks of rows are drawn
by a few threads, each block from its own child of the seed, so the bytes do
not depend on how many threads ran.
"""

from __future__ import annotations

import numpy as np

from datagen import BLOCK_ROWS, THREADS

# Share of missing values in the 13 integer columns of the Kaggle Display
# Advertising Challenge set (the 1TB logs have the same columns), FROM
# MEMORY and rounded: there is no network here to read them off the data.
# What matters to the kernel is that the shares are uneven and that two
# columns have none.
MISSING_SHARE = (0.45, 0.0, 0.21, 0.22, 0.03, 0.22, 0.04, 0.0, 0.04, 0.45,
                 0.04, 0.77, 0.22)
_DRAWS = 1 << 16


def category_bins(u: np.ndarray, n_bins: int) -> np.ndarray:
    """The frequency rank of a category drawn at uniform `u` in [0, 1):
    floor((n_bins - 1) u^3), in 0 .. n_bins-2 (the top bin stays the NaN
    bin, which no categorical column takes)."""
    return np.floor((n_bins - 1) * np.power(u, 3.0)).astype(np.int64)


def column_tables(n_numeric: int, n_features: int,
                  n_bins: int) -> np.ndarray:
    """uint8 [F, 65536]: the bin of a cell of column j whose 16-bit draw
    is v."""
    if n_numeric > len(MISSING_SHARE):
        raise ValueError(f"{n_numeric} numeric columns, missing shares of "
                         f"{len(MISSING_SHARE)}")
    v = np.arange(_DRAWS)
    tables = np.empty((n_features, _DRAWS), np.uint8)
    for j in range(n_numeric):
        cut = int(round(MISSING_SHARE[j] * _DRAWS))
        present = (v - cut) * (n_bins - 1) // (_DRAWS - cut)
        tables[j] = np.where(v < cut, n_bins - 1, present)
    tables[n_numeric:] = category_bins((v + 0.5) / _DRAWS, n_bins)
    return tables


def click_log_bins(rows: int, n_numeric: int, n_features: int, n_bins: int,
                   seed: int) -> np.ndarray:
    """uint8 [R, F]: columns 0 .. n_numeric-1 numeric with NaN bins, the
    rest categorical (module docstring)."""
    from concurrent.futures import ThreadPoolExecutor

    flat_tables = column_tables(n_numeric, n_features, n_bins).reshape(-1)
    column_base = np.arange(n_features, dtype=np.int32) * _DRAWS
    out = np.empty((rows, n_features), np.uint8)
    n_blocks = -(-rows // BLOCK_ROWS)

    def fill(i: int) -> None:
        rng = np.random.default_rng(np.random.SeedSequence([seed, 5, i]))
        dst = out[i * BLOCK_ROWS:(i + 1) * BLOCK_ROWS]
        words = rng.integers(0, 2 ** 64 - 1, size=-(-dst.size // 4),
                             dtype=np.uint64, endpoint=True)
        draws = words.view(np.uint16)[:dst.size].reshape(dst.shape)
        # mode="clip": every index is in range, and "raise" would buffer
        np.take(flat_tables, draws + column_base, out=dst, mode="clip")

    with ThreadPoolExecutor(THREADS) as ex:
        list(ex.map(fill, range(n_blocks)))
    return out


def random_routed_trees(n_trees: int, depth: int, n_features: int,
                        n_bins: int, cat_features, seed: int) -> dict:
    """Node tables of `n_trees` full trees (heap layout [T, 2^(depth+1)-1],
    every internal node splits). A node's feature is uniform over the
    columns; an ordinal node's threshold uniform over the bins a numeric
    value takes, but the last (which would send every present value left);
    a category node's threshold drawn as the column's own rows are, so that
    rows match it; its learned direction for the NaN bin a fair coin; leaf
    values N(0,1)."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 6]))
    n_nodes = 2 ** (depth + 1) - 1
    shape = (n_trees, n_nodes)
    is_leaf = np.zeros(shape, bool)
    is_leaf[:, n_nodes // 2:] = True
    feature = rng.integers(0, n_features, size=shape, dtype=np.int32)
    ordinal = rng.integers(0, n_bins - 2, size=shape, dtype=np.int32)
    category = category_bins(rng.random(shape), n_bins).astype(np.int32)
    return {
        "feature": feature,
        "threshold_bin": np.where(np.isin(feature, cat_features), category,
                                  ordinal),
        "is_leaf": is_leaf,
        "leaf_value": rng.standard_normal(shape).astype(np.float32),
        "default_left": rng.random(shape) < 0.5,
    }
