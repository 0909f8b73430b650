"""Inputs of the Bosch scoring cell from `--seed`: a wide, sparse sensor
table as BINS, and leaf-wise trees with learned NaN directions grown on that
distribution. The same seed gives the same inputs. Kept here, not imported
from the program.

The table (Kaggle, "Bosch Production Line Performance", the numeric files):
a part passes a few of some fifty stations and a column belongs to one
station, so four cells in five are missing and the missing columns come in
runs. Drawn so: the columns are cut into runs ("stations", 8-31 columns
each); a station's missing share is drawn once from 0.45 .. 0.995, every
column of it takes that share give or take 0.03, and all shares are then
shifted so that their mean is `MISSING_MEAN` (0.81). A cell of column j is
missing with share p_j, independently (a share is held in 256ths: a cell is
missing where a random byte is below `missing_bytes[j]`); a present value is
uniform over the VALUE bins 0 .. n_bins-2 (what quantile binning makes of a
continuous feature) and a missing one takes the reserved top bin n_bins-1,
the quantizer's NaN bin under `missing_policy="learn"`.

The trees are drawn as `datagen_leafwise.leafwise_trees` draws them, with the
one difference that a leaf's MASS is counted with the NaN route: start from
one leaf of mass 1 whose box is every feature's full value range with NaN
alive in it; n_leaves - 1 times draw a leaf with probability proportional to
its mass, a feature uniform among those whose value range in that leaf is
wider than one bin, a threshold uniform inside that range and a default
direction by a fair coin, and split: present values <= threshold go left,
NaN goes where the coin said. A feature's share of a leaf's mass is

    p_j [NaN still reaches the leaf] + (1 - p_j) (values left) / (n_bins-1)

and a child's mass the parent's with that feature's share replaced, so
growth follows the rows such a table would send. Every leaf keeps a value
bin of every feature, so every leaf is reachable by construction; the
side a node does NOT send NaN to keeps (1 - p_j) of it at most, a fifth, so
the trees dig deep along the NaN routes (tens of levels at 255 leaves).
Numbering is LightGBM's (see `datagen_leafwise.py`).
"""

from __future__ import annotations

import numpy as np

BLOCK_ROWS = 1 << 14          # rows a block; fixed, it is part of the data
THREADS = 6
MISSING_MEAN = 0.81


def missing_bytes(n_features: int, seed: int) -> np.ndarray:
    """uint16 [F], at most 255: column j is missing where a uniform byte is
    below missing_bytes[j], so its share is missing_bytes[j] / 256."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 5]))
    share = np.empty(n_features)
    j = 0
    while j < n_features:
        run = int(rng.integers(8, 32))
        station = rng.uniform(0.45, 0.995)
        share[j:j + run] = station + rng.uniform(-0.03, 0.03,
                                                 len(share[j:j + run]))
        j += run
    for _ in range(8):          # the mean, inside the clip
        share = np.clip(share + (MISSING_MEAN - share.mean()), 0.05, 0.994)
    return np.round(share * 256).astype(np.uint16)


def sparse_bins(rows: int, n_features: int, n_bins: int, seed: int,
                missing: np.ndarray) -> np.ndarray:
    """uint8 [R, F]: bin n_bins-1 where the cell is missing, else uniform
    over 0 .. n_bins-2. Blocks of rows are drawn by a few threads, each
    block from its own child of the seed, so the bytes do not depend on how
    many threads ran."""
    from concurrent.futures import ThreadPoolExecutor

    out = np.empty((rows, n_features), np.uint8)
    cut = np.minimum(missing, 255).astype(np.uint8)[None, :]
    n_blocks = -(-rows // BLOCK_ROWS)

    def fill(i: int) -> None:
        rng = np.random.default_rng(np.random.SeedSequence([seed, 6, i]))
        dst = out[i * BLOCK_ROWS:(i + 1) * BLOCK_ROWS]
        # two uniform bytes a cell: the value, scaled into the value bins
        # (byte x (n_bins-1) >> 8: two of them come up twice as often, which
        # no kernel's time depends on), and the missing draw
        pair = rng.integers(0, 256, size=(2, BLOCK_ROWS, n_features),
                            dtype=np.uint8)[:, :len(dst)]
        value = pair[0].astype(np.uint16)
        value *= n_bins - 1
        value >>= 8
        np.copyto(dst, value, casting="unsafe")
        np.copyto(dst, n_bins - 1, where=pair[1] < cut)

    with ThreadPoolExecutor(THREADS) as ex:
        list(ex.map(fill, range(n_blocks)))
    return out


def leafwise_nan_trees(n_trees: int, n_leaves: int, n_features: int,
                       n_bins: int, seed: int, missing: np.ndarray) -> dict:
    """Node tables of `n_trees` leaf-wise trees of `n_leaves` leaves each,
    grown on the table's distribution: feature, threshold_bin (0 ..
    n_bins-3), left_child, right_child int32 and default_left bool
    [T, n_leaves-1], leaf_value float32 [T, n_leaves]."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 3]))
    T, L, F = n_trees, n_leaves, n_features
    n_val = n_bins - 1                      # value bins; bin n_val is NaN's
    p = missing.astype(np.float64) / 256.0
    trees = np.arange(T)
    lo = np.zeros((T, L, F), np.uint8)
    hi = np.full((T, L, F), n_val - 1, np.uint8)
    nan_ok = np.ones((T, L, F), bool)
    mass = np.zeros((T, L), np.float64)
    mass[:, 0] = 1.0
    # where a leaf hangs: (node, 0 left / 1 right); the root leaf nowhere
    leaf_parent = np.full((T, L), -1, np.int64)
    leaf_side = np.zeros((T, L), np.int64)
    feature = np.zeros((T, L - 1), np.int32)
    threshold = np.zeros((T, L - 1), np.int32)
    default_left = np.zeros((T, L - 1), bool)
    child = np.zeros((T, L - 1, 2), np.int32)

    def share(f_lo, f_hi, alive, pf):
        return pf * alive + (1.0 - pf) * (f_hi - f_lo + 1.0) / n_val

    for k in range(L - 1):
        cum = np.cumsum(mass[:, :k + 1], axis=1)
        leaf = (cum < (rng.random(T) * cum[:, -1])[:, None]).sum(axis=1)
        leaf = np.minimum(leaf, k)
        l_lo, l_hi = lo[trees, leaf], hi[trees, leaf]           # [T, F]
        wide = l_hi > l_lo
        if not wide.any(axis=1).all():
            raise ValueError("a drawn leaf has no feature left to split")
        f = np.argmax(np.where(wide, rng.random((T, F)), -1.0), axis=1)
        f_lo = l_lo[trees, f].astype(np.int64)
        f_hi = l_hi[trees, f].astype(np.int64)
        t = f_lo + np.floor(rng.random(T) * (f_hi - f_lo)).astype(np.int64)
        t = np.minimum(t, f_hi - 1)           # left lo..t, right t+1..hi
        dl = rng.random(T) < 0.5
        feature[:, k], threshold[:, k], default_left[:, k] = f, t, dl
        # node k takes the leaf's place under the leaf's parent
        hung = leaf_parent[trees, leaf] >= 0
        child[trees[hung], leaf_parent[trees, leaf][hung],
              leaf_side[trees, leaf][hung]] = k
        new = k + 1
        child[:, k, 0], child[:, k, 1] = ~leaf, ~new
        leaf_parent[trees, leaf], leaf_side[trees, leaf] = k, 0
        leaf_parent[:, new], leaf_side[:, new] = k, 1
        # the boxes, the NaN routes and the masses
        alive = nan_ok[trees, leaf, f]
        lo[:, new], hi[:, new], nan_ok[:, new] = l_lo, l_hi, nan_ok[trees,
                                                                     leaf]
        lo[trees, new, f] = t + 1
        hi[trees, leaf, f] = t
        nan_ok[trees, leaf, f] = alive & dl
        nan_ok[trees, new, f] = alive & ~dl
        pf = p[f]
        whole = share(f_lo, f_hi, alive, pf)
        parent_mass = mass[trees, leaf]
        mass[trees, leaf] = parent_mass * share(f_lo, t, alive & dl,
                                                pf) / whole
        mass[:, new] = parent_mass * share(t + 1, f_hi, alive & ~dl,
                                           pf) / whole
        for at in (leaf, np.full(T, new)):
            dead = ~(hi[trees, at] > lo[trees, at]).any(axis=1)
            mass[trees[dead], at[dead]] = 0.0
    return {
        "feature": feature, "threshold_bin": threshold,
        "default_left": default_left,
        "left_child": np.ascontiguousarray(child[:, :, 0]),
        "right_child": np.ascontiguousarray(child[:, :, 1]),
        "leaf_value": rng.standard_normal((T, L)).astype(np.float32),
    }
