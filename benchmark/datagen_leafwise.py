"""Leaf-wise (best-first) trees from `--seed`, as node lists: the same seed
gives the same trees. Kept here, not imported from the program.

LightGBM grows a tree best-first: it splits the leaf with the largest gain,
which on real data follows the rows a leaf holds, so the tree digs deep
where the mass is and is 13-20 levels deep at 255 leaves. Training 500 such
trees on 10.5M rows in every run's set-up would cost minutes; the scoring
kernel has no data-dependent branch and its time does not depend on what the
trees hold, only on their shape. So the trees are DRAWN with that shape:

    start from one leaf of mass 1 over the full bin box (every feature's
    range 0 .. n_bins-1); n_leaves - 1 times: draw a leaf with probability
    proportional to its mass, a feature uniform among those whose bin range
    in that leaf is wider than one bin, a threshold uniform inside that
    range, and split: rows with bin <= threshold go left. Each child's mass
    is the parent's times its share of the range (what uniform rows would
    send it), so growth follows the mass.

Every leaf's box is non-empty, so every leaf is reachable by construction.
Numbering is LightGBM's: split k makes internal node k; the split leaf keeps
its index as the LEFT child and the RIGHT child is new leaf k + 1; a child
reference c < 0 is leaf ~c. Leaf values are N(0, 1). All trees are drawn
together, one split of every tree at a time, from ONE generator: the trees
depend on the seed and the shapes alone.
"""

from __future__ import annotations

import numpy as np


def leafwise_trees(n_trees: int, n_leaves: int, n_features: int, n_bins: int,
                   seed: int) -> dict:
    """Node tables of `n_trees` leaf-wise trees of `n_leaves` leaves each:
    feature, threshold_bin, left_child, right_child int32 [T, n_leaves-1],
    leaf_value float32 [T, n_leaves]."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 3]))
    T, L, F = n_trees, n_leaves, n_features
    trees = np.arange(T)
    lo = np.zeros((T, L, F), np.int32)
    hi = np.full((T, L, F), n_bins - 1, np.int32)
    mass = np.zeros((T, L), np.float64)
    mass[:, 0] = 1.0
    # where a leaf hangs: (node, 0 left / 1 right); the root leaf nowhere
    leaf_parent = np.full((T, L), -1, np.int64)
    leaf_side = np.zeros((T, L), np.int64)
    feature = np.zeros((T, L - 1), np.int32)
    threshold = np.zeros((T, L - 1), np.int32)
    child = np.zeros((T, L - 1, 2), np.int32)
    for k in range(L - 1):
        # a leaf by its mass (a leaf whose box is one bin wide in every
        # feature cannot be split and has its mass set to 0 below)
        cum = np.cumsum(mass[:, :k + 1], axis=1)
        leaf = (cum < (rng.random(T) * cum[:, -1])[:, None]).sum(axis=1)
        leaf = np.minimum(leaf, k)
        l_lo, l_hi = lo[trees, leaf], hi[trees, leaf]           # [T, F]
        wide = l_hi > l_lo
        if not wide.any(axis=1).all():
            raise ValueError("a drawn leaf has no feature left to split")
        f = np.argmax(np.where(wide, rng.random((T, F)), -1.0), axis=1)
        f_lo, f_hi = l_lo[trees, f], l_hi[trees, f]
        t = f_lo + np.floor(rng.random(T) * (f_hi - f_lo)).astype(np.int32)
        t = np.minimum(t, f_hi - 1)           # left lo..t, right t+1..hi
        feature[:, k], threshold[:, k] = f, t
        # node k takes the leaf's place under the leaf's parent
        hung = leaf_parent[trees, leaf] >= 0
        child[trees[hung], leaf_parent[trees, leaf][hung],
              leaf_side[trees, leaf][hung]] = k
        new = k + 1
        child[:, k, 0], child[:, k, 1] = ~leaf, ~new
        leaf_parent[trees, leaf], leaf_side[trees, leaf] = k, 0
        leaf_parent[:, new], leaf_side[:, new] = k, 1
        # the boxes and the masses
        lo[:, new], hi[:, new] = l_lo, l_hi
        lo[trees, new, f] = t + 1
        hi[trees, leaf, f] = t
        share = (t - f_lo + 1) / (f_hi - f_lo + 1)
        parent_mass = mass[trees, leaf]
        mass[trees, leaf] = parent_mass * share
        mass[:, new] = parent_mass * (1.0 - share)
        for at in (leaf, np.full(T, new)):
            dead = ~(hi[trees, at] > lo[trees, at]).any(axis=1)
            mass[trees[dead], at[dead]] = 0.0
    return {
        "feature": feature, "threshold_bin": threshold,
        "left_child": np.ascontiguousarray(child[:, :, 0]),
        "right_child": np.ascontiguousarray(child[:, :, 1]),
        "leaf_value": rng.standard_normal((T, L)).astype(np.float32),
    }
