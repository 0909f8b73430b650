"""A random forest grown to purity, as node lists with a class distribution
a leaf, from a FIXED forest seed: every `--seed` scores the same forest (the
program compiles the same tables in every run) and draws its own rows. Kept
here, not imported from the program.

scikit-learn's `RandomForestClassifier` at its defaults grows every tree
until its leaves are pure (`max_depth=None`, `min_samples_leaf=1`), on a
bootstrap of the training set. On MNIST (60,000 training rows, 784 pixel
columns of 0..255, 10 classes) that is a few thousand leaves a tree, the
deepest 20-30 nodes down: easy regions close early in large pure leaves,
hard ones are cut down to a handful of samples. MNIST is not on this disk
and a fit in every run's set-up would cost minutes; the scoring kernel has
no data-dependent branch and its time depends on the trees' SHAPE alone. So
the trees are DRAWN with that shape, by mass:

    the root holds the bootstrap's `samples` rows over the full bin box
    (every column's range 0 .. n_bins-1). A node of mass m is PURE, a leaf,
    when m <= its own purity mass (drawn as it is made: `purity_mass` x the
    tree's own factor x (0.25 + an exponential of mean 1), so leaves close
    at every size and trees differ in size), or when it lies `max_depth`
    nodes down. Otherwise it splits: a column uniform among all (one whose
    range in the node's box is a single bin is drawn again), a threshold
    inside that range, nearer its middle than its ends (the mean of two
    uniform draws), rows with bin <= threshold left; a child's
    mass is the node's times its share of the range: what uniform rows
    would send it.

Every leaf's box is non-empty, so every leaf is reachable by construction,
and uniform rows reach a leaf in proportion to its mass. A node keeps the
ranges of the columns its path has cut (at most one a level), not the whole
box: 784 columns x 400,000 nodes would be 600 MB. Nodes and leaves are
numbered apart, each level by level from the root (node 0); a child
reference c < 0 is leaf ~c. All trees are drawn together, a level of every
tree at a time, from ONE generator.

A leaf's value is a class distribution over `n_classes`: one class (uniform)
holds 0.80-0.99 of it and the rest is spread over the others by uniform
weights, float32. (The library's pure leaf is a one-hot; a forest's sum of
one-hots is a small integer, which any precision holds, so that a
comparison against it could not tell float32 from bfloat16. The vectors
are drawn to float32's width so that the comparison can.)
"""

from __future__ import annotations

import numpy as np

# What the configuration's "assumed" block states; the job passes them on.
DEFAULTS = dict(samples=60_000, purity_mass=24.2, tree_spread=0.075,
                max_depth=40)


def grown_forest(n_trees: int, n_features: int, n_bins: int, n_classes: int,
                 forest_seed: int, samples: int = DEFAULTS["samples"],
                 purity_mass: float = DEFAULTS["purity_mass"],
                 tree_spread: float = DEFAULTS["tree_spread"],
                 max_depth: int = DEFAULTS["max_depth"]) -> dict:
    """Node tables of `n_trees` trees grown to purity by mass: feature,
    threshold_bin, left_child, right_child int32 [T, N] (N the widest tree's
    internal nodes; unused slots feature -1), leaf_value float32 [T, L, C],
    n_leaves int32 [T], leaf_depth int32 [T, L] (nodes on a leaf's path; -1:
    no such leaf)."""
    rng = np.random.default_rng(np.random.SeedSequence([forest_seed, 11]))
    T, D = n_trees, max_depth
    factor = np.exp(tree_spread * rng.standard_normal(T))
    # the frontier, sorted by tree: a level's nodes before they are told
    # apart into leaves and internal nodes
    tree = np.arange(T)
    mass = np.full(T, float(samples))
    hung = np.full((T, 2), -1, np.int64)        # (parent's record, side)
    cut_f = np.full((T, D), -1, np.int16)       # the columns the path cut
    cut_lo = np.zeros((T, D), np.int16)
    cut_hi = np.zeros((T, D), np.int16)
    n_cut = np.zeros(T, np.int64)
    # the records of internal nodes, in the order they are made
    rec = dict(tree=[], node=[], feature=[], threshold=[])
    children = []                               # [records, 2] a level
    leaves = dict(tree=[], leaf=[], depth=[])
    n_nodes = np.zeros(T, np.int64)
    n_leaves = np.zeros(T, np.int64)
    made = 0                                    # records before this level
    flat_child = np.zeros((0, 2), np.int64)
    for depth in range(D + 1):
        if not len(tree):
            break
        purity = purity_mass * factor[tree] * (
            0.25 + rng.exponential(1.0, len(tree)))
        is_leaf = (mass <= purity) | (depth == D)
        # a column whose range here is wider than a bin, a threshold in it
        f = rng.integers(0, n_features, len(tree))
        for _ in range(8):
            at = cut_f == f[:, None]
            has, slot = at.any(axis=1), at.argmax(axis=1)
            rows = np.arange(len(tree))
            lo = np.where(has, cut_lo[rows, slot], 0)
            hi = np.where(has, cut_hi[rows, slot], n_bins - 1)
            narrow = (hi <= lo) & ~is_leaf
            if not narrow.any():
                break
            f = np.where(narrow, rng.integers(0, n_features, len(tree)), f)
        is_leaf |= hi <= lo
        middling = (rng.random(len(tree)) + rng.random(len(tree))) / 2.0
        thr = lo + np.floor(middling * (hi - lo)).astype(np.int64)
        thr = np.minimum(thr, hi - 1)
        # numbers: a tree's nodes (and leaves) of this level follow those of
        # the levels above, in the frontier's order
        first = np.searchsorted(tree, np.arange(T))

        def numbered(mask):
            rank = np.cumsum(mask) - mask
            return rank - rank[np.minimum(first, len(tree) - 1)][tree]

        node = n_nodes[tree] + numbered(~is_leaf)
        leaf = n_leaves[tree] + numbered(is_leaf)
        ref = np.where(is_leaf, ~leaf, node)
        rooted = hung[:, 0] >= 0
        flat_child[hung[rooted, 0], hung[rooted, 1]] = ref[rooted]
        leaves["tree"].append(tree[is_leaf])
        leaves["leaf"].append(leaf[is_leaf])
        leaves["depth"].append(np.full(int(is_leaf.sum()), depth))
        n_leaves += np.bincount(tree[is_leaf], minlength=T)
        inner = ~is_leaf
        n_nodes += np.bincount(tree[inner], minlength=T)
        k = int(inner.sum())
        rec["tree"].append(tree[inner])
        rec["node"].append(node[inner])
        rec["feature"].append(f[inner])
        rec["threshold"].append(thr[inner])
        flat_child = np.concatenate([flat_child, np.zeros((k, 2), np.int64)])
        # the children: left then right of every internal node
        share = (thr - lo + 1) / (hi - lo + 1)
        two = np.repeat(np.nonzero(inner)[0], 2)
        side = np.tile([0, 1], k)
        record = made + np.repeat(np.arange(k), 2)
        made += k
        new_f, new_lo, new_hi = cut_f[two], cut_lo[two], cut_hi[two]
        new_n = n_cut[two] + ~has[two]
        put = np.where(has[two], slot[two], n_cut[two])
        rows = np.arange(2 * k)
        new_f[rows, put] = f[two]
        new_lo[rows, put] = np.where(side == 0, lo[two], thr[two] + 1)
        new_hi[rows, put] = np.where(side == 0, thr[two], hi[two])
        mass = mass[two] * np.where(side == 0, share[two], 1.0 - share[two])
        tree, hung = tree[two], np.stack([record, side], axis=1)
        cut_f, cut_lo, cut_hi, n_cut = new_f, new_lo, new_hi, new_n
    rec = {k: np.concatenate(v) for k, v in rec.items()}
    leaves = {k: np.concatenate(v) for k, v in leaves.items()}
    N, L = max(1, int(n_nodes.max())), int(n_leaves.max())
    out = {
        "feature": np.full((T, N), -1, np.int32),
        "threshold_bin": np.zeros((T, N), np.int32),
        "left_child": np.zeros((T, N), np.int32),
        "right_child": np.zeros((T, N), np.int32),
        "n_leaves": n_leaves.astype(np.int32),
        "leaf_depth": np.full((T, L), -1, np.int32),
    }
    at = (rec["tree"], rec["node"])
    out["feature"][at] = rec["feature"]
    out["threshold_bin"][at] = rec["threshold"]
    out["left_child"][at] = flat_child[:, 0]
    out["right_child"][at] = flat_child[:, 1]
    out["leaf_depth"][leaves["tree"], leaves["leaf"]] = leaves["depth"]
    # a leaf's class distribution
    own = rng.integers(0, n_classes, (T, L))
    share = rng.uniform(0.80, 0.99, (T, L))
    rest = rng.random((T, L, n_classes))
    np.put_along_axis(rest, own[..., None], 0.0, axis=2)
    rest *= ((1.0 - share) / rest.sum(axis=2))[..., None]
    np.put_along_axis(rest, own[..., None], share[..., None], axis=2)
    rest[out["leaf_depth"] < 0] = 0.0
    out["leaf_value"] = rest.astype(np.float32)
    return out


BLOCK_ROWS = 1 << 18          # rows a block; fixed, it is part of the data
THREADS = 6


def uniform_pixels(rows: int, n_features: int, seed: int) -> np.ndarray:
    """Uniform random pixels, uint8 [R, F], in 0..255: `datagen.
    uniform_bins`'s blocks and threads (a block from its own child of the
    seed, so the bytes do not depend on how many threads ran), drawn as
    bytes and without the fold to fewer than 256 bins, which a byte cannot
    name."""
    from concurrent.futures import ThreadPoolExecutor

    out = np.empty((rows, n_features), np.uint8)
    flat = out.reshape(-1)
    per = BLOCK_ROWS * n_features

    def fill(i: int) -> None:
        rng = np.random.default_rng(np.random.SeedSequence([seed, 2, i]))
        dst = flat[i * per:(i + 1) * per]
        # (bytes drawn as bytes: five times as fast as 64-bit words here)
        dst[:] = rng.integers(0, 256, size=dst.size, dtype=np.uint8)

    with ThreadPoolExecutor(THREADS) as ex:
        list(ex.map(fill, range(-(-flat.size // per))))
    return out


def skeleton(tables: dict) -> dict:
    """What the forest's work is counted from (`opcount_forest.py`): its
    internal nodes, its leaves, the entries of its leaves' paths (a leaf 14
    nodes down has 14) and its deepest leaf."""
    depth = tables["leaf_depth"]
    leaves = int(tables["n_leaves"].sum())
    return {"nodes": leaves - len(tables["n_leaves"]), "leaves": leaves,
            "path_entries": int(depth[depth >= 0].sum()),
            "deepest_leaf": int(depth.max())}
