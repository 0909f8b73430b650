"""XGBoost's deep multiclass model on Covertype, DRAWN: `multi:softprob`
trees of depth at most 16 emitted AS THE LIBRARY'S JSON DICT (what
`Booster.save_model("m.json")` writes and `json.load` reads), from a FIXED
model seed: every `--seed` scores the same model (the program imports it and
compiles the same tables in every run) and draws its own rows. Kept here,
not imported from the program.

The source (GPUTreeShap's `covtype-large`): 1,000 rounds x 7 classes of
`max_depth` 16 on Covertype (581,012 rows; 10 continuous columns, 44 binary
ones; no missing value), `eta` 0.3, `max_bin` 256, `min_child_weight` 1,
some 6.6 million leaves in 7,000 trees. Covertype is not on this disk and a
fit would take hours; the scoring kernel has no data-dependent branch and
its time depends on the trees' SHAPE alone. So the trees are drawn with
that shape, DEPTH-WISE BY HESSIAN MASS, the quantity the library stops on:

    tree i is round i // 7's tree of class i % 7 (`tree_info`: round-major).
    Its root holds the hessian mass of the set for that class and round:
    rows x (first_round x exp(-round / fade) + plateau x share(class) ^
    skew): in round 0 every row of every class weighs 2 p (1 - p) at p =
    1/7, so all seven trees are large; later only the rows near a class's
    boundary weigh anything, and a rare class (Covertype's smallest is
    0.5% of the rows) has few of them: its trees are tens of leaves.
    A node of mass m at depth d is a LEAF when d = 16 (`max_depth`) or when
    m < 2 x `min_child_weight` x (1 + an exponential of mean 1): no split
    leaves both children their weight. Otherwise it splits: a continuous
    column with probability `continuous_share`, else a binary one (a column
    whose range in the node's box is one bin is drawn again), a threshold
    on one of the column's cuts inside the node's range (a continuous
    column's 255 cuts, nearer the middle of the range than its ends; a
    binary column's one); a child's mass is the node's times its share of
    the range times a PURITY factor u ^ `purity` (u uniform): a child is
    purer than its parent, so its rows weigh less.

Nodes are numbered as the library numbers them, level by level in the order
they are made (the root 0), leaves and internal nodes in ONE numbering;
`left_children[n]` -1 marks a leaf, whose value lies in
`split_conditions[n]`: `eta` x a normal draw, float32. An internal node's
`split_conditions[n]` is the cut's value, and a row goes LEFT where x < it
(the library's STRICT test). A continuous column f takes the values b x
unit(f), b = 0..255, and its cut k is (k + 1) x unit(f): a row's value sits
ON a cut whenever it is the bin's smallest. A binary column's cut is 1.0.
Every node's box is non-empty: every leaf is reachable by construction.
"""

from __future__ import annotations

import numpy as np

CLASSES = 7
CONTINUOUS = 10                 # columns 0..9; 10..53 are binary
# Covertype's class shares (UCI: 211,840 / 283,301 / 35,754 / 2,747 /
# 9,493 / 17,367 / 20,510 of 581,012 rows)
CLASS_SHARE = (0.3646, 0.4876, 0.0615, 0.0047, 0.0163, 0.0299, 0.0353)
# What the configuration's "assumed" block states; the job passes them on.
DEFAULTS = dict(rows=581_012, max_depth=16, min_child_weight=1.0, eta=0.3,
                first_round=0.245, fade=12.0, plateau=0.105, skew=1.0,
                purity=0.15, continuous_share=0.75)


def units(n_features: int) -> np.ndarray:
    """float32 [F]: the step between a continuous column's values (metres,
    degrees, a hillshade index: powers of two and not), 1 of a binary one."""
    u = np.ones(n_features, np.float32)
    u[:CONTINUOUS] = np.asarray(
        [7.5, 1.5, 0.25, 5.5, 2.5, 28.0, 1.0, 1.0, 1.0, 28.125],
        np.float32)[:min(CONTINUOUS, n_features)]
    return u


def column_bins(n_features: int) -> np.ndarray:
    """int64 [F]: the bins of each column, 256 or 2."""
    return np.where(np.arange(n_features) < CONTINUOUS, 256, 2)


def drawn_model(rounds: int, n_features: int, model_seed: int,
                base_score: float = 0.5, **drawing) -> dict:
    """The library's JSON dict of `rounds` x 7 trees (module docstring)."""
    p = {**DEFAULTS, **drawing}
    rng = np.random.default_rng(np.random.SeedSequence([model_seed, 13]))
    T, D = rounds * CLASSES, int(p["max_depth"])
    bins = column_bins(n_features)
    share = np.asarray(CLASS_SHARE)[np.arange(T) % CLASSES]
    root_mass = p["rows"] * (
        p["first_round"] * np.exp(-(np.arange(T) // CLASSES) / p["fade"])
        + p["plateau"] * share ** p["skew"])
    # the frontier, sorted by tree: a level's nodes before they are told
    # apart into leaves and internal nodes
    tree = np.arange(T)
    mass = root_mass
    hung = np.full((T, 2), -1, np.int64)        # (parent's record, side)
    cut_f = np.full((T, D), -1, np.int16)       # the columns the path cut
    cut_lo = np.zeros((T, D), np.int16)
    cut_hi = np.zeros((T, D), np.int16)
    n_cut = np.zeros(T, np.int64)
    rec = dict(tree=[], node=[], feature=[], cut=[], leaf=[])
    n_made = np.zeros(T, np.int64)              # a tree's nodes so far
    made = 0                                    # records before this level
    child = np.zeros((0, 2), np.int64)          # [records, 2]: the ids
    for depth in range(D + 1):
        if not len(tree):
            break
        k = len(tree)
        stop = 2.0 * p["min_child_weight"] * (1.0 + rng.exponential(1.0, k))
        is_leaf = (mass < stop) | (depth == D)
        rows = np.arange(k)

        def column():
            cont = rng.random(k) < p["continuous_share"]
            return np.where(cont, rng.integers(0, CONTINUOUS, k),
                            rng.integers(CONTINUOUS, n_features, k))

        f = column()
        for _ in range(12):
            at = cut_f == f[:, None]
            has, slot = at.any(axis=1), at.argmax(axis=1)
            lo = np.where(has, cut_lo[rows, slot], 0)
            hi = np.where(has, cut_hi[rows, slot], bins[f] - 1)
            narrow = (hi <= lo) & ~is_leaf
            if not narrow.any():
                break
            f = np.where(narrow, column(), f)
        is_leaf |= hi <= lo
        middling = (rng.random(k) + rng.random(k)) / 2.0
        thr = np.minimum(lo + np.floor(middling * (hi - lo)).astype(np.int64),
                         hi - 1)                # left: bins lo..thr
        # the library's ids: a tree's nodes of this level follow those of
        # the levels above, in the frontier's order
        first = np.searchsorted(tree, np.arange(T))
        rank = np.arange(k) - first[tree]
        node = n_made[tree] + rank
        n_made += np.bincount(tree, minlength=T)
        rooted = hung[:, 0] >= 0
        child[hung[rooted, 0], hung[rooted, 1]] = node[rooted]
        rec["tree"].append(tree)
        rec["node"].append(node)
        rec["feature"].append(np.where(is_leaf, 0, f))
        rec["cut"].append(np.where(is_leaf, 0, thr))
        rec["leaf"].append(is_leaf)
        child = np.concatenate([child, np.full((k, 2), -1, np.int64)])
        inner = np.nonzero(~is_leaf)[0]
        two = np.repeat(inner, 2)
        side = np.tile([0, 1], len(inner))
        record = made + two
        made += k
        part = (thr - lo + 1) / (hi - lo + 1)
        new_f, new_lo, new_hi = cut_f[two], cut_lo[two], cut_hi[two]
        new_n = n_cut[two] + ~has[two]
        put = np.where(has[two], slot[two], n_cut[two])
        r2 = np.arange(len(two))
        new_f[r2, put] = f[two]
        new_lo[r2, put] = np.where(side == 0, lo[two], thr[two] + 1)
        new_hi[r2, put] = np.where(side == 0, thr[two], hi[two])
        mass = (mass[two] * np.where(side == 0, part[two], 1.0 - part[two])
                * rng.random(len(two)) ** p["purity"])
        tree, hung = tree[two], np.stack([record, side], axis=1)
        cut_f, cut_lo, cut_hi, n_cut = new_f, new_lo, new_hi, new_n
    rec = {k: np.concatenate(v) for k, v in rec.items()}
    order = np.lexsort((rec["node"], rec["tree"]))
    rec = {k: v[order] for k, v in rec.items()}
    child = child[order]
    leaf = rec["leaf"]
    value = (p["eta"] * 0.5 * rng.standard_normal(len(leaf))).astype(
        np.float32)
    cond = np.where(
        leaf, value,
        (rec["cut"] + 1).astype(np.float32) * units(n_features)[
            rec["feature"]]).astype(np.float32)
    nan_left = np.where(leaf, 0, rng.integers(0, 2, len(leaf)))
    bounds = np.concatenate([[0], np.cumsum(np.bincount(rec["tree"],
                                                        minlength=T))])
    trees = []
    for t in range(T):
        s = slice(bounds[t], bounds[t + 1])
        n = bounds[t + 1] - bounds[t]
        trees.append({
            "id": t, "left_children": child[s, 0].tolist(),
            "right_children": child[s, 1].tolist(),
            "split_indices": rec["feature"][s].tolist(),
            "split_conditions": cond[s].astype(np.float64).tolist(),
            "default_left": nan_left[s].tolist(),
            "split_type": [0] * n,
            "tree_param": {"num_nodes": str(n), "num_feature":
                           str(n_features), "size_leaf_vector": "1"}})
    return {"learner": {
        "learner_model_param": {"base_score": f"{base_score:E}",
                                "num_class": str(CLASSES),
                                "num_feature": str(n_features),
                                "num_target": "1"},
        "objective": {"name": "multi:softprob",
                      "softmax_multiclass_param": {"num_class":
                                                   str(CLASSES)}},
        "gradient_booster": {"name": "gbtree", "model": {
            "gbtree_model_param": {"num_parallel_tree": "1",
                                   "num_trees": str(T)},
            "tree_info": (np.arange(T) % CLASSES).tolist(),
            "trees": trees}}},
        "version": [2, 0, 3]}


def skeleton(model: dict) -> dict:
    """What the model's work is counted from (`opcount_xgb.py`): its
    internal nodes, its leaves, the entries of its leaves' paths (a leaf 14
    nodes down has 14), its deepest leaf, and its trees' leaf counts'
    smallest, mean and largest."""
    nodes = leaves = entries = deepest = 0
    sizes = []
    for tree in model["learner"]["gradient_booster"]["model"]["trees"]:
        left = np.asarray(tree["left_children"], np.int64)
        right = np.asarray(tree["right_children"], np.int64)
        depth = np.zeros(len(left), np.int64)
        level, d = np.zeros(1, np.int64), 0
        while len(level):                       # a level of the tree a step
            depth[level] = d
            inner = level[left[level] >= 0]
            level, d = np.concatenate([left[inner], right[inner]]), d + 1
        is_leaf = left < 0
        nodes += int((~is_leaf).sum())
        leaves += int(is_leaf.sum())
        entries += int(depth[is_leaf].sum())
        deepest = max(deepest, int(depth.max(initial=0)))
        sizes.append(int(is_leaf.sum()))
    return {"nodes": nodes, "leaves": leaves, "path_entries": entries,
            "deepest_leaf": deepest, "trees": len(sizes),
            "leaves_a_tree": [min(sizes), round(leaves / len(sizes), 1),
                              max(sizes)]}


def rows_and_bins(rows: int, n_features: int, seed: int,
                  on_cut: float = 0.5) -> tuple:
    """(raw float32 [R, F], the bins they were drawn in, uint8 [R, F]):
    every column uniform over its bins, 0..255 or 0..1, from `--seed`; a
    continuous value sits ON its bin's cut (b x unit, the bin's smallest
    value: the row the STRICT test is about) with probability `on_cut`,
    else half a unit inside; a binary one is 0.0 or 1.0."""
    import datagen_forest

    b = datagen_forest.uniform_pixels(rows, n_features, seed)
    b[:, CONTINUOUS:] &= 1
    rng = np.random.default_rng(np.random.SeedSequence([seed, 6]))
    inside = rng.random((rows, min(CONTINUOUS, n_features)),
                        dtype=np.float32) >= on_cut
    X = b.astype(np.float32)
    X[:, :CONTINUOUS] += 0.5 * inside
    return X * units(n_features), b
