"""LightGBM's Allstate claims model and its rows: a leaf-wise ensemble over
CATEGORICAL and NUMERIC columns, category sets and thresholds in one tree,
emitted AS THE LIBRARY'S MODEL TEXT, and raw rows by each column's law. Kept
here, not imported from the program.

The model is DRAWN, not trained (as `datagen_leafwise.py`'s: training 500
leaf-wise trees on 13M rows in every run's set-up would cost minutes, and the
kernel's time depends on the trees' shape alone), from a FIXED model seed, so
that every `--seed` scores the same model:

    a CATEGORICAL column's categories are the raw ids 0 .. k-1; a fixed
    shuffle says which id is the most frequent, the next, ... (rank r has
    weight 1 / (r + 1)^a, the column's law); the model NAMES the `named`
    most frequent ones, as LightGBM's own `max_bin` cut keeps the frequent
    categories of a long-tailed column and folds the rest. A NUMERIC column
    (cardinality 0 in the lists) takes the values 0 .. n_bins-1, uniform,
    and a threshold of it is b + 0.5, b in 0 .. n_bins-2: `n_bins` - 1
    thresholds a column, the library's `max_bin` cut.
    Start from one leaf of mass 1 whose box holds every named category of
    every categorical column and every value of every numeric one;
    n_leaves - 1 times: draw a leaf with probability proportional to its
    mass; a column uniform among those that can still split the leaf's box
    (two or more categories left, two or more values left). A categorical
    column splits by the library's rule: the box's categories in a random
    order (their gradient statistic's), a prefix of that order from either
    end of at most `max_cat_threshold` categories (at least one on either
    side), ONE category against the rest where the box has at most
    `max_cat_to_onehot` left; the left child's box keeps the set, the right
    child's the rest. A numeric column splits by a threshold uniform inside
    the box's range: values <= b + 0.5 go left. Each child's mass is the
    parent's times its share of the box's frequency.

Every leaf's box is non-empty, so every leaf is reachable by construction.
Numbering is LightGBM's (split k makes internal node k; the split leaf keeps
its index as the LEFT child and the RIGHT child is new leaf k + 1; a child
reference c < 0 is leaf ~c). All trees are drawn together, one split of every
tree at a time, from ONE generator.

The text is what the library writes for such a model: a categorical node has
`decision_type` 9 (bit 0 categorical, missing type NaN: a NaN goes right),
`threshold` the index of the node's bitset in the tree's `cat_boundaries`
and `cat_threshold` the uint32 words over RAW ids; a numeric node has
`decision_type` 2 (default left, missing type None: the column held no NaN
in training) and its threshold as a number; leaf values with the shrinkage
applied.

`--seed` draws the ROWS: a code a cell, uint16 [rows, columns] (raw ids run
past a byte). A categorical column's code is drawn by the column's law over
ALL its ids (those the model never names among them: the tail of a
long-tailed column) with three values no model names at a small share each
(NaN, -1 and an id past every bitset); a numeric column's code is its value,
uniform, never NaN. `raw_values` says what float a code stands for.
"""

from __future__ import annotations

import numpy as np

# What a code past a categorical column's k ids stands for: the values
# LightGBM's rule sends right whatever the set (module docstring).
SPECIALS = (float("nan"), -1.0, 100000.0)


def column_weights(cardinalities: list, exponents: list,
                   model_seed: int) -> list:
    """Per column the frequency of raw id i, float64 [k] summing to 1: rank
    r of a fixed shuffle of the ids has weight 1 / (r + 1)^a. A numeric
    column (cardinality 0): None."""
    rng = np.random.default_rng(np.random.SeedSequence([model_seed, 11]))
    out = []
    for k, a in zip(cardinalities, exponents):
        if not k:
            out.append(None)
            continue
        w = 1.0 / np.arange(1, k + 1, dtype=np.float64) ** a
        p = np.empty(k)
        p[rng.permutation(k)] = w / w.sum()
        out.append(p)
    return out


def named_ids(weights: list, named: list) -> list:
    """Per categorical column the raw ids the model may name, the most
    frequent first (int64 [named]); None of a numeric column."""
    return [None if w is None else np.argsort(-w, kind="stable")[:n]
            for w, n in zip(weights, named)]


def drawn_trees(n_trees: int, n_leaves: int, weights: list, named: list,
                n_bins: int, model_seed: int, max_cat_threshold: int = 32,
                max_cat_to_onehot: int = 4) -> dict:
    """The drawn ensemble (module docstring): feature int32 [T, L-1],
    left_child / right_child int32 [T, L-1], threshold_bin int32 [T, L-1]
    (a numeric node's b: values <= b + 0.5 go left), left_set bool
    [T, L-1, K] (a categorical node's set over the column's NAMED ids, in
    `named_ids`' order; K the most a column names; all False at a numeric
    node), leaf_value float32 [T, L] N(0, 1)."""
    rng = np.random.default_rng(np.random.SeedSequence([model_seed, 3]))
    T, L, F = n_trees, n_leaves, len(weights)
    cat_cols = [f for f, w in enumerate(weights) if w is not None]
    num_cols = [f for f, w in enumerate(weights) if w is None]
    Fc, Fn = len(cat_cols), len(num_cols)
    # a column's place among its kind's
    place = np.zeros(F, np.int64)
    place[cat_cols], place[num_cols] = np.arange(Fc), np.arange(Fn)
    is_cat = np.zeros(F, bool)
    is_cat[cat_cols] = True
    K = max([named[f] for f in cat_cols], default=1)
    freq = np.zeros((max(Fc, 1), K))
    start = np.zeros((max(Fc, 1), K), bool)
    ids_of = named_ids(weights, named)
    for i, f in enumerate(cat_cols):
        freq[i, :len(ids_of[f])] = weights[f][ids_of[f]]
        start[i, :len(ids_of[f])] = True
    trees = np.arange(T)
    box = np.zeros((T, L, max(Fc, 1), K), bool)
    box[:, 0] = start
    lo = np.zeros((T, L, max(Fn, 1)), np.int32)
    hi = np.full((T, L, max(Fn, 1)), n_bins - 1 if Fn else 0, np.int32)
    mass = np.zeros((T, L))
    mass[:, 0] = 1.0
    leaf_parent = np.full((T, L), -1, np.int64)
    leaf_side = np.zeros((T, L), np.int64)
    feature = np.zeros((T, L - 1), np.int32)
    threshold = np.zeros((T, L - 1), np.int32)
    left_set = np.zeros((T, L - 1, K), bool)
    child = np.zeros((T, L - 1, 2), np.int32)

    def splittable(at):
        """bool [T, F]: the columns that can still split leaf `at`'s box."""
        wide = np.zeros((T, F), bool)
        wide[:, cat_cols] = (box[trees, at].sum(axis=2) >= 2)[:, :Fc]
        wide[:, num_cols] = (hi[trees, at] > lo[trees, at])[:, :Fn]
        return wide

    for k in range(L - 1):
        cum = np.cumsum(mass[:, :k + 1], axis=1)
        leaf = np.minimum(
            (cum < (rng.random(T) * cum[:, -1])[:, None]).sum(axis=1), k)
        wide = splittable(leaf)
        if not wide.any(axis=1).all():
            raise ValueError("a drawn leaf has no column left to split")
        f = np.argmax(np.where(wide, rng.random((T, F)), -1.0), axis=1)
        by_set = is_cat[f]
        # (both kinds' draws are made for every tree, so that the generator's
        # stream does not depend on which kind a tree took)
        fc = np.where(by_set, place[f], 0)
        fn = np.where(by_set, 0, place[f])
        # --- a category set: the box's categories in a random order
        b = box[trees, leaf, fc]                             # [T, K]
        n = b.sum(axis=1)
        key = np.where(b, rng.random((T, K)), 2.0)
        rank = np.argsort(np.argsort(key, axis=1), axis=1)
        most = np.maximum(np.minimum(max_cat_threshold, n - 1), 1)
        m = np.where(n <= max_cat_to_onehot, 1,
                     1 + np.floor(rng.random(T) * most).astype(np.int64))
        m = np.minimum(m, most)
        from_end = rng.random(T) < 0.5
        left = b & np.where(from_end[:, None], rank >= (n - m)[:, None],
                            rank < m[:, None])
        w = freq[fc]
        share_set = (w * left).sum(axis=1) / np.maximum(
            (w * b).sum(axis=1), 1e-300)
        # --- a threshold: uniform inside the box's range
        f_lo, f_hi = lo[trees, leaf, fn], hi[trees, leaf, fn]
        t = f_lo + np.floor(rng.random(T) * (f_hi - f_lo)).astype(np.int32)
        t = np.maximum(np.minimum(t, f_hi - 1), f_lo)  # left lo..t, right ..hi
        share_thr = (t - f_lo + 1) / (f_hi - f_lo + 1)

        feature[:, k] = f
        threshold[:, k] = np.where(by_set, 0, t)
        left_set[:, k] = left & by_set[:, None]
        hung = leaf_parent[trees, leaf] >= 0
        child[trees[hung], leaf_parent[trees, leaf][hung],
              leaf_side[trees, leaf][hung]] = k
        new = k + 1
        child[:, k, 0], child[:, k, 1] = ~leaf, ~new
        leaf_parent[trees, leaf], leaf_side[trees, leaf] = k, 0
        leaf_parent[:, new], leaf_side[:, new] = k, 1
        # the boxes and the masses
        box[:, new] = box[trees, leaf]
        lo[:, new], hi[:, new] = lo[trees, leaf], hi[trees, leaf]
        s, o = trees[by_set], trees[~by_set]
        box[s, new, fc[s]] = (b & ~left)[s]
        box[s, leaf[s], fc[s]] = left[s]
        lo[o, new, fn[o]] = t[o] + 1
        hi[o, leaf[o], fn[o]] = t[o]
        share = np.where(by_set, share_set, share_thr)
        parent_mass = mass[trees, leaf]
        mass[trees, leaf] = parent_mass * share
        mass[:, new] = parent_mass * (1.0 - share)
        for at in (leaf, np.full(T, new)):
            dead = ~splittable(at).any(axis=1)
            mass[trees[dead], at[dead]] = 0.0
    return {
        "feature": feature, "threshold_bin": threshold, "left_set": left_set,
        "left_child": np.ascontiguousarray(child[:, :, 0]),
        "right_child": np.ascontiguousarray(child[:, :, 1]),
        "leaf_value": rng.standard_normal((T, L)).astype(np.float32),
    }


def _ints(values) -> str:
    return " ".join(str(int(v)) for v in values)


def _floats(values) -> str:
    return " ".join(f"{float(v):.17g}" for v in values)


def model_text(trees: dict, ids: list, shrinkage: float) -> str:
    """The drawn ensemble as LightGBM's model text (module docstring).
    `ids`: per column the raw ids a set's positions stand for (`named_ids`),
    None of a numeric column."""
    T, N = trees["feature"].shape
    n_features = len(ids)
    lines = ["tree", "version=v3", "num_class=1", "num_tree_per_iteration=1",
             "label_index=0", f"max_feature_idx={n_features - 1}",
             "objective=binary sigmoid:1",
             "feature_names=" + " ".join(
                 f"Column_{i}" for i in range(n_features)),
             "feature_infos=" + " ".join(["none"] * n_features), ""]
    for t in range(T):
        bounds, words, thresholds, decisions = [0], [], [], []
        for n in range(N):
            col = ids[int(trees["feature"][t, n])]
            if col is None:
                thresholds.append(f"{int(trees['threshold_bin'][t, n])}.5")
                decisions.append(2)
                continue
            raw = col[np.flatnonzero(trees["left_set"][t, n, :len(col)])]
            run = np.zeros(int(raw.max()) // 32 + 1, np.uint64)
            np.bitwise_or.at(run, raw >> 5, np.uint64(1) << (
                raw & 31).astype(np.uint64))
            thresholds.append(str(len(bounds) - 1))
            decisions.append(9)
            words += [int(w) for w in run]
            bounds.append(len(words))
        values = trees["leaf_value"][t].astype(np.float64) * shrinkage
        lines += [
            f"Tree={t}", f"num_leaves={N + 1}", f"num_cat={len(bounds) - 1}",
            "split_feature=" + _ints(trees["feature"][t]),
            "split_gain=" + _floats(np.ones(N)),
            "threshold=" + " ".join(thresholds),
            "decision_type=" + _ints(decisions),
            "left_child=" + _ints(trees["left_child"][t]),
            "right_child=" + _ints(trees["right_child"][t]),
            "leaf_value=" + _floats(values),
            "leaf_weight=" + _floats(np.zeros(N + 1)),
            "leaf_count=" + _ints(np.zeros(N + 1)),
            "internal_value=" + _floats(np.zeros(N)),
            "internal_weight=" + _floats(np.zeros(N)),
            "internal_count=" + _ints(np.zeros(N))]
        if len(bounds) > 1:
            lines += ["cat_boundaries=" + _ints(bounds),
                      "cat_threshold=" + _ints(words)]
        lines += ["is_linear=0", f"shrinkage={shrinkage:.17g}", ""]
    return "\n".join(lines + ["end of trees", "", "pandas_categorical:null",
                              ""])


def drawn_model(shapes: dict, assumed: dict, shrinkage: float) -> str:
    """The configuration's model as the library's text: `shapes` (n_trees,
    n_leaves, n_bins, model_seed, max_cat_threshold, max_cat_to_onehot) and
    `assumed` (cardinalities, exponents, named: a list entry a column, 0 of
    a numeric one)."""
    weights = column_weights(assumed["cardinalities"], assumed["exponents"],
                             shapes["model_seed"])
    trees = drawn_trees(shapes["n_trees"], shapes["n_leaves"], weights,
                        assumed["named"], shapes["n_bins"],
                        shapes["model_seed"], shapes["max_cat_threshold"],
                        shapes["max_cat_to_onehot"])
    return model_text(trees, named_ids(weights, assumed["named"]), shrinkage)


def raw_values(cardinality: int, n_bins: int) -> np.ndarray:
    """float32: the raw value a code stands for. A categorical column of k
    ids [k + 3]: the id itself, then `SPECIALS`; a numeric column
    (cardinality 0) [n_bins]: the value itself."""
    if not cardinality:
        return np.arange(n_bins, dtype=np.float32)
    return np.concatenate([np.arange(cardinality, dtype=np.float32),
                           np.asarray(SPECIALS, np.float32)])


def drawn_codes(rows: int, weights: list, n_bins: int, special_share: float,
                seed: int) -> np.ndarray:
    """uint16 [rows, columns]: a code a cell (`raw_values`). A categorical
    column by its law with `special_share` of each of the three special
    values: a 16-bit uniform draw through the inverse of the law's
    cumulative sum (a table of 65,536 entries: a law's share is met to
    1/65,536); a numeric column uniform over its `n_bins` values."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 5]))
    out = np.empty((rows, len(weights)), np.uint16)
    for c, w in enumerate(weights):
        if w is None:
            out[:, c] = rng.integers(0, n_bins, rows, dtype=np.uint16)
            continue
        p = np.concatenate([w * (1.0 - len(SPECIALS) * special_share),
                            [special_share] * len(SPECIALS)])
        table = np.minimum(np.searchsorted(
            np.cumsum(p), (np.arange(65536) + 0.5) / 65536),
            len(p) - 1).astype(np.uint16)
        out[:, c] = table[rng.integers(0, 65536, rows, dtype=np.uint16)]
    return out
