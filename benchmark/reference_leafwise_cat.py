"""The plain reference of a LightGBM model with CATEGORY-SET splits beside
numerical ones: NumPy, float64, the library's model TEXT walked over RAW
values (category ids and numbers).

Imports nothing of the program and nothing of the benchmark's other
references, and reads the text with its own few lines: what the program's
importer and its bin mapper make of the same text is under test. The
semantics are LightGBM's (`Tree::CategoricalDecision`): a tree of L leaves
is L - 1 internal nodes, node 0 the root. A node whose `decision_type` has
bit 0 set is CATEGORICAL: its `threshold` is the index i of its bitset, the
uint32 words `cat_threshold[cat_boundaries[i]:cat_boundaries[i + 1]]`; a row
whose value is x goes LEFT iff x is not NaN, int(x) (toward zero) is not
negative and lies inside the bitset, and its bit is set; everything else
goes RIGHT: an id the set does not name, an id past the bitset, a negative
value, and NaN (the node's missing type is NaN, bits 2-3 of `decision_type`
= 2; with missing type None or Zero a NaN counts as id 0). A node without
bit 0 is numerical: x <= threshold goes left (NaN right: the cell's numeric
columns hold none). A child reference
c >= 0 is an internal node, c < 0 is leaf ~c, and the tree scores
leaf_value[~c], which holds the shrinkage already. Raw score = the sum over
the trees, in tree order.

`control` puts ONE thing wrong, for the runs that `correct` has to fail:
    "ordinal_test"       the set test read as `id <= threshold`, the bitset's
                         index taken for a number, at every set node
    "dropped_category"   ONE category dropped from ONE set a tree (the
                         lowest id of the tree's set node seed % its
                         set nodes)
    "unnamed_left"       an id that NO set of the model names on its column
                         (the long tail, an id past the bitsets, -1, NaN)
                         goes LEFT at every node
    "bfloat16_leaves"    leaf values rounded to bfloat16 (the nearest
                         precision below the configuration's float32)
    "threshold_bin_off"  every numerical threshold one bin (1.0) higher: the
                         ordinal side's control, the sets untouched
"""

from __future__ import annotations

import numpy as np

CONTROLS = ("ordinal_test", "dropped_category", "unnamed_left",
            "bfloat16_leaves", "threshold_bin_off")


def bfloat16(values: np.ndarray) -> np.ndarray:
    """float32 values rounded to bfloat16 (nearest even), as float32."""
    bits = np.ascontiguousarray(values, np.float32).view(np.uint32)
    return ((bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000).view(
        np.float32)


def parse(text: str) -> list:
    """The `Tree=` blocks of a model text, a dict of arrays each."""
    trees, cur = [], None
    ints = ("split_feature", "decision_type", "left_child", "right_child",
            "cat_boundaries", "cat_threshold")
    floats = ("threshold", "leaf_value")
    for line in text.splitlines():
        if line.startswith("Tree="):
            cur = {}
            trees.append(cur)
        elif cur is not None and "=" in line:
            k, _, v = line.partition("=")
            if k in ints:
                cur[k] = np.asarray([int(x) for x in v.split()], np.int64)
            elif k in floats:
                cur[k] = np.asarray([float(x) for x in v.split()],
                                    np.float64)
            elif k == "num_leaves":
                cur[k] = int(v)
        elif line.startswith("end of trees"):
            break
    for tree in trees:      # (a tree without a set node writes no bitset)
        tree.setdefault("cat_boundaries", np.asarray([0, 1], np.int64))
        tree.setdefault("cat_threshold", np.zeros(1, np.int64))
    return trees


def in_bitset(tree: dict, node: np.ndarray, ids: np.ndarray) -> np.ndarray:
    """Whether id `ids[i]` (int64, any value) is a set bit of node
    `node[i]`'s bitset, elementwise."""
    # (a numerical node's threshold is no index: read as set 0, unused)
    at = np.where(tree["decision_type"][node] & 1,
                  tree["threshold"][node], 0).astype(np.int64)
    lo, hi = tree["cat_boundaries"][at], tree["cat_boundaries"][at + 1]
    ok = (ids >= 0) & (ids < 32 * (hi - lo))
    i = np.where(ok, ids, 0)
    word = tree["cat_threshold"][np.minimum(
        lo + (i >> 5), len(tree["cat_threshold"]) - 1)]
    return ok & ((word >> (i & 31)) & 1).astype(bool)


def named_ids(trees: list, n_features: int) -> list:
    """Per column the set of raw ids that some set of the model names."""
    out = [set() for _ in range(n_features)]
    for tree in trees:
        if tree["num_leaves"] < 2:
            continue
        for n in np.flatnonzero(tree["decision_type"] & 1):
            i = int(tree["threshold"][n])
            words = tree["cat_threshold"][
                tree["cat_boundaries"][i]:tree["cat_boundaries"][i + 1]]
            out[int(tree["split_feature"][n])].update(
                32 * w + b for w, word in enumerate(words)
                for b in range(32) if int(word) >> b & 1)
    return out


def leaf_of_rows(tree: dict, X: np.ndarray, control: str | None = None,
                 unnamed: np.ndarray | None = None):
    """(leaf index, nodes on its path, set nodes on its path) of each row
    of raw float `X`, for ONE tree (the numerical nodes on a path are the
    rest). `unnamed` (control "unnamed_left"):
    bool [rows, columns], the cells whose value no set of the model names."""
    rows = np.arange(X.shape[0])
    if tree["num_leaves"] < 2:
        zero = np.zeros(len(rows), np.int64)
        return zero, zero, zero
    cur = np.zeros(len(rows), np.int64)
    depth = np.zeros(len(rows), np.int64)
    sets = np.zeros(len(rows), np.int64)
    cat = (tree["decision_type"] & 1).astype(bool)
    nan_is_zero = (tree["decision_type"] >> 2) != 2
    while True:
        inner = cur >= 0
        if not inner.any():
            return ~cur, depth, sets
        n = np.where(inner, cur, 0)
        col = tree["split_feature"][n]
        x = X[rows, col]
        nan = np.isnan(x)
        ids = np.trunc(np.where(nan, 0.0, np.clip(x, -1.0, 2.0 ** 40))
                       ).astype(np.int64)
        ids = np.where(nan & ~nan_is_zero[n], -1, ids)
        thr = tree["threshold"][n]
        if control == "threshold_bin_off":
            thr = thr + ~cat[n]             # one bin up, numerical nodes only
        below = ~nan & (x <= thr)
        if control == "ordinal_test":
            left = below
        else:
            left = np.where(cat[n], in_bitset(tree, n, ids), below)
            if control == "unnamed_left":
                left = left | (cat[n] & unnamed[rows, col])
        cur = np.where(inner, np.where(left, tree["left_child"][n],
                                       tree["right_child"][n]), cur)
        depth += inner
        sets += inner & cat[n]


def raw_scores(text: str, X: np.ndarray, visited: list | None = None,
               control: str | None = None, seed: int = 0) -> tuple:
    """(float64 raw scores [rows], facts of the walk) of the model `text`
    over raw float rows `X`. `visited` (a list, optional) gets a bool
    [leaves] a tree, set where a row reached the leaf. The facts: `deepest`
    (nodes on the longest path a row took), `set_nodes` and `ordinal_nodes`
    (set nodes and numerical nodes a row passes a tree, on average),
    `widest_set` (the most ids a set of the model names), `leaves` (the
    model's)."""
    if control is not None and control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}")
    trees = parse(text)
    out = np.zeros(X.shape[0], np.float64)
    unnamed = None
    if control == "unnamed_left":
        names = named_ids(trees, X.shape[1])
        unnamed = np.stack([
            ~np.isin(np.where(np.isnan(X[:, c]), -1.0, X[:, c]).astype(
                np.int64), sorted(names[c])) for c in range(X.shape[1])], 1)
    deepest, passed, ordinal, widest = 0, 0.0, 0.0, 0
    for t, tree in enumerate(trees):
        values = tree["leaf_value"]
        if control == "bfloat16_leaves":
            values = bfloat16(values.astype(np.float32)).astype(np.float64)
        at_sets = np.flatnonzero(tree["decision_type"] & 1) \
            if tree["num_leaves"] > 1 else ()
        if control == "dropped_category" and len(at_sets):
            tree = dict(tree, cat_threshold=tree["cat_threshold"].copy())
            i = int(tree["threshold"][at_sets[seed % len(at_sets)]])
            for w in range(tree["cat_boundaries"][i],
                           tree["cat_boundaries"][i + 1]):
                word = int(tree["cat_threshold"][w])
                if word:
                    tree["cat_threshold"][w] = word & (word - 1)
                    break
        leaf, depth, sets = leaf_of_rows(tree, X, control, unnamed)
        if visited is not None:
            seen = np.zeros(tree["num_leaves"], bool)
            seen[leaf] = True
            visited.append(seen)
        deepest = max(deepest, int(depth.max(initial=0)))
        passed += float(sets.mean()) if len(sets) else 0.0
        ordinal += float((depth - sets).mean()) if len(sets) else 0.0
        if len(at_sets):
            bits = np.unpackbits(tree["cat_threshold"].astype("<u4").view(
                np.uint8))
            widest = max(widest, int(np.add.reduceat(
                bits, 32 * tree["cat_boundaries"][:-1]).max()))
        out += values[leaf]
    return out, {"deepest": deepest, "set_nodes": passed / max(len(trees), 1),
                 "ordinal_nodes": ordinal / max(len(trees), 1),
                 "widest_set": widest,
                 "leaves": sum(t["num_leaves"] for t in trees)}
