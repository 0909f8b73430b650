"""The plain reference of an XGBoost multiclass model's scoring, NumPy,
float64, from the library's own JSON dict (`Booster.save_model("m.json")`,
read by `json.load`; `datagen_xgb.drawn_model` emits the same).

Imports nothing of the program and nothing of the benchmark's other
references. The semantics (the library's `Booster.predict` of a `gbtree`
model under `multi:softprob`): tree i is the arrays `left_children`,
`right_children` (-1: the node is a leaf), `split_indices`,
`split_conditions` and `default_left` of `trees[i]`, its root node 0. At an
internal node n a row whose value x = X[row, split_indices[n]] is NaN goes
LEFT where `default_left[n]` and else RIGHT; any other row goes LEFT where
x < split_conditions[n], STRICTLY, and else RIGHT. A leaf n answers
`split_conditions[n]` (`eta` is in it) into class `tree_info[i]`. The
margins [rows, classes] are `base_score` + each class's sum over its trees,
the answer their softmax. Values and conditions are float32 (the library
casts both), compared as they are and summed in float64. The walk is of the
tree as the library holds it: it knows nothing of node lists, sub-trees,
bins, lanes or `nextafter`.

`patched` gives the model with ONE thing wrong, for the runs that `correct`
has to fail (the program is handed the patched model, or the reference of
the patched model stands in for the program, and either is held to the
reference of the right one):
    "not_strict"            x <= condition goes left, at every node (every
                            condition moved to the next float32 above it)
    "column_major_classes"  tree i scored into class i // rounds: the trees
                            of a class taken to lie in a row
    "bfloat16_leaves"       every leaf value rounded to bfloat16 (the
                            nearest precision below the configuration's
                            float32)
    "no_link"               the margins answered, not their softmax
    "dropped_chain"         leaves more than 8 nodes down answer nothing:
                            what a chain of sub-trees that loses its links
                            gives
    "eta_twice"             every leaf value multiplied by `eta` once more
"""

from __future__ import annotations

import numpy as np

CONTROLS = ("not_strict", "column_major_classes", "bfloat16_leaves",
            "no_link", "dropped_chain", "eta_twice")
CHAIN_KEPT_NODES = 8


def booster(model: dict) -> dict:
    return model["learner"]["gradient_booster"]["model"]


def n_classes(model: dict) -> int:
    return max(1, int(model["learner"]["learner_model_param"]["num_class"]))


def bfloat16(values: np.ndarray) -> np.ndarray:
    """float32 values rounded to bfloat16 (nearest even), as float32."""
    bits = np.ascontiguousarray(values, np.float32).view(np.uint32)
    return ((bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000).view(
        np.float32)


def node_depths(left: np.ndarray, right: np.ndarray) -> np.ndarray:
    """Nodes above each node of one tree (the root 0), a level a step."""
    depth = np.zeros(len(left), np.int64)
    level, d = np.zeros(1, np.int64), 0
    while len(level):
        depth[level] = d
        inner = level[left[level] >= 0]
        level, d = np.concatenate([left[inner], right[inner]]), d + 1
    return depth


def patched(model: dict, control: str | None, eta: float = 0.3) -> dict:
    """The model with the control's ONE thing wrong (`None`, and "no_link",
    whose wrong thing is the CALL's: as it is)."""
    if control is None or control == "no_link":
        return model
    if control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}")
    held = booster(model)
    trees = held["trees"]
    if control == "column_major_classes":
        C = n_classes(model)
        rounds = len(trees) // C
        trees = [trees[(i % C) * rounds + i // C] for i in range(len(trees))]
    else:
        out = []
        for tree in trees:
            left = np.asarray(tree["left_children"], np.int64)
            cond = np.asarray(tree["split_conditions"], np.float32)
            leaf = left < 0
            if control == "not_strict":
                cond = np.where(leaf, cond, np.nextafter(
                    cond, np.float32(np.inf)))
            elif control == "bfloat16_leaves":
                cond = np.where(leaf, bfloat16(cond), cond)
            elif control == "eta_twice":
                cond = np.where(leaf, cond * np.float32(eta), cond)
            else:
                deep = node_depths(left, np.asarray(
                    tree["right_children"], np.int64)) > CHAIN_KEPT_NODES
                cond = np.where(leaf & deep, np.float32(0), cond)
            out.append({**tree, "split_conditions":
                        cond.astype(np.float64).tolist()})
        trees = out
    learner = model["learner"]
    return {**model, "learner": {**learner, "gradient_booster": {
        **learner["gradient_booster"], "model": {**held, "trees": trees}}}}


def margins(model: dict, X: np.ndarray, visited: list | None = None):
    """(float64 margins [rows, classes], the deepest path any row took, the
    nodes a row passed in a tree on average) over raw float rows `X`.
    `visited` (a list, optional) takes one bool array a tree: the nodes of
    the library's numbering that a row ended in."""
    held = booster(model)
    X = np.asarray(X, np.float32)
    base = float(model["learner"]["learner_model_param"]["base_score"])
    out = np.full((X.shape[0], n_classes(model)), base, np.float64)
    deepest, passed = 0, 0
    for tree, cls in zip(held["trees"], held["tree_info"]):
        left = np.asarray(tree["left_children"], np.int64)
        right = np.asarray(tree["right_children"], np.int64)
        column = np.asarray(tree["split_indices"], np.int64)
        cond = np.asarray(tree["split_conditions"], np.float32)
        nan_left = np.asarray(tree["default_left"], bool)
        end = np.zeros(X.shape[0], np.int64)        # the leaf a row ends in
        rows = np.arange(X.shape[0])
        n = np.zeros(X.shape[0], np.int64)
        depth = 0
        while True:
            on = left[n] >= 0
            end[rows[~on]] = n[~on]
            rows, n = rows[on], n[on]
            if not len(rows):
                break
            depth += 1
            passed += len(rows)
            x = X[rows, column[n]]
            go_left = np.where(np.isnan(x), nan_left[n], x < cond[n])
            n = np.where(go_left, left[n], right[n])
        deepest = max(deepest, depth)
        if visited is not None:
            seen = np.zeros(len(left), bool)
            seen[end] = True
            visited.append(seen)
        out[:, int(cls)] += cond[end].astype(np.float64)
    return out, deepest, passed / max(1, len(held["trees"]) * X.shape[0])


def softmax(m: np.ndarray) -> np.ndarray:
    e = np.exp(m - m.max(axis=1, keepdims=True))
    return e / e.sum(axis=1, keepdims=True)


def answer(model: dict, X: np.ndarray, control: str | None = None,
           eta: float = 0.3) -> np.ndarray:
    """What a program with the control's one thing wrong answers, float64
    [rows, classes]: class probabilities ("no_link": the margins)."""
    m = margins(patched(model, control, eta), X)[0]
    return m if control == "no_link" else softmax(m)
