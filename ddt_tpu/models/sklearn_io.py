"""scikit-learn forests as node lists: a fitted `RandomForestClassifier` /
`RandomForestRegressor` (or `ExtraTrees*`, or one `DecisionTree*`) to a
`models/tree.NodeListEnsemble` with VECTOR LEAVES, the layout that scores
it on the device: `predict_proba` (the mean over the trees of the reached
leaf's class distribution) or the regressor's mean.

    ens = from_sklearn(forest)                      # raw thresholds
    mapper = threshold_bin_mapper(ens, n_bins=256)  # models/lightgbm_io
    proba = api.predict(ens, X, mapper=mapper, cfg=cfg)   # [rows, classes]
    labels = forest.classes_[proba.argmax(axis=1)]

Duck-typed: nothing of scikit-learn is imported; what is read is each
estimator's `tree_` (`children_left`, `children_right`, `feature`,
`threshold`, `value`), `estimators_`, `n_features_in_`, `n_outputs_` and,
of a classifier, `classes_`.

What is the same: scikit-learn sends a row LEFT where `x <= threshold`, the
repository's one split rule, and a leaf is a node whose `children_left` is
-1. Its nodes and leaves share one numbering; here the internal nodes and
the leaves are numbered apart, each in the order the library holds them, so
the root stays node 0.

Where this departs from the library, each time without changing an answer
for float32 rows (the library casts its rows to float32 itself):

- Thresholds are float64 there and float32 here. A threshold is rounded
  DOWN to the nearest float32 (never to nearest): for every float32 x,
  `x <= t` and `x <= float32_below(t)` are the same statement.
- A leaf's `value` is normalised at import (a classifier's row divided by
  its sum in float64, then float32), where `predict_proba` normalises at
  every call; the forest's mean is then taken in float32 (the device) or
  float64 (`reference/numpy_predict.predict_proba_node_list`), where the
  library sums float64: agreement to float32 rounding, 1e-7, not bitwise.
- Missing values: the library's `missing_go_to_left` is NOT read (the
  array is filled whether or not the fit saw a NaN, so it cannot be refused
  by name): rows are expected to hold no NaN, and one that does goes RIGHT
  at every node that tests it, as `NaN <= t` is false.

Refused by name: multi-output forests (`n_outputs_` > 1), an unfitted
model, a forest whose trees disagree on the class count.
"""

from __future__ import annotations

import numpy as np

from ddt_tpu.models.tree import NodeListEnsemble, node_list_from_trees


def _float32_below(t: np.ndarray) -> np.ndarray:
    """The largest float32 not above each float64 `t`."""
    t32 = t.astype(np.float32)
    above = t32.astype(np.float64) > t
    return np.where(above, np.nextafter(t32, np.float32(-np.inf)), t32)


def _tree_lists(tree, classifier: bool) -> tuple:
    """One `tree_` as `node_list_from_trees` takes it: (nodes, leaves)."""
    left = np.asarray(tree.children_left, np.int64)
    right = np.asarray(tree.children_right, np.int64)
    is_leaf = left < 0
    # internal nodes and leaves numbered apart, each in the library's order
    number = np.where(is_leaf, ~(np.cumsum(is_leaf) - 1),
                      np.cumsum(~is_leaf) - 1)
    at = np.nonzero(~is_leaf)[0]
    value = np.asarray(tree.value, np.float64)[is_leaf, 0, :]
    if classifier:
        value = value / value.sum(axis=1, keepdims=True)
    thr = _float32_below(np.asarray(tree.threshold, np.float64)[at])
    nodes = [(int(f), 0, float(t), 0.0, int(lc), int(rc))
             for f, t, lc, rc in zip(np.asarray(tree.feature)[at], thr,
                                     number[left[at]], number[right[at]])]
    return nodes, value.astype(np.float32)


def from_sklearn(model) -> NodeListEnsemble:
    """A fitted scikit-learn forest (or single tree) as a node list with
    vector leaves, `loss` "mean": C = the classes of a classifier (in
    `classes_` order), 1 of a regressor. Raw thresholds only
    (`has_bin_thresholds` False): rank them with
    `models/lightgbm_io.threshold_bin_mapper` to score binned rows."""
    estimators = getattr(model, "estimators_", None)
    if estimators is None and hasattr(model, "tree_"):
        estimators = [model]
    if not estimators:
        raise ValueError(
            "from_sklearn: not a fitted scikit-learn forest or tree (no "
            "estimators_ and no tree_)")
    if int(getattr(model, "n_outputs_", 1)) > 1:
        raise ValueError(
            f"from_sklearn: a multi-output forest (n_outputs_ "
            f"{model.n_outputs_}) is not supported: one output's class "
            "distribution (or one regression target) a leaf")
    classifier = hasattr(model, "classes_")
    trees = [_tree_lists(e.tree_, classifier) for e in estimators]
    columns = {lv.shape[1] for _, lv in trees}
    if len(columns) != 1:
        raise ValueError(
            f"from_sklearn: the trees disagree on the columns a leaf holds "
            f"({sorted(columns)})")
    C = columns.pop()
    return node_list_from_trees(
        trees, n_features=int(model.n_features_in_), learning_rate=1.0,
        base_score=0.0, loss="mean", n_classes=C if classifier else 1,
        has_raw_thresholds=True, has_bin_thresholds=False)
