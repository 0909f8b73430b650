"""The model of the Covertype CatBoost MultiClass scoring cell from `--seed`:
oblivious (symmetric) trees of VECTOR leaves as the library EXPORTS them, a
dict in the layout of `model.save_model(path, format="json")`, DRAWN, not
trained. The same seed gives the same model. Kept here, not imported from
the program: the program's importer (`models/catboost_io.from_catboost_json`)
is under test with the kernel, and `reference_oblivious_mc.py` walks this
same dict by itself. The rows are `datagen.uniform_bins`: what quantile
borders make of a continuous column.

The layout (REMEMBERED from the library's exporter; no network here):

    oblivious_trees[t].splits[d]    {"split_type": "FloatFeature",
                                     "float_feature_index": i, "border": b}
                                    d = 0 is the LOW bit of the leaf index
    oblivious_trees[t].leaf_values  2^D x C numbers, LEAF-major, the class
                                    innermost: leaf i, class c at i C + c
    oblivious_trees[t].leaf_weights 2^D numbers (not read by a scorer)
    features_info.float_features[i] {"feature_index", "flat_feature_index",
                                     "borders": ascending, "has_nans",
                                     "nan_value_treatment"}
    scale_and_bias                  [scale, [bias_0 .. bias_{C-1}]]
    model_info.params.loss_function {"type": "MultiClass"}

A column's borders are k + 0.5 for the ranks k = 0 .. n_bins-2 (254 under the
library's `border_count=254`), so a row whose VALUE is its bin b (what the
cell scores: `binned=True`) has b borders below it, and `value > border_k`
is `bin > k`: the walk of the dict over the bins as values is the scoring
of the binned rows. A tree's D (feature, border) pairs are distinct (a
repeated pair is drawn again); the feature is uniform over the columns and
the border uniform over the ranks, so a split's bit is set for half of
uniform rows on average. Two splits of a tree may fall on ONE column (about
240 of 1000 trees at depth 6 over 54 columns): the leaves behind `bin > a`
and `bin <= b`, b < a, are dead, as in a trained model. Leaf vectors are
N(0, sigma) and the bias vector N(0, bias_sigma), float32 values: the
kernel has no data-dependent branch, and the sigmas set the margins' size
alone.
"""

from __future__ import annotations

import numpy as np


def border_of(rank):
    """The border of rank k in every column's list: k + 0.5."""
    return rank + 0.5


def drawn_model(n_trees: int, depth: int, n_features: int, n_bins: int,
                n_classes: int, seed: int, leaf_sigma: float,
                bias_sigma: float, scale: float = 1.0) -> dict:
    """The export's dict (module docstring) of `n_trees` trees of `depth`
    splits and 2^depth x `n_classes` leaf values."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 3]))
    feature = rng.integers(0, n_features, (n_trees, depth), dtype=np.int32)
    rank = rng.integers(0, n_bins - 1, (n_trees, depth), dtype=np.int32)
    while True:
        pair = np.sort(feature.astype(np.int64) * n_bins + rank, axis=1)
        again = (pair[:, 1:] == pair[:, :-1]).any(axis=1)
        if not again.any():
            break
        n = int(again.sum())
        feature[again] = rng.integers(0, n_features, (n, depth))
        rank[again] = rng.integers(0, n_bins - 1, (n, depth))
    leaves = (rng.standard_normal((n_trees, 1 << depth, n_classes))
              * leaf_sigma).astype(np.float32)
    bias = (rng.standard_normal(n_classes) * bias_sigma).astype(np.float32)
    borders = [float(border_of(k)) for k in range(n_bins - 1)]
    return {
        "oblivious_trees": [{
            "splits": [{"split_type": "FloatFeature",
                        "float_feature_index": int(feature[t, d]),
                        "border": float(border_of(rank[t, d])),
                        "split_index": int(feature[t, d]) * (n_bins - 1)
                        + int(rank[t, d])}
                       for d in range(depth)],
            # leaf-major, the class innermost
            "leaf_values": leaves[t].reshape(-1).astype(np.float64).tolist(),
            "leaf_weights": [1.0] * (1 << depth),
        } for t in range(n_trees)],
        "features_info": {"float_features": [{
            "feature_index": i, "flat_feature_index": i, "borders": borders,
            "has_nans": False, "nan_value_treatment": "AsIs",
        } for i in range(n_features)]},
        "scale_and_bias": [float(scale), bias.astype(np.float64).tolist()],
        "model_info": {"params": {"loss_function": {"type": "MultiClass"}}},
    }
