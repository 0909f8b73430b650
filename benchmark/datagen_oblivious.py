"""The model of the Epsilon scoring cell from `--seed`: oblivious (symmetric)
trees as CatBoost holds them, DRAWN, not trained. The same seed gives the
same model. Kept here, not imported from the program. The rows are
`datagen.uniform_bins`: what quantile borders make of a dense continuous
column.

A tree of depth D is D splits, each a (feature, border rank) pair, and 2^D
leaf values. A tree's pairs are distinct (a repeated pair is drawn again:
the same question twice would leave half the tree's leaves unreachable);
the feature is uniform over the columns and the border uniform over the
ranks 0 .. n_bins-2 (254 borders a column under the library's
`border_count=254`), so a split's bit is set for between 1/255 and 254/255
of uniform rows and for half of them on average. Two splits of a tree may
fall on ONE feature (15 in 2000 trees at depth 6): the leaves that ask for
`bin > a` and `bin <= b` with b < a are then dead, as in a trained model.
Leaf values are N(0, sigma): the kernel has no data-dependent branch, and
sigma sets |score| alone.
"""

from __future__ import annotations

import numpy as np


def oblivious_trees(n_trees: int, depth: int, n_features: int, n_bins: int,
                    seed: int, leaf_sigma: float) -> dict:
    """split_feature, split_bin int32 [T, D] (split d is bit d of the leaf
    index, set where bin > split_bin) and leaf_value float32 [T, 2^D]."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 3]))
    feature = rng.integers(0, n_features, (n_trees, depth), dtype=np.int32)
    border = rng.integers(0, n_bins - 1, (n_trees, depth), dtype=np.int32)
    while True:
        pair = np.sort(feature.astype(np.int64) * n_bins + border, axis=1)
        again = (pair[:, 1:] == pair[:, :-1]).any(axis=1)
        if not again.any():
            break
        n = int(again.sum())
        feature[again] = rng.integers(0, n_features, (n, depth))
        border[again] = rng.integers(0, n_bins - 1, (n, depth))
    leaf = rng.standard_normal((n_trees, 1 << depth)) * leaf_sigma
    return {"split_feature": feature, "split_bin": border,
            "leaf_value": leaf.astype(np.float32)}
