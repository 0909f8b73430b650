"""Config-3 at-scale witness: 1M-row categorical training over 4 virtual
partitions (round-4 verdict item 7).

The distributed-categorical BASELINE config (Criteo-like, 4 partitions)
had e2e miniatures and toy-size partition identity tests but no
at-capacity witness the way config-5 got its 20M-row run. This script
trains the Criteo shape — 13 numeric + 26 high-cardinality (Zipf,
100k-card) categorical columns, frequency-encoded, one-vs-rest splits —
at >= 1M rows on a 4-device virtual CPU mesh, asserts BIT-IDENTITY of
the grown trees against the single-device run, and records wallclock +
peak RSS for docs/PERF.md.

Run OFF the chip (pure CPU; the virtual mesh is the point):
    python experiments/config3_scale.py [rows] [trees]
"""

from __future__ import annotations

import json
import os
import resource
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4").strip()

import jax                                          # noqa: E402

# The config call pins the platform whatever the environment says (must
# precede first device use).
jax.config.update("jax_platforms", "cpu")

from ddt_tpu.backends import get_backend            # noqa: E402
from ddt_tpu.config import TrainConfig              # noqa: E402
from ddt_tpu.data.categorical import fit_categorical_encoder  # noqa: E402
from ddt_tpu.data.datasets import synthetic_ctr     # noqa: E402
from ddt_tpu.data.quantizer import fit_bin_mapper   # noqa: E402
from ddt_tpu.driver import Driver                   # noqa: E402


def main():
    rows = int(sys.argv[1]) if len(sys.argv) > 1 else 1_000_000
    trees = int(sys.argv[2]) if len(sys.argv) > 2 else 10
    bins = 63
    t0 = time.perf_counter()
    Xn, Xc, y = synthetic_ctr(rows, seed=5)
    enc = fit_categorical_encoder(Xc, n_bins=bins)
    X = np.concatenate([Xn, enc.transform(Xc).astype(np.float32)], axis=1)
    cat = tuple(range(Xn.shape[1], X.shape[1]))
    m = fit_bin_mapper(X, n_bins=bins, cat_features=cat)
    Xb = m.transform(X)
    prep_s = time.perf_counter() - t0
    print(f"# prepared {rows} x {X.shape[1]} (26 cat cols, card<=100k "
          f"-> {bins}-bin frequency encoding) in {prep_s:.1f}s",
          flush=True)

    results = {}
    ens = {}
    for parts in (1, 4):
        # min_split_gain carries the documented noise floor (ops/split.py
        # "Determinism boundary"): a signal-free node's best gain is
        # ~1e-8 f32 cancellation noise whose ORDER-dependent sign flips
        # between the single matmul and the 4-shard psum; at 0.0 the
        # split/no-split decision sits on that razor edge and ~1% of
        # deep nodes legitimately diverge (observed at 1M rows before
        # this floor was set — the same rule every identity fuzz uses).
        cfg = TrainConfig(n_trees=trees, max_depth=6, n_bins=bins,
                          backend="tpu", n_partitions=parts,
                          min_split_gain=1e-3,
                          cat_features=cat)
        be = get_backend(cfg)
        t0 = time.perf_counter()
        ens[parts] = Driver(be, cfg, log_every=5).fit(Xb, y)
        dt = time.perf_counter() - t0
        results[parts] = dt
        print(f"# n_partitions={parts}: {dt:.1f}s "
              f"({rows * trees / dt / 1e6:.2f} Mrow-trees/s)", flush=True)

    # Identity contract at this scale (measured, docs/PERF.md round-5):
    # the 4-shard psum's f32 summation order differs from the single
    # matmul's, so bf16-boundary candidate ties can flip — the same seam
    # as chunked accumulation (ops/split.py "Determinism boundary"),
    # whose incidence grows with row count. The checkable claim:
    #   (a) every tree BEFORE the first divergence is bitwise identical;
    #   (b) the first divergent tree's root causes are PROVABLE ties
    #       (tie comparator, per-tree, leaf tolerance widened for
    #       1M-row f32 leaf-sum drift);
    #   (c) later trees legitimately cascade (they train on the
    #       residuals the tied choice changed) — quality equivalence is
    #       asserted instead (holdout AUC delta).
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tests"))
    from tree_compare import assert_prefix_identity_mod_ties

    prefix_n, first_div = assert_prefix_identity_mod_ties(
        ens[1], ens[4], 1e-3)
    agreement = float((ens[1].feature == ens[4].feature).mean())

    hold_n, hold_seed = 200_000, 77
    Xn_h, Xc_h, y_h = synthetic_ctr(hold_n, seed=hold_seed)
    Xh = np.concatenate(
        [Xn_h, enc.transform(Xc_h).astype(np.float32)], axis=1)
    Xhb = m.transform(Xh)
    from ddt_tpu.utils.metrics import auc
    auc1 = auc(y_h, ens[1].predict_raw(Xhb, binned=True))
    auc4 = auc(y_h, ens[4].predict_raw(Xhb, binned=True))
    assert abs(auc1 - auc4) < 1e-3, (auc1, auc4)

    n_cat_splits = int(np.isin(ens[4].feature[~ens[4].is_leaf],
                               list(cat)).sum())
    assert n_cat_splits > 0, "no categorical splits grew; data too easy"

    peak_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024
    print(json.dumps({
        "rows": rows, "trees": trees, "bins": bins,
        "features": X.shape[1], "cat_features": len(cat),
        "wallclock_1part_s": round(results[1], 1),
        "wallclock_4part_s": round(results[4], 1),
        "bitwise_prefix_trees": (first_div if first_div is not None
                                 else trees),
        "first_divergent_tree": first_div,
        "split_agreement": round(agreement, 4),
        "holdout_auc_1part": round(auc1, 5),
        "holdout_auc_4part": round(auc4, 5),
        "n_cat_splits": n_cat_splits,
        "peak_rss_mb": round(peak_mb, 1),
    }), flush=True)


if __name__ == "__main__":
    main()
