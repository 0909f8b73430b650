"""Job kind `score_leafwise_nan`: one job is one `api.predict` of the
configuration's LEAF-WISE ensemble WITH LEARNED NaN DIRECTIONS (a node list:
255 leaves a tree, LightGBM's own layout and its `use_missing` routing) over
its binned batch, a wide sensor table four-fifths missing: host uint8 rows
in, host float32 raw margins out, both transfers counted. Reports
`score_mrows_per_s`: all the rows of the calls that finished over all the
time of the window.

The job asks what serves the model FIRST, before any row is drawn: `setup`
builds the model in the program, lowers the scoring program and reads the
program's `ddt:predict:ensemble` span, and exits non-zero, with no result
line, unless the program carries `tpu_custom_call` and the span says
`node_list` 1 and `missing_routes` 1. A program whose node list has no field
for the directions fails earlier still, at the constructor. It asks NOTHING
about tiling (the K-blocks of the select, `trees_per_step`, the row tile):
the per-layer metrics report it.

`check` holds a seeded sample of rows of EVERY call of the window to the
plain reference's float64 node walk (`reference_leafwise_nan.py`), and
refuses a sample in which the NaN route decides less than the
configuration's share of the node visits to either side, or the ordinal
compare less than its own, or which reaches less than the configuration's
share of the ensemble's leaves, or no leaf deeper than `deep_leaf_min`
levels: a dead route, a dead subtree or a model the heap kernels could have
served cannot pass. Limits are in the configuration's file under "check",
each with the readings it was set from.

A CONTROL run hands the program a model with one thing wrong and holds its
answer to the right one: `--set patched_table='"<control>"'`, the control one
of `reference_leafwise_nan.CONTROLS`. It is no TrainConfig field and is taken
out before the program's configuration is made; run.py prints CONTROL and no
result line, and the control has to come out `correct` false.
What it shares with job kind `score` (the call, the rate, the finite-scores
scan, the lowered program's question) it takes from `jobs/score.py`.
"""

from __future__ import annotations

import sys

import numpy as np

import datagen_bosch
import reference_leafwise_nan
from jobs import score

PATCH = "patched_table"


class Job(score.Job):
    """`score.Job` (one `api.predict` a job, the rows over the span) with a
    NaN-routed node-list ensemble over a sparse table, the what-ran question
    asked first, and the sample held to the routes, the leaves and the
    depth."""

    def __init__(self, cell: dict, seed: int, rehearse: bool, control: dict):
        self.patch = control.get(PATCH)
        super().__init__(cell, seed, rehearse,
                         {k: v for k, v in control.items() if k != PATCH})

    def setup(self) -> None:
        from ddt_tpu.models.tree import NodeListEnsemble

        s, m = self.shapes, self.cell["config"]["model"]
        self.nan_bin = s["n_bins"] - 1
        self.missing = datagen_bosch.missing_bytes(s["features"], self.seed)
        self.tables = datagen_bosch.leafwise_nan_trees(
            s["n_trees"], s["n_leaves"], s["features"], s["n_bins"],
            self.seed, self.missing)
        # what the program is handed: the tables, or a control's
        t = reference_leafwise_nan.patched(self.tables, self.patch)
        try:
            self.ens = NodeListEnsemble(
                feature=t["feature"], threshold_bin=t["threshold_bin"],
                threshold_raw=np.zeros(t["feature"].shape, np.float32),
                left_child=t["left_child"], right_child=t["right_child"],
                leaf_value=t["leaf_value"],
                n_leaves=np.full(s["n_trees"], s["n_leaves"], np.int32),
                split_gain=np.zeros(t["feature"].shape, np.float32),
                n_features=s["features"], learning_rate=m["learning_rate"],
                base_score=m["base_score"], loss=m["loss"],
                n_bins=s["n_bins"], default_left=t.get("default_left"),
                missing_bin=True)
        except (TypeError, ValueError) as e:
            raise SystemExit(
                "score_leafwise_nan: this program's node list takes no "
                f"learned NaN directions ({type(e).__name__}: {e}). No rows "
                "drawn, no warm-up, no window, no result line.")
        self.what_ran = self._what_ran()
        if not (self.rehearse or self.patch
                or all(ok for *_, ok in self.what_ran)):
            for what, value, limit, _ in self.what_ran:
                print(f"score_leafwise_nan: {what}: {value} (limit {limit})",
                      file=sys.stderr)
            raise SystemExit(
                "score_leafwise_nan: no Pallas kernel serves this NaN-routed "
                "node-list model here, or the program does not say that one "
                "does. No rows drawn, no warm-up, no window, no result line.")
        self.Xb = datagen_bosch.sparse_bins(
            s["rows"], s["features"], s["n_bins"], self.seed, self.missing)

    # ------------------------------------------------------------------ #

    def check(self, outputs: list, warm_up) -> list:
        if not outputs:
            return []
        s, lim, m = self.shapes, self.limits, self.cell["config"]["model"]
        checks = []
        shaped = all(o.shape == (s["rows"],) and o.dtype == np.float32
                     for o in outputs)
        checks.append(("every call returned float32 [rows]", shaped, True,
                       shaped))
        differ = sum(not np.array_equal(o, warm_up) for o in outputs)
        checks.append(("calls of the window whose scores differ from the "
                       "warm-up call's in any bit", differ, 0, differ == 0))
        if not shaped:
            return checks + self.what_ran

        rng = np.random.default_rng(np.random.SeedSequence([self.seed, 4]))
        idx = np.sort(rng.choice(s["rows"],
                                 size=min(lim["sample_rows"], s["rows"]),
                                 replace=False))
        visited = np.zeros(self.tables["leaf_value"].shape, bool)
        want, deepest, visits = reference_leafwise_nan.raw_scores(
            self.tables, m["learning_rate"], m["base_score"], self.Xb[idx],
            self.nan_bin, visited)
        gap = max(float(np.max(np.abs(o[idx].astype(np.float64) - want)))
                  for o in outputs)
        checks.append((f"scores of {len(idx)} sampled rows in each of "
                       f"{len(outputs)} calls vs the float64 reference "
                       f"(|score| up to {float(np.abs(want).max()):.2f}), "
                       "max |gap|", gap, lim["score_atol"],
                       bool(gap <= lim["score_atol"])))
        nan_left, nan_right, ordinal = visits / max(1, visits.sum())
        print(f"score_leafwise_nan: {visits.sum()} node visits of the "
              f"sample: the NaN route sent {nan_left:.4%} left and "
              f"{nan_right:.4%} right, an ordinal compare decided "
              f"{ordinal:.4%}", flush=True)
        nan_least = float(min(nan_left, nan_right))
        checks.append(("least share of the sample's node visits that the "
                       "NaN route decides to one side", nan_least,
                       f">= {lim['nan_route_share_min']}",
                       bool(nan_least >= lim["nan_route_share_min"])))
        checks.append(("share of the sample's node visits that an ordinal "
                       "compare decides", float(ordinal),
                       f">= {lim['ordinal_share_min']}",
                       bool(ordinal >= lim["ordinal_share_min"])))
        share = float(visited.mean())
        checks.append((f"share of the ensemble's {visited.size} leaves "
                       "that the sample reaches", share,
                       f">= {lim['leaf_share_min']}",
                       bool(share >= lim["leaf_share_min"])))
        checks.append(("nodes on the deepest path a sampled row takes",
                       deepest, f"> {lim['deep_leaf_min']}",
                       bool(deepest > lim["deep_leaf_min"])))
        return checks + self.what_ran

    def _what_ran(self) -> list:
        """Which scoring program serves the model, asked BEFORE the first
        row is drawn: the program's own record, the `ddt:predict:ensemble`
        span of the model's build (`node_list` 1: the path-matrix form;
        `missing_routes` 1: with the NaN directions in its compare), and on
        the chip `score.Job`'s question too, whether the lowered program
        carries a compiled Pallas kernel (a CPU lowers no such call).
        Nothing about the kernel's tiling."""
        from ddt_tpu.backends import get_backend
        from ddt_tpu.telemetry.annotations import recent_spans

        get_backend(self.cfg)._predict_fn(self.ens)     # builds the model
        built = [sp for sp in recent_spans()
                 if sp["name"] == "ddt:predict:ensemble"]
        counts = built[-1]["counts"] if built else {}
        print(f"score_leafwise_nan: ddt:predict:ensemble {counts}",
              flush=True)
        want = {"node_list": 1, "missing_routes": 1}
        said = {k: counts.get(k) for k in want}
        return super()._what_ran() + [
            ("the program's record says a node-list form with the NaN "
             f"route serves ({want})", said, True, said == want)]
