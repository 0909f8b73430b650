"""Job kind `score_leafwise`: one job is one `api.predict` of the
configuration's LEAF-WISE ensemble (a node list: 255 leaves a tree, 13-20
levels deep, LightGBM's own layout) over its binned batch: host uint8 rows
in, host float32 raw margins out, both transfers counted. Reports
`score_mrows_per_s`: all the rows of the calls that finished over all the
time of the window.

The job asks what serves the model FIRST, before any row is drawn: `setup`
builds the model in the program, lowers the scoring program and reads the
program's `ddt:predict:ensemble` span, and exits non-zero, with no result
line, unless the program carries `tpu_custom_call` and the span says
`node_list` 1. A program without the node-list layout fails earlier still,
at the import. It asks NOTHING about tiling (`path_mxu_tiles_per_tree`,
`trees_per_step`, the row tile): how a later kernel blocks the work is what
later PRs change, and the per-layer metrics report it.

`check` holds a seeded sample of rows of EVERY call of the window to the
plain reference's float64 node walk (`reference_leafwise.py`), and refuses a
sample that reaches less than the configuration's share of the ensemble's
leaves, or no leaf deeper than `deep_leaf_min` levels: a dead subtree, or a
model the heap kernels could have served, cannot pass. Limits are in the
configuration's file under "check", each with the readings it was set from.
What it shares with job kind `score` (the call, the rate, the finite-scores
scan, the lowered program's question) it takes from `jobs/score.py`.
"""

from __future__ import annotations

import sys

import numpy as np

import datagen
import datagen_leafwise
import reference_leafwise
from jobs import score


class Job(score.Job):
    """`score.Job` (one `api.predict` a job, the rows over the span) with a
    node-list ensemble, the what-ran question asked first, and the sample
    held to the ensemble's leaves and depth."""

    def setup(self) -> None:
        from ddt_tpu.models.tree import NodeListEnsemble

        s, m = self.shapes, self.cell["config"]["model"]
        self.tables = datagen_leafwise.leafwise_trees(
            s["n_trees"], s["n_leaves"], s["features"], s["n_bins"],
            self.seed)
        t = self.tables
        self.ens = NodeListEnsemble(
            feature=t["feature"], threshold_bin=t["threshold_bin"],
            threshold_raw=np.zeros(t["feature"].shape, np.float32),
            left_child=t["left_child"], right_child=t["right_child"],
            leaf_value=t["leaf_value"],
            n_leaves=np.full(s["n_trees"], s["n_leaves"], np.int32),
            split_gain=np.zeros(t["feature"].shape, np.float32),
            n_features=s["features"], learning_rate=m["learning_rate"],
            base_score=m["base_score"], loss=m["loss"], n_bins=s["n_bins"])
        self.what_ran = self._what_ran()
        if not self.rehearse and not all(ok for *_, ok in self.what_ran):
            for what, value, limit, _ in self.what_ran:
                print(f"score_leafwise: {what}: {value} (limit {limit})",
                      file=sys.stderr)
            raise SystemExit(
                "score_leafwise: no Pallas kernel serves this node-list "
                "model here, or the program does not say that one does. No "
                "rows drawn, no warm-up, no window, no result line.")
        self.Xb = datagen.uniform_bins(s["rows"], s["features"], s["n_bins"],
                                       self.seed)

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
        want, deepest = reference_leafwise.raw_scores(
            self.tables, m["learning_rate"], m["base_score"], self.Xb[idx],
            visited)
        gap = max(float(np.max(np.abs(o[idx].astype(np.float64) - want)))
                  for o in outputs)
        checks.append((f"scores of {len(idx)} sampled rows in each of "
                       f"{len(outputs)} calls vs the float64 reference "
                       f"(|score| up to {float(np.abs(want).max()):.2f}), "
                       "max |gap|", gap, lim["score_atol"],
                       bool(gap <= lim["score_atol"])))
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
        span of the model's build (`node_list` 1: the path-matrix form),
        and on the chip `score.Job`'s question too, whether the lowered
        program carries a compiled Pallas kernel (a CPU lowers no such
        call). Nothing about the kernel's tiling."""
        from ddt_tpu.backends import get_backend
        from ddt_tpu.telemetry.annotations import recent_spans

        get_backend(self.cfg)._predict_fn(self.ens)     # builds the model
        built = [sp for sp in recent_spans()
                 if sp["name"] == "ddt:predict:ensemble"]
        counts = built[-1]["counts"] if built else {}
        print(f"score_leafwise: ddt:predict:ensemble {counts}", flush=True)
        said = counts.get("node_list")
        return super()._what_ran() + [
            ("the program's record says a node-list form serves "
             "(node_list 1)", said, 1, said == 1)]
