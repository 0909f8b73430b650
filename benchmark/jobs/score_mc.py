"""Job kind `score_mc`: one job is one `api.predict` of the configuration's
MULTICLASS ensemble over its batch: host uint8 rows in, host float32 raw
scores [rows, classes] out, both transfers counted. Reports
`score_mrows_per_s`: all the rows of the calls that finished over all the time
of the window.

The job fails at once where the Pallas traversal kernel does not serve the
model: before the warm-up call `setup` lowers the scoring program and exits
non-zero, with no result line, unless it carries `tpu_custom_call`. The
fallback (the XLA one-hot path) would take many minutes a call at this size,
and a cell that times the fallback measures nothing this configuration is for.

`check` holds a seeded sample of rows of EVERY call of the window, all class
columns, to the plain reference's float64 traversal (`reference_mc.py`);
limits are in the configuration's file under "check", each with the readings
it was set from. What it shares with job kind `score` (the call, the
rate, the finite-scores scan) it takes from `jobs/score.py`.
"""

from __future__ import annotations

import sys

import numpy as np

import datagen
import reference_mc
from jobs import score


class Job(score.Job):
    """`score.Job` (one `api.predict` a job, the rows over the span) with a
    multiclass ensemble, the what-ran question asked first, and every class
    column checked."""

    def setup(self) -> None:
        from ddt_tpu.models.tree import empty_ensemble

        s, m = self.shapes, self.cell["config"]["model"]
        if s["n_trees"] != s["rounds"] * s["n_classes"]:
            raise ValueError(f"n_trees is not rounds x n_classes in {s}")
        self.tables = datagen.random_full_trees(
            s["n_trees"], s["max_depth"], s["features"], s["n_bins"],
            self.seed)
        self.ens = empty_ensemble(s["n_trees"], s["max_depth"],
                                  s["features"], m["learning_rate"],
                                  m["base_score"], m["loss"],
                                  s["n_classes"])
        for k, v in self.tables.items():
            getattr(self.ens, k)[:] = v
        self.what_ran = self._what_ran()
        if not self.rehearse and not all(ok for *_, ok in self.what_ran):
            for what, value, limit, _ in self.what_ran:
                print(f"score_mc: {what}: {value} (limit {limit})",
                      file=sys.stderr)
            raise SystemExit(
                "score_mc: the Pallas traversal kernel does not serve this "
                "model here; the fallback is not what this cell measures. "
                "No warm-up, no window, no result line.")
        self.Xb = datagen.uniform_bins(s["rows"], s["features"], s["n_bins"],
                                       self.seed)

    # ------------------------------------------------------------------ #

    def check(self, outputs: list, warm_up) -> list:
        if not outputs:
            return []
        s, lim, m = self.shapes, self.limits, self.cell["config"]["model"]
        C = s["n_classes"]
        checks = []
        shaped = all(o.shape == (s["rows"], C) and o.dtype == np.float32
                     for o in outputs)
        checks.append((f"every call returned float32 [rows, {C}]", shaped,
                       True, shaped))
        differ = sum(not np.array_equal(o, warm_up) for o in outputs)
        checks.append(("calls of the window whose scores differ from the "
                       "warm-up call's in any bit", differ, 0, differ == 0))
        if not shaped:
            return checks + self.what_ran

        rng = np.random.default_rng(np.random.SeedSequence([self.seed, 4]))
        idx = np.sort(rng.choice(s["rows"],
                                 size=min(lim["sample_rows"], s["rows"]),
                                 replace=False))
        want = reference_mc.raw_scores(self.tables, s["max_depth"],
                                       m["learning_rate"], m["base_score"],
                                       C, self.Xb[idx])
        gap = max(float(np.max(np.abs(o[idx].astype(np.float64) - want)))
                  for o in outputs)
        checks.append((f"scores of {len(idx)} sampled rows x {C} classes in "
                       f"each of {len(outputs)} calls vs the float64 "
                       f"reference (|score| up to "
                       f"{float(np.abs(want).max()):.2f}), max |gap|", gap,
                       lim["score_atol"], bool(gap <= lim["score_atol"])))
        return checks + self.what_ran

    def _what_ran(self) -> list:
        """Which scoring program serves the model, asked BEFORE the first
        call. On the chip: `score.Job`'s question, whether the lowered
        program carries a compiled Pallas kernel. In a rehearsal a CPU
        lowers no such call, so the program's own record stands in:
        `tree_group` on the `ddt:predict:ensemble` span, 128 when the
        kernel serves."""
        from ddt_tpu.backends import get_backend
        from ddt_tpu.telemetry.annotations import recent_spans

        get_backend(self.cfg)._predict_fn(self.ens)     # builds the model
        built = [sp for sp in recent_spans()
                 if sp["name"] == "ddt:predict:ensemble"]
        counts = built[-1]["counts"] if built else {}
        print(f"score_mc: ddt:predict:ensemble {counts}", flush=True)
        served = counts.get("tree_group") == 128
        return super()._what_ran() or [
            ("the program's record says the Pallas traversal kernel serves "
             "(tree_group 128)", served, True, served)]
