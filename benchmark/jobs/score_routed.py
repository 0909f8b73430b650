"""Job kind `score_routed`: one job is one `api.predict` of the configuration's
ROUTED ensemble (a learned direction for missing values and one-vs-rest
category nodes beside the ordinal compare) over its binned click-log batch:
host uint8 rows in, host float32 raw margins out, both transfers counted.
Reports `score_routed_mrows_per_s`: `score.Job`'s rate, all the rows of the
calls that finished over all the time of the window, under a name of its own,
so that `BENCHMARK.json` can give this cell's rate a bound of its own (PR 46;
the configuration's file says why, under "bound").

The job fails at once where the routed form of the Pallas traversal kernel
does not serve the model: before the warm-up call `setup` lowers the scoring
program and reads the program's `ddt:predict:ensemble` span, and exits
non-zero, with no result line, unless the program carries `tpu_custom_call`
and the span says `routing_tables` 2 and `nodes_per_tile` 1. The fallback
(the XLA one-hot path) and the unrouted form are what other cells or no cell
measure; a program that does not say which form serves cannot be held to one.

`check` holds a seeded sample of rows of EVERY call of the window to the
plain reference's float64 traversal (`reference_routed.py`), and refuses a
sample in which one of the four routes (ordinal compare, category match, NaN
bin sent left, NaN bin sent right) decides less than the configuration's
share of the node visits: a dead route cannot pass. Limits are in the
configuration's file under "check", each with the readings it was set from.
What it shares with job kind `score` (the call, the rate, the finite-scores
scan, the lowered program's question) it takes from `jobs/score.py`.
"""

from __future__ import annotations

import sys

import numpy as np

import datagen_routed
import reference_routed
from jobs import score

# The routes `check` holds to the configuration's `route_share_min`, by
# their place in `reference_routed.ROUTES`; the fifth, the category node's
# rest, is printed with them.
HELD_ROUTES = (0, 1, 3, 4)


class Job(score.Job):
    """`score.Job` (one `api.predict` a job, the rows over the span) with a
    routed ensemble over click-log rows, the what-ran question asked first,
    and every route held alive in the checked sample."""

    def setup(self) -> None:
        from ddt_tpu.models.tree import empty_ensemble

        s, m = self.shapes, self.cell["config"]["model"]
        self.cat = tuple(s["cat_features"])
        if self.cat != tuple(range(s["numeric_features"], s["features"])):
            raise ValueError("the categorical columns are not the last "
                             f"features - numeric_features of {s}")
        self.missing_bin = s["n_bins"] - 1
        self.tables = datagen_routed.random_routed_trees(
            s["n_trees"], s["max_depth"], s["features"], s["n_bins"],
            self.cat, self.seed)
        self.ens = empty_ensemble(s["n_trees"], s["max_depth"],
                                  s["features"], m["learning_rate"],
                                  m["base_score"], m["loss"],
                                  missing_bin=True, n_bins=s["n_bins"],
                                  cat_features=self.cat)
        for k, v in self.tables.items():
            getattr(self.ens, k)[:] = v
        self.what_ran = self._what_ran()
        if not self.rehearse and not all(ok for *_, ok in self.what_ran):
            for what, value, limit, _ in self.what_ran:
                print(f"score_routed: {what}: {value} (limit {limit})",
                      file=sys.stderr)
            raise SystemExit(
                "score_routed: the routed form of the Pallas traversal "
                "kernel does not serve this model here, or the program does "
                "not say that it does. No warm-up, no window, no result "
                "line.")
        self.Xb = datagen_routed.click_log_bins(
            s["rows"], s["numeric_features"], s["features"], s["n_bins"],
            self.seed)

    def end_to_end(self, win: dict) -> dict:
        (rate,) = super().end_to_end(win).values()
        return {"score_routed_mrows_per_s": rate}

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
        routes = np.zeros(len(reference_routed.ROUTES), np.int64)
        want = reference_routed.raw_scores(
            self.tables, s["max_depth"], m["learning_rate"], m["base_score"],
            self.Xb[idx], self.missing_bin, self.cat, routes)
        gap = max(float(np.max(np.abs(o[idx].astype(np.float64) - want)))
                  for o in outputs)
        checks.append((f"scores of {len(idx)} sampled rows in each of "
                       f"{len(outputs)} calls vs the float64 reference "
                       f"(|score| up to {float(np.abs(want).max()):.2f}), "
                       "max |gap|", gap, lim["score_atol"],
                       bool(gap <= lim["score_atol"])))
        shares = routes / routes.sum()
        print(f"score_routed: {routes.sum()} node visits of the sample by "
              "what decided them: " + ", ".join(
                  f"{name} {share:.4%}" for name, share in zip(
                      reference_routed.ROUTES, shares)), flush=True)
        least = min(HELD_ROUTES, key=lambda r: shares[r])
        checks.append(("least share of the sample's node visits that one of "
                       "the four routes decides "
                       f"({reference_routed.ROUTES[least]})",
                       float(shares[least]), f">= {lim['route_share_min']}",
                       bool(shares[least] >= lim["route_share_min"])))
        return checks + self.what_ran

    def _what_ran(self) -> list:
        """Which scoring program serves the model, asked BEFORE the first
        call: the program's own record, the `ddt:predict:ensemble` span of
        the model's build (`tree_group` 128: the traversal kernel;
        `routing_tables` 2: with the missing and the categorical table;
        `nodes_per_tile` 1: one node a weight tile), and on the chip
        `score.Job`'s question too, whether the lowered program carries a
        compiled Pallas kernel (a CPU lowers no such call)."""
        from ddt_tpu.backends import get_backend
        from ddt_tpu.telemetry.annotations import recent_spans

        get_backend(self.cfg)._predict_fn(self.ens)     # builds the model
        built = [sp for sp in recent_spans()
                 if sp["name"] == "ddt:predict:ensemble"]
        counts = built[-1]["counts"] if built else {}
        print(f"score_routed: ddt:predict:ensemble {counts}", flush=True)
        want = {"tree_group": 128, "routing_tables": 2, "nodes_per_tile": 1}
        said = {k: counts.get(k) for k in want}
        return super()._what_ran() + [
            ("the program's record says the routed traversal kernel serves "
             f"({want})", said, True, said == want)]
