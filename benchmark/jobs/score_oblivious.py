"""Job kind `score_oblivious`: one job is one `api.predict` of the
configuration's ensemble of OBLIVIOUS trees (CatBoost's symmetric trees: D
splits and 2^D leaf values a tree, the leaf a D-bit number) over its dense
binned batch: host uint8 rows in, host float32 raw margins out, both
transfers counted. Reports `score_mrows_per_s`: all the rows of the calls
that finished over all the time of the window.

The job asks what serves the model FIRST, before any row is drawn: `setup`
builds the model in the program, lowers the scoring program and reads the
program's `ddt:predict:ensemble` span, and exits non-zero, with no result
line, unless the program carries `tpu_custom_call` and the span says
`oblivious` 1 and `select_columns_per_tree` equal to the model's depth (6
served natively; an expansion to a heap or a node list would select 63). A
program without the oblivious layout fails earlier still, at the import. It
asks NOTHING about tiling (the K-blocks of the select, `trees_per_step`, the
row tile): the per-layer metrics report it.

`check` holds a seeded sample of rows of EVERY call of the window to the
plain reference's float64 bit walk (`reference_oblivious.py`), and refuses a
sample in which any of the D bit positions is set in less or more of the
(row, tree) visits than the configuration's shares, or which reaches less
than the configuration's share of the ensemble's leaves: a dead split, a
constant column or a corner of the bin box cannot pass. Limits are in the
configuration's file under "check", each with the readings it was set from.

A CONTROL run hands the program a model with one thing wrong and holds its
answer to the right one: `--set patched_table='"<control>"'`, the control one
of `reference_oblivious.CONTROLS`. It is no TrainConfig field and is taken
out before the program's configuration is made; run.py prints CONTROL and no
result line, and the control has to come out `correct` false.
What it shares with job kind `score` (the call, the rate, the finite-scores
scan, the lowered program's question) it takes from `jobs/score.py`.
"""

from __future__ import annotations

import sys

import numpy as np

import datagen
import datagen_oblivious
import reference_oblivious
from jobs import score

PATCH = "patched_table"


class Job(score.Job):
    """`score.Job` (one `api.predict` a job, the rows over the span) with an
    oblivious ensemble, the what-ran question asked first, and the sample
    held to the bits and the leaves."""

    def __init__(self, cell: dict, seed: int, rehearse: bool, control: dict):
        self.patch = control.get(PATCH)
        super().__init__(cell, seed, rehearse,
                         {k: v for k, v in control.items() if k != PATCH})

    def setup(self) -> None:
        try:
            from ddt_tpu.models.tree import ObliviousEnsemble
        except ImportError as e:
            raise SystemExit(
                f"score_oblivious: this program has no oblivious layout "
                f"({e}). No rows drawn, no warm-up, no window, no result "
                "line.")
        s, m = self.shapes, self.cell["config"]["model"]
        self.tables = datagen_oblivious.oblivious_trees(
            s["n_trees"], s["depth"], s["features"], s["n_bins"], self.seed,
            m["leaf_sigma"])
        # what the program is handed: the tables, or a control's
        t = reference_oblivious.patched(self.tables, self.patch,
                                        s["features"])
        self.ens = ObliviousEnsemble(
            split_feature=t["split_feature"], split_bin=t["split_bin"],
            leaf_value=t["leaf_value"], n_features=s["features"],
            scale=m["scale"], bias=m["bias"], loss=m["loss"],
            n_bins=s["n_bins"])
        self.what_ran = self._what_ran()
        if not (self.rehearse or self.patch
                or all(ok for *_, ok in self.what_ran)):
            for what, value, limit, _ in self.what_ran:
                print(f"score_oblivious: {what}: {value} (limit {limit})",
                      file=sys.stderr)
            raise SystemExit(
                "score_oblivious: no Pallas kernel serves this oblivious "
                "model as it is here, or the program does not say that one "
                "does. No rows drawn, no warm-up, no window, no result line.")
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
        want, bit_set = reference_oblivious.raw_scores(
            self.tables, m["scale"], m["bias"], self.Xb[idx], visited)
        gap = max(float(np.max(np.abs(o[idx].astype(np.float64) - want)))
                  for o in outputs)
        checks.append((f"scores of {len(idx)} sampled rows in each of "
                       f"{len(outputs)} calls vs the float64 reference "
                       f"(|score| up to {float(np.abs(want).max()):.2f}), "
                       "max |gap|", gap, lim["score_atol"],
                       bool(gap <= lim["score_atol"])))
        shares = bit_set / float(len(idx) * s["n_trees"])
        print(f"score_oblivious: {len(idx) * s['n_trees']} (row, tree) "
              "visits of the sample: bit d set in "
              + " ".join(f"{v:.4%}" for v in shares), flush=True)
        lo, hi = lim["bit_share_min"], lim["bit_share_max"]
        checks.append(("least and largest share of the sample's (row, tree) "
                       "visits in which one of the bit positions is set",
                       [float(shares.min()), float(shares.max())],
                       f"within {lo} .. {hi}",
                       bool(lo <= shares.min() and shares.max() <= hi)))
        share = float(visited.mean())
        checks.append((f"share of the ensemble's {visited.size} leaves "
                       "that the sample reaches", share,
                       f">= {lim['leaf_share_min']}",
                       bool(share >= lim["leaf_share_min"])))
        return checks + self.what_ran

    def _what_ran(self) -> list:
        """Which scoring program serves the model, asked BEFORE the first
        row is drawn: the program's own record, the `ddt:predict:ensemble`
        span of the model's build (`oblivious` 1: the oblivious form;
        `select_columns_per_tree` the depth: the layout as it is, no
        expansion), and on the chip `score.Job`'s question too, whether the
        lowered program carries a compiled Pallas kernel (a CPU lowers no
        such call). Nothing about the kernel's tiling."""
        from ddt_tpu.backends import get_backend
        from ddt_tpu.telemetry.annotations import recent_spans

        get_backend(self.cfg)._predict_fn(self.ens)     # builds the model
        built = [sp for sp in recent_spans()
                 if sp["name"] == "ddt:predict:ensemble"]
        counts = built[-1]["counts"] if built else {}
        print(f"score_oblivious: ddt:predict:ensemble {counts}", flush=True)
        want = {"oblivious": 1,
                "select_columns_per_tree": self.shapes["depth"]}
        said = {k: counts.get(k) for k in want}
        return super()._what_ran() + [
            ("the program's record says the oblivious form serves the "
             f"layout as it is ({want})", said, True, said == want)]
