"""Job kind `score_oblivious_mc`: one job is one `api.predict` of the
configuration's CatBoost MULTICLASS model (oblivious trees of VECTOR leaves:
D splits and 2^D x C leaf values a tree, `MultiClass`) over its binned
batch: host uint8 rows in, host float32 [rows, classes] class probabilities
out, both transfers counted, the softmax taken by the device's own program.
Reports `score_mrows_per_s`: all the rows of the calls that finished over
all the time of the window.

The model reaches the program AS A USER'S WOULD: `datagen_oblivious_mc.
drawn_model` emits the library's JSON dict from `--seed` and
`models/catboost_io.from_catboost_json` imports it, so the importer's
leaf-major reading of `leaf_values` is under test with the kernel; the call
is `api.predict(model, Xb, binned=True)`, as in every scoring cell (ROADMAP
M11; the drawn borders are k + 0.5, so a bin IS a value the model's own
mapper bins back to itself, which `setup` checks on a few rows).

The job asks what serves the model FIRST, before any row is drawn: `setup`
imports the model (a program whose importer turns vector leaves away exits
here, in seconds), builds it in the program WITH its link, lowers that
program and reads the program's `ddt:predict:ensemble` span, and exits
non-zero, with no result line, unless the program carries `tpu_custom_call`
and the span says `oblivious` 1, `leaf_columns` = the classes, `link`
"softmax" and `select_columns_per_tree` equal to the model's depth (6 served
natively; an expansion to a heap or a node list would select 63). It asks
NOTHING about tiling or about which unit looks the leaves up: the per-layer
metrics report it.

`check` holds a seeded sample of rows of EVERY call of the window to the
plain reference's float64 walk of the same dict (`reference_oblivious_mc.
py`), softmax included, and refuses a sample in which any of the D bit
positions is set in less or more of the (row, tree) visits than the
configuration's shares, which reaches less than the configuration's share
of the model's leaves, or in which some class is the argmax of less than
the configuration's share of the rows: a dead split, a constant column, a
corner of the bin box or a dead class column cannot pass. Limits are in the
configuration's file under "check", each with the readings it was set from.

A CONTROL run hands the program a model with one thing wrong (or, for
"no_link", asks it for the margins) and holds its answer to the right one:
`--set patched_table='"<control>"'`, the control one of
`reference_oblivious_mc.CONTROLS`. It is no TrainConfig field and is taken
out before the program's configuration is made; run.py prints CONTROL and no
result line, and the control has to come out `correct` false.
What it shares with job kind `score` (the rate, the finite-scores scan) it
takes from `jobs/score.py`; the lowered program's question it asks itself,
of the ONE program the window runs (the one that ends in the link).
"""

from __future__ import annotations

import sys

import numpy as np

import datagen
import datagen_oblivious_mc
import reference_oblivious_mc
from jobs import score

PATCH = "patched_table"


class Job(score.Job):
    """`score.Job` (one `api.predict` a job, the rows over the span) with an
    imported CatBoost multiclass model, the what-ran question asked first,
    and the sample held to the bits, the leaves and the classes."""

    def __init__(self, cell: dict, seed: int, rehearse: bool, control: dict):
        self.patch = control.get(PATCH)
        super().__init__(cell, seed, rehearse,
                         {k: v for k, v in control.items() if k != PATCH})

    def setup(self) -> None:
        from ddt_tpu.models import catboost_io

        s, m = self.shapes, self.cell["config"]["model"]
        self.model = datagen_oblivious_mc.drawn_model(
            s["n_trees"], s["depth"], s["features"], s["n_bins"],
            s["n_classes"], self.seed, m["leaf_sigma"], m["bias_sigma"],
            m["scale"])
        # what the program is handed: the model, or a control's
        try:
            self.ens = catboost_io.from_catboost_json(
                reference_oblivious_mc.patched(self.model, self.patch),
                n_bins=s["n_bins"])
        except ValueError as e:
            raise SystemExit(
                f"score_oblivious_mc: this program imports no CatBoost "
                f"model of vector leaves ({e}). No rows drawn, no warm-up, "
                "no window, no result line.") from None
        self.what_ran = self._what_ran()
        if not (self.rehearse or self.patch
                or all(ok for *_, ok in self.what_ran)):
            for what, value, limit, _ in self.what_ran:
                print(f"score_oblivious_mc: {what}: {value} (limit {limit})",
                      file=sys.stderr)
            raise SystemExit(
                "score_oblivious_mc: no Pallas kernel serves this oblivious "
                "model of vector leaves as it is with the link on the "
                "device here, or the program does not say that one does. "
                "No rows drawn, no warm-up, no window, no result line.")
        self.Xb = datagen.uniform_bins(s["rows"], s["features"], s["n_bins"],
                                       self.seed)
        head = self.Xb[:4096]
        moved = int((self.ens.bin_mapper().transform(
            head.astype(np.float32)) != head).sum())
        print("score_oblivious_mc: cells of the first rows whose bin under "
              f"the model's own mapper is not the bin they were drawn in: "
              f"{moved} of {head.size}", flush=True)
        if moved:
            raise SystemExit("score_oblivious_mc: the model's own mapper "
                             "does not bin a bin to itself")

    def one_job(self):
        from ddt_tpu import api

        return api.predict(self.ens, self.Xb, binned=True,
                           raw=self.patch == "no_link", cfg=self.cfg)

    # ------------------------------------------------------------------ #

    def check(self, outputs: list, warm_up) -> list:
        if not outputs:
            return []
        s, lim = self.shapes, self.limits
        C = s["n_classes"]
        checks = []
        shaped = all(o.shape == (s["rows"], C) and o.dtype == np.float32
                     for o in outputs)
        checks.append((f"every call returned float32 [rows, {C}]", shaped,
                       True, shaped))
        differ = sum(not np.array_equal(o, warm_up) for o in outputs)
        checks.append(("calls of the window whose answers differ from the "
                       "warm-up call's in any bit", differ, 0, differ == 0))
        if not shaped:
            return checks + self.what_ran
        self._say_where_each_call_went(len(outputs))

        rng = np.random.default_rng(np.random.SeedSequence([self.seed, 4]))
        idx = np.sort(rng.choice(s["rows"],
                                 size=min(lim["sample_rows"], s["rows"]),
                                 replace=False))
        visited = np.zeros((s["n_trees"], 1 << s["depth"]), bool)
        margins, bit_set = reference_oblivious_mc.margins(
            self.model, self.Xb[idx], visited)
        want = reference_oblivious_mc.softmax(margins)
        gap = max(float(np.max(np.abs(o[idx].astype(np.float64) - want)))
                  for o in outputs)
        checks.append((f"class probabilities of {len(idx)} sampled rows x "
                       f"{C} classes in each of {len(outputs)} calls vs the "
                       "float64 reference (margins from "
                       f"{float(margins.min()):.2f} to "
                       f"{float(margins.max()):.2f}), max |gap|", gap,
                       lim["proba_atol"], bool(gap <= lim["proba_atol"])))
        off = max(float(np.max(np.abs(
            o[idx].astype(np.float64).sum(axis=1) - 1.0))) for o in outputs)
        checks.append(("a sampled row's class probabilities summed, max "
                       "|sum - 1|", off, lim["proba_sum_atol"],
                       bool(off <= lim["proba_sum_atol"])))
        shares = bit_set / float(len(idx) * s["n_trees"])
        print(f"score_oblivious_mc: {len(idx) * s['n_trees']} (row, tree) "
              "visits of the sample: bit d set in "
              + " ".join(f"{v:.4%}" for v in shares), flush=True)
        lo, hi = lim["bit_share_min"], lim["bit_share_max"]
        checks.append(("least and largest share of the sample's (row, tree) "
                       "visits in which one of the bit positions is set",
                       [float(shares.min()), float(shares.max())],
                       f"within {lo} .. {hi}",
                       bool(lo <= shares.min() and shares.max() <= hi)))
        share = float(visited.mean())
        checks.append((f"share of the model's {visited.size} leaves that "
                       "the sample reaches", share,
                       f">= {lim['leaf_share_min']}",
                       bool(share >= lim["leaf_share_min"])))
        # of the PROGRAM's answer: a class column that never wins is dead
        wins = min(float(np.bincount(o[idx].argmax(axis=1), minlength=C
                                     ).min()) / len(idx) for o in outputs)
        checks.append((f"least share of the sampled rows of which one of the "
                       f"{C} classes is the argmax of the call's answer",
                       wins, f">= {lim['argmax_share_min']}",
                       bool(wins >= lim["argmax_share_min"])))
        return checks + self.what_ran

    @staticmethod
    def _say_where_each_call_went(n_calls: int) -> None:
        """The host's account of the window's calls, from the program's span
        ring (ms by span name, the root's own time last): a call that
        stalled says where. Printed, never checked; nothing where the
        program keeps no such account."""
        try:
            from ddt_tpu.telemetry.annotations import account, root_spans
        except ImportError:
            return
        for k, root in enumerate(root_spans("predict")[-n_calls:]):
            took = account(root)
            print(f"score_oblivious_mc: call {k + 1} "
                  f"{took['duration_ns'] / 1e6:.1f} ms: " + " ".join(
                      f"{name} {ns / 1e6:.1f}"
                      for name, ns in took["self_ns"].items()), flush=True)

    def _what_ran(self) -> list:
        """Which scoring program serves the model, asked BEFORE the first
        row is drawn: the program's own record, the `ddt:predict:ensemble`
        span of the model's build WITH its link (`oblivious` 1: the
        oblivious form; `leaf_columns`: a column a class; `link`: the
        softmax on the device; `select_columns_per_tree` the depth: the
        layout as it is, no expansion), and on the chip whether the lowered
        program carries a compiled Pallas kernel (a CPU lowers no such
        call), asked of THAT program. Nothing about the kernel's tiling."""
        import jax

        from ddt_tpu.backends import get_backend
        from ddt_tpu.telemetry.annotations import recent_spans
        from ddt_tpu.utils import device

        be = get_backend(self.cfg)
        link = self.patch != "no_link"
        # the program the window runs: the one that ends in the link
        fn, ens_dev, *_ = be._predict_entry(self.ens, link=link)
        lowered = []
        if device.platform() == "tpu":
            x_spec = jax.ShapeDtypeStruct(
                (min(self.shapes["rows"], be.predict_chunk_rows(
                    self.shapes["features"])), self.shapes["features"]),
                np.uint8)
            has = "tpu_custom_call" in jax.jit(fn).lower(
                *ens_dev, x_spec).as_text()
            lowered = [("scoring program carries a compiled Pallas kernel "
                        "(tpu_custom_call)", has, True, has)]
        built = [sp for sp in recent_spans()
                 if sp["name"] == "ddt:predict:ensemble"]
        counts = built[-1]["counts"] if built else {}
        print(f"score_oblivious_mc: ddt:predict:ensemble {counts}",
              flush=True)
        want = {"oblivious": 1, "leaf_columns": self.shapes["n_classes"],
                "link": "softmax" if link else "none",
                "select_columns_per_tree": self.shapes["depth"]}
        said = {k: counts.get(k) for k in want}
        return lowered + [
            ("the program's record says the oblivious form serves the "
             "vector leaves as they are, the link on the device "
             f"({want})", said, True, said == want)]
