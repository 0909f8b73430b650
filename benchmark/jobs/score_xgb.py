"""Job kind `score_xgb`: one job is one `api.predict` of the configuration's
XGBoost MULTICLASS model (`multi:softprob`, depth at most 16: GPUTreeShap's
`covtype-large`, the SCORING half) over the set's binned rows: host uint8
rows in, host float32 [rows, classes] class probabilities out, both
transfers counted. Reports `score_mrows_per_s`: all the rows of the calls
that finished over all the time of the window.

The model reaches the program AS A USER'S WOULD: `datagen_xgb.drawn_model`
emits the library's JSON dict (from the configuration's FIXED model seed, so
every `--seed` scores the same model), `models/xgboost_io.from_xgboost_json`
imports it (the data holds no missing value: `missing=False`),
`models/lightgbm_io.threshold_bin_mapper` ranks its thresholds into 256 bins,
and the raw float rows (`datagen_xgb.rows_and_bins`: half of the continuous
values ON a cut, where `<` and `<=` part) are binned by that mapper, in
set-up; the call is `api.predict(model, Xb, binned=True)`, as in every
scoring cell (ROADMAP M11).

The job asks what serves the model FIRST, before any row is drawn: `setup`
imports the program's XGBoost module (a program from before it exits here,
in seconds), draws and imports the model, builds it in the program, lowers
the scoring program and reads the program's `ddt:predict:ensemble` span, and
exits non-zero, with no result line, unless the program carries
`tpu_custom_call` and the span says `node_list` 1, `leaf_columns` = the
classes, `link` "softmax", trees of ONE sub-tree (`single_subtree_trees` >
0) beside a tree of more than `subtrees_max_min` (`subtrees_per_tree_max`).
It asks NOTHING about tiling (the lanes of a sub-tree, `trees_per_step`, the
row tile, the select's packing): the per-layer metrics report it. The
model's skeleton (nodes, leaves, path entries) goes under
`shapes["skeleton"]`, where `opcount_xgb.py` counts the work from.

`check` holds a seeded sample of rows of EVERY call of the window to the
plain reference's float64 walk of the library's own arrays
(`reference_xgb.py`, on the RAW rows), and refuses a sample which reaches
less than the configuration's share of the model's leaves, whose rows pass
fewer nodes a tree than its limit, or in which no row goes deeper than
`deep_leaf_min` nodes: a dead sub-tree or a model a heap could have held
cannot pass. Limits are in the configuration's file under "check", each
with the readings it was set from.

A CONTROL run hands the program a model with one thing wrong (or, for
"no_link", asks it for the margins) and holds its answer to the right one:
`--set patched_table='"<control>"'`, the control one of
`reference_xgb.CONTROLS`. It is no TrainConfig field and is taken out before
the program's configuration is made; run.py prints CONTROL and no result
line, and the control has to come out `correct` false.
What it shares with job kind `score` (the rate, the finite-scores scan) it
takes from `jobs/score.py`; the lowered program's question it asks itself,
of the ONE program the window runs (`score.Job`'s would build the model a
second time, without the link: 3 GB more on the device).
"""

from __future__ import annotations

import sys

import numpy as np

import datagen_xgb
import reference_xgb
from jobs import score

PATCH = "patched_table"
SAID = ("node_list", "leaf_columns", "link", "single_subtree_trees",
        "subtrees_per_tree_max")


class Job(score.Job):
    """`score.Job` (one `api.predict` a job, the rows over the span) with an
    imported XGBoost multiclass model, the what-ran question asked first,
    and the sample held to the leaves, the path lengths and the depth."""

    def __init__(self, cell: dict, seed: int, rehearse: bool, control: dict):
        self.patch = control.get(PATCH)
        super().__init__(cell, seed, rehearse,
                         {k: v for k, v in control.items() if k != PATCH})

    def setup(self) -> None:
        try:
            from ddt_tpu.models import xgboost_io
        except ImportError as e:
            raise SystemExit(
                f"score_xgb: this program imports no XGBoost model ({e}). "
                "No model drawn, no rows drawn, no warm-up, no window, no "
                "result line.") from None
        from ddt_tpu.models.lightgbm_io import threshold_bin_mapper

        s, cfg = self.shapes, self.cell["config"]
        # (a rehearsal's "drawing" fades the first round's mass at once, so
        # that its two rounds hold small trees beside large ones)
        self.model = datagen_xgb.drawn_model(
            s["rounds"], s["features"], s["model_seed"],
            cfg["model"]["base_score"],
            **{**cfg["assumed"]["drawing"], **s.get("drawing", {})})
        s["skeleton"] = datagen_xgb.skeleton(self.model)
        print(f"score_xgb: the skeleton of model_seed {s['model_seed']}: "
              f"{s['skeleton']}", flush=True)
        # what the program is handed: the model, or a control's
        self.ens = xgboost_io.from_xgboost_json(
            reference_xgb.patched(self.model, self.patch,
                                  cfg["assumed"]["drawing"]["eta"]),
            missing=False)
        self.mapper = threshold_bin_mapper(self.ens, n_bins=s["n_bins"])
        self.what_ran = self._what_ran()
        if not (self.rehearse or self.patch
                or all(ok for *_, ok in self.what_ran)):
            for what, value, limit, _ in self.what_ran:
                print(f"score_xgb: {what}: {value} (limit {limit})",
                      file=sys.stderr)
            raise SystemExit(
                "score_xgb: no Pallas kernel serves this model of softmax's "
                "round-major trees cut into chained sub-trees with the link "
                "on the device here, or the program does not say that one "
                "does. No rows drawn, no warm-up, no window, no result "
                "line.")
        self.X, drawn = datagen_xgb.rows_and_bins(
            s["rows"], s["features"], self.seed, cfg["assumed"]["on_cut"])
        self.Xb = self.mapper.transform(self.X)
        moved = int((self.Xb != drawn).sum())
        print(f"score_xgb: cells whose bin under the model's own mapper is "
              f"not the bin they were drawn in: {moved} of {drawn.size}",
              flush=True)

    def one_job(self):
        from ddt_tpu import api

        return api.predict(self.ens, self.Xb, binned=True,
                           raw=self.patch == "no_link", cfg=self.cfg)

    # ------------------------------------------------------------------ #

    def check(self, outputs: list, warm_up) -> list:
        if not outputs:
            return []
        s, lim = self.shapes, self.limits
        checks = []
        shaped = all(o.shape == (s["rows"], s["n_classes"])
                     and o.dtype == np.float32 for o in outputs)
        checks.append(("every call returned float32 [rows, classes]",
                       shaped, True, shaped))
        differ = sum(not np.array_equal(o, warm_up) for o in outputs)
        checks.append(("calls of the window whose answers differ from the "
                       "warm-up call's in any bit", differ, 0, differ == 0))
        if not shaped:
            return checks + self.what_ran

        rng = np.random.default_rng(np.random.SeedSequence([self.seed, 4]))
        idx = np.sort(rng.choice(s["rows"],
                                 size=min(lim["sample_rows"], s["rows"]),
                                 replace=False))
        visited: list = []
        margins, deepest, passed = reference_xgb.margins(
            self.model, self.X[idx], visited)
        want = reference_xgb.softmax(margins)
        gap = max(float(np.max(np.abs(o[idx].astype(np.float64) - want)))
                  for o in outputs)
        checks.append((f"class probabilities of {len(idx)} sampled rows in "
                       f"each of {len(outputs)} calls vs the float64 "
                       "reference (margins from "
                       f"{float(margins.min()):.2f} to "
                       f"{float(margins.max()):.2f}), max |gap|",
                       gap, lim["proba_atol"],
                       bool(gap <= lim["proba_atol"])))
        trees = reference_xgb.booster(self.model)["trees"]
        reached = sum(int(v.sum()) for v in visited)
        share = reached / s["skeleton"]["leaves"]
        checks.append((f"share of the model's {s['skeleton']['leaves']} "
                       "leaves that the sample reaches", share,
                       f">= {lim['leaf_share_min']}",
                       bool(share >= lim["leaf_share_min"])))
        checks.append((f"nodes a sampled row passes in one of the "
                       f"{len(trees)} trees, on average", passed,
                       f">= {lim['path_nodes_min']}",
                       bool(passed >= lim["path_nodes_min"])))
        checks.append(("nodes on the deepest path a sampled row takes",
                       deepest, f"> {lim['deep_leaf_min']}",
                       bool(deepest > lim["deep_leaf_min"])))
        return checks + self.what_ran

    def _what_ran(self) -> list:
        """Which scoring program serves the model, asked BEFORE the first
        row is drawn: the program's own record, the `ddt:predict:ensemble`
        span of the model's build WITH its link (`node_list` 1: the
        path-matrix form; `leaf_columns`: a column a class; `link`: the
        softmax on the device; trees of one sub-tree beside a tree of
        many), and on the chip `score.Job`'s question, whether the lowered
        program carries a compiled Pallas kernel (a CPU lowers no such
        call), asked of THAT program (the one without the link would be a
        second copy of the tables on the device). Nothing about the
        kernel's tiling."""
        import jax

        from ddt_tpu.backends import get_backend
        from ddt_tpu.telemetry.annotations import recent_spans
        from ddt_tpu.utils import device

        be = get_backend(self.cfg)
        # the program the window runs: the one that ends in the link
        fn, ens_dev, *_ = be._predict_entry(self.ens,
                                            link=self.patch != "no_link")
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
        print(f"score_xgb: ddt:predict:ensemble {counts}", flush=True)
        said = {k: counts.get(k) for k in SAID}
        most = self.limits["subtrees_max_min"]
        ok = (said["node_list"] == 1
              and said["leaf_columns"] == self.shapes["n_classes"]
              and said["link"] == ("none" if self.patch == "no_link"
                                   else "softmax")
              and (said["single_subtree_trees"] or 0) > 0
              and (said["subtrees_per_tree_max"] or 0) > most)
        return lowered + [
            ("the program's record says a node-list form serves softmax's "
             "round-major trees, the link on the device, trees of one "
             f"sub-tree beside one of more than {most} (node_list 1, "
             "leaf_columns = classes, link softmax, single_subtree_trees > "
             f"0, subtrees_per_tree_max > {most})", said, True, ok)]
