"""Job kind `score_forest`: one job is one `api.predict` of the
configuration's AVERAGED FOREST (a node list with a class distribution a
leaf: scikit-learn's random forest grown to purity, some 4,000 leaves a
tree, scored by `predict_proba`'s rule) over its binned batch: host uint8
rows in, host float32 [rows, classes] mean class distributions out, both
transfers counted. Reports `score_mrows_per_s`: all the rows of the calls
that finished over all the time of the window.

The job asks what serves the model FIRST, before any row is drawn: `setup`
asks the program whether its node list holds vector leaves at all (a program
from before them exits here, in seconds), draws the forest
(`datagen_forest.py`: from the configuration's FIXED forest seed, so every
`--seed` scores the same forest), builds it in the program, lowers the
scoring program and reads the program's `ddt:predict:ensemble` span, and
exits non-zero, with no result line, unless the program carries
`tpu_custom_call` and the span says `node_list` 1, `subtrees_per_tree` over
1 and `leaf_columns` = the classes. It asks NOTHING about tiling (the lanes
of a sub-tree, `trees_per_step`, the row tile, the K-blocks): the per-layer
metrics report it. The forest's skeleton (nodes, leaves, path entries) goes
under `shapes["skeleton"]`, where `opcount_forest.py` counts the work from.

`check` holds a seeded sample of rows of EVERY call of the window to the
plain reference's float64 walk of the uncut trees (`reference_forest.py`),
and refuses a sample which reaches less than the configuration's share of
the forest's leaves, whose rows pass fewer nodes a tree than its limit, or
in which no row goes deeper than `deep_leaf_min` nodes: a dead sub-tree or
a forest a heap could have held cannot pass. Limits are in the
configuration's file under "check", each with the readings it was set from.

A CONTROL run hands the program a forest with one thing wrong and holds its
answer to the right one: `--set patched_table='"<control>"'`, the control one
of `reference_forest.CONTROLS`. It is no TrainConfig field and is taken out
before the program's configuration is made; run.py prints CONTROL and no
result line, and the control has to come out `correct` false.
What it shares with job kind `score` (the call, the rate, the finite-scores
scan, the lowered program's question) it takes from `jobs/score.py`.
"""

from __future__ import annotations

import sys

import numpy as np

import datagen_forest
import reference_forest
from jobs import score

PATCH = "patched_table"


class Job(score.Job):
    """`score.Job` (one `api.predict` a job, the rows over the span) with an
    averaged forest of full-depth trees, the what-ran question asked first,
    and the sample held to the leaves, the path lengths and the depth."""

    def __init__(self, cell: dict, seed: int, rehearse: bool, control: dict):
        self.patch = control.get(PATCH)
        super().__init__(cell, seed, rehearse,
                         {k: v for k, v in control.items() if k != PATCH})

    def setup(self) -> None:
        from ddt_tpu.models import tree

        if not hasattr(tree.NodeListEnsemble, "vector_leaves"):
            raise SystemExit(
                "score_forest: this program's node list holds no vector "
                "leaves (one output column, no mean over the trees). No "
                "forest drawn, no rows drawn, no warm-up, no window, no "
                "result line.")
        s, m = self.shapes, self.cell["config"]["model"]
        self.tables = datagen_forest.grown_forest(
            s["n_trees"], s["features"], s["n_bins"], s["n_classes"],
            s["forest_seed"], **self.cell["config"]["assumed"]["drawing"])
        s["skeleton"] = datagen_forest.skeleton(self.tables)
        print(f"score_forest: the skeleton of forest_seed {s['forest_seed']}"
              f": {s['skeleton']}; leaves a tree "
              f"{self.tables['n_leaves'].min()}.."
              f"{self.tables['n_leaves'].max()}", flush=True)
        # what the program is handed: the tables, or a control's
        t = reference_forest.patched(self.tables, self.patch)
        shape = t["feature"].shape
        self.ens = tree.NodeListEnsemble(
            feature=t["feature"], threshold_bin=t["threshold_bin"],
            threshold_raw=np.zeros(shape, np.float32),
            left_child=t["left_child"], right_child=t["right_child"],
            leaf_value=t["leaf_value"].astype(np.float32),
            n_leaves=t["n_leaves"], split_gain=np.zeros(shape, np.float32),
            n_features=s["features"], learning_rate=m["learning_rate"],
            base_score=m["base_score"], loss=m["loss"],
            n_classes=s["n_classes"], n_bins=s["n_bins"])
        self.what_ran = self._what_ran()
        if not (self.rehearse or self.patch
                or all(ok for *_, ok in self.what_ran)):
            for what, value, limit, _ in self.what_ran:
                print(f"score_forest: {what}: {value} (limit {limit})",
                      file=sys.stderr)
            raise SystemExit(
                "score_forest: no Pallas kernel serves this forest of "
                "chained sub-trees with vector leaves here, or the program "
                "does not say that one does. No rows drawn, no warm-up, no "
                "window, no result line.")
        self.Xb = datagen_forest.uniform_pixels(s["rows"], s["features"],
                                                self.seed)

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
        checks.append(("calls of the window whose scores differ from the "
                       "warm-up call's in any bit", differ, 0, differ == 0))
        if not shaped:
            return checks + self.what_ran

        rng = np.random.default_rng(np.random.SeedSequence([self.seed, 4]))
        idx = np.sort(rng.choice(s["rows"],
                                 size=min(lim["sample_rows"], s["rows"]),
                                 replace=False))
        visited = np.zeros(self.tables["leaf_value"].shape[:2], bool)
        want, deepest, passed = reference_forest.class_scores(
            self.tables, self.Xb[idx], visited)
        gap = max(float(np.max(np.abs(o[idx].astype(np.float64) - want)))
                  for o in outputs)
        checks.append((f"class scores of {len(idx)} sampled rows in each of "
                       f"{len(outputs)} calls vs the float64 reference (a "
                       f"score up to {float(want.max()):.2f}), max |gap|",
                       gap, lim["score_atol"],
                       bool(gap <= lim["score_atol"])))
        leaves = int(self.tables["n_leaves"].sum())
        share = float(visited.sum() / leaves)
        checks.append((f"share of the forest's {leaves} leaves that the "
                       "sample reaches", share,
                       f">= {lim['leaf_share_min']}",
                       bool(share >= lim["leaf_share_min"])))
        checks.append(("nodes a sampled row passes in a tree, on average",
                       passed, f">= {lim['path_nodes_min']}",
                       bool(passed >= lim["path_nodes_min"])))
        checks.append(("nodes on the deepest path a sampled row takes",
                       deepest, f"> {lim['deep_leaf_min']}",
                       bool(deepest > lim["deep_leaf_min"])))
        return checks + self.what_ran

    def _what_ran(self) -> list:
        """Which scoring program serves the forest, asked BEFORE the first
        row is drawn: the program's own record, the `ddt:predict:ensemble`
        span of the model's build (`node_list` 1: the path-matrix form;
        `subtrees_per_tree` over 1: its trees cut and chained;
        `leaf_columns`: a class vector a leaf), and on the chip
        `score.Job`'s question too, whether the lowered program carries a
        compiled Pallas kernel (a CPU lowers no such call). Nothing about
        the kernel's tiling."""
        from ddt_tpu.backends import get_backend
        from ddt_tpu.telemetry.annotations import recent_spans

        get_backend(self.cfg)._predict_fn(self.ens)     # builds the model
        built = [sp for sp in recent_spans()
                 if sp["name"] == "ddt:predict:ensemble"]
        counts = built[-1]["counts"] if built else {}
        print(f"score_forest: ddt:predict:ensemble {counts}", flush=True)
        said = {k: counts.get(k) for k in ("node_list", "subtrees_per_tree",
                                           "leaf_columns")}
        ok = (said["node_list"] == 1
              and (said["subtrees_per_tree"] or 0) > 1
              and said["leaf_columns"] == self.shapes["n_classes"])
        return super()._what_ran() + [
            ("the program's record says a node-list form serves trees cut "
             "into sub-trees with a class vector a leaf (node_list 1, "
             "subtrees_per_tree > 1, leaf_columns = classes)", said, True,
             ok)]
