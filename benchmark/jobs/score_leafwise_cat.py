"""Job kind `score_leafwise_cat`: one job is one `api.predict` of the
configuration's LEAF-WISE ensemble with CATEGORY-SET splits beside ordinal
ones (LightGBM's Allstate claims model: 255 leaves a tree over 16 categorical
and 16 numeric columns, a bitset test or a threshold at every node of the
same tree) over its binned batch: host uint8 rows in, host float32 raw
margins out, both transfers counted. Reports `score_mrows_per_s`: all the
rows of the calls that finished over all the time of the window.

The model reaches the program AS A USER'S WOULD: `datagen_leafwise_cat.
drawn_model` emits the library's model TEXT (from the configuration's FIXED
model seed, so every `--seed` scores the same model), `models/lightgbm_io.
from_lightgbm_text` imports it and `threshold_bin_mapper` gives every raw id
the model names a bin of its own and every other value one bin more, and
ranks a numeric column's thresholds; the raw rows (`drawn_codes`: ids by
each column's law, the long tail the model never names, NaN, -1 and an id
past every bitset among them; numeric values over their bins) are binned by
THAT mapper, in set-up (the mapper's own `transform` of every value a column
takes, gathered by the rows' codes; the sampled rows go through `transform`
whole and have to agree); the call is `api.predict(model, Xb, binned=True)`,
as in every scoring cell (ROADMAP M11).

The job asks what serves the model FIRST, before any row is drawn: a program
that imports no category sets into a node list fails at the import, in
seconds; `setup` then builds the model in the program, lowers the scoring
program and reads the program's `ddt:predict:ensemble` span, and exits
non-zero, with no result line, unless the program carries `tpu_custom_call`
and the span says `node_list` 1 and `category_sets` 1. It asks NOTHING about
tiling: how a kernel makes the set test is what later PRs change, and the
per-layer metrics report it.

`check` holds a seeded sample of rows of EVERY call of the window to the
plain reference's float64 walk of the TEXT's bitsets and thresholds over the
RAW values (`reference_leafwise_cat.py`: the importer and the mapper are
under test with the kernel), and refuses a sample that reaches less than the
configuration's share of the ensemble's leaves, no leaf deeper than
`deep_leaf_min` levels, fewer set nodes or fewer ordinal nodes a path than
`set_nodes_min` / `ordinal_nodes_min`, or a model whose widest set is no
wider than `widest_set_min` (one the heap import's one-vs-rest chains could
have served). Limits are in the configuration's file under "check", each
with the readings it was set from.

A CONTROL run holds the program's answer to a reference with ONE thing
wrong: `--set patched_table='"<control>"'`, the control one of
`reference_leafwise_cat.CONTROLS`, or `"every"`: each of them in turn, a
check line each, so that ONE run on the chip reads them all at the cell's own
size. It is no TrainConfig field and is taken out before the program's
configuration is made; run.py prints CONTROL and no result line, and every
control has to come out FAILED. What it shares with job kinds `score` and
`score_leafwise` (the call, the rate, the finite-scores scan, the lowered
program's question) it takes from them.
"""

from __future__ import annotations

import sys

import numpy as np

import datagen_leafwise_cat
import reference_leafwise_cat
from jobs import score, score_leafwise

SAID = ("node_list", "category_sets")
PATCH = "patched_table"


class Job(score_leafwise.Job):
    """`score_leafwise.Job` with the model imported from the library's text,
    raw rows (category ids and numbers) binned by the model's own mapper,
    and the sample held to the text's bitsets and thresholds."""

    def __init__(self, cell: dict, seed: int, rehearse: bool, control: dict):
        self.patch = control.get(PATCH)
        super().__init__(cell, seed, rehearse,
                         {k: v for k, v in control.items() if k != PATCH})

    def setup(self) -> None:
        from ddt_tpu.models import lightgbm_io

        s, cfg = self.shapes, self.cell["config"]
        assumed = cfg["assumed"]["drawing"]
        self.text = datagen_leafwise_cat.drawn_model(
            s, assumed, cfg["model"]["learning_rate"])
        # (a program from before category sets in a node list raises here)
        self.ens = lightgbm_io.from_lightgbm_text(self.text)
        self.mapper = lightgbm_io.threshold_bin_mapper(self.ens,
                                                       n_bins=s["n_bins"])
        self.what_ran = self._what_ran()
        if not self.rehearse and not all(ok for *_, ok in self.what_ran):
            for what, value, limit, _ in self.what_ran:
                print(f"score_leafwise_cat: {what}: {value} (limit {limit})",
                      file=sys.stderr)
            raise SystemExit(
                "score_leafwise_cat: no Pallas kernel serves this node-list "
                "model with category sets here, or the program does not say "
                "that one does. No rows drawn, no warm-up, no window, no "
                "result line.")
        weights = datagen_leafwise_cat.column_weights(
            assumed["cardinalities"], assumed["exponents"], s["model_seed"])
        self.codes = datagen_leafwise_cat.drawn_codes(
            s["rows"], weights, s["n_bins"], assumed["special_share"],
            self.seed)
        # Every value a column takes through the model's own mapper, once;
        # a row's bin is its code's.
        self.values = [datagen_leafwise_cat.raw_values(k, s["n_bins"])
                       for k in assumed["cardinalities"]]
        longest = max(len(v) for v in self.values)
        alphabet = np.stack([np.pad(v, (0, longest - len(v)),
                                    constant_values=-1.0)
                             for v in self.values], axis=1)
        bins = self.mapper.transform(alphabet)
        self.Xb = np.empty(self.codes.shape, np.uint8)
        for c in range(self.codes.shape[1]):
            self.Xb[:, c] = bins[:, c][self.codes[:, c]]
        named = {c: len(ids) for c, (ids, _) in
                 self.mapper.category_ids.items()}
        cols = sorted(named)
        unnamed = float((self.Xb[:, cols] == np.asarray(
            [named[c] for c in cols], np.uint8)).mean())
        print(f"score_leafwise_cat: ids the model names a column {named}; "
              "share of the category columns' cells in the bin of every "
              f"other value {unnamed:.4f}", flush=True)

    def raw_rows(self, idx: np.ndarray) -> np.ndarray:
        """The raw float rows `idx`, from their codes."""
        return np.stack([v[self.codes[idx, c]]
                         for c, v in enumerate(self.values)], axis=1)

    # ------------------------------------------------------------------ #

    def check(self, outputs: list, warm_up) -> list:
        if not outputs:
            return []
        s, lim = self.shapes, self.limits
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
        X = self.raw_rows(idx)
        moved = int((self.mapper.transform(X) != self.Xb[idx]).sum())
        checks.append(("sampled cells whose bin under the mapper's "
                       "transform of the raw row is not the bin the call "
                       "took", moved, 0, moved == 0))
        if self.patch:
            return checks + self._controls(outputs, idx, X)
        visited: list = []
        want, facts = reference_leafwise_cat.raw_scores(self.text, X,
                                                        visited)
        checks.append(self._gap_line(outputs, idx, want, "reference"))
        share = float(np.concatenate(visited).mean())
        checks.append((f"share of the ensemble's {facts['leaves']} leaves "
                       "that the sample reaches", share,
                       f">= {lim['leaf_share_min']}",
                       bool(share >= lim["leaf_share_min"])))
        checks.append(("nodes on the deepest path a sampled row takes",
                       facts["deepest"], f"> {lim['deep_leaf_min']}",
                       bool(facts["deepest"] > lim["deep_leaf_min"])))
        checks.append(("category-set nodes a sampled row passes a tree, on "
                       "average", facts["set_nodes"],
                       f">= {lim['set_nodes_min']}",
                       bool(facts["set_nodes"] >= lim["set_nodes_min"])))
        checks.append(("ordinal nodes a sampled row passes a tree, on "
                       "average", facts["ordinal_nodes"],
                       f">= {lim['ordinal_nodes_min']}",
                       bool(facts["ordinal_nodes"]
                            >= lim["ordinal_nodes_min"])))
        checks.append(("ids in the model's widest set", facts["widest_set"],
                       f"> {lim['widest_set_min']}",
                       bool(facts["widest_set"] > lim["widest_set_min"])))
        return checks + self.what_ran

    def _gap_line(self, outputs: list, idx, want, who: str) -> tuple:
        gap = max(float(np.max(np.abs(o[idx].astype(np.float64) - want)))
                  for o in outputs)
        return (f"scores of {len(idx)} sampled rows in each of "
                f"{len(outputs)} calls vs the float64 {who}'s walk of the "
                "text's bitsets and thresholds over raw values (|score| up "
                f"to {float(np.abs(want).max()):.2f}), max |gap|",
                gap, self.limits["score_atol"],
                bool(gap <= self.limits["score_atol"]))

    def _controls(self, outputs: list, idx, X) -> list:
        """A control run's lines: the calls' scores against the reference
        with ONE thing wrong, a line a control (`patched_table` names one,
        or "every"). Each has to FAIL."""
        names = reference_leafwise_cat.CONTROLS if self.patch == "every" \
            else (self.patch,)
        return [self._gap_line(
            outputs, idx, reference_leafwise_cat.raw_scores(
                self.text, X, control=name, seed=self.seed)[0],
            f"CONTROL {name}") for name in names]

    def _what_ran(self) -> list:
        """Which scoring program serves the model, asked BEFORE the first
        row is drawn: the program's own record, the `ddt:predict:ensemble`
        span of the model's build (`node_list` 1: the path-matrix form;
        `category_sets` 1: the set test is the program's), and on the chip
        `score.Job`'s question too, whether the lowered program carries a
        compiled Pallas kernel. Nothing about the kernel's tiling."""
        from ddt_tpu.backends import get_backend
        from ddt_tpu.telemetry.annotations import recent_spans

        get_backend(self.cfg)._predict_fn(self.ens)     # builds the model
        built = [sp for sp in recent_spans()
                 if sp["name"] == "ddt:predict:ensemble"]
        counts = built[-1]["counts"] if built else {}
        print(f"score_leafwise_cat: ddt:predict:ensemble {counts}",
              flush=True)
        said = {k: counts.get(k) for k in SAID}
        return score.Job._what_ran(self) + [
            ("the program's record says a node-list form serves the "
             "category sets (node_list 1, category_sets 1)", said, True,
             all(said[k] == 1 for k in SAID))]
