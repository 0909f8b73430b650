"""The benchmark's own tests (`benchmark/tests`), collected into tier-1.

They guard what the driver's chip check reads: the reference, the `correct`
rule, the op counts, and the trace readers, which match names THIS program
prints (`jit_predict_raw_effective`, `%ddt_predict_traverse`, the
`ddt:predict:*` spans). Nothing under `benchmark/` is edited for it: the
path set-up is what `benchmark/tests/conftest.py` does for `pytest
benchmark/tests`, and each test keeps its own name behind its module's.

Two of those tests pin POSITIONS in `BENCHMARK.json` (`PINNED`, below), and
the driver takes a later PR's entries only at the END of their lists, so no
PR after them can satisfy both. Only a `benchmark` PR may edit them. Here
they run whole against the manifest with every later entry moved BEFORE the
ones they pin: `benchmark/run.py` reads those lists by membership alone.
One of them also pins the XGBoost cell as the ONLY reader of
`score_link_ms`; a later cell whose link is the device's too
(`LATER_ON_LINK`) is left out of that one list in the view the test gets.

One more pins a COUNT of the program's that a later PR took down
(`DENSE_TILES`, below): the Allstate cell's `path_mxu_tiles_per_tree` as 2 x
`select_k_blocks` + 4, every lane tile of the select reading every K-block,
18, where the model's node lanes are ordered by K-block since PR 56 and the
kernel asks 12. It runs whole against the program under DENSE spans, the
form it pins (what a model gets whose blocks do not split), and the cell's
model as the program really builds it is held to its counts right after.

And one pins a count that PR 58 took down (`MUX_SELECTS`, below): the
Covertype CatBoost cell's `resolve_selects_per_tree` as 441, seven
multiplexers of 63 selects, where vector leaves of depth 3 or more are looked
up by sublane gathers since PR 58 and the lookup costs 105 VALU operations a
(row, tree), 56 of them gathers. It runs whole against the program under the
MULTIPLEXER, the form it pins (what one column and vector leaves under depth 3
get), and the cell's model as the program really builds it is held to `[105,
56]` right after.
"""

import importlib
import json
import os
import sys

import pytest

BENCHMARK = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
MODULES = ("test_call_anatomy", "test_correct", "test_correct_mc",
           "test_correct_routed", "test_correct_leafwise",
           "test_correct_leafwise_nan", "test_correct_oblivious",
           "test_correct_forest", "test_correct_xgb",
           "test_correct_leafwise_cat", "test_correct_oblivious_mc",
           "test_opcount",
           "test_tracefile", "test_device_stage_ms", "test_host_account")

sys.path[:0] = [os.path.join(BENCHMARK, "tests"), BENCHMARK]
pytest.register_assert_rewrite(*MODULES)
for _mod in map(importlib.import_module, MODULES):
    for _name, _obj in vars(_mod).items():
        if _name.startswith("test_"):       # two modules share test names
            globals()[f"{_mod.__name__}__{_name[5:]}"] = _obj
        elif callable(_obj) and not _name.startswith("_"):
            globals().setdefault(_name, _obj)       # fixtures, helpers

XGB_CELL = "covtype-xgb-d16-score-1chip"
# The cells that joined `score_link_ms` after the test that pins the XGBoost
# cell ALONE on it (PR 57's: an oblivious model's link is the device's too);
# `benchmark/tests/test_correct_oblivious_mc.py` holds the metric to it.
LATER_ON_LINK = {"covtype-catboost1000t-d6-mc-score-1chip"}
PINNED = ("test_correct_xgb__xgb_metrics_name_their_readers_and_the_cell_alone",
          "test_host_account__every_new_metric_file_has_its_entry_and_the_"
          "xgb_cell_is_left_out")


def _as_pinned(manifest):
    """The manifest in the order `PINNED` wants: the XGBoost cell the last
    name of each per-layer list it is on, PR 52's five entries the last of
    `per_layer`. Nothing is added, and nothing dropped but `LATER_ON_LINK`
    from the one list on which the XGBoost cell is pinned ALONE."""
    if "per_layer" not in manifest:
        return manifest
    pr52 = sys.modules["test_host_account"]
    last = set(pr52.NEW) | {n + ".routed" for n in pr52.ROUTED}
    manifest["per_layer"].sort(key=lambda m: m["name"] in last)  # stable
    for m in manifest["per_layer"]:
        m.get("workloads", []).sort(key=XGB_CELL.__eq__)
        if m["name"] == "score_link_ms":
            m["workloads"] = [w for w in m["workloads"]
                              if w not in LATER_ON_LINK]
    return manifest


def _with_later_entries_first(test):
    def run(monkeypatch):
        load = json.load
        monkeypatch.setattr(json, "load", lambda f: _as_pinned(load(f)))
        test()
    return run


for _name in PINNED:
    globals()[_name] = _with_later_entries_first(globals()[_name])


def _last_call_said(*keys):
    """Those counts of the newest `ddt:predict` root span."""
    from ddt_tpu.telemetry.annotations import recent_spans

    counts = [sp for sp in recent_spans()
              if sp["name"] == "ddt:predict"][-1]["counts"]
    return [counts[k] for k in keys]


DENSE_TILES = "test_correct_leafwise_cat__cat_metrics_are_counted_by_name"


def _under_dense_set_spans(test):
    def run(leafwise_cat_job, monkeypatch):
        from ddt_tpu.backends import get_backend
        from ddt_tpu.models import tree

        # (the model's tables live in the backend's cache under the model's
        # token: emptied on both sides, so each form is built where asked)
        cache = get_backend(leafwise_cat_job.cfg)._predict_cache
        with monkeypatch.context() as m:
            m.setattr(tree, "choose_set_spans", lambda *a: None)
            cache.clear()
            test(leafwise_cat_job)
        cache.clear()
        leafwise_cat_job.one_job()
        assert _last_call_said(
            "path_mxu_tiles_per_tree", "catset_mxu_tiles_per_tree",
            "select_mxu_tiles", "select_k_blocks") == [12, 7, 8, 7]
    return run


globals()[DENSE_TILES] = _under_dense_set_spans(globals()[DENSE_TILES])


MUX_SELECTS = "test_correct_oblivious_mc__mc_metrics_are_counted_by_name"


def _under_the_multiplexer(test):
    def run(oblivious_mc_job, monkeypatch):
        import jax

        from ddt_tpu.backends import get_backend
        from ddt_tpu.ops import predict_oblivious

        # (the model's plan lives in the backend's cache under the model's
        # token and its traced kernel in jit's: emptied on both sides, so
        # each form is built and traced where asked)
        cache = get_backend(oblivious_mc_job.cfg)._predict_cache
        with monkeypatch.context() as m:
            m.setattr(predict_oblivious, "_gathered", lambda *a: False)
            cache.clear()
            jax.clear_caches()
            test(oblivious_mc_job)
            muxed = oblivious_mc_job.one_job()
        cache.clear()
        jax.clear_caches()
        got = oblivious_mc_job.one_job()
        assert _last_call_said("resolve_selects_per_tree",
                               "resolve_gathers_per_tree") == [105, 56]
        # the gathered lookup picks the multiplexer's float32 values and
        # sums them in its order: the same probabilities, to the bit
        assert (got == muxed).all() and (got == oblivious_mc_job.sound).all()
    return run


globals()[MUX_SELECTS] = _under_the_multiplexer(globals()[MUX_SELECTS])
