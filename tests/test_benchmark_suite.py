"""The benchmark's own tests (`benchmark/tests`), collected into tier-1.

They guard what the driver's chip check reads: the reference, the `correct`
rule, the op counts, and the trace readers, which match names THIS program
prints (`jit_predict_raw_effective`, `%ddt_predict_traverse`, the
`ddt:predict:*` spans). Nothing under `benchmark/` is edited for it: the
path set-up is what `benchmark/tests/conftest.py` does for `pytest
benchmark/tests`, and each test keeps its own name behind its module's.
"""

import importlib
import os
import sys

import pytest

BENCHMARK = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
MODULES = ("test_call_anatomy", "test_correct", "test_correct_mc",
           "test_correct_routed", "test_correct_leafwise",
           "test_correct_leafwise_nan", "test_correct_oblivious",
           "test_correct_forest", "test_correct_xgb",
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
