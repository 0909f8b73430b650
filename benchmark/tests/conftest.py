"""Run by hand, from the repo's root: `pytest benchmark/tests` (the slow ones
drive whole rehearsal runs on the CPU: `-m "not slow"` leaves them out)."""

import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: drives a whole rehearsal run")
