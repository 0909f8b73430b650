"""Test harness config.

Tests run on CPUs. Distributed tests run single-process multi-device
(SURVEY.md §4 "Distributed without a cluster"): 8 virtual XLA CPU devices
via --xla_force_host_platform_device_count, which is read when the CPU
client is first instantiated, so it is set before jax is imported. The
platform is forced through jax.config as well as left to JAX_PLATFORMS,
so the suite cannot open a chip even where the environment names one. On
the chip the program runs through the chip tool: `python chip_smoke.py`.
"""

import os
import sys

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# The suite COUNTS compiles (telemetry's jit_compiles / recompile
# counters), and entry points under test (cli.main) switch the persistent
# compile cache on for the whole process: with it live, a count depends on
# what an earlier test — or an earlier run of the suite — left on disk.
# Off for the suite; tests/test_chip_smoke.py checks the cache's placement
# in subprocesses of its own.
jax.config.update("jax_enable_compilation_cache", False)
assert jax.default_backend() == "cpu", jax.default_backend()
assert len(jax.devices()) == 8, jax.devices()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# The suite's bit-identity contracts (native == NumPy oracle, CPU == TPU
# ensembles, N == 1 partitions, streamed == in-memory) assume the native
# kernels' SERIAL summation order: at OpenMP team sizes > 1 the histogram
# reduction reassociates float32 sums (~1e-6 — native/histogram.cpp), which
# can flip near-tie bf16 argmax splits in any module that trains through
# CPUDevice. Pin one thread for the whole suite regardless of the host's
# core count or OMP_NUM_THREADS; multi-thread kernel behavior has its own
# explicit coverage (test_native.py
# test_native_multithread_allclose_deterministic, which raises the team
# size inside its body and restores it).
# Import cost at collection: a fresh .so is one dlopen (~ms); after a
# .cpp edit this triggers the rebuild here instead of at first CPUDevice
# use — acceptable, the suite is normally run whole from the repo root.
# ImportError: no toolchain. OSError: ctypes.CDLL on a corrupt/wrong-arch/
# unresolvable library (e.g. a sanitizer build named via DDT_NATIVE_LIB
# without its runtime preloaded). Either way the suite still runs on the
# NumPy fallback kernels — which need no pin. Anything ELSE (say a
# TypeError in the ctypes setup) is a real binding bug: swallowing it here
# used to turn such bugs into nondeterministic bit-identity flakes with no
# visible cause (round-5 advisor finding), so it now propagates.
try:
    from ddt_tpu import native as _native

    _native.omp_set_threads(1)
except (ImportError, OSError) as _pin_err:
    import warnings

    warnings.warn(
        f"native thread-pin skipped ({type(_pin_err).__name__}: {_pin_err});"
        " suite runs on the NumPy fallback kernels",
        RuntimeWarning,
        stacklevel=1,
    )


@pytest.fixture
def budget(monkeypatch):
    """budget(groups, depth, F, C, optional): the traversal kernel's VMEM
    budget at which exactly `groups` tree groups fit a table block at that
    shape (`ops/predict_pallas.table_plan`), so that a few hundred trees
    already take several blocks: the BUDGET shrinks, never the program.
    The jit caches are dropped around the test: the budget is read while
    tracing."""
    from ddt_tpu.ops import predict_pallas as jpp

    def shrink(groups, depth, n_features, n_classes, optional=0):
        monkeypatch.setattr(jpp, "_VMEM_BUDGET_BYTES", jpp._vmem_bytes(
            groups, depth, n_features, n_classes, jpp._DEFAULT_TILE_R,
            optional))
    jax.clear_caches()
    yield shrink
    jax.clear_caches()


def pytest_configure(config):
    # tier-1 runs `-m "not slow"`; the benchmark's tests
    # (tests/test_benchmark_suite.py) carry the one such mark.
    config.addinivalue_line("markers", "slow: drives a whole rehearsal run")
