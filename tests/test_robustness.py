"""The chaos matrix (docs/ROBUSTNESS.md): fault injection, retry seams,
checkpoint hardening, degrade ladder, straggler watchdog — plus the
zero-overhead guard that proves a plan-less run never touches any of it.

The recovery bar everywhere is BIT-IDENTITY: training is deterministic
given binned data, so a fault that the robustness layer absorbs must
leave the final ensemble exactly equal to an undisturbed run's."""

import json
import os

import numpy as np
import pytest

from ddt_tpu import api
from ddt_tpu.config import TrainConfig
from ddt_tpu.models.tree import TreeEnsemble, empty_ensemble
from ddt_tpu.robustness import faultplan, set_fault_sink
from ddt_tpu.robustness.watchdog import StragglerWatchdog
from ddt_tpu.streaming import fit_streaming
from ddt_tpu.telemetry.events import RunLog
from ddt_tpu.utils import checkpoint, retry


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    """Every test starts and ends with no active plan and no sink — a
    leaked activation would silently fault unrelated tests."""
    faultplan.deactivate(None)
    set_fault_sink(None)
    yield
    faultplan.deactivate(None)
    set_fault_sink(None)


def _binary(rows=2000, n_bins=29, features=7, seed=5):
    rng = np.random.default_rng(seed)
    Xb = rng.integers(0, n_bins, size=(rows, features), dtype=np.uint8)
    y = (Xb[:, 0] + rng.integers(0, 6, size=rows) > 18).astype(np.float32)
    return Xb, y


def _chunks(Xb, y, n):
    bounds = np.linspace(0, len(y), n + 1).astype(np.int64)

    def f(c):
        return Xb[bounds[c]:bounds[c + 1]], y[bounds[c]:bounds[c + 1]]

    return f


def _assert_ens_equal(a, b):
    np.testing.assert_array_equal(a.feature, b.feature)
    np.testing.assert_array_equal(a.threshold_bin, b.threshold_bin)
    np.testing.assert_array_equal(a.is_leaf, b.is_leaf)
    np.testing.assert_array_equal(a.leaf_value, b.leaf_value)
    np.testing.assert_array_equal(a.split_gain, b.split_gain)


# ------------------------------------------------------------------ #
# retry engine (fake clock: deadline, jitter bounds, event emission)
# ------------------------------------------------------------------ #
class FakeClock:
    def __init__(self):
        self.t = 0.0
        self.sleeps = []

    def clock(self):
        return self.t

    def sleep(self, s):
        self.sleeps.append(s)
        self.t += s


def test_retry_succeeds_after_transient_failures_and_emits_events():
    rl = RunLog()
    set_fault_sink(rl)
    clk = FakeClock()
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise IOError(f"blip {calls['n']}")
        return "ok"

    out = retry.retry_call(flaky, seam="test.seam",
                           policy=retry.RetryPolicy(attempts=4, base_s=1.0,
                                                    multiplier=2.0,
                                                    jitter=0.5,
                                                    deadline_s=100.0),
                           clock=clk.clock, sleep=clk.sleep)
    assert out == "ok" and calls["n"] == 3
    faults = rl.events("fault")
    assert [e["kind"] for e in faults] == ["retry", "retry"]
    assert faults[0]["seam"] == "test.seam"
    assert faults[0]["attempt"] == 1 and faults[1]["attempt"] == 2
    assert faults[0]["error"] == "OSError"   # IOError is OSError


def test_retry_jitter_bounds_and_backoff_growth():
    pol = retry.RetryPolicy(attempts=6, base_s=1.0, multiplier=2.0,
                            jitter=0.5, deadline_s=1e9)
    for seed in range(10):
        clk = FakeClock()
        n = {"v": 0}

        def always_fail():
            n["v"] += 1
            raise IOError("x")

        with pytest.raises(IOError):
            retry.retry_call(always_fail, seam="jitter.test", policy=pol,
                             clock=clk.clock, sleep=clk.sleep,
                             rng=__import__("random").Random(seed))
        assert n["v"] == 6
        assert len(clk.sleeps) == 5
        for k, s in enumerate(clk.sleeps):
            full = pol.base_s * pol.multiplier ** k
            assert full * (1 - pol.jitter) <= s <= full, (k, s)


def test_retry_deadline_stops_before_overrunning():
    rl = RunLog()
    set_fault_sink(rl)
    clk = FakeClock()

    def always_fail():
        raise IOError("x")

    pol = retry.RetryPolicy(attempts=100, base_s=1.0, multiplier=2.0,
                            jitter=0.0, deadline_s=10.0)
    with pytest.raises(IOError):
        retry.retry_call(always_fail, seam="deadline.test", policy=pol,
                         clock=clk.clock, sleep=clk.sleep)
    # 1 + 2 + 4 = 7 slept; the next 8s sleep would pass 10s — refused.
    assert clk.t <= pol.deadline_s
    kinds = [e["kind"] for e in rl.events("fault")]
    assert kinds[-1] == "retry_deadline"


def test_retry_never_absorbs_non_transient():
    calls = {"n": 0}

    def boom():
        calls["n"] += 1
        raise ValueError("logic bug")

    with pytest.raises(ValueError):
        retry.retry_call(boom, seam="typed.test")
    assert calls["n"] == 1          # no second attempt


def test_retry_exhausted_emits_and_raises():
    rl = RunLog()
    set_fault_sink(rl)
    clk = FakeClock()
    with pytest.raises(IOError):
        retry.retry_call(
            lambda: (_ for _ in ()).throw(IOError("down")),
            seam="exhaust.test",
            policy=retry.RetryPolicy(attempts=3, base_s=0.01,
                                     deadline_s=100.0),
            clock=clk.clock, sleep=clk.sleep)
    kinds = [e["kind"] for e in rl.events("fault")]
    assert kinds == ["retry", "retry", "retry", "retry_exhausted"]


def test_is_transient_classification():
    assert retry.is_transient(IOError("x"))
    assert retry.is_transient(TimeoutError("x"))
    assert retry.is_transient(RuntimeError("UNAVAILABLE: connection reset"))
    assert retry.is_transient(faultplan.InjectedTransient("d2h"))
    assert not retry.is_transient(ValueError("x"))
    assert not retry.is_transient(faultplan.InjectedCrash("kill"))
    assert not retry.is_transient(
        faultplan.InjectedResourceExhausted("hist"))
    # Permanent filesystem errors fail identically on attempt 2 — a
    # mis-named chunk file must surface immediately, not after a full
    # backoff budget dressed up as transient-fault recovery.
    for exc in (FileNotFoundError(2, "no such file"),
                PermissionError(13, "denied"),
                IsADirectoryError(21, "is a dir"),
                NotADirectoryError(20, "not a dir")):
        assert not retry.is_transient(exc), exc
    # ...but an OSError with no errno (or a transient one) still retries.
    assert retry.is_transient(OSError("nfs blip"))


# ------------------------------------------------------------------ #
# fault plan mechanics
# ------------------------------------------------------------------ #
def test_fault_plan_parse_validation():
    with pytest.raises(ValueError, match="unknown site"):
        faultplan.load_plan({"faults": [{"site": "nope"}]})
    with pytest.raises(ValueError, match="unknown keys"):
        faultplan.load_plan(
            {"faults": [{"site": "hist.build", "wat": 1}]})
    with pytest.raises(ValueError, match="unknown error kind"):
        faultplan.load_plan(
            {"faults": [{"site": "hist.build", "error": "nope"}]})
    with pytest.raises(ValueError, match="'faults'"):
        faultplan.load_plan({"seed": 1})


def test_fault_plan_times_and_criteria(tmp_path):
    p = tmp_path / "plan.json"
    p.write_text(json.dumps({"faults": [
        {"site": "stream.chunk_read", "chunk": 2, "times": 2},
    ]}))
    plan = faultplan.load_plan(str(p))
    prev = faultplan.activate(plan)
    try:
        faultplan.inject("stream.chunk_read", chunk=1)   # no match
        with pytest.raises(faultplan.InjectedIOError):
            faultplan.inject("stream.chunk_read", chunk=2)
        with pytest.raises(faultplan.InjectedIOError):
            faultplan.inject("stream.chunk_read", chunk=2)
        faultplan.inject("stream.chunk_read", chunk=2)   # budget spent
    finally:
        faultplan.deactivate(prev)
    assert len(plan.fired_log) == 2


def test_fault_plan_injected_events_reach_sink():
    rl = RunLog()
    set_fault_sink(rl)
    prev = faultplan.activate(faultplan.load_plan(
        {"faults": [{"site": "fetch_tree"}]}))
    try:
        with pytest.raises(faultplan.InjectedTransient):
            faultplan.inject("fetch_tree")
    finally:
        faultplan.deactivate(prev)
    ev = rl.events("fault")
    assert len(ev) == 1 and ev[0]["kind"] == "injected"
    assert ev[0]["site"] == "fetch_tree"


def test_straggler_perturbation_is_query_not_raise():
    prev = faultplan.activate(faultplan.load_plan({"faults": [
        {"site": "straggler", "device": 1, "delay_ms": 250.0,
         "rounds": [2, 3], "times": 10},
    ]}))
    try:
        assert faultplan.perturb_ms("straggler", device=1, round=1) == 0.0
        assert faultplan.perturb_ms("straggler", device=0, round=2) == 0.0
        assert faultplan.perturb_ms("straggler", device=1, round=2) == 250.0
    finally:
        faultplan.deactivate(prev)
    assert faultplan.perturb_ms("straggler", device=1, round=2) == 0.0


# ------------------------------------------------------------------ #
# checkpoint hardening
# ------------------------------------------------------------------ #
def _mk_ens(cfg, F=7, rounds_filled=0, seed=0):
    ens = empty_ensemble(cfg.n_trees, cfg.max_depth, F, cfg.learning_rate,
                         0.0, cfg.loss, cfg.n_classes, n_bins=cfg.n_bins)
    rng = np.random.default_rng(seed)
    k = rounds_filled
    if k:
        ens.feature[:k] = rng.integers(0, F, ens.feature[:k].shape)
        ens.leaf_value[:k] = rng.random(ens.leaf_value[:k].shape,
                                        dtype=np.float32)
    return ens


def test_torn_pair_falls_back_to_last_good_history(tmp_path):
    cfg = TrainConfig(n_trees=10, max_depth=3, n_bins=29, backend="cpu")
    ck = str(tmp_path / "ck")
    e2 = _mk_ens(cfg, rounds_filled=2, seed=1)
    checkpoint.save_checkpoint(ck, e2, cfg, 2)
    # Simulate the crash-between-replaces: a NEWER ensemble lands but the
    # cursor never follows (the exact torn state ckpt.save.between
    # injects end-to-end in scripts/chaos_smoke.py).
    e4 = _mk_ens(cfg, rounds_filled=4, seed=2)
    prev = faultplan.activate(faultplan.load_plan(
        {"faults": [{"site": "ckpt.save.between", "round": 4}]}))
    try:
        with pytest.raises(faultplan.InjectedCrash):
            checkpoint.save_checkpoint(ck, e4, cfg, 4)
    finally:
        faultplan.deactivate(prev)
    rl = RunLog()
    fresh = _mk_ens(cfg)
    rounds = checkpoint.try_resume(ck, fresh, cfg, run_log=rl)
    assert rounds == 2
    np.testing.assert_array_equal(fresh.feature[:2], e2.feature[:2])
    kinds = [e["kind"] for e in rl.events("fault")]
    assert "checkpoint_corrupt" in kinds
    assert "checkpoint_fallback" in kinds


def test_corrupt_cursor_json_is_no_checkpoint_not_a_crash(tmp_path):
    cfg = TrainConfig(n_trees=10, max_depth=3, n_bins=29, backend="cpu")
    ck = str(tmp_path / "ck")
    os.makedirs(ck)
    # A torn/truncated cursor next to no ensemble and no history.
    with open(os.path.join(ck, checkpoint.CURSOR_FILE), "w") as f:
        f.write('{"completed_rounds": 2, "conf')     # truncated JSON
    with open(os.path.join(ck, checkpoint.CKPT_FILE), "wb") as f:
        f.write(b"PK\x03\x04 garbage npz")
    rl = RunLog()
    fresh = _mk_ens(cfg)
    assert checkpoint.try_resume(ck, fresh, cfg, run_log=rl) == 0
    kinds = [e["kind"] for e in rl.events("fault")]
    assert "checkpoint_corrupt" in kinds
    assert "checkpoint_unrecoverable" in kinds


def test_unreadable_npz_with_valid_cursor_falls_back(tmp_path):
    cfg = TrainConfig(n_trees=10, max_depth=3, n_bins=29, backend="cpu")
    ck = str(tmp_path / "ck")
    e2 = _mk_ens(cfg, rounds_filled=2, seed=3)
    checkpoint.save_checkpoint(ck, e2, cfg, 2)
    e4 = _mk_ens(cfg, rounds_filled=4, seed=4)
    checkpoint.save_checkpoint(ck, e4, cfg, 4)
    # Replace the TOP-LEVEL ensemble with garbage (a torn rewrite is a
    # NEW file, so the history hard links keep the good inode; in-place
    # bit rot would corrupt the shared inode too and fall back one more
    # round — still recovered, one save older).
    garbage = os.path.join(ck, "garbage.bin")
    with open(garbage, "wb") as f:
        f.write(b"PK\x03\x04 torn npz")
    os.replace(garbage, os.path.join(ck, checkpoint.CKPT_FILE))
    fresh = _mk_ens(cfg)
    rl = RunLog()
    # History ckpt-000004 links the PRE-corruption inode, so the newest
    # history pair still validates and resume loses nothing.
    assert checkpoint.try_resume(ck, fresh, cfg, run_log=rl) == 4
    np.testing.assert_array_equal(fresh.feature[:4], e4.feature[:4])
    assert "checkpoint_fallback" in [
        e["kind"] for e in rl.events("fault")]


def test_history_keeps_last_k(tmp_path):
    cfg = TrainConfig(n_trees=20, max_depth=3, n_bins=29, backend="cpu")
    ck = str(tmp_path / "ck")
    for r in (2, 4, 6, 8, 10):
        checkpoint.save_checkpoint(ck, _mk_ens(cfg, rounds_filled=r),
                                   cfg, r)
    hist = sorted(d for d in os.listdir(ck)
                  if d.startswith(checkpoint.HISTORY_PREFIX))
    assert hist == ["ckpt-000006", "ckpt-000008", "ckpt-000010"]


def test_old_format_cursor_without_digest_still_resumes(tmp_path):
    """Pre-hardening checkpoints (no digest, no history) stay resumable."""
    cfg = TrainConfig(n_trees=10, max_depth=3, n_bins=29, backend="cpu")
    ck = str(tmp_path / "ck")
    os.makedirs(ck)
    e3 = _mk_ens(cfg, rounds_filled=3, seed=5)
    np.savez_compressed(os.path.join(ck, checkpoint.CKPT_FILE + ".tmp"),
                        **e3.to_dict())
    os.replace(os.path.join(ck, checkpoint.CKPT_FILE + ".tmp.npz")
               if os.path.exists(
                   os.path.join(ck, checkpoint.CKPT_FILE + ".tmp.npz"))
               else os.path.join(ck, checkpoint.CKPT_FILE + ".tmp"),
               os.path.join(ck, checkpoint.CKPT_FILE))
    with open(os.path.join(ck, checkpoint.CURSOR_FILE), "w") as f:
        json.dump({"completed_rounds": 3,
                   "config": checkpoint._cfg_fingerprint(cfg)}, f)
    fresh = _mk_ens(cfg)
    assert checkpoint.try_resume(ck, fresh, cfg) == 3
    np.testing.assert_array_equal(fresh.feature[:3], e3.feature[:3])


def test_incompatible_config_still_raises(tmp_path):
    cfg = TrainConfig(n_trees=10, max_depth=3, n_bins=29, backend="cpu")
    ck = str(tmp_path / "ck")
    checkpoint.save_checkpoint(ck, _mk_ens(cfg, rounds_filled=2), cfg, 2)
    other = cfg.replace(learning_rate=0.5)
    with pytest.raises(ValueError, match="incompatible config"):
        checkpoint.try_resume(ck, _mk_ens(other), other)


def test_robustness_knobs_are_resume_compatible(tmp_path):
    """A run that crashed UNDER a fault plan resumes WITHOUT one — the
    robustness fields are system knobs outside the fingerprint."""
    cfg = TrainConfig(n_trees=10, max_depth=3, n_bins=29, backend="cpu",
                      fault_plan="/tmp/plan.json",
                      straggler_repartition=True)
    ck = str(tmp_path / "ck")
    checkpoint.save_checkpoint(ck, _mk_ens(cfg, rounds_filled=2), cfg, 2)
    clean = TrainConfig(n_trees=10, max_depth=3, n_bins=29, backend="cpu")
    assert checkpoint.try_resume(ck, _mk_ens(clean), clean) == 2


# ------------------------------------------------------------------ #
# end-to-end chaos: injected faults -> bit-identical ensembles
# ------------------------------------------------------------------ #
def test_injected_stream_read_fault_is_bit_exact():
    Xb, y = _binary()
    n_chunks = 4
    cfg = TrainConfig(n_trees=5, max_depth=3, n_bins=29, backend="tpu",
                      seed=2)
    clean = fit_streaming(_chunks(Xb, y, n_chunks), n_chunks, cfg)
    rl = RunLog()
    prev = faultplan.activate(faultplan.load_plan({"faults": [
        {"site": "stream.chunk_read", "chunk": 1, "times": 1},
        {"site": "stream.chunk_read", "chunk": 3, "times": 1},
    ]}))
    try:
        chaotic = fit_streaming(_chunks(Xb, y, n_chunks), n_chunks, cfg,
                                run_log=rl)
    finally:
        faultplan.deactivate(prev)
    _assert_ens_equal(clean, chaotic)
    kinds = [e["kind"] for e in rl.events("fault")]
    assert kinds.count("injected") == 2
    assert "retry" in kinds
    counters = rl.events("counters")[0]
    assert counters["fault_retries"] >= 2


def test_injected_fetch_tree_fault_is_bit_exact():
    Xb, y = _binary(1200)
    cfg = TrainConfig(n_trees=4, max_depth=3, n_bins=29, backend="tpu",
                      seed=2)
    # profile=True forces the granular path, whose fetch_tree seam the
    # plan targets (the fused path fetches whole blocks).
    ref = api.train(Xb, y, cfg, binned=True, profile=True)
    prev = faultplan.activate(faultplan.load_plan(
        {"faults": [{"site": "fetch_tree", "times": 2}]}))
    try:
        chaotic = api.train(Xb, y, cfg, binned=True, profile=True)
    finally:
        faultplan.deactivate(prev)
    _assert_ens_equal(ref.ensemble, chaotic.ensemble)


def test_granular_fit_without_checkpointing_accepts_every_0():
    """checkpoint_every=0 with no checkpoint_dir was valid before the
    watchdog's cadence check landed on the granular loop — the modulo
    must not resurrect it as a ZeroDivisionError."""
    from ddt_tpu.backends import get_backend
    from ddt_tpu.driver import Driver

    Xb, y = _binary(600)
    cfg = TrainConfig(n_trees=2, max_depth=3, n_bins=29, backend="tpu")
    be = get_backend(cfg)
    ens = Driver(be, cfg, log_every=10**9, checkpoint_dir=None,
                 checkpoint_every=0, profile=True).fit(Xb, y)
    assert ens.feature.shape[0] == cfg.n_trees


def test_cfg_fault_plan_is_activated_by_the_trainer(tmp_path):
    Xb, y = _binary(900)
    p = tmp_path / "plan.json"
    p.write_text(json.dumps(
        {"faults": [{"site": "fetch_tree", "times": 1}]}))
    rl = RunLog()
    cfg = TrainConfig(n_trees=3, max_depth=3, n_bins=29, backend="tpu",
                      fault_plan=str(p))
    res = api.train(Xb, y, cfg, binned=True, profile=True, run_log=rl)
    assert res.ensemble.n_trees == 3
    kinds = [e["kind"] for e in rl.events("fault")]
    assert "injected" in kinds and "retry" in kinds
    assert faultplan.active_plan() is None     # deactivated on exit


def test_multihost_init_timeout_retries(monkeypatch):
    from ddt_tpu.parallel import mesh as mesh_lib

    calls = {"n": 0}

    class FakeDistributed:
        @staticmethod
        def initialize(**kw):
            calls["n"] += 1

    monkeypatch.setattr(mesh_lib.jax, "distributed", FakeDistributed())
    monkeypatch.setattr(mesh_lib, "_init_args", None)
    monkeypatch.setattr(retry.time, "sleep", lambda s: None)
    prev = faultplan.activate(faultplan.load_plan(
        {"faults": [{"site": "multihost.init", "times": 1}]}))
    try:
        mesh_lib.initialize_multihost("127.0.0.1:9999", 1, 0)
    finally:
        faultplan.deactivate(prev)
    assert calls["n"] == 1      # attempt 2 reached the real initialize
    monkeypatch.setattr(mesh_lib, "_init_args", None)


def test_hist_oom_degrade_ladder_is_value_identical():
    from ddt_tpu.backends.tpu import TPUDevice

    cfg = TrainConfig(n_trees=2, max_depth=3, n_bins=29, backend="tpu",
                      hist_impl="segment")
    be = TPUDevice(cfg)
    rng = np.random.default_rng(0)
    Xb = rng.integers(0, 29, size=(512, 5), dtype=np.uint8)
    g = rng.random(512, dtype=np.float32)
    h = rng.random(512, dtype=np.float32)
    ni = np.zeros(512, np.int32)
    data = be.upload(Xb)
    ref = np.asarray(be.build_histograms(data, g, h, ni, 1))
    be2 = TPUDevice(cfg)
    rl = RunLog()
    set_fault_sink(rl)
    prev = faultplan.activate(faultplan.load_plan(
        {"faults": [{"site": "hist.build", "times": 1}]}))
    try:
        out = np.asarray(be2.build_histograms(
            be2.upload(Xb), g, h, ni, 1))
    finally:
        faultplan.deactivate(prev)
    # segment -> (ladder) -> matmul: value-identical here (integer-free
    # f32 sums at this scale agree bitwise on CPU XLA is NOT guaranteed,
    # so compare to the MATMUL reference instead of bitwise-to-segment).
    from ddt_tpu.ops import histogram as hist_ops
    import jax.numpy as jnp

    want = np.asarray(hist_ops.build_histograms_matmul(
        jnp.asarray(Xb), jnp.asarray(g), jnp.asarray(h),
        jnp.asarray(ni), 1, 29))
    np.testing.assert_allclose(out, want, rtol=0, atol=0)
    assert be2._hist_degrade == 1            # sticky
    ev = rl.events("fault")
    assert [e["kind"] for e in ev if e["kind"] == "hist_oom_degrade"]
    assert ref.shape == out.shape


# ------------------------------------------------------------------ #
# straggler watchdog + repartition
# ------------------------------------------------------------------ #
def test_watchdog_unit_detection_and_latch():
    wd = StragglerWatchdog(threshold=1.5, patience=2)
    balanced = {0: {"grow": 100.0}, 1: {"grow": 110.0}, 2: {"grow": 95.0}}
    skewed = {0: {"grow": 100.0}, 1: {"grow": 400.0}, 2: {"grow": 95.0}}
    assert wd.observe_round(0, balanced) is None
    obs = wd.observe_round(1, skewed)
    assert obs is not None and obs.device == 1 and obs.streak == 1
    assert not wd.pending_repartition
    obs2 = wd.observe_round(2, skewed)
    assert obs2.streak == 2 and wd.pending_repartition
    wd.repartition_done()
    assert not wd.pending_repartition
    # A DIFFERENT straggler resets the streak.
    other = {0: {"grow": 500.0}, 1: {"grow": 100.0}, 2: {"grow": 95.0}}
    assert wd.observe_round(3, skewed).streak == 1
    assert wd.observe_round(4, other).streak == 1


def test_injected_straggler_detection_and_repartition_bit_exact(tmp_path):
    """2-partition mesh run: injected straggler trips the watchdog, the
    repartition flag rotates shards at the checkpoint cadence, and the
    final ensemble is bit-identical to the undisturbed run (shard
    contents never move — only their device assignment)."""
    Xb, y = _binary(1600)
    # Default skew threshold: the watchdog's median excludes the
    # candidate lane, so 2.0 is reachable even with two lanes.
    base = TrainConfig(n_trees=6, max_depth=3, n_bins=29, backend="tpu",
                       n_partitions=2, seed=4,
                       straggler_repartition=True)
    # The flag forces the granular path, so the undisturbed reference
    # runs granular too (the fused path differs by documented
    # FMA-contraction ULPs — driver.py's resume-score seam).
    ref = api.train(Xb, y, base, binned=True)
    rl = RunLog()
    cfg = base
    prev = faultplan.activate(faultplan.load_plan({"faults": [
        {"site": "straggler", "device": 1, "delay_ms": 600000.0,
         "rounds": [1, 6], "times": 6},
    ]}))
    try:
        # checkpoint_every=2 -> the repartition boundary arrives fast.
        chaotic = api.train(Xb, y, cfg, binned=True, run_log=rl,
                            checkpoint_dir=str(tmp_path / "ck"),
                            checkpoint_every=2)
    finally:
        faultplan.deactivate(prev)
    _assert_ens_equal(ref.ensemble, chaotic.ensemble)
    kinds = [e["kind"] for e in rl.events("fault")]
    assert "straggler_detected" in kinds
    assert "repartition" in kinds


def test_partition_phases_carry_injected_straggler_lane():
    Xb, y = _binary(1600)
    cfg = TrainConfig(n_trees=3, max_depth=3, n_bins=29, backend="tpu",
                      n_partitions=2, seed=4)
    rl = RunLog()
    prev = faultplan.activate(faultplan.load_plan({"faults": [
        {"site": "straggler", "device": 0, "delay_ms": 123.0,
         "times": 1},
    ]}))
    try:
        api.train(Xb, y, cfg, binned=True, run_log=rl)
    finally:
        faultplan.deactivate(prev)
    pp = rl.events("partition_phases")
    assert pp
    lanes = {p["device"]: p["phases"] for p in pp[0]["partitions"]}
    assert lanes[0].get("straggler_injected") == 123.0


# ------------------------------------------------------------------ #
# zero-overhead guard (the telemetry disabled-path bar)
# ------------------------------------------------------------------ #
def test_no_plan_no_overhead_guard(monkeypatch, tmp_path):
    """With no fault plan active, the injection/retry layer must be a
    module-global read: firing, backoff, and straggler perturbation all
    explode if touched — training (checkpointed, so every seam runs)
    must complete anyway."""
    from ddt_tpu.utils import retry as retry_mod

    def _boom(*a, **k):
        raise AssertionError("robustness slow path touched with no plan")

    monkeypatch.setattr(faultplan.FaultPlan, "fire", _boom)
    monkeypatch.setattr(faultplan.FaultPlan, "delay_ms", _boom)
    monkeypatch.setattr(retry_mod, "_backoff_loop", _boom)
    monkeypatch.setattr(retry_mod.time, "sleep", _boom)
    Xb, y = _binary(900)
    cfg = TrainConfig(n_trees=4, max_depth=3, n_bins=29, backend="tpu")
    res = api.train(Xb, y, cfg, binned=True,
                    checkpoint_dir=str(tmp_path / "ck"),
                    checkpoint_every=2)
    assert res.ensemble.n_trees == 4
    # The streaming path's wrapped chunk reads hold the same bar.
    ens = fit_streaming(_chunks(Xb, y, 3), 3,
                        TrainConfig(n_trees=2, max_depth=3, n_bins=29,
                                    backend="tpu"))
    assert ens.n_trees == 2


def test_atomic_save_model_and_ensemble(tmp_path):
    """api.save_model / TreeEnsemble.save leave no torn artifact and
    keep numpy's .npz suffixing semantics."""
    cfg = TrainConfig(n_trees=4, max_depth=3, n_bins=29, backend="cpu")
    ens = _mk_ens(cfg, rounds_filled=2, seed=7)
    p = str(tmp_path / "model.npz")
    api.save_model(p, ens)
    assert os.path.exists(p) and not os.path.exists(p + ".tmp.npz")
    loaded = api.load_model(p)
    np.testing.assert_array_equal(loaded.ensemble.feature, ens.feature)
    bare = str(tmp_path / "bare")
    ens.save(bare)
    assert os.path.exists(bare + ".npz")
    TreeEnsemble.load(bare + ".npz")
