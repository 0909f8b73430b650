"""Serving tier (ddt_tpu/serve/): coalescer correctness under
concurrency, hot-swap atomicity, SLO telemetry, and run-log
back-compat.

Everything runs in-process against the engine (the HTTP layer is a thin
adapter covered by scripts/serve_smoke.py); the CPU 'tpu' backend (XLA
CPU) scores for real. Timing-sensitive behavior is made deterministic
with thread barriers and generous admission windows — the tests assert
STRUCTURE (who got which rows, which model answered), never wall-clock.
"""

import threading

import numpy as np
import pytest

from ddt_tpu import api
from ddt_tpu.config import TrainConfig
from ddt_tpu.data import datasets
from ddt_tpu.serve.batcher import MicroBatcher, ShuttingDown
from ddt_tpu.serve.engine import (ServeEngine, bucket_for,
                                  default_buckets)
from ddt_tpu.telemetry import report as tele_report
from ddt_tpu.telemetry.events import RunLog, validate_event


@pytest.fixture(scope="module")
def trained():
    """Two small models (same shape, different seeds) + config + offline
    reference scores, shared module-wide (training is the slow part)."""
    X, y = datasets.synthetic_binary(3000, seed=5)
    kw = dict(n_trees=6, max_depth=3, n_bins=31, backend="tpu",
              log_every=10**9)
    res_a = api.train(X, y, **kw)
    # A genuinely different model version (seed alone changes nothing
    # without bagging): halving the learning rate moves every leaf.
    res_b = api.train(X, y, learning_rate=0.05, **kw)
    cfg = TrainConfig(backend="tpu", n_bins=31)
    ref = {
        "a": np.asarray(api.predict(res_a.ensemble, X, mapper=res_a.mapper,
                                    cfg=cfg)),
        "b": np.asarray(api.predict(res_b.ensemble, X, mapper=res_b.mapper,
                                    cfg=cfg)),
    }
    return dict(X=X, res_a=res_a, res_b=res_b, cfg=cfg, ref=ref)


def _bundle(res):
    return api.ModelBundle(ensemble=res.ensemble, mapper=res.mapper)


def _engine(trained, **kw):
    kw.setdefault("max_wait_ms", 25.0)      # deterministic coalescing
    kw.setdefault("max_batch", 64)
    return ServeEngine(_bundle(trained["res_a"]), trained["cfg"], **kw)


# --------------------------------------------------------------------- #
# buckets
# --------------------------------------------------------------------- #
def test_bucket_ladder():
    assert default_buckets(64) == (1, 2, 4, 8, 16, 32, 64)
    assert default_buckets(48) == (1, 2, 4, 8, 16, 32, 48)
    bs = default_buckets(64)
    assert bucket_for(1, bs) == 1
    assert bucket_for(3, bs) == 4
    assert bucket_for(64, bs) == 64
    assert bucket_for(999, bs) == 64        # oversize: largest bucket


# --------------------------------------------------------------------- #
# coalescer correctness under concurrent submitters
# --------------------------------------------------------------------- #
def test_concurrent_submitters_coalesce_and_keep_rows_straight(trained):
    """16 barrier-synchronized single-row submitters: every response is
    the offline answer FOR THAT ROW (no drops, no duplicates, no
    permutation), and the batcher provably coalesced >= 8 of them into
    one dispatch (the ISSUE 8 acceptance bar)."""
    eng = _engine(trained)
    try:
        X, ref = trained["X"], trained["ref"]["a"]
        n = 16
        barrier = threading.Barrier(n)
        got = [None] * n

        def worker(i):
            barrier.wait()
            got[i] = eng.predict(X[i:i + 1], timeout=60.0)[0]

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        np.testing.assert_allclose(np.array(got), ref[:n],
                                   rtol=1e-6, atol=1e-7)
        assert eng.stats.coalesce_max >= 8, eng.stats.snapshot()
    finally:
        eng.close()


def test_mixed_size_requests_slice_back_positionally(trained):
    """Concurrent requests of different row counts: each gets exactly
    its own block back (the scatter is positional, not shape-matched)."""
    eng = _engine(trained)
    try:
        X, ref = trained["X"], trained["ref"]["a"]
        spans = [(0, 1), (1, 8), (9, 3), (12, 5), (17, 1), (18, 16)]
        barrier = threading.Barrier(len(spans))
        got = [None] * len(spans)

        def worker(k, start, cnt):
            barrier.wait()
            got[k] = eng.predict(X[start:start + cnt], timeout=60.0)

        threads = [threading.Thread(target=worker, args=(k, s, c))
                   for k, (s, c) in enumerate(spans)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        for k, (s, c) in enumerate(spans):
            assert got[k].shape[0] == c
            np.testing.assert_allclose(got[k], ref[s:s + c],
                                       rtol=1e-6, atol=1e-7)
    finally:
        eng.close()


def test_raw_float_rows_bin_with_the_training_mapper(trained):
    """Float rows submitted to a mapper-carrying model score identically
    to the offline mapper path (binning happens under the serving
    model, per-dispatch)."""
    eng = _engine(trained)
    try:
        X, ref = trained["X"], trained["ref"]["a"]
        out = eng.predict(X[:7].astype(np.float32), timeout=60.0)
        np.testing.assert_allclose(out, ref[:7], rtol=1e-6, atol=1e-7)
    finally:
        eng.close()


def test_dispatch_errors_reach_the_waiter_not_the_thread(trained):
    """A request the model cannot score fails ITS OWN waiter with the
    cause; the dispatcher thread survives and keeps serving."""
    eng = ServeEngine(
        api.ModelBundle(ensemble=trained["res_a"].ensemble, mapper=None),
        trained["cfg"], max_wait_ms=5.0)
    try:
        with pytest.raises(ValueError, match="bin mapper"):
            # Float rows but no mapper on the bundle: transform refuses.
            eng.predict(np.zeros((1, eng._model.n_features), np.float32),
                        timeout=60.0)
        # The engine still serves binned requests afterwards.
        Xb = trained["res_a"].mapper.transform(trained["X"][:3])
        out = eng.predict(Xb, timeout=60.0)
        np.testing.assert_allclose(out, trained["ref"]["a"][:3],
                                   rtol=1e-6, atol=1e-7)
    finally:
        eng.close()


def test_submit_validation_and_shutdown(trained):
    eng = _engine(trained)
    with pytest.raises(ValueError, match="features"):
        eng.predict(np.zeros((1, 3), np.uint8))
    eng.close()
    with pytest.raises(ShuttingDown):
        eng.predict_async(np.zeros((1, eng._model.n_features), np.uint8))


def test_oversize_request_scores_on_pretraced_shapes(trained):
    """A request larger than max_batch dispatches solo but must STILL
    ride pre-traced bucket shapes (chunked scoring) — and return the
    offline answer for every row."""
    eng = _engine(trained, max_batch=8, max_wait_ms=1.0)
    try:
        X, ref = trained["X"], trained["ref"]["a"]
        out = eng.predict(X[:21], timeout=60.0)   # 21 > max_batch=8
        assert out.shape[0] == 21
        np.testing.assert_allclose(out, ref[:21], rtol=1e-6, atol=1e-7)
    finally:
        eng.close()


def test_dispatch_validates_width_per_request(trained):
    """A stale-width request (the submit-vs-dispatch swap race) fails
    ITS OWN waiter at dispatch time; a valid request sharing the
    admission window still gets its answer."""
    eng = _engine(trained)
    try:
        F = eng._model.n_features
        # Bypass submit-time validation — exactly what a hot swap to a
        # different-width model does to an already-queued request.
        bad = eng._batcher.submit(np.zeros((1, F + 2), np.uint8), 1)
        good = eng.predict_async(
            trained["res_a"].mapper.transform(trained["X"][:1]))
        with pytest.raises(ValueError, match="features"):
            bad.result(timeout=60.0)
        np.testing.assert_allclose(good.result(timeout=60.0),
                                   trained["ref"]["a"][:1],
                                   rtol=1e-6, atol=1e-7)
    finally:
        eng.close()


def test_batcher_respects_row_budget():
    """Unit-level: requests never split, batches never exceed max_batch
    rows (except a lone oversize request, which dispatches solo)."""
    batches = []
    done = threading.Event()

    def dispatch(batch, depth):
        batches.append([r.n for r in batch])
        for r in batch:
            r.set_result(np.zeros(r.n))
        if sum(len(b) for b in batches) >= 4:
            done.set()

    mb = MicroBatcher(dispatch, max_wait_ms=30.0, max_batch=4)
    reqs = [mb.submit(np.zeros((n, 2)), n) for n in (3, 3, 4, 9)]
    for r in reqs:
        r.result(timeout=30.0)
    mb.close()
    flat = [n for b in batches for n in b]
    assert flat == [3, 3, 4, 9]             # FIFO, nothing dropped
    for b in batches:
        assert sum(b) <= 4 or (len(b) == 1 and b[0] > 4)


# --------------------------------------------------------------------- #
# hot swap
# --------------------------------------------------------------------- #
def test_hot_swap_mid_flight_returns_old_or_new_never_a_mix(trained):
    """Requests hammer the engine while the model swaps A -> B
    mid-flight: zero failures, and every multi-row response matches
    model A's answer for the WHOLE block or model B's — never a blend
    (one model reference per micro-batch)."""
    eng = _engine(trained, max_wait_ms=2.0)
    try:
        X = trained["X"]
        ra, rb = trained["ref"]["a"], trained["ref"]["b"]
        stop = threading.Event()
        results, errors = [], []

        def hammer(tid):
            rng = np.random.default_rng(tid)
            while not stop.is_set():
                s = int(rng.integers(0, 100))
                c = int(rng.integers(1, 6))
                try:
                    out = eng.predict(X[s:s + c], timeout=60.0)
                    results.append((s, c, np.asarray(out)))
                except Exception as e:  # ddtlint: disable=broad-except — collected and asserted empty below
                    errors.append(repr(e))

        threads = [threading.Thread(target=hammer, args=(t,))
                   for t in range(4)]
        for t in threads:
            t.start()
        # let some A-era requests land, then swap, then more traffic
        import time as _time

        while len(results) < 20:
            _time.sleep(0.002)
        swap_info = eng.swap(_bundle(trained["res_b"]))
        while len(results) < 60:
            _time.sleep(0.002)
        stop.set()
        for t in threads:
            t.join(60)
        assert not errors, errors[:5]
        assert swap_info["old"] != swap_info["new"]

        n_b = 0
        for s, c, out in results:
            is_a = np.allclose(out, ra[s:s + c], rtol=1e-6, atol=1e-7)
            is_b = np.allclose(out, rb[s:s + c], rtol=1e-6, atol=1e-7)
            assert is_a or is_b, f"rows [{s}:{s + c}] match neither model"
            n_b += bool(is_b and not is_a)
        # Traffic after the swap exists, so SOME responses came from B.
        assert n_b > 0
        assert eng.model_token == swap_info["new"]
    finally:
        eng.close()


def test_swap_emits_counter_and_fault_event(trained):
    from ddt_tpu.telemetry import counters as tele_counters

    rl = RunLog()                            # ring-only
    eng = _engine(trained, run_log=rl)
    try:
        c0 = tele_counters.snapshot()
        eng.swap(_bundle(trained["res_b"]))
        assert tele_counters.delta(c0)["serve_hot_swaps"] == 1
        kinds = [e["kind"] for e in rl.events("fault")]
        assert "hot_swap" in kinds
    finally:
        eng.close()


# --------------------------------------------------------------------- #
# SLO telemetry + schema back-compat
# --------------------------------------------------------------------- #
def test_serve_latency_event_emits_validates_and_renders(trained, tmp_path):
    path = str(tmp_path / "serve.jsonl")
    eng = _engine(trained, run_log=path)
    try:
        for i in range(10):
            eng.predict(trained["X"][i:i + 1], timeout=60.0)
        payload = eng.emit_latency()
        assert payload["requests"] == 10
        assert payload["p50_ms"] <= payload["p99_ms"] <= payload["p999_ms"]
    finally:
        eng.close()
    events = tele_report.read_events(path)
    sl = [e for e in events if e["event"] == "serve_latency"]
    assert len(sl) == 1                      # close() found an empty window
    validate_event(sl[0])
    summary = tele_report.summarize(events)
    s = summary["serving"]
    assert s["requests"] == 10 and s["windows"] == 1
    assert s["coalesce_max"] >= 1
    rendered = tele_report.render(summary)
    assert "serving: 10 requests" in rendered
    assert "p99=" in rendered


def test_empty_window_emits_nothing(trained):
    rl = RunLog()
    eng = _engine(trained, run_log=rl)
    try:
        assert eng.emit_latency() is None
        assert rl.events("serve_latency") == []
    finally:
        eng.close()


def _v3_log(path):
    """A minimal schema-3 log exactly as the pre-serving writer shaped
    it — the back-compat fixture (serve_latency must be purely
    additive)."""
    import json

    recs = [
        {"event": "run_manifest", "schema": 3, "t": 100.0, "seq": 0,
         "trainer": "driver", "backend": "tpu", "loss": "logloss",
         "n_trees": 2, "max_depth": 3, "rows": 10, "features": 4,
         "run_id": "cafe01234567", "host": 0},
        {"event": "round", "schema": 3, "t": 101.0, "seq": 1,
         "round": 1, "ms_per_round": 5.0, "train_loss": 0.6},
        {"event": "cost_analysis", "schema": 3, "t": 101.5, "seq": 2,
         "op": "hist", "flops": 1e9, "bytes_accessed": 1e8,
         "phase": "grow", "calls": 2, "device_kind": "cpu"},
        {"event": "phase_timings", "schema": 3, "t": 102.0, "seq": 3,
         "phases": [{"phase": "grow", "ms_total": 5.0,
                     "ms_per_call": 2.5, "calls": 2, "share": 1.0}]},
        {"event": "run_end", "schema": 3, "t": 103.0, "seq": 4,
         "completed_rounds": 1, "wallclock_s": 3.0},
    ]
    with open(path, "w", encoding="utf-8") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")


def test_old_schema_logs_parse_through_report_merge_trace(tmp_path):
    """Schema <= 3 logs (no serve_latency) keep parsing through
    report/merge/trace after the v4 bump, and their summaries carry
    serving=None so renderers change nothing."""
    from ddt_tpu.telemetry import merge as tele_merge
    from ddt_tpu.telemetry import perfetto

    p = str(tmp_path / "v3.jsonl")
    _v3_log(p)
    events = tele_merge.merge_paths([p])
    summary = tele_report.summarize(events)
    assert summary["serving"] is None
    rendered = tele_report.render(summary)
    assert "serving:" not in rendered
    out = str(tmp_path / "trace.json")
    assert perfetto.write_trace(events, out) > 0


# --------------------------------------------------------------------- #
# express lane (ISSUE 12)
# --------------------------------------------------------------------- #
def test_express_lane_dispatches_single_rows_at_empty_queue(trained):
    """A lone single-row request at an empty queue rides the express
    lane: correct score, stamped token, express counted in the stats
    window — and it never paid the admission window (structural: the
    window is absurdly long, the test would time out if it waited)."""
    eng = _engine(trained, max_wait_ms=60_000.0)
    try:
        X, ref = trained["X"], trained["ref"]["a"]
        got = eng.predict(X[:1], timeout=30.0)
        np.testing.assert_allclose(got, ref[:1], rtol=1e-6, atol=1e-7)
        w = eng.stats.window_summary(reset=False)
        assert w["express"] == 1 and w["requests"] == 1
        assert eng.health()["express_lane"] is True
    finally:
        eng.close()


def test_express_lane_closes_under_load(trained):
    """With the dispatch gate held (a batch 'mid-flight') and requests
    queued, a single-row submit must NOT express — it joins the queue
    and coalesces with the backlog once the gate frees."""
    eng = _engine(trained, max_wait_ms=5.0)
    try:
        X, ref = trained["X"], trained["ref"]["a"]
        eng._batcher._gate.acquire()          # simulate dispatch in flight
        try:
            queued = [eng.predict_async(X[i:i + 1]) for i in range(4)]
        finally:
            eng._batcher._gate.release()
        for i, p in enumerate(queued):
            np.testing.assert_allclose(p.result(timeout=30.0),
                                       ref[i:i + 1],
                                       rtol=1e-6, atol=1e-7)
        w = eng.stats.window_summary(reset=False)
        assert w["express"] == 0, w           # the lane stayed shut
        assert w["coalesce_max"] > 1          # the backlog coalesced
    finally:
        eng.close()


def test_express_lane_old_or_new_never_a_mix_under_hot_swap(trained):
    """Express responses under a mid-flight hot swap: every single-row
    answer matches model A's or model B's offline score exactly — the
    lane reads the model reference once, so a swap cannot blend."""
    eng = _engine(trained, max_wait_ms=1.0)
    try:
        X = trained["X"]
        ra, rb = trained["ref"]["a"], trained["ref"]["b"]
        stop = threading.Event()
        results, errors = [], []

        def hammer(tid):
            rng = np.random.default_rng(tid)
            while not stop.is_set():
                s = int(rng.integers(0, 100))
                try:
                    out = eng.predict(X[s:s + 1], timeout=60.0)
                    results.append((s, np.asarray(out)))
                except Exception as e:  # ddtlint: disable=broad-except — collected and asserted empty below
                    errors.append(repr(e))

        threads = [threading.Thread(target=hammer, args=(t,))
                   for t in range(3)]
        for t in threads:
            t.start()
        import time as _time

        while len(results) < 15:
            _time.sleep(0.002)
        eng.swap(_bundle(trained["res_b"]))
        while len(results) < 45:
            _time.sleep(0.002)
        stop.set()
        for t in threads:
            t.join(60)
        assert not errors, errors[:5]
        n_b = 0
        for s, out in results:
            is_a = np.allclose(out, ra[s:s + 1], rtol=1e-6, atol=1e-7)
            is_b = np.allclose(out, rb[s:s + 1], rtol=1e-6, atol=1e-7)
            assert is_a or is_b, f"row {s} matches neither model"
            n_b += bool(is_b and not is_a)
        assert n_b > 0
        assert eng.stats.express > 0          # some traffic took the lane
    finally:
        eng.close()


def test_express_lane_opt_out_and_shutdown(trained):
    """express_lane=False keeps every request on the queued path; a
    closed engine's express path raises ShuttingDown like submit."""
    eng = _engine(trained, max_wait_ms=2.0, express_lane=False)
    X = trained["X"]
    out = eng.predict(X[:1], timeout=30.0)
    assert out.shape[0] == 1
    assert eng.stats.window_summary(reset=False)["express"] == 0
    assert eng.health()["express_lane"] is False
    eng.close()
    eng2 = _engine(trained)
    eng2.close()
    with pytest.raises(ShuttingDown):
        eng2.predict_async(np.zeros((1, eng2.n_features), np.uint8))


def test_batcher_deadline_pinned_to_oldest_request_fake_clock():
    """The admission deadline is pinned to the OLDEST queued request
    when its window opens — later arrivals re-notify the Condition but
    must not re-arm the window (a re-arming batcher stretches a batch
    past the head request's budget under a steady trickle; this
    fake-clock drive would then never dispatch and the result() below
    would time out)."""
    fake = {"t": 0.0}
    batches = []

    def dispatch(batch, depth):
        batches.append([r.n for r in batch])
        for r in batch:
            r.set_result(np.zeros(r.n))

    mb = MicroBatcher(dispatch, max_wait_ms=50.0, max_batch=1000,
                      clock=lambda: fake["t"])
    try:
        a = mb.submit(np.zeros((1, 2)), 1)       # head: deadline t=0.05
        trickle = [mb.submit(np.zeros((1, 2)), 1) for _ in range(3)]
        # Advance PAST the head's deadline, then trickle one more
        # arrival: its notify wakes the dispatcher, which must see the
        # head's (expired) deadline — NOT a fresh one measured from
        # this arrival — and dispatch everything queued.
        fake["t"] = 0.06
        late = mb.submit(np.zeros((1, 2)), 1)
        a.result(timeout=10.0)
        late.result(timeout=10.0)
        for r in trickle:
            r.result(timeout=10.0)
        # Everything dispatched (a re-armer never gets here), and the
        # head request was not left waiting behind the trickle: its
        # batch is the FIRST one. (The real-time timeout wake can race
        # the late submit, legally splitting `late` into a second
        # batch — the pin under test is the head's deadline, not the
        # packing.)
        assert sum(len(b) for b in batches) == 5
        assert len(batches[0]) >= 4, batches
    finally:
        mb.close()


# --------------------------------------------------------------------- #
# zero-copy binned wire path (ISSUE 12)
# --------------------------------------------------------------------- #
def test_decode_raw_rows_contract():
    from ddt_tpu.serve.http import decode_raw_rows

    body = bytes(range(12))
    rows = decode_raw_rows(body, 4, 12)
    assert rows.shape == (3, 4) and rows.dtype == np.uint8
    np.testing.assert_array_equal(rows.reshape(-1),
                                  np.frombuffer(body, np.uint8))
    with pytest.raises(ValueError, match="Content-Length"):
        decode_raw_rows(body, 4, None)
    with pytest.raises(ValueError, match="declared"):
        decode_raw_rows(body, 4, 13)          # truncated body
    with pytest.raises(ValueError, match="whole number"):
        decode_raw_rows(body, 5, 12)          # width mismatch
    with pytest.raises(ValueError, match="empty"):
        decode_raw_rows(b"", 4, 0)


def test_binned_raw_wire_parity_with_float_body(trained):
    """End to end over real HTTP: POST /predict?binned=raw (the body IS
    the uint8 row block) scores bit-identically to the JSON float-body
    path on the same engine — the zero-copy path changes transport,
    never answers."""
    import json as _json
    import urllib.error
    import urllib.request

    from ddt_tpu.serve.http import serve_forever

    eng = _engine(trained, max_wait_ms=2.0)
    ready = threading.Event()
    th = threading.Thread(target=serve_forever, args=(eng,),
                          kwargs=dict(port=0, ready_event=ready),
                          daemon=True)
    th.start()
    assert ready.wait(60)
    port = eng.http_port
    try:
        X = trained["X"]
        Xb = trained["res_a"].mapper.transform(X[:5])

        def post(path, data, ctype):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}{path}", data=data,
                headers={"Content-Type": ctype}, method="POST")
            with urllib.request.urlopen(req, timeout=30) as r:
                return _json.loads(r.read())

        r_raw = post("/predict?binned=raw", Xb.tobytes(),
                     "application/octet-stream")
        r_json = post("/predict",
                      _json.dumps({"rows": X[:5].tolist()}).encode(),
                      "application/json")
        assert r_raw["model"] == r_json["model"]
        np.testing.assert_array_equal(np.asarray(r_raw["scores"]),
                                      np.asarray(r_json["scores"]))
        # Width mismatch: 400, loudly.
        try:
            post("/predict?binned=raw", Xb.tobytes()[:-1],
                 "application/octet-stream")
            raise AssertionError("truncated raw body was accepted")
        except urllib.error.HTTPError as e:
            body = e.read()
            assert e.code == 400
            assert b"whole number" in body or b"declared" in body
        # /healthz reports the serving tier (f32 here — no quantize).
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=30) as r:
            h = _json.loads(r.read())
        assert h["predict_impl"] == "f32"
    finally:
        post_shutdown = urllib.request.Request(
            f"http://127.0.0.1:{port}/shutdown", data=b"{}",
            method="POST")
        urllib.request.urlopen(post_shutdown, timeout=30).read()
        th.join(30)


# --------------------------------------------------------------------- #
# per-request trace propagation + /metrics exposition (ISSUE 17)
# --------------------------------------------------------------------- #
def test_trace_breakdown_on_express_and_coalesced_lanes(trained):
    """Every completed request carries a trace id and a full timing
    breakdown (handler/queue/gate/device/wake summing into total), on
    BOTH lanes: the express lane's handler segment is structurally zero
    (accept and admit are the same stamp), the coalesced lane's queue
    segment covers the admission window. The ring at debug_traces()
    holds the records."""
    from ddt_tpu.serve.batcher import trace_breakdown

    eng = _engine(trained, max_wait_ms=2.0)
    try:
        X = trained["X"]
        p_express = eng.predict_async(X[:1])
        p_express.result(timeout=30.0)
        assert p_express.trace_id is not None
        bd = trace_breakdown(p_express)
        assert bd is not None
        assert set(bd) == {"handler_ms", "queue_ms", "gate_ms",
                           "device_ms", "wake_ms", "total_ms"}
        assert bd["handler_ms"] == 0.0        # express: accept == admit
        assert bd["device_ms"] > 0.0
        assert bd["total_ms"] >= bd["device_ms"]

        p_batch = eng.predict_async(X[:3])    # multi-row: queued lane
        p_batch.result(timeout=30.0)
        bd2 = trace_breakdown(p_batch)
        assert bd2 is not None and bd2["total_ms"] > 0.0
        ring = eng.debug_traces()
        assert set(ring) == {"default"}
        ids = [t["trace_id"] for t in ring["default"]]
        assert p_express.trace_id in ids and p_batch.trace_id in ids
        rec = next(t for t in ring["default"]
                   if t["trace_id"] == p_express.trace_id)
        assert rec["express"] is True and rec["rows"] == 1
        assert rec["device_ms"] == bd["device_ms"]
    finally:
        eng.close()


def test_trace_id_propagation_and_opt_out(trained):
    """A client-supplied trace id is honored verbatim; with
    request_traces=False no breakdown is measured (marks stay None) but
    a supplied id still rides through — propagation without
    measurement — and nothing lands in the ring."""
    from ddt_tpu.serve.batcher import trace_breakdown

    eng = _engine(trained, max_wait_ms=2.0)
    try:
        p = eng.predict_async(trained["X"][:1], trace_id="client-abc-1")
        p.result(timeout=30.0)
        assert p.trace_id == "client-abc-1"
        assert trace_breakdown(p) is not None
    finally:
        eng.close()
    eng2 = _engine(trained, max_wait_ms=2.0, request_traces=False)
    try:
        p = eng2.predict_async(trained["X"][:1], trace_id="client-abc-2")
        p.result(timeout=30.0)
        assert p.trace_id == "client-abc-2"   # echoed, not measured
        assert trace_breakdown(p) is None
        q = eng2.predict_async(trained["X"][:1])
        q.result(timeout=30.0)
        assert q.trace_id is None             # no server-minted ids
        assert eng2.debug_traces() == {"default": []}
    finally:
        eng2.close()


def test_serve_trace_flush_emits_validating_event(trained):
    """flush_traces() lands the ring as ONE schema-valid serve_trace
    event (reason stamped); an empty ring emits nothing."""
    rl = RunLog()
    eng = _engine(trained, max_wait_ms=2.0, run_log=rl)
    try:
        assert eng.flush_traces() == 0        # nothing served yet
        for i in range(3):
            eng.predict(trained["X"][i:i + 1], timeout=30.0)
        n = eng.flush_traces(reason="on_demand")
        assert n == 3
        evs = rl.events("serve_trace")
        assert len(evs) == 1
        validate_event(evs[0])
        assert evs[0]["count"] == 3 and evs[0]["reason"] == "on_demand"
        assert len(evs[0]["traces"]) == 3
        assert all(t["total_ms"] >= 0 for t in evs[0]["traces"])
    finally:
        eng.close()


def test_metrics_exposition_renders_and_parses(trained):
    """The /metrics body: every process counter becomes a
    ddt_*_total series, the per-model histogram is CUMULATIVE with
    le="+Inf" equal to _count, and _count equals the requests served."""
    from ddt_tpu.serve.metrics import parse_exposition, render_metrics
    from ddt_tpu.telemetry import counters as tele_counters

    eng = _engine(trained, max_wait_ms=2.0)
    try:
        for i in range(5):
            eng.predict(trained["X"][i:i + 1], timeout=30.0)
        text = render_metrics(tele_counters.snapshot(),
                              eng.metrics_snapshot())
        series = parse_exposition(text)
        for key, v in tele_counters.snapshot().items():
            name = f"ddt_{key}_total"
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                assert series[name][()] == float(v), name
        lab = lambda **kw: frozenset(kw.items())  # noqa: E731
        count = series["ddt_serve_latency_ms_count"][lab(model="default")]
        assert count == 5.0
        buckets = series["ddt_serve_latency_ms_bucket"]
        inf = buckets[lab(model="default", le="+Inf")]
        assert inf == count                   # +Inf == _count by contract
        finite = sorted(
            ((float(dict(k)["le"]), v) for k, v in buckets.items()
             if dict(k)["le"] != "+Inf"))
        vals = [v for _, v in finite]
        assert vals == sorted(vals)           # cumulative: monotone
        assert series["ddt_serve_backlog_rows"][lab(model="default")] == 0.0
        assert series["ddt_serve_resident_models"][()] == 1.0
        assert "ddt_serve_slo_objective_ms" not in series  # no SLO here
    finally:
        eng.close()


def test_metrics_scrape_is_read_only_vs_stats_emit(trained):
    """THE regression pin (ISSUE 17): /metrics never resets anything.
    Interleave scrapes with /stats?emit=1 over live HTTP — the emitted
    window still carries every request (scrapes stole none), back-to-
    back scrapes with no traffic are byte-identical, and the histogram
    count keeps running across the window reset. Trace id round trip
    rides the same storm."""
    import json as _json
    import urllib.request

    from ddt_tpu.serve.http import serve_forever

    eng = _engine(trained, max_wait_ms=2.0)
    ready = threading.Event()
    th = threading.Thread(target=serve_forever, args=(eng,),
                          kwargs=dict(port=0, ready_event=ready),
                          daemon=True)
    th.start()
    assert ready.wait(60)
    port = eng.http_port

    def get(path):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=30) as r:
            return r.read().decode()

    try:
        X = trained["X"]
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/predict",
            data=_json.dumps({"rows": X[:1].tolist()}).encode(),
            headers={"Content-Type": "application/json",
                     "X-DDT-Trace-Id": "pin-roundtrip-7"},
            method="POST")
        with urllib.request.urlopen(req, timeout=30) as r:
            r.read()
            assert r.headers["X-DDT-Trace-Id"] == "pin-roundtrip-7"
            timing = r.headers["X-DDT-Timing"]
        segs = dict(kv.split("=") for kv in timing.split(","))
        assert set(segs) == {"handler", "queue", "gate", "device",
                             "wake", "total"}
        for i in range(1, 6):
            with urllib.request.urlopen(urllib.request.Request(
                    f"http://127.0.0.1:{port}/predict",
                    data=_json.dumps({"rows": X[i:i + 1].tolist()}
                                     ).encode(),
                    headers={"Content-Type": "application/json"},
                    method="POST"), timeout=30) as r:
                r.read()
        scrape_a = get("/metrics")
        assert _json.loads(get("/stats"))["requests"] == 6
        scrape_b = get("/metrics")
        assert scrape_a == scrape_b           # scrape-idempotent
        # The ?emit=1 window still owns ALL the traffic: the two
        # scrapes and the plain /stats read in between stole nothing.
        emitted = _json.loads(get("/stats?emit=1"))
        assert emitted["requests"] == 6
        assert _json.loads(get("/stats"))["requests"] == 0  # reset
        from ddt_tpu.serve.metrics import parse_exposition
        series = parse_exposition(get("/metrics"))
        key = frozenset({("model", "default")})
        assert series["ddt_serve_latency_ms_count"][key] == 6.0
        # /debug/requests: the ring over HTTP, id still addressable.
        dbg = _json.loads(get("/debug/requests"))
        ids = [t["trace_id"] for t in dbg["models"]["default"]]
        assert "pin-roundtrip-7" in ids
    finally:
        post_shutdown = urllib.request.Request(
            f"http://127.0.0.1:{port}/shutdown", data=b"{}",
            method="POST")
        urllib.request.urlopen(post_shutdown, timeout=30).read()
        th.join(30)


def test_single_model_healthz_unchanged_pre_slo(trained):
    """Satellite pin: a single-model server's health payload gained
    NOTHING from the SLO machinery (no slo keys, no fleet keys) — the
    operations plane is schema-additive and fleet-scoped."""
    eng = _engine(trained)
    try:
        h = eng.health()
        assert not any(k.startswith("slo") for k in h)
        assert "backlog_rows" not in h and "resident_models" not in h
    finally:
        eng.close()


def test_v4_serve_log_roundtrips_merge_and_trace(trained, tmp_path):
    """A log WITH serve_latency events survives merge + Perfetto export
    (the event rides as an instant marker)."""
    import json

    from ddt_tpu.telemetry import merge as tele_merge
    from ddt_tpu.telemetry import perfetto

    path = str(tmp_path / "serve.jsonl")
    eng = _engine(trained, run_log=path)
    try:
        for i in range(4):
            eng.predict(trained["X"][i:i + 1], timeout=60.0)
        eng.emit_latency()
    finally:
        eng.close()
    events = tele_merge.merge_paths([path])
    out = str(tmp_path / "trace.json")
    assert perfetto.write_trace(events, out) > 0
    with open(out, encoding="utf-8") as f:
        names = [e.get("name") for e in json.load(f)["traceEvents"]]
    assert "serve_latency" in names
