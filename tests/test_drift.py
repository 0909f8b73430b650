"""Drift observatory + champion/challenger shadow mode (ISSUE 19).

Covers the pipeline end to end: divergence math, the time-sliced
rolling window with latched alerts, the training-reference capture and
its npz round trip (including pre-drift artifacts loading with drift
OFF, never an error), the fleet wiring (health/metrics/debug/event
surfaces), shadow-mode bit-identity and misconfig rejection over HTTP,
/metrics read-only semantics with drift enabled, and the `report
drift` rollup with graceful degradation over pre-drift logs.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from ddt_tpu import api
from ddt_tpu.config import TrainConfig
from ddt_tpu.data import datasets
from ddt_tpu.data.quantizer import feature_bincounts
from ddt_tpu.serve import drift as serve_drift
from ddt_tpu.serve.control import (FleetConfigError, FleetSpec,
                                   build_fleet)
from ddt_tpu.serve.drift import DriftTracker, divergence
from ddt_tpu.serve.metrics import parse_exposition, render_metrics
from ddt_tpu.telemetry import counters as tele_counters
from ddt_tpu.telemetry import report as tele_report
from ddt_tpu.telemetry.events import validate_event


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """Two models over the same bin space (champion + challenger) and a
    reference-less 'pre-drift era' artifact, shared module-wide."""
    X, y = datasets.synthetic_binary(3000, seed=11)
    kw = dict(n_trees=5, max_depth=3, n_bins=31, backend="tpu",
              log_every=10**9)
    champ = api.train(X, y, **kw)
    chall = api.train(X, y, learning_rate=0.05, **kw)
    td = tmp_path_factory.mktemp("drift_models")
    p_champ = str(td / "champ.npz")
    p_chall = str(td / "chall.npz")
    champ.save(p_champ)
    chall.save(p_chall)
    # A pre-drift artifact: same model, reference stripped before save —
    # byte-level what every artifact looked like before ISSUE 19.
    saved_ref = champ.mapper.ref_counts
    champ.mapper.ref_counts = None
    p_legacy = str(td / "legacy.npz")
    champ.save(p_legacy)
    champ.mapper.ref_counts = saved_ref
    cfg = TrainConfig(backend="tpu", n_bins=31)
    ref_scores = np.asarray(api.predict(
        champ.ensemble, X, mapper=champ.mapper, cfg=cfg))
    return dict(X=X, y=y, champ=champ, chall=chall, cfg=cfg,
                paths=dict(champ=p_champ, chall=p_chall,
                           legacy=p_legacy),
                ref_scores=ref_scores)


# --------------------------------------------------------------------- #
# divergence math
# --------------------------------------------------------------------- #
def test_divergence_identical_histograms_score_zero():
    rng = np.random.default_rng(0)
    ref = rng.integers(1, 100, size=(4, 8)).astype(np.int64)
    psi, js = divergence(ref, ref * 3)      # same shape, scaled counts
    assert psi.shape == js.shape == (4,)
    np.testing.assert_allclose(psi, 0.0, atol=1e-9)
    np.testing.assert_allclose(js, 0.0, atol=1e-9)


def test_divergence_disjoint_histograms_saturate():
    ref = np.zeros((1, 8), np.int64)
    win = np.zeros((1, 8), np.int64)
    ref[0, :4] = 100
    win[0, 4:] = 100
    psi, js = divergence(ref, win)
    assert psi[0] > 1.0                      # far past any threshold
    assert 0.99 < js[0] <= 1.0 + 1e-9        # JS base 2 is bounded [0,1]
    # JS is symmetric; PSI is too (its summand is symmetric in p,q)
    psi2, js2 = divergence(win, ref)
    np.testing.assert_allclose(js, js2, atol=1e-12)
    np.testing.assert_allclose(psi, psi2, atol=1e-12)


def test_divergence_matches_feature_bincounts_shapes():
    rng = np.random.default_rng(1)
    Xb = rng.integers(0, 16, size=(500, 6)).astype(np.uint8)
    counts = feature_bincounts(Xb, 16)
    assert counts.shape == (6, 16)
    assert counts.sum() == 500 * 6
    psi, js = divergence(counts, counts)
    np.testing.assert_allclose(psi, 0.0, atol=1e-9)


# --------------------------------------------------------------------- #
# DriftTracker: windowing, latched alerts, omit-don't-lie
# --------------------------------------------------------------------- #
def _batches(rng, lo, hi, rows, n_f):
    return rng.integers(lo, hi, size=(rows, n_f)).astype(np.uint8)


def test_tracker_below_min_rows_reports_none():
    rng = np.random.default_rng(2)
    ref = feature_bincounts(_batches(rng, 0, 8, 2000, 3), 16)
    trk = DriftTracker(ref, min_rows=256)
    assert trk.observe(0.0, _batches(rng, 0, 8, 100, 3)) is None
    st = trk.state(0.0)
    assert st["window_rows"] == 100
    assert st["psi_max"] is None and st["js_max"] is None
    assert trk.per_feature(0.0) is None
    assert not trk.has_pending()


def test_tracker_latched_alert_fires_once_and_rearms():
    rng = np.random.default_rng(3)
    ref = feature_bincounts(_batches(rng, 0, 8, 4000, 3), 16)
    trk = DriftTracker(ref, window_s=10.0, min_rows=64)
    # in-distribution traffic: scored, quiet
    assert trk.observe(0.0, _batches(rng, 0, 8, 300, 3)) is None
    st = trk.state(0.0)
    assert st["psi_max"] is not None and not st["alerting"]
    # shifted traffic (bins 8..16 the reference never saw): ONE latched
    # alert no matter how many shifted batches follow
    alert = trk.observe(1.0, _batches(rng, 8, 16, 600, 3))
    assert alert is not None and alert["psi_max"] >= trk.threshold
    assert alert["alerts"] == 1 and "feature" in alert
    for _ in range(5):
        assert trk.observe(1.5, _batches(rng, 8, 16, 200, 3)) is None
    assert trk.state(1.5)["alerting"] is True
    assert trk.state(1.5)["alerts"] == 1
    # the payload waits for a handler flush
    assert trk.has_pending()
    pend = trk.take_pending()
    assert len(pend) == 1 and pend[0] == alert
    assert not trk.has_pending() and trk.take_pending() == []
    # window expiry empties the ring -> scores vanish, alert re-arms
    st = trk.state(100.0)
    assert st["window_rows"] == 0 and st["psi_max"] is None
    assert st["alerting"] is False          # cooled below threshold
    alert2 = trk.observe(101.0, _batches(rng, 8, 16, 300, 3))
    assert alert2 is not None and alert2["alerts"] == 2


def test_tracker_ring_rotation_drops_only_expired_slices():
    rng = np.random.default_rng(4)
    ref = feature_bincounts(_batches(rng, 0, 8, 4000, 2), 16)
    trk = DriftTracker(ref, window_s=16.0, min_rows=1)  # 1 s per slice
    trk.observe(0.0, _batches(rng, 0, 8, 100, 2))
    trk.observe(8.0, _batches(rng, 0, 8, 50, 2))
    assert trk.state(8.0)["window_rows"] == 150
    # advance past the first slice's expiry but not the second's
    assert trk.state(17.0)["window_rows"] == 50
    assert trk.state(40.0)["window_rows"] == 0


def test_tracker_per_feature_attribution_sorts_worst_first():
    rng = np.random.default_rng(5)
    ref = feature_bincounts(_batches(rng, 0, 8, 4000, 3), 16)
    trk = DriftTracker(ref, min_rows=1)
    # shift ONLY feature 2
    Xb = _batches(rng, 0, 8, 500, 3)
    Xb[:, 2] = rng.integers(10, 16, size=500)
    trk.observe(0.0, Xb)
    pf = trk.per_feature(0.0)
    assert [r["feature"] for r in pf][0] == 2
    assert pf[0]["psi"] >= pf[-1]["psi"]
    assert trk.state(0.0)["feature"] == 2


def test_tracker_rejects_malformed_reference():
    with pytest.raises(ValueError, match="n_features"):
        DriftTracker(np.zeros(8, np.int64))


# --------------------------------------------------------------------- #
# reference capture + artifact round trip
# --------------------------------------------------------------------- #
def test_train_captures_reference_and_npz_round_trips(trained, tmp_path):
    mapper = trained["champ"].mapper
    ref = mapper.ref_counts
    assert ref is not None and ref.dtype == np.int64
    assert ref.shape == (mapper.n_features, mapper.n_bins)
    assert ref.sum() == 3000 * mapper.n_features   # every cell counted
    bundle = api.load_model(trained["paths"]["champ"])
    np.testing.assert_array_equal(bundle.mapper.ref_counts, ref)


def test_pre_drift_artifact_loads_with_drift_off(trained):
    """A reference-less artifact is the pre-ISSUE-19 on-disk format:
    it must load cleanly and serve with drift tracking silently OFF."""
    bundle = api.load_model(trained["paths"]["legacy"])
    assert bundle.mapper.ref_counts is None
    eng = build_fleet([FleetSpec(name="old", ref=trained["paths"]
                                 ["legacy"])], backend="tpu")
    try:
        eng.predict(trained["X"][:4], model="old", timeout=60.0)
        h = eng.health()["models"]["old"]
        assert "drift_psi_max" not in h        # schema-additive absence
        assert eng.metrics_snapshot()["models"]["old"]["drift"] is None
        dbg = eng.debug_drift()["models"]["old"]
        assert dbg["reference"] is False and "state" not in dbg
    finally:
        eng.close()


def test_drift_required_on_referenceless_artifact_is_config_error(
        trained):
    with pytest.raises(FleetConfigError, match="reference"):
        build_fleet([FleetSpec(name="old", ref=trained["paths"]
                               ["legacy"], drift=True)], backend="tpu")


def test_drift_false_disables_despite_reference(trained):
    eng = build_fleet([FleetSpec(name="m", ref=trained["paths"]["champ"],
                                 drift=False)], backend="tpu")
    try:
        eng.predict(trained["X"][:4], model="m", timeout=60.0)
        assert "drift_psi_max" not in eng.health()["models"]["m"]
    finally:
        eng.close()


# --------------------------------------------------------------------- #
# fleet end to end: event, health, /metrics, debug, report
# --------------------------------------------------------------------- #
def test_fleet_drift_surfaces_end_to_end(trained, tmp_path):
    """Shifted traffic on a reference-carrying model lights up every
    surface — run-log event, healthz, metrics exposition (with a
    parse_exposition round trip), /debug/drift, report drift — while
    an un-shifted control model on the same fleet stays quiet."""
    path = str(tmp_path / "drift.jsonl")
    eng = build_fleet(
        [FleetSpec(name="hot", ref=trained["paths"]["champ"]),
         FleetSpec(name="cool", ref=trained["paths"]["chall"])],
        backend="tpu", max_wait_ms=5.0, run_log=path)
    X = trained["X"]
    try:
        # control: in-distribution traffic only
        for i in range(0, 600, 100):
            eng.predict(X[i:i + 100], model="cool", timeout=60.0)
        # target: enough shifted rows to clear MIN_ROWS and latch
        shifted = X + 5.0 * np.abs(X).max(axis=0)
        for i in range(0, 600, 100):
            eng.predict(shifted[i:i + 100], model="hot", timeout=60.0)

        h = eng.health()["models"]
        assert h["hot"]["drift_alerting"] is True
        assert h["hot"]["drift_alerts"] == 1
        assert h["hot"]["drift_psi_max"] >= serve_drift.PSI_ALERT
        assert h["hot"]["drift_window_rows"] >= serve_drift.MIN_ROWS
        assert h["cool"]["drift_alerting"] is False
        assert h["cool"]["drift_alerts"] == 0

        # exposition + round trip
        text = render_metrics(tele_counters.snapshot(),
                              eng.metrics_snapshot())
        parsed = parse_exposition(text)

        def series(name, model):
            return parsed[name][frozenset({("model", model)})]

        assert series("ddt_drift_alerting", "hot") == 1.0
        assert series("ddt_drift_alerting", "cool") == 0.0
        assert series("ddt_drift_model_alerts_total", "hot") == 1.0
        assert series("ddt_drift_psi_max", "hot") >= serve_drift.PSI_ALERT
        assert series("ddt_drift_js_max", "hot") <= 1.0
        for name in ("hot", "cool"):
            assert series("ddt_drift_psi_threshold", name) \
                == serve_drift.PSI_ALERT

        # per-feature attribution
        dbg = eng.debug_drift()["models"]["hot"]
        assert dbg["reference"] is True
        assert dbg["state"]["alerting"] is True
        assert dbg["per_feature"][0]["psi"] >= dbg["per_feature"][-1]["psi"]

        # windows carry the drift extras and validate against the schema
        emitted = eng.emit_latency(reset=True)
        assert emitted["hot"]["drift_alerting"] is True
        assert emitted["cool"]["drift_alerting"] is False
        for s in emitted.values():
            validate_event({"event": "serve_latency", "schema": 5,
                            "t": 0.0, "seq": 0, **s})
    finally:
        eng.close()

    events = tele_report.read_events(path)
    drifts = [e for e in events if e["event"] == "drift"]
    assert len(drifts) == 1 and drifts[0]["model_name"] == "hot"
    assert drifts[0]["psi_max"] >= serve_drift.PSI_ALERT
    for e in drifts:
        validate_event(e)
    # the counter moved, and its direction is registered lower-is-better
    assert tele_counters.snapshot()["drift_alerts"] >= 1
    from ddt_tpu.telemetry.diffing import COUNTER_DIRECTIONS
    assert COUNTER_DIRECTIONS["drift_alerts"] == "lower"

    summary = tele_report.summarize(events)
    dr = summary["drift"]
    assert dr["models"]["hot"]["alerts"] == 1
    assert dr["models"]["hot"]["alerting"] is True
    assert dr["models"]["cool"]["alerts"] == 0
    rendered = tele_report.render_drift(summary)
    assert "hot" in rendered and "ALERTING" in rendered
    assert "drift:" in tele_report.render(summary)


# --------------------------------------------------------------------- #
# shadow mode
# --------------------------------------------------------------------- #
def _shadow_fleet(trained, **kw):
    return build_fleet(
        [FleetSpec(name="champ", ref=trained["paths"]["champ"]),
         FleetSpec(name="chall", ref=trained["paths"]["chall"],
                   shadow_of="champ")],
        backend="tpu", max_wait_ms=5.0, **kw)


def test_shadow_champion_responses_bit_identical_to_shadow_off(trained):
    """THE acceptance pin: attaching a challenger changes nothing about
    what the champion's clients see — scores are bit-identical to a
    shadow-less fleet on the same traffic."""
    X = trained["X"]
    eng_off = build_fleet(
        [FleetSpec(name="champ", ref=trained["paths"]["champ"])],
        backend="tpu", max_wait_ms=5.0)
    try:
        base = [np.asarray(eng_off.predict(X[i:i + 64], model="champ",
                                           timeout=60.0))
                for i in range(0, 512, 64)]
    finally:
        eng_off.close()
    eng_on = _shadow_fleet(trained)
    try:
        shadowed = [np.asarray(eng_on.predict(X[i:i + 64], model="champ",
                                              timeout=60.0))
                    for i in range(0, 512, 64)]
    finally:
        eng_on.close()
    for a, b in zip(base, shadowed):
        np.testing.assert_array_equal(a, b)     # bit-identical


def test_shadow_scores_champion_traffic_off_response_path(trained):
    eng = _shadow_fleet(trained)
    X = trained["X"]
    try:
        eng.n_features_for("chall")             # force-resident
        for i in range(0, 512, 64):
            eng.predict(X[i:i + 64], model="champ", timeout=60.0)
        # the scorer thread drains asynchronously — poll, don't race
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            s = eng.health()["models"]["champ"]["shadow"]
            if s["rows"] >= 64 and s["mean_abs_diff"] is not None:
                break
            time.sleep(0.05)
        assert s["model"] == "chall" and s["champion"] == "champ"
        assert s["rows"] >= 64
        # different learning rates -> genuinely different predictions
        assert s["mean_abs_diff"] > 0
        assert s["ms_p50"] is not None and s["errors"] == 0
        assert eng.health()["models"]["chall"]["shadow_of"] == "champ"
        # metrics exposition carries the {model,shadow} series
        parsed = parse_exposition(render_metrics(
            tele_counters.snapshot(), eng.metrics_snapshot()))
        labels = frozenset({("model", "champ"), ("shadow", "chall")})
        assert parsed["ddt_shadow_scored_rows_total"][labels] >= 64
        assert parsed["ddt_shadow_mean_abs_diff"][labels] > 0
        # windows carry the shadow extras
        w = eng.emit_latency(reset=True)["champ"]
        assert w["shadow_model"] == "chall" and w["shadow_rows"] >= 64
        validate_event({"event": "serve_latency", "schema": 5,
                        "t": 0.0, "seq": 0, **w})
    finally:
        eng.close()


def test_shadow_skips_not_loads_an_evicted_challenger(trained):
    """The scorer must never do file I/O: a non-resident challenger
    means skipped batches, not a load from the shadow thread."""
    eng = _shadow_fleet(trained, preload=False)
    X = trained["X"]
    try:
        eng.n_features_for("champ")             # champion only
        for i in range(0, 256, 64):
            eng.predict(X[i:i + 64], model="champ", timeout=60.0)
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            s = eng.health()["models"]["champ"]["shadow"]
            if s["skipped"] >= 1:
                break
            time.sleep(0.05)
        assert s["skipped"] >= 1 and s["rows"] == 0
        assert s["mean_abs_diff"] is None       # omit, don't lie
        assert eng.health()["models"]["chall"]["resident"] is False
    finally:
        eng.close()


def test_shadow_drop_on_full_never_blocks():
    """Unit-level: a stuffed queue drops (counted) instead of growing
    or blocking the caller."""
    class _Slot:
        model = None
    sc = serve_drift.ShadowScorer("c", "m", _Slot(), time.monotonic)
    try:
        with sc._cv:                            # freeze the drain
            for i in range(serve_drift.ShadowScorer.QUEUE_CAP + 3):
                if len(sc._q) >= sc.QUEUE_CAP:
                    sc._dropped += 1
                else:
                    sc._q.append((np.zeros((1, 2), np.uint8), [0.0]))
            assert sc._dropped == 3
            assert len(sc._q) == sc.QUEUE_CAP
    finally:
        sc.close()
    assert sc.summary()["dropped"] == 3


def test_shadow_topology_validation(trained):
    p = trained["paths"]
    # dangling champion
    with pytest.raises(FleetConfigError, match="shadow_of"):
        build_fleet([FleetSpec(name="a", ref=p["champ"],
                               shadow_of="ghost")], backend="tpu")
    # chains refused
    with pytest.raises(FleetConfigError, match="chain|shadow"):
        build_fleet([FleetSpec(name="a", ref=p["champ"]),
                     FleetSpec(name="b", ref=p["chall"], shadow_of="a"),
                     FleetSpec(name="c", ref=p["chall"], shadow_of="b")],
                    backend="tpu")
    # one challenger per champion
    with pytest.raises(FleetConfigError, match="challenger"):
        build_fleet([FleetSpec(name="a", ref=p["champ"]),
                     FleetSpec(name="b", ref=p["chall"], shadow_of="a"),
                     FleetSpec(name="c", ref=p["chall"], shadow_of="a")],
                    backend="tpu")


def test_remove_shadowed_champion_refused_until_shadow_goes(trained):
    eng = _shadow_fleet(trained)
    try:
        with pytest.raises(ValueError, match="shadow"):
            eng.remove_model("champ")
        eng.remove_model("chall")               # detaches cleanly
        assert "shadow" not in eng.health()["models"]["champ"]
        eng.remove_model("champ")               # now removable
    finally:
        eng.close()


# --------------------------------------------------------------------- #
# HTTP: structured errors + read-only /metrics with drift live
# --------------------------------------------------------------------- #
def _post(port, path, payload):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=30) as r:
        return json.loads(r.read())


def _get_raw(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=30) as r:
        return r.read().decode()


@pytest.fixture()
def served_drift_fleet(trained):
    from ddt_tpu.serve.http import serve_forever

    eng = build_fleet(
        [FleetSpec(name="champ", ref=trained["paths"]["champ"]),
         FleetSpec(name="chall", ref=trained["paths"]["chall"],
                   shadow_of="champ")],
        backend="tpu", max_wait_ms=5.0)
    ready = threading.Event()
    th = threading.Thread(target=serve_forever, args=(eng,),
                          kwargs=dict(port=0, ready_event=ready),
                          daemon=True)
    th.start()
    assert ready.wait(60)
    yield eng, eng.http_port
    try:
        _post(eng.http_port, "/shutdown", {})
    except OSError:
        pass
    th.join(30)


def test_http_drift_misconfig_is_structured_400_never_500(
        served_drift_fleet, trained):
    eng, port = served_drift_fleet
    # drift=true on a reference-less artifact
    try:
        _post(port, "/models", {"action": "add", "name": "old",
                                "ref": trained["paths"]["legacy"],
                                "drift": True})
        raise AssertionError("reference-less drift=true accepted")
    except urllib.error.HTTPError as e:
        assert e.code == 400
        body = json.loads(e.read())
        assert "error" in body and "reference" in body["error"]
    # second challenger on an already-shadowed champion
    try:
        _post(port, "/models", {"action": "add", "name": "c2",
                                "ref": trained["paths"]["chall"],
                                "shadow_of": "champ"})
        raise AssertionError("second challenger accepted")
    except urllib.error.HTTPError as e:
        assert e.code == 400
        assert "error" in json.loads(e.read())
    # dangling shadow_of
    try:
        _post(port, "/models", {"action": "add", "name": "c3",
                                "ref": trained["paths"]["chall"],
                                "shadow_of": "ghost"})
        raise AssertionError("dangling shadow_of accepted")
    except urllib.error.HTTPError as e:
        assert e.code == 400
    # the fleet is intact after every rejection
    assert set(json.loads(_get_raw(port, "/healthz"))["models"]) \
        == {"champ", "chall"}


def test_http_metrics_read_only_with_drift_enabled(served_drift_fleet,
                                                   trained):
    """Extends the ISSUE-17 pin to the drift era: scrapes never rotate
    the drift window, reset a tracker, or steal from the emit window."""
    eng, port = served_drift_fleet
    X = trained["X"]
    shifted = X + 5.0 * np.abs(X).max(axis=0)
    for i in range(0, 600, 100):
        _post(port, "/models/champ/predict",
              {"rows": shifted[i:i + 100].tolist()})

    def drift_series(text):
        return {k: v for k, v in parse_exposition(text).items()
                if k.startswith("ddt_drift_")}

    # The drift observer runs AFTER the waiter has its answer
    # (serve/engine.py), so the last batch may still be on its way into
    # the window when its response arrives: scrape until it is there
    # (scrapes are read-only, which is what this test pins), then
    # compare two scrapes of a settled window.
    champ = frozenset({("model", "champ")})
    deadline = time.monotonic() + 30.0
    while True:
        a = _get_raw(port, "/metrics")
        if drift_series(a)["ddt_drift_window_rows"][champ] >= 600 \
                or time.monotonic() > deadline:
            break
        time.sleep(0.01)
    dbg = json.loads(_get_raw(port, "/debug/drift"))
    assert dbg["fleet"] is True
    assert dbg["models"]["champ"]["state"]["alerting"] is True
    b = _get_raw(port, "/metrics")

    # scrape-idempotent on the drift series: the scrapes (and the
    # /debug/drift read between them) rotated no window, reset no
    # tracker (shadow series are excluded — the scorer thread drains
    # its queue asynchronously between reads by design)
    da, db = drift_series(a), drift_series(b)
    assert da == db
    assert frozenset({("model", "champ")}) in da["ddt_drift_alerting"]
    # the emit window still owns all the traffic after two scrapes
    emitted = json.loads(_get_raw(port, "/models/champ/stats?emit=1"))
    assert emitted["requests"] == 6


# --------------------------------------------------------------------- #
# report: rollup + graceful degradation over pre-drift logs
# --------------------------------------------------------------------- #
def test_report_drift_degrades_gracefully_on_pre_drift_logs(tmp_path):
    """A v5-era log with no drift signal summarizes exactly as before
    (drift section absent) and `report drift` fails loudly — while the
    full report renders unchanged."""
    path = str(tmp_path / "old.jsonl")
    from ddt_tpu.telemetry.events import RunLog
    with RunLog(path) as rl:
        rl.emit("run_manifest", trainer="driver", backend="cpu",
                loss="logloss", n_trees=2, max_depth=3, rows=10,
                features=4)
        rl.emit("serve_latency", requests=10, p50_ms=1.0, p99_ms=2.0,
                p999_ms=3.0, max_ms=3.0, batches=2, coalesce_mean=5.0,
                coalesce_max=8, queue_depth_max=1, window_s=1.0,
                model_name="old")
        rl.emit("run_end", completed_rounds=0, wallclock_s=0.1)
    summary = tele_report.summarize(tele_report.read_events(path))
    assert summary.get("drift") is None
    with pytest.raises(ValueError, match="drift"):
        tele_report.render_drift(summary)
    rendered = tele_report.render(summary)
    assert "drift:" not in rendered
    assert "run:" in rendered                 # the full report is intact


def test_report_drift_rollup_joins_events_and_windows(tmp_path):
    path = str(tmp_path / "drift.jsonl")
    from ddt_tpu.telemetry.events import RunLog
    with RunLog(path) as rl:
        rl.emit("run_manifest", trainer="driver", backend="cpu",
                loss="logloss", n_trees=2, max_depth=3, rows=10,
                features=4)
        rl.emit("serve_latency", requests=600, p50_ms=1.0, p99_ms=2.0,
                p999_ms=3.0, max_ms=3.0, batches=6, coalesce_mean=100.0,
                coalesce_max=100, queue_depth_max=1, window_s=1.0,
                model_name="hot", drift_psi_max=0.9, drift_js_max=0.5,
                drift_alerting=True, shadow_model="ch",
                shadow_rows=512, shadow_mean_abs_diff=0.012,
                shadow_ms_p50=0.4)
        rl.emit("drift", model_name="hot", psi_max=0.9, js_max=0.5,
                psi_mean=0.4, feature=3, window_rows=600,
                window_s=300.0, threshold=0.25, alerts=1)
        rl.emit("run_end", completed_rounds=0, wallclock_s=0.1)
    summary = tele_report.summarize(tele_report.read_events(path))
    rec = summary["drift"]["models"]["hot"]
    assert rec["alerts"] == 1 and rec["worst_feature"] == 3
    assert rec["worst_psi_max"] == 0.9 and rec["threshold"] == 0.25
    assert rec["shadow"]["model"] == "ch"
    assert rec["shadow"]["mean_abs_diff"] == 0.012
    rendered = tele_report.render_drift(summary)
    assert "hot" in rendered and "ch" in rendered
    # --json path: the rollup is a plain JSON object
    json.dumps(summary["drift"])
