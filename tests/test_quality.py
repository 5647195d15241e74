"""Model-quality observability (ROADMAP item D): drift gauges vs a
shadow retrain, the flight recorder's replay-payload capture, the
replay harness's answer differ, the canary verdict math, and the
drift-band breach auto-triggering the rolling /reload lane exactly
once per episode."""

import datetime as _dt
import json
import urllib.request

import numpy as np
import pytest

from predictionio_tpu.data.storage import set_storage
from predictionio_tpu.obs import flight, metrics, quality

from tests.test_storage import make_storage
from tests.test_stream import _rate, _seed_world, _train_reco

UTC = _dt.timezone.utc


@pytest.fixture(autouse=True)
def _clean_quality_state():
    quality.STATE.clear()
    yield
    quality.STATE.clear()


class _FakeModel:
    """A bare factor model (the ShadowRef/drift contract surface)."""

    def __init__(self, n_users=24, n_items=40, rank=6, seed=0):
        rng = np.random.default_rng(seed)
        self.user_factors = rng.normal(size=(n_users, rank)).astype(
            np.float32)
        self.item_factors = rng.normal(size=(n_items, rank)).astype(
            np.float32)
        self.user_ids = {f"u{i}": i for i in range(n_users)}
        self.item_ids = {f"i{i}": i for i in range(n_items)}


# ---------------------------------------------------------------------------
# drift math
# ---------------------------------------------------------------------------

class TestDriftReport:
    def test_identical_model_has_no_drift(self):
        m = _FakeModel()
        shadow = quality.ShadowRef(m, "inst")
        report = quality.drift_report(m, shadow)
        assert report["recall_vs_retrain"] == 1.0
        assert report["rmse_drift"] == 0.0
        assert report["factor_drift"] == 0.0
        assert quality.breached_metrics(report) == []

    def test_shadow_is_a_snapshot_not_a_reference(self):
        # the live model mutates AFTER the snapshot: drift must measure
        # against the frozen tables, not follow the mutation
        m = _FakeModel()
        shadow = quality.ShadowRef(m, "inst")
        m.user_factors = m.user_factors * 3.0
        report = quality.drift_report(m, shadow)
        assert report["factor_drift"] > 0.5

    def test_corruption_breaches_every_metric(self):
        m = _FakeModel()
        shadow = quality.ShadowRef(m, "inst")
        m.user_factors = m.user_factors * 7.0 + 3.0
        m.item_factors = m.item_factors[:, ::-1].copy()
        report = quality.publish_drift(quality.drift_report(m, shadow))
        assert report["recall_vs_retrain"] < 0.9
        assert set(report["breached"]) == {
            "recall_vs_retrain", "rmse_drift", "factor_drift"}
        # the gauges carry the SAME numbers (one source of truth)
        assert metrics.REGISTRY.get(
            "pio_model_quality_recall_vs_retrain"
        ).value == report["recall_vs_retrain"]
        assert metrics.REGISTRY.get(
            "pio_model_quality_rmse_drift").value == report["rmse_drift"]
        # ...and the /admin/quality state holds the identical report
        assert quality.STATE.report()["drift"] == report

    def test_band_is_configurable(self, monkeypatch):
        report = {"recall_vs_retrain": 0.85, "rmse_drift": 0.05,
                  "factor_drift": 0.02}
        assert quality.breached_metrics(report, band=0.10) == [
            "recall_vs_retrain"]
        monkeypatch.setenv("PIO_QUALITY_DRIFT_BAND", "0.2")
        assert quality.breached_metrics(report) == []
        monkeypatch.setenv("PIO_QUALITY_DRIFT_BAND", "0.01")
        assert quality.breached_metrics(report) == [
            "recall_vs_retrain", "rmse_drift", "factor_drift"]

    def test_disjoint_vocab_yields_no_verdict(self):
        a, b = _FakeModel(seed=1), _FakeModel(seed=2)
        b.user_ids = {f"x{i}": i for i in range(24)}
        report = quality.drift_report(b, quality.ShadowRef(a, "inst"))
        assert report["recall_vs_retrain"] is None
        assert quality.breached_metrics(report) == []


# ---------------------------------------------------------------------------
# answer differ (replay + canary shared currency)
# ---------------------------------------------------------------------------

class TestCompareAnswers:
    def test_ranked_overlap_and_score_delta(self):
        base = {"itemScores": [{"item": "a", "score": 1.0},
                               {"item": "b", "score": 0.8},
                               {"item": "c", "score": 0.6}]}
        cand = {"itemScores": [{"item": "a", "score": 1.1},
                               {"item": "c", "score": 0.7},
                               {"item": "d", "score": 0.5}]}
        diff = quality.compare_answers(base, cand, k=3)
        assert diff["overlap"] == pytest.approx(2 / 3, abs=1e-4)
        assert diff["score_delta"] == pytest.approx(0.1, abs=1e-6)

    def test_identical_ranked_answers(self):
        a = {"itemScores": [{"item": "x", "score": 2.0}]}
        assert quality.compare_answers(a, a) == {
            "overlap": 1.0, "score_delta": 0.0}

    def test_scalar_answers_compare_by_value(self):
        assert quality.compare_answers(
            {"result": 6.0}, {"result": 6.0})["overlap"] == 1.0
        diff = quality.compare_answers({"result": 6.0}, {"result": 8.0})
        assert diff["overlap"] == 0.0
        assert diff["score_delta"] == pytest.approx(2.0)

    def test_empty_baseline_cannot_be_missed(self):
        assert quality.compare_answers(
            {"itemScores": []},
            {"itemScores": [{"item": "a", "score": 1.0}]})["overlap"] == 1.0


# ---------------------------------------------------------------------------
# canary verdict math
# ---------------------------------------------------------------------------

def _observe_lane(lane, seconds, n):
    child = quality.CANARY_SECONDS.labels(lane)
    for _ in range(n):
        child.observe(seconds)


class TestCanaryVerdict:
    @pytest.fixture(autouse=True)
    def _fresh_lanes(self):
        quality.STATE.canary_begin("r9", "base", "cand")  # resets lanes
        yield
        quality.STATE.canary_end("test_done", None)

    def test_undecided_until_min_pairs(self, monkeypatch):
        monkeypatch.setenv("PIO_CANARY_MIN_PAIRS", "5")
        _observe_lane("baseline", 0.01, 10)
        _observe_lane("canary", 0.01, 10)
        for _ in range(3):
            quality.STATE.add_paired({"overlap": 1.0, "score_delta": 0.0})
        assert quality.STATE.canary_verdict()["verdict"] == "undecided"

    def test_clean_candidate_promotes(self, monkeypatch):
        monkeypatch.setenv("PIO_CANARY_MIN_PAIRS", "5")
        _observe_lane("baseline", 0.01, 20)
        _observe_lane("canary", 0.012, 20)
        for _ in range(8):
            quality.STATE.add_paired({"overlap": 0.9, "score_delta": 0.01})
        verdict = quality.STATE.canary_verdict()
        assert verdict["verdict"] == "promote", verdict

    def test_low_overlap_rolls_back(self, monkeypatch):
        monkeypatch.setenv("PIO_CANARY_MIN_PAIRS", "5")
        _observe_lane("baseline", 0.01, 20)
        _observe_lane("canary", 0.01, 20)
        for _ in range(8):
            quality.STATE.add_paired({"overlap": 0.1, "score_delta": 2.0})
        verdict = quality.STATE.canary_verdict()
        assert verdict["verdict"] == "rollback"
        assert any("quality" in r for r in verdict["reasons"])

    def test_latency_burn_rolls_back_via_slo_math(self, monkeypatch):
        # canary answers blow the serving-latency threshold while the
        # baseline stays clean: the latency gate (the same bucket→burn
        # math obs/slo.py uses) must fail the candidate even though
        # every paired ANSWER matches perfectly
        monkeypatch.setenv("PIO_CANARY_MIN_PAIRS", "5")
        monkeypatch.setenv("PIO_SLO_LATENCY_MS", "100")
        _observe_lane("baseline", 0.01, 40)
        _observe_lane("canary", 0.5, 40)
        for _ in range(8):
            quality.STATE.add_paired({"overlap": 1.0, "score_delta": 0.0})
        verdict = quality.STATE.canary_verdict()
        assert verdict["verdict"] == "rollback"
        assert any("latency" in r for r in verdict["reasons"])
        assert verdict["latency"]["canary"]["over_threshold_rate"] == 1.0
        assert verdict["latency"]["baseline"]["over_threshold_rate"] == 0.0

    def test_burning_baseline_does_not_blame_the_canary(self, monkeypatch):
        # shared pain: both lanes equally slow — the RELATIVE gate must
        # not roll back a candidate for the fleet's pre-existing burn
        monkeypatch.setenv("PIO_CANARY_MIN_PAIRS", "5")
        monkeypatch.setenv("PIO_SLO_LATENCY_MS", "100")
        _observe_lane("baseline", 0.5, 40)
        _observe_lane("canary", 0.5, 40)
        for _ in range(8):
            quality.STATE.add_paired({"overlap": 1.0, "score_delta": 0.0})
        assert quality.STATE.canary_verdict()["verdict"] == "promote"

    def test_paired_errors_roll_back(self, monkeypatch):
        monkeypatch.setenv("PIO_CANARY_MIN_PAIRS", "5")
        _observe_lane("baseline", 0.01, 20)
        _observe_lane("canary", 0.01, 20)
        for _ in range(6):
            quality.STATE.add_paired({"overlap": 1.0, "score_delta": 0.0})
        for _ in range(4):
            quality.STATE.add_paired(None, error="canary answered 500")
        assert quality.STATE.canary_verdict()["verdict"] == "rollback"


# ---------------------------------------------------------------------------
# flight recorder payload capture
# ---------------------------------------------------------------------------

class TestPayloadCapture:
    def test_capture_off_by_default(self):
        rec = flight.FlightRecorder(capacity=8)
        assert not rec.record_payload("/queries.json", {"user": "u"})
        assert rec.payloads() == []
        dump = rec.dump()
        assert "payloads" not in dump
        assert dump["payload_capture"] == {
            "capacity": 0, "captured": 0, "included": False}

    def test_bounded_capture_and_byte_cap(self, monkeypatch):
        monkeypatch.setenv("PIO_FLIGHT_PAYLOADS", "3")
        monkeypatch.setenv("PIO_FLIGHT_PAYLOAD_BYTES", "64")
        rec = flight.FlightRecorder(capacity=8)
        for k in range(5):
            assert rec.record_payload("/queries.json", {"user": f"u{k}"})
        # count cap: only the newest 3 stay
        got = [p["payload"]["user"] for p in rec.payloads()]
        assert got == ["u2", "u3", "u4"]
        # oversized payload skipped + counted
        skipped = metrics.REGISTRY.get(
            "pio_flight_payloads_skipped_total").value
        assert not rec.record_payload("/queries.json",
                                      {"blob": "x" * 500})
        assert metrics.REGISTRY.get(
            "pio_flight_payloads_skipped_total").value == skipped + 1
        # dump carries bodies only when explicitly included
        assert "payloads" not in rec.dump()
        dump = rec.dump(include_payloads=True)
        assert [p["payload"]["user"] for p in dump["payloads"]] == got

    def test_admin_flight_redacts_without_token(self, memory_storage,
                                                monkeypatch):
        from predictionio_tpu.serving.engine_server import EngineServer
        from tests.test_health import get_json, train_const

        monkeypatch.setenv("PIO_FLIGHT_PAYLOADS", "8")
        flight.RECORDER.clear()
        engine, _ = train_const(memory_storage)
        server = EngineServer(engine, "const", host="127.0.0.1", port=0,
                              storage=memory_storage).start()
        base = f"http://127.0.0.1:{server.port}"
        try:
            req = urllib.request.Request(
                base + "/queries.json", data=b'{"mult": 2}',
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=10) as resp:
                resp.read()
            # no token configured: counts only, bodies redacted
            status, dump = get_json(base + "/admin/flight")
            assert status == 200
            assert dump["payload_capture"]["captured"] == 1
            assert not dump["payload_capture"]["included"]
            assert "payloads" not in dump
            # token configured AND presented: the bodies travel
            monkeypatch.setenv("PIO_ADMIN_TOKEN", "s3cret")
            status, dump = get_json(
                base + "/admin/flight",
                headers={"Authorization": "Bearer s3cret"})
            assert status == 200
            assert dump["payloads"][0]["payload"] == {"mult": 2}
        finally:
            server.stop()
            flight.RECORDER.clear()


# ---------------------------------------------------------------------------
# replay harness
# ---------------------------------------------------------------------------

class TestReplayHarness:
    def test_replay_diffs_and_registers(self):
        from predictionio_tpu.workflow import replay as replay_mod

        def baseline(payload):
            return {"itemScores": [{"item": "a", "score": 1.0},
                                   {"item": "b", "score": 0.5}]}, 0.001

        def candidate(payload):
            if payload.get("user") == "drifted":
                return {"itemScores": [{"item": "z", "score": 9.0},
                                       {"item": "y", "score": 8.0}]}, 0.002
            return {"itemScores": [{"item": "a", "score": 1.0},
                                   {"item": "b", "score": 0.5}]}, 0.002

        payloads = [{"payload": {"user": "ok1"}},
                    {"payload": {"user": "ok2"}},
                    {"payload": {"user": "drifted"}}]
        report = replay_mod.replay(payloads, candidate, baseline, k=2)
        assert report["n"] == 3 and report["diffed"] == 3
        assert report["mean_overlap"] == pytest.approx(2 / 3, abs=1e-4)
        assert report["worst_overlap"] == 0.0
        assert report["latency_ms"]["baseline"]["p50_ms"] > 0
        # registered as THE replay report /admin/quality serves
        assert quality.STATE.report()["replay"]["n"] == 3
        # per-query examples carry the diff
        drifted = [q for q in report["queries"]
                   if q["payload"]["user"] == "drifted"]
        assert drifted[0]["overlap"] == 0.0

    def test_lane_errors_are_counted_not_raised(self):
        from predictionio_tpu.workflow import replay as replay_mod

        def baseline(payload):
            return {"result": 1.0}, 0.001

        def flaky(payload):
            raise ConnectionError("candidate down")

        report = replay_mod.replay([{"payload": {}}] * 3, flaky, baseline,
                                   register=False)
        assert report["errors"] == {"baseline": 0, "candidate": 3}
        assert report["diffed"] == 0 and report["mean_overlap"] is None

    def test_end_to_end_over_live_servers(self, memory_storage,
                                          monkeypatch):
        """Capture real payloads through a live engine server, replay
        them server-vs-server, and read the report back off
        GET /admin/quality — the whole harness in one pass."""
        from predictionio_tpu.serving.engine_server import EngineServer
        from predictionio_tpu.workflow import replay as replay_mod
        from tests.test_health import get_json, train_const

        monkeypatch.setenv("PIO_FLIGHT_PAYLOADS", "16")
        monkeypatch.setenv("PIO_ADMIN_TOKEN", "tok")
        flight.RECORDER.clear()
        engine, _ = train_const(memory_storage)
        server = EngineServer(engine, "const", host="127.0.0.1", port=0,
                              storage=memory_storage).start()
        base = f"http://127.0.0.1:{server.port}"
        try:
            for mult in (2, 3, 4):
                req = urllib.request.Request(
                    base + "/queries.json",
                    data=json.dumps({"mult": mult}).encode(),
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=10) as resp:
                    resp.read()
            report = replay_mod.replay_urls(base, base)
            assert report["n"] == 3
            assert report["mean_overlap"] == 1.0
            assert report["errors"] == {"baseline": 0, "candidate": 0}
            status, served = get_json(
                base + "/admin/quality",
                headers={"Authorization": "Bearer tok"})
            assert status == 200
            assert served["replay"]["mean_overlap"] == 1.0
        finally:
            server.stop()
            flight.RECORDER.clear()

    def test_fetch_payloads_explains_redaction(self, memory_storage,
                                               monkeypatch):
        from predictionio_tpu.serving.engine_server import EngineServer
        from predictionio_tpu.workflow import replay as replay_mod
        from tests.test_health import train_const

        monkeypatch.delenv("PIO_ADMIN_TOKEN", raising=False)
        engine, _ = train_const(memory_storage)
        server = EngineServer(engine, "const", host="127.0.0.1", port=0,
                              storage=memory_storage).start()
        try:
            with pytest.raises(RuntimeError, match="PIO_ADMIN_TOKEN"):
                replay_mod.fetch_payloads(
                    f"http://127.0.0.1:{server.port}")
        finally:
            server.stop()


# ---------------------------------------------------------------------------
# drift → rolling-reload trigger (e2e on the real fold lane)
# ---------------------------------------------------------------------------

class TestDriftReloadTrigger:
    @pytest.fixture()
    def world(self, tmp_path):
        storage = make_storage("eventlog", tmp_path)
        set_storage(storage)
        app = storage.apps().insert("stream")
        storage.events().init(app.id)
        _seed_world(storage, app.id)
        yield storage, app.id
        set_storage(None)

    def test_breach_fires_reload_exactly_once(self, world, monkeypatch):
        from predictionio_tpu.workflow.stream import StreamUpdater

        storage, app_id = world
        monkeypatch.setenv("PIO_QUALITY_EVERY", "1")
        engine, instance = _train_reco(storage, engine_id="drift_rl",
                                       iterations=4)
        fired = []
        updater = StreamUpdater(engine, "drift_rl", storage=storage,
                                instance=instance,
                                reload_trigger=lambda: fired.append(1))
        reloads_before = metrics.REGISTRY.get(
            "pio_quality_reloads_total").value

        # healthy fold: probe runs (cadence 1) and stays inside band
        storage.events().insert_batch([_rate("q_u0", "i1", 4.0)], app_id)
        stats = updater.poll_once()
        assert stats["published"]
        assert stats["quality"]["breached"] == []
        assert not fired

        # corrupt the streamed model (what a buggy fold lane would do)
        folder = updater._folders[0]
        folder.model.user_factors = folder.model.user_factors * 9.0 + 2.0
        report = updater.probe_quality()
        assert report["breached"], report
        assert len(fired) == 1
        assert metrics.REGISTRY.get(
            "pio_quality_reloads_total").value == reloads_before + 1
        # the breach auto-resynced the updater onto the bound instance:
        # its model matches the shadow again
        assert updater.probe_quality()["breached"] == []

        # SAME instance, drift again: the latch holds — no reload storm
        # while the retrain is in flight
        folder = updater._folders[0]
        folder.model.user_factors = folder.model.user_factors * 9.0 + 2.0
        report = updater.probe_quality()
        assert report["breached"]
        assert len(fired) == 1, "second breach in the episode re-fired"

        # a NEW trained instance re-arms the trigger
        _train_reco(storage, engine_id="drift_rl", iterations=4)
        updater.resync()
        folder = updater._folders[0]
        folder.model.user_factors = folder.model.user_factors * 9.0 + 2.0
        assert updater.probe_quality()["breached"]
        assert len(fired) == 2

    def test_breach_reloads_live_server_over_http(self, world,
                                                  monkeypatch):
        """The default HTTP trigger: a breach GETs /reload on the
        configured URL — the serving side rolls back to the last full
        retrain while the streamer resyncs."""
        from predictionio_tpu.serving.engine_server import EngineServer
        from predictionio_tpu.workflow.stream import StreamUpdater

        storage, app_id = world
        monkeypatch.setenv("PIO_QUALITY_EVERY", "1")
        engine, instance = _train_reco(storage, engine_id="drift_http",
                                       iterations=4)
        server = EngineServer(engine, "drift_http", host="127.0.0.1",
                              port=0, storage=storage).start()
        try:
            url = f"http://127.0.0.1:{server.port}"
            updater = StreamUpdater(engine, "drift_http", storage=storage,
                                    instance=instance,
                                    patch_servers=[server],
                                    reload_urls=[url])
            reloads = []
            orig_reload = server.reload
            server.reload = lambda *a, **k: (reloads.append(a),
                                             orig_reload(*a, **k))[1]
            folder = updater._folders[0]
            folder.model.user_factors = folder.model.user_factors * 9.0
            report = updater.probe_quality()
            assert report["breached"]
            # the server's /reload lane ran exactly once, rolling it
            # back onto the last full retrain (same instance id — the
            # rollback IS the point)
            assert len(reloads) == 1
            assert server.deployment.instance.id == instance.id
        finally:
            server.stop()


# ---------------------------------------------------------------------------
# drift probe through the real fold lane (fold stays inside the band)
# ---------------------------------------------------------------------------

class TestFoldQualityProbe:
    def test_honest_folds_stay_inside_band(self, tmp_path, monkeypatch):
        from predictionio_tpu.workflow.stream import StreamUpdater

        storage = make_storage("eventlog", tmp_path)
        set_storage(storage)
        try:
            app = storage.apps().insert("stream")
            storage.events().init(app.id)
            _seed_world(storage, app.id)
            monkeypatch.setenv("PIO_QUALITY_EVERY", "1")
            engine, instance = _train_reco(storage, engine_id="drift_ok")
            updater = StreamUpdater(engine, "drift_ok", storage=storage,
                                    instance=instance)
            rng = np.random.default_rng(4)
            delta = [_rate(f"u{int(rng.integers(0, 40))}",
                           f"i{int(rng.integers(0, 25))}",
                           float(rng.integers(2, 11)) / 2.0)
                     for _ in range(40)]
            storage.events().insert_batch(delta, app.id)
            stats = updater.poll_once()
            assert stats["published"]
            q = stats["quality"]
            # real fold-in moves factors a little, never outside band
            assert q["breached"] == []
            assert q["recall_vs_retrain"] > 0.9
            assert q["rmse_drift"] < 0.1
        finally:
            set_storage(None)


# ---------------------------------------------------------------------------
# dashboard /quality panel
# ---------------------------------------------------------------------------

class TestDashboardQualityPanel:
    def test_panel_renders_the_one_state(self, memory_storage):
        from predictionio_tpu.tools.dashboard import DashboardServer
        from tests.test_health import get

        dash = DashboardServer(storage=memory_storage, host="127.0.0.1",
                               port=0).start()
        base = f"http://127.0.0.1:{dash.port}"
        try:
            status, body, _ = get(base + "/quality")
            assert status == 200
            assert "no drift probe yet" in body
            assert "no replay report yet" in body
            quality.publish_drift(
                {"recall_vs_retrain": 0.8, "rmse_drift": 0.5,
                 "factor_drift": 0.01, "shadow_instance": "shadow_y",
                 "sampled_users": 4})
            quality.STATE.set_replay(
                {"n": 7, "diffed": 7, "mean_overlap": 0.93,
                 "worst_overlap": 0.5, "mean_score_delta": 0.01,
                 "errors": {"baseline": 0, "candidate": 0}})
            status, body, _ = get(base + "/quality")
            assert status == 200
            assert "BREACHED" in body and "rmse_drift" in body
            assert "0.93" in body
            status, body, _ = get(base + "/")
            assert 'href="/quality"' in body
        finally:
            dash.stop()


# -- review regressions --------------------------------------------------------

def test_all_error_candidate_reaches_rollback(monkeypatch):
    """A candidate that 500s EVERY request produces only pair errors
    and zero canary-lane answers — it must reach the rollback verdict,
    not hide behind "insufficient data" forever."""
    monkeypatch.setenv("PIO_CANARY_MIN_PAIRS", "5")
    quality.STATE.canary_begin("r1", "base", "cand")
    _observe_lane("baseline", 0.01, 10)
    for _ in range(6):
        quality.STATE.add_paired(None, error="canary answered 500")
    verdict = quality.STATE.canary_verdict()
    assert verdict["verdict"] == "rollback"
    assert any("paired canary errors" in r for r in verdict["reasons"])
    quality.STATE.canary_end("test_done", None)


def test_admin_quality_report_strips_replayed_payloads():
    """The replay report's per-query examples carry RAW captured
    payloads (user data): /admin/quality serves aggregates only, same
    contract as /admin/flight's redaction."""
    from predictionio_tpu.workflow import replay as replay_mod

    def target(payload):
        return {"result": 1.0}, 0.001

    report = replay_mod.replay(
        [{"payload": {"user": "secret-u", "ssn-ish": "data"}}],
        target, target)
    assert report["queries"], "the CLI-side report keeps the examples"
    served = quality.STATE.report()["replay"]
    assert "queries" not in served
    assert served["n"] == 1 and served["mean_overlap"] == 1.0
    assert "secret-u" not in json.dumps(quality.STATE.report())
