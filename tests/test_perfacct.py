"""Performance accounting (obs/perfacct.py + obs/timeline.py and the
serving/CLI wiring): MFU gauges from cost_analysis with the analytic
fallback, data-path ledger + staleness monotonicity across a train
publish, tail-latency attribution arithmetic, timeline ring eviction
and cadence, the /admin/timeline + /admin/tail auth matrix, and the
`pio top --once --json` output shape."""

import json
import time
import urllib.error
import urllib.request
from dataclasses import dataclass

import numpy as np
import pytest

from predictionio_tpu.core import (
    Algorithm,
    DataSource,
    Engine,
    FirstServing,
    IdentityPreparator,
)
from predictionio_tpu.core.params import EngineParams, Params
from predictionio_tpu.obs import flight, metrics, perfacct, timeline
from predictionio_tpu.obs.flight import FlightRecorder
from predictionio_tpu.obs.perfacct import (
    DataPathLedger,
    StepAccountant,
    tail_report,
    twotower_matmul_flops,
)
from predictionio_tpu.obs.timeline import Timeline, sparkline
from predictionio_tpu.workflow.train import run_train


def http(method, url, body=None, headers=None, timeout=15):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, dict(resp.headers), resp.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), e.read().decode()


@pytest.fixture(autouse=True)
def _reset_perfacct():
    """The ledger and timeline are process-global; each test starts
    from a clean clock and empty rings."""
    perfacct.LEDGER.clear()
    timeline.TIMELINE.clear()
    yield
    perfacct.LEDGER.clear()
    timeline.TIMELINE.clear()


# ---------------------------------------------------------------------------
# MFU: cost_analysis path + analytic fallback
# ---------------------------------------------------------------------------

@pytest.fixture
def v5e_peaks(monkeypatch):
    """Steer the accounting onto the v5e row: on the CPU backend no
    utilisation is computed, so tests of the MFU arithmetic and its
    surfaces hand it the chip's peaks themselves."""
    monkeypatch.setattr(perfacct, "device_peaks",
                        lambda: perfacct.DEVICE_PEAKS["TPU v5 lite"])


def test_no_utilisation_on_cpu_and_unknown_tpu_kind_raises(monkeypatch):
    """CPU backend: no MFU, no roofline position, no gauge child — not
    one against a v5e peak. A TPU whose kind has no row is an error."""
    import jax

    assert perfacct.device_peaks() is None
    assert perfacct.mfu(1e9, 0.01) is None
    acct = StepAccountant("cpu-only-model", 1e9, 1e6)
    assert acct.observe(0.01) is None and acct.last_mfu is None
    exported = metrics.REGISTRY.render()
    assert 'pio_train_mfu{model="cpu-only-model"}' not in exported
    assert 'pio_roofline_position{model="cpu-only-model"}' not in exported

    class FakeTpu:
        platform = "tpu"
        device_kind = "TPU v9 imaginary"

    monkeypatch.setattr(jax, "devices", lambda *a: [FakeTpu()])
    with pytest.raises(LookupError, match="TPU v9 imaginary"):
        perfacct.device_peaks()
    FakeTpu.device_kind = "TPU v5 lite"
    assert perfacct.device_peaks().bf16_flops == 197e12


def test_costs_from_compiled_real_cpu_executable():
    """A real CPU-compiled step: cost_analysis either reports flops
    (the primary path) or the helper declines with None — it must
    never raise on any backend."""
    import jax
    import jax.numpy as jnp

    compiled = jax.jit(lambda x: x @ x).lower(jnp.ones((16, 16))).compile()
    costs = perfacct.costs_from_compiled(compiled)
    if costs is not None:
        flops, bytes_accessed = costs
        assert flops > 0 and bytes_accessed >= 0


def test_accountant_falls_back_when_cost_analysis_fails(v5e_peaks):
    class Boom:
        def cost_analysis(self):
            raise RuntimeError("no cost model on this backend")

    acct = StepAccountant.from_compiled("fallback-model", Boom(),
                                        fallback_flops=2.5e9,
                                        fallback_bytes=1e6)
    assert acct.source == "analytic"
    assert acct.flops_per_step == 2.5e9
    mfu = acct.observe(0.01)
    assert mfu > 0
    fam = metrics.REGISTRY.get("pio_train_mfu")
    assert fam.labels("fallback-model").value == pytest.approx(mfu)
    assert metrics.REGISTRY.get("pio_step_flops").labels(
        "fallback-model").value == 2.5e9
    # bytes known -> the roofline-position gauge is set
    assert metrics.REGISTRY.get("pio_roofline_position").labels(
        "fallback-model").value > 0


def test_accountant_empty_cost_analysis_also_falls_back():
    class Empty:
        def cost_analysis(self):
            return [{}]  # jax returning nothing usable

    acct = StepAccountant.from_compiled("empty-model", Empty(),
                                        fallback_flops=1e6)
    assert acct.source == "analytic"


def test_twotower_matmul_flops_matches_trainer_method():
    """The one-formula contract: the trainer's method delegates to
    the shared perfacct formula."""
    from predictionio_tpu.ops.twotower import (
        TwoTowerConfig,
        TwoTowerTrainer,
        _tail_widths,
    )

    rng = np.random.default_rng(0)
    u, i = rng.integers(0, 8, 64), rng.integers(0, 8, 64)
    cfg = TwoTowerConfig(dim=4, batch_size=16, epochs=1)
    trainer = TwoTowerTrainer((u, i, None), 8, 8, cfg)
    assert trainer.matmul_flops_per_step() == twotower_matmul_flops(
        trainer.batch, cfg.dim, _tail_widths(cfg))


def test_twotower_run_populates_live_mfu_gauge(v5e_peaks):
    """Acceptance: a CPU train run sets pio_train_mfu > 0 via either
    the cost-analysis or the analytic fallback path."""
    from predictionio_tpu.ops.twotower import TwoTowerConfig, TwoTowerTrainer

    rng = np.random.default_rng(1)
    u, i = rng.integers(0, 8, 64), rng.integers(0, 8, 64)
    trainer = TwoTowerTrainer((u, i, None), 8, 8,
                              TwoTowerConfig(dim=4, batch_size=16, epochs=2))
    trainer.run()
    assert trainer._acct is not None
    assert trainer._acct.source in ("cost_analysis", "analytic")
    assert metrics.REGISTRY.get("pio_train_mfu").labels(
        "twotower").value > 0


# ---------------------------------------------------------------------------
# data-path ledger + staleness clock
# ---------------------------------------------------------------------------

def test_staleness_monotonic_then_drops_across_publish():
    ledger = DataPathLedger()
    assert ledger.staleness_seconds(now=50.0) == 0.0  # nothing ingested
    ledger.note_ingest(ts=100.0)
    # grows monotonically while the events wait for a model
    assert ledger.staleness_seconds(now=110.0) == pytest.approx(10.0)
    assert ledger.staleness_seconds(now=130.0) == pytest.approx(30.0)
    ledger.note_train_read(ts=140.0)   # the model will cover ts<=100
    ledger.note_publish(ts=150.0)
    # everything ingested is now servable: clock back to zero
    assert ledger.staleness_seconds(now=160.0) == 0.0


def test_staleness_events_arriving_during_train():
    ledger = DataPathLedger()
    ledger.note_ingest(ts=100.0)
    ledger.note_train_read(ts=110.0)   # horizon will be 100
    ledger.note_ingest(ts=115.0)       # lands mid-train
    ledger.note_publish(ts=120.0)
    # the mid-train event is NOT covered: it waits from the horizon
    # boundary (the ledger's documented approximation)
    assert ledger.staleness_seconds(now=130.0) == pytest.approx(30.0)
    ledger.note_train_read(ts=140.0)
    ledger.note_publish(ts=150.0)
    assert ledger.staleness_seconds(now=160.0) == 0.0


def test_ledger_stage_accumulation_and_gauge():
    ledger = DataPathLedger()
    ledger.start_run("run-1")
    ledger.note_stage("read", 1.5)
    ledger.note_stage("bin_cache_load", 0.25)
    ledger.note_stage("bin_cache_load", 0.25)  # additive (two sides)
    snap = ledger.snapshot()
    assert snap["runs"][-1]["run"] == "run-1"
    assert snap["runs"][-1]["stages"] == {
        "read": 1.5, "bin_cache_load": 0.5}


def test_stage_gauge_resets_per_run():
    """The gauge describes the CURRENT run: a warm run that skips
    compile must not keep exporting the cold run's compile seconds."""
    ledger = DataPathLedger()
    ledger.start_run("cold")
    ledger.note_stage("compile", 12.0)
    family = metrics.REGISTRY.get("pio_datapath_stage_seconds")
    assert family.labels("compile").value == 12.0
    ledger.start_run("warm")
    ledger.note_stage("read", 0.5)
    stages = {vals[0]: c.value for vals, c in family.children()}
    assert "compile" not in stages
    assert stages["read"] == 0.5
    # run history keeps the cold run's full story
    assert ledger.snapshot()["runs"][0]["stages"]["compile"] == 12.0


def test_sqlite_insert_batch_notes_ingest(tmp_path):
    """Every bulk storage writer feeds the freshness clock — the
    sqlite transaction lane included."""
    from predictionio_tpu.data.event import Event
    from predictionio_tpu.data.storage import Storage

    st = Storage.from_env({
        "PIO_STORAGE_SOURCES_S_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_S_PATH": str(tmp_path / "store"),
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_NAME": "events",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "S",
        "PIO_STORAGE_REPOSITORIES_METADATA_NAME": "meta",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "S",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_NAME": "models",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "S",
    })
    st.events().init(1)
    st.events().insert_batch(
        [Event(event="rate", entity_type="user", entity_id="u1")], 1)
    assert perfacct.LEDGER.staleness_seconds() >= 0.0
    snap = perfacct.LEDGER.snapshot()
    assert snap["last_ingest_unix"] is not None


def test_run_train_feeds_ledger_and_staleness(memory_storage):
    """Acceptance: across a fake-workflow train publish the staleness
    gauge DECREASES, and the run's ledger carries the pipeline
    stages."""
    from predictionio_tpu.data.event import Event

    @dataclass
    class P(Params):
        pass

    class DS(DataSource):
        def read_training(self, ctx):
            return 1.0

    class Algo(Algorithm):
        def train(self, ctx, pd):
            return pd + 1.0

        def predict(self, model, query):
            return {"result": model}

    # ingest through the storage API: the base insert_batch notes the
    # freshness clock
    memory_storage.events().init(1)
    memory_storage.events().insert_batch(
        [Event(event="rate", entity_type="user", entity_id="u1",
               target_entity_type="item", target_entity_id="i1",
               properties={"rating": 4.0})], 1)
    time.sleep(0.05)
    before = perfacct.LEDGER.staleness_seconds()
    assert before > 0.0

    engine = Engine(DS, IdentityPreparator, {"algo": Algo}, FirstServing)
    ep = EngineParams(
        data_source_params=("", P()),
        preparator_params=("", None),
        algorithm_params_list=[("algo", P())],
        serving_params=("", None),
    )
    instance = run_train(engine, ep, engine_id="perfacct",
                         storage=memory_storage)
    after = perfacct.LEDGER.staleness_seconds()
    assert after < before
    assert after == 0.0  # nothing arrived during the train
    assert metrics.REGISTRY.get(
        "pio_model_staleness_seconds").labels().value == 0.0
    # the run's stage ledger: read/prepare/fit from Engine.train, the
    # whole-train wall from workflow/train.py
    snap = perfacct.LEDGER.snapshot()
    run = next(r for r in snap["runs"] if r["run"] == instance.id)
    for stage in ("read", "prepare", "fit", "train"):
        assert stage in run["stages"], (stage, run["stages"])
    assert snap["model_horizon_unix"] is not None


# ---------------------------------------------------------------------------
# tail-latency attribution
# ---------------------------------------------------------------------------

def _synthetic_records():
    """19 fast requests dominated by dispatch + 1 slow one dominated by
    queue wait: the tail answer must be 'queue'."""
    records = []
    for i in range(19):
        d = 10.0 + i * 0.1
        records.append({"duration_ms": d, "stages": {
            "parse": 0.1, "queue": d * 0.2, "dispatch": d * 0.6,
            "serialize": 0.1,
            "unattributed": d - 0.2 - d * 0.8}})
    d = 100.0
    records.append({"duration_ms": d, "stages": {
        "parse": 0.1, "queue": 90.0, "dispatch": 8.0, "serialize": 0.1,
        "unattributed": 1.8}})
    return records


def test_tail_report_arithmetic():
    report = tail_report(_synthetic_records(), q=0.95)
    assert report["total_count"] == 20
    assert report["tail_count"] >= 1
    assert report["threshold_ms"] == pytest.approx(100.0)
    stages = report["stages"]
    # shares are in [0, 1], never negative, and ~sum to 1 for the tail
    tail_sum = sum(s["tail_share"] for s in stages.values())
    assert tail_sum == pytest.approx(1.0, abs=0.01)
    for s in stages.values():
        assert s["tail_share"] >= 0.0 and s["median_share"] >= 0.0
    # acceptance: >= 95% of above-p95 time attributed to NAMED stages
    assert report["attributed_tail_share"] >= 0.95
    assert report["dominant_tail_stage"] == "queue"
    # the answer differs from the median cohort: queue GROWS in the
    # tail, dispatch shrinks
    assert stages["queue"]["delta_share"] > 0.5
    assert stages["dispatch"]["delta_share"] < 0.0


def test_tail_report_needs_enough_records():
    report = tail_report([{"duration_ms": 1.0, "stages": {}}], q=0.95)
    assert report["tail_count"] == 0 and report["stages"] == {}


def test_tail_report_rejects_bad_quantile():
    with pytest.raises(ValueError):
        tail_report([], q=1.5)


def test_negative_remainder_clamped_and_counted():
    """Satellite: attributed stages exceeding the wall total clamp the
    unattributed remainder to 0 (never negative) and count the clamp in
    pio_flight_negative_remainder_total."""
    counter = metrics.REGISTRY.get("pio_flight_negative_remainder_total")
    before = counter.labels().value
    rec = FlightRecorder(capacity=4)
    key = rec.begin("neg1", "S", "POST", "/q")
    rec.note_stage("dispatch", 10.0, trace_id="neg1")  # 10s >> wall time
    record = rec.finish(key, 200)
    assert record["stages"]["unattributed"] == 0.0
    assert counter.labels().value == before + 1
    # tail attribution over such records stays non-negative
    report = tail_report([record] * 6, q=0.5)
    for s in report["stages"].values():
        assert s["tail_share"] >= 0.0


# ---------------------------------------------------------------------------
# timeline ring
# ---------------------------------------------------------------------------

def test_timeline_ring_eviction_and_capacity():
    t = Timeline(interval=0.0, capacity=3,
                 collectors=[lambda now: {"x": now}])
    for i in range(5):
        assert t.sample(now=float(i), force=True)
    points = t.series()["series"]["x"]
    assert [p[0] for p in points] == [2.0, 3.0, 4.0]  # oldest evicted


def test_timeline_cadence_rate_limits():
    t = Timeline(interval=100.0, capacity=8,
                 collectors=[lambda now: {"x": 1.0}])
    assert t.sample(now=1000.0)
    assert not t.sample(now=1050.0)        # inside the interval: no-op
    assert t.sample(now=1101.0)            # past it: sampled
    assert t.sample(now=1102.0, force=True)  # force bypasses the cadence
    assert len(t.series()["series"]["x"]) == 3


def test_timeline_env_cadence_read_per_sample(monkeypatch):
    t = Timeline(capacity=4, collectors=[lambda now: {"x": 1.0}])
    monkeypatch.setenv("PIO_TIMELINE_INTERVAL_SEC", "0")
    assert t.sample(now=1.0) and t.sample(now=1.1)
    monkeypatch.setenv("PIO_TIMELINE_INTERVAL_SEC", "3600")
    assert not t.sample(now=2.0)


def test_timeline_broken_collector_isolated():
    def boom(now):
        raise RuntimeError("broken probe")

    t = Timeline(interval=0.0, capacity=4,
                 collectors=[boom, lambda now: {"ok": 7.0}])
    assert t.sample(now=1.0, force=True)
    assert t.series()["series"]["ok"] == [[1.0, 7.0]]


def test_default_collectors_pick_up_mfu_and_staleness(v5e_peaks):
    StepAccountant("twotower", 1e9).observe(0.01)
    perfacct.LEDGER.note_ingest()
    t = Timeline(interval=0.0, capacity=8)
    t.sample(force=True)
    series = t.series()["series"]
    assert "mfu.twotower" in series and series["mfu.twotower"][-1][1] > 0
    assert "staleness_sec" in series


def test_timeline_staleness_grows_between_notes():
    """The staleness collector ASKS the ledger at the sample instant:
    the series (and the gauge) must keep growing while events wait,
    not freeze at the last ingest note's value."""
    perfacct.LEDGER.note_ingest(ts=100.0)
    t = Timeline(interval=0.0, capacity=8)
    t.sample(now=110.0, force=True)
    t.sample(now=150.0, force=True)
    points = t.series()["series"]["staleness_sec"]
    assert points[0][1] == pytest.approx(10.0)
    assert points[1][1] == pytest.approx(50.0)
    # sampling also refreshed the passive gauge for /metrics scrapes
    assert metrics.REGISTRY.get("pio_model_staleness_seconds").labels(
    ).value == pytest.approx(50.0)


def test_sparkline_shapes():
    assert sparkline([]) == ""
    assert sparkline([5.0, 5.0, 5.0]) == "▁▁▁"      # flat != empty
    line = sparkline(list(range(100)), width=10)
    assert len(line) == 10
    assert line[-1] == "█" and line[0] != "█"


# ---------------------------------------------------------------------------
# live server: /admin/timeline + /admin/tail (+ auth matrix)
# ---------------------------------------------------------------------------

@pytest.fixture()
def dash_server(memory_storage):
    from predictionio_tpu.tools.dashboard import DashboardServer

    server = DashboardServer(storage=memory_storage, host="127.0.0.1",
                             port=0).start()
    yield server
    server.stop()
    flight.RECORDER.clear()


def test_admin_timeline_collects_samples_at_test_cadence(dash_server, monkeypatch, v5e_peaks):
    """Acceptance: GET /admin/timeline returns >= 2 samples for a
    tracked gauge at the test cadence (interval 0 -> every read
    samples)."""
    monkeypatch.setenv("PIO_TIMELINE_INTERVAL_SEC", "0")
    StepAccountant("twotower", 1e9).observe(0.01)
    base = f"http://127.0.0.1:{dash_server.port}"
    for _ in range(2):
        status, _, body = http("GET", f"{base}/admin/timeline")
        assert status == 200
    payload = json.loads(body)
    assert len(payload["series"]["mfu.twotower"]) >= 2
    # the data-path ledger rides along
    assert "staleness_seconds" in payload["datapath"]


def test_admin_tail_serves_attribution(dash_server):
    # the requests driven here are themselves flight-recorded, so the
    # endpoint has real records to attribute
    base = f"http://127.0.0.1:{dash_server.port}"
    for _ in range(6):
        http("GET", f"{base}/healthz")          # not recorded (shared)
        http("GET", f"{base}/metrics")          # not recorded (shared)
        http("GET", f"{base}/")                 # recorded
    status, _, body = http("GET", f"{base}/admin/tail")
    assert status == 200
    report = json.loads(body)
    assert report["total_count"] >= 4
    for s in report["stages"].values():
        assert s["tail_share"] >= 0.0
    status, _, _ = http("GET", f"{base}/admin/tail?q=abc")
    assert status == 400


def test_admin_timeline_and_tail_auth_matrix(dash_server, monkeypatch):
    """PIO_ADMIN_TOKEN gates both new admin routes like every other
    /admin/* diagnostic; healthz/metrics stay open."""
    base = f"http://127.0.0.1:{dash_server.port}"
    monkeypatch.setenv("PIO_ADMIN_TOKEN", "s3cret")
    for route in ("/admin/timeline", "/admin/tail"):
        status, headers, _ = http("GET", base + route)
        assert status == 401
        assert headers.get("WWW-Authenticate") == "Bearer"
        status, _, _ = http("GET", base + route,
                            headers={"Authorization": "Bearer wrong"})
        assert status == 401
        status, _, _ = http("GET", base + route,
                            headers={"Authorization": "Bearer s3cret"})
        assert status == 200
    status, _, _ = http("GET", f"{base}/healthz")
    assert status == 200
    monkeypatch.delenv("PIO_ADMIN_TOKEN")
    status, _, _ = http("GET", f"{base}/admin/timeline")
    assert status == 200


def test_dashboard_timeline_panel_renders(dash_server, v5e_peaks):
    StepAccountant("twotower", 1e9).observe(0.01)
    base = f"http://127.0.0.1:{dash_server.port}"
    status, _, body = http("GET", f"{base}/timeline")
    assert status == 200
    assert "Metric timelines" in body and "Data-path ledger" in body


# ---------------------------------------------------------------------------
# pio top
# ---------------------------------------------------------------------------

def test_pio_top_once_json_shape(capsys, monkeypatch, v5e_peaks):
    monkeypatch.setenv("PIO_TIMELINE_INTERVAL_SEC", "0")
    StepAccountant("twotower", 1e9).observe(0.01)
    from predictionio_tpu.tools.cli import main

    assert main(["top", "--once", "--json"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert set(payload) >= {"interval_sec", "capacity", "series",
                            "datapath"}
    assert "mfu.twotower" in payload["series"]
    point = payload["series"]["mfu.twotower"][-1]
    assert isinstance(point, list) and len(point) == 2
    assert point[1] > 0


def test_pio_top_once_text_frame(capsys, monkeypatch, v5e_peaks):
    monkeypatch.setenv("PIO_TIMELINE_INTERVAL_SEC", "0")
    StepAccountant("twotower", 1e9).observe(0.01)
    perfacct.LEDGER.start_run("frame-run")
    perfacct.LEDGER.note_stage("train", 1.0)
    from predictionio_tpu.tools.cli import main

    assert main(["top", "--once"]) == 0
    out = capsys.readouterr().out
    assert "mfu.twotower" in out
    assert "model staleness" in out and "frame-run" in out


def test_pio_top_json_requires_once():
    from predictionio_tpu.tools.cli import main

    assert main(["top", "--json"]) == 1
