"""Health & SLO subsystem: probes, /healthz + /readyz on every server,
watchdogs, burn-rate math, OpenMetrics exemplars, the push path, and
the admin-auth matrix (obs/health.py, obs/slo.py, obs/push.py,
serving/http.py wiring)."""

import json
import logging
import os
import threading
import time
import urllib.error
import urllib.request

import pytest

from predictionio_tpu.core import (
    Algorithm,
    DataSource,
    Engine,
    FirstServing,
    IdentityPreparator,
)
from predictionio_tpu.core.params import EngineParams, Params
from predictionio_tpu.data.storage import Storage
from predictionio_tpu.obs import flight, health, metrics, push, slo, trace
from predictionio_tpu.serving import engine_server as engine_server_mod
from predictionio_tpu.serving.engine_server import EngineServer, MicroBatcher
from predictionio_tpu.serving.event_server import EventServer
from predictionio_tpu.serving.http import HTTPServerBase, JSONRequestHandler
from predictionio_tpu.serving.storage_server import StorageServer
from predictionio_tpu.tools.admin import AdminServer
from predictionio_tpu.tools.dashboard import DashboardServer
from predictionio_tpu.workflow.train import run_train


def get(url, headers=None, method="GET", body=None):
    req = urllib.request.Request(url, headers=headers or {}, method=method,
                                 data=body)
    try:
        with urllib.request.urlopen(req, timeout=10) as resp:
            return resp.status, resp.read().decode(), dict(resp.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode(), dict(e.headers)


def get_json(url, headers=None, method="GET", body=None):
    status, text, _ = get(url, headers, method, body)
    return status, json.loads(text or "null")


# -- probe registry ------------------------------------------------------------

def test_probe_status_transitions_and_aggregation():
    reg = health.HealthRegistry()
    state = {"status": health.OK}
    reg.register("flappy", lambda: health.ProbeResult(state["status"], "x"))
    reg.register("steady", lambda: health.ok("fine"))

    overall, detail = reg.run()
    assert overall == health.OK
    assert detail["flappy"]["status"] == "ok"
    assert detail["steady"]["latency_ms"] >= 0

    state["status"] = health.DEGRADED
    overall, detail = reg.run()
    assert overall == health.DEGRADED

    state["status"] = health.FAILED
    overall, detail = reg.run()
    assert overall == health.FAILED
    assert detail["flappy"]["reason"] == "x"


def test_raising_probe_is_failed_not_a_crash():
    reg = health.HealthRegistry()

    def boom():
        raise RuntimeError("backend exploded")

    reg.register("boom", boom)
    overall, detail = reg.run()
    assert overall == health.FAILED
    assert "backend exploded" in detail["boom"]["reason"]


def test_probe_registration_is_last_wins():
    reg = health.HealthRegistry()
    reg.register("p", lambda: health.failed("old"))
    reg.register("p", lambda: health.ok("new"))
    overall, detail = reg.run()
    assert overall == health.OK and detail["p"]["reason"] == "new"
    reg.unregister("p")
    assert reg.names() == []


def test_queue_depth_probe():
    assert health.queue_depth_probe(lambda: 2, 10)().status == health.OK
    deep = health.queue_depth_probe(lambda: 10, 10)()
    assert deep.status == health.DEGRADED and "10" in deep.reason
    assert health.queue_depth_probe(lambda: None, 10)().status == health.OK


def test_probe_results_land_in_metrics():
    reg = health.HealthRegistry()
    reg.register("metricated", lambda: health.degraded("meh"))
    reg.run()
    gauge = metrics.REGISTRY.get("pio_health_probe_status")
    assert gauge.labels("metricated").value == 1.0  # degraded rank


# -- /healthz + /readyz on every server ---------------------------------------

from dataclasses import dataclass


@dataclass
class ConstParams(Params):
    value: float = 1.0


class ConstDataSource(DataSource):
    def __init__(self, params: ConstParams):
        super().__init__(params)

    def read_training(self, ctx):
        return self.params.value


class ConstAlgo(Algorithm):
    def __init__(self, params: ConstParams):
        super().__init__(params)

    def train(self, ctx, pd):
        return pd + self.params.value

    def predict(self, model, query):
        return {"result": model * query["mult"]}


def train_const(storage):
    engine = Engine(ConstDataSource, IdentityPreparator,
                    {"const": ConstAlgo}, FirstServing)
    ep = EngineParams(
        data_source_params=("", ConstParams(value=1.0)),
        preparator_params=("", None),
        algorithm_params_list=[("const", ConstParams(value=2.0))],
        serving_params=("", None),
    )
    return engine, run_train(engine, ep, engine_id="const", storage=storage)


def test_every_server_answers_healthz_and_readyz(memory_storage):
    engine, _ = train_const(memory_storage)
    servers = [
        EventServer(storage=memory_storage, host="127.0.0.1", port=0),
        EngineServer(engine, "const", host="127.0.0.1", port=0,
                     storage=memory_storage),
        StorageServer(storage=memory_storage, host="127.0.0.1", port=0),
        DashboardServer(storage=memory_storage, host="127.0.0.1", port=0),
        AdminServer(storage=memory_storage, host="127.0.0.1", port=0),
    ]
    try:
        for server in servers:
            server.start()
            base = f"http://127.0.0.1:{server.port}"
            status, body = get_json(f"{base}/healthz")
            assert status == 200 and body == {"status": "alive"}, type(server)
            status, body = get_json(f"{base}/readyz")
            assert status == 200, (type(server), body)
            assert body["status"] in ("ok", "degraded")
            # the per-server storage probe ran against live storage
            assert body["probes"]["storage"]["status"] == "ok"
            assert "devices" in body["probes"]
    finally:
        for server in servers:
            server.stop()


def test_readyz_503_when_storage_backend_is_down(tmp_path):
    storage = Storage.from_env({
        "PIO_STORAGE_SOURCES_SQL_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_SQL_PATH": str(tmp_path / "pio.db"),
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "SQL",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "SQL",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "SQL",
    })
    server = EventServer(storage=storage, host="127.0.0.1", port=0).start()
    try:
        base = f"http://127.0.0.1:{server.port}"
        status, body = get_json(f"{base}/readyz")
        assert status == 200 and body["probes"]["storage"]["status"] == "ok"
        # kill the backend: every query on the closed handle now raises
        storage.client_for("METADATA").close()
        status, body = get_json(f"{base}/readyz")
        assert status == 503
        assert body["status"] == "failed"
        assert body["probes"]["storage"]["status"] == "failed"
        assert body["probes"]["storage"]["reason"]  # names the repos
        # liveness is unaffected: the process still answers
        assert get_json(f"{base}/healthz")[0] == 200
    finally:
        server.stop()


def test_sqlite_health_check_round_trips(tmp_path):
    from predictionio_tpu.data.backends.sqlite import SqliteStorageClient

    client = SqliteStorageClient({"PATH": str(tmp_path / "h.db")})
    assert client.health_check() is True
    client.close()
    with pytest.raises(Exception):
        client.health_check()


# -- watchdogs -----------------------------------------------------------------

def _wait_for(predicate, timeout=5.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if predicate():
            return True
        time.sleep(0.02)
    return False


def stall_count(name):
    family = metrics.REGISTRY.get("pio_watchdog_stall_total")
    return family.labels(name).value


def test_watchdog_fires_on_stalled_work(caplog):
    wd = health.Watchdog("t-stall", min_seconds=0.01, min_history=1,
                         factor=5.0)
    with wd.watch():
        pass  # ~instant: trailing median ≈ 0 -> deadline = 0.01 * 5
    before = stall_count("t-stall")
    token = trace.activate("feedfacefeedfacefeedfacefeedface")
    try:
        with caplog.at_level(logging.WARNING, logger="pio.stall"):
            with wd.watch():
                assert _wait_for(
                    lambda: stall_count("t-stall") == before + 1)
    finally:
        trace.deactivate(token)
    records = [r for r in caplog.records if r.name == "pio.stall"]
    assert records, "stall log line missing"
    payload = records[-1].pio
    assert payload["watchdog"] == "t-stall"
    assert payload["trace"] == "feedfacefeedfacefeedfacefeedface"


def test_watchdog_fires_once_per_watch_and_records_history():
    wd = health.Watchdog("t-once", min_seconds=0.01, min_history=1,
                         factor=2.0)
    with wd.watch():
        pass
    before = stall_count("t-once")
    with wd.watch():
        _wait_for(lambda: stall_count("t-once") == before + 1)
        time.sleep(0.15)  # well past a second deadline's worth
    assert stall_count("t-once") == before + 1
    assert wd.deadline_seconds() is not None


def test_watchdog_not_armed_without_history():
    wd = health.Watchdog("t-cold", min_seconds=0.01, min_history=8)
    assert wd.deadline_seconds() is None
    before = stall_count("t-cold")
    with wd.watch():
        time.sleep(0.05)
    assert stall_count("t-cold") == before


def test_deadman_stall_dumps_stacks(tmp_path, monkeypatch):
    monkeypatch.setenv("PIO_FLIGHT_DIR", str(tmp_path))
    wd = health.Watchdog("t-train", min_seconds=0.01, min_history=1,
                         factor=2.0, dump_stacks=True)
    before = stall_count("t-train")
    with wd.deadman():
        wd.beat(0.005)  # history lands; deadline becomes ~0.02s
        assert _wait_for(lambda: stall_count("t-train") == before + 1)
    dumps = [f for f in os.listdir(tmp_path)
             if f.startswith("stall-t-train")]
    assert dumps, "stack dump file missing"
    with open(tmp_path / dumps[0]) as f:
        doc = json.load(f)
    assert doc["stall"]["watchdog"] == "t-train"
    assert doc["threads"]  # every thread's stack captured


def test_deadman_beat_resets_deadline():
    wd = health.Watchdog("t-beat", min_seconds=0.05, min_history=1,
                         factor=2.0)
    before = stall_count("t-beat")
    with wd.deadman():
        for _ in range(6):
            wd.beat(0.04)  # deadline 0.1s, beaten every ~0.04s
            time.sleep(0.04)
    assert stall_count("t-beat") == before


def test_start_deadman_concurrent_arms_exactly_once(monkeypatch):
    """Regression (graftlint JT20): two threads racing through
    start_deadman() must converge on ONE armed monitor entry — the old
    check-then-arm split let both arm, leaking a watch that fired
    forever because beats re-armed only the recorded key."""
    wd = health.Watchdog("t-arm-race", min_seconds=0.05, min_history=1,
                         factor=2.0)
    barrier = threading.Barrier(2)
    real_arm = health._MONITOR.arm

    def synced_arm(watch):
        # both threads are past the armed-already check before either
        # arms: the widest possible race window, deterministically
        barrier.wait(timeout=5)
        return real_arm(watch)

    monkeypatch.setattr(health._MONITOR, "arm", synced_arm)
    threads = [threading.Thread(target=wd.start_deadman) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=5)
    try:
        with health._MONITOR._cond:
            mine = [k for k, w in health._MONITOR._watches.items()
                    if w.watchdog is wd]
        assert len(mine) == 1, f"expected one armed watch, got {mine}"
        assert wd._deadman_key == mine[0]
    finally:
        with wd._lock:
            key, wd._deadman_key = wd._deadman_key, None
        if key is not None:
            health._MONITOR.disarm(key)


def test_microbatcher_dispatch_stall_fires_watchdog(monkeypatch):
    tight = health.Watchdog("serving-dispatch-test", min_seconds=0.01,
                            min_history=1, factor=2.0)
    monkeypatch.setattr(engine_server_mod, "_DISPATCH_WATCHDOG", tight)
    delay = {"sec": 0.0}

    def run_one(payload):
        time.sleep(delay["sec"])
        return payload

    batcher = MicroBatcher(lambda ps: [run_one(p) for p in ps], run_one)
    try:
        batcher.submit("warm")  # builds the trailing history
        before = stall_count("serving-dispatch-test")
        delay["sec"] = 0.25
        batcher.submit("slow")
        assert _wait_for(
            lambda: stall_count("serving-dispatch-test") == before + 1)
    finally:
        batcher.stop()


def test_microbatcher_registers_queue_probe(monkeypatch):
    batcher = MicroBatcher(lambda ps: ps, lambda p: p)
    try:
        assert "serving_queue" in health.REGISTRY.names()
        _, detail = health.REGISTRY.run()
        assert detail["serving_queue"]["status"] == "ok"
    finally:
        batcher.stop()
    assert "serving_queue" not in health.REGISTRY.names()


def test_worker_loop_survives_internal_failure():
    """An exception escaping the dispatch path fails THAT batch's
    waiters and is logged — the worker thread stays alive for the
    next submit (the JT09 hazard, fixed)."""
    calls = {"n": 0}

    def run_one(payload):
        calls["n"] += 1
        return payload

    batcher = MicroBatcher(lambda ps: [run_one(p) for p in ps], run_one)
    try:
        # sabotage a non-dispatch internal: _record_splits raising must
        # not kill the worker loop
        original = batcher._record_splits

        def explode(*a, **k):
            batcher._record_splits = original
            raise RuntimeError("bookkeeping bug")

        batcher._record_splits = explode
        with pytest.raises(RuntimeError):
            batcher.submit("a")
        assert batcher.submit("b") == "b"  # worker still alive
    finally:
        batcher.stop()


# -- SLO burn-rate math --------------------------------------------------------

def test_burn_rate_math_on_synthetic_series():
    budget = 0.01  # objective 0.99
    t0 = 1_000_000.0
    steady = [(t0 + i * 60, 1000.0 + 100 * i, 1000.0 + 100 * i)
              for i in range(10)]
    assert slo.burn_rate(steady, t0 + 540, 300.0, budget) == 0.0

    # next 5m after the steady run: 100 requests, all bad -> error rate
    # 1.0 over that window -> burn 100 (baseline = the t0+540 sample)
    regressed = steady + [(t0 + 840, steady[-1][1], steady[-1][2] + 100)]
    burn = slo.burn_rate(regressed, t0 + 840, 300.0, budget)
    assert burn == pytest.approx(100.0)

    # half bad -> burn 50
    half = steady + [(t0 + 840, steady[-1][1] + 50, steady[-1][2] + 100)]
    assert slo.burn_rate(half, t0 + 840, 300.0, budget) == pytest.approx(50.0)

    assert slo.burn_rate([], t0, 300.0, budget) is None
    assert slo.burn_rate(steady[:1], t0, 300.0, budget) is None
    # no traffic in the window -> None, not 0
    flat = [(t0, 10.0, 10.0), (t0 + 300, 10.0, 10.0)]
    assert slo.burn_rate(flat, t0 + 300, 300.0, budget) is None


def test_multiwindow_alert_requires_both_windows():
    mon = slo.SLOMonitor([slo.SLO(name="t-avail", kind="availability",
                                  metric="nonexistent", objective=0.99)])
    t0 = 2_000_000.0
    # long healthy history, then a 450-request 100%-error burst younger
    # than 5m: the 5m window burns hot (450/2850 = 15.8x budget) but 1h
    # dilutes it below threshold (450/35850 = 1.3x) -> the fast page
    # holds until the burst persists into the long window too
    for i in range(61):
        mon.record("t-avail", t0 + i * 60, 36000.0 + 600 * i,
                   36000.0 + 600 * i)
    last_good, last_total = 36000.0 + 600 * 60, 36000.0 + 600 * 60
    mon.record("t-avail", t0 + 61 * 60, last_good, last_total + 450)
    report = mon.evaluate(now=t0 + 61 * 60)
    entry = report["slos"][0]
    assert entry["burn_rates"]["5m"] >= slo.FAST_BURN
    assert entry["burn_rates"]["1h"] < slo.FAST_BURN
    assert entry["state"] == "ok"


def test_latency_regression_fires_fast_burn_alert():
    """Acceptance: a synthetic latency regression on the REAL
    pio_serving_request_seconds histogram drives the fast-window
    burn-rate alert to firing."""
    hist = metrics.REGISTRY.get("pio_serving_request_seconds")
    child = hist.labels("slo-regression-test")
    slo_def = slo.SLO(name="t-latency", kind="latency",
                      metric="pio_serving_request_seconds",
                      objective=0.99, threshold_ms=100.0)
    mon = slo.SLOMonitor([slo_def])
    t0 = 3_000_000.0
    # healthy traffic: all under the 100ms threshold
    for _ in range(200):
        child.observe(0.005)
    good, total = slo_def.measure()
    mon.record("t-latency", t0, good, total)
    # regression: the next wave blows through the threshold
    for _ in range(200):
        child.observe(0.5)
    good, total = slo_def.measure()
    mon.record("t-latency", t0 + 240, good, total)
    report = mon.evaluate(now=t0 + 240)
    entry = report["slos"][0]
    assert entry["burn_rates"]["5m"] >= slo.FAST_BURN
    assert entry["alerts"]["fast"]["firing"] is True
    assert entry["state"] == "firing"
    hist.remove("slo-regression-test")


def test_slo_monitor_rides_flight_snapshot_cadence():
    assert "slo" in {
        name for name, _fn in flight._snapshot_listeners
    }, "SLO sampler not registered on the flight snapshot cadence"


def test_admin_slo_endpoint_and_cli(memory_storage, capsys):
    server = EventServer(storage=memory_storage, host="127.0.0.1",
                         port=0).start()
    try:
        status, body = get_json(
            f"http://127.0.0.1:{server.port}/admin/slo")
        assert status == 200
        names = {e["name"] for e in body["slos"]}
        assert {"serving-latency", "http-availability"} <= names
    finally:
        server.stop()
    from predictionio_tpu.tools.cli import main

    assert main(["slo"]) in (0, 1)
    out = capsys.readouterr().out
    assert "serving-latency" in out and "http-availability" in out


# -- OpenMetrics + exemplars ---------------------------------------------------

def test_openmetrics_document_shape():
    c = metrics.counter("pio_test_om_total", "om test counter", ("k",))
    c.labels("v").inc(3)
    h = metrics.histogram("pio_test_om_seconds", "om test histogram",
                          buckets=(0.1, 1.0))
    h.observe(0.05, exemplar={"trace_id": "abcd1234abcd1234"})
    text = metrics.REGISTRY.render_openmetrics()
    assert text.endswith("# EOF\n")
    # counter family drops _total, the sample keeps it
    assert "# TYPE pio_test_om counter" in text
    assert 'pio_test_om_total{k="v"} 3' in text
    # exemplar rides the bucket the observation landed in
    assert ('pio_test_om_seconds_bucket{le="0.1"} 1 '
            '# {trace_id="abcd1234abcd1234"} 0.05') in text
    # the Prometheus document is unchanged (no exemplars, no EOF)
    prom = metrics.REGISTRY.render()
    assert "# {" not in prom and "# EOF" not in prom
    assert "pio_test_om_total" in prom


def test_exemplar_carries_served_request_trace_id(memory_storage):
    """Acceptance: OpenMetrics exposition carries an exemplar bearing a
    real trace id from a served request."""
    from predictionio_tpu.data.metadata import AccessKey

    app = memory_storage.apps().insert("health-ex-app")
    memory_storage.events().init(app.id)
    key = AccessKey.generate(app.id)
    memory_storage.access_keys().insert(key)
    server = EventServer(storage=memory_storage, host="127.0.0.1",
                         port=0).start()
    trace_id = "cafe0123cafe0123cafe0123cafe0123"
    try:
        base = f"http://127.0.0.1:{server.port}"
        status, _, _ = get(
            f"{base}/events.json?accessKey={key.key}",
            headers={"Content-Type": "application/json",
                     trace.TRACE_HEADER: trace_id},
            method="POST",
            body=json.dumps({"event": "view", "entityType": "user",
                             "entityId": "u1"}).encode(),
        )
        assert status == 201
        # a request's latency is observed AFTER its answer is written
        # (serving/http.py _instrument's finally), on another thread than
        # the one that serves the next connection: wait for it, bounded
        deadline = time.monotonic() + 5.0
        while True:
            status, text, headers = get(
                f"{base}/metrics",
                headers={"Accept": "application/openmetrics-text"})
            assert status == 200
            assert "application/openmetrics-text" in headers["Content-Type"]
            exemplar_lines = [l for l in text.splitlines()
                              if f'trace_id="{trace_id}"' in l]
            if exemplar_lines or time.monotonic() > deadline:
                break
            time.sleep(0.02)
        assert exemplar_lines, "no exemplar carrying the request trace id"
        assert all(" # {" in l for l in exemplar_lines)
        # content negotiation: default Accept still gets Prometheus text
        _, prom_text, prom_headers = get(f"{base}/metrics")
        assert "version=0.0.4" in prom_headers["Content-Type"]
        assert "# EOF" not in prom_text
    finally:
        server.stop()


# -- push path -----------------------------------------------------------------

class _FlakySink:
    """HTTP sink failing the first N pushes, then accepting."""

    def __init__(self, fail_first=1):
        self.hits = []
        self.fail_first = fail_first
        sink = self

        class Handler(JSONRequestHandler):
            server_version = "FlakySink/0.1"

            def do_POST(self):
                body = self._read_body()
                sink.hits.append(body)
                if len(sink.hits) <= sink.fail_first:
                    self._send(503, {"message": "not yet"})
                else:
                    self._send(200, {"message": "ok"})

        self.server = HTTPServerBase("127.0.0.1", 0, Handler).start()

    @property
    def url(self):
        return f"http://127.0.0.1:{self.server.port}/push"

    def stop(self):
        self.server.stop()


def test_pusher_retries_flaky_sink_with_backoff():
    sink = _FlakySink(fail_first=1)
    pusher = push.MetricsPusher(sink.url, interval=0.05, max_backoff=0.2)
    try:
        pusher.start()
        assert _wait_for(lambda: len(sink.hits) >= 3)
    finally:
        pusher.stop()
        sink.stop()
    # the pushed document is OpenMetrics (exemplar-capable)
    assert sink.hits[-1].rstrip().endswith(b"# EOF")
    family = metrics.REGISTRY.get("pio_push_total")
    assert family.labels("ok").value >= 1
    assert family.labels("error").value >= 1


def test_pusher_push_once_never_raises_on_dead_sink():
    pusher = push.MetricsPusher("http://127.0.0.1:9/push", timeout=0.2)
    assert pusher.push_once() is False


def test_pusher_starts_from_env(monkeypatch):
    sink = _FlakySink(fail_first=0)
    monkeypatch.setenv("PIO_PUSH_URL", sink.url)
    monkeypatch.setenv("PIO_PUSH_INTERVAL_SEC", "0.05")
    try:
        pusher = push.start_from_env()
        assert pusher is not None
        assert push.start_from_env() is pusher  # idempotent
        assert _wait_for(lambda: len(sink.hits) >= 1)
    finally:
        push.stop()
        sink.stop()


# -- admin auth ----------------------------------------------------------------

def test_admin_auth_matrix(memory_storage, monkeypatch):
    server = EventServer(storage=memory_storage, host="127.0.0.1",
                         port=0).start()
    base = f"http://127.0.0.1:{server.port}"
    try:
        # no token configured: everything open (trusted-network default)
        assert get(f"{base}/admin/flight")[0] == 200
        monkeypatch.setenv("PIO_ADMIN_TOKEN", "s3cret")
        # /admin/* routes 401 without / with a wrong bearer
        for path, method in (("/admin/flight", "GET"),
                             ("/admin/slo", "GET"),
                             ("/admin/profile?seconds=1", "POST")):
            body = b"" if method == "POST" else None
            status, text, headers = get(f"{base}{path}", method=method,
                                        body=body)
            assert status == 401, (path, status)
            assert headers.get("WWW-Authenticate") == "Bearer"
            assert get(f"{base}{path}",
                       headers={"Authorization": "Bearer wrong"},
                       method=method, body=body)[0] == 401
        # correct bearer: through (profile may 501 on CPU — not 401)
        auth = {"Authorization": "Bearer s3cret"}
        assert get(f"{base}/admin/flight", headers=auth)[0] == 200
        assert get(f"{base}/admin/slo", headers=auth)[0] == 200
        assert get(f"{base}/admin/profile?seconds=1", headers=auth,
                   method="POST", body=b"")[0] != 401
        # scraping + probing surfaces stay unauthenticated
        assert get(f"{base}/healthz")[0] == 200
        assert get(f"{base}/readyz")[0] == 200
        assert get(f"{base}/metrics")[0] == 200
    finally:
        server.stop()


# -- flight-dir growth cap -----------------------------------------------------

def test_flight_dump_dir_is_capped(tmp_path, monkeypatch):
    monkeypatch.setenv("PIO_FLIGHT_DIR", str(tmp_path))
    monkeypatch.setenv("PIO_FLIGHT_MAX_DUMPS", "3")
    evicted = metrics.REGISTRY.get("pio_flight_dumps_evicted_total")
    before = evicted.value
    paths = []
    for i in range(6):
        path = flight.write_dump_file(f"flight-test{i}", {"i": i})
        assert path is not None
        os.utime(path, (1_700_000_000 + i, 1_700_000_000 + i))
        paths.append(path)
    remaining = sorted(f for f in os.listdir(tmp_path)
                       if f.endswith(".json"))
    assert len(remaining) == 3
    # oldest evicted first: the newest dump always survives
    assert os.path.basename(paths[-1]) in remaining
    assert os.path.basename(paths[0]) not in remaining
    assert evicted.value >= before + 3


def test_flight_dump_byte_cap(tmp_path, monkeypatch):
    monkeypatch.setenv("PIO_FLIGHT_DIR", str(tmp_path))
    monkeypatch.setenv("PIO_FLIGHT_MAX_DUMPS", "100")
    monkeypatch.setenv("PIO_FLIGHT_MAX_DUMP_BYTES", "300")
    for i in range(5):
        path = flight.write_dump_file(f"fat{i}", {"pad": "x" * 100})
        os.utime(path, (1_700_000_000 + i, 1_700_000_000 + i))
    total = sum(
        os.path.getsize(os.path.join(tmp_path, f))
        for f in os.listdir(tmp_path) if f.endswith(".json"))
    assert total <= 300
    assert any(f.startswith("fat4") for f in os.listdir(tmp_path))


def test_error_dump_goes_through_capped_writer(tmp_path, monkeypatch):
    monkeypatch.setenv("PIO_FLIGHT_DIR", str(tmp_path))
    recorder = flight.FlightRecorder(capacity=8)
    key = recorder.begin("a" * 32, "TestSrv", "GET", "/boom")
    recorder.finish(key, 500, "RuntimeError: boom")
    dumps = [f for f in os.listdir(tmp_path) if f.startswith("flight-")]
    assert len(dumps) == 1
