"""Flight recorder, on-demand profiling and structured diagnostics
(obs/flight.py, obs/profiler.py, obs/logging.py + the serving wiring):
ring-buffer eviction, stage-timing attribution, error-triggered
capture, the /admin endpoints on live in-process servers, slow-request
logging, trace-log rotation, and the per-batch span satellite."""

import io
import json
import logging
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
from predictionio_tpu.obs import flight, metrics, trace
from predictionio_tpu.obs import logging as obs_logging
from predictionio_tpu.obs.flight import FlightRecorder
from predictionio_tpu.serving.engine_server import EngineServer, MicroBatcher
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


# ---------------------------------------------------------------------------
# recorder core
# ---------------------------------------------------------------------------

def test_ring_buffer_eviction_order():
    rec = FlightRecorder(capacity=3)
    for i in range(5):
        key = rec.begin(f"trace{i}", "S", "GET", f"/r{i}")
        rec.finish(key, 200)
    records = rec.records()
    # oldest two evicted; survivors oldest-first
    assert [r["route"] for r in records] == ["/r2", "/r3", "/r4"]
    assert [r["trace"] for r in records] == ["trace2", "trace3", "trace4"]
    # n limits from the newest end; n <= 0 is "none", not Python's
    # [-0:] == everything
    assert [r["route"] for r in rec.records(2)] == ["/r3", "/r4"]
    assert rec.records(0) == [] and rec.records(-5) == []


def test_stage_attribution_and_unattributed_remainder():
    rec = FlightRecorder(capacity=8)
    key = rec.begin("t1", "S", "POST", "/q")
    rec.note_stage("queue", 0.002, trace_id="t1")
    rec.note_stage("dispatch", 0.003, trace_id="t1")
    rec.note_stage("dispatch", 0.001, trace_id="t1")  # accumulates
    time.sleep(0.01)
    record = rec.finish(key, 200)
    stages = record["stages"]
    assert stages["queue"] == pytest.approx(2.0, abs=0.01)
    assert stages["dispatch"] == pytest.approx(4.0, abs=0.01)
    # stages always sum to the total by construction
    assert sum(stages.values()) == pytest.approx(record["duration_ms"],
                                                 abs=0.05)
    assert stages["unattributed"] > 0


def test_oldest_open_record_owns_the_trace():
    # nested servers can serve the same propagated trace id at once:
    # stage notes must attach to the EDGE (oldest) request
    rec = FlightRecorder(capacity=8)
    edge = rec.begin("shared", "Engine", "POST", "/q")
    inner = rec.begin("shared", "Storage", "GET", "/find")
    rec.note_stage("queue", 0.005, trace_id="shared")
    inner_rec = rec.finish(inner, 200)
    edge_rec = rec.finish(edge, 200)
    assert "queue" in edge_rec["stages"]
    assert "queue" not in inner_rec["stages"]


def test_metric_snapshots_ride_along():
    rec = FlightRecorder(capacity=4, snapshot_interval=0.0)
    key = rec.begin("t1", "S", "GET", "/")
    rec.finish(key, 200)
    dump = rec.dump()
    assert dump["metric_snapshots"], "interval-0 recorder must snapshot"
    snap = dump["metric_snapshots"][-1]
    assert snap["ts"] > 0
    # the snapshot is a compact registry summary, json-serializable
    assert "pio_flight_records_total" in snap["metrics"]
    json.dumps(dump)


# ---------------------------------------------------------------------------
# live engine server: /admin/flight + stage timings + error capture
# ---------------------------------------------------------------------------

from dataclasses import dataclass


@dataclass
class OneParams(Params):
    pass


class OneDataSource(DataSource):
    def __init__(self, params):
        super().__init__(params)

    def read_training(self, ctx):
        return 1.0


class MaybeBoomAlgo(Algorithm):
    """predict() raises on {"boom": true} — the induced handler error."""

    def __init__(self, params):
        super().__init__(params)

    def train(self, ctx, pd):
        return pd + 2.0

    def predict(self, model, query):
        if query.get("boom"):
            raise RuntimeError("induced kaboom")
        return {"result": model * query["mult"]}


def _await_sealed(trace_id, timeout=5.0):
    """The flight record seals on the HANDLER thread after the response
    bytes already reached the client (obs/flight.py finish runs in the
    instrument wrapper's finally) — a test reading the ring right after
    its request must wait for the seal, not race it."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        for r in flight.RECORDER.records():
            if r["trace"] == trace_id:
                return r
        time.sleep(0.02)
    raise AssertionError(
        f"record for trace {trace_id} never sealed within {timeout}s: "
        f"{[(r.get('route'), r.get('trace')) for r in flight.RECORDER.records()]}")


@pytest.fixture()
def flight_server(memory_storage):
    engine = Engine(OneDataSource, IdentityPreparator,
                    {"algo": MaybeBoomAlgo}, FirstServing)
    ep = EngineParams(
        data_source_params=("", OneParams()),
        preparator_params=("", None),
        algorithm_params_list=[("algo", OneParams())],
        serving_params=("", None),
    )
    run_train(engine, ep, engine_id="flight", storage=memory_storage)
    flight.RECORDER.clear()
    server = EngineServer(engine, "flight", host="127.0.0.1", port=0,
                          storage=memory_storage).start()
    yield server
    server.stop()
    flight.RECORDER.clear()


def test_admin_flight_returns_recorded_requests(flight_server):
    """Acceptance: GET /admin/flight on a live engine server answers the
    last N completed request records with stage timings and the trace
    id each response carried."""
    base = f"http://127.0.0.1:{flight_server.port}"
    trace_ids = []
    for mult in (2, 3, 4):
        status, headers, body = http("POST", f"{base}/queries.json",
                                     {"mult": mult})
        assert status == 200 and json.loads(body) == {"result": 3.0 * mult}
        trace_ids.append(headers[trace.TRACE_HEADER])
    # a record seals after its answer has left (``pio:http.finish``): the
    # third may still be open when its client reads the ring
    for trace_id in trace_ids:
        _await_sealed(trace_id)

    status, _, body = http("GET", f"{base}/admin/flight")
    assert status == 200
    dump = json.loads(body)
    queries = [r for r in dump["records"] if r["route"] == "/queries.json"]
    assert len(queries) == 3
    # records correlate with the trace ids the clients saw, in order
    assert [r["trace"] for r in queries] == trace_ids
    for r in queries:
        assert r["status"] == 200 and r["method"] == "POST"
        stages = r["stages"]
        # the engine query path attributes queue + dispatch (batcher
        # splits), parse + serialize (handler), remainder explicit
        for stage in ("queue", "dispatch", "parse", "serialize",
                      "unattributed"):
            assert stage in stages, (stage, stages)
        assert sum(stages.values()) == pytest.approx(
            r["duration_ms"], abs=0.1)
        # the request's own span tree rode along, same trace id
        names = [s["name"] for s in r["spans"]]
        assert "serve.query" in names and "http.engineserver" in names
        assert {s["trace"] for s in r["spans"]} == {r["trace"]}
    # ?n= limits from the newest end
    status, _, body = http("GET", f"{base}/admin/flight?n=1")
    limited = json.loads(body)["records"]
    assert len([r for r in limited if r["route"] == "/queries.json"]) <= 1


def test_induced_error_lands_in_dump_without_operator_action(
        flight_server, tmp_path, monkeypatch):
    """Acceptance: an induced handler error appears in the flight dump
    (and, with PIO_FLIGHT_DIR set, as an automatic dump file) with no
    operator action."""
    monkeypatch.setenv("PIO_FLIGHT_DIR", str(tmp_path / "dumps"))
    base = f"http://127.0.0.1:{flight_server.port}"
    status, headers, body = http("POST", f"{base}/queries.json",
                                 {"boom": True})
    assert status == 500
    failed_trace = headers[trace.TRACE_HEADER]

    # the record seals (and the error dump writes) on the handler
    # thread AFTER the 500 already reached the client — wait for it
    _await_sealed(failed_trace)
    status, _, body = http("GET", f"{base}/admin/flight")
    assert status == 200
    record = next(r for r in json.loads(body)["records"]
                  if r["trace"] == failed_trace)
    assert record["status"] == 500
    assert "RuntimeError" in record["error"]
    assert "induced kaboom" in record["error"]
    # the slow/error filter keeps it
    status, _, body = http("GET", f"{base}/admin/flight?slow=1")
    assert any(r["trace"] == failed_trace
               for r in json.loads(body)["records"])
    # the automatic on-disk dump was written and parses (the write
    # follows the seal on the handler thread — poll briefly)
    deadline = time.monotonic() + 5.0
    dumps = []
    while not dumps and time.monotonic() < deadline:
        dumps = list((tmp_path / "dumps").glob("flight-*.json"))
        if not dumps:
            time.sleep(0.02)
    assert dumps, "error must trigger an automatic dump file"
    on_disk = json.loads(dumps[0].read_text())
    assert any(r.get("trace") == failed_trace for r in on_disk["records"])


def test_slow_request_flag_stage_sums_and_json_log(flight_server,
                                                   monkeypatch):
    """PIO_SLOW_MS=0 flags everything: the record is marked slow, its
    stages sum to the total, and the pio.slow logger emits a
    JSON-parseable line carrying the same trace id + breakdown."""
    monkeypatch.setenv("PIO_SLOW_MS", "0")
    buf = io.StringIO()
    handler = logging.StreamHandler(buf)
    handler.setFormatter(obs_logging.JSONFormatter())
    slow_logger = logging.getLogger("pio.slow")
    slow_logger.addHandler(handler)
    old_level = slow_logger.level
    slow_logger.setLevel(logging.WARNING)
    try:
        base = f"http://127.0.0.1:{flight_server.port}"
        status, headers, _ = http("POST", f"{base}/queries.json",
                                  {"mult": 7})
        assert status == 200
        trace_id = headers[trace.TRACE_HEADER]
        record = _await_sealed(trace_id)
        # the pio.slow line fires on the handler thread right after
        # the seal — keep our log handler attached until it lands
        deadline = time.monotonic() + 5.0
        while trace_id not in buf.getvalue() and (
                time.monotonic() < deadline):
            time.sleep(0.02)
    finally:
        slow_logger.removeHandler(handler)
        slow_logger.setLevel(old_level)

    assert record["slow"] is True
    assert sum(record["stages"].values()) == pytest.approx(
        record["duration_ms"], abs=0.1)

    lines = [l for l in buf.getvalue().splitlines() if l.strip()]
    payloads = [json.loads(l) for l in lines]  # every line parses
    mine = next(p for p in payloads if p.get("trace") == trace_id)
    assert mine["level"] == "WARNING"
    assert mine["stages"] == record["stages"]
    assert mine["route"] == "/queries.json"


def test_profile_endpoint_is_clean_noop_on_cpu(flight_server):
    """Acceptance: POST /admin/profile answers a clean 501 on the CPU
    backend (tier-1) instead of pretending to profile."""
    base = f"http://127.0.0.1:{flight_server.port}"
    status, _, body = http("POST", f"{base}/admin/profile?seconds=0.01")
    assert status == 501
    payload = json.loads(body)
    assert payload["backend"] == "cpu"
    assert "no-op on CPU" in payload["message"]
    # malformed seconds is a client error, not a 501
    status, _, _ = http("POST", f"{base}/admin/profile?seconds=soon")
    assert status == 400


def test_profile_endpoint_forced_capture_returns_artifact(
        flight_server, tmp_path, monkeypatch):
    """PIO_PROFILE_FORCE=1 drives the FULL capture path on CPU: the
    endpoint must answer an artifact path that exists."""
    monkeypatch.setenv("PIO_PROFILE_FORCE", "1")
    monkeypatch.setenv("PIO_PROFILE_DIR", str(tmp_path / "prof"))
    base = f"http://127.0.0.1:{flight_server.port}"
    # generous client timeout: the first capture in a cold process pays
    # the jax import + backend init (tens of seconds on a loaded box)
    status, _, body = http("POST", f"{base}/admin/profile?seconds=0.05",
                           timeout=180)
    assert status == 200, body
    payload = json.loads(body)
    assert payload["artifact"] == str(tmp_path / "prof")
    import os

    assert os.path.isdir(payload["artifact"])


# ---------------------------------------------------------------------------
# structured logging
# ---------------------------------------------------------------------------

def test_json_log_lines_carry_active_trace_id():
    buf = io.StringIO()
    handler = logging.StreamHandler(buf)
    handler.setFormatter(obs_logging.JSONFormatter())
    logger = logging.getLogger("test.flight.json")
    logger.addHandler(handler)
    logger.setLevel(logging.INFO)
    try:
        token = trace.activate("cafe" * 8)
        try:
            logger.info("inside a request", extra={"pio": {"k": 1}})
        finally:
            trace.deactivate(token)
        logger.info("outside any request")
    finally:
        logger.removeHandler(handler)
    lines = [json.loads(l) for l in buf.getvalue().splitlines()]
    assert lines[0]["trace"] == "cafe" * 8
    assert lines[0]["message"] == "inside a request"
    assert lines[0]["k"] == 1
    assert "trace" not in lines[1]


def test_plain_formatter_appends_trace():
    record = logging.LogRecord("n", logging.INFO, "p", 1, "msg", (), None)
    fmt = obs_logging.PlainTraceFormatter("%(message)s")
    token = trace.activate("feed" * 8)
    try:
        assert fmt.format(record) == f"msg [trace={'feed' * 8}]"
    finally:
        trace.deactivate(token)
    assert fmt.format(record) == "msg"


# ---------------------------------------------------------------------------
# trace-log rotation (satellite)
# ---------------------------------------------------------------------------

def test_trace_log_rotates_by_size(tmp_path, monkeypatch):
    log_path = tmp_path / "spans.jsonl"
    monkeypatch.setenv("PIO_TRACE_LOG", str(log_path))
    monkeypatch.setenv("PIO_TRACE_LOG_MAX_BYTES", "400")
    counter = metrics.REGISTRY.get("pio_trace_log_rotations_total")
    before = counter.value
    token = trace.activate(trace.new_trace_id())
    try:
        for _ in range(20):
            with trace.span("rotate.me", pad="x" * 40):
                pass
    finally:
        trace.deactivate(token)
    assert counter.value > before
    rolled = tmp_path / "spans.jsonl.1"
    assert rolled.exists()
    # both files hold intact JSON lines (rotation never splits a line)
    for path in (log_path, rolled):
        for line in path.read_text().splitlines():
            assert json.loads(line)["name"] == "rotate.me"


# ---------------------------------------------------------------------------
# per-batch span (satellite)
# ---------------------------------------------------------------------------

def test_multi_query_batch_span_carries_member_trace_ids():
    trace.clear_recent()
    release = threading.Event()

    def run_one(payload):
        release.wait(2.0)  # first (lone) dispatch parks the worker
        return payload

    def run_batch(payloads):
        return payloads

    batcher = MicroBatcher(run_batch, run_one, max_batch=16)
    try:
        member_ids = []
        threads = []

        def lone():
            batcher.submit("lone")

        t0 = threading.Thread(target=lone)
        t0.start()
        time.sleep(0.05)  # the worker is now parked inside run_one

        def submit_traced(tid):
            token = trace.activate(tid)
            try:
                assert batcher.submit(f"q-{tid}") == f"q-{tid}"
            finally:
                trace.deactivate(token)

        for i in range(4):
            tid = trace.new_trace_id()
            member_ids.append(tid)
            th = threading.Thread(target=submit_traced, args=(tid,))
            th.start()
            threads.append(th)
        time.sleep(0.05)  # queued behind the parked worker
        release.set()
        t0.join(5)
        for th in threads:
            th.join(5)
    finally:
        batcher.stop()

    batch_spans = [s for s in trace.recent_spans()
                   if s["name"] == "serve.batch"]
    assert batch_spans, "a >1 dispatch must emit its serve.batch span"
    recorded_members = [m for s in batch_spans for m in s["members"]]
    assert set(member_ids) <= set(recorded_members)
    assert all(s["batch_size"] > 1 for s in batch_spans)


# ---------------------------------------------------------------------------
# CLI: pio flight / pio metrics --json
# ---------------------------------------------------------------------------

def test_pio_flight_cli_dumps_live_server(flight_server, capsys):
    from predictionio_tpu.tools.cli import main

    base = f"http://127.0.0.1:{flight_server.port}"
    assert http("POST", f"{base}/queries.json", {"mult": 2})[0] == 200
    assert main(["flight", "--url", base, "-n", "5"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert any(r["route"] == "/queries.json" for r in payload["records"])


def test_pio_metrics_json_is_machine_readable(flight_server, capsys):
    from predictionio_tpu.tools.cli import main

    base = f"http://127.0.0.1:{flight_server.port}"
    assert http("POST", f"{base}/queries.json", {"mult": 2})[0] == 200
    # in-process registry mode
    assert main(["metrics", "--json"]) == 0
    samples = json.loads(capsys.readouterr().out)
    assert samples['pio_serving_request_seconds_count{engine="flight"}'] >= 1
    # server mode produces the same flat shape
    assert main(["metrics", "--json", "--url", base]) == 0
    remote = json.loads(capsys.readouterr().out)
    assert remote['pio_serving_request_seconds_count{engine="flight"}'] >= 1


# ---------------------------------------------------------------------------
# dashboard flight view (satellite)
# ---------------------------------------------------------------------------

def test_dashboard_flight_view(memory_storage):
    from predictionio_tpu.tools.dashboard import DashboardServer

    flight.RECORDER.clear()
    server = DashboardServer(storage=memory_storage, host="127.0.0.1",
                             port=0).start()
    try:
        base = f"http://127.0.0.1:{server.port}"
        assert http("GET", f"{base}/")[0] == 200  # recorded by flight
        status, _, html_body = http("GET", f"{base}/flight")
        assert status == 200
        assert "Flight recorder" in html_body
        assert "/admin/flight" in html_body
        status, _, slow_body = http("GET", f"{base}/flight?slow=1")
        assert status == 200 and "Slow / errored" in slow_body
        # the JSON dump route works on the dashboard too
        status, _, body = http("GET", f"{base}/admin/flight")
        assert status == 200
        assert any(r["route"] == "/" for r in json.loads(body)["records"])
    finally:
        server.stop()
        flight.RECORDER.clear()
