"""Unified telemetry subsystem (obs/): metrics core + Prometheus
exposition on every server, request tracing with X-PIO-Trace-Id
propagation engine server -> rest storage client -> storage server,
JAX runtime instrumentation, and the satellite fixes that ride along
(Stats.report pruning, ServingStats on the shared histogram)."""

import datetime as _dt
import json
import re
import urllib.request

import pytest

from predictionio_tpu.obs import jaxmon, metrics, trace
from predictionio_tpu.obs.metrics import Registry

UTC = _dt.timezone.utc


def http_get(url, headers=None):
    req = urllib.request.Request(url, headers=headers or {})
    with urllib.request.urlopen(req, timeout=10) as resp:
        return resp.status, dict(resp.headers), resp.read().decode()


def http_post(url, body, headers=None):
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=10) as resp:
        return resp.status, dict(resp.headers), resp.read().decode()


# ---------------------------------------------------------------------------
# metrics core
# ---------------------------------------------------------------------------

def test_counter_labels_and_monotonicity():
    reg = Registry()
    c = reg.counter("t_requests_total", "help", ("route", "status"))
    c.labels("/a", "200").inc()
    c.labels("/a", "200").inc(2)
    c.labels(route="/b", status="500").inc()
    assert c.labels("/a", "200").value == 3
    assert c.labels("/b", "500").value == 1
    with pytest.raises(ValueError):
        c.labels("/a", "200").inc(-1)
    with pytest.raises(ValueError):
        c.labels("/only-one")


def test_gauge_set_inc_dec():
    reg = Registry()
    g = reg.gauge("t_inflight", "help")
    g.inc()
    g.inc()
    g.dec()
    assert g.value == 1
    g.set(42.5)
    assert g.value == 42.5


def test_histogram_bucket_math_and_quantiles():
    reg = Registry()
    h = reg.histogram("t_latency_seconds", "help",
                      buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.02, 0.02, 0.5, 3.0):
        h.observe(v)
    child = h.labels()
    assert child.count == 5
    assert child.sum == pytest.approx(3.545)
    # cumulative: le=0.01 -> 1, le=0.1 -> 3, le=1.0 -> 4, +Inf -> 5
    cum = dict(
        (bound, count) for bound, count in child.cumulative()
    )
    assert cum[0.01] == 1 and cum[0.1] == 3 and cum[1.0] == 4
    assert cum[float("inf")] == 5
    # quantiles interpolate inside the crossing bucket
    assert 0.01 <= child.quantile(0.5) <= 0.1
    assert child.quantile(0.0) == 0.0
    # the open-ended tail answers the last finite bound
    assert child.quantile(1.0) == 1.0


def test_histogram_boundary_values_are_inclusive():
    reg = Registry()
    h = reg.histogram("t_edges", "help", buckets=(1.0, 2.0))
    h.observe(1.0)   # le="1" is inclusive, Prometheus semantics
    h.observe(2.0)
    cum = dict(h.labels().cumulative())
    assert cum[1.0] == 1 and cum[2.0] == 2


def test_registry_dedup_and_type_conflict():
    reg = Registry()
    a = reg.counter("t_dup", "help", ("x",))
    assert reg.counter("t_dup", "help", ("x",)) is a
    with pytest.raises(ValueError):
        reg.gauge("t_dup", "help", ("x",))
    with pytest.raises(ValueError):
        reg.counter("t_dup", "help", ("y",))
    h = reg.histogram("t_dup_h", "help", buckets=(0.1, 1.0))
    assert reg.histogram("t_dup_h", "help", buckets=(0.1, 1.0)) is h
    with pytest.raises(ValueError):  # silently-different buckets misbucket
        reg.histogram("t_dup_h", "help", buckets=(0.5, 2.0))
    # atomic (count, sum) pair for average computations
    h.observe(0.3)
    assert h.labels().snapshot() == (1, pytest.approx(0.3))


def test_label_escaping_in_exposition():
    reg = Registry()
    c = reg.counter("t_esc", "help", ("msg",))
    c.labels('say "hi"\nback\\slash').inc()
    text = reg.render()
    assert r'msg="say \"hi\"\nback\\slash"' in text


_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? [0-9.eE+-]+(Inf)?$"
)


def assert_valid_prometheus(text: str) -> dict:
    """Validate the text-format document shape; return {name: value}
    for unlabeled samples and histogram invariants for labeled ones."""
    samples = {}
    by_series = {}
    for line in text.strip().splitlines():
        if line.startswith("# HELP ") or line.startswith("# TYPE "):
            continue
        assert _SAMPLE_RE.match(line), f"bad exposition line: {line!r}"
        name_part, value = line.rsplit(" ", 1)
        samples[name_part] = float(value)
        by_series.setdefault(name_part, float(value))
    # histogram invariant: the +Inf bucket equals the series count
    for key, value in samples.items():
        m = re.match(r"^(.*)_bucket\{(.*)le=\"\+Inf\"\}$", key)
        if m:
            base, labels = m.group(1), m.group(2).rstrip(",")
            count_key = f"{base}_count{{{labels}}}" if labels else (
                f"{base}_count")
            count_key = count_key.replace("{}", "")
            assert samples[count_key] == value, key
    return samples


def test_render_is_valid_prometheus_text():
    reg = Registry()
    reg.counter("t_total", "help", ("k",)).labels("v").inc(3)
    reg.gauge("t_gauge", "plain gauge").set(1.5)
    h = reg.histogram("t_h", "hist", ("k",), buckets=(0.1, 1.0))
    h.labels("v").observe(0.05)
    h.labels("v").observe(5.0)
    samples = assert_valid_prometheus(reg.render())
    assert samples['t_total{k="v"}'] == 3
    assert samples['t_h_bucket{k="v",le="+Inf"}'] == 2
    assert samples['t_h_count{k="v"}'] == 2


def test_metrics_route_collapses_ids():
    from predictionio_tpu.serving.http import metrics_route

    assert metrics_route("/") == "/"
    assert metrics_route("/events.json") == "/events.json"
    eid = "0123456789abcdef0123456789abcdef"
    assert metrics_route(f"/events/{eid}.json") == "/events/:id.json"
    assert metrics_route(f"/storage/models/{eid}") == "/storage/models/:id"
    assert metrics_route(f"/storage/events/scan/{eid}") == (
        "/storage/events/scan/:id")
    assert metrics_route("/queries.json") == "/queries.json"


def test_metrics_route_cardinality_is_capped(monkeypatch):
    from predictionio_tpu.serving import http

    monkeypatch.setattr(http, "_routes_seen", set())
    monkeypatch.setattr(http, "_MAX_ROUTES", 4)
    assert [http.metrics_route(f"/probe{i}") for i in range(4)] == [
        f"/probe{i}" for i in range(4)]
    # a scanner's 5th+ distinct path collapses instead of growing labels
    assert http.metrics_route("/probe4") == ":other"
    assert http.metrics_route("/probe0") == "/probe0"  # known stays known


def test_invalid_trace_header_is_reminted(event_server):
    server, app, key = event_server
    base = f"http://127.0.0.1:{server.port}"
    bad = "not-a-trace-id!{}"
    _, headers, _ = http_get(f"{base}/", headers={trace.TRACE_HEADER: bad})
    echoed = headers[trace.TRACE_HEADER]
    assert echoed != bad
    assert trace.valid_trace_id(echoed)
    assert not trace.valid_trace_id("x" * 65)
    assert not trace.valid_trace_id("")
    assert trace.valid_trace_id("deadbeef-0123-4567")


# ---------------------------------------------------------------------------
# /metrics exposition on the servers
# ---------------------------------------------------------------------------

@pytest.fixture()
def event_server(memory_storage):
    from predictionio_tpu.data.metadata import AccessKey
    from predictionio_tpu.serving.event_server import EventServer

    app = memory_storage.apps().insert("obs-app")
    memory_storage.events().init(app.id)
    key = AccessKey.generate(app.id)
    memory_storage.access_keys().insert(key)
    server = EventServer(storage=memory_storage, host="127.0.0.1", port=0).start()
    yield server, app, key
    server.stop()


def test_event_server_metrics_endpoint(event_server):
    server, app, key = event_server
    base = f"http://127.0.0.1:{server.port}"
    http_post(f"{base}/events.json?accessKey={key.key}",
              {"event": "view", "entityType": "user", "entityId": "u1"})
    status, headers, text = http_get(f"{base}/metrics")
    assert status == 200
    assert headers["Content-Type"].startswith("text/plain")
    samples = assert_valid_prometheus(text)
    key_ = ('pio_http_requests_total{server="PIOEventServer",'
            'method="POST",route="/events.json",status="201"}')
    assert samples[key_] >= 1
    # the duration histogram and in-flight gauge ride along
    assert any(k.startswith("pio_http_request_duration_seconds_bucket"
                            '{server="PIOEventServer"') for k in samples)
    assert 'pio_http_requests_in_flight{server="PIOEventServer"}' in samples


def test_storage_server_metrics_endpoint_without_auth_key(memory_storage):
    from predictionio_tpu.serving.storage_server import StorageServer

    server = StorageServer(storage=memory_storage, host="127.0.0.1",
                           port=0, auth_key="sekrit").start()
    try:
        base = f"http://127.0.0.1:{server.port}"
        # /metrics is a scrape endpoint: served before storage auth
        status, _, text = http_get(f"{base}/metrics")
        assert status == 200
        assert_valid_prometheus(text)
        # compile-cache and trace counters are part of the document
        assert "pio_jax_compile_cache_total" in text
        assert "pio_trace_spans_total" in text
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# engine server + end-to-end trace propagation over REST storage
# ---------------------------------------------------------------------------

def _rest_client(port):
    from predictionio_tpu.data.storage import Storage

    return Storage.from_env({
        "PIO_STORAGE_SOURCES_CENTRAL_TYPE": "rest",
        "PIO_STORAGE_SOURCES_CENTRAL_HOSTS": "127.0.0.1",
        "PIO_STORAGE_SOURCES_CENTRAL_PORTS": str(port),
        "PIO_STORAGE_REPOSITORIES_METADATA_NAME": "meta",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "CENTRAL",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_NAME": "events",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "CENTRAL",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_NAME": "models",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "CENTRAL",
    })


class _TraceAlgoHolder:
    """Serve-time storage client for StorageReadingAlgo (set per test)."""

    client = None
    app_id = None


def _build_reading_engine():
    from dataclasses import dataclass

    from predictionio_tpu.core import (
        Algorithm,
        DataSource,
        Engine,
        FirstServing,
        IdentityPreparator,
    )
    from predictionio_tpu.core.params import Params

    @dataclass
    class NoParams(Params):
        pass

    class OneDataSource(DataSource):
        def __init__(self, params):
            super().__init__(params)

        def read_training(self, ctx):
            return 1.0

    class StorageReadingAlgo(Algorithm):
        """predict() does a REST storage read — the serve-time storage
        round-trip the trace must decompose."""

        def __init__(self, params):
            super().__init__(params)

        def train(self, ctx, pd):
            return pd

        def predict(self, model, query):
            events = _TraceAlgoHolder.client.events().find(
                _TraceAlgoHolder.app_id)
            return {"events": len(events), "model": model}

    return Engine(OneDataSource, IdentityPreparator,
                  {"reader": StorageReadingAlgo}, FirstServing), NoParams


def test_trace_chain_engine_to_storage_server(memory_storage):
    """Acceptance: one served query produces a span chain sharing one
    trace id from the engine server through the REST storage backend to
    the storage server, and /metrics shows serving + span counts."""
    from predictionio_tpu.core.params import EngineParams
    from predictionio_tpu.data.event import Event
    from predictionio_tpu.serving.engine_server import EngineServer
    from predictionio_tpu.serving.storage_server import StorageServer
    from predictionio_tpu.workflow.train import run_train

    storage_server = StorageServer(storage=memory_storage, host="127.0.0.1",
                                   port=0).start()
    engine_server = None
    try:
        client = _rest_client(storage_server.port)
        app = client.apps().insert("traced-app")
        client.events().init(app.id)
        client.events().insert(
            Event(event="view", entity_type="user", entity_id="u1"), app.id)
        _TraceAlgoHolder.client = client
        _TraceAlgoHolder.app_id = app.id

        engine, NoParams = _build_reading_engine()
        ep = EngineParams(
            data_source_params=("", NoParams()),
            preparator_params=("", None),
            algorithm_params_list=[("reader", NoParams())],
            serving_params=("", None),
        )
        run_train(engine, ep, engine_id="traced", storage=memory_storage)
        engine_server = EngineServer(
            engine, "traced", host="127.0.0.1", port=0,
            storage=memory_storage).start()

        trace.clear_recent()
        trace_id = "feedfacecafebeef" * 2
        base = f"http://127.0.0.1:{engine_server.port}"
        status, headers, body = http_post(
            f"{base}/queries.json", {"q": 1},
            headers={trace.TRACE_HEADER: trace_id})
        assert status == 200
        assert json.loads(body)["events"] == 1
        # the trace id round-trips in the response
        assert headers[trace.TRACE_HEADER] == trace_id

        # each server's outer http span is emitted by ITS handler
        # thread as the instrument wrapper unwinds — AFTER the response
        # bytes already reached the caller, so the full chain lands
        # asynchronously with the client's return: poll briefly
        import time as _time

        wanted = ("http.engineserver", "serve.query", "serve.dispatch",
                  "storage.find", "http.storageserver")
        deadline = _time.monotonic() + 5.0
        while True:
            spans = trace.recent_spans(trace_id=trace_id)
            names = [s["name"] for s in spans]
            if all(e in names for e in wanted) or (
                    _time.monotonic() >= deadline):
                break
            _time.sleep(0.02)
        for expected in wanted:
            assert expected in names, (expected, names)
        assert {s["trace"] for s in spans} == {trace_id}
        # parenthood: serve.query is a child of the engine-server span
        by_name = {s["name"]: s for s in spans}
        assert by_name["serve.query"]["parent"] is not None
        assert all("duration_ms" in s and s["duration_ms"] >= 0
                   for s in spans)

        # /metrics on the engine server: serving histogram + span counts
        _, _, text = http_get(f"{base}/metrics")
        samples = assert_valid_prometheus(text)
        assert samples['pio_serving_request_seconds_count{engine="traced"}'] >= 1
        assert samples['pio_trace_spans_total{name="serve.query"}'] >= 1
    finally:
        if engine_server is not None:
            engine_server.stop()
        storage_server.stop()
        _TraceAlgoHolder.client = None


def test_span_records_nothing_without_active_trace():
    trace.clear_recent()
    with trace.span("orphan.work"):
        pass
    assert trace.recent_spans() == []


def test_span_records_error_and_nesting(tmp_path, monkeypatch):
    monkeypatch.setenv("PIO_TRACE_LOG", str(tmp_path / "trace.jsonl"))
    trace.clear_recent()
    token = trace.activate("t" * 32)
    try:
        with trace.span("outer"):
            with pytest.raises(ValueError):
                with trace.span("inner", detail="x"):
                    raise ValueError("boom")
    finally:
        trace.deactivate(token)
    spans = trace.recent_spans(trace_id="t" * 32)
    inner = next(s for s in spans if s["name"] == "inner")
    outer = next(s for s in spans if s["name"] == "outer")
    assert inner["parent"] == outer["span"]
    assert inner["error"].startswith("ValueError")
    assert inner["detail"] == "x"
    # mirrored as JSON lines to PIO_TRACE_LOG
    lines = [json.loads(l) for l in
             (tmp_path / "trace.jsonl").read_text().splitlines()]
    assert {l["name"] for l in lines} == {"outer", "inner"}


# ---------------------------------------------------------------------------
# ids: one process-wide generator, no system call an id (PERF.md §6, PR 38)
# ---------------------------------------------------------------------------

TRACE_ID_FORM = re.compile(r"[0-9a-f]{32}")
SPAN_ID_FORM = re.compile(r"[0-9a-f]{16}")


def test_ids_are_minted_without_a_system_call(monkeypatch):
    """``uuid4`` read ``os.urandom`` an id: ``getrandom`` with the
    interpreter released, five times a request and dispatch. The
    generator is seeded as the module is imported and asks nothing
    afterwards."""
    import os
    import uuid

    def refused(*args, **kwargs):
        raise AssertionError("an id asked the system for randomness")

    monkeypatch.setattr(os, "urandom", refused)
    monkeypatch.setattr(uuid, "uuid4", refused)
    for _ in range(100):
        trace_id, span_id = trace.new_trace_id(), trace._new_span_id()
        assert TRACE_ID_FORM.fullmatch(trace_id), trace_id
        assert SPAN_ID_FORM.fullmatch(span_id), span_id
        assert trace.valid_trace_id(trace_id)
        assert trace.valid_span_id(span_id)
    with trace.new_trace() as trace_id:
        with trace.span("ids.no_syscall") as span_id:
            pass
    record, = trace.recent_spans(trace_id=trace_id)
    assert (record["trace"], record["span"]) == (trace_id, span_id)


@pytest.mark.parametrize("mint", ["new_trace_id", "_new_span_id"])
def test_200000_ids_from_8_threads_are_distinct(mint):
    import threading

    draw = getattr(trace, mint)
    drawn = [None] * 8
    start = threading.Barrier(8)

    def work(k):
        start.wait()
        drawn[k] = [draw() for _ in range(25_000)]

    threads = [threading.Thread(target=work, args=(k,)) for k in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    ids = [i for part in drawn for i in part]
    assert len(ids) == 200_000 == len(set(ids))
    form = TRACE_ID_FORM if mint == "new_trace_id" else SPAN_ID_FORM
    assert all(form.fullmatch(i) for i in ids)


def _next_ids(n=8):
    return ([trace.new_trace_id() for _ in range(n)]
            + [trace._new_span_id() for _ in range(n)])


def test_a_forked_child_does_not_repeat_its_parents_next_ids():
    """A fork copies the generator's state: unseeded, the child's next
    ids ARE the parent's next ids, and two replicas of a fleet would
    label different requests alike."""
    import os
    import warnings

    read_end, write_end = os.pipe()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # forking beside other threads
        pid = os.fork()
    if pid == 0:  # the child: report and leave, running nothing else
        try:
            os.write(write_end, " ".join(_next_ids()).encode())
        finally:
            os._exit(0)
    os.close(write_end)
    ours = _next_ids()
    with os.fdopen(read_end, "rb") as pipe:
        theirs = pipe.read().decode().split()
    assert os.waitpid(pid, 0)[1] == 0
    assert len(theirs) == len(ours) == 16
    assert not set(theirs) & set(ours)
    assert all(trace.valid_span_id(i) for i in theirs)


def test_a_spawned_child_does_not_repeat_its_parents_next_ids():
    import concurrent.futures
    import multiprocessing

    spawn = multiprocessing.get_context("spawn")
    with concurrent.futures.ProcessPoolExecutor(1, mp_context=spawn) as pool:
        theirs = [pool.submit(trace.new_trace_id).result(timeout=120)
                  for _ in range(8)]
    ours = [trace.new_trace_id() for _ in range(8)]
    assert len(set(theirs)) == 8 and not set(theirs) & set(ours)
    assert all(TRACE_ID_FORM.fullmatch(i) for i in theirs)


def test_span_records_keep_their_ids_and_parent_chain():
    """What a record, the ``X-PIO-Trace-Id`` echo and obs/collect.py's
    stitching see: the trace's 32 hex digits on every record, a fresh
    16 hex digits a span, each child naming its parent's."""
    trace.clear_recent()
    with trace.new_trace() as trace_id:
        assert TRACE_ID_FORM.fullmatch(trace_id)
        assert trace.traced_headers() == {trace.TRACE_HEADER: trace_id}
        with trace.span("chain.a") as a:
            assert trace.traced_headers()[trace.PARENT_HEADER] == a
            with trace.span("chain.b") as b:
                with trace.span("chain.c") as c:
                    pass
            with trace.span("chain.d") as d:
                pass
    assert trace.current_trace_id() is None
    by_name = {r["name"]: r for r in trace.recent_spans(trace_id=trace_id)}
    records = [by_name[n] for n in ("chain.a", "chain.b", "chain.c", "chain.d")]
    assert [r["span"] for r in records] == [a, b, c, d]
    assert len({a, b, c, d}) == 4
    assert all(SPAN_ID_FORM.fullmatch(i) for i in (a, b, c, d))
    assert [r["parent"] for r in records] == [None, a, b, a]
    assert {r["trace"] for r in records} == {trace_id}


# ---------------------------------------------------------------------------
# JAX runtime instrumentation
# ---------------------------------------------------------------------------

def test_compile_cache_counters_via_jax_monitoring():
    """The jaxmon bridge is registered by enable_persistent_cache and
    counts the real jax.monitoring events."""
    from jax import monitoring

    from predictionio_tpu.parallel.compile_cache import enable_persistent_cache

    enable_persistent_cache()  # installs the bridge (idempotent)
    assert jaxmon.install()    # second call: already installed

    hits0 = jaxmon.COMPILE_CACHE_TOTAL.labels("hit").value
    miss0 = jaxmon.COMPILE_CACHE_TOTAL.labels("miss").value
    monitoring.record_event("/jax/compilation_cache/cache_hits")
    monitoring.record_event("/jax/compilation_cache/cache_misses")
    monitoring.record_event("/jax/compilation_cache/cache_misses")
    assert jaxmon.COMPILE_CACHE_TOTAL.labels("hit").value == hits0 + 1
    assert jaxmon.COMPILE_CACHE_TOTAL.labels("miss").value == miss0 + 2

    child = jaxmon.COMPILE_SECONDS.labels("backend_compile")
    c0, s0 = child.count, child.sum
    monitoring.record_event_duration_secs(
        "/jax/core/compile/backend_compile_duration", 0.5)
    assert child.count == c0 + 1
    assert child.sum == pytest.approx(s0 + 0.5)
    # unknown events are ignored, not errors
    monitoring.record_event("/jax/some/new/event")
    monitoring.record_event_duration_secs("/jax/some/new/duration", 1.0)


def test_real_compile_feeds_compile_histogram():
    """Jitting fresh code emits backend-compile durations through the
    installed listener — the integration proof without depending on
    persistent-cache behavior."""
    import jax
    import jax.numpy as jnp

    assert jaxmon.install()
    before = sum(
        jaxmon.COMPILE_SECONDS.labels(p).count
        for p in ("trace", "lower", "backend_compile")
    )

    @jax.jit
    def fresh(x):
        return (x * 3 + 1).sum()

    fresh(jnp.arange(7)).block_until_ready()
    after = sum(
        jaxmon.COMPILE_SECONDS.labels(p).count
        for p in ("trace", "lower", "backend_compile")
    )
    assert after > before


def test_transfer_and_train_step_instruments():
    d0 = jaxmon.TRANSFER_BYTES.labels("h2d").value
    jaxmon.record_transfer(1024, "h2d")
    jaxmon.record_transfer(None, "h2d")  # no-op, never raises
    assert jaxmon.TRANSFER_BYTES.labels("h2d").value == d0 + 1024

    c0 = jaxmon.TRAIN_STEP_SECONDS.labels().count
    jaxmon.observe_train_step(0.01)
    assert jaxmon.TRAIN_STEP_SECONDS.labels().count == c0 + 1

    # device gauges: CPU may report nothing — must not raise either way
    assert jaxmon.update_device_memory_gauges() >= 0


def test_batch_predict_dense_counts_transfers():
    import numpy as np

    from predictionio_tpu.models import batch_predict_dense

    class Model:
        def predict_batch(self, feats):
            return np.asarray([f.sum() for f in feats])

    h0 = jaxmon.TRANSFER_BYTES.labels("h2d").value
    d0 = jaxmon.TRANSFER_BYTES.labels("d2h").value
    out = batch_predict_dense(Model(), [(0, {"features": [1.0, 2.0]}),
                                        (1, {"features": [3.0, 4.0]})])
    assert [v for _, v in out] == [3.0, 7.0]
    assert jaxmon.TRANSFER_BYTES.labels("h2d").value == h0 + 16  # 2x2 f32
    assert jaxmon.TRANSFER_BYTES.labels("d2h").value > d0


# ---------------------------------------------------------------------------
# satellites: Stats.report pruning, ServingStats on the shared histogram
# ---------------------------------------------------------------------------

def test_stats_report_prunes_stale_buckets_without_update():
    from predictionio_tpu.serving.stats import Stats, _hour_bucket

    s = Stats()
    stale = _hour_bucket() - _dt.timedelta(hours=5)
    s._buckets[stale][7][(201, "old", "user")] = 3
    s._buckets[_hour_bucket()][7][(201, "new", "user")] = 1
    report = s.report(7)
    hours = [b["hour"] for b in report["buckets"]]
    assert stale.isoformat() not in hours
    assert len(hours) == 1
    # pruned from memory too, not just filtered out of the report
    assert stale not in s._buckets


def test_serving_stats_reports_from_shared_histogram():
    from predictionio_tpu.serving.engine_server import (
        _SERVING_SECONDS,
        ServingStats,
    )

    st = ServingStats("obs-hist-engine")
    for v in [0.001] * 50 + [0.2] * 50:
        st.record(v)
    assert st.request_count == 100
    assert st.total_serving_sec == pytest.approx(0.05 + 10.0)
    snap = st.snapshot()
    assert snap["requestCount"] == 100
    assert snap["lastServingSec"] == 0.2
    # bucket-interpolated percentiles from the SAME series /metrics shows
    assert 0.0005 <= snap["p50ServingSec"] <= 0.0025
    assert 0.1 <= snap["p99ServingSec"] <= 0.25
    child = _SERVING_SECONDS.labels("obs-hist-engine")
    assert child.count == 100
    # a second ServingStats for the same engine (a fleet replica, or a
    # restarted in-process server) starts ITS OWN counts from zero but
    # keeps recording into the SAME engine-wide registry series — the
    # SLO burn rate and shedding must see every replica's traffic
    fresh = ServingStats("obs-hist-engine")
    assert fresh.request_count == 0
    assert _SERVING_SECONDS.labels("obs-hist-engine").count == 100
    fresh.record(0.2)
    assert fresh.request_count == 1
    assert st.request_count == 100  # the older server's view is per-server
    assert _SERVING_SECONDS.labels("obs-hist-engine").count == 101


# ---------------------------------------------------------------------------
# pio metrics CLI
# ---------------------------------------------------------------------------

def test_pio_metrics_cli_remote_and_local(event_server, capsys):
    from predictionio_tpu.tools.cli import main

    server, app, key = event_server
    base = f"http://127.0.0.1:{server.port}"
    assert main(["metrics", "--url", base]) == 0
    out = capsys.readouterr().out
    assert "# TYPE pio_http_requests_total counter" in out
    assert_valid_prometheus(out)

    assert main(["metrics"]) == 0
    out = capsys.readouterr().out
    assert "pio_jax_compile_cache_total" in out
