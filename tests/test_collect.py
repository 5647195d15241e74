"""Fleet-wide observability federation (obs/collect.py): merge math,
cross-process trace stitching, the span-query surface, and the
acceptance e2e — a query driven through the router against a 3-replica
fleet (hedging armed) yields ONE stitched tree containing router,
replica and storage-server spans, and ``GET /admin/fleet/metrics``
bucket counts equal the sum of the members'.
"""

from __future__ import annotations

import json
import re
import time
import urllib.error
import urllib.request
from dataclasses import dataclass

import pytest

from predictionio_tpu.obs import collect, metrics, trace
from predictionio_tpu.resilience import chaos

from tests.test_health import get, get_json, train_const
from tests.test_fleet import post, running_fleet


# ---------------------------------------------------------------------------
# exposition parsing + merge math
# ---------------------------------------------------------------------------

M1 = """\
# HELP pio_x_total things
# TYPE pio_x_total counter
pio_x_total{kind="a"} 3
# TYPE pio_g gauge
pio_g{slot="z"} 7
# TYPE pio_serving_request_seconds histogram
pio_serving_request_seconds_bucket{engine="e",le="0.1"} 5
pio_serving_request_seconds_bucket{engine="e",le="+Inf"} 6
pio_serving_request_seconds_sum{engine="e"} 0.9
pio_serving_request_seconds_count{engine="e"} 6
"""

M2 = """\
# TYPE pio_x_total counter
pio_x_total{kind="a"} 4
pio_x_total{kind="b"} 1
# TYPE pio_g gauge
pio_g{slot="z"} 2
pio_g{other="y"} 5
# TYPE pio_serving_request_seconds histogram
pio_serving_request_seconds_bucket{engine="e",le="0.1"} 1
pio_serving_request_seconds_bucket{engine="e",le="+Inf"} 4
pio_serving_request_seconds_sum{engine="e"} 1.5
pio_serving_request_seconds_count{engine="e"} 4
"""


def merged_two_members():
    return collect.merge_families([
        ("r0", collect.parse_exposition(M1)),
        ("r1", collect.parse_exposition(M2)),
    ])


def test_parse_exposition_families_and_labels():
    fams = collect.parse_exposition(M1)
    assert fams["pio_x_total"]["kind"] == "counter"
    assert fams["pio_serving_request_seconds"]["kind"] == "histogram"
    samples = fams["pio_serving_request_seconds"]["samples"]
    key = ("pio_serving_request_seconds_bucket",
           (("engine", "e"), ("le", "0.1")))
    assert samples[key] == 5.0
    # exemplars and escapes survive
    fams = collect.parse_exposition(
        '# TYPE h histogram\nh_bucket{le="0.1"} 2 # {trace_id="ab"} '
        '0.05 123.0\nweird{msg="a\\"b"} 1\n')
    assert fams["h"]["samples"][("h_bucket", (("le", "0.1"),))] == 2.0
    assert fams["weird"]["samples"][("weird", (("msg", 'a"b'),))] == 1.0


def test_merge_counters_sum_and_histograms_sum_bucketwise():
    flat = collect.flat_samples(merged_two_members())
    assert flat['pio_x_total{kind="a"}'] == 7.0
    assert flat['pio_x_total{kind="b"}'] == 1.0  # disjoint label sets union
    assert flat['pio_serving_request_seconds_bucket'
                '{engine="e",le="0.1"}'] == 6.0
    assert flat['pio_serving_request_seconds_bucket'
                '{engine="e",le="+Inf"}'] == 10.0
    assert flat['pio_serving_request_seconds_count{engine="e"}'] == 10.0
    assert flat['pio_serving_request_seconds_sum{engine="e"}'] == 2.4


def test_merge_gauges_keep_member_label():
    flat = collect.flat_samples(merged_two_members())
    # a gauge is NEVER summed: one series per member, member visible
    assert flat['pio_g{member="r0",slot="z"}'] == 7.0
    assert flat['pio_g{member="r1",slot="z"}'] == 2.0
    assert flat['pio_g{member="r1",other="y"}'] == 5.0
    assert 'pio_g{slot="z"}' not in flat


def test_render_merged_is_reparseable():
    merged = merged_two_members()
    text = collect.render_merged(merged)
    assert "# TYPE pio_serving_request_seconds histogram" in text
    again = collect.parse_exposition(text)
    assert collect.flat_samples(
        collect.merge_families([])) == {}
    # counters re-parse to the same values (gauges re-parse with their
    # member label already attached)
    assert again["pio_x_total"]["samples"][
        ("pio_x_total", (("kind", "a"),))] == 7.0


def test_fleet_slo_burn_over_merged_histogram(monkeypatch):
    monkeypatch.setenv("PIO_SLO_LATENCY_MS", "100")
    monkeypatch.setenv("PIO_SLO_LATENCY_OBJECTIVE", "0.99")
    slo = collect.fleet_slo(merged_two_members())
    # good = merged counts in buckets covering 100ms: le=0.1 -> 6
    assert slo["good"] == 6.0 and slo["total"] == 10.0
    assert slo["error_rate"] == pytest.approx(0.4)
    assert slo["burn"] == pytest.approx(40.0)
    # no traffic -> no burn, distinguishable from burning at 0
    empty = collect.fleet_slo(collect.merge_families([]))
    assert empty["burn"] is None and empty["error_rate"] is None


def test_quantile_from_flat_interpolates():
    flat = collect.flat_samples(merged_two_members())
    q = collect.quantile_from_flat(
        flat, "pio_serving_request_seconds", 0.5)
    # rank 5 of 10 inside the first bucket [0, 0.1): interpolated
    assert 0.0 < q < 0.1
    assert collect.quantile_from_flat({}, "nope", 0.5) is None


def test_merge_degrades_on_dead_member():
    """A member answering 5xx (or nothing) must degrade the merge to
    the members that answered — never fail it."""
    import socket

    # a port with nothing listening: transport failure
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        dead_port = s.getsockname()[1]
    members = [collect.Member("local", None),
               collect.Member("gone", f"http://127.0.0.1:{dead_port}")]
    report = collect.federate_metrics(members)
    by_name = {m["name"]: m for m in report["members"]}
    assert by_name["local"]["ok"] is True
    assert by_name["gone"]["ok"] is False and by_name["gone"]["error"]
    assert report["merged_from"] == ["local"]
    assert report["samples"]  # the local registry still merged


# ---------------------------------------------------------------------------
# tree assembly
# ---------------------------------------------------------------------------

def synthetic_spans():
    return [
        {"trace": "t", "span": "a", "parent": None, "name": "http.router",
         "server": "router", "start_unix": 1.0, "duration_ms": 50.0},
        {"trace": "t", "span": "b", "parent": "a", "name": "router.attempt",
         "replica": "r0", "start_unix": 1.001, "duration_ms": 49.0},
        {"trace": "t", "span": "c", "parent": "b",
         "name": "http.engineserver", "server": "engineserver",
         "start_unix": 1.002, "duration_ms": 47.0},
        {"trace": "t", "span": "h", "parent": "a", "name": "router.attempt",
         "replica": "r1", "hedge": True, "start_unix": 1.03,
         "duration_ms": 12.0},
        {"trace": "t", "span": "e", "parent": "zz",
         "name": "http.storageserver", "server": "storageserver",
         "start_unix": 1.01, "duration_ms": 3.0},
    ]


def test_build_tree_annotations_and_missing_parent():
    doc = collect.build_tree("t", synthetic_spans(),
                             members=[{"name": "local", "ok": True,
                                       "evicted_total": 9}])
    assert doc["span_count"] == 5
    assert set(doc["processes"]) == {"router", "engineserver",
                                     "storageserver"}
    # the evicted parent became an explicit placeholder root
    assert doc["complete"] is False and doc["missing_spans"] == ["zz"]
    roots = doc["roots"]
    assert len(roots) == 2
    real = next(r for r in roots if not r.get("missing"))
    placeholder = next(r for r in roots if r.get("missing"))
    assert "evicted" in placeholder["note"] and "9" in placeholder["note"]
    assert placeholder["children"][0]["name"] == "http.storageserver"
    # children sorted by start; process/replica inherit down the tree
    attempts = real["children"]
    assert [a["replica"] for a in attempts] == ["r0", "r1"]
    assert attempts[1]["hedge"] is True
    engine = attempts[0]["children"][0]
    assert engine["process"] == "engineserver"
    assert engine["replica"] == "r0"  # inherited from the attempt
    # parent-edge latency: child start minus parent start, in ms
    assert attempts[0]["edge_ms"] == pytest.approx(1.0)
    assert engine["edge_ms"] == pytest.approx(1.0)


def test_build_tree_dedupes_nothing_but_renders_complete():
    spans = [s for s in synthetic_spans() if s["span"] != "e"]
    doc = collect.build_tree("t", spans)
    assert doc["complete"] is True and len(doc["roots"]) == 1


def test_build_tree_breaks_parent_cycles():
    """A malformed member payload (self-parenting span, two spans
    parenting each other) must not hang or vanish: the cycle is broken
    at its earliest span, promoted to an annotated root, and the doc
    reports not-complete."""
    spans = [
        {"trace": "t", "span": "s", "parent": "s", "name": "self.loop",
         "start_unix": 1.0, "duration_ms": 1.0},
        {"trace": "t", "span": "x", "parent": "y", "name": "cyc.a",
         "start_unix": 2.0, "duration_ms": 1.0},
        {"trace": "t", "span": "y", "parent": "x", "name": "cyc.b",
         "start_unix": 3.0, "duration_ms": 1.0},
    ]
    doc = collect.build_tree("t", spans)
    assert doc["complete"] is False
    assert set(doc["cyclic_spans"]) == {"s", "x"}
    rendered = collect.format_trace_tree(doc)  # must terminate
    assert "cycle" in rendered
    names = {n.get("name") for n in _tree_nodes(doc)}
    assert names == {"self.loop", "cyc.a", "cyc.b"}  # nothing dropped


def test_format_trace_tree_renders_glyphs_and_partial():
    doc = collect.build_tree("t", synthetic_spans(),
                             members=[{"name": "local", "ok": True,
                                       "evicted_total": 9}])
    doc["members"] = [{"name": "local", "url": None, "role": "local",
                       "ok": True, "spans": 5, "evicted_total": 9},
                      {"name": "gone", "url": "http://x", "role": "replica",
                       "ok": False, "error": "HTTP 503"}]
    text = collect.format_trace_tree(doc)
    assert "PARTIAL" in text
    assert "└─" in text and "├─" in text
    assert "replica=r0" in text and "hedge" in text
    assert "missing span zz" in text
    assert "ERROR: HTTP 503" in text
    assert "<engineserver>" in text


# ---------------------------------------------------------------------------
# span ring: PIO_SPAN_RING + eviction counter
# ---------------------------------------------------------------------------

def test_span_ring_env_capacity_and_eviction_counter(monkeypatch):
    monkeypatch.setenv("PIO_SPAN_RING", "4")
    trace.clear_recent()
    before = trace.evicted_total()
    token = trace.activate(trace.new_trace_id())
    try:
        for _ in range(7):
            with trace.span("ring.unit"):
                pass
    finally:
        trace.deactivate(token)
    assert len(trace.recent_spans()) == 4
    assert trace.evicted_total() == before + 3
    # restoring the env restores the capacity on the next emit
    monkeypatch.setenv("PIO_SPAN_RING", "64")
    token = trace.activate(trace.new_trace_id())
    try:
        with trace.span("ring.unit"):
            pass
    finally:
        trace.deactivate(token)
    assert trace.recent_spans() and len(trace.recent_spans()) == 5


def test_traced_headers_carry_context_only_when_active():
    assert trace.traced_headers({"A": "b"}) == {"A": "b"}
    token = trace.activate("feedface" * 4)
    try:
        with trace.span("hdr.unit"):
            headers = trace.traced_headers({"A": "b"})
            assert headers["A"] == "b"
            assert headers[trace.TRACE_HEADER] == "feedface" * 4
            assert trace.valid_span_id(headers[trace.PARENT_HEADER])
    finally:
        trace.deactivate(token)


# ---------------------------------------------------------------------------
# span-query surface on a live server
# ---------------------------------------------------------------------------

def test_admin_spans_endpoint(memory_storage):
    from predictionio_tpu.serving.storage_server import StorageServer

    server = StorageServer(storage=memory_storage, host="127.0.0.1",
                           port=0).start()
    try:
        base = f"http://127.0.0.1:{server.port}"
        tid = "ad0be" + trace.new_trace_id()[:27]
        token = trace.activate(tid)
        try:
            with trace.span("spanpage.unit", detail=1):
                pass
        finally:
            trace.deactivate(token)
        status, page = get_json(f"{base}/admin/spans?trace={tid}")
        assert status == 200
        assert page["server"] == "PIOStorageServer"
        assert page["ring_capacity"] == trace.ring_capacity()
        assert isinstance(page["evicted_total"], int)
        assert [s["name"] for s in page["spans"]] == ["spanpage.unit"]
        # a non-id-shaped trace filter is rejected, not echoed around
        status, _ = get_json(f"{base}/admin/spans?trace=zzz")
        assert status == 400
        status, _ = get_json(f"{base}/admin/spans?trace={tid}&n=x")
        assert status == 400
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# acceptance e2e: 3-replica fleet + storage server, hedging armed
# ---------------------------------------------------------------------------

class _Holder:
    client = None
    app_id = None


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


def _build_reading_engine():
    from predictionio_tpu.core import (Algorithm, DataSource, Engine,
                                       FirstServing, IdentityPreparator)
    from predictionio_tpu.core.params import Params

    @dataclass
    class NoParams(Params):
        pass

    class OneDataSource(DataSource):
        def read_training(self, ctx):
            return 1.0

    class StorageReadingAlgo(Algorithm):
        """predict() does a REST storage read — the cross-process hop
        the stitched trace must contain."""

        def train(self, ctx, pd):
            return pd

        def predict(self, model, query):
            events = _Holder.client.events().find(_Holder.app_id)
            return {"events": len(events), "model": model}

    return Engine(OneDataSource, IdentityPreparator,
                  {"reader": StorageReadingAlgo}, FirstServing), NoParams


def _tree_nodes(doc):
    out = []

    def walk(node):
        out.append(node)
        for child in node.get("children") or []:
            walk(child)

    for root in doc.get("roots") or []:
        walk(root)
    return out


def _canon_serving(samples):
    """Serving-histogram samples with canonically sorted labels, so a
    member's rendered text and the merged flat form compare equal."""
    out = {}
    for key, value in samples.items():
        if not key.startswith("pio_serving_request_seconds"):
            continue
        name, _, labels = key.partition("{")
        labels = labels.rstrip("}")
        pairs = sorted(re.findall(r'([a-zA-Z_]+)="([^"]*)"', labels))
        out[(name, tuple(pairs))] = out.get((name, tuple(pairs)), 0.0) + value
    return out


def test_acceptance_stitched_trace_and_fleet_metrics(memory_storage,
                                                     monkeypatch):
    """ISSUE acceptance: a query driven through the router against a
    3-replica fleet (hedging armed) yields a single stitched tree
    containing router, replica and storage-server spans, and
    ``GET /admin/fleet/metrics`` bucket counts equal the sum of the
    members' — zero non-429 errors under load."""
    from predictionio_tpu.core.params import EngineParams
    from predictionio_tpu.data.event import Event
    from predictionio_tpu.serving.engine_server import EngineServer
    from predictionio_tpu.serving.fleet import (FleetSupervisor,
                                                threaded_fleet)
    from predictionio_tpu.serving.router import QueryRouter
    from predictionio_tpu.serving.storage_server import StorageServer
    from predictionio_tpu.tools import cli
    from predictionio_tpu.workflow.train import run_train

    storage_server = StorageServer(storage=memory_storage,
                                   host="127.0.0.1", port=0).start()
    fleet = router = None
    try:
        client = _rest_client(storage_server.port)
        app = client.apps().insert("fed-app")
        client.events().init(app.id)
        client.events().insert(
            Event(event="view", entity_type="user", entity_id="u1"),
            app.id)
        _Holder.client, _Holder.app_id = client, app.id
        engine, NoParams = _build_reading_engine()
        ep = EngineParams(
            data_source_params=("", NoParams()),
            preparator_params=("", None),
            algorithm_params_list=[("reader", NoParams())],
            serving_params=("", None),
        )
        run_train(engine, ep, engine_id="fed", storage=memory_storage)

        # the storage server joins the pane of glass as a configured
        # member (the "event/storage/stream addresses" knob)
        monkeypatch.setenv(
            "PIO_OBS_MEMBERS",
            f"storage=http://127.0.0.1:{storage_server.port}")

        def factory(name):
            return EngineServer(engine, "fed", host="127.0.0.1", port=0,
                                storage=memory_storage, chaos_tag=name)

        fleet = FleetSupervisor(threaded_fleet(3, factory),
                                probe_interval=0.1).start()
        assert fleet.wait_ready(timeout=60), fleet.snapshot()
        router = QueryRouter(fleet, host="127.0.0.1", port=0).start()
        base = f"http://127.0.0.1:{router.port}"

        trace.clear_recent()
        trace_ids = []
        for _ in range(30):  # past HedgeClock.min_samples: hedging arms
            status, body, headers = post(
                base + "/queries.json", body=b'{"q": 1}')
            assert status == 200, body  # zero non-429 (indeed, none)
            assert json.loads(body)["events"] == 1
            trace_ids.append(headers[trace.TRACE_HEADER])
        assert router.hedge.deadline() is not None  # hedging armed

        tid = trace_ids[-1]
        wanted = ("http.router", "router.attempt", "http.engineserver",
                  "storage.find", "http.storageserver")
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            names = [s["name"] for s in trace.recent_spans(trace_id=tid)]
            if all(w in names for w in wanted):
                break
            time.sleep(0.02)
        assert all(w in names for w in wanted), names

        # -- the stitched tree off the router -------------------------------
        status, doc = get_json(base + f"/admin/trace?id={tid}")
        assert status == 200
        assert doc["complete"] is True, doc.get("missing_spans")
        assert len(doc["roots"]) == 1  # ONE tree, not a forest
        root = doc["roots"][0]
        assert root["name"] == "http.router"
        assert {"router", "engineserver", "storageserver"} <= set(
            doc["processes"])
        nodes = _tree_nodes(doc)
        by_name = {}
        for node in nodes:
            by_name.setdefault(node.get("name"), []).append(node)
        # the replica hop is a child of a router.attempt span, and the
        # storage-server edge sits under the rest client's storage span
        engine_edge = by_name["http.engineserver"][0]
        assert engine_edge["process"] == "engineserver"
        assert engine_edge["replica"] in {"r0", "r1", "r2"}
        storage_edge = by_name["http.storageserver"][0]
        assert storage_edge["process"] == "storageserver"
        assert isinstance(storage_edge.get("edge_ms"), (int, float))
        # every fleet member (and the configured storage) answered
        ok_members = {m["name"] for m in doc["members"] if m["ok"]}
        assert {"local", "r0", "r1", "r2", "storage"} <= ok_members

        # -- pio trace renders the same document ----------------------------
        rc = cli.main(["trace", tid, "--url", base])
        assert rc == 0
        rc = cli.main(["trace", "feedfacefeedface", "--url", base])
        assert rc == 1  # unknown trace: no spans

        # -- metric federation: merged == sum of the members ----------------
        status, report = get_json(base + "/admin/fleet/metrics")
        assert status == 200
        assert all(m["ok"] for m in report["members"]), report["members"]
        assert {m["name"] for m in report["members"]} == {
            "r0", "r1", "r2", "storage"}
        member_sums = {}
        for member in report["members"]:
            _, text, _ = get(member["url"] + "/metrics")
            for key, value in _canon_serving(
                    metrics.samples_dict(text)).items():
                member_sums[key] = member_sums.get(key, 0.0) + value
        merged = _canon_serving(report["samples"])
        bucket_keys = [k for k in member_sums
                       if k[0].endswith("_bucket")]
        assert bucket_keys
        for key in bucket_keys:
            assert merged[key] == member_sums[key], key
        # the merged serving histogram carries the fleet SLO burn
        assert report["slo"]["total"] >= 30
        assert report["slo"]["burn"] is not None
        # the text form re-parses
        status, text, _ = get(base + "/admin/fleet/metrics?format=prom")
        assert status == 200 and "# TYPE" in text
        assert collect.parse_exposition(text)

        # -- fleet-wide tail attribution ------------------------------------
        status, tail = get_json(base + "/admin/fleet/tail")
        assert status == 200
        assert tail["total_count"] >= 4
        assert tail["stages"], tail
        assert {m["name"] for m in tail["members"]} == {
            "r0", "r1", "r2", "storage"}
        assert set(tail["member_tail"]) <= {"r0", "r1", "r2", "storage"}
        assert sum(e["tail_count"] for e in
                   tail["member_tail"].values()) == tail["tail_count"]

        # -- pio top --fleet drives off the federated endpoint --------------
        rc = cli.main(["top", "--fleet", "--once", "--url", base])
        assert rc == 0
    finally:
        if router is not None:
            router.stop()
        if fleet is not None:
            fleet.stop()
        storage_server.stop()
        _Holder.client = None


def test_hedged_attempt_is_sibling_span(memory_storage, monkeypatch):
    """A hedged second attempt appears as a SIBLING ``router.attempt``
    span (marked hedge) under the same trace — the stitched tree shows
    the placement decision, not just its winner."""
    monkeypatch.setenv("PIO_HEDGE_MIN_MS", "40")
    engine, _ = train_const(memory_storage)
    with running_fleet(memory_storage, engine, n=2) as (fleet, router,
                                                        base):
        for _ in range(25):  # arm the hedge clock
            status, _, _ = post(base + "/queries.json")
            assert status == 200
        chaos.configure("batcher@r1:hang:2s")
        trace_ids = []
        for _ in range(8):
            status, body, headers = post(base + "/queries.json")
            assert status == 200, body
            trace_ids.append(headers[trace.TRACE_HEADER])
        chaos.clear()
        # the hung primary's attempt span seals when the hang releases:
        # poll for a trace carrying BOTH attempts
        hedged = None
        deadline = time.monotonic() + 6.0
        while hedged is None and time.monotonic() < deadline:
            for tid in trace_ids:
                spans = [s for s in trace.recent_spans(trace_id=tid)
                         if s["name"] == "router.attempt"]
                if len(spans) >= 2 and any(s.get("hedge") for s in spans):
                    hedged = tid
                    break
            time.sleep(0.05)
        assert hedged is not None, "no hedged trace found"
        doc = collect.stitch_trace(hedged,
                                   collect.default_members(router))
        attempts = [n for n in _tree_nodes(doc)
                    if n.get("name") == "router.attempt"]
        assert len(attempts) >= 2
        parents = {a.get("parent") for a in attempts}
        assert len(parents) == 1  # siblings under the one router span
        assert any(a.get("hedge") for a in attempts)
        replicas = {a.get("replica") for a in attempts}
        assert replicas == {"r0", "r1"}


def test_canary_shadow_span_rides_the_original_trace(memory_storage):
    """The router's canary shadow replays a query on the worker pool
    AFTER the client is answered — its ``router.shadow`` span must
    still join the ORIGINAL request's trace as a marked sibling."""
    engine, _ = train_const(memory_storage)
    with running_fleet(memory_storage, engine, n=2) as (fleet, router,
                                                        base):
        replica = fleet.ready_replicas()[0]
        tid = trace.new_trace_id()
        ctx = trace.SpanContext(trace_id=tid, span_id="feedfacecafe0001")
        router._canary_shadow(replica, b'{"mult": 2}', b'{"result": 6.0}',
                              ctx=ctx)
        deadline = time.monotonic() + 5.0
        shadow = None
        while shadow is None and time.monotonic() < deadline:
            for s in trace.recent_spans(trace_id=tid):
                if s["name"] == "router.shadow":
                    shadow = s
            time.sleep(0.02)
        assert shadow is not None
        assert shadow["parent"] == "feedfacecafe0001"
        assert shadow["shadow"] is True
        assert shadow["replica"] == replica.name


def test_fleet_tail_degrades_on_dead_member(memory_storage):
    """A member mid-restart degrades the fleet tail merge (reported,
    not fatal) — the surviving members still attribute."""
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        dead_port = s.getsockname()[1]
    members = [collect.Member("local", None),
               collect.Member("gone", f"http://127.0.0.1:{dead_port}")]
    report = collect.federate_tail(members)
    by_name = {m["name"]: m for m in report["members"]}
    assert by_name["local"]["ok"] is True
    assert by_name["gone"]["ok"] is False


def test_dashboard_trace_view(memory_storage):
    from predictionio_tpu.tools.dashboard import DashboardServer

    server = DashboardServer(storage=memory_storage, host="127.0.0.1",
                             port=0).start()
    try:
        base = f"http://127.0.0.1:{server.port}"
        status, text, _ = get(base + "/trace")
        assert status == 200 and "<form" in text
        tid = trace.new_trace_id()
        token = trace.activate(tid)
        try:
            with trace.span("dash.unit"):
                pass
        finally:
            trace.deactivate(token)
        status, text, _ = get(base + f"/trace?id={tid}")
        assert status == 200 and "dash.unit" in text
        status, text, _ = get(base + "/trace?id=%3Cscript%3E")
        assert status == 200 and "not an id-shaped" in text
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# ops-journal + anomaly federation
# ---------------------------------------------------------------------------

def _dead_member():
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    return collect.Member("gone", f"http://127.0.0.1:{port}")


def test_federate_journal_merges_and_degrades():
    from predictionio_tpu.obs import journal

    journal.emit("reload", instance="i-1")
    journal.emit("breaker", target="t", state="open")
    members = [collect.Member("local", None), _dead_member()]
    report = collect.federate_journal(members, n=50)
    by_name = {m["name"]: m for m in report["members"]}
    assert by_name["local"]["ok"] is True
    assert by_name["local"]["events"] == 2
    assert by_name["gone"]["ok"] is False and by_name["gone"]["error"]
    assert report["merged_from"] == ["local"]
    kinds = [e["kind"] for e in report["events"]]
    assert kinds == ["reload", "breaker"]  # wall-clock ordered
    assert all(e["fleet_member"] == "local" for e in report["events"])


def test_federate_journal_dedupes_shared_process_journal():
    """Threaded replicas share one process journal: the same event
    reported by two member views must appear once, stamped with the
    first member that reported it."""
    from predictionio_tpu.obs import journal

    journal.emit("swap", phase="start")
    members = [collect.Member("r0", None), collect.Member("r1", None)]
    report = collect.federate_journal(members, n=50)
    assert [m["events"] for m in report["members"]] == [1, 0]
    assert len(report["events"]) == 1
    assert report["events"][0]["fleet_member"] == "r0"


def test_federate_journal_kind_filter_passes_through():
    from predictionio_tpu.obs import journal

    journal.emit("reload", instance="i-1")
    journal.emit("patch", outcome="ok")
    report = collect.federate_journal(
        [collect.Member("local", None)], n=50, kind="patch")
    assert [e["kind"] for e in report["events"]] == ["patch"]


def test_federate_anomaly_unions_active_and_degrades():
    from predictionio_tpu.obs import anomaly

    verdict = {"mode": "step", "direction": "up", "z": 9.0,
               "baseline": 10.0, "recent": 15.0, "onset_ts": 1450.0,
               "since": 1540.0}
    anomaly.SENTINEL._active["serve_p99_ms.e"] = dict(verdict)
    members = [collect.Member("local", None), _dead_member()]
    report = collect.federate_anomaly(members)
    by_name = {m["name"]: m for m in report["members"]}
    assert by_name["local"]["ok"] is True
    assert by_name["local"]["active"] == 1
    assert by_name["gone"]["ok"] is False and by_name["gone"]["error"]
    assert report["merged_from"] == ["local"]
    assert report["any_active"] is True
    row = report["active"][0]
    assert row["series"] == "serve_p99_ms.e"
    assert row["fleet_member"] == "local"
    assert row["mode"] == "step"


def test_federate_anomaly_all_quiet():
    report = collect.federate_anomaly([collect.Member("local", None)])
    assert report["any_active"] is False
    assert report["active"] == []
    assert report["members"][0]["active"] == 0

