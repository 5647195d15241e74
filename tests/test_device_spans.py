"""The program's spans on the profiler's clock (obs/trace.device_span):
every serving and training layer boundary ISSUE 25 names, with its parent,
read back from a real jax.profiler capture on the CPU."""

import datetime as dt
import glob
import json
import os
import pickle
import subprocess
import sys
import threading
import time
import urllib.request
import uuid

import numpy as np
import pytest

from predictionio_tpu.obs import trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: span -> the span it lies in, on its own thread (ISSUE 25, Tentpole 2)
PARENTS = {
    "pio:http.request": None,
    "pio:http.parse": "pio:http.request",
    "pio:serve.admit": "pio:http.request",
    "pio:serve.wait": "pio:http.request",
    "pio:http.respond": "pio:http.request",
    "pio:batch.collect": None,
    "pio:batch.dispatch": None,
    "pio:batch.deliver": None,
    "pio:engine.prepare": "pio:batch.dispatch",
    "pio:index.search": "pio:batch.dispatch",
    "pio:index.enqueue": "pio:index.search",
    "pio:index.fetch": "pio:index.search",
    "pio:index.route": "pio:index.search",
    "pio:engine.decode": "pio:batch.dispatch",
    "pio:train.epoch": None,
    "pio:train.report": None,
}


def test_device_span_imports_no_jax():
    """The event and storage servers hold no JAX: in such a process a
    device_span (and span, which opens one) is a null context."""
    code = (
        "import sys\n"
        "import predictionio_tpu.obs\n"
        "from predictionio_tpu.obs import trace\n"
        "with trace.device_span('a.b', size=1):\n"
        "    with trace.span('c.d', device='e.f', n=2):\n"
        "        pass\n"
        "with trace.new_trace():\n"
        "    with trace.span('c.d'):\n"
        "        pass\n"
        "assert trace.recent_spans()[-1]['name'] == 'c.d'\n"
        "assert 'jax' not in sys.modules, sorted(\n"
        "    m for m in sys.modules if m.startswith('jax'))\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr


def read_spans(trace_dir):
    """[(name, start, end, line, attrs)] of every pio: annotation."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out, line_no = [], 0
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            line_no += 1
            for e in line.events:
                if e.name.startswith(trace.DEVICE_SPAN_PREFIX):
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                line_no, dict(e.stats)))
    return out


def parent_of(span, spans):
    """The innermost span of the same thread that contains ``span``."""
    name, start, end, line, _ = span
    around = [s for s in spans if s is not span and s[3] == line
              and s[1] <= start and s[2] >= end]
    return min(around, key=lambda s: s[2] - s[1])[0] if around else None


def start_profiler(trace_dir):
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)


def deploy_tiny_als(memory_storage, n_users=40, n_items=300, rank=8):
    from predictionio_tpu.core.params import EngineParams
    from predictionio_tpu.data.bimap import BiMap
    from predictionio_tpu.data.metadata import EngineInstance, Model
    from predictionio_tpu.models.als import ALSModel, ALSParams
    from predictionio_tpu.ops.als import ALSFactors
    from predictionio_tpu.serving.engine_server import EngineServer
    from predictionio_tpu.templates.recommendation import (
        RecoDataSourceParams, recommendation_engine)

    rng = np.random.default_rng(7)
    X = rng.normal(size=(n_users, rank)).astype(np.float32)
    Y = rng.normal(size=(n_items, rank)).astype(np.float32)
    ep = EngineParams(
        data_source_params=("", RecoDataSourceParams(app_name="spans")),
        preparator_params=("", None),
        algorithm_params_list=[("als", ALSParams(rank=rank))],
        serving_params=("", None),
    ).to_json_dict()
    now = dt.datetime.now(tz=dt.timezone.utc)
    instance = EngineInstance(
        id=uuid.uuid4().hex, status="COMPLETED", start_time=now,
        end_time=now, engine_id="spans", engine_version="0",
        engine_variant="default", engine_factory="test", batch="test",
        data_source_params=json.dumps(ep["dataSourceParams"]),
        preparator_params=json.dumps(ep["preparatorParams"]),
        algorithms_params=json.dumps(ep["algorithmParamsList"]),
        serving_params=json.dumps(ep["servingParams"]))
    memory_storage.engine_instances().insert(instance)
    model = ALSModel(
        ALSFactors(user_factors=X, item_factors=Y),
        BiMap.from_vocab([f"u{i}" for i in range(n_users)]),
        BiMap.from_vocab([f"i{i}" for i in range(n_items)]))
    memory_storage.models().insert(Model(
        id=instance.id, models=pickle.dumps([model])))
    return EngineServer(recommendation_engine(), "spans", host="127.0.0.1",
                        port=0, storage=memory_storage).start()


def post_query(port, user, trace_id=None):
    headers = {"Content-Type": "application/json"}
    if trace_id:
        headers[trace.TRACE_HEADER] = trace_id
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/queries.json",
        data=json.dumps({"user": user, "num": 5}).encode(), headers=headers)
    with urllib.request.urlopen(req, timeout=30) as resp:
        assert resp.status == 200
        return json.loads(resp.read())


def test_every_serving_boundary_is_a_span_with_its_parent(
        memory_storage, tmp_path, monkeypatch):
    import jax

    from predictionio_tpu.resilience import chaos

    # the scorer's device route, so that a dispatch enqueues and fetches
    monkeypatch.setenv("PIO_SERVE_PLACEMENT", "device")
    server = deploy_tiny_als(memory_storage)
    try:
        post_query(server.port, "u1")                 # warm, untraced
        start_profiler(tmp_path)
        lone_id = "feedfacecafebeef" * 2
        try:
            assert post_query(server.port, "u2", lone_id)["itemScores"]
            # the worker sleeps at its chaos seam with one query in hand:
            # the three that arrive meanwhile leave as one batch
            chaos.configure("batcher:latency:150ms")
            threads = [threading.Thread(target=post_query,
                                        args=(server.port, f"u{3 + i}"))
                       for i in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(30)
            assert not any(t.is_alive() for t in threads)
            # a client has its answer before its handler has closed
            # pio:http.request, and a span that is open when the trace
            # stops is not in it: wait for the handlers' bookkeeping
            deadline = time.monotonic() + 10
            while server.inflight_count() and time.monotonic() < deadline:
                time.sleep(0.005)
        finally:
            chaos.reset()
            jax.profiler.stop_trace()
    finally:
        server.stop()
    spans = read_spans(tmp_path)
    names = {s[0] for s in spans}
    serving = {n for n in PARENTS if not n.startswith("pio:train.")}
    assert serving <= names, serving - names
    for span in spans:
        if span[0] in serving:
            assert parent_of(span, spans) == PARENTS[span[0]], span
    paths = {s[4]["path"] for s in spans if s[0] == "pio:batch.dispatch"}
    assert paths == {"lone", "batched"}
    sizes = [s[4]["size"] for s in spans if s[0] == "pio:batch.dispatch"]
    assert max(sizes) > 1 and sum(sizes) == 5
    seqs = [s[4]["seq"] for s in spans if s[0] == "pio:batch.dispatch"]
    assert len(set(seqs)) == len(seqs)
    # every search says which side answered it and how many rows it held:
    # one marker a dispatch, the lone query's and the batches' alike
    routes = [s[4] for s in spans if s[0] == "pio:index.route"]
    assert len(routes) == len(sizes)
    assert {r["route"] for r in routes} == {"xla_device"}
    assert sorted(r["rows"] for r in routes) == sorted(sizes)
    # the engine's query vectors are host data (rows of the user table)
    assert {r["inputs"] for r in routes} == {"host"}
    # one request, one identifier: on the handler thread and, for a query
    # that was dispatched alone, on the worker's dispatch and below it
    mine = [s for s in spans if s[4].get("trace") == lone_id]
    assert {s[0] for s in mine} >= {
        "pio:http.request", "pio:http.parse", "pio:serve.admit",
        "pio:serve.wait", "pio:http.respond", "pio:batch.dispatch",
        "pio:engine.prepare", "pio:index.search", "pio:index.enqueue",
        "pio:index.fetch", "pio:engine.decode"}
    assert len({s[3] for s in mine}) == 2             # handler and worker
    others = [s for s in spans if s[0] == "pio:http.request"
              and s[4].get("trace") != lone_id]
    assert len({s[4]["trace"] for s in others}) == len(others) == 4


@pytest.mark.parametrize("kernel,route", [("on", "kernel"),
                                          ("off", "xla_device")])
def test_the_route_marker_says_where_the_query_vectors_were(
        tmp_path, kernel, route):
    """``inputs`` = ``host`` for host data (it crosses inside the compiled
    call), ``device`` for a ``jax.Array`` (a program's output stays where it
    is: the sequence engine's head); ``ExactIndex.stats()`` counts both."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.index.exact import ExactIndex

    rng = np.random.default_rng(30)
    index = ExactIndex(kernel=kernel, block_items=256, placement="device")
    index.build(rng.normal(size=(300, 8)).astype(np.float32))
    queries = rng.normal(size=(4, 8)).astype(np.float32)
    on_device = jnp.asarray(queries[:2])
    index.search(queries, 5)                          # warm, untraced
    index.search(on_device, 5)
    start_profiler(tmp_path)
    try:
        index.search(queries, 5)
        index.search(on_device, 5)
        index.search(queries[0], 5)
    finally:
        jax.profiler.stop_trace()
    spans = read_spans(tmp_path)
    markers = [s for s in spans if s[0] == "pio:index.route"]
    assert [(m[4]["inputs"], m[4]["rows"], m[4]["route"]) for m in markers] \
        == [("host", 4, route), ("device", 2, route), ("host", 1, route)]
    for m in markers:
        assert parent_of(m, spans) == "pio:index.search"
    stats = index.stats()
    assert stats["inputs"] == {"host": 3, "device": 2}
    assert stats["routes"][route] == stats["searches"] == 5


def test_training_boundaries_are_spans(tmp_path):
    import jax

    from predictionio_tpu.obs import jaxmon
    from predictionio_tpu.ops.twotower import TwoTowerConfig, TwoTowerTrainer

    rng = np.random.default_rng(3)
    trainer = TwoTowerTrainer(
        (rng.integers(0, 200, 512), rng.integers(0, 300, 512), None),
        200, 300, TwoTowerConfig(dim=16, batch_size=128, epochs=1 << 20))
    trainer.run(epochs=1)                             # compiles
    start_profiler(tmp_path)
    try:
        trainer.run(epochs=3)
    finally:
        jax.profiler.stop_trace()
    spans = read_spans(tmp_path)
    by_name = {}
    for s in spans:
        by_name.setdefault(s[0], []).append(s)
    assert len(by_name["pio:train.epoch"]) == 2
    assert len(by_name["pio:train.report"]) == 2
    assert [s[4]["epoch"] for s in by_name["pio:train.epoch"]] == [1, 2]
    for s in spans:
        assert parent_of(s, spans) == PARENTS[s[0]]
    # the compiled epoch program's scopes were kept when it compiled
    scopes = set(jaxmon.SCOPE_MAPS["jit_epoch"].values())
    assert {"twotower.step", "twotower.gather", "twotower.adagrad_user",
            "twotower.adagrad_item"} <= scopes
