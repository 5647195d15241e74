"""Continuous profiling plane (obs/contprof.py): the bounded trie,
role/wait classification, refcounted sampler lifecycle across every
server kind and the stream daemon, overhead self-governance (synthetic
slow clock pins the auto-downshift; a real run pins the tier-1 cost
ceiling), the ``/admin/prof`` + fleet + CLI + dashboard surfaces, and
the acceptance e2e — a hedging 3-replica fleet under load whose
``?slow=1`` tail flame names trace ids the flight recorder's slow ring
also holds.
"""

from __future__ import annotations

import json
import threading
import time
from dataclasses import dataclass

import pytest

from predictionio_tpu.obs import collect, contprof, flight, metrics, trace

from tests.test_health import get, get_json, train_const
from tests.test_fleet import post, running_fleet


@pytest.fixture(autouse=True)
def fresh_profiler():
    """Per-test isolation for the process-global profiler: drop leaked
    owners (a crashed test's server never released) and all samples."""
    p = contprof.PROFILER

    def scrub():
        for owner in p.owners():
            p.release(owner)
        p.reset()

    scrub()
    yield
    scrub()


def sampler_threads():
    return [t for t in threading.enumerate()
            if t.name == "pio-contprof" and t.is_alive()]


# ---------------------------------------------------------------------------
# bounded trie
# ---------------------------------------------------------------------------

def test_trie_folds_stacks_with_cpu_wait_split():
    t = contprof._Trie(budget=64)
    t.add(["[handler]", "a.py:f", "b.py:g"], waiting=False)
    t.add(["[handler]", "a.py:f", "b.py:g"], waiting=False)
    t.add(["[handler]", "a.py:f"], waiting=True)
    folded = t.folded()
    assert folded["[handler];a.py:f;b.py:g"] == {"cpu": 2, "wait": 0}
    assert folded["[handler];a.py:f"] == {"cpu": 0, "wait": 1}
    assert t.cpu == 2 and t.wait == 1
    assert t.stats()["evictions"] == 0


def test_trie_bounds_nodes_and_counts_evictions():
    budget = 32
    t = contprof._Trie(budget=budget)
    # synthetic deep stacks: 40 distinct 20-frame chains would need 800
    # nodes — the budget must hold and every sample still land
    for i in range(40):
        t.add([f"s{i}.py:f{d}" for d in range(20)], waiting=False)
    assert t.nodes <= budget + 1  # +1: the reserved overflow terminal
    assert t.evictions > 0
    # no sample is lost: overflow truncates at the deepest existing
    # node, and a stack matching nothing lands on "(evicted)"
    assert t.cpu == 40
    folded = t.folded()
    total = sum(c["cpu"] + c["wait"] for c in folded.values())
    assert total == 40
    assert "(evicted)" in folded


def test_endpoint_tries_fold_overflow_into_other(monkeypatch):
    monkeypatch.setenv("PIO_PROF_MAX_ENDPOINTS", "2")
    p = contprof.ContProfiler()
    with p._lock:
        for i in range(5):
            p._endpoint_trie(f"/route{i}").add(["x.py:f"], waiting=False)
    snap = p.snapshot()
    assert "(other)" in snap["endpoints"]
    assert len(snap["endpoints"]) <= 3  # 2 routes + the fold bucket


# ---------------------------------------------------------------------------
# classification
# ---------------------------------------------------------------------------

def test_role_inference_name_then_frames():
    assert contprof._role_of("pio-batcher-r0", []) == "batcher"
    assert contprof._role_of("pio-watchdog:x", []) == "watchdog"
    assert contprof._role_of("pio-contprof", []) == "sampler"
    assert contprof._role_of("MainThread", []) == "main"
    assert contprof._role_of(
        "Thread-7", [("socketserver.py", "process_request_thread"),
                     ("http.py", "do_POST")]) == "handler"
    assert contprof._role_of(
        "Thread-3", [("engine_server.py", "_loop")]) == "batcher"
    assert contprof._role_of("Thread-9", [("x.py", "run")]) == "other"


def test_wait_classification_leaf_only():
    assert contprof._is_waiting([("a.py", "f"), ("threading.py", "wait")])
    assert contprof._is_waiting([("socket.py", "recv_into")])
    assert contprof._is_waiting([("selectors.py", "select")])
    # a threading.py leaf that is NOT a named wait is real CPU time
    assert not contprof._is_waiting([("threading.py", "is_set")])
    assert not contprof._is_waiting([("als.py", "solve")])
    # only the leaf decides: waiting deeper in the stack is history
    assert not contprof._is_waiting([("threading.py", "wait"),
                                     ("als.py", "solve")])


# ---------------------------------------------------------------------------
# sampler lifecycle: refcounted owners
# ---------------------------------------------------------------------------

def test_retain_release_refcount_controls_the_thread():
    p = contprof.ContProfiler()
    assert not p.running()
    p.retain("a")
    p.retain("b")
    assert p.running() and p.owners() == ["a", "b"]
    p.release("a")
    assert p.running()  # one owner still holds it
    p.release("b")
    assert not p.running() and p.owners() == []
    # restart after full drain works
    p.retain("c")
    assert p.running()
    p.release("c")
    assert not p.running()


def test_double_retain_never_starts_a_second_sampler():
    before = len(sampler_threads())
    p = contprof.ContProfiler()
    p.retain("server")
    first = p._thread
    p.retain("server")  # a /reload re-entering start()
    p.retain("another")
    assert p._thread is first  # same thread, not a second sampler
    assert len(sampler_threads()) == before + 1
    p.release("server")
    p.release("another")
    assert not p.running()


@pytest.mark.parametrize("kind", ["event", "storage", "dashboard",
                                  "engine"])
def test_server_start_stop_drives_profiler_lifecycle(
        kind, memory_storage):
    """Every HTTPServerBase main (event/storage/dashboard/engine — the
    router rides the same base class and is exercised in the e2e below)
    retains the sampler on start and releases it on stop; a double stop
    (drain_stop then stop) releases exactly once."""
    from predictionio_tpu.serving.event_server import EventServer
    from predictionio_tpu.serving.storage_server import StorageServer
    from predictionio_tpu.tools.dashboard import DashboardServer

    if kind == "event":
        server = EventServer(storage=memory_storage, host="127.0.0.1",
                             port=0)
    elif kind == "storage":
        server = StorageServer(storage=memory_storage, host="127.0.0.1",
                               port=0)
    elif kind == "dashboard":
        server = DashboardServer(storage=memory_storage,
                                 host="127.0.0.1", port=0)
    else:
        from predictionio_tpu.serving.engine_server import EngineServer

        engine, _ = train_const(memory_storage)
        server = EngineServer(engine, "const", host="127.0.0.1", port=0,
                              storage=memory_storage)
    assert not contprof.PROFILER.running()
    server.start()
    try:
        assert contprof.PROFILER.running()
        assert len(sampler_threads()) == 1
        assert contprof.PROFILER.owners()  # this server holds it
    finally:
        server.stop()
    assert not contprof.PROFILER.running()
    assert contprof.PROFILER.owners() == []
    server.stop()  # drain_stop -> stop double-release is a no-op
    assert contprof.PROFILER.owners() == []


def test_two_servers_share_one_sampler(memory_storage):
    from predictionio_tpu.serving.event_server import EventServer
    from predictionio_tpu.serving.storage_server import StorageServer

    a = EventServer(storage=memory_storage, host="127.0.0.1",
                    port=0).start()
    b = StorageServer(storage=memory_storage, host="127.0.0.1",
                      port=0).start()
    try:
        assert len(sampler_threads()) == 1  # shared, not duplicated
        a.stop()
        assert contprof.PROFILER.running()  # b still holds it
    finally:
        b.stop()
    assert not contprof.PROFILER.running()


def test_stream_daemon_retains_and_releases_sampler():
    """``pio stream``'s run_forever holds the profiler for the daemon's
    lifetime — a PIO process like any server."""
    from predictionio_tpu.workflow.stream import StreamUpdater

    updater = object.__new__(StreamUpdater)  # the daemon loop only
    updater.poll_once = lambda: None         # touches poll_once
    stop = threading.Event()
    t = threading.Thread(
        target=updater.run_forever,
        kwargs={"interval": 0.01, "stop": stop}, daemon=True)
    t.start()
    try:
        deadline = time.monotonic() + 5.0
        while (not contprof.PROFILER.running()
               and time.monotonic() < deadline):
            time.sleep(0.01)
        assert contprof.PROFILER.running()
        assert any(o.startswith("StreamUpdater:")
                   for o in contprof.PROFILER.owners())
    finally:
        stop.set()
        t.join(timeout=5.0)
    assert not contprof.PROFILER.running()
    assert contprof.PROFILER.owners() == []


# ---------------------------------------------------------------------------
# overhead governance
# ---------------------------------------------------------------------------

class ScriptedClock:
    """perf_counter stand-in: every call advances a fixed step, so one
    _tick() measures a deterministic 'sampling cost'."""

    def __init__(self, step: float):
        self.t = 0.0
        self.step = step

    def __call__(self) -> float:
        self.t += self.step
        return self.t


def test_overhead_downshift_converges_under_budget(monkeypatch):
    """ISSUE acceptance pin: with a synthetic slow clock making every
    sampling pass 'cost' ~4ms against a 40ms interval (10x the 1%
    budget), the governor halves the rate until the EMA fits under
    PIO_PROF_MAX_OVERHEAD — and never below the 1 Hz floor."""
    monkeypatch.setenv("PIO_PROF_HZ", "25")
    monkeypatch.setenv("PIO_PROF_MAX_OVERHEAD", "0.01")
    monkeypatch.setenv("PIO_PROF_WARMUP_TICKS", "0")
    before = metrics.REGISTRY.get("pio_prof_downshifts_total").value
    p = contprof.ContProfiler(clock=ScriptedClock(0.001))
    for _ in range(60):
        p._tick()
    assert p.effective_hz() < 25.0  # it DID downshift
    assert p.effective_hz() >= contprof.MIN_HZ
    assert p.overhead_ratio() <= contprof.max_overhead()
    after = metrics.REGISTRY.get("pio_prof_downshifts_total").value
    assert after > before
    # downshift-only by design: a later cheap pass does not raise it
    cheap = p.effective_hz()
    p._clock = p._cpu_clock = ScriptedClock(1e-9)
    p._tick()
    assert p.effective_hz() == cheap


def test_warmup_ticks_exempt_from_governance(monkeypatch):
    """The governor's grace period: over-budget passes during the first
    PIO_PROF_WARMUP_TICKS never downshift (import-heavy process start
    looks 10-100x steady-state cost), the warm-up EMA is DISCARDED at
    the boundary, and the re-seeded EMA averages EMA_SEED_TICKS passes
    before the first decision — one startup spike never parks the
    rate."""
    monkeypatch.setenv("PIO_PROF_HZ", "25")
    monkeypatch.setenv("PIO_PROF_MAX_OVERHEAD", "0.01")
    monkeypatch.setenv("PIO_PROF_WARMUP_TICKS", "10")
    p = contprof.ContProfiler(clock=ScriptedClock(0.01))  # 100x budget
    for _ in range(10):
        p._tick()
    assert p.effective_hz() == 25.0  # warm-up: no downshift despite cost
    # steady state turns cheap: the startup EMA must not linger and
    # force a downshift the current cost does not justify, even across
    # the whole seed window
    p._clock = p._cpu_clock = ScriptedClock(1e-6)
    for _ in range(contprof.EMA_SEED_TICKS + 2):
        p._tick()
    assert p.effective_hz() == 25.0
    assert p.overhead_ratio() <= contprof.max_overhead()
    # but a genuinely expensive steady state still governs post-warm-up
    p._clock = p._cpu_clock = ScriptedClock(0.01)
    for _ in range(contprof.EMA_SEED_TICKS + 2):
        p._tick()
    assert p.effective_hz() < 25.0


def test_hz_zero_disables_sampling_but_not_surfaces(monkeypatch):
    monkeypatch.setenv("PIO_PROF_HZ", "0")
    p = contprof.ContProfiler()
    assert p._tick() == 0.5  # idle poll, no sample
    snap = p.snapshot()
    assert snap["total_samples"] == 0
    assert snap["hz"] == 0.0


def test_real_sampler_overhead_under_5pct_at_default_rate():
    """Tier-1 cost ceiling: the real sampler at the default 25 Hz on a
    process with live threads must cost well under 5% of wall time.
    The worker mix mirrors a serving process — short compute bursts
    between waits (pure GIL-saturated spinners would starve the
    sampler's own pass and measure GIL queueing, not sampling cost)."""
    p = contprof.ContProfiler()
    stop = threading.Event()

    def work():
        while not stop.is_set():
            sum(i * i for i in range(200))
            stop.wait(0.002)

    workers = [threading.Thread(target=work, daemon=True)
               for _ in range(3)]
    for w in workers:
        w.start()
    p.retain("tier1")
    try:
        time.sleep(1.0)
        assert p.snapshot()["total_samples"] > 0
        assert p.overhead_ratio() < 0.05
    finally:
        stop.set()
        p.release("tier1")
        for w in workers:
            w.join(timeout=2.0)


def test_single_spike_costs_at_most_one_halving(monkeypatch):
    """Cascade guard: ONE expensive pass (a GC pause billed to the
    sampler thread) spikes the EMA for several ticks as it decays — the
    governor must not convert that one event into halving-per-tick down
    to the floor. A downshift discards the EMA and holds the next
    decision for EMA_SEED_TICKS, so the spike costs exactly one step."""
    monkeypatch.setenv("PIO_PROF_HZ", "25")
    monkeypatch.setenv("PIO_PROF_MAX_OVERHEAD", "0.01")
    monkeypatch.setenv("PIO_PROF_WARMUP_TICKS", "0")
    before = metrics.REGISTRY.get("pio_prof_downshifts_total").value
    cheap, spike = ScriptedClock(1e-7), ScriptedClock(0.01)
    p = contprof.ContProfiler(clock=cheap)
    for _ in range(contprof.EMA_SEED_TICKS + 1):
        p._tick()
    assert p.effective_hz() == 25.0
    p._clock = p._cpu_clock = spike
    p._tick()  # the one expensive pass
    p._clock = p._cpu_clock = cheap
    for _ in range(3 * contprof.EMA_SEED_TICKS):
        p._tick()
    assert p.effective_hz() == 12.5  # one halving, not a cascade
    after = metrics.REGISTRY.get("pio_prof_downshifts_total").value
    assert after - before == 1


def test_gil_contention_does_not_downshift(monkeypatch):
    """The governor meters CPU time, not wall time: on a loaded server a
    sampling pass takes large WALL time waiting its turn at the GIL, but
    the sampler's own CPU cost stays tiny — it must keep its full
    sampling rate (wall-based metering downshifted to the floor exactly
    under load). Driven tick by tick on injected clocks: a pass that
    "takes" 30 ms of wall time (75% of the 40 ms interval) and 10 us of
    the sampler thread's CPU time (0.025%, under the 1% budget)."""
    monkeypatch.setenv("PIO_PROF_HZ", "25")
    monkeypatch.setenv("PIO_PROF_MAX_OVERHEAD", "0.01")
    monkeypatch.setenv("PIO_PROF_WARMUP_TICKS", "0")
    # what the sampler thread meters itself with when nothing is
    # injected: its own CPU clock, not the wall clock
    assert contprof.ContProfiler()._cpu_clock is time.thread_time
    before = metrics.REGISTRY.get("pio_prof_downshifts_total").value
    p = contprof.ContProfiler(clock=ScriptedClock(0.030),
                              cpu_clock=ScriptedClock(1e-5))
    for _ in range(10 * contprof.EMA_SEED_TICKS):
        p._tick()
    assert p.snapshot()["total_samples"] > 0
    assert p.overhead_ratio() < 0.01
    assert p.effective_hz() == 25.0
    assert metrics.REGISTRY.get("pio_prof_downshifts_total").value == before
    # the same wall cost billed as CPU time is over the budget: the
    # clocks are what decides, not the pass
    q = contprof.ContProfiler(clock=ScriptedClock(1e-5),
                              cpu_clock=ScriptedClock(0.030))
    for _ in range(10 * contprof.EMA_SEED_TICKS):
        q._tick()
    assert q.effective_hz() < 25.0


# ---------------------------------------------------------------------------
# per-request attribution
# ---------------------------------------------------------------------------

def test_request_attribution_endpoint_slow_and_dominant(monkeypatch):
    monkeypatch.setenv("PIO_SLOW_MS", "0")  # everything is tail
    p = contprof.ContProfiler()
    p.request_begin("trace-1", "/queries.json")
    for _ in range(5):
        p._sample_once()
    dominant = p.request_end()
    assert dominant is not None and ":" in dominant
    # this thread was sampled into the route's trie and the slow cohort
    by_route = p.snapshot(endpoint="/queries.json")
    assert by_route["samples"]["cpu"] + by_route["samples"]["wait"] >= 5
    slow = p.snapshot(slow=True)
    assert slow["slice"] == "slow"
    assert "trace-1" in slow["slow_trace_ids"]
    # after request_end the thread no longer attributes
    p._sample_once()
    assert p.snapshot(slow=True)["slow_trace_ids"] == ["trace-1"]


def test_fast_requests_stay_out_of_slow_cohort(monkeypatch):
    monkeypatch.setenv("PIO_SLOW_MS", "60000")
    p = contprof.ContProfiler()
    p.request_begin("trace-fast", "/x")
    p._sample_once()
    p.request_end()
    snap = p.snapshot(slow=True)
    assert snap["slow_trace_ids"] == []
    assert snap["samples"] == {"cpu": 0, "wait": 0}


# ---------------------------------------------------------------------------
# renderers
# ---------------------------------------------------------------------------

def _payload():
    return {
        "slice": "all", "hz": 25.0, "effective_hz": 25.0,
        "overhead_ratio": 0.004, "max_overhead": 0.01,
        "samples": {"cpu": 6, "wait": 4},
        "folded": {
            "[handler];server.py:read": {"cpu": 1, "wait": 0},
            "[handler];socket.py:recv_into": {"cpu": 0, "wait": 4},
            "[handler];decoder.py:decode": {"cpu": 2, "wait": 0},
            "[batcher];als.py:solve": {"cpu": 3, "wait": 0},
        },
    }


def test_collapsed_text_is_folded_flamegraph_form():
    text = contprof.collapsed_text(_payload())
    assert "[handler];socket.py:recv_into 4\n" in text
    assert "[batcher];als.py:solve 3\n" in text


def test_hot_frames_rank_by_self_time():
    hot = contprof.hot_frames(_payload(), n=2)
    assert hot[0]["frame"] == "socket.py:recv_into"
    assert hot[0]["total"] == 4 and hot[0]["wait"] == 4
    assert len(hot) == 2


def test_format_flame_tree_marks_waits_and_hot_frames():
    text = contprof.format_flame(_payload())
    assert "continuous profile [all]" in text
    assert "6 cpu / 4 wait" in text
    assert "~wait" in text  # the parked leaf is marked
    assert "hot frames" in text
    empty = contprof.format_flame({"folded": {}, "samples": {}})
    assert "(no samples yet)" in empty


def test_merge_folded_sums_members():
    a = {"folded": {"x;y": {"cpu": 1, "wait": 0}},
         "samples": {"cpu": 1, "wait": 0}}
    b = {"folded": {"x;y": {"cpu": 2, "wait": 1},
                    "z": {"cpu": 0, "wait": 1}},
         "samples": {"cpu": 2, "wait": 2}}
    merged = contprof.merge_folded([a, b])
    assert merged["slice"] == "fleet"
    assert merged["folded"]["x;y"] == {"cpu": 3, "wait": 1}
    assert merged["folded"]["z"] == {"cpu": 0, "wait": 1}
    assert merged["samples"] == {"cpu": 3, "wait": 2}


def test_serve_path_breakdown_buckets_handler_self_time():
    shares = contprof.serve_path_breakdown(_payload())
    # batcher stacks are excluded; handler total = 7
    assert shares["socket"] == round(4 / 7, 4)
    assert shares["json"] == round(2 / 7, 4)
    assert shares["parse"] == round(1 / 7, 4)
    assert contprof.serve_path_breakdown({"folded": {}}) == {}


# ---------------------------------------------------------------------------
# federation plane
# ---------------------------------------------------------------------------

def test_federate_prof_merges_and_degrades_on_dead_member():
    contprof.PROFILER._trie.add(["[main]", "a.py:f"], waiting=False)
    report = collect.federate_prof([
        collect.Member("local", None),
        collect.Member("dead", "http://127.0.0.1:1"),
    ])
    by_name = {m["name"]: m for m in report["members"]}
    assert by_name["local"]["ok"] and by_name["local"]["samples"] >= 1
    assert not by_name["dead"]["ok"] and by_name["dead"]["error"]
    assert report["merged_from"] == ["local"]
    assert report["merged"]["folded"]["[main];a.py:f"]["cpu"] == 1


# ---------------------------------------------------------------------------
# HTTP surface + CLI + dashboard on a single server
# ---------------------------------------------------------------------------

def test_admin_prof_endpoint_and_cli(memory_storage, capsys):
    from predictionio_tpu.serving.event_server import EventServer
    from predictionio_tpu.tools import cli

    server = EventServer(storage=memory_storage, host="127.0.0.1",
                         port=0).start()
    try:
        base = f"http://127.0.0.1:{server.port}"
        # let the sampler fold a few passes of the live server
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            status, payload = get_json(base + "/admin/prof")
            assert status == 200
            if payload["total_samples"] > 0:
                break
            time.sleep(0.05)
        assert payload["running"] is True
        assert payload["slice"] == "all"
        assert payload["total_samples"] > 0
        assert payload["folded"]  # stacks landed
        # the sampler names itself in the flame
        assert any(s.startswith("[sampler]") for s in payload["folded"])
        # collapsed form for external tooling
        status, text, headers = get(base + "/admin/prof?format=collapsed")
        assert status == 200
        assert headers["Content-Type"].startswith("text/plain")
        assert ";" in text and text.strip().rsplit(" ", 1)[1].isdigit()
        # slow slice answers (empty cohort on an idle server)
        status, slow = get_json(base + "/admin/prof?slow=1")
        assert status == 200 and slow["slice"] == "slow"
        assert slow["slow_trace_ids"] == []
        # the 501 device-profile answer now points here
        import urllib.error
        import urllib.request

        req = urllib.request.Request(
            base + "/admin/profile?seconds=0.01", data=b"", method="POST")
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(req, timeout=10)
        assert err.value.code == 501
        body = json.loads(err.value.read())
        assert body["host_profiler"] == "/admin/prof"
        assert "GET /admin/prof" in body["hint"]
        # pio prof renders the same payload through the shared renderer
        assert cli.main(["prof", "--url", base]) == 0
        out = capsys.readouterr().out
        assert "continuous profile [all]" in out
        assert "hot frames" in out
        assert cli.main(["prof", "--url", base, "--collapsed"]) == 0
        out = capsys.readouterr().out
        assert "[sampler]" in out
        assert cli.main(["prof", "--url", base, "--json"]) == 0
        assert json.loads(capsys.readouterr().out)["slice"] == "all"
    finally:
        server.stop()


def test_dashboard_prof_view(memory_storage):
    from predictionio_tpu.tools.dashboard import DashboardServer

    server = DashboardServer(storage=memory_storage, host="127.0.0.1",
                             port=0).start()
    try:
        base = f"http://127.0.0.1:{server.port}"
        status, text, _ = get(base + "/prof")
        assert status == 200 and "continuous profile" in text
        status, text, _ = get(base + "/prof?slow=1")
        assert status == 200 and "[slow]" in text
        # the index links the flame view
        status, text, _ = get(base + "/")
        assert status == 200 and "/prof" in text
    finally:
        server.stop()


def test_timeline_carries_prof_overhead_series():
    from predictionio_tpu.obs import timeline

    sample = timeline.contprof_collector()(0.0)
    assert set(sample) == {"prof.overhead"}
    assert isinstance(sample["prof.overhead"], float)
    # the default collector set carries the series
    merged = {}
    for collector in timeline.default_collectors():
        merged.update(collector(0.0))
    assert "prof.overhead" in merged


# ---------------------------------------------------------------------------
# acceptance e2e: hedging fleet under load -> tail flame joins flight
# ---------------------------------------------------------------------------

def _train_slow_engine(storage, sleep_ms=60.0):
    """A const-style engine whose predict sleeps: every query is a tail
    request once PIO_SLOW_MS sits below the sleep."""
    from predictionio_tpu.core import (Algorithm, DataSource, Engine,
                                       FirstServing, IdentityPreparator)
    from predictionio_tpu.core.params import EngineParams, Params
    from predictionio_tpu.workflow.train import run_train

    @dataclass
    class NoParams(Params):
        pass

    class OneDataSource(DataSource):
        def read_training(self, ctx):
            return 1.0

    class SlowAlgo(Algorithm):
        def train(self, ctx, pd):
            return pd

        def predict(self, model, query):
            time.sleep(sleep_ms / 1e3)
            return {"model": model}

    engine = Engine(OneDataSource, IdentityPreparator,
                    {"slowalgo": SlowAlgo}, FirstServing)
    ep = EngineParams(
        data_source_params=("", NoParams()),
        preparator_params=("", None),
        algorithm_params_list=[("slowalgo", NoParams())],
        serving_params=("", None),
    )
    # trained under "const": running_fleet's factory binds that id
    run_train(engine, ep, engine_id="const", storage=storage)
    return engine


def test_acceptance_tail_flame_joins_flight_slow_ring(memory_storage,
                                                      monkeypatch,
                                                      capsys):
    """ISSUE acceptance: under router load with hedging armed,
    ``GET /admin/prof?slow=1`` yields a non-empty tail flame whose
    trace ids appear in the flight recorder's slow ring, ``pio prof
    --fleet`` renders the member-merged view, and the run sees zero
    non-429 client errors."""
    from predictionio_tpu.tools import cli

    # fast sampling with a permissive budget (tiny test intervals would
    # otherwise downshift mid-run), tail threshold under the sleep
    monkeypatch.setenv("PIO_PROF_HZ", "200")
    monkeypatch.setenv("PIO_PROF_MAX_OVERHEAD", "0.5")
    monkeypatch.setenv("PIO_SLOW_MS", "20")
    engine = _train_slow_engine(memory_storage, sleep_ms=60.0)
    with running_fleet(memory_storage, engine) as (fleet, router, base):
        assert contprof.PROFILER.running()  # router+replicas retain it
        trace_ids = []
        for _ in range(30):  # past HedgeClock.min_samples
            status, body, headers = post(base + "/queries.json",
                                         body=b'{"q": 1}')
            assert status == 200, body  # zero non-429 (indeed, none)
            trace_ids.append(headers[trace.TRACE_HEADER])
        assert router.hedge.deadline() is not None  # hedging armed

        # -- the tail flame off the router ------------------------------
        status, slow = get_json(base + "/admin/prof?slow=1")
        assert status == 200
        assert slow["samples"]["cpu"] + slow["samples"]["wait"] > 0
        assert slow["folded"]  # non-empty tail flame
        assert slow["slow_trace_ids"]
        assert set(slow["slow_trace_ids"]) & set(trace_ids)

        # its trace ids join the flight recorder's slow ring
        slow_records = flight.RECORDER.records(slow_only=True)
        ring = {r.get("trace") for r in slow_records}
        joined = set(slow["slow_trace_ids"]) & ring
        assert joined, (slow["slow_trace_ids"], ring)
        # slow flight records name the dominant host frame (satellite:
        # `pio flight --slow` names code, not just stages)
        stamped = [r for r in slow_records
                   if r.get("dominant_frame")]
        assert stamped
        assert all(":" in r["dominant_frame"] for r in stamped)

        # -- member-merged fleet view -----------------------------------
        status, report = get_json(base + "/admin/fleet/prof")
        assert status == 200
        assert {m["name"] for m in report["members"]} == {"r0", "r1",
                                                          "r2"}
        assert all(m["ok"] for m in report["members"])
        assert report["merged"]["folded"]
        assert report["merged_from"] == ["r0", "r1", "r2"]
        status, text, _ = get(
            base + "/admin/fleet/prof?format=collapsed")
        assert status == 200 and ";" in text

        # -- pio prof drives the same surfaces --------------------------
        assert cli.main(["prof", "--fleet", "--url", base]) == 0
        out = capsys.readouterr().out
        assert "member r0" in out and "continuous profile" in out
        assert cli.main(["prof", "--url", base, "--slow"]) == 0
        out = capsys.readouterr().out
        assert "slow-cohort trace ids" in out
    assert not contprof.PROFILER.running()  # fleet teardown released

