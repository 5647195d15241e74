"""Serving fleet: replica supervisor, health-routed query router and
the rolling zero-downtime hot-swap (serving/fleet.py,
serving/router.py), plus the shared SIGTERM drain handler
(serving/http.py).

Chaos comes through the PR-6 seams: ``ThreadedReplica.kill()`` dies
like a crashed process (listening socket closed abruptly), and the
``batcher@<replica>:hang`` tagged chaos rule hangs exactly one
replica's dispatch loop while its peers keep answering.
"""

from __future__ import annotations

import contextlib
import json
import signal
import threading
import time
import urllib.error
import urllib.request

import pytest

from predictionio_tpu.obs import metrics
from predictionio_tpu.resilience import chaos
from predictionio_tpu.resilience.admission import ShedDecision
from predictionio_tpu.serving import fleet as fleet_mod
from predictionio_tpu.serving.engine_server import EngineServer
from predictionio_tpu.serving.fleet import (DEAD, READY, FleetSupervisor,
                                            threaded_fleet)
from predictionio_tpu.serving.http import install_drain_handler
from predictionio_tpu.serving.router import QueryRouter

from tests.test_health import get, get_json, train_const


def post(url, body=b'{"mult": 2}', headers=None, timeout=15):
    req = urllib.request.Request(
        url, data=body, method="POST",
        headers={"Content-Type": "application/json", **(headers or {})})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, resp.read().decode(), dict(resp.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode(), dict(e.headers)


@contextlib.contextmanager
def running_fleet(storage, engine, n=3, probe_interval=0.05,
                  backoff=None, engine_name="const", **engine_kw):
    """N threaded const-engine replicas behind a router on an
    ephemeral port; yields (fleet, router, base_url). ``engine_name``
    labels the serving metrics — tests that assert on cumulative
    histograms pass a private name so earlier tests' observations
    (chaos hangs especially) don't sit in their tail."""
    def factory(name):
        return EngineServer(engine, engine_name, host="127.0.0.1",
                            port=0, storage=storage, max_batch=8,
                            chaos_tag=name, **engine_kw)

    fleet = FleetSupervisor(threaded_fleet(n, factory),
                            probe_interval=probe_interval,
                            backoff=backoff).start()
    router = None
    try:
        assert fleet.wait_ready(timeout=60), fleet.snapshot()
        router = QueryRouter(fleet, host="127.0.0.1", port=0).start()
        yield fleet, router, f"http://127.0.0.1:{router.port}"
    finally:
        chaos.clear()
        if router is not None:
            router.stop()
        fleet.stop()


def counter_value(name, *labels):
    family = metrics.REGISTRY.get(name)
    if family is None:
        return 0.0
    return family.labels(*labels).value if labels else family.value


# -- routing basics ------------------------------------------------------------

def test_fleet_starts_routes_and_balances(memory_storage, monkeypatch):
    """3 replicas come up READY, the router answers queries with the
    serving replica stamped, and placement spreads across replicas.
    Hedging is off: the per-replica counts must sum exactly to the
    queries sent, and a scheduling hiccup past the hedge floor would
    legitimately add a duplicate."""
    monkeypatch.setenv("PIO_HEDGE_QUANTILE", "0")
    engine, _ = train_const(memory_storage)
    with running_fleet(memory_storage, engine) as (fleet, router, base):
        served = set()
        for _ in range(24):
            status, body, headers = post(base + "/queries.json")
            assert status == 200, body
            assert json.loads(body) == {"result": 6.0}
            served.add(headers["X-PIO-Replica"])
        assert len(served) >= 2, served  # p2c spreads the load
        # per-replica request counts agree traffic reached >1 replica
        counts = {r.name: r.server.stats.request_count
                  for r in fleet.replicas}
        assert sum(counts.values()) == 24, counts
        # the operator surface sees the same fleet
        status, snap = get_json(base + "/admin/fleet")
        assert status == 200
        assert snap["ready"] == 3 and snap["size"] == 3
        assert {r["state"] for r in snap["replicas"]} == {READY}
        # router readiness mirrors the rotation
        status, ready = get_json(base + "/readyz")
        assert status == 200
        assert ready["probes"]["storage"]["status"] == "ok"


def test_router_503_when_nothing_in_rotation(memory_storage):
    """Admin drain empties the rotation: the router answers 503 +
    Retry-After (and readyz FAILED) instead of hanging; readmit
    restores service."""
    engine, _ = train_const(memory_storage)
    with running_fleet(memory_storage, engine, n=1) as (fleet, router,
                                                        base):
        status, body, _ = post(
            base + "/admin/fleet", body=json.dumps({"drain": "r0"}).encode())
        assert status == 200, body
        status, body, headers = post(base + "/queries.json")
        assert status == 503, body
        assert headers["Retry-After"] == "1"
        status, _ = get_json(base + "/readyz")
        assert status == 503  # a router with no rotation is NOT ready
        status, body, _ = post(
            base + "/admin/fleet",
            body=json.dumps({"readmit": "r0"}).encode())
        assert status == 200, body
        assert fleet.wait_ready(timeout=10)
        status, _, _ = post(base + "/queries.json")
        assert status == 200


# -- satellite: shed/degraded passthrough --------------------------------------

def test_router_passes_through_shed_and_degraded(memory_storage,
                                                 monkeypatch):
    """A replica's 429 Retry-After travels to the client UN-retried
    (retrying shed traffic amplifies the overload), and the degraded
    stamp survives the router hop — both counted in
    pio_router_passthrough_total{reason}."""
    engine, _ = train_const(memory_storage)
    with running_fleet(memory_storage, engine) as (fleet, router, base):
        calls = {"n": 0}

        def always_shed():
            calls["n"] += 1
            return ShedDecision("queue_depth", 7, "test shed")

        for r in fleet.replicas:
            monkeypatch.setattr(r.server.admission, "check", always_shed)
        shed_before = counter_value("pio_router_passthrough_total", "shed")
        status, body, headers = post(base + "/queries.json")
        assert status == 429, body
        assert headers["Retry-After"] == "7"
        assert json.loads(body)["reason"] == "queue_depth"
        # exactly ONE replica was consulted: the shed was not retried
        assert calls["n"] == 1
        assert counter_value("pio_router_passthrough_total",
                             "shed") == shed_before + 1

        for r in fleet.replicas:
            monkeypatch.undo()
        # degraded mode: open every replica's storage circuit; the
        # query still answers, stamped, through the router
        for r in fleet.replicas:
            r.server._storage_breaker.record_failure()
            r.server._storage_breaker.record_failure()
        deg_before = counter_value("pio_router_passthrough_total",
                                   "degraded")
        status, body, headers = post(base + "/queries.json")
        assert status == 200, body
        assert "last-loaded instance" in headers["X-PIO-Degraded"]
        assert counter_value("pio_router_passthrough_total",
                             "degraded") == deg_before + 1


# -- satellite: hedging pins the tail ------------------------------------------

def test_hedge_rescues_hung_replica(memory_storage, monkeypatch):
    """A chaos-hung replica no longer sets the measured p99: once the
    reply exceeds the trailing-quantile hedge deadline, a second
    request races on the healthy replica and answers in milliseconds
    instead of the hang's seconds."""
    monkeypatch.setenv("PIO_HEDGE_MIN_MS", "40")
    engine, _ = train_const(memory_storage)
    with running_fleet(memory_storage, engine, n=2) as (fleet, router,
                                                        base):
        # warm the trailing window past HedgeClock.min_samples
        for _ in range(25):
            status, _, _ = post(base + "/queries.json")
            assert status == 200
        assert router.hedge.deadline() is not None
        hedges_before = counter_value("pio_router_hedges_total")
        chaos.configure("batcher@r1:hang:2s")
        latencies = []
        for _ in range(12):
            t0 = time.perf_counter()
            status, body, _ = post(base + "/queries.json")
            latencies.append(time.perf_counter() - t0)
            assert status == 200, body
        chaos.clear()
        # the hang is 2s; every answer must have beaten it by far
        assert sorted(latencies)[-1] < 1.5, latencies
        assert counter_value("pio_router_hedges_total") > hedges_before


def test_hedged_shed_answer_defers_to_primary_success(memory_storage,
                                                      monkeypatch):
    """A hedge that lands on a shedding replica answers 429 in
    sub-milliseconds — long before the slow primary it was meant to
    rescue. That racer answer must NOT win the race: the router holds
    it and returns the primary's eventual 200 (hedging exists to cut
    the tail, not to convert would-be successes into client-visible
    errors)."""
    monkeypatch.setenv("PIO_HEDGE_MIN_MS", "40")
    engine, _ = train_const(memory_storage)
    with running_fleet(memory_storage, engine, n=2) as (fleet, router,
                                                        base):
        for _ in range(25):  # warm the trailing window
            status, _, _ = post(base + "/queries.json")
            assert status == 200
        assert router.hedge.deadline() is not None
        shedder = next(r for r in fleet.replicas if r.name == "r1")
        monkeypatch.setattr(
            shedder.server.admission, "check",
            lambda: ShedDecision("queue_depth", 1, "test shed"))
        chaos.configure("batcher@r0:hang:2s")
        hedged = False
        # p2c places ~half the queries on the hung r0; the first 2s
        # success then trains the hedge clock past the hang, so only
        # the earliest r0 placements hedge — stop at the first one
        for _ in range(12):
            before = counter_value("pio_router_hedges_total")
            status, body, headers = post(base + "/queries.json")
            assert status in (200, 429), body
            if counter_value("pio_router_hedges_total") > before:
                # the hedge raced r1's instant 429 and lost on purpose:
                # the hung primary's 200 is the client's answer
                assert status == 200, body
                assert headers["X-PIO-Replica"] == "r0"
                hedged = True
                break
        chaos.clear()
        assert hedged, "no query ever hedged"


# -- acceptance: chaos kill + hang + rolling swap ------------------------------

def test_fleet_chaos_acceptance(memory_storage, monkeypatch):
    """The tier-1 acceptance story: 3 replicas under chaos — one
    killed, one hung — serve a continuous query load with ZERO
    non-429 errors; the supervisor restarts the dead replica under
    backoff; a rolling hot-swap onto a freshly trained instance
    completes while queries keep answering and the fleet never drops
    below 2 ready replicas."""
    monkeypatch.setenv("PIO_HEDGE_MIN_MS", "50")
    monkeypatch.setenv("PIO_DRAIN_TIMEOUT", "5")
    engine, _ = train_const(memory_storage)
    with running_fleet(memory_storage, engine) as (fleet, router, base):
        for _ in range(30):  # arm the hedge clock
            status, _, _ = post(base + "/queries.json")
            assert status == 200

        results = []
        failures = []
        stop_evt = threading.Event()

        def loader():
            while not stop_evt.is_set():
                try:
                    status, body, _ = post(base + "/queries.json")
                    results.append(status)
                    if status not in (200, 429):
                        failures.append((status, body[:200]))
                except Exception as e:  # noqa: BLE001 — a transport
                    # error IS the outage the fleet must prevent
                    failures.append(("transport", repr(e)))

        threads = [threading.Thread(target=loader) for _ in range(4)]
        for t in threads:
            t.start()
        try:
            # chaos: hang r1's dispatch loop, crash r0 outright
            chaos.configure("batcher@r1:hang:2s")
            victim = fleet.replicas[0]
            victim.kill()
            time.sleep(1.0)
            # the supervisor restarts the dead replica
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline and victim.state != READY:
                time.sleep(0.05)
            assert victim.state == READY, fleet.snapshot()
            assert victim.restarts >= 1
            assert counter_value("pio_fleet_restarts_total", "r0") >= 1
            chaos.clear()

            # rolling hot-swap to a NEW trained instance, sampling the
            # ready floor throughout
            _, new_instance = train_const(memory_storage)
            min_ready = [fleet.size()]
            swap_done = threading.Event()

            def sampler():
                while not swap_done.is_set():
                    min_ready.append(fleet.ready_count())
                    time.sleep(0.01)

            sample_thread = threading.Thread(target=sampler)
            sample_thread.start()
            try:
                result = fleet.rolling_reload()
            finally:
                swap_done.set()
                sample_thread.join(timeout=5)
            assert result["outcome"] == "ok", result
            assert sorted(result["swapped"]) == ["r0", "r1", "r2"]
            assert min(min_ready) >= 2, min(min_ready)
            assert fleet.version() == new_instance.id
            for r in fleet.replicas:
                assert r.version == new_instance.id
        finally:
            stop_evt.set()
            for t in threads:
                t.join(timeout=30)
        assert not failures, failures[:5]
        assert results.count(200) > 50, len(results)
        # queries answered THROUGH the swap window, not just before it
        status, _, _ = post(base + "/queries.json")
        assert status == 200


@pytest.mark.slow
def test_fleet_kill_swap_soak(memory_storage, monkeypatch):
    """Soak: 3 replica kills and 2 rolling swaps under continuous
    load, zero non-429 errors end to end."""
    monkeypatch.setenv("PIO_HEDGE_MIN_MS", "50")
    monkeypatch.setenv("PIO_DRAIN_TIMEOUT", "5")
    engine, _ = train_const(memory_storage)
    with running_fleet(memory_storage, engine) as (fleet, router, base):
        failures = []
        answered = []
        stop_evt = threading.Event()

        def loader():
            while not stop_evt.is_set():
                try:
                    status, body, _ = post(base + "/queries.json")
                    answered.append(status)
                    if status not in (200, 429):
                        failures.append((status, body[:200]))
                except Exception as e:  # noqa: BLE001
                    failures.append(("transport", repr(e)))

        threads = [threading.Thread(target=loader) for _ in range(4)]
        for t in threads:
            t.start()
        try:
            for round_no in range(3):
                victim = fleet.replicas[round_no % fleet.size()]
                restarts_before = victim.restarts
                victim.kill()
                # right after kill() the state is STILL READY (the
                # supervisor needs consecutive probe failures to
                # notice): wait for the restart, THEN for readiness
                deadline = time.monotonic() + 60
                while (time.monotonic() < deadline
                       and not (victim.restarts > restarts_before
                                and victim.state == READY)):
                    time.sleep(0.05)
                assert victim.restarts > restarts_before, fleet.snapshot()
                assert victim.state == READY, fleet.snapshot()
                if round_no < 2:
                    train_const(memory_storage)
                    result = fleet.rolling_reload()
                    assert result["outcome"] == "ok", result
        finally:
            stop_evt.set()
            for t in threads:
                t.join(timeout=30)
        assert not failures, failures[:5]
        assert answered.count(200) > 100


# -- supervisor: restart backoff -----------------------------------------------

def test_supervisor_restart_backoff_schedule(memory_storage):
    """Crash-looping replicas back off: the supervisor consults the
    backoff schedule with an INCREASING attempt number (reset only
    after a stable period), and each restart lands in
    pio_fleet_restarts_total."""
    engine, _ = train_const(memory_storage)
    attempts = []

    def recording_backoff(attempt):
        attempts.append(attempt)
        return 0.05

    with running_fleet(memory_storage, engine, n=2,
                       backoff=recording_backoff) as (fleet, _, base):
        victim = fleet.replicas[0]
        # the counter is process-global and replica names recur across
        # fleets (tests included): assert the delta, not the absolute
        restarts_before = counter_value("pio_fleet_restarts_total", "r0")
        for expected_restarts in (1, 2):
            victim.kill()
            deadline = time.monotonic() + 30
            while (time.monotonic() < deadline
                   and victim.restarts < expected_restarts):
                time.sleep(0.02)
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline and victim.state != READY:
                time.sleep(0.02)
            assert victim.state == READY, fleet.snapshot()
        assert victim.restarts == 2
        # second crash inside the stable window -> attempt number grew
        assert attempts[:2] == [0, 1], attempts
        assert counter_value("pio_fleet_restarts_total",
                             "r0") == restarts_before + 2.0


def test_drained_replica_crash_is_detected(memory_storage):
    """A drain parks a replica out of rotation, but the supervisor
    still notices when its process dies while parked: the replica goes
    DEAD and restarts instead of reading "draining" (with a
    live-looking port) forever."""
    engine, _ = train_const(memory_storage)
    with running_fleet(memory_storage, engine, n=2,
                       backoff=lambda attempt: 0.05) as (fleet, _, base):
        status, body, _ = post(
            base + "/admin/fleet", body=json.dumps({"drain": "r0"}).encode())
        assert status == 200, body
        victim = fleet.replicas[0]
        # die like a crashed process: the listening socket closes but
        # the server object stays in place (process_alive must see
        # through it — a bare object-presence check reads "draining"
        # forever here)
        victim.kill()
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and victim.restarts < 1:
            time.sleep(0.02)
        assert victim.restarts >= 1, fleet.snapshot()
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and victim.state != READY:
            time.sleep(0.02)
        assert victim.state == READY, fleet.snapshot()


def test_probe_verdict_cannot_overwrite_a_concurrent_drain(memory_storage):
    """The residual probe-vs-drain race, BOTH probe outcomes: a state
    write landing after probe_and_update's re-check must lose to a
    concurrent DRAINING. A green probe readmitting straight to READY
    was already guarded; a failed probe flipping the drained replica
    to EVICTED is the same bug one hop removed — the next green probe
    readmits from EVICTED. Deliberate transitions (the swap's and the
    admin readmit) still pass."""
    engine, _ = train_const(memory_storage)
    with running_fleet(memory_storage, engine, n=2,
                       probe_interval=1.0) as (fleet, _, _base):
        replica = fleet.replicas[0]
        fleet._set_state(replica, fleet_mod.DRAINING, deliberate=True)
        # probe-driven writes (the racy post-re-check ones) lose
        fleet._set_state(replica, fleet_mod.EVICTED)
        assert replica.state == fleet_mod.DRAINING, fleet.snapshot()
        fleet._set_state(replica, READY)
        assert replica.state == fleet_mod.DRAINING, fleet.snapshot()
        # the operator's / the swap's readmit is deliberate and wins
        fleet._set_state(replica, fleet_mod.EVICTED, deliberate=True)
        assert replica.state == fleet_mod.EVICTED, fleet.snapshot()


def test_stop_fences_straggling_swap_writes(memory_storage):
    """A rolling-swap thread can outlive stop() (it checks the stop
    event only between replicas, and one replica's reload can block
    for minutes): its late writes must not flip a STOPPED replica back
    or re-mint the per-replica gauge children stop() retired — a later
    fleet in the same process would inherit phantom replica series."""
    engine, _ = train_const(memory_storage)

    def factory(name):
        return EngineServer(engine, "const", host="127.0.0.1", port=0,
                            storage=memory_storage, max_batch=8,
                            chaos_tag=name)

    fleet = FleetSupervisor(threaded_fleet(2, factory),
                            probe_interval=0.05).start()
    try:
        assert fleet.wait_ready(timeout=60), fleet.snapshot()
    finally:
        fleet.stop()
    r0 = fleet.replicas[0]
    assert r0.state == fleet_mod.STOPPED
    # exactly what a straggling swap thread would do next:
    fleet._set_state(r0, fleet_mod.DRAINING, deliberate=True)
    fleet._set_state(r0, fleet_mod.EVICTED, deliberate=True)
    fleet._refresh_version(r0)
    assert r0.state == fleet_mod.STOPPED, fleet.snapshot()
    up = metrics.REGISTRY.get("pio_fleet_replica_up")
    names = {vals[0] for vals, _ in (up.children() if up else [])}
    assert r0.name not in names, names
    # and no NEW swap can start against a stopped fleet
    assert not fleet.start_rolling_reload()


# -- admin surface -------------------------------------------------------------

def test_admin_fleet_auth_and_reload_control(memory_storage, monkeypatch):
    """/admin/fleet honors the PIO_ADMIN_TOKEN bearer gate like every
    admin route; POST {"reload": true} answers 202 and runs a swap."""
    engine, _ = train_const(memory_storage)
    with running_fleet(memory_storage, engine, n=2) as (fleet, router,
                                                        base):
        monkeypatch.setenv("PIO_ADMIN_TOKEN", "s3cret")
        status, _, _ = get(base + "/admin/fleet")
        assert status == 401
        # GET /reload triggers the same fleet-wide swap as the gated
        # admin route — it must sit behind the same bearer token
        status, _, _ = get(base + "/reload")
        assert status == 401
        # the public status page must not leak the byte-identical
        # fleet snapshot (ports, instance ids, probe verdicts) that
        # the token just gated one route over — aggregates only
        status, body, _ = get(base + "/")
        assert status == 200
        fleet_view = json.loads(body)["fleet"]
        assert fleet_view == {"size": 2, "ready": 2}
        auth = {"Authorization": "Bearer s3cret"}
        status, body, _ = get(base + "/admin/fleet", headers=auth)
        assert status == 200 and json.loads(body)["size"] == 2
        monkeypatch.delenv("PIO_ADMIN_TOKEN")

        train_const(memory_storage)
        status, body, _ = post(base + "/admin/fleet",
                               body=json.dumps({"reload": True}).encode())
        assert status == 202, body
        # while that swap runs, a second reload request answers 409 on
        # this route exactly like the router's GET /reload does
        status, body, _ = post(base + "/admin/fleet",
                               body=json.dumps({"reload": True}).encode())
        assert status == 409, body
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            _, snap = get_json(base + "/admin/fleet")
            if (not snap["swap"]["active"]
                    and snap["swap"]["last"] is not None):
                break
            time.sleep(0.05)
        assert snap["swap"]["last"]["outcome"] == "ok", snap
        # a no-fleet server 404s the route (negative case)
        status, _, _ = get(
            f"http://127.0.0.1:{fleet.replicas[0].port}/admin/fleet")
        assert status == 404


def test_admin_fleet_rejects_multiple_actions(memory_storage):
    """apply_admin runs exactly one action; a body carrying two (e.g.
    `pio fleet --drain r0 --readmit r1`) must answer 400 rather than
    run the first by precedence and silently drop the second."""
    engine, _ = train_const(memory_storage)
    with running_fleet(memory_storage, engine, n=2) as (fleet, _, base):
        status, body, _ = post(
            base + "/admin/fleet",
            body=json.dumps({"drain": "r0", "readmit": "r1"}).encode())
        assert status == 400, body
        assert "one action per call" in body
        # and neither action ran
        assert fleet.replicas[0].state == READY, fleet.snapshot()


# -- satellite: graceful SIGTERM drain -----------------------------------------

def test_drain_handler_finishes_inflight_requests(memory_storage):
    """The shared SIGTERM handler stops accepting, lets the in-flight
    query finish (it used to be dropped mid-response), then frees the
    port."""
    engine, _ = train_const(memory_storage)
    server = EngineServer(engine, "const", host="127.0.0.1", port=0,
                          storage=memory_storage).start()
    base = f"http://127.0.0.1:{server.port}"
    handler = install_drain_handler(server)
    try:
        chaos.configure("batcher:latency:0.4")
        outcome = {}

        def slow_query():
            outcome["result"] = post(base + "/queries.json",
                                     b'{"mult": 3}')

        t = threading.Thread(target=slow_query)
        t.start()
        time.sleep(0.15)  # the query is inside the slowed dispatch
        handler()         # what SIGTERM would run
        t.join(timeout=10)
        status, body, _ = outcome["result"]
        assert status == 200 and json.loads(body) == {"result": 9.0}
        # drained and stopped: the port no longer accepts
        deadline = time.monotonic() + 5
        refused = False
        while time.monotonic() < deadline and not refused:
            try:
                post(base + "/queries.json", timeout=2)
            except (urllib.error.URLError, ConnectionError, OSError):
                refused = True
        assert refused
    finally:
        chaos.clear()
        signal.signal(signal.SIGTERM, signal.SIG_DFL)
        server.stop()


# -- dashboard -------------------------------------------------------------

def test_dashboard_fleet_panel(memory_storage):
    from predictionio_tpu.tools.dashboard import DashboardServer

    dash = DashboardServer(storage=memory_storage, host="127.0.0.1",
                           port=0).start()
    base = f"http://127.0.0.1:{dash.port}"
    try:
        status, body, _ = get(base + "/fleet")
        assert status == 200 and "No fleet supervised" in body
        engine, _ = train_const(memory_storage)
        with running_fleet(memory_storage, engine, n=2) as (fleet, _, _b):
            status, body, _ = get(base + "/fleet")
            assert status == 200
            assert "r0" in body and "r1" in body and "2/2 ready" in body
        status, body, _ = get(base + "/")
        assert 'href="/fleet"' in body
    finally:
        dash.stop()


# -- tagged chaos --------------------------------------------------------------

def test_chaos_tag_scopes_rule_to_one_replica():
    """`batcher@r1` rules fire only for the tagged instance; untagged
    rules fire for everyone."""
    chaos.configure("batcher@r1:error:1")
    with pytest.raises(chaos.ChaosError):
        chaos.inject("batcher", tag="r1")
    chaos.inject("batcher", tag="r0")   # other tag: silent
    chaos.inject("batcher")             # untagged seam: silent
    chaos.configure("batcher:error:1")
    with pytest.raises(chaos.ChaosError):
        chaos.inject("batcher", tag="r0")  # untagged rule hits all tags
    chaos.clear()


# -- review regressions --------------------------------------------------------

def test_subprocess_argv_forces_single_server_children():
    """PIO_REPLICAS in the environment must not recurse into subprocess
    replicas (each child re-entering the fleet path is a fork bomb):
    the child argv pins --replicas 1 and the child env overrides the
    inherited variable."""
    from predictionio_tpu.serving.fleet import (SubprocessReplica,
                                                deploy_fleet_argv)

    argv = deploy_fleet_argv("engine.json")
    joined = " ".join(argv)
    assert "--replicas 1" in joined
    replica = SubprocessReplica("r0", argv)
    assert replica._env.get("PIO_REPLICAS", "1") == "1"


def test_probe_never_readmits_drained_replica(memory_storage):
    """A green /readyz must not overrule a deliberate drain: the
    monitor's probes and the swap's convergence waits leave DRAINING
    replicas out of rotation until an explicit readmit."""
    engine, _ = train_const(memory_storage)
    with running_fleet(memory_storage, engine, n=2) as (fleet, _, base):
        replica = fleet.replicas[0]
        status, body, _ = post(
            base + "/admin/fleet", body=json.dumps({"drain": "r0"}).encode())
        assert status == 200, body
        # direct probe + a few monitor cadences: still draining
        fleet.probe_and_update(replica)
        time.sleep(0.3)
        assert replica.state == "draining"
        # a rolling swap skips (not readmits) the operator-held replica
        # — and with r0 held, r1 is the ONLY replica in rotation, so
        # the swap refuses to drain it too (reloading it would take
        # ready to zero for the whole warm window)
        train_const(memory_storage)
        result = fleet.rolling_reload()
        assert replica.state == "draining"
        assert "operator-drained" in ";".join(result["errors"])
        assert "refusing to drain the fleet to zero" in ";".join(
            result["errors"])
        assert result["swapped"] == []


def test_fleet_stop_removes_timeline_collector(memory_storage):
    """A stopped fleet must deregister its timeline collector, or the
    timeline pins the supervisor (replicas, models and all) forever
    while its dead 0-ready samples clobber a successor fleet's."""
    from predictionio_tpu.obs import timeline as timeline_mod

    engine, _ = train_const(memory_storage)
    before = len(timeline_mod.TIMELINE._collectors)
    with running_fleet(memory_storage, engine, n=1):
        assert len(timeline_mod.TIMELINE._collectors) == before + 1
    assert len(timeline_mod.TIMELINE._collectors) == before


def test_chaos_clear_site_drops_tagged_rules():
    """clear("batcher") clears the whole seam including batcher@r1 —
    an operator clearing a seam means the seam, not one spelling."""
    chaos.configure("batcher:latency:10ms,batcher@r1:hang:5s,"
                    "storage:error:0.5")
    chaos.clear("batcher")
    assert [r.site for r in chaos.active()] == ["storage"]
    # exact site@tag clears one instance only
    chaos.configure("batcher@r1:hang:5s,batcher@r2:hang:5s")
    chaos.clear("batcher@r1")
    assert [r.site for r in chaos.active()] == ["batcher@r2"]
    chaos.clear()


def test_stale_pooled_connection_retries_fresh_without_breaker_charge():
    """A pooled keep-alive that died while idle is retried once on a
    fresh connection inside the client — the caller (and therefore the
    replica's breaker) never sees the stale-socket failure."""
    import http.client
    import socket

    from predictionio_tpu.serving.router import _ReplicaClient

    # a tiny HTTP listener that answers every connection's first request
    listener = socket.create_server(("127.0.0.1", 0))
    port = listener.getsockname()[1]
    stop = threading.Event()

    def serve():
        while not stop.is_set():
            try:
                listener.settimeout(0.2)
                conn, _ = listener.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            try:
                conn.recv(65536)
                conn.sendall(b"HTTP/1.1 200 OK\r\n"
                             b"Content-Type: application/json\r\n"
                             b"Content-Length: 2\r\n\r\n{}")
            finally:
                conn.close()  # server-side close: pooled conn goes stale

    server_thread = threading.Thread(target=serve, daemon=True)
    server_thread.start()
    try:
        client = _ReplicaClient("127.0.0.1", port)
        status, data, _ = client.request("POST", "/queries.json", b"{}",
                                         {"Content-Type":
                                          "application/json"}, 5.0)
        assert status == 200
        # plant a STALE pooled connection: connected, then killed
        stale = http.client.HTTPConnection("127.0.0.1", port, timeout=5)
        stale.connect()
        stale.sock.close()
        client._idle.append(stale)
        # the request must silently fail over to a fresh connection
        status, data, _ = client.request("POST", "/queries.json", b"{}",
                                         {"Content-Type":
                                          "application/json"}, 5.0)
        assert status == 200 and data == b"{}"
        client.close()
    finally:
        stop.set()
        listener.close()
        server_thread.join(timeout=5)


# -- acceptance: chaos -> anomaly attributed -> recovery -> durable journal ----

def test_fleet_chaos_anomaly_journal_e2e(memory_storage, monkeypatch,
                                         tmp_path, capsys):
    """The ops-journal + sentinel acceptance loop end to end: injected
    batcher latency on ONE replica of a 2-replica fleet raises the
    fleet-wide serve p99, the sentinel detects the shift and attributes
    it to the chaos journal event, ``pio anomalies`` gates 1 while
    active and 0 after the ring turns over post-recovery, and the
    journal file outlives the fleet (read back torn-tail-safely, the
    restart-durability contract)."""
    from predictionio_tpu.obs import anomaly, journal
    from predictionio_tpu.obs import timeline as timeline_mod
    from predictionio_tpu.tools.cli import main as cli_main

    monkeypatch.setenv("PIO_HEDGE_QUANTILE", "0")  # no hedge rescue:
    # the injected latency must land in the histogram tail
    sink = tmp_path / "journal.jsonl"
    monkeypatch.setenv("PIO_JOURNAL_PATH", str(sink))
    # a fresh timeline focused on the serving p99 (the rate/staleness
    # collectors would add unrelated series whose test-paced samples
    # could alarm on their own); capacity 40 so the calm baseline
    # survives the chaos phase and the post-recovery ring still turns
    # over inside the test
    tl = timeline_mod.Timeline(
        interval=0.0, capacity=40,
        collectors=[timeline_mod.quantile_collector(
            "pio_serving_request_seconds", 0.99, "serve_p99_ms",
            scale=1e3)])
    monkeypatch.setattr(timeline_mod, "TIMELINE", tl)
    # a private engine name: the shared cumulative histogram for
    # "const" carries earlier tests' chaos hangs in its tail, which
    # would bury this test's 250 ms injections
    series = "serve_p99_ms.journal_e2e"

    from predictionio_tpu.core import (Engine, FirstServing,
                                       IdentityPreparator)
    from predictionio_tpu.core.params import EngineParams
    from predictionio_tpu.workflow.train import run_train

    from tests.test_health import (ConstAlgo, ConstDataSource,
                                   ConstParams)

    engine = Engine(ConstDataSource, IdentityPreparator,
                    {"const": ConstAlgo}, FirstServing)
    run_train(engine, EngineParams(
        data_source_params=("", ConstParams(value=1.0)),
        preparator_params=("", None),
        algorithm_params_list=[("const", ConstParams(value=2.0))],
        serving_params=("", None)),
        engine_id="journal_e2e", storage=memory_storage)
    with running_fleet(memory_storage, engine, n=2,
                       engine_name="journal_e2e") as (fleet, router,
                                                      base):
        # a baseline the test controls: the series is the p99 of a
        # CUMULATIVE histogram, so with only the 16 real queries below
        # it is their maximum — and one start-up straggler (first
        # dispatch, thread spin-up, a loaded CI host) landing past the
        # sentinel's 12-point baseline window reads as a step up before
        # any chaos exists. 250 observations of 20 ms pin the calm p99
        # inside their bucket (a straggler or two is < 1% of the mass),
        # while three injected 250 ms queries below are > 1% and still
        # move it.
        from predictionio_tpu.obs import metrics as metrics_mod

        calm = metrics_mod.REGISTRY.get(
            "pio_serving_request_seconds").labels("journal_e2e")
        for _ in range(250):
            calm.observe(0.020)
        for _ in range(16):
            status, body, _ = post(base + "/queries.json")
            assert status == 200, body
            tl.sample(now=time.time())
        report = anomaly.SENTINEL.scan(now=time.time())
        assert series not in report["active"], report  # calm baseline

        chaos.configure("batcher@r1:latency:250ms")  # journals "chaos"
        # the router picks between the two replicas at random: send
        # until three queries have met the fault on r1
        slow = 0
        for _ in range(24):
            t0 = time.perf_counter()
            status, body, _ = post(base + "/queries.json", timeout=30)
            assert status == 200, body
            slow += time.perf_counter() - t0 >= 0.25
            tl.sample(now=time.time())
            if slow >= 3:
                break
        assert slow >= 3
        # the sentinel wants the shift sustained, not one point: the
        # cumulative p99 stays where the third slow query put it
        for _ in range(5):
            tl.sample(now=time.time())
        report = anomaly.SENTINEL.scan(now=time.time())
        assert series in report["active"], report
        verdict = report["active"][series]
        assert verdict["direction"] == "up"
        assert verdict["cause"]["kind"] == "chaos", verdict
        assert counter_value("pio_anomaly_active", series) == 1.0
        assert cli_main(["anomalies"]) == 1
        out = capsys.readouterr().out
        assert series in out and "chaos" in out

        chaos.clear()
        for _ in range(48):
            status, body, _ = post(base + "/queries.json")
            assert status == 200, body
            tl.sample(now=time.time())
        report = anomaly.SENTINEL.scan(now=time.time())
        assert series not in report["active"], report
        assert counter_value("pio_anomaly_active", series) == 0.0
        assert cli_main(["anomalies"]) == 0
        assert "no active anomalies" in capsys.readouterr().out

    assert journal.JOURNAL.flush(timeout=10.0)
    events, corrupt = journal.read_back(str(sink))
    assert corrupt == 0
    kinds = [e["kind"] for e in events]
    for expected in ("replica_state", "chaos", "anomaly",
                     "anomaly_resolved"):
        assert expected in kinds, kinds
    onset = next(e for e in events if e["kind"] == "anomaly")
    assert onset["series"] == series
    assert onset["cause_kind"] == "chaos"
    # a restarted process (fresh Journal over the same path) appends to
    # the same history
    fresh = journal.Journal()
    fresh.emit("reload", instance="post-restart")
    assert fresh.flush(timeout=10.0)
    events2, _ = journal.read_back(str(sink))
    assert len(events2) == len(events) + 1
    assert events2[-1]["instance"] == "post-restart"
    fresh.reset()
