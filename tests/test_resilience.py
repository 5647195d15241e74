"""Resilience subsystem: retry/deadline/breaker policies, chaos-driven
fault injection, admission control (429 + Retry-After), degraded-mode
serving, and SLO alert delivery (predictionio_tpu/resilience/*)."""

import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from predictionio_tpu.data.storage import (
    Storage,
    StorageUnavailableError,
)
from predictionio_tpu.obs import health, metrics, slo
from predictionio_tpu.resilience import admission, alerts, chaos, policy
from predictionio_tpu.resilience.policy import (
    CircuitBreaker,
    CircuitOpenError,
    Policy,
    RetryBudgetExceeded,
)
from predictionio_tpu.serving import engine_server as engine_server_mod
from predictionio_tpu.serving.engine_server import EngineServer, MicroBatcher
from predictionio_tpu.serving.event_server import EventServer
from predictionio_tpu.serving.http import HTTPServerBase, JSONRequestHandler

from tests.test_health import _wait_for, get, get_json, train_const


def post(url, body=b"{}", headers=None, timeout=15):
    req = urllib.request.Request(
        url, data=body, method="POST",
        headers={"Content-Type": "application/json", **(headers or {})})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, resp.read().decode(), dict(resp.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode(), dict(e.headers)


# -- Policy: retry budget + full-jitter backoff --------------------------------

def test_backoff_full_jitter_bounds():
    """Jittered-backoff bounds: every delay for retry k lies in
    [0, min(cap, base * 2^k)], and the draws actually spread (full
    jitter, not a constant)."""
    p = Policy(backoff_base=0.2, backoff_cap=1.0)
    for attempt, ceiling in enumerate([0.2, 0.4, 0.8, 1.0, 1.0]):
        draws = [p.backoff_seconds(attempt) for _ in range(200)]
        assert all(0.0 <= d <= ceiling for d in draws), (attempt, ceiling)
        assert max(draws) > ceiling * 0.5  # the upper half is reachable
        assert min(draws) < ceiling * 0.5  # ...and so is the lower


def test_retry_budget_exhaustion():
    calls = {"n": 0}

    def always_down():
        calls["n"] += 1
        raise ConnectionRefusedError("nope")

    p = Policy(retries=3)
    with pytest.raises(ConnectionRefusedError):
        p.run(always_down, sleep=lambda s: None)
    assert calls["n"] == 4  # 1 attempt + 3 retries

    calls["n"] = 0
    with pytest.raises(RetryBudgetExceeded) as ei:
        p.run(always_down, sleep=lambda s: None, raise_exhausted=True)
    assert ei.value.attempts == 4
    assert isinstance(ei.value.last, ConnectionRefusedError)

    # non-idempotent: the budget is never spent
    calls["n"] = 0
    with pytest.raises(ConnectionRefusedError):
        p.run(always_down, idempotent=False, sleep=lambda s: None)
    assert calls["n"] == 1


def test_application_errors_are_not_retried():
    calls = {"n": 0}

    def bad_request():
        calls["n"] += 1
        raise ValueError("your fault, not the network's")

    with pytest.raises(ValueError):
        Policy(retries=5).run(bad_request, sleep=lambda s: None)
    assert calls["n"] == 1


def test_retry_success_after_transient_failures():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise ConnectionResetError("blip")
        return "ok"

    assert Policy(retries=3).run(flaky, sleep=lambda s: None) == "ok"
    assert calls["n"] == 3


# -- circuit breaker lifecycle -------------------------------------------------

def test_breaker_open_half_open_close_lifecycle():
    br = CircuitBreaker("t-lifecycle", failure_threshold=2,
                        reset_timeout=0.08)
    assert br.state == policy.CLOSED and br.allow()
    br.record_failure()
    assert br.state == policy.CLOSED  # one failure is not an outage
    br.record_failure()
    assert br.state == policy.OPEN
    assert not br.allow()             # fail fast, no connect attempt
    assert br.retry_after() > 0

    time.sleep(0.1)
    assert br.allow()                 # the half-open probe
    assert br.state == policy.HALF_OPEN
    assert not br.allow()             # only one probe at a time
    br.record_failure()               # probe failed: re-open, re-arm
    assert br.state == policy.OPEN and not br.allow()

    time.sleep(0.1)
    assert br.allow()
    br.record_success()               # probe succeeded: recovery
    assert br.state == policy.CLOSED and br.allow()


def test_policy_fails_fast_while_circuit_open():
    br = CircuitBreaker("t-fast", failure_threshold=1, reset_timeout=60.0)
    br.record_failure()
    calls = {"n": 0}

    def fn():
        calls["n"] += 1

    with pytest.raises(CircuitOpenError) as ei:
        Policy().run(fn, breaker=br, sleep=lambda s: None)
    assert calls["n"] == 0            # the transport was never touched
    assert ei.value.retry_after > 0


def test_admitted_call_keeps_its_retry_budget():
    """A call admitted while closed retries through the circuit opening
    mid-call — that is what lets retries ride out the blip that opened
    it (new calls fail fast meanwhile)."""
    br = CircuitBreaker("t-midcall", failure_threshold=2, reset_timeout=60.0)
    calls = {"n": 0}

    def recovers_on_third():
        calls["n"] += 1
        if calls["n"] < 3:
            raise ConnectionResetError("down")
        return "back"

    assert Policy(retries=3).run(recovers_on_third, breaker=br,
                                 sleep=lambda s: None) == "back"
    assert br.state == policy.CLOSED  # success closed it again


def test_breaker_state_gauge_and_health_probe():
    br = policy.breaker_for("t-gauge", failure_threshold=1,
                            reset_timeout=60.0)
    gauge = metrics.REGISTRY.get("pio_circuit_state")
    assert gauge.labels("t-gauge").value == 0.0
    br.record_failure()
    assert gauge.labels("t-gauge").value == 2.0
    # the circuit_breakers health probe reports open circuits DEGRADED
    assert "circuit_breakers" in health.REGISTRY.names()
    _, detail = health.REGISTRY.run()
    assert detail["circuit_breakers"]["status"] == "degraded"
    assert "t-gauge" in detail["circuit_breakers"]["reason"]
    br.record_success()
    assert gauge.labels("t-gauge").value == 0.0
    _, detail = health.REGISTRY.run()
    assert detail["circuit_breakers"]["status"] == "ok"


def test_rest_transport_circuit_opens_and_fails_fast():
    """Enough consecutive connection failures against a dead storage
    endpoint open its circuit; the NEXT call answers instantly with a
    circuit-open StorageUnavailableError (no connect, no timeout)."""
    from tests.test_rest_storage import _client_storage

    client = _client_storage(1)  # nothing listens on port 1
    # each idempotent read burns 1+3 attempts; two reads cross the
    # default threshold of 5 consecutive failures
    for _ in range(2):
        with pytest.raises(StorageUnavailableError):
            client.apps().get_all()
    base_url = "http://127.0.0.1:1"
    assert policy.breaker_for(base_url).state == policy.OPEN
    t0 = time.perf_counter()
    with pytest.raises(StorageUnavailableError) as ei:
        client.apps().get_all()
    assert "circuit open" in str(ei.value)
    assert time.perf_counter() - t0 < 0.1  # failed fast, not via timeouts


# -- chaos harness -------------------------------------------------------------

def test_chaos_spec_parsing():
    rules = chaos.parse_spec(
        "storage:latency:50ms,storage:error:0.25,batcher:hang:2s,"
        "train:error")
    assert [(r.site, r.kind, r.amount) for r in rules] == [
        ("storage", "latency", 0.05),
        ("storage", "error", 0.25),
        ("batcher", "hang", 2.0),
        ("train", "error", 1.0),
    ]
    for bad in ("storage", "storage:latency", "storage:explode:1",
                "storage:error:1.5", "storage:latency:soon"):
        with pytest.raises(ValueError):
            chaos.parse_spec(bad)


def test_chaos_injection_latency_and_error():
    chaos.configure("seam:latency:30ms")
    t0 = time.perf_counter()
    chaos.inject("seam")
    assert time.perf_counter() - t0 >= 0.03
    chaos.inject("other-seam")  # no rules for it: no-op

    chaos.configure("seam:error:1")
    with pytest.raises(chaos.ChaosError) as ei:
        chaos.inject("seam")
    # the injected failure classifies as a CONNECTION failure — the
    # breaker/retry machinery cannot tell it from a real outage
    assert isinstance(ei.value, ConnectionError)
    counted = metrics.REGISTRY.get("pio_chaos_injections_total")
    assert counted.labels("seam", "error").value >= 1

    chaos.clear()
    chaos.inject("seam")  # cleared: no-op


def test_chaos_env_and_admin_mutation(monkeypatch):
    monkeypatch.setenv("PIO_CHAOS", "storage:latency:1ms")
    assert [r.site for r in chaos.configure_from_env()] == ["storage"]
    state = chaos.apply_admin({"add": "batcher:error:0.5"})
    assert len(state["rules"]) == 2 and state["enabled"]
    state = chaos.apply_admin({"clear": "storage"})
    assert [r["site"] for r in state["rules"]] == ["batcher"]
    state = chaos.apply_admin({"clear": True})
    assert state == chaos.describe() and not state["enabled"]
    with pytest.raises(ValueError):
        chaos.apply_admin({})
    with pytest.raises(ValueError):
        chaos.apply_admin({"spec": "nope"})


def test_server_start_does_not_revert_admin_chaos(monkeypatch):
    """Explicit configuration outranks the env for the process's life:
    a second in-process server start (configure_from_env again) must
    not re-enable injection an operator turned off."""
    monkeypatch.setenv("PIO_CHAOS", "storage:error:0.1")
    assert [r.site for r in chaos.configure_from_env()] == ["storage"]
    chaos.clear()  # the operator's decision
    assert chaos.configure_from_env() == []  # later boot: stays off
    chaos.configure("batcher:latency:1ms")
    assert [r.site for r in chaos.configure_from_env()] == ["batcher"]


def test_admin_chaos_endpoint_and_cli(memory_storage, capsys):
    server = EventServer(storage=memory_storage, host="127.0.0.1",
                         port=0).start()
    base = f"http://127.0.0.1:{server.port}"
    try:
        status, body = get_json(f"{base}/admin/chaos")
        assert status == 200 and body["enabled"] is False
        status, _, _ = post(f"{base}/admin/chaos",
                            json.dumps({"spec": "storage:latency:1ms"})
                            .encode())
        assert status == 200
        assert [r.spec() for r in chaos.active()] == ["storage:latency:0.001s"]
        status, _, _ = post(f"{base}/admin/chaos", b'{"spec": "bad"}')
        assert status == 400

        from predictionio_tpu.tools.cli import main

        assert main(["chaos", "--url", base]) == 0
        assert "storage" in capsys.readouterr().out
        assert main(["chaos", "--url", base, "--clear"]) == 0
        assert chaos.active() == []
    finally:
        server.stop()


def test_admin_chaos_requires_bearer_when_token_set(memory_storage,
                                                   monkeypatch):
    server = EventServer(storage=memory_storage, host="127.0.0.1",
                         port=0).start()
    base = f"http://127.0.0.1:{server.port}"
    try:
        monkeypatch.setenv("PIO_ADMIN_TOKEN", "s3cret")
        assert get(f"{base}/admin/chaos")[0] == 401
        assert get(f"{base}/admin/resilience")[0] == 401
        auth = {"Authorization": "Bearer s3cret"}
        assert get(f"{base}/admin/chaos", headers=auth)[0] == 200
        status, body = get_json(f"{base}/admin/resilience")
        assert status == 401
        status, text, _ = get(f"{base}/admin/resilience", headers=auth)
        assert status == 200 and "circuits" in json.loads(text)
    finally:
        server.stop()


# -- admission controller (unit) -----------------------------------------------

def test_admission_controller_signals():
    signals = {"depth": 0, "inflight": 0.0, "burn": 0.0}
    ctl = admission.AdmissionController(
        "t", queue_depth=lambda: signals["depth"],
        inflight=lambda: signals["inflight"],
        burn=lambda: signals["burn"],
        max_queue_depth=4, max_inflight=8, max_burn=14.4)
    assert ctl.check() is None

    signals["depth"] = 4
    decision = ctl.check()
    assert decision.reason == "queue_depth" and decision.retry_after >= 1
    signals["depth"] = 40
    assert ctl.check().retry_after > 1  # deeper backlog, longer advice

    signals["depth"] = 0
    # the gauge counts the current request itself: AT the limit is
    # admitted (otherwise inflight=1 would shed everything), one past
    # it is shed
    signals["inflight"] = 8
    assert ctl.check() is None
    signals["inflight"] = 9
    assert ctl.check().reason == "inflight"

    signals["inflight"] = 0.0
    signals["burn"] = 20.0
    decision = ctl.check()
    assert decision.reason == "burn_rate" and decision.retry_after >= 10

    # declarative overrides; 0 disables a signal
    ctl.configure({"burn": 0, "queue_depth": 2})
    assert ctl.check() is None
    signals["depth"] = 2
    assert ctl.check().reason == "queue_depth"
    shed = metrics.REGISTRY.get("pio_shed_total")
    assert shed.labels("t", "queue_depth").value >= 2
    snap = ctl.snapshot()
    assert snap["limits"]["queue_depth"] == 2 and snap["shedTotal"] >= 4


# -- engine server integration: shedding under synthetic overload -------------

def test_engine_server_sheds_with_429_under_overload(memory_storage):
    """Chaos-injected dispatch latency + a tight queue limit: the
    flood gets a mix of 200s and 429s (with Retry-After), and the p99
    of ACCEPTED requests stays bounded — overload degrades into
    explicit shed, not queueing collapse."""
    engine, _ = train_const(memory_storage)
    server = EngineServer(engine, "const", host="127.0.0.1", port=0,
                          storage=memory_storage, max_batch=1).start()
    base = f"http://127.0.0.1:{server.port}"
    try:
        server.admission.configure(
            {"queue_depth": 2, "inflight": 0, "burn": 0})
        chaos.configure("batcher:latency:0.15")
        results = []
        lock = threading.Lock()

        def one_query():
            t0 = time.perf_counter()
            status, _, headers = post(f"{base}/queries.json",
                                      b'{"mult": 2}')
            with lock:
                results.append(
                    (status, time.perf_counter() - t0, headers))

        # wave 1 saturates the (slowed) dispatcher and builds a queue;
        # wave 2 arrives into the backlog and meets the shedder
        wave1 = [threading.Thread(target=one_query) for _ in range(4)]
        for t in wave1:
            t.start()
        time.sleep(0.1)  # inside wave 1's ~0.6s drain window
        wave2 = [threading.Thread(target=one_query) for _ in range(12)]
        for t in wave2:
            t.start()
        for t in wave1 + wave2:
            t.join()
        statuses = [r[0] for r in results]
        assert statuses.count(200) >= 1, statuses
        assert statuses.count(429) >= 1, statuses
        for status, _, headers in results:
            if status == 429:
                assert int(headers["Retry-After"]) >= 1
        accepted = sorted(r[1] for r in results if r[0] == 200)
        # queue cap 2 + one in dispatch at 0.15s each: the accepted
        # tail is a few dispatches deep, never the whole flood's wait
        assert accepted[-1] < 3.0, accepted
        shed = metrics.REGISTRY.get("pio_shed_total")
        assert shed.labels("engine", "queue_depth").value >= 1
        # the shed is reconstructable from the status page
        _, body = get_json(base + "/")
        assert body["admission"]["shedTotal"] >= 1
    finally:
        chaos.clear()
        server.stop()


# -- engine server integration: degraded-mode serving --------------------------

def test_degraded_serving_with_killed_sqlite_backend(tmp_path):
    """Acceptance: storage dies under a live engine server -> the
    storage circuit opens, /readyz reports DEGRADED (200, not 503/
    FAILED), queries keep answering from the last-loaded model with an
    X-PIO-Degraded stamp, and their latency stays bounded while the
    breaker is open."""
    storage = Storage.from_env({
        "PIO_STORAGE_SOURCES_SQL_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_SQL_PATH": str(tmp_path / "pio.db"),
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "SQL",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "SQL",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "SQL",
    })
    engine, _ = train_const(storage)
    server = EngineServer(engine, "const", host="127.0.0.1", port=0,
                          storage=storage).start()
    base = f"http://127.0.0.1:{server.port}"
    try:
        # healthy baseline: ready, no degraded stamp
        status, body = get_json(f"{base}/readyz")
        assert status == 200 and body["probes"]["storage"]["status"] == "ok"
        status, _, headers = post(f"{base}/queries.json", b'{"mult": 3}')
        assert status == 200 and "X-PIO-Degraded" not in headers

        # kill the backend: every storage touch now raises
        storage.client_for("METADATA").close()

        # consecutive readiness probes trip the storage circuit
        # (failure_threshold=2); readyz stays 200 throughout — storage
        # loss with a loaded model is DEGRADED, never FAILED
        for _ in range(3):
            status, body = get_json(f"{base}/readyz")
            assert status == 200, body
            assert body["status"] in ("ok", "degraded")
            assert body["probes"]["storage"]["status"] in (
                "ok", "degraded")
        assert body["status"] == "degraded"
        assert "degraded" in body["probes"]["storage"]["reason"].lower() \
            or "circuit" in body["probes"]["storage"]["reason"]
        assert server._storage_breaker.state == policy.OPEN
        gauge = metrics.REGISTRY.get("pio_circuit_state")
        assert gauge.labels("storage:const").value == 2.0

        # the last-loaded model still answers, stamped + bounded
        latencies = []
        for _ in range(8):
            t0 = time.perf_counter()
            status, text, headers = post(f"{base}/queries.json",
                                         b'{"mult": 3}')
            latencies.append(time.perf_counter() - t0)
            assert status == 200
            assert json.loads(text) == {"result": 9.0}
            assert "last-loaded instance" in headers["X-PIO-Degraded"]
        assert sorted(latencies)[-1] < 2.0, latencies
        # /reload cannot work without storage — and says so (an HTTP
        # error answer, never a crashed connection)
        status, _ = get_json(f"{base}/reload")
        assert status in (404, 503)
        # the status page names the condition
        _, body = get_json(base + "/")
        assert body["degraded"] and body["storageCircuit"]["state"] == "open"
    finally:
        server.stop()


def test_degraded_mode_recovers_when_storage_returns(memory_storage,
                                                     monkeypatch):
    """Recovery closes the loop: chaos-injected storage errors open the
    circuit; clearing them lets the half-open probe succeed, serving
    leaves degraded mode with no restart."""
    monkeypatch.setenv("PIO_BREAKER_RESET_SEC", "0.1")
    engine, _ = train_const(memory_storage)
    server = EngineServer(engine, "const", host="127.0.0.1", port=0,
                          storage=memory_storage).start()
    base = f"http://127.0.0.1:{server.port}"
    try:
        chaos.configure("storage:error:1")
        for _ in range(3):
            status, body = get_json(f"{base}/readyz")
            assert status == 200
        assert body["status"] == "degraded"
        assert server.degraded_reason() is not None
        _, _, headers = post(f"{base}/queries.json", b'{"mult": 1}')
        assert "X-PIO-Degraded" in headers

        chaos.clear()
        time.sleep(0.15)  # past the reset window: next probe is let through
        status, body = get_json(f"{base}/readyz")
        assert status == 200 and body["probes"]["storage"]["status"] == "ok"
        assert server.degraded_reason() is None
        _, _, headers = post(f"{base}/queries.json", b'{"mult": 1}')
        assert "X-PIO-Degraded" not in headers
    finally:
        chaos.clear()
        server.stop()


# -- chaos hang vs the dispatch watchdog ---------------------------------------

def test_watchdog_still_fires_on_chaos_hang(monkeypatch):
    """A true hang (chaos ``batcher:hang``) is the watchdog's job, not
    admission control's: the stall fires while the dispatch is still
    hung."""
    tight = health.Watchdog("dispatch-chaos-test", min_seconds=0.01,
                            min_history=1, factor=2.0)
    monkeypatch.setattr(engine_server_mod, "_DISPATCH_WATCHDOG", tight)

    def stall_count():
        return metrics.REGISTRY.get(
            "pio_watchdog_stall_total").labels("dispatch-chaos-test").value

    batcher = MicroBatcher(lambda ps: ps, lambda p: p)
    try:
        batcher.submit("warm")  # builds the trailing-median history
        before = stall_count()
        chaos.configure("batcher:hang:0.3")
        done = threading.Event()

        def submit_hung():
            try:
                batcher.submit("hung", timeout=5)
            finally:
                done.set()

        threading.Thread(target=submit_hung, daemon=True).start()
        assert _wait_for(lambda: stall_count() == before + 1)
        chaos.clear()
        assert done.wait(5)  # the hang ends; the waiter is answered
    finally:
        chaos.clear()
        batcher.stop()


# -- SLO alert webhook delivery ------------------------------------------------

class _WebhookSink:
    """Local HTTP sink; optionally 503s the first N deliveries."""

    def __init__(self, fail_first=0):
        self.payloads = []
        self.hits = 0
        sink = self

        class Handler(JSONRequestHandler):
            server_version = "WebhookSink/0.1"

            def do_POST(self):
                body = self._read_body()
                sink.hits += 1
                if sink.hits <= fail_first:
                    self._send(503, {"message": "not yet"})
                else:
                    sink.payloads.append(json.loads(body))
                    self._send(200, {"message": "ok"})

        self.server = HTTPServerBase("127.0.0.1", 0, Handler).start()

    @property
    def url(self):
        return f"http://127.0.0.1:{self.server.port}/hook"

    def stop(self):
        self.server.stop()


def _availability_monitor():
    mon = slo.SLOMonitor([slo.SLO(name="t-hook", kind="availability",
                                  metric="nonexistent", objective=0.99)])
    t0 = 5_000_000.0
    # long healthy history so both fast windows can burn hot later
    for i in range(75):
        mon.record("t-hook", t0 + i * 60, 600.0 * i, 600.0 * i)
    return mon, t0 + 74 * 60, 600.0 * 74


def test_webhook_fires_on_alert_transitions():
    sink = _WebhookSink()
    hook = alerts.AlertWebhook(sink.url, policy=Policy(
        deadline=5.0, retries=2, backoff_base=0.01, backoff_cap=0.05))
    slo.add_alert_listener(hook.on_transition)
    mon, t_last, n = _availability_monitor()

    def mine():
        # the listener is global: the process-wide MONITOR may fire its
        # own transitions during the test — count only this SLO's pages
        return [p for p in sink.payloads if p["slo"] == "t-hook"]

    try:
        # a total outage: every request in the last hour+ is an error
        mon.record("t-hook", t_last + 60, n, n + 5000)
        mon.evaluate(now=t_last + 60)
        assert _wait_for(lambda: len(mine()) >= 1)
        assert mine()[0]["state"] == "firing"
        assert mine()[0]["slo_report"]["state"] == "firing"
        # steady evaluation while still firing: no duplicate page
        mon.record("t-hook", t_last + 120, n, n + 5000)
        mon.evaluate(now=t_last + 120)
        # recovery: lots of healthy traffic dilutes every window
        good = n + 900_000
        mon.record("t-hook", t_last + 22000, good, good + 5000)
        mon.evaluate(now=t_last + 22000)
        assert _wait_for(lambda: len(mine()) >= 2)
        assert mine()[-1]["state"] == "resolved"
        assert len(mine()) == 2  # one per TRANSITION, not per tick
        # the sink has the page before the sender counts its answer
        family = metrics.REGISTRY.get("pio_alert_webhook_total")
        assert _wait_for(lambda: family.labels("ok").value >= 2)
    finally:
        slo.remove_alert_listener(hook.on_transition)
        hook.stop()
        sink.stop()


def test_webhook_retries_flaky_sink_through_policy():
    sink = _WebhookSink(fail_first=2)
    hook = alerts.AlertWebhook(sink.url, policy=Policy(
        deadline=5.0, retries=4, backoff_base=0.01, backoff_cap=0.05))
    try:
        assert hook.deliver({"type": "slo_alert", "slo": "t",
                             "state": "firing"}) is True
        assert sink.hits == 3  # two 503s retried through, then delivered
    finally:
        hook.stop()
        sink.stop()


def test_webhook_starts_from_env(monkeypatch):
    sink = _WebhookSink()
    monkeypatch.setenv("PIO_ALERT_WEBHOOK_URL", sink.url)
    try:
        hook = alerts.start_from_env()
        assert hook is not None
        assert alerts.start_from_env() is hook  # idempotent
        assert hook.on_transition in slo._alert_listeners
    finally:
        alerts.stop()
        sink.stop()
    assert hook.on_transition not in slo._alert_listeners


def test_find_does_not_backoff_against_an_open_circuit():
    """find()'s whole-scan retry loop gives up immediately on a
    circuit-open failure — backoff-sleeping against a breaker that is
    guaranteed to fail fast would defeat its purpose."""
    from tests.test_rest_storage import _client_storage

    client = _client_storage(1)
    for _ in range(2):  # open the endpoint's circuit
        with pytest.raises(StorageUnavailableError):
            client.apps().get_all()
    assert policy.breaker_for("http://127.0.0.1:1").state == policy.OPEN
    t0 = time.perf_counter()
    with pytest.raises(StorageUnavailableError) as ei:
        client.events().find(app_id=1)
    assert "circuit open" in str(ei.value)
    assert time.perf_counter() - t0 < 0.1  # no backoff sleeps happened


def test_snapshot_cadence_evaluates_slos():
    """The flight-recorder cadence hook must EVALUATE, not just sample:
    evaluation is what refreshes the burn gauges (the shed signal) and
    fires alert transitions (the webhook) on an unattended server."""
    import predictionio_tpu.obs.flight as flight_mod

    for _name, fn in flight_mod._snapshot_listeners:
        fn()
    family = metrics.REGISTRY.get("pio_slo_burn_rate")
    labels = {values for values, _ in family.children()}
    assert ("serving-latency", "5m") in labels


# -- declarative SLO + shedding config -----------------------------------------

def test_declarative_slo_configuration():
    try:
        slo.configure({"latency_ms": 50, "latency_objective": 0.999,
                       "availability_objective": 0.995})
        by_name = {s.name: s for s in slo.MONITOR.slos()}
        assert by_name["serving-latency"].threshold_ms == 50
        assert by_name["serving-latency"].objective == 0.999
        assert by_name["http-availability"].objective == 0.995
    finally:
        slo.configure({})  # back to env defaults
    by_name = {s.name: s for s in slo.MONITOR.slos()}
    assert by_name["serving-latency"].threshold_ms == 100.0


def test_slo_file_loading(tmp_path, monkeypatch):
    conf = tmp_path / "slo.json"
    conf.write_text(json.dumps({"latency_ms": 42,
                                "shed": {"queue_depth": 9}}))
    monkeypatch.setenv("PIO_SLO_FILE", str(conf))
    monkeypatch.setattr(slo, "_file_config_path", None)
    monkeypatch.setattr(slo, "_file_config", None)
    try:
        loaded = slo.configure_from_env()
        assert loaded["shed"] == {"queue_depth": 9}
        by_name = {s.name: s for s in slo.MONITOR.slos()}
        assert by_name["serving-latency"].threshold_ms == 42
    finally:
        slo.configure({})


def test_engine_variant_slo_block_reaches_admission(memory_storage):
    from predictionio_tpu.workflow.variant import EngineVariant

    variant = EngineVariant.from_dict({
        "engineFactory": "x.Y",
        "slo": {"latency_ms": 75,
                "shed": {"queue_depth": 7, "inflight": 11}},
    })
    assert variant.slo_conf()["latency_ms"] == 75
    with pytest.raises(ValueError):
        EngineVariant.from_dict(
            {"engineFactory": "x.Y", "slo": ["nope"]}).slo_conf()

    engine, _ = train_const(memory_storage)
    server = EngineServer(engine, "const", host="127.0.0.1", port=0,
                          storage=memory_storage,
                          slo_conf=variant.slo_conf())
    try:
        assert server.admission.max_queue_depth == 7
        assert server.admission.max_inflight == 11
        by_name = {s.name: s for s in slo.MONITOR.slos()}
        assert by_name["serving-latency"].threshold_ms == 75
    finally:
        slo.configure({})
        server.stop()


def test_variant_slo_block_layers_over_slo_file(memory_storage, tmp_path,
                                                monkeypatch):
    """A variant block overrides only the keys it names: the file's
    other objectives survive instead of snapping back to env
    defaults."""
    conf = tmp_path / "slo.json"
    conf.write_text(json.dumps({"latency_ms": 42}))
    monkeypatch.setenv("PIO_SLO_FILE", str(conf))
    monkeypatch.setattr(slo, "_file_config_path", None)
    monkeypatch.setattr(slo, "_file_config", None)
    engine, _ = train_const(memory_storage)
    server = EngineServer(engine, "const", host="127.0.0.1", port=0,
                          storage=memory_storage,
                          slo_conf={"availability_objective": 0.95})
    try:
        by_name = {s.name: s for s in slo.MONITOR.slos()}
        assert by_name["serving-latency"].threshold_ms == 42
        assert by_name["http-availability"].objective == 0.95
    finally:
        slo.configure({})
        server.stop()
