"""The Engine Server: deployed-engine query serving, default port 8000.

Behavior contract from the reference (core/.../workflow/CreateServer.scala):

  - boots from the latest COMPLETED EngineInstance for an engine
    (Console.deploy picks it, Console.scala:845-852), reloading models
    from the Models repo (createServerActorWithEngine:190)
  - ``POST /queries.json`` (:462): JSON query -> every algorithm's
    predict on its model -> Serving combines -> JSON response; per
    request stats (requestCount / avg serving time :552-559); optional
    feedback loop POSTs a ``predict`` event (+prId) back to the event
    server (:488-550)
  - ``GET /`` status page with engine info, params and request stats
    (:433-459)
  - ``GET /reload`` hot-swaps to the latest completed instance (:592)
  - ``POST /stop`` shuts the server down (:600)
  - bind retry x3 with 1s backoff (MasterActor, :340-350)

The reference's Akka Master/Server actor pair collapses into one
threaded HTTP server with a swappable Deployment reference. Concurrent
queries are micro-batched (MicroBatcher): handler threads queue
payloads, a worker drains the queue into ONE vectorized
``Deployment.query_batch`` dispatch — batches form exactly when the
device is the bottleneck, and a lone request pays no extra latency
(SURVEY.md §7.5).
"""

from __future__ import annotations

import datetime as _dt
import json
import logging
import os
import threading
import time
import urllib.request
import uuid
from typing import Any, List, Optional
from urllib.parse import urlparse

from predictionio_tpu.core.engine import Engine
from predictionio_tpu.data.storage import Storage, get_storage
from predictionio_tpu.obs import (dataobs, flight, health, jaxmon, journal,
                                  metrics, slo as slo_mod, trace)
from predictionio_tpu.parallel.mesh import MeshContext
from predictionio_tpu.resilience import chaos
from predictionio_tpu.resilience.admission import AdmissionController
from predictionio_tpu.resilience.policy import CLOSED as _BREAKER_CLOSED
from predictionio_tpu.resilience.policy import breaker_for
from predictionio_tpu.serving.http import HTTPServerBase, JSONRequestHandler
from predictionio_tpu.workflow.deploy import Deployment, prepare_deploy

log = logging.getLogger(__name__)

DEFAULT_PORT = 8000  # ref: CreateServer.scala:83
UTC = _dt.timezone.utc

#: the one serving-latency series (obs tentpole): the status page's
#: count/avg/p50/p99 and the /metrics histogram read the SAME child, so
#: a dashboard and the operator landing page can never disagree
_SERVING_SECONDS = metrics.histogram(
    "pio_serving_request_seconds",
    "End-to-end serve time per query (queue wait + dispatch), recorded "
    "inside the engine server",
    ("engine",),
)

#: stall detection over micro-batch dispatches: armed once enough
#: dispatches have built a trailing median, fires when one exceeds
#: PIO_STALL_FACTOR x that median (floor 1s x factor)
_DISPATCH_WATCHDOG = health.Watchdog("serving_dispatch")

#: streaming model patches (workflow/stream.py fold-in lane): applied /
#: stale-instance-rejected / unsupported-or-malformed
_MODEL_PATCHES = metrics.counter(
    "pio_model_patches_total",
    "Streaming model patches received by outcome (applied / stale / "
    "rejected)",
    ("result",),
)


def _http_inflight() -> float:
    """Requests currently inside this engine server (the shared HTTP
    layer's in-flight gauge) — the admission controller's concurrency
    signal. The label is derived from the handler's server_version the
    same way serving/http.py derives it, so a rename cannot silently
    point this at an untouched gauge child reading 0.0 forever."""
    family = metrics.REGISTRY.get("pio_http_requests_in_flight")
    if family is None:
        return 0.0
    label = _EngineRequestHandler.server_version.split("/", 1)[0]
    return family.labels(label).value


class ServingStats:
    """Request bookkeeping (ref: CreateServer.scala:552-559).

    Every record lands in the shared, engine-wide
    ``pio_serving_request_seconds{engine=...}`` histogram — the
    percentiles on the status page and ``GET /metrics`` report from
    that one source of truth. Counts/totals are additionally tracked
    per ServingStats (per server — fleet replicas need per-replica
    numbers)."""

    def __init__(self, engine_id: str = "default"):
        self._lock = threading.Lock()
        # the registry child is process-global per engine: every live
        # server for this engine (N threaded fleet replicas included)
        # records into the SAME series, so /metrics, the serving-latency
        # SLO and burn-driven shedding see ALL traffic — a regression
        # confined to one replica must still move the shared histogram.
        # Per-SERVER bookkeeping (status page counts) lives
        # locally: a new server starts its own counts from zero while
        # the registry series stays cumulative, Prometheus-style.
        self._hist = _SERVING_SECONDS.labels(engine_id)
        self._count = 0
        self._sum = 0.0
        self.last_serving_sec = 0.0
        self.start_time = _dt.datetime.now(tz=UTC)

    @property
    def request_count(self) -> int:
        return self._count

    @property
    def total_serving_sec(self) -> float:
        return self._sum

    def record(self, seconds: float) -> None:
        # the serving request's trace id rides along as an OpenMetrics
        # exemplar on whichever latency bucket this query landed in
        trace_id = trace.current_trace_id()
        self._hist.observe(
            seconds,
            exemplar={"trace_id": trace_id} if trace_id else None)
        with self._lock:
            self._count += 1
            self._sum += seconds
            self.last_serving_sec = seconds

    def snapshot(self) -> dict:
        with self._lock:
            count, total = self._count, self._sum
        return {
            "startTime": self.start_time.isoformat(),
            "requestCount": count,
            "avgServingSec": total / count if count else 0.0,
            "lastServingSec": self.last_serving_sec,
            # bucket-interpolated, the PromQL histogram_quantile
            # estimate over the engine-wide shared series (all
            # in-process servers for this engine, /metrics' view)
            "p50ServingSec": self._hist.quantile(0.50),
            "p99ServingSec": self._hist.quantile(0.99),
        }


def _phases(account: Optional[trace.ThreadAccount]) -> dict:
    return account.snapshot() if account is not None else {}


class _Pending:
    __slots__ = ("payload", "event", "result", "error", "abandoned",
                 "t_submit", "trace_ctx")

    def __init__(self, payload):
        self.payload = payload
        self.event = threading.Event()
        self.result = None
        self.error: Optional[BaseException] = None
        self.abandoned = False  # submitter timed out; skip device work
        self.t_submit = time.perf_counter()
        # the submitting handler thread's trace context: contextvars do
        # not cross the hop to the batcher worker, so it rides along and
        # is re-activated around a lone dispatch; a >1 batch dispatches
        # under its own ``serve.batch`` span carrying every member's
        # trace id (the ROADMAP obs follow-up)
        self.trace_ctx = trace.current_context()


class MicroBatcher:
    """Coalesce concurrent queries into one vectorized dispatch.

    Handler threads submit; one worker drains whatever is queued (up to
    ``max_batch``) and answers the whole batch through
    ``Deployment.query_batch`` — one device dispatch amortized over all
    waiters. No artificial wait window: a lone request is served
    immediately, and batches form naturally while the device is busy
    with the previous one (the reference serves queries one-per-request
    inside detached futures, CreateServer.scala:472 — this is the TPU
    dispatch-amortizing upgrade on that contract).

    A failing batch falls back to per-item evaluation so one malformed
    query 400s alone instead of poisoning its batchmates.

    Health wiring: every dispatch runs under the ``serving_dispatch``
    watchdog (a dispatch exceeding PIO_STALL_FACTOR x the trailing
    median fires ``pio_watchdog_stall_total`` + a ``pio.stall`` log),
    and the queue's depth is a registered readiness probe — a backlog
    of ``PIO_QUEUE_DEPTH_LIMIT`` (default 8 x max_batch) turns
    ``/readyz`` DEGRADED before callers start timing out.
    """

    def __init__(self, run_batch, run_one, max_batch: int = 64,
                 chaos_tag: Optional[str] = None):
        import queue as _queue
        import weakref

        self._run_batch = run_batch
        self._run_one = run_one
        self._max_batch = max_batch
        # names THIS batcher at the chaos seam: a fleet tags each
        # replica's batcher by replica name, so `batcher@r1:hang:5s`
        # hangs one replica while its peers keep answering
        self._chaos_tag = chaos_tag
        self._queue: "_queue.Queue[_Pending]" = _queue.Queue()
        # readiness probe over the queue depth (weakref: a dropped
        # batcher must not be kept alive by the health registry)
        queue_ref = weakref.ref(self._queue)
        depth_limit = metrics.env_int("PIO_QUEUE_DEPTH_LIMIT",
                                      max_batch * 8)
        self._queue_probe = health.queue_depth_probe(
            lambda: (q.qsize() if (q := queue_ref()) is not None
                     else None),
            max(1, depth_limit))
        # namespaced per replica on the shared process registry:
        # threaded fleet replicas each get their own probe (an
        # un-namespaced name is last-registration-wins, which would
        # hide every other replica's queue backlog from readiness)
        self._probe_name = ("serving_queue" if chaos_tag is None
                            else f"serving_queue:{chaos_tag}")
        health.REGISTRY.register(self._probe_name, self._queue_probe)
        # batch-size histogram: the observable proof that amortization
        # actually happens under load — exposed in
        # the server's status JSON
        self._hist_lock = threading.Lock()
        self._hist: dict = {}
        # rolling (queue_wait, dispatch) seconds per answered request:
        # separates time spent WAITING for the worker from time inside
        # the model dispatch — the split a concurrency sweep needs to
        # tell queueing from device work
        from collections import deque

        self._splits = deque(maxlen=50_000)
        # abandoned submitters (timed out waiting) are counted here and
        # EXCLUDED from the splits: their queue wait is the caller's
        # timeout and their dispatch time covers work the worker skipped
        # — folding them in would skew the percentiles read from
        # recent_splits() with numbers no served request saw
        self._abandoned = 0
        # dispatches so far; only the worker writes it. Rides on each
        # pio:batch.dispatch span so a trace can tell them apart
        self._seq = 0
        # the worker's spans on two clocks (obs/trace.account_thread);
        # the worker sets it as its loop starts
        self._account: Optional[trace.ThreadAccount] = None
        self._stop = False
        # orders submit()'s stop-check+enqueue against stop()'s flag+wake,
        # so nothing can be enqueued after the worker's shutdown drain
        self._stop_lock = threading.Lock()
        # named so the continuous profiler (obs/contprof.py) labels the
        # batch loop's samples with the "batcher" role
        self._worker = threading.Thread(target=self._loop, daemon=True,
                                        name="pio-batcher")
        self._worker.start()

    def submit(self, payload, timeout: float = 30.0):
        pending = _Pending(payload)
        with self._stop_lock:
            if self._stop:
                raise RuntimeError("serving batcher is stopped")
            self._queue.put(pending)
        if not pending.event.wait(timeout):
            # leave a tombstone so the worker spends no device time
            # answering a waiter that already gave up
            pending.abandoned = True
            raise TimeoutError("query timed out in the serving batcher")
        if pending.error is not None:
            raise pending.error
        return pending.result

    def stop(self) -> None:
        with self._stop_lock:
            if self._stop:
                return
            self._stop = True
            self._queue.put(_Pending(None))  # wake the worker
        # remove only OUR probe: if a newer in-process batcher already
        # re-registered the name, its live probe must survive this stop
        health.REGISTRY.unregister(self._probe_name, self._queue_probe)
        # the worker's shutdown drain answers everything still queued, so
        # no submitter blocks out its full timeout on a dying server
        self._worker.join(timeout=60)

    def _loop(self) -> None:
        import queue as _queue

        self._account = trace.account_thread()
        leftover: List[_Pending] = []
        while True:
            with trace.device_span("batch.idle"):   # nothing to do
                first = self._queue.get()
            if self._stop:
                leftover.append(first)
                break
            batch = [first]
            try:
                with trace.device_span("batch.collect"):
                    while len(batch) < self._max_batch:
                        try:
                            batch.append(self._queue.get_nowait())
                        except _queue.Empty:
                            break
                with _DISPATCH_WATCHDOG.watch():
                    # chaos seam: injected latency/hangs land INSIDE the
                    # dispatch watchdog's watch window (a chaos hang is
                    # what tier-1 uses to prove the watchdog still
                    # fires), injected errors fail this batch's waiters
                    chaos.inject("batcher", tag=self._chaos_tag)
                    self._answer(batch)
            except Exception as e:  # noqa: BLE001 — a dead worker starves
                # every future submitter silently; log, fail THIS batch's
                # waiters, keep the loop alive
                log.exception("batch worker iteration failed")
                for p in batch:
                    if not p.event.is_set():
                        p.error = e
                        p.event.set()
        # shutdown drain: only the worker consumes the queue, so nothing
        # races it; the stop-lock guarantees no later enqueues. A drain
        # failure must be logged too — stranded submitters block out
        # their full timeout with no symptom otherwise.
        try:
            while True:
                try:
                    leftover.append(self._queue.get_nowait())
                except _queue.Empty:
                    break
            for p in leftover:
                if p.payload is not None and not p.event.is_set():
                    p.error = RuntimeError("serving batcher stopped")
                    p.event.set()
        except Exception:  # noqa: BLE001 — see above
            log.exception("batcher shutdown drain failed")

    def histogram(self) -> dict:
        """Dispatch-size distribution since start: {"1": lone requests,
        "2": two-query dispatches, ...}. Sizes > 1 are queries that
        shared one device dispatch. ``phases``: where the worker thread's
        time went since start, ``{span name: [count, self wall ns, self
        CPU ns]}`` (obs/trace.ThreadAccount); they add up to its time."""
        with self._hist_lock:
            hist = {str(k): v for k, v in sorted(self._hist.items())}
            abandoned = self._abandoned
        return {
            "maxBatch": self._max_batch,
            "dispatches": sum(hist.values()),
            "batchSizeHistogram": hist,
            # timed-out submitters, kept OUT of the latency splits
            "abandonedRequests": abandoned,
            "phases": _phases(self._account),
        }

    def _answer(self, batch) -> None:
        live = [p for p in batch if not p.abandoned]
        if len(live) < len(batch):
            with self._hist_lock:
                self._abandoned += len(batch) - len(live)
        batch = live
        if not batch:
            return
        with self._hist_lock:
            self._hist[len(batch)] = self._hist.get(len(batch), 0) + 1
        self._seq += 1
        t_start = time.perf_counter()
        if len(batch) == 1:
            p = batch[0]
            token = (trace.activate_context(p.trace_ctx)
                     if p.trace_ctx is not None else None)
            try:
                with trace.span("serve.dispatch", device="batch.dispatch",
                                batch_size=1, seq=self._seq, size=1,
                                path="lone"):
                    p.result = self._run_one(p.payload)
            except BaseException as e:  # noqa: BLE001 — relayed to caller
                p.error = e
            finally:
                if token is not None:
                    trace.deactivate(token)
            self._deliver(batch, t_start)
            return
        # the multi-query dispatch gets its OWN span: one record, under
        # a batch-minted trace id, carrying every member's trace id —
        # so a member's span chain joins its batchmates' (previously a
        # >1 batch ran untraced), and each member's flight record
        # learns the dispatch size it shared
        members = [p.trace_ctx.trace_id for p in batch
                   if p.trace_ctx is not None]
        for tid in members:
            flight.note_field("batch_size", len(batch), trace_id=tid)
        try:
            batch_token = trace.activate(trace.new_trace_id())
            try:
                with trace.span("serve.batch", device="batch.dispatch",
                                batch_size=len(batch), members=members,
                                seq=self._seq, size=len(batch),
                                path="batched"):
                    results = self._run_batch([p.payload for p in batch])
            finally:
                trace.deactivate(batch_token)
            for p, r in zip(batch, results):
                p.result = r
        except BaseException as e:
            # isolate the poison query: each waiter gets its own verdict
            # (and the fallback costs a serial re-dispatch — worth a log)
            log.warning("batch dispatch of %d queries failed (%s: %s); "
                        "re-running individually to isolate the poison "
                        "query", len(batch), type(e).__name__, e)
            for p in batch:
                token = (trace.activate_context(p.trace_ctx)
                         if p.trace_ctx is not None else None)
                try:
                    with trace.span("serve.dispatch",
                                    device="batch.dispatch", batch_size=1,
                                    fallback=True, seq=self._seq, size=1,
                                    path="lone"):
                        p.result = self._run_one(p.payload)
                except BaseException as e:  # noqa: BLE001
                    p.error = e
                finally:
                    if token is not None:
                        trace.deactivate(token)
        self._deliver(batch, t_start)

    def _deliver(self, batch, t_start: float) -> None:
        """Hand a finished dispatch back: its time splits, then every
        waiter's wake-up."""
        with trace.device_span("batch.deliver", size=len(batch)):
            self._record_splits(batch, t_start)
            for p in batch:
                p.event.set()

    def _record_splits(self, batch, t_start: float) -> None:
        t_done = time.perf_counter()
        with self._hist_lock:
            for p in batch:
                if p.abandoned:
                    # the submitter's timeout raced the dispatch (the
                    # entry filter in _answer only catches tombstones
                    # laid BEFORE the drain): count it, don't let its
                    # give-up-sized wait skew the percentiles
                    self._abandoned += 1
                    continue
                self._splits.append((t_start - p.t_submit, t_done - t_start))
        # the same split, attributed to each request's flight record
        # (outside the histogram lock: flight takes its own)
        for p in batch:
            if p.abandoned or p.trace_ctx is None:
                continue
            tid = p.trace_ctx.trace_id
            flight.note_stage("queue", t_start - p.t_submit, trace_id=tid)
            flight.note_stage("dispatch", t_done - t_start, trace_id=tid)

    def recent_splits(self, n: int):
        """Last ``n`` answered requests' (queue_wait_sec, dispatch_sec)
        pairs, oldest first."""
        with self._hist_lock:
            items = list(self._splits)
        return items[-n:]

    def queue_depth(self) -> int:
        """Requests waiting for the worker right now (the admission
        controller's primary shed signal)."""
        return self._queue.qsize()


class StepWorker:
    """The worker of a deployment whose algorithm answers IN STEPS
    (``core.Algorithm.stepwise``: ``begin``, ``step``, ``cancel``; a sequence
    model over a per-session cache, ``models/sessionrec.SeqStackAlgorithm``).

    :class:`MicroBatcher` answers a batch as a whole, so a query that needs
    a few milliseconds would wait behind a batchmate that needs a second.
    Here the worker keeps every admitted query as a ticket and calls the
    algorithm's ``step`` over all of them, again and again: a step does a
    bounded amount of work (every pending short extension, one chunk of one
    long history), hands back what it finished, and new arrivals join at the
    next step. A query therefore waits for the step in flight and for its
    own steps, never for another query's whole answer.

    The submit / stop / queue_depth / histogram / recent_splits surface is
    the batcher's, so the server around it is the same."""

    def __init__(self, deployment_of, chaos_tag: Optional[str] = None):
        import queue as _queue
        from collections import deque

        self._deployment_of = deployment_of
        self._chaos_tag = chaos_tag
        self._queue: "_queue.Queue[_Pending]" = _queue.Queue()
        self._hist_lock = threading.Lock()
        self._hist: dict = {}               # finished per step -> steps
        self._splits = deque(maxlen=50_000)
        self._abandoned = 0
        self._account: Optional[trace.ThreadAccount] = None
        self._stop = False
        self._stop_lock = threading.Lock()
        self._worker = threading.Thread(target=self._loop, daemon=True,
                                        name="pio-batcher")
        self._worker.start()

    def submit(self, payload, timeout: float = 30.0):
        pending = _Pending(payload)
        with self._stop_lock:
            if self._stop:
                raise RuntimeError("serving step worker is stopped")
            self._queue.put(pending)
        if not pending.event.wait(timeout):
            pending.abandoned = True
            raise TimeoutError("query timed out in the serving step worker")
        if pending.error is not None:
            raise pending.error
        return pending.result

    def stop(self) -> None:
        with self._stop_lock:
            if self._stop:
                return
            self._stop = True
            self._queue.put(_Pending(None))  # wake the worker
        self._worker.join(timeout=60)

    def queue_depth(self) -> int:
        return self._queue.qsize()

    def histogram(self) -> dict:
        with self._hist_lock:
            hist = {str(k): v for k, v in sorted(self._hist.items())}
            abandoned = self._abandoned
        return {"stepwise": True, "dispatches": sum(hist.values()),
                "batchSizeHistogram": hist,
                "answered": sum(int(k) * v for k, v in hist.items()),
                "abandonedRequests": abandoned,
                "phases": _phases(self._account)}

    def recent_splits(self, n: int):
        """Last ``n`` answered requests' (seconds from submit to admission —
        the wait for the step in flight —, seconds from there to the answer,
        whether the model called the query an extension), oldest first."""
        with self._hist_lock:
            return list(self._splits)[-n:]

    def _fail(self, pending, error) -> None:
        pending.error = error
        pending.event.set()

    def _arrivals(self, block: bool) -> List[_Pending]:
        """Whatever is queued; with nothing in hand, wait for the first."""
        import queue as _queue

        got = []
        if block:
            with trace.device_span("batch.idle"):   # nothing to do
                got.append(self._queue.get())
        try:
            for _ in range(self._queue.qsize() + 1):
                got.append(self._queue.get_nowait())
        except _queue.Empty:
            pass
        return got

    def _loop(self) -> None:
        self._account = trace.account_thread()
        waiting: List[_Pending] = []     # admitted, no ticket yet
        # (pending, deployment, ticket, t_first): a ticket is stepped by
        # the deployment that began it, through a reload too
        active: List[tuple] = []
        while True:
            try:
                waiting += self._arrivals(block=not (waiting or active))
                if self._stop:
                    break
                with _DISPATCH_WATCHDOG.watch():
                    chaos.inject("batcher", tag=self._chaos_tag)
                    waiting, active = self._advance(waiting, active)
            except Exception as e:  # noqa: BLE001 — a dead worker starves
                # every later submitter: fail what was in hand, go on
                log.exception("step worker iteration failed")
                for p in waiting + [a[0] for a in active]:
                    if not p.event.is_set():
                        self._fail(p, e)
                for _, deployment, ticket, _ in active:
                    try:
                        deployment.algorithms[0].cancel(
                            deployment.models[0], ticket)
                    except Exception:  # noqa: BLE001
                        log.exception("ticket cancel failed")
                waiting, active = [], []
        # shutdown: a submitter must not block out its timeout on a
        # stopped server, and a failure here must leave a trace
        try:
            for p in waiting + [a[0] for a in active]:
                if p.payload is not None and not p.event.is_set():
                    self._fail(p, RuntimeError("serving step worker stopped"))
        except Exception:  # noqa: BLE001 — see above
            log.exception("step worker shutdown drain failed")

    def _advance(self, waiting, active):
        """Admit what can be admitted, run one step, deliver. The format of
        an answer is the algorithm's (``done(ticket, prediction)``) and
        Serving's; nothing of it is known here."""
        deployment = self._deployment_of()
        algorithm, model = deployment.algorithms[0], deployment.models[0]
        still_waiting = []
        with trace.device_span("batch.collect"):
            for p in waiting:
                if p.abandoned:
                    with self._hist_lock:
                        self._abandoned += 1
                    continue
                try:
                    ticket = algorithm.begin(model, p.payload)
                except Exception as e:  # noqa: BLE001 — this query's own
                    self._fail(p, e)
                    continue
                if ticket is None:
                    still_waiting.append(p)
                else:
                    active.append((p, deployment, ticket,
                                   time.perf_counter()))
        by_deployment: dict = {}
        for entry in active:
            by_deployment.setdefault(id(entry[1]), []).append(entry)
        still_active = []
        for entries in by_deployment.values():
            stepping = entries[0][1]
            algorithm, model = stepping.algorithms[0], stepping.models[0]
            live = []
            for entry in entries:
                if entry[0].abandoned:
                    algorithm.cancel(model, entry[2])
                    with self._hist_lock:
                        self._abandoned += 1
                else:
                    live.append(entry)
            entry_of = {id(e[2]): e for e in live}
            delivered = []

            def deliver(ticket, prediction, _entry_of=entry_of,
                        _out=delivered, _serving=stepping.serving):
                # called the moment a ticket is answered: an extension's
                # answer does not wait for the step's chunk
                p, _, _, t_first = _entry_of.pop(id(ticket))
                t_done = time.perf_counter()
                with trace.device_span("batch.deliver", size=1):
                    with trace.device_span("engine.decode"):
                        p.result = _serving.serve(p.payload, [prediction])
                    p.event.set()
                _out.append((t_first - p.t_submit, t_done - t_first,
                             getattr(ticket, "extension", None)))

            if live:
                algorithm.step(model, [e[2] for e in live], deliver)
            still_active += entry_of.values()
            with self._hist_lock:
                self._hist[len(delivered)] = self._hist.get(
                    len(delivered), 0) + 1
                self._splits.extend(delivered)
        return still_waiting, still_active


class EngineServer(HTTPServerBase):
    """One deployed engine behind HTTP (ref: CreateServer.scala:100,106)."""

    def __init__(
        self,
        engine: Engine,
        engine_id: str,
        engine_version: str = "0",
        engine_variant: str = "default",
        host: str = "0.0.0.0",
        port: int = DEFAULT_PORT,
        ctx: Optional[MeshContext] = None,
        storage: Optional[Storage] = None,
        feedback_url: Optional[str] = None,
        feedback_access_key: Optional[str] = None,
        log_url: Optional[str] = None,
        bind_retries: int = 3,
        micro_batch: bool = True,
        max_batch: int = 64,
        slo_conf: Optional[dict] = None,
        chaos_tag: Optional[str] = None,
    ):
        self.engine = engine
        self.engine_id = engine_id
        self.engine_version = engine_version
        self.engine_variant = engine_variant
        self.ctx = ctx or MeshContext()
        self.storage = storage or get_storage()
        self.feedback_url = feedback_url
        self.feedback_access_key = feedback_access_key
        self.log_url = log_url
        self.stats = ServingStats(engine_id)
        self._deployment_lock = threading.Lock()
        # degraded-mode circuit: fed by readiness storage probes and
        # reloads. While not closed, queries keep answering from the
        # last-loaded model with an X-PIO-Degraded stamp and /readyz
        # reports DEGRADED (not FAILED) — losing storage must not read
        # as losing the server.
        self._storage_breaker = breaker_for(f"storage:{engine_id}",
                                            failure_threshold=2)
        self.deployment: Deployment = self._load_latest()
        # chaos identity: a fleet replica is tagged by its supervisor
        # (subprocess replicas via PIO_CHAOS_TAG) so operators can fault
        # ONE replica of a fleet; a standalone server stays untagged
        self.chaos_tag = chaos_tag or os.environ.get("PIO_CHAOS_TAG") or None
        self._batcher = None
        if micro_batch:
            # a full collection is a span like any other: on the worker it
            # is accounted, on a handler it shows in a capture
            trace.span_collections()
        if micro_batch and self.deployment.stepwise:
            # an algorithm that answers in steps gets the step worker; every
            # other engine keeps the batcher as it was
            self._batcher = StepWorker(self._current_deployment,
                                       chaos_tag=self.chaos_tag)
        elif micro_batch:
            self._batcher = MicroBatcher(
                self._query_batch_now, self._query_now,
                max_batch=max_batch, chaos_tag=self.chaos_tag)

        # admission control (resilience tentpole): shed with 429 +
        # Retry-After from queue depth / in-flight / SLO burn signals
        # BEFORE queueing collapse. Thresholds: env defaults, then the
        # PIO_SLO_FILE "shed" block, then the engine.json "slo.shed"
        # block (most specific wins).
        file_conf = slo_mod.configure_from_env() or {}
        if slo_conf:
            # layer the variant block OVER the file's objectives — a
            # variant that only overrides availability must not silently
            # drop the file's latency threshold back to env defaults
            slo_mod.configure({**file_conf, **slo_conf})
        self.admission = AdmissionController(
            "engine",
            queue_depth=lambda: (self._batcher.queue_depth()
                                 if self._batcher is not None else None),
            inflight=_http_inflight,
            max_queue_depth=metrics.env_int("PIO_SHED_QUEUE_DEPTH",
                                            max_batch * 4),
        )
        for conf in (file_conf, slo_conf or {}):
            shed = conf.get("shed") if isinstance(conf, dict) else None
            if shed:
                self.admission.configure(shed)

        # daily version check, no-op unless PIO_UPDATE_URL is configured
        # (ref: UpgradeActor, CreateServer.scala:163-170,246)
        from predictionio_tpu.tools.upgrade import start_upgrade_daemon

        start_upgrade_daemon("engine-server")

        # bind retry x3 with 1s backoff (ref: CreateServer.scala:340-350)
        super().__init__(host, port, _EngineRequestHandler, bind_retries=bind_retries)

    # -- deployment management ----------------------------------------------
    def _resolve_instance(self, instance_id: Optional[str] = None):
        """The COMPLETED instance a (re)load targets: a SPECIFIC one
        when ``instance_id`` names it (the canary rollback lane), else
        the latest. Resolution only — the OOM preflight must see the
        target id before anything is unpickled or device-put."""
        if instance_id:
            instance = self.storage.engine_instances().get(instance_id)
            if instance is None or instance.status != "COMPLETED":
                raise RuntimeError(
                    f"engine instance {instance_id} not found or not "
                    "COMPLETED")
        else:
            instance = self.storage.engine_instances().get_latest_completed(
                self.engine_id, self.engine_version, self.engine_variant
            )
        if instance is None:
            raise RuntimeError(
                f"No valid engine instance found for engine {self.engine_id} "
                f"{self.engine_version} {self.engine_variant}"
            )
        return instance

    def _load_latest(self, instance_id: Optional[str] = None) -> Deployment:
        """Build a warm deployment of the latest COMPLETED instance —
        or of a SPECIFIC completed instance when ``instance_id`` names
        one (the canary rollback lane: the fleet swaps its canary
        replica back onto the baseline instance, not onto "latest",
        which IS the candidate being rolled back)."""
        instance = self._resolve_instance(instance_id)
        deployment = prepare_deploy(self.engine, instance, self.ctx, self.storage)
        self._warmup(deployment)
        return deployment

    def _warmup(self, deployment: Deployment) -> None:
        """Pre-compile each algorithm's serve buckets BEFORE the
        deployment goes live, so the first query after deploy/reload
        pays no XLA compile (SURVEY.md §7.5 hard part #2). Warm-up
        failures never block a deploy — worst case is reference
        behavior (first query compiles)."""
        t0 = time.perf_counter()
        for algo, model in zip(deployment.algorithms, deployment.models):
            try:
                algo.warmup(model, self.ctx)
            except Exception:  # noqa: BLE001
                log.exception("warmup failed for %s", type(algo).__name__)
        log.info("serve warm-up done in %.2fs", time.perf_counter() - t0)

    def reload(self, instance_id: Optional[str] = None,
               force: bool = False) -> str:
        """Hot-swap to the latest completed instance (ref: /reload :592)
        — or to the specific completed instance ``instance_id`` names
        (``GET /reload?instance=<id>``, the canary rollback lane).
        The swap happens only after the new deployment is warm — live
        traffic never waits on the new model's compiles. A reload that
        fails on storage feeds the degraded-mode circuit; one that
        succeeds closes it (recovery path).

        OOM preflight (obs/memacct.py): the target instance is priced
        from its stored blob BEFORE anything loads; an estimate beyond
        current headroom raises :class:`memacct.PreflightRefused`
        (route: 507 + the JSON reason) unless ``force`` — load+warm
        precedes the swap, so during the window BOTH deployments are
        resident and the un-subtracted headroom check is exactly
        right. The successful swap releases the OLD deployment's
        ledger footprints, so gauges drop with the swap, not the GC."""
        from predictionio_tpu.data.storage import StorageError
        from predictionio_tpu.obs import memacct

        try:
            instance = self._resolve_instance(instance_id)
        except (StorageError, ConnectionError):
            self._storage_breaker.record_failure()
            raise
        # may raise PreflightRefused — deliberately OUTSIDE the breaker
        # accounting: a refused deploy is a capacity verdict, not a
        # storage failure, and must not push the server degraded
        try:
            memacct.preflight_check(instance.id, self.storage,
                                    force=force)
        except memacct.PreflightRefused as e:
            journal.emit("preflight_refused", instance=instance.id,
                         detail=str(e)[:200])
            raise
        try:
            deployment = prepare_deploy(self.engine, instance, self.ctx,
                                        self.storage)
        except (StorageError, ConnectionError):
            self._storage_breaker.record_failure()
            raise
        if self._batcher is not None and deployment.stepwise != isinstance(
                self._batcher, StepWorker):
            # the worker's kind was chosen at start, for the deployment
            # there was: a batcher would run a stepwise algorithm's whole
            # answer inside a batch, a step worker cannot drive any other
            raise RuntimeError(
                f"reload refused: instance {instance.id} "
                f"{'answers' if deployment.stepwise else 'does not answer'} "
                "in steps and this server's worker was started for the "
                "other kind; deploy it on a new server")
        self._warmup(deployment)
        self._storage_breaker.record_success()
        with self._deployment_lock:
            old, self.deployment = self.deployment, deployment
        journal.emit("reload", instance=deployment.instance.id,
                     prev=old.instance.id, requested=instance_id,
                     forced=force or None)
        # retire the swapped-out instance's residency (weakref sweep is
        # the backstop; the deliberate seam keeps gauges honest NOW)
        for model in old.models:
            memacct.release_model(model)
        return deployment.instance.id

    # -- streaming model patches (workflow/stream.py) -----------------------
    class StalePatch(RuntimeError):
        """The patch targets an instance this server no longer serves."""

    def apply_patch(self, payload: dict) -> dict:
        """Apply a streaming fold-in patch to the live deployment —
        the lightweight freshness lane between full reloads. Applied
        under the deployment lock (between queries); each algorithm's
        ``apply_patch`` swaps rows copy-on-write, so in-flight queries
        see old-or-new tables, never torn rows.

        Raises :class:`StalePatch` when ``instanceId`` names another
        instance (the caller should resync), ValueError on malformed or
        unsupported blocks. Returns {"applied": n_blocks}."""
        instance_id = payload.get("instanceId")
        blocks = payload.get("algorithms")
        if not isinstance(blocks, list) or not blocks:
            _MODEL_PATCHES.labels("rejected").inc()
            raise ValueError("patch needs a non-empty 'algorithms' list")
        with self._deployment_lock:
            deployment = self.deployment
            if instance_id and instance_id != deployment.instance.id:
                _MODEL_PATCHES.labels("stale").inc()
                journal.emit("patch", outcome="stale",
                             instance=instance_id,
                             deployed=deployment.instance.id)
                raise self.StalePatch(
                    f"patch targets instance {instance_id} but "
                    f"{deployment.instance.id} is deployed")
            applied = 0
            for block in blocks:
                if not isinstance(block, dict):
                    _MODEL_PATCHES.labels("rejected").inc()
                    raise ValueError("each algorithm block must be an object")
                idx = block.get("index", 0)
                if not isinstance(idx, int) or not (
                        0 <= idx < len(deployment.algorithms)):
                    _MODEL_PATCHES.labels("rejected").inc()
                    raise ValueError(f"algorithm index {idx!r} out of range")
                algo = deployment.algorithms[idx]
                model = deployment.models[idx]
                try:
                    ok = algo.apply_patch(model, block)
                except ValueError:
                    _MODEL_PATCHES.labels("rejected").inc()
                    raise
                if not ok:
                    _MODEL_PATCHES.labels("rejected").inc()
                    raise ValueError(
                        f"algorithm {type(algo).__name__} does not "
                        "support model patches — use /reload")
                applied += 1
        _MODEL_PATCHES.labels("applied").inc()
        journal.emit("patch", outcome="ok", applied=applied,
                     instance=instance_id)
        return {"applied": applied}

    # -- degraded mode ------------------------------------------------------
    def degraded_reason(self) -> Optional[str]:
        """Non-None while serving degraded: the storage circuit is not
        closed, so the last-loaded model answers queries but reloads
        and feedback durability cannot be trusted. The string is the
        ``X-PIO-Degraded`` response header."""
        if self._storage_breaker.state == _BREAKER_CLOSED:
            return None
        with self._deployment_lock:
            instance_id = self.deployment.instance.id
        return ("storage unavailable; serving last-loaded instance "
                f"{instance_id}")

    def storage_readyz_probe(self) -> health.ProbeResult:
        """The engine server's ``/readyz`` storage probe (the shared
        handler prefers this hook over the default
        ``health.storage_probe``): storage loss while a model is loaded
        is DEGRADED, not FAILED — the server can still do its one job
        (answer queries); it cannot reload or verify freshness. The
        probe feeds the degraded-mode circuit: consecutive failures
        open it (after which probes fail FAST instead of stalling every
        readiness check on a dead backend), and the half-open probe's
        eventual success closes it — recovery needs no restart."""
        breaker = self._storage_breaker
        if not breaker.allow():
            return health.degraded(
                f"storage circuit open (next probe in "
                f"{breaker.retry_after():.0f}s); {self.degraded_reason()}")
        try:
            result = health.storage_probe(self.storage)
        except Exception as e:  # noqa: BLE001 — a raising probe IS the finding
            result = health.failed(f"{type(e).__name__}: {e}")
        if result.status == health.FAILED:
            breaker.record_failure()
            return health.degraded(
                f"{result.reason}; serving degraded from the last-loaded "
                "model")
        breaker.record_success()
        return result

    # -- query path ---------------------------------------------------------
    def _current_deployment(self) -> Deployment:
        with self._deployment_lock:
            return self.deployment

    def _query_now(self, payload: Any) -> Any:
        with self._deployment_lock:
            deployment = self.deployment
        return deployment.query(payload)

    def _query_batch_now(self, payloads) -> Any:
        with self._deployment_lock:
            deployment = self.deployment
        return deployment.query_batch(payloads)

    def query(self, payload: Any) -> Any:
        t0 = time.perf_counter()
        # pio:serve.wait on the device trace's clock: what this handler
        # thread waits for (the batcher's queue and the dispatch; the
        # dispatch itself on a server that runs without a batcher)
        with trace.span("serve.query", device="serve.wait",
                        engine=self.engine_id):
            if self._batcher is not None:
                result = self._batcher.submit(payload)
            else:
                t_disp = time.perf_counter()
                result = self._query_now(payload)
                flight.note_stage("dispatch", time.perf_counter() - t_disp)
        elapsed = time.perf_counter() - t0
        self.stats.record(elapsed)
        self._note_query_coverage(payload)
        if self.feedback_url and self.feedback_access_key:
            # prId lets follow-up events join back to this prediction
            # (ref: CreateServer feedback loop assigns prId :488-550)
            pr_id = uuid.uuid4().hex
            if isinstance(result, dict):
                result = {**result, "prId": pr_id}
            with self._deployment_lock:
                instance_id = self.deployment.instance.id
            threading.Thread(
                target=self._send_feedback,
                args=(payload, result, pr_id, instance_id),
                daemon=True,
            ).start()
        return result

    def _note_query_coverage(self, payload: Any) -> None:
        """Unknown-entity accounting at the query-decode seam
        (obs/dataobs.py): how many user/item references this query
        named, and how many the SERVED model has never seen — the
        "is the model stale for the traffic we actually get" signal.
        Best-effort: accounting must never break serving."""
        try:
            if not isinstance(payload, dict) or not dataobs.DATAOBS.enabled():
                return
            users = [payload["user"]] if payload.get("user") is not None \
                else []
            items = list(payload.get("items") or [])
            if payload.get("item") is not None:
                items.append(payload["item"])
            if not users and not items:
                return
            with self._deployment_lock:
                models = list(self.deployment.models)
            user_maps = [m.user_ids for m in models
                         if getattr(m, "user_ids", None) is not None]
            item_maps = [m.item_ids for m in models
                         if getattr(m, "item_ids", None) is not None]
            refs = unknown = 0
            if users and user_maps:
                refs += len(users)
                unknown += sum(
                    1 for u in users
                    if not any(str(u) in ids for ids in user_maps))
            if items and item_maps:
                refs += len(items)
                unknown += sum(
                    1 for i in items
                    if not any(str(i) in ids for ids in item_maps))
            if refs:
                dataobs.DATAOBS.note_query(refs, unknown)
        except Exception:  # noqa: BLE001
            log.debug("query coverage accounting failed", exc_info=True)

    @staticmethod
    def _post_json(url: str, payload: Any, what: str) -> None:
        """One best-effort JSON POST (shared by the feedback loop and
        remote error log; failures are logged, never raised)."""
        try:
            req = urllib.request.Request(
                url,
                data=json.dumps(payload).encode(),
                # the feedback loop posts to the EVENT SERVER — a fleet
                # member: the trace context (when one is active on this
                # thread) lets the collector stitch prediction ->
                # feedback into one tree (JT17)
                headers=trace.traced_headers(
                    {"Content-Type": "application/json"}),
                method="POST",
            )
            urllib.request.urlopen(req, timeout=5)
        except Exception as e:  # noqa: BLE001 — best-effort
            log.warning("%s POST failed: %s", what, e)

    def remote_log(self, message: str, level: str = "ERROR") -> None:
        """POST an error line to the configured --log-url (ref:
        CreateServer.scala:413-424 remoteLog — fire-and-forget, a dead
        log endpoint must never affect serving)."""
        if not self.log_url:
            return
        payload = {
            "level": level,
            "message": message,
            "engineId": self.engine_id,
            "engineVariant": self.engine_variant,
        }
        threading.Thread(
            target=self._post_json, args=(self.log_url, payload, "remote log"),
            daemon=True,
        ).start()

    def _send_feedback(self, query: Any, prediction: Any, pr_id: str, instance_id: str) -> None:
        """Async predict-event feedback loop (ref: CreateServer.scala:488-550)."""
        event = {
            "event": "predict",
            "entityType": "pio_pr",
            "entityId": instance_id,
            "prId": pr_id,
            "properties": {"query": query, "prediction": prediction},
        }
        self._post_json(
            f"{self.feedback_url}/events.json?accessKey={self.feedback_access_key}",
            event, "feedback loop",
        )

    def stop(self) -> None:
        if self._batcher is not None:
            self._batcher.stop()
        # fleet replica stop: retire this server's residency from the
        # memory ledger — a stopped replica's models must not keep
        # exporting pio_model_device_bytes until the GC happens by
        from predictionio_tpu.obs import memacct

        with self._deployment_lock:
            models = list(self.deployment.models)
        for model in models:
            memacct.release_model(model)
        super().stop()

    def status(self) -> dict:
        """ref: status landing page content (CreateServer.scala:433-459)."""
        with self._deployment_lock:
            instance = self.deployment.instance
            models = list(self.deployment.models)
        # retrieval surface: stats of each model's BUILT ANN index
        # (built at warm-up; None for non-retrieval algorithms — a
        # status read must never trigger a build)
        retrieval = [
            m.retrieval_stats() if hasattr(m, "retrieval_stats") else None
            for m in models
        ]
        from predictionio_tpu.ops.topk import measured_dispatch_latency

        return {
            "status": "alive",
            "engineId": self.engine_id,
            "engineVersion": self.engine_version,
            "engineVariant": self.engine_variant,
            "engineInstanceId": instance.id,
            "engineFactory": instance.engine_factory,
            "trainedAt": instance.end_time.isoformat(),
            "algorithms": json.loads(instance.algorithms_params or "[]"),
            "stats": self.stats.snapshot(),
            # micro-batching evidence: dispatch-size distribution
            # (None when micro-batching is disabled)
            "batcher": (self._batcher.histogram()
                        if self._batcher is not None else None),
            # resilience surface: shed limits/counters + degraded mode
            "admission": self.admission.snapshot(),
            "degraded": self.degraded_reason(),
            "storageCircuit": self._storage_breaker.snapshot(),
            "retrieval": retrieval,
            # which device answers (and the compile-cache outcome of
            # this process): a 200 alone never shows the chip was used
            "device": {
                **jaxmon.device_report(),
                # the floor ops/topk.py's placement policy routes by
                # (measured once per process, then cached)
                "dispatch_latency_sec": measured_dispatch_latency(),
            },
        }


_STATUS_HTML = """<!DOCTYPE html>
<html><head><title>{engine_id} — PredictionIO-TPU engine</title>
<style>
 body {{ font-family: system-ui, sans-serif; margin: 2rem auto; max-width: 60rem; }}
 h1 {{ font-size: 1.4rem; }} table {{ border-collapse: collapse; }}
 td, th {{ border: 1px solid #ccc; padding: .3rem .6rem; text-align: left; }}
 code {{ background: #f4f4f4; padding: 0 .2rem; }}
</style></head><body>
<h1>Engine <code>{engine_id}</code> is deployed</h1>
<table>
<tr><th>Engine variant</th><td>{engine_variant}</td></tr>
<tr><th>Engine instance</th><td>{engine_instance_id}</td></tr>
<tr><th>Engine factory</th><td>{engine_factory}</td></tr>
<tr><th>Trained at</th><td>{trained_at}</td></tr>
<tr><th>Started</th><td>{start_time}</td></tr>
<tr><th>Requests served</th><td>{request_count}</td></tr>
<tr><th>Average serving time</th><td>{avg_ms:.2f} ms</td></tr>
<tr><th>Last serving time</th><td>{last_ms:.2f} ms</td></tr>
</table>
<h2>Algorithms</h2><pre>{algorithms}</pre>
<p>POST queries to <code>/queries.json</code>; JSON status at
<code>/</code> (Accept: application/json); <code>/reload</code> swaps in
the latest trained instance.</p>
</body></html>
"""


class _EngineRequestHandler(JSONRequestHandler):
    server_version = "PIOEngineServer/0.1"

    def do_GET(self):
        path = urlparse(self.path).path
        if path == "/":
            status = self.server_ref.status()
            # browsers get the operator landing page (ref:
            # CreateServer.scala:433-459 + the twirl index template);
            # programmatic clients keep the JSON contract
            if "text/html" in (self.headers.get("Accept") or ""):
                import html as _html

                stats = status["stats"]
                esc = lambda v: _html.escape(str(v))  # noqa: E731
                html = _STATUS_HTML.format(
                    engine_id=esc(status["engineId"]),
                    engine_variant=esc(status["engineVariant"]),
                    engine_instance_id=esc(status["engineInstanceId"]),
                    engine_factory=esc(status["engineFactory"]),
                    trained_at=esc(status["trainedAt"]),
                    start_time=esc(stats["startTime"]),
                    request_count=stats["requestCount"],
                    avg_ms=stats["avgServingSec"] * 1e3,
                    last_ms=stats["lastServingSec"] * 1e3,
                    algorithms=esc(json.dumps(status["algorithms"], indent=2)),
                )
                self._send(200, html, content_type="text/html; charset=UTF-8")
            else:
                self._send(200, status)
        elif path == "/reload":
            from urllib.parse import parse_qs

            from predictionio_tpu.obs import memacct

            params = parse_qs(urlparse(self.path).query)
            target = (params.get("instance") or [None])[0]
            force = (params.get("force") or ["0"])[0].lower() in (
                "1", "true")
            try:
                instance_id = self.server_ref.reload(target, force=force)
                self._send(200, {"message": "reloaded", "engineInstanceId": instance_id})
            except memacct.PreflightRefused as e:
                # 507 Insufficient Storage: the candidate would exceed
                # device-memory headroom — refused BEFORE any load, the
                # serving model untouched; ?force=1 (or the fleet
                # admin's {"force": true}) overrides
                self._send(507, {"message": str(e),
                                 "preflight": e.decision})
            except RuntimeError as e:
                self.server_ref.remote_log(f"reload failed: {e}")
                self._send(404, {"message": str(e)})
            except Exception as e:  # noqa: BLE001 — a dead backend must
                # answer 503, not crash the keep-alive connection; the
                # failure already fed the degraded-mode circuit
                log.exception("reload failed")
                self.server_ref.remote_log(
                    f"reload failed: {type(e).__name__}: {e}")
                self._send(503, {"message": f"reload failed: {e}"})
        else:
            self._send(404, {"message": "Not Found"})

    def do_POST(self):
        path = urlparse(self.path).path
        if path == "/queries.json":
            # admission control FIRST — before the body parse, before
            # any queue time: an overloaded server's cheapest work is
            # saying no (429 + Retry-After), and the shed must be
            # reconstructable (counter + flight record)
            with trace.device_span("serve.admit"):
                decision = self.server_ref.admission.check()
            if decision is not None:
                flight.note_field("shed", decision.reason)
                self._send(
                    429,
                    {"message": "overloaded — retry after the advised "
                                "delay", "reason": decision.reason,
                     "detail": decision.detail,
                     "retryAfterSec": decision.retry_after},
                    extra_headers={"Retry-After": str(decision.retry_after)})
                return
            try:
                payload = self._read_json()
            except json.JSONDecodeError as e:
                self._send(400, {"message": f"invalid JSON: {e}"})
                return
            # opt-in replay capture (PIO_FLIGHT_PAYLOADS): the byte cap
            # reuses the Content-Length the read already knew
            flight.record_payload(
                "/queries.json", payload,
                nbytes=int(self.headers.get("Content-Length") or 0))
            try:
                result = self.server_ref.query(payload)
            except (KeyError, TypeError, ValueError) as e:
                # malformed query for this engine (ref: 400 on bad query JSON)
                self._send(400, {"message": f"bad query: {e}"})
                return
            except Exception as e:
                log.exception("query failed")
                # the answered-500 path never raises through the
                # instrumented wrapper, so name the error here — the
                # flight record must carry WHAT failed, not just "500"
                flight.note_field("error", f"{type(e).__name__}: {e}")
                self.server_ref.remote_log(
                    f"query failed: {type(e).__name__}: {e}"
                )
                self._send(500, {"message": str(e)})
                return
            degraded = self.server_ref.degraded_reason()
            self._send(200, result,
                       extra_headers=({"X-PIO-Degraded": degraded}
                                      if degraded else None))
        elif path == "/model/patch":
            # same bearer gate as /admin/*: a patch MUTATES the served
            # model — an open route would let anyone rewrite factors
            from predictionio_tpu.serving.http import _admin_authorized

            if not _admin_authorized(self):
                self._send(401, {"message": "missing or invalid bearer "
                                            "token (PIO_ADMIN_TOKEN)"},
                           extra_headers={"WWW-Authenticate": "Bearer"})
                return
            try:
                payload = self._read_json()
            except json.JSONDecodeError as e:
                self._send(400, {"message": f"invalid JSON: {e}"})
                return
            try:
                result = self.server_ref.apply_patch(payload)
            except EngineServer.StalePatch as e:
                self._send(409, {"message": str(e)})
                return
            except (ValueError, TypeError, KeyError) as e:
                self._send(400, {"message": f"bad patch: {e}"})
                return
            except Exception as e:  # noqa: BLE001 — a failing patch must
                # answer 500, never crash the keep-alive connection
                log.exception("model patch failed")
                self._send(500, {"message": str(e)})
                return
            self._send(200, {"message": "patched", **result})
        elif path == "/stop":
            self._send(200, {"message": "stopping"})
            self.server_ref.stop()
        else:
            self._send(404, {"message": "Not Found"})


def deploy(
    engine: Engine,
    engine_id: str,
    engine_version: str = "0",
    engine_variant: str = "default",
    **kwargs,
) -> EngineServer:
    """Convenience: build + start a server for the latest completed
    instance (the `pio deploy` path, Console.scala:830)."""
    return EngineServer(
        engine, engine_id, engine_version=engine_version,
        engine_variant=engine_variant, **kwargs
    ).start()
