"""Request tracing: trace ids, spans, structured per-span records.

One slow query needs decomposing — was it serving (queue + dispatch),
the storage round-trip, or device compute? The reference has nothing
here (its answer is the Spark UI, which never sees the serving path).
This module is a deliberately small tracer:

  - a trace id is minted at the edge (the shared HTTP handler,
    serving/http.py) or accepted from the ``X-PIO-Trace-Id`` request
    header, and propagated to downstream storage-server calls by the
    ``rest`` backend client (data/backends/rest.py)
  - ``span("storage.find")`` wraps a unit of work; on exit a structured
    record {trace, span, parent, name, start_unix, duration_ms, ...}
    is appended to an in-process ring buffer, optionally mirrored as a
    JSON line to the file named by ``PIO_TRACE_LOG`` (size-rotated:
    current + one ``.1`` roll, threshold ``PIO_TRACE_LOG_MAX_BYTES``,
    rolls counted in ``pio_trace_log_rotations_total``), and counted
    in the ``pio_trace_spans_total{name=...}`` metric
  - context travels in a contextvar; spans nest (parent ids) within a
    thread, and ``current_context()``/``activate_context()`` hand the
    trace across explicit thread hops (the serving micro-batcher)
  - cross-process parenting: outbound intra-fleet calls attach the
    active span id as ``X-PIO-Parent-Span`` (``traced_headers()``)
    beside the trace id; the receiving edge (serving/http.py) parents
    its span to it, so obs/collect.py can stitch the per-process rings
    into one tree. The ring is sized by ``PIO_SPAN_RING`` and counts
    evictions in ``pio_trace_spans_evicted_total`` — the collector's
    "why is this trace partial" evidence.

Spans only record while a trace is active — background work that no
request asked about stays silent, so the ring buffer and trace log hold
request-shaped evidence, not noise.

``device_span("batch.dispatch", size=31)`` is the same boundary on the
DEVICE trace's clock: a ``jax.profiler.TraceAnnotation`` named
``pio:batch.dispatch`` on the profiler's host plane, so a device event
or an idle gap of a captured trace can be held against what the host
was doing. It needs no active trace (the batcher worker's loop has
none), costs well under a microsecond while no profiler session runs,
and is a null context in a process that never imported JAX (the event
and storage servers). ``span()`` opens one too, so a boundary that
already records is annotated from the same call site.

A WORKER thread may ask, once (``account_thread()``), that every span it
opens be accounted on two clocks: per span name a count, its SELF wall
nanoseconds and its SELF thread-CPU nanoseconds (its own less what its child
spans covered; the time between its outermost spans goes under
``unspanned``), so that the names add up to the thread's time. Wall less CPU
is the time the thread was not running: inside a name of ``WAIT_PHASES`` it
was meant to wait, anywhere else it wanted the interpreter (or the CPU, or a
transfer) and did not have it. The same call site gives the annotation, the
record and the accounting; a thread that did not ask pays one thread-local
read a span. Where a read of the thread's CPU clock costs more than
``CPU_READ_LIMIT_NS`` (a sandboxed kernel answers it in 6 us, in steps of 10
ms, and charges a waiting thread: PERF.md §6, PR 37) the account keeps the
wall clock alone and reports every CPU figure as None: not measured.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import json
import logging
import os
import random
import re
import sys
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional

from predictionio_tpu.obs import metrics

log = logging.getLogger(__name__)

#: propagation header, engine server -> storage client -> storage server
TRACE_HEADER = "X-PIO-Trace-Id"

#: the CALLER's active span id, riding beside the trace id on every
#: intra-fleet request: the receiving server parents its edge span to
#: it, so the federation collector (obs/collect.py) can stitch the
#: per-process rings into ONE cross-process tree instead of a forest
#: of per-process roots
PARENT_HEADER = "X-PIO-Parent-Span"

#: ids we mint are 32-hex; inbound ids must at least be id-SHAPED (hex
#: + hyphens, bounded length) — anything else is discarded and re-minted
#: at the edge, so untrusted header bytes never reach response headers,
#: downstream requests or the span log
_TRACE_ID_RE = re.compile(r"^[0-9a-fA-F-]{8,64}$")

#: span ids we mint are 16-hex; same inbound-shape discipline as trace
#: ids (an invalid parent is dropped, the edge span simply roots)
_SPAN_ID_RE = re.compile(r"^[0-9a-fA-F]{8,32}$")


def valid_trace_id(value: str) -> bool:
    return bool(value and _TRACE_ID_RE.match(value))


def valid_span_id(value: str) -> bool:
    return bool(value and _SPAN_ID_RE.match(value))

#: default ring buffer size: enough for a test run or a quick operator
#: look-back; serving hosts size it via PIO_SPAN_RING (a fleet member
#: whose ring evicts a trace's spans makes that trace PARTIAL at the
#: collector — pio_trace_spans_evicted_total says why)
RECENT_LIMIT = 4096


def ring_capacity() -> int:
    """The span ring size (``PIO_SPAN_RING``, default
    :data:`RECENT_LIMIT`; read per emit so env changes and test
    monkeypatching take effect without a restart)."""
    try:
        cap = int(os.environ.get("PIO_SPAN_RING", RECENT_LIMIT))
    except ValueError:
        return RECENT_LIMIT
    return max(1, cap)

#: PIO_TRACE_LOG rotation threshold: when the current file outgrows
#: this many bytes it is rolled to ``<path>.1`` (replacing any previous
#: roll) — current + one rolled file bound the disk footprint at ~2x
_LOG_MAX_BYTES_DEFAULT = 64 * 1024 * 1024

_SPANS_TOTAL = metrics.counter(
    "pio_trace_spans_total",
    "Spans recorded, by span name",
    ("name",),
)

_LOG_ROTATIONS_TOTAL = metrics.counter(
    "pio_trace_log_rotations_total",
    "PIO_TRACE_LOG size-based rotations (each drops the previously "
    "rolled file's spans)",
)

_SPANS_EVICTED_TOTAL = metrics.counter(
    "pio_trace_spans_evicted_total",
    "Span records evicted from the in-process ring (PIO_SPAN_RING) — "
    "a trace the federation collector reports as partial lost its "
    "spans here",
)


class SpanContext(NamedTuple):
    """Immutable (trace id, active span id) — safe to hand across threads."""

    trace_id: str
    span_id: Optional[str]


_ctx: "contextvars.ContextVar[Optional[SpanContext]]" = contextvars.ContextVar(
    "pio_trace_ctx", default=None
)

_recent: "collections.deque[Dict[str, Any]]" = collections.deque(
    maxlen=ring_capacity()
)
_emit_lock = threading.Lock()

# the PIO_TRACE_LOG sink keeps one append-mode handle (re-opened only
# when the env var changes): per-span open()/close() under a lock shared
# by every handler thread would serialize the serving hot path on
# filesystem syscalls
_log_lock = threading.Lock()
_log_file = None
_log_path: Optional[str] = None
_log_failed_path: Optional[str] = None


def _write_log_line(line: str) -> None:
    global _log_file, _log_path, _log_failed_path
    path = os.environ.get("PIO_TRACE_LOG")
    if not path or path == _log_failed_path:
        # a sink that failed once stays off (until the env var changes):
        # warning + failed syscall per span would flood a serving host
        return
    try:
        max_bytes = int(os.environ.get("PIO_TRACE_LOG_MAX_BYTES",
                                       _LOG_MAX_BYTES_DEFAULT))
    except ValueError:
        max_bytes = _LOG_MAX_BYTES_DEFAULT
    try:
        with _log_lock:
            if path != _log_path:
                if _log_file is not None:
                    _log_file.close()
                _log_file = open(path, "a", encoding="utf-8")  # graftlint: disable=JT21 — _log_lock exists to serialize this very handle; the open is once per path change, not per span
                _log_path = path
            elif max_bytes > 0 and _log_file.tell() >= max_bytes:
                # size-based rotation: keep current + ONE rolled file —
                # an unbounded span log on a serving host eventually
                # fills the disk (the pre-rotation failure mode). tell()
                # is the write offset of our own append handle, so no
                # stat() syscall rides the span hot path.
                _log_file.close()
                os.replace(path, path + ".1")
                _log_file = open(path, "a", encoding="utf-8")  # graftlint: disable=JT21 — rotation must be atomic with the handle swap the lock guards; once per PIO_TRACE_LOG_MAX_BYTES of spans
                _LOG_ROTATIONS_TOTAL.inc()
            _log_file.write(line + "\n")
            _log_file.flush()
    except OSError as e:
        _log_failed_path = path
        log.warning("trace log %s unwritable, span sink disabled: %s",
                    path, e)


#: the process's one source of ids, seeded once from the system (a forked
#: child seeds it again, a spawned one imports it anew). ``uuid4`` reads
#: ``os.urandom`` a call: a system call made with the interpreter released,
#: which a thread gets back only behind whoever else wants it (3.7 ms a
#: dispatch for the batcher's worker beside 14 woken handlers: PERF.md §6,
#: PRs 37-38). These are correlation ids, not secrets: an id that must not be
#: guessed (a feedback ``prId``, a scan id) stays on ``uuid4``.
#: ``getrandbits`` is one C call, whole under the interpreter's lock.
_ids = random.Random(os.urandom(32))
_id_bits = _ids.getrandbits

os.register_at_fork(after_in_child=lambda: _ids.seed(os.urandom(32)))


def new_trace_id() -> str:
    """32 lower-case hex digits, as ``uuid4().hex`` gave."""
    return "%032x" % _id_bits(128)


def _new_span_id() -> str:
    return "%016x" % _id_bits(64)


def current_context() -> Optional[SpanContext]:
    return _ctx.get()


def current_trace_id() -> Optional[str]:
    ctx = _ctx.get()
    return ctx.trace_id if ctx else None


def activate(trace_id: str, span_id: Optional[str] = None):
    """Install a trace context; returns a token for ``deactivate``."""
    return _ctx.set(SpanContext(trace_id=trace_id, span_id=span_id))


def activate_context(ctx: SpanContext):
    return _ctx.set(ctx)


def deactivate(token) -> None:
    _ctx.reset(token)


#: extra per-span consumers (the flight recorder routes spans into the
#: request record they belong to). A sink must be fast and non-raising;
#: a raising sink is dropped with a warning rather than poisoning the
#: span exit path of every handler thread.
_sinks: List[Any] = []


def add_sink(fn) -> None:
    """Register ``fn(record: dict)`` to be called for every emitted
    span record (idempotent per function object)."""
    with _emit_lock:
        if fn not in _sinks:
            _sinks.append(fn)


def remove_sink(fn) -> None:
    with _emit_lock:
        if fn in _sinks:
            _sinks.remove(fn)


def _emit(record: Dict[str, Any]) -> None:
    global _recent
    _SPANS_TOTAL.labels(record["name"]).inc()
    with _emit_lock:
        cap = ring_capacity()
        if _recent.maxlen != cap:
            # PIO_SPAN_RING changed since the last emit: re-bound the
            # ring in place (a shrink drops the oldest spans — those
            # ARE evictions, the collector must be able to say so)
            dropped = max(0, len(_recent) - cap)
            _recent = collections.deque(_recent, maxlen=cap)
            if dropped:
                _SPANS_EVICTED_TOTAL.inc(dropped)
        if len(_recent) == _recent.maxlen:
            _SPANS_EVICTED_TOTAL.inc()
        _recent.append(record)
        sinks = list(_sinks)
    for fn in sinks:
        try:
            fn(record)
        except Exception:  # noqa: BLE001 — a sink must never break spans
            log.exception("span sink %r failed; removing it", fn)
            remove_sink(fn)
    if os.environ.get("PIO_TRACE_LOG"):
        _write_log_line(json.dumps(record, sort_keys=True))


def recent_spans(n: Optional[int] = None,
                 trace_id: Optional[str] = None) -> List[Dict[str, Any]]:
    """The last ``n`` span records (optionally one trace's), oldest
    first — the in-process view tests and `pio`-side tooling read."""
    with _emit_lock:
        records = list(_recent)
    if trace_id is not None:
        records = [r for r in records if r["trace"] == trace_id]
    return records if n is None else records[-n:]


def clear_recent() -> None:
    with _emit_lock:
        _recent.clear()


@contextlib.contextmanager
def new_trace():
    """Activate a FRESH trace for the scope of a background job (a
    stream fold cycle, a replay run): its spans and the trace headers
    its outbound calls attach (:func:`traced_headers`) all correlate
    under one minted id, so ``pio trace`` can follow the job across
    the fleet. Yields the trace id."""
    token = activate(new_trace_id())
    try:
        yield current_trace_id()
    finally:
        deactivate(token)


def evicted_total() -> int:
    """Spans this process's ring has evicted so far (the collector
    quotes it when it reports a trace as partial)."""
    return int(_SPANS_EVICTED_TOTAL.value)


def traced_headers(headers: Optional[Dict[str, str]] = None
                   ) -> Dict[str, str]:
    """A copy of ``headers`` carrying the active trace context: the
    trace id (``X-PIO-Trace-Id``) and, when a span is open, its id as
    the ``X-PIO-Parent-Span`` the receiving server parents its edge
    span to. No active trace -> the headers pass through untouched
    (background probes and daemons stay silent) — so every intra-fleet
    call site can attach propagation unconditionally (graftlint JT17
    audits that they do)."""
    out = dict(headers or {})
    ctx = _ctx.get()
    if ctx is not None:
        out[TRACE_HEADER] = ctx.trace_id
        if ctx.span_id:
            out[PARENT_HEADER] = ctx.span_id
    return out


#: accounted names inside which a worker thread is MEANT to be off the CPU:
#: nothing queued, or the device at work. Every other accounted name is host
#: work, and off the CPU there is contention (benchmarks/WORKER_PHASES.md)
WAIT_PHASES = frozenset({"batch.idle", "index.fetch", "seq.wait"})

#: the accounted time a thread spent between its outermost spans
UNSPANNED = "unspanned"

#: a read of the thread's CPU clock (``time.thread_time_ns``) that costs more
#: than this is not worth making four times a span
CPU_READ_LIMIT_NS = 1000

_thread = threading.local()


def _cpu_clock_is_cheap() -> bool:
    """Whether this host answers ``time.thread_time_ns`` within the limit
    (the least of five reads: a vDSO or a plain syscall does, a sandbox's
    emulated kernel does not)."""
    def read_ns() -> int:
        t0 = time.perf_counter_ns()
        time.thread_time_ns()
        return time.perf_counter_ns() - t0

    return min(read_ns() for _ in range(5)) <= CPU_READ_LIMIT_NS


class ThreadAccount:
    """One thread's spans on two clocks (module docstring). Only its own
    thread writes; ``snapshot`` and ``innermost`` may be read from any."""

    __slots__ = ("totals", "busy", "cpu_clock", "_lock", "_open", "_wall",
                 "_cpu")

    def __init__(self):
        #: the thread's CPU clock; None where it is too dear to read (the
        #: CPU figures are then None too)
        self.cpu_clock = (time.thread_time_ns if _cpu_clock_is_cheap()
                          else None)
        #: name -> [count, self wall ns, self CPU ns] of the spans closed
        self.totals: Dict[str, list] = {}
        #: the open spans, outermost first: [name, wall at entry, CPU at
        #: entry, wall its closed children covered, CPU they covered]
        self._open: List[list] = []
        # the books are written under it, a few hundred ns a span, so that
        # a snapshot from another thread finds them whole
        self._lock = threading.Lock()
        #: inside ``enter`` / ``exit``: a collection that starts there (they
        #: allocate) is annotated but not accounted, it would find the lock
        #: taken
        self.busy = False
        # where the last outermost span ended
        self._wall, self._cpu = self._now()

    def _now(self):
        return (time.perf_counter_ns(),
                self.cpu_clock() if self.cpu_clock is not None else 0)

    def _add(self, name: str, wall: int, cpu: int) -> None:
        total = self.totals.get(name)
        if total is None:
            total = self.totals[name] = [0, 0, 0]
        total[0] += 1
        total[1] += wall
        total[2] += cpu

    def enter(self, name: str) -> None:
        self.busy = True
        with self._lock:
            wall, cpu = self._now()
            if not self._open:
                self._add(UNSPANNED, wall - self._wall, cpu - self._cpu)
            self._open.append([name, wall, cpu, 0, 0])
        self.busy = False

    def exit(self) -> None:
        self.busy = True
        with self._lock:
            wall, cpu = self._now()
            name, wall0, cpu0, child_wall, child_cpu = self._open.pop()
            wall_all, cpu_all = wall - wall0, cpu - cpu0
            self._add(name, wall_all - child_wall, cpu_all - child_cpu)
            if self._open:
                self._open[-1][3] += wall_all
                self._open[-1][4] += cpu_all
            else:
                self._wall, self._cpu = wall, cpu
        self.busy = False

    def snapshot(self) -> Dict[str, list]:
        """``{name: [count, self wall ns, self CPU ns]}``: the spans closed
        so far (the counts are theirs) and, in wall time, what the spans
        still open have had of their own: two snapshots differ by the
        thread's time between them (another thread's CPU clock is not read:
        a span's CPU is booked as it closes; None where this host's CPU
        clock is too dear to read)."""
        # asked by the account's own thread, a collection that starts in
        # here must not come for the lock again
        own = thread_account() is self
        self.busy = self.busy or own
        with self._lock:
            out = {name: list(t) for name, t in self.totals.items()}
            until = time.perf_counter_ns()
            for name, wall0, _, child_wall, _ in reversed(self._open):
                out.setdefault(name, [0, 0, 0])[1] += (
                    until - wall0 - child_wall)
                until = wall0
            if not self._open:
                out.setdefault(UNSPANNED, [0, 0, 0])[1] += until - self._wall
        if own:
            self.busy = False
        if self.cpu_clock is None:
            for total in out.values():
                total[2] = None
        return out

    def innermost(self) -> Optional[str]:
        """The name of the innermost span open now, if any."""
        try:
            return self._open[-1][0]
        except IndexError:
            return None


def account_thread() -> ThreadAccount:
    """From now on every span THIS thread opens is accounted; the account
    is the thread's one, however often it asks."""
    account = getattr(_thread, "account", None)
    if account is None:
        account = _thread.account = ThreadAccount()
    return account


def thread_account() -> Optional[ThreadAccount]:
    """This thread's account, None where it never asked for one."""
    return getattr(_thread, "account", None)


class _Accounted:
    """An annotation that also enters and leaves the thread's account."""

    __slots__ = ("_account", "_name", "_annotation")

    def __init__(self, account, name, annotation):
        self._account, self._name = account, name
        self._annotation = annotation

    def __enter__(self):
        self._annotation.__enter__()
        self._account.enter(self._name)

    def __exit__(self, *exc):
        self._account.exit()
        return self._annotation.__exit__(*exc)


#: every annotation this module writes into a profiler trace starts so
DEVICE_SPAN_PREFIX = "pio:"

_NO_SPAN = contextlib.nullcontext()
#: ``jax.profiler.TraceAnnotation``, once this process is seen to hold JAX
_annotation = None


def device_span(name: str, **attrs: Any):
    """``with device_span("index.fetch"):`` — the block as a
    ``pio:index.fetch`` span of a profiler capture, on the device
    trace's clock (module docstring). Attributes are scalars; the
    active request's trace id rides along as ``trace``, which is how
    the spans of one request are told from another's. On a thread that
    asked (:func:`account_thread`) the block is accounted too."""
    account = getattr(_thread, "account", None)
    if account is not None and not account.busy:
        return _Accounted(account, name, _annotate(name, attrs))
    return _annotate(name, attrs)


def _annotate(name: str, attrs: Dict[str, Any]):
    global _annotation
    if _annotation is None:
        if "jax" not in sys.modules:
            return _NO_SPAN
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    ctx = _ctx.get()
    if ctx is not None:
        attrs["trace"] = ctx.trace_id
    return _annotation(DEVICE_SPAN_PREFIX + name, **attrs)


def _gc_span(phase: str, info: Dict[str, Any]) -> None:
    """``gc.callbacks``: a FULL collection as a ``pio:gc`` span on the thread
    that collects (both calls of one collection come on that thread). The
    young generations' collections are many and short, and a span round each
    cost ``slates-c8`` 2% of its rate (PERF.md §6, PR 37)."""
    if info.get("generation") != 2:
        return
    if phase == "start":
        _thread.gc = device_span("gc", generation=2)
        _thread.gc.__enter__()
    else:
        opened = _thread.__dict__.pop("gc", None)
        if opened is not None:
            opened.__exit__(None, None, None)


def span_collections() -> None:
    """From now on every full garbage collection of this process (the
    oldest generation's: the one that walks the id maps) is a ``pio:gc``
    span, accounted where its thread is."""
    import gc

    if _gc_span not in gc.callbacks:
        gc.callbacks.append(_gc_span)


@contextlib.contextmanager
def span(name: str, device: Optional[str] = None, **attrs: Any):
    """Record one unit of work under the active trace.

    No active trace -> nothing is recorded, so library code can span
    unconditionally. Attributes must be JSON-serializable scalars; the
    span record is emitted on exit even when the body raises (the error
    is noted, then propagates). With or without a trace the block is
    also a :func:`device_span`, named ``device`` where the boundary has
    another name on the device trace than in the record (its scalar
    attributes ride along)."""
    annotation = device_span(device or name, **{
        k: v for k, v in attrs.items()
        if isinstance(v, (str, int, float, bool))})
    parent = _ctx.get()
    if parent is None:
        with annotation:
            yield None
        return
    span_id = _new_span_id()
    token = _ctx.set(SpanContext(trace_id=parent.trace_id, span_id=span_id))
    start_unix = time.time()
    t0 = time.perf_counter()
    error: Optional[str] = None
    annotation.__enter__()
    try:
        yield span_id
    except BaseException as e:
        error = f"{type(e).__name__}: {e}"
        raise
    finally:
        annotation.__exit__(None, None, None)
        _ctx.reset(token)
        record: Dict[str, Any] = {
            "trace": parent.trace_id,
            "span": span_id,
            "parent": parent.span_id,
            "name": name,
            "start_unix": round(start_unix, 6),
            "duration_ms": round((time.perf_counter() - t0) * 1e3, 3),
        }
        if error is not None:
            record["error"] = error
        if attrs:
            record.update(attrs)
        _emit(record)
