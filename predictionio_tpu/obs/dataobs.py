"""Data & ingest observability: streaming sketches over the event stream.

Seventeen PRs of observability watch the SERVING side — latency,
memory, quality, the fleet — but the event stream every model is
trained and folded from was a blind spot between the event server's
201 and ``pio_model_staleness_seconds``. The reference ran a whole
event-store tier under the server (PAPER.md §0, HBase) and the Spark
literature this tree's roadmap leans on names input skew as the
dominant straggler cause; ROADMAP item C's entity-hash partitioning
needs that skew MEASURED before it can be planned, and item B's
per-app tenancy needs per-(app, event) accounting.

This module is the one source of truth for online event-stream
statistics, maintained with BOUNDED streaming sketches — no per-entity
dict anywhere (graftlint JT23 exists because that is the failure mode
this module replaces):

  - per-(app, event-name) rates: a bounded counter table with an
    ``(other)`` overflow row (the contprof endpoint-cap discipline)
    feeding ``pio_data_events_total{app,event}`` and the ``data.eps``
    timeline series
  - heavy hitters over entity ids: a count-min sketch (point
    estimates) + a space-saving top-k table, with a Zipf skew fitted
    over the top-k log-log curve (``pio_data_entity_skew`` — the input
    to item C's partition planning)
  - cardinality per entity field: HyperLogLog (±~2.3% at p=11)
  - fixed-budget quantile sketches over event values, payload bytes
    and ingest inter-arrival
  - a per-event-name schema profile (field set + inferred types),
    FROZEN at each COMPLETED train instance (workflow/train.py) and
    diffed live: a new/vanished/retyped field is a ``schema_change``
    journal event; a skew or unknown-entity breach is ``data_breach``
  - the serving-side coverage gauge ``pio_query_unknown_entity_ratio``:
    the fraction of query entity references unseen by the served model
    ("is the model stale for the traffic we actually get")

The bulk lanes are OBSERVED ASYNCHRONOUSLY: ``observe_batch`` /
``observe_columnar`` / ``observe_tail`` only stamp a timestamp and
enqueue references into a bounded queue (the journal-writer
discipline); a daemon worker does the sketching off the hot path, so
the zero-copy ingest lane pays an append, not a hash pass. The
single-event 201 lane sketches inline (one event is cheap, and the
schema diff should fire on the request that caused it). Tests call
:meth:`DataObs.flush` as the barrier.

Observation seams (who counts what — exactly once per accepted event):

  - the event server's 201 lane calls :meth:`DataObs.observe_event`
    (full fidelity: count, entities, sampled schema, payload bytes)
  - bulk storage lanes call :meth:`DataObs.observe_batch` /
    :meth:`DataObs.observe_columnar` (eventlog row/JSON/columnar,
    sqlite batch, the base-class Python loop); the eventlog's single
    ``insert`` delegates to its batch lane with observation OFF so the
    server's 201-lane observation stays the only count
  - single-row DAO writes below the server are NOT observed — every
    server lane and every bulk lane is
  - the streaming delta tail (workflow/stream.py) feeds entity/name
    sketches via :meth:`DataObs.observe_tail` without touching the
    ingest counters (in a combined process the insert lane already
    counted those rows)

Config (env, read per call so tests can monkeypatch):
  PIO_DATAOBS_DISABLE           1 disables every observe hook
  PIO_DATAOBS_TOPK              space-saving capacity (default 128)
  PIO_DATAOBS_CM_WIDTH          count-min width, power of 2 (1024)
  PIO_DATAOBS_CM_DEPTH          count-min depth (4)
  PIO_DATAOBS_HLL_P             HyperLogLog precision bits (11)
  PIO_DATAOBS_QUANTILE_BINS     quantile-sketch centroid budget (256)
  PIO_DATAOBS_MAX_RATE_ROWS     (app, event) rate rows before (other)
                                overflow (default 256)
  PIO_DATAOBS_MAX_SCHEMAS       event names profiled (default 64)
  PIO_DATAOBS_MAX_FIELDS        fields per profile (default 64)
  PIO_DATAOBS_SCHEMA_SAMPLE     profile every Nth event per name (8)
  PIO_DATAOBS_VANISH_AFTER      sampled events without a frozen field
                                before it counts as vanished (default 32)
  PIO_DATAOBS_RATE_WINDOW_SEC   eps window (default 30)
  PIO_DATAOBS_QUERY_WINDOW      query refs in the unknown-ratio window
                                (default 1024)
  PIO_DATAOBS_QUEUE             queued bulk batches before drops (512)
  PIO_DATAOBS_SKEW_BREACH       Zipf-skew data_breach threshold (2.0)
  PIO_DATAOBS_UNKNOWN_BREACH    unknown-ratio data_breach threshold (0.5)
  PIO_DATAOBS_BREACH_INTERVAL_SEC  breach re-check throttle (5)
"""

from __future__ import annotations

import collections
import logging
import math
import threading
import time
from typing import Any, Dict, Iterable, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from predictionio_tpu.obs import metrics

log = logging.getLogger(__name__)

_EVENTS_TOTAL = metrics.counter(
    "pio_data_events_total",
    "Events observed by the data plane, by app and event name "
    "(bounded rows; overflow lands on the '(other)' row)",
    ("app", "event"),
)

_TAIL_EVENTS_TOTAL = metrics.counter(
    "pio_data_tail_events_total",
    "Delta-tail rows observed by the data plane (entity/name sketches "
    "only — the insert lane already counted these events)",
)

_BYTES_TOTAL = metrics.counter(
    "pio_data_ingest_bytes_total",
    "Ingest payload bytes observed by the data plane",
)

_SKEW = metrics.gauge(
    "pio_data_entity_skew",
    "Fitted Zipf skew over the entity-id heavy-hitter table "
    "(log-count vs log-rank slope, negated; higher = hotter keys)",
)

_CARDINALITY = metrics.gauge(
    "pio_data_entity_cardinality",
    "HyperLogLog distinct-count estimate per entity field",
    ("field",),
)

_SCHEMA_CHANGES = metrics.counter(
    "pio_data_schema_changes_total",
    "Live schema drifts vs the profile frozen at the last COMPLETED "
    "train instance, by change kind",
    ("change",),
)

_BREACHES = metrics.counter(
    "pio_data_breaches_total",
    "data_breach journal events emitted, by kind",
    ("kind",),
)

_QUEUE_DROPPED = metrics.counter(
    "pio_data_batches_dropped_total",
    "Bulk observation batches dropped because the dataobs worker "
    "queue was full (the sketches under-count, ingest never blocks)",
)

_UNKNOWN_RATIO = metrics.gauge(
    "pio_query_unknown_entity_ratio",
    "Fraction of query entity references unseen by the served model "
    "(windowed; the model-stale-for-this-traffic signal)",
)

#: the two entity fields every lane carries; a FIXED key set, so the
#: per-field HLL map is bounded by construction
ENTITY_FIELDS = ("entityId", "targetEntityId")

#: odd multipliers for multiply-shift row hashing (count-min depth
#: rows derive their indexes from ONE 64-bit key hash)
_ROW_SALTS = (
    0x9E3779B97F4A7C15, 0xC2B2AE3D27D4EB4F,
    0x165667B19E3779F9, 0xD6E8FEB86659FD93,
    0xA0761D6478BD642F, 0xE7037ED1A0B428DB,
    0x8EBC6AF09C88C6E3, 0x589965CC75374CC3,
)


def _hash_u64(items: Iterable[Any]) -> np.ndarray:
    """One 64-bit hash per item (Python's siphash, reinterpreted
    unsigned) — the single per-item Python-level cost the hot lane
    pays; everything downstream is vectorized numpy."""
    return np.fromiter((hash(x) for x in items), np.int64).astype(np.uint64)


class CountMinSketch:
    """Fixed (depth x width) counter table; point estimate = min over
    rows. Width must be a power of two (multiply-shift indexing)."""

    def __init__(self, width: int = 1024, depth: int = 4):
        if width & (width - 1):
            raise ValueError("count-min width must be a power of 2")
        self.width = int(width)
        self.depth = max(1, min(int(depth), len(_ROW_SALTS)))
        self._shift = np.uint64(64 - int(math.log2(self.width)))
        self._table = np.zeros((self.depth, self.width), np.int64)
        self.total = 0

    def _indexes(self, hashes: np.ndarray) -> np.ndarray:
        rows = np.empty((self.depth, hashes.size), np.int64)
        for i in range(self.depth):
            mixed = hashes * np.uint64(_ROW_SALTS[i])
            rows[i] = (mixed >> self._shift).astype(np.int64)
        return rows

    def update(self, hashes: np.ndarray, counts: np.ndarray) -> None:
        if hashes.size == 0:
            return
        idx = self._indexes(hashes)
        for i in range(self.depth):
            np.add.at(self._table[i], idx[i], counts)
        self.total += int(counts.sum())

    def estimate(self, key: Any) -> int:
        h = np.array([hash(key)], np.int64).astype(np.uint64)
        idx = self._indexes(h)
        return int(min(self._table[i, idx[i, 0]] for i in range(self.depth)))


class SpaceSaving:
    """Bounded heavy-hitter table (batch Misra-Gries / space-saving):
    at most ``capacity`` tracked keys; when an update round overflows,
    the table is compacted back to the top ``capacity`` keys and the
    admission floor rises to the largest evicted count — an admitted
    key's count overestimates by at most its recorded ``err``."""

    def __init__(self, capacity: int = 128):
        self.capacity = max(8, int(capacity))
        self._counts: Dict[Any, int] = {}
        self._err: Dict[Any, int] = {}
        self._floor = 0

    def offer_counts(self, batch: Mapping[Any, int]) -> None:
        counts = self._counts
        err = self._err
        floor = self._floor
        for key, c in batch.items():
            if key in counts:
                counts[key] += c
            else:
                counts[key] = floor + c
                err[key] = floor
        if len(counts) > self.capacity:
            # compact: keep the top-capacity keys; the floor becomes the
            # largest evicted count (space-saving's replaced-min value).
            # argpartition, not a sort — compaction runs once per
            # update round on the ingest hot lane
            keys = list(counts.keys())
            vals = np.fromiter(counts.values(), np.int64, count=len(keys))
            split = vals.size - self.capacity
            part = np.argpartition(vals, split - 1)
            self._floor = int(vals[part[split - 1]])
            kept = part[split:]
            self._counts = {keys[i]: int(vals[i]) for i in kept}
            self._err = {keys[i]: err.get(keys[i], 0) for i in kept}

    def top(self, n: int = 20) -> List[Tuple[Any, int, int]]:
        ranked = sorted(self._counts.items(), key=lambda kv: kv[1],
                        reverse=True)
        return [(k, c, self._err.get(k, 0)) for k, c in ranked[:n]]

    def __len__(self) -> int:
        return len(self._counts)


class HyperLogLog:
    """Classic HLL over 64-bit hashes: 2**p one-byte registers."""

    def __init__(self, p: int = 11):
        self.p = max(4, min(int(p), 18))
        self.m = 1 << self.p
        self._registers = np.zeros(self.m, np.uint8)

    def add_hashes(self, hashes: np.ndarray) -> None:
        if hashes.size == 0:
            return
        idx = (hashes >> np.uint64(64 - self.p)).astype(np.int64)
        rest_bits = 64 - self.p
        w = (hashes & np.uint64((1 << rest_bits) - 1)).astype(np.float64)
        # rank = leading zeros of the rest_bits-wide field + 1:
        # frexp's exponent e satisfies w in [2^(e-1), 2^e), so
        # floor(log2 w) = e - 1 and rank = rest_bits - (e - 1)
        _, e = np.frexp(w)
        rank = np.where(w > 0, rest_bits - (e - 1),
                        rest_bits + 1).astype(np.uint8)
        np.maximum.at(self._registers, idx, rank)

    def estimate(self) -> float:
        m = float(self.m)
        alpha = 0.7213 / (1.0 + 1.079 / m)
        regs = self._registers.astype(np.float64)
        raw = alpha * m * m / np.sum(np.exp2(-regs))
        zeros = int(np.count_nonzero(self._registers == 0))
        if raw <= 2.5 * m and zeros:
            return m * math.log(m / zeros)  # linear-counting range
        return float(raw)


class QuantileSketch:
    """Fixed-budget streaming quantiles: a sorted centroid array
    (value, weight) re-binned equi-depth whenever it outgrows the
    budget; queries interpolate the cumulative-weight curve with exact
    min/max pinning the tails."""

    def __init__(self, budget: int = 256):
        self.budget = max(16, int(budget))
        self._vals = np.empty(0, np.float64)
        self._cnts = np.empty(0, np.float64)
        self.n = 0
        self.vmin = math.inf
        self.vmax = -math.inf

    def update(self, values: np.ndarray,
               weights: Optional[np.ndarray] = None) -> None:
        values = np.asarray(values, np.float64).ravel()
        if weights is None:
            weights = np.ones(values.size, np.float64)
        else:
            weights = np.asarray(weights, np.float64).ravel()
        finite = np.isfinite(values)
        values, weights = values[finite], weights[finite]
        if values.size == 0:
            return
        self.vmin = min(self.vmin, float(values.min()))
        self.vmax = max(self.vmax, float(values.max()))
        self.n += int(weights.sum())
        v = np.concatenate([self._vals, values])
        c = np.concatenate([self._cnts, weights])
        order = np.argsort(v, kind="stable")
        v, c = v[order], c[order]
        if v.size > self.budget:
            cum = np.cumsum(c)
            total = cum[-1]
            edges = total * np.arange(1, self.budget + 1) / self.budget
            ends = np.searchsorted(cum, edges, side="left")
            ends = np.minimum(ends, v.size - 1)
            starts = np.unique(np.concatenate([[0], ends[:-1] + 1]))
            starts = starts[starts < v.size]
            wsum = np.add.reduceat(c, starts)
            vsum = np.add.reduceat(v * c, starts)
            keep = wsum > 0
            v = vsum[keep] / wsum[keep]
            c = wsum[keep]
        self._vals, self._cnts = v, c

    def add(self, value: float, count: float = 1.0) -> None:
        self.update(np.array([value]), np.array([float(count)]))

    def quantile(self, q: float) -> float:
        if self._vals.size == 0:
            return 0.0
        if q <= 0.0:
            return self.vmin
        if q >= 1.0:
            return self.vmax
        cum = np.cumsum(self._cnts)
        total = cum[-1]
        rank = q * total
        # midpoint cumulative positions of each centroid
        mids = cum - self._cnts / 2.0
        i = int(np.searchsorted(mids, rank))
        if i <= 0:
            lo_v, lo_m = self.vmin, 0.0
            hi_v, hi_m = float(self._vals[0]), float(mids[0])
        elif i >= self._vals.size:
            lo_v, lo_m = float(self._vals[-1]), float(mids[-1])
            hi_v, hi_m = self.vmax, float(total)
        else:
            lo_v, lo_m = float(self._vals[i - 1]), float(mids[i - 1])
            hi_v, hi_m = float(self._vals[i]), float(mids[i])
        span = hi_m - lo_m
        frac = (rank - lo_m) / span if span > 0 else 1.0
        return lo_v + (hi_v - lo_v) * min(1.0, max(0.0, frac))

    def summary(self) -> Dict[str, float]:
        if self.n == 0:
            return {"n": 0}
        return {
            "n": int(self.n),
            "min": round(self.vmin, 6),
            "p50": round(self.quantile(0.50), 6),
            "p90": round(self.quantile(0.90), 6),
            "p99": round(self.quantile(0.99), 6),
            "max": round(self.vmax, 6),
        }


_TYPE_NAMES = {bool: "bool", int: "int", float: "float", str: "str",
               list: "list", dict: "dict", type(None): "null"}


def _infer_type(value: Any) -> str:
    return _TYPE_NAMES.get(type(value), type(value).__name__)


class DataObs:
    """Process-global event-stream statistics; all state bounded by
    fixed budgets (the sketches above plus capped tables with explicit
    overflow), served by ``GET /admin/data`` and merged fleet-wide by
    obs/collect.federate_data."""

    def __init__(self):
        self._lock = threading.Lock()
        # worker side (the journal-writer discipline): the bulk lanes
        # enqueue under _q_cond and never touch the sketches; a lazy
        # daemon thread drains into the _locked methods
        self._q_lock = threading.Lock()
        self._q_cond = threading.Condition(self._q_lock)
        self._q: "collections.deque[tuple]" = collections.deque()
        self._worker: Optional[threading.Thread] = None
        self._pending = 0  # queued + in-flight batches (flush barrier)
        self._reset_locked()

    # -- lifecycle ----------------------------------------------------------
    def _reset_locked(self) -> None:
        env_i = metrics.env_int
        self._cms = CountMinSketch(env_i("PIO_DATAOBS_CM_WIDTH", 1024),
                                   env_i("PIO_DATAOBS_CM_DEPTH", 4))
        self._hot = SpaceSaving(env_i("PIO_DATAOBS_TOPK", 128))
        p = env_i("PIO_DATAOBS_HLL_P", 11)
        self._hll = {field: HyperLogLog(p) for field in ENTITY_FIELDS}
        bins = env_i("PIO_DATAOBS_QUANTILE_BINS", 256)
        self._value_q = QuantileSketch(bins)
        self._bytes_q = QuantileSketch(bins)
        self._gap_q = QuantileSketch(bins)  # inter-arrival, ms
        self._rates: Dict[Tuple[str, str], int] = {}
        self._events_total = 0
        self._tail_total = 0
        self._bytes_total = 0
        self._rate_ring: collections.deque = collections.deque(maxlen=512)
        self._last_rate_push_mono = 0.0
        self._last_observe_mono = 0.0
        # per-event-name live schema profiles:
        # name -> {"samples": int, "fields": {field: [type, last_seen]}}
        self._schemas: Dict[str, Dict[str, Any]] = {}
        self._frozen: Dict[str, Dict[str, str]] = {}
        self._frozen_at: Optional[float] = None
        self._frozen_instance: Optional[str] = None
        self._changes: collections.deque = collections.deque(maxlen=128)
        self._changes_seen: set = set()
        self._changes_total = 0
        # unknown-entity coverage window: (refs, unknown) pairs, and the
        # window's two sums kept beside it (integers: no drift), so that a
        # query costs the same under the lock whether the window is full
        self._queries: collections.deque = collections.deque(
            maxlen=max(16, metrics.env_int("PIO_DATAOBS_QUERY_WINDOW",
                                           1024)))
        self._query_refs = 0
        self._query_unknown = 0
        self._breach_active: Dict[str, bool] = {}
        self._last_breach_check = 0.0

    def reset(self) -> None:
        """Drop every sketch and re-read the budget knobs (tests; a
        restarted server's fresh stats)."""
        self.flush(timeout=1.0)
        with self._q_cond:
            self._pending -= len(self._q)
            self._q.clear()
            self._q_cond.notify_all()
        with self._lock:
            self._reset_locked()
        _SKEW.set(0.0)
        _UNKNOWN_RATIO.set(0.0)
        for field in ENTITY_FIELDS:
            _CARDINALITY.labels(field).set(0.0)

    @staticmethod
    def enabled() -> bool:
        return metrics.env_int("PIO_DATAOBS_DISABLE", 0) == 0

    # -- ingest seams -------------------------------------------------------
    def observe_event(self, app_id: Any, event: Any,
                      payload_bytes: Optional[int] = None) -> None:
        """The event server's 201 lane: one accepted Event with its
        decoded properties — full fidelity (count, entities, sampled
        schema, payload bytes)."""
        if not self.enabled():
            return
        name = event.event
        ids = [event.entity_id]
        targets = [event.target_entity_id] if event.target_entity_id else []
        with self._lock:
            self._count_locked(app_id, {name: 1}, 1, time.time(),
                               time.monotonic())
            self._entities_locked(ids, targets)
            self._schema_locked(name, event.properties)
            if event.properties:
                vals = [v for v in event.properties.values()
                        if isinstance(v, (int, float))
                        and not isinstance(v, bool)]
                if vals:
                    self._value_q.update(np.asarray(vals, np.float64))
            if payload_bytes:
                self._bytes_total += int(payload_bytes)
                _BYTES_TOTAL.inc(payload_bytes)
                self._bytes_q.add(float(payload_bytes))
        self._maybe_check_breach()

    def observe_batch(self, app_id: Any,
                      names: Sequence[Any],
                      entity_ids: Optional[Sequence[Any]] = None,
                      target_ids: Optional[Sequence[Any]] = None,
                      payload_lens: Optional[np.ndarray] = None,
                      events: Optional[Sequence[Any]] = None) -> None:
        """A bulk storage lane: per-field sequences as the lane already
        holds them (str or encoded bytes — no re-encoding). ``events``
        (when the lane has Python Event objects anyway) feeds the
        sampled schema profile and value sketch."""
        if not self.enabled() or not names:
            return
        # the hot lane pays ONE timestamp + deque append; the worker
        # thread does the hashing and sketching (el_append_rows
        # releases the GIL, so the overlap is real)
        self._enqueue(("batch", time.time(), time.monotonic(), app_id,
                       names, entity_ids, target_ids, payload_lens,
                       events))

    def _apply_batch(self, now: float, mono: float, app_id: Any,
                     names: Sequence[Any],
                     entity_ids: Optional[Sequence[Any]],
                     target_ids: Optional[Sequence[Any]],
                     payload_lens: Optional[np.ndarray],
                     events: Optional[Sequence[Any]]) -> None:
        name_counts = collections.Counter(names)
        with self._lock:
            self._count_locked(app_id, name_counts, len(names), now, mono)
            self._entities_locked(entity_ids, target_ids)
            if payload_lens is not None and len(payload_lens):
                lens = np.asarray(payload_lens, np.float64)
                total = int(lens.sum())
                self._bytes_total += total
                _BYTES_TOTAL.inc(total)
                self._bytes_q.update(lens)
            if events is not None:
                step = max(1, metrics.env_int("PIO_DATAOBS_SCHEMA_SAMPLE",
                                              8))
                vals: List[float] = []
                for e in events[::step]:
                    self._schema_locked(e.event, e.properties)
                    if e.properties:
                        vals.extend(
                            v for v in e.properties.values()
                            if isinstance(v, (int, float))
                            and not isinstance(v, bool))
                if vals:
                    self._value_q.update(np.asarray(vals, np.float64))

    def observe_events(self, app_id: Any, events: Sequence[Any]) -> None:
        """A bulk lane holding Python Event objects (sqlite batch, the
        base-class insert loop): extract the field sequences once and
        enqueue — these lanes are transaction-bound, so the listcomps
        are noise next to the commit."""
        if not self.enabled() or not events:
            return
        self._enqueue((
            "batch", time.time(), time.monotonic(), app_id,
            [e.event for e in events],
            [e.entity_id for e in events],
            [e.target_entity_id for e in events
             if e.target_entity_id is not None],
            None, events))

    def observe_columnar(self, app_id: Any, cols: Any) -> None:
        """A columnar bulk lane: counts via bincount over the code
        arrays — fully vectorized, uniques bounded by the vocab."""
        if not self.enabled():
            return
        n = len(cols.name_codes)
        if n == 0:
            return
        # bincount over the code arrays is vectorized-cheap; run it
        # inline (the caller may reuse its buffers) and enqueue the
        # small count dicts for the worker
        name_counts = self._columnar_counts(cols.name_codes, cols.names)
        ent_counts = self._columnar_counts(cols.entity_codes,
                                           cols.entity_vocab)
        tgt_counts = self._columnar_counts(
            getattr(cols, "target_codes", None),
            getattr(cols, "target_vocab", None))
        values = np.array(getattr(cols, "values", ()), np.float64,
                          copy=True).ravel()
        self._enqueue(("counts", time.time(), time.monotonic(), app_id,
                       name_counts, n, ent_counts, tgt_counts, values))

    def observe_tail(self, app_id: Any, cols: Any) -> None:
        """The streaming delta tail: entity/name sketches only — the
        insert lane already counted these events, so the tail must not
        inflate eps/events_total (it refreshes skew and cardinality in
        the SERVING process, where the inserts happened elsewhere)."""
        if not self.enabled():
            return
        n = len(cols.name_codes)
        if n == 0:
            return
        ent_counts = self._columnar_counts(cols.entity_codes,
                                           cols.entity_vocab)
        tgt_counts = self._columnar_counts(
            getattr(cols, "target_codes", None),
            getattr(cols, "target_vocab", None))
        self._enqueue(("tail", app_id, n, ent_counts, tgt_counts))

    # -- the worker (journal-writer discipline) -----------------------------
    def _enqueue(self, item: tuple) -> None:
        cap = max(8, metrics.env_int("PIO_DATAOBS_QUEUE", 512))
        with self._q_cond:
            if len(self._q) >= cap:
                # monitoring must never block or grow unboundedly:
                # under-count and say so
                _QUEUE_DROPPED.inc()
                return
            self._q.append(item)
            self._pending += 1
            self._ensure_worker_locked()
            self._q_cond.notify()

    def _ensure_worker_locked(self) -> None:
        if self._worker is not None and self._worker.is_alive():
            return
        self._worker = threading.Thread(
            target=self._drain_forever, daemon=True,
            name="pio-dataobs-worker")
        self._worker.start()

    def _drain_forever(self) -> None:
        while True:
            try:
                with self._q_cond:
                    while not self._q:
                        # timed wait: spurious-wakeup loop, stays
                        # parkable forever on an idle queue
                        self._q_cond.wait(1.0)
                    batch = list(self._q)
                    self._q.clear()
                for item in batch:
                    try:
                        self._apply(item)
                    except Exception:  # noqa: BLE001 — one malformed
                        # batch must cost its own stats, never the
                        # worker thread
                        log.exception("dataobs worker failed on a batch")
                with self._q_cond:
                    self._pending = max(0, self._pending - len(batch))
                    self._q_cond.notify_all()
                self._maybe_check_breach()
            except Exception:  # noqa: BLE001 — the worker dying
                # silently would stall flush() barriers and freeze the
                # sketches; log and keep draining
                log.exception("dataobs worker iteration failed")

    def _apply(self, item: tuple) -> None:
        kind = item[0]
        if kind == "batch":
            self._apply_batch(*item[1:])
        elif kind == "counts":
            _, now, mono, app_id, name_counts, n, ents, tgts, values = item
            with self._lock:
                self._count_locked(app_id, name_counts, n, now, mono)
                self._entity_counts_locked(ents, tgts)
                if values.size:
                    self._value_q.update(values)
        elif kind == "tail":
            _, app_id, n, ents, tgts = item
            _TAIL_EVENTS_TOTAL.inc(n)
            with self._lock:
                self._tail_total += n
                self._entity_counts_locked(ents, tgts)

    def flush(self, timeout: float = 5.0) -> bool:
        """Block until every queued bulk batch reached the sketches (or
        timeout) — the barrier tests and report() use; the observe
        paths themselves never wait."""
        deadline = time.monotonic() + timeout
        with self._q_cond:
            while self._pending > 0:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._q_cond.wait(timeout=remaining)
        return True

    # -- serving seam -------------------------------------------------------
    def note_query(self, refs: int, unknown: int) -> None:
        """One served query's entity references: how many the query
        named, how many the served model had never seen."""
        if not self.enabled() or refs <= 0:
            return
        refs, unknown = int(refs), int(unknown)
        with self._lock:
            window = self._queries
            if len(window) == window.maxlen:
                old_refs, old_unknown = window[0]  # the append drops it
                self._query_refs -= old_refs
                self._query_unknown -= old_unknown
            window.append((refs, unknown))
            self._query_refs += refs
            self._query_unknown += unknown
            ratio = self._unknown_ratio_locked()
        _UNKNOWN_RATIO.set(ratio)
        self._maybe_check_breach()

    def _unknown_ratio_locked(self) -> float:
        if not self._query_refs:
            return 0.0
        return self._query_unknown / float(self._query_refs)

    def unknown_ratio(self) -> float:
        with self._lock:
            return self._unknown_ratio_locked()

    # -- schema freeze ------------------------------------------------------
    def freeze_schemas(self, instance_id: Optional[str] = None) -> None:
        """Freeze the live profiles as the trained-against schema (the
        COMPLETED-train seam in workflow/train.py); subsequent drift is
        diffed against THIS snapshot."""
        with self._lock:
            self._frozen = {
                name: {f: meta[0]
                       for f, meta in prof["fields"].items()}
                for name, prof in self._schemas.items()
            }
            self._frozen_at = time.time()
            self._frozen_instance = instance_id
            self._changes_seen.clear()

    # -- internals ----------------------------------------------------------
    @staticmethod
    def _columnar_counts(codes: Any, vocab: Any) -> Dict[Any, int]:
        if codes is None or vocab is None:
            return {}
        codes = np.asarray(codes)
        if codes.size == 0:
            return {}
        counts = np.bincount(codes[codes >= 0])
        nz = np.nonzero(counts)[0]
        out: Dict[Any, int] = {}
        for code in nz:
            try:
                key = vocab[int(code)]
            except (IndexError, KeyError):
                continue
            out[key] = int(counts[code])
        return out

    def _count_locked(self, app_id: Any, name_counts: Mapping[Any, int],
                      n: int, now: float, mono: float) -> None:
        # timestamps are stamped at the OBSERVE seam (the enqueue), not
        # at worker-drain time, so eps and inter-arrival reflect ingest
        if self._last_observe_mono:
            self._gap_q.add((mono - self._last_observe_mono) * 1e3)
        self._last_observe_mono = mono
        self._events_total += n
        cap = max(8, metrics.env_int("PIO_DATAOBS_MAX_RATE_ROWS", 256))
        app = str(app_id)
        for raw, c in name_counts.items():
            name = (raw.decode("utf-8", "replace")
                    if isinstance(raw, (bytes, bytearray)) else str(raw))
            row = (app, name)
            if row not in self._rates and len(self._rates) >= cap:
                row = (app, "(other)")
            self._rates[row] = self._rates.get(row, 0) + int(c)
            _EVENTS_TOTAL.labels(row[0], row[1]).inc(c)
        if mono - self._last_rate_push_mono >= 0.25 or not self._rate_ring:
            self._rate_ring.append((now, self._events_total))
            self._last_rate_push_mono = mono

    def _entities_locked(self, entity_ids: Optional[Sequence[Any]],
                         target_ids: Optional[Sequence[Any]]) -> None:
        if entity_ids:
            counts = collections.Counter(entity_ids)
            keys = list(counts.keys())
            vals = np.fromiter(counts.values(), np.int64, count=len(counts))
            hashes = _hash_u64(keys)
            self._cms.update(hashes, vals)
            self._hll["entityId"].add_hashes(hashes)
            self._hot.offer_counts(counts)
        if target_ids:
            t_counts = collections.Counter(target_ids)
            # the row lane pads absent targets with empty strings
            for absent in (b"", "", None):
                t_counts.pop(absent, None)
            if t_counts:
                self._hll["targetEntityId"].add_hashes(
                    _hash_u64(t_counts.keys()))

    def _entity_counts_locked(self, ent_counts: Mapping[Any, int],
                              tgt_counts: Mapping[Any, int]) -> None:
        if ent_counts:
            keys = list(ent_counts.keys())
            vals = np.fromiter(ent_counts.values(), np.int64,
                               count=len(ent_counts))
            hashes = _hash_u64(keys)
            self._cms.update(hashes, vals)
            self._hll["entityId"].add_hashes(hashes)
            self._hot.offer_counts(ent_counts)
        if tgt_counts:
            self._hll["targetEntityId"].add_hashes(
                _hash_u64(tgt_counts.keys()))

    def _schema_locked(self, name: Any, properties: Optional[dict]) -> None:
        if isinstance(name, (bytes, bytearray)):
            name = name.decode("utf-8", "replace")
        else:
            name = str(name)
        max_schemas = max(1, metrics.env_int("PIO_DATAOBS_MAX_SCHEMAS", 64))
        prof = self._schemas.get(name)
        if prof is None:
            if len(self._schemas) >= max_schemas:
                return  # over budget: new names go unprofiled, counted only
            prof = self._schemas[name] = {"samples": 0, "fields": {}}
        prof["samples"] += 1
        samples = prof["samples"]
        fields = prof["fields"]
        props = properties or {}
        max_fields = max(1, metrics.env_int("PIO_DATAOBS_MAX_FIELDS", 64))
        frozen = self._frozen.get(name)
        for field, value in props.items():
            t = _infer_type(value)
            meta = fields.get(field)
            if meta is None:
                if len(fields) >= max_fields:
                    continue
                fields[field] = [t, samples]
                if frozen is not None and field not in frozen:
                    self._change_locked(name, field, "added", new_type=t)
            else:
                meta[1] = samples
                if meta[0] != t:
                    meta[0] = t
                if frozen is not None and field in frozen and (
                        frozen[field] != t):
                    self._change_locked(name, field, "retyped",
                                        old_type=frozen[field], new_type=t)
        if frozen is not None:
            vanish_after = max(1, metrics.env_int(
                "PIO_DATAOBS_VANISH_AFTER", 32))
            for field in frozen:
                if field in props:
                    continue
                meta = fields.get(field)
                last_seen = meta[1] if meta else 0
                if samples - last_seen >= vanish_after:
                    self._change_locked(name, field, "vanished",
                                        old_type=frozen[field])

    def _change_locked(self, name: str, field: str, change: str,
                       old_type: Optional[str] = None,
                       new_type: Optional[str] = None) -> None:
        key = (name, field, change, old_type, new_type)
        if key in self._changes_seen or len(self._changes_seen) >= 512:
            return
        self._changes_seen.add(key)
        self._changes_total += 1
        entry = {"ts": time.time(), "event": name, "field": field,
                 "change": change}
        if old_type:
            entry["old_type"] = old_type
        if new_type:
            entry["new_type"] = new_type
        self._changes.append(entry)
        _SCHEMA_CHANGES.labels(change).inc()
        from predictionio_tpu.obs import journal

        journal.emit("schema_change", event=name, field=field,
                     change=change, old_type=old_type, new_type=new_type)

    # -- derived stats ------------------------------------------------------
    def skew(self) -> float:
        """Zipf skew fitted over the heavy-hitter table: the negated
        slope of log(count) vs log(rank). 0.0 until at least 8 hitters
        are tracked."""
        with self._lock:
            top = self._hot.top(32)
        if len(top) < 8:
            return 0.0
        counts = np.array([max(1, c) for _, c, _ in top], np.float64)
        ranks = np.arange(1, counts.size + 1, dtype=np.float64)
        slope = np.polyfit(np.log(ranks), np.log(counts), 1)[0]
        return max(0.0, float(-slope))

    def eps(self, now: Optional[float] = None) -> float:
        """Events/sec over the rate window (ingest lanes only — the
        tail is excluded by construction)."""
        now = time.time() if now is None else now
        window = max(1.0, metrics.env_float("PIO_DATAOBS_RATE_WINDOW_SEC",
                                            30.0))
        with self._lock:
            ring = list(self._rate_ring)
            total = self._events_total
        if not ring:
            return 0.0
        cutoff = now - window
        base_ts, base_count = ring[0]
        for ts, count in ring:
            if ts >= cutoff:
                break
            base_ts, base_count = ts, count
        dt = now - base_ts
        if dt <= 0:
            return 0.0
        return max(0.0, (total - base_count) / dt)

    def cardinality(self) -> Dict[str, int]:
        with self._lock:
            return {field: int(round(h.estimate()))
                    for field, h in self._hll.items()}

    # -- breach sentinel ----------------------------------------------------
    def _maybe_check_breach(self) -> None:
        interval = metrics.env_float("PIO_DATAOBS_BREACH_INTERVAL_SEC", 5.0)
        mono = time.monotonic()
        with self._lock:
            if interval > 0 and mono - self._last_breach_check < interval:
                return
            self._last_breach_check = mono
        self.check_breaches()

    def check_breaches(self) -> List[str]:
        """Evaluate the breach thresholds now (also runs throttled from
        the observe seams). Emits ``data_breach`` journal events on the
        rising edge, with hysteresis at 80% of each threshold."""
        fired: List[str] = []
        skew = self.skew()
        _SKEW.set(skew)
        card = self.cardinality()
        for field, est in card.items():
            _CARDINALITY.labels(field).set(est)
        skew_thresh = metrics.env_float("PIO_DATAOBS_SKEW_BREACH", 2.0)
        with self._lock:
            top = self._hot.top(1)
            total = self._cms.total
        extra: Dict[str, Any] = {}
        if top and total:
            key, count, _ = top[0]
            if isinstance(key, (bytes, bytearray)):
                key = key.decode("utf-8", "replace")
            extra = {"top_entity": str(key),
                     "top_share": round(count / total, 4)}
        if self._edge("entity_skew", skew, skew_thresh,
                      skew=round(skew, 3), **extra):
            fired.append("entity_skew")
        ratio = self.unknown_ratio()
        _UNKNOWN_RATIO.set(ratio)
        unk_thresh = metrics.env_float("PIO_DATAOBS_UNKNOWN_BREACH", 0.5)
        if self._edge("unknown_entity", ratio, unk_thresh,
                      unknown_ratio=round(ratio, 4)):
            fired.append("unknown_entity")
        return fired

    def _edge(self, kind: str, value: float, threshold: float,
              **fields: Any) -> bool:
        if threshold <= 0:
            return False
        with self._lock:
            active = self._breach_active.get(kind, False)
            fire = value >= threshold and not active
            if fire:
                self._breach_active[kind] = True
            elif active and value < 0.8 * threshold:
                self._breach_active[kind] = False
        if fire:
            _BREACHES.labels(kind).inc()
            from predictionio_tpu.obs import journal

            # "breach", not "kind": the journal event's own kind is
            # data_breach
            journal.emit("data_breach", breach=kind, threshold=threshold,
                         **fields)
        return fire

    # -- the /admin/data payload -------------------------------------------
    def report(self, top_n: int = 20) -> Dict[str, Any]:
        self.flush(timeout=2.0)
        self.check_breaches()
        with self._lock:
            rates = sorted(
                ({"app": app, "event": name, "count": c}
                 for (app, name), c in self._rates.items()),
                key=lambda r: -r["count"])
            top = []
            for key, count, err in self._hot.top(top_n):
                if isinstance(key, (bytes, bytearray)):
                    key = key.decode("utf-8", "replace")
                top.append({"id": str(key), "count": count, "err": err})
            profiles = {
                name: {f: meta[0]
                       for f, meta in prof["fields"].items()}
                for name, prof in self._schemas.items()
            }
            changes = list(self._changes)
            out: Dict[str, Any] = {
                "events_total": self._events_total,
                "tail_events_total": self._tail_total,
                "bytes_total": self._bytes_total,
                "queries_seen": self._query_refs,
                "quantiles": {
                    "value": self._value_q.summary(),
                    "payload_bytes": self._bytes_q.summary(),
                    "interarrival_ms": self._gap_q.summary(),
                },
                "breach_active": {k: v for k, v in
                                  self._breach_active.items() if v},
            }
        out["eps"] = round(self.eps(), 3)
        out["rates"] = rates
        out["entities"] = {
            "skew": round(self.skew(), 4),
            "top": top,
            "cardinality": self.cardinality(),
        }
        out["unknown_ratio"] = round(self.unknown_ratio(), 4)
        out["schema"] = {
            "profiles": profiles,
            "frozen_at": self._frozen_at,
            "frozen_instance": self._frozen_instance,
            "changes": changes,
            "changes_total": self._changes_total,
        }
        return out


#: the process-global data plane every seam records into
DATAOBS = DataObs()


def timeline_points(now: float) -> Dict[str, float]:
    """The ``data.*`` timeline series (obs/timeline.py collector — the
    collectors-ASK-the-subsystem stance): recomputed at the sample
    instant, which also refreshes the gauges for plain /metrics
    scrapes."""
    skew = DATAOBS.skew()
    _SKEW.set(skew)
    ratio = DATAOBS.unknown_ratio()
    _UNKNOWN_RATIO.set(ratio)
    return {
        "data.eps": DATAOBS.eps(now),
        "data.skew": skew,
        "data.unknown_ratio": ratio,
    }
