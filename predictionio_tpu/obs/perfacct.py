"""Performance accounting: live MFU/roofline gauges, the data-path
ledger, and tail-latency attribution.

Three numbers a deployment watches — data-path seconds
(events->model), concurrent-tail p99, and two-tower MFU — kept
continuously, as live gauges (the benchmark under ``benchmarks/`` reads
the chip's own trace instead):

  MFU / roofline gauges
    Every instrumented trainer builds a :class:`StepAccountant`: the
    FLOP/byte cost of its compiled step comes from
    ``jax.stages.Compiled.cost_analysis()`` when the backend reports it
    (:func:`costs_from_compiled`), falling
    back to the analytic formulas this repo already trusts — the
    two-tower matmul count (:func:`twotower_matmul_flops`) and ALS's
    ``work_model``. Each observed step sets:

      pio_train_mfu{model=}           achieved FLOP/s over the chip peak
      pio_step_flops{model=}          FLOPs per step (cost basis)
      pio_step_bytes{model=}          HBM bytes per step (when known)
      pio_roofline_position{model=}   operational intensity / ridge
                                      point: > 1 compute-bound,
                                      < 1 memory-bound

  Data-path ledger (:data:`LEDGER`)
    Wall-time per stage of the events->model pipeline (read / prepare /
    bin / transfer / fit / train / bin-cache / compile), recorded by
    core/engine.py, workflow/train.py, ops/bincache.py and ops/als.py
    (the zero-copy lane splits its one native call into read=scan and
    bin=fill, and the transfer watcher times the H2D window) into a bounded
    per-run history plus ``pio_datapath_stage_seconds{stage=}``, and
    the freshness gauge ROADMAP item C will gate on:

      pio_model_staleness_seconds     seconds the oldest ingested event
                                      NOT yet reflected in the servable
                                      model has been waiting (0 when
                                      the model covers every ingest)

    Ingest seams (the event server, the bulk storage writers) call
    :func:`note_ingest`; a training read captures the horizon the model
    will cover (:func:`~DataPathLedger.note_train_read`); a completed
    publish moves the servable horizon forward
    (:func:`~DataPathLedger.note_publish`) — so the gauge grows while
    events wait and drops across a model publish.

  Tail-latency attribution (:func:`tail_report`)
    Aggregates the flight recorder's per-request stage timings into the
    question "for requests above p95, which stage (queue wait,
    dispatch, serialize, parse, unattributed) dominates — and how does
    that differ from the median request?". Served at ``GET
    /admin/tail`` on every server. Stage shares are never negative:
    obs/flight.py clamps the unattributed remainder at 0 (and counts
    the clamps in ``pio_flight_negative_remainder_total``).

Chip peaks live in ONE table keyed by jax's ``device_kind``
(:data:`DEVICE_PEAKS`). A TPU
whose kind has no row is an error, not a default, and on the CPU
backend no utilisation is computed or exported at all. jax is only
imported inside the cost-analysis and peak helpers — the module stays
importable by parents that must stay off jax and the pure-CPU servers.
"""

from __future__ import annotations

import collections
import dataclasses
import logging
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from predictionio_tpu.obs import flight, metrics

log = logging.getLogger(__name__)

@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    bf16_flops: float          # FLOP/s
    hbm_bytes_per_sec: float


#: per-chip peaks keyed by ``jax.devices()[0].device_kind`` — the one
#: copy in the package: every live gauge divides by these.
#: Source: Google Cloud documentation, "TPU v5e"
#: (cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 819 GB/s HBM.
DEVICE_PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(bf16_flops=197e12, hbm_bytes_per_sec=819e9),
}


def device_peaks() -> Optional[ChipPeaks]:
    """Peaks of this process's default device: None on the CPU backend
    (a utilisation against a chip that is not there means nothing);
    ``LookupError`` for an accelerator whose kind has no row."""
    import jax

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return None
    peaks = DEVICE_PEAKS.get(dev.device_kind)
    if peaks is None:
        raise LookupError(
            f"no peak FLOP/s / HBM row for device kind "
            f"{dev.device_kind!r} in obs/perfacct.py DEVICE_PEAKS "
            f"(known: {sorted(DEVICE_PEAKS)}) — add the chip's published "
            "peaks with their source before reporting a utilisation")
    return peaks


def mfu(flops: float, seconds: float) -> Optional[float]:
    """Model FLOPs utilization: achieved FLOP/s over the chip peak
    (the live ``pio_train_mfu`` gauge). None on the CPU backend."""
    peaks = device_peaks()
    if peaks is None:
        return None
    if seconds <= 0.0:
        return 0.0
    return flops / seconds / peaks.bf16_flops


def twotower_matmul_flops(batch: int, dim: int,
                          tail_widths: Sequence[int]) -> float:
    """Analytic matmul FLOPs per two-tower training step (fwd + bwd):
    the [B, B] logits einsum and its two rank-D backward products, plus
    the tail MLP matmuls. The optimizer's elementwise work
    deliberately does not count."""
    B, D = float(batch), float(dim)
    flops = 3 * 2.0 * B * B * D          # logits fwd + dL/du + dL/dv
    per_row = sum(2.0 * a * b
                  for a, b in zip(tail_widths[:-1], tail_widths[1:]))
    flops += 2 * 3 * per_row * B         # two towers, fwd+bwd(x2)
    return flops


def active_param_flops(tokens: float, dense_params: float,
                       expert_params: float, expert_picks: float) -> float:
    """Forward FLOPs of an expert model on the ACTIVE-parameter basis: two
    operations a parameter a token over the parameters every token passes
    (``dense_params``: attention, dense FFNs, router), and two a parameter
    for each (token, pick) that reached a computing expert
    (``expert_picks`` x ``expert_params`` of one expert). With zero-compute
    experts, or with only a share of the experts held here, the compute a
    token costs varies with where its picks went, so the basis takes the
    picks as COUNTED (``SeqStackModel.counters``), not ``top_k`` a token.
    Attention's own products are the caller's to add."""
    return 2.0 * (float(tokens) * float(dense_params)
                  + float(expert_picks) * float(expert_params))


# -- cost analysis of compiled steps ------------------------------------------

def costs_from_compiled(compiled: Any) -> Optional[Tuple[float, float]]:
    """(flops, bytes accessed) per execution from a
    ``jax.stages.Compiled``'s ``cost_analysis()``, or None when the
    backend reports nothing usable (no flop count in its cost model)
    — the caller then falls back to its analytic formula. Never
    raises: accounting must not change whether training runs."""
    try:
        analysis = compiled.cost_analysis()
    except Exception as e:  # noqa: BLE001 — backend-dependent surface
        log.debug("cost_analysis unavailable: %s", e)
        return None
    if not isinstance(analysis, dict):
        return None
    flops = float(analysis.get("flops") or 0.0)
    if flops <= 0.0:
        return None
    return flops, float(analysis.get("bytes accessed") or 0.0)


# -- gauges -------------------------------------------------------------------

_TRAIN_MFU = metrics.gauge(
    "pio_train_mfu",
    "Model FLOPs utilization of the last observed training step: "
    "achieved FLOP/s over the chip's bf16 peak (obs/perfacct.py "
    "DEVICE_PEAKS by device_kind; not exported on the CPU backend)",
    ("model",),
)
_STEP_FLOPS = metrics.gauge(
    "pio_step_flops",
    "FLOPs per training step (cost_analysis of the compiled step, or "
    "the analytic fallback formula)",
    ("model",),
)
_STEP_BYTES = metrics.gauge(
    "pio_step_bytes",
    "HBM bytes accessed per training step where the cost basis "
    "reports them (0 = unknown)",
    ("model",),
)
_ROOFLINE_POSITION = metrics.gauge(
    "pio_roofline_position",
    "Operational intensity of the step over the chip's ridge point "
    "(peak FLOPs / peak HBM bytes): > 1 compute-bound, < 1 "
    "memory-bound (only set when the byte cost is known)",
    ("model",),
)
_MODEL_STALENESS = metrics.gauge(
    "pio_model_staleness_seconds",
    "Seconds the oldest ingested event not yet reflected in the "
    "servable model has been waiting (0 when the model covers every "
    "ingested event)",
)
_DATAPATH_STAGE_SECONDS = metrics.gauge(
    "pio_datapath_stage_seconds",
    "Wall seconds the current/last training run spent per "
    "events->model pipeline stage (read / prepare / bin / transfer / "
    "fit / train / bin_cache_load / bin_cache_save / compile). The "
    "zero-copy lane reports read = the native scan share, bin = the "
    "native resolve+plan+fill share, transfer = the host->device wire "
    "window (put dispatch -> confirmed resident)",
    ("stage",),
)


class StepAccountant:
    """Per-model step cost + the gauge updates for each observed step.

    Built once per trainer (the cost basis is shape-stable across
    steps); ``observe(seconds, steps=n)`` after each device dispatch
    refreshes the MFU/roofline gauges from ``steps`` steps' worth of
    the basis over the measured wall time.
    """

    def __init__(self, model: str, flops_per_step: float,
                 bytes_per_step: float = 0.0, source: str = "analytic"):
        self.model = model
        self.flops_per_step = float(flops_per_step)
        self.bytes_per_step = float(bytes_per_step)
        self.source = source
        self.last_mfu: Optional[float] = None
        _STEP_FLOPS.labels(model).set(self.flops_per_step)
        _STEP_BYTES.labels(model).set(self.bytes_per_step)
        peaks = device_peaks()
        if peaks is not None and self.bytes_per_step > 0.0:
            intensity = self.flops_per_step / self.bytes_per_step
            ridge = peaks.bf16_flops / peaks.hbm_bytes_per_sec
            _ROOFLINE_POSITION.labels(model).set(intensity / ridge)

    @classmethod
    def from_compiled(cls, model: str, compiled: Any,
                      fallback_flops: float,
                      fallback_bytes: float = 0.0) -> "StepAccountant":
        """cost_analysis() basis when the backend reports one, the
        analytic fallback otherwise — the ISSUE's two-tier contract."""
        costs = costs_from_compiled(compiled) if compiled is not None else None
        if costs is not None:
            return cls(model, costs[0], costs[1], source="cost_analysis")
        return cls(model, fallback_flops, fallback_bytes, source="analytic")

    def observe(self, seconds: float, steps: int = 1) -> Optional[float]:
        """Record one timed dispatch covering ``steps`` steps; returns
        (and gauges) the resulting MFU — None, and no gauge, on the
        CPU backend."""
        self.last_mfu = mfu(self.flops_per_step * steps, seconds)
        if self.last_mfu is not None:
            _TRAIN_MFU.labels(self.model).set(self.last_mfu)
        return self.last_mfu


# -- data-path ledger ---------------------------------------------------------

#: completed/in-progress runs kept in the ledger snapshot
LEDGER_RUN_CAPACITY = 8


class DataPathLedger:
    """Stage wall-times per training run + the model-freshness clock.

    SCOPE: the clock is **per process**. It is exact wherever ingest
    and publish share a process (`pio train` after an
    import, single-process deployments, tier-1) and is the substrate
    the streaming path (ROADMAP item C) will build on; a split
    deployment (event server here, trainer there) sees only its own
    seams — item C moves the horizon into storage so every process
    reads the same clock. The gauge refreshes on every ingest/publish
    note AND on every timeline sample (the staleness collector calls
    :meth:`staleness_seconds`), so a scraped value is at most one
    sample interval stale while any server is being watched.

    Freshness bookkeeping (all wall-clock receipt times, not event
    times — the operator question is "how long are events waiting",
    not "how old is the data"):

      note_ingest      an event (batch) landed in the store
      note_train_read  a training read finished: the model being built
                       will reflect everything ingested up to now
      note_publish     that model became servable — the horizon the
                       last training read captured is now live

    ``staleness_seconds`` = now - (oldest ingest past the servable
    horizon). Events arriving DURING a train are conservatively dated
    at the publish horizon (the ledger tracks boundaries, not every
    event timestamp).
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._runs: "collections.deque[Dict[str, Any]]" = collections.deque(
            maxlen=LEDGER_RUN_CAPACITY)
        self._current: Optional[Dict[str, Any]] = None
        self._last_ingest: Optional[float] = None
        self._first_unreflected: Optional[float] = None
        self._pending_horizon: Optional[float] = None
        self._model_horizon: Optional[float] = None

    # -- per-run stage timings ---------------------------------------------
    def start_run(self, run_id: str) -> None:
        with self._lock:
            self._start_run_locked(run_id)
        # the gauge describes the CURRENT run: stages the new run never
        # executes (a warm run skipping compile) must not keep exporting
        # the previous run's seconds; history lives in snapshot().runs
        _DATAPATH_STAGE_SECONDS.reset()

    def note_stage(self, stage: str, seconds: float) -> None:
        """Attribute ``seconds`` to ``stage`` of the current run
        (additive — bin-cache loads can happen per side). Stages noted
        outside any run land in an implicit one, so ad-hoc trainer use
        (tests, notebooks) still shows up."""
        with self._lock:
            if self._current is None:
                self._start_run_locked("adhoc")
            stages = self._current["stages"]
            total = round(stages.get(stage, 0.0) + seconds, 4)
            stages[stage] = total
        _DATAPATH_STAGE_SECONDS.labels(stage).set(total)

    def _start_run_locked(self, run_id: str) -> None:
        # caller holds the lock
        run = {"run": run_id, "start_unix": round(time.time(), 3),
               "stages": {}}
        self._current = run
        self._runs.append(run)

    # -- freshness ----------------------------------------------------------
    def note_ingest(self, ts: Optional[float] = None) -> None:
        ts = time.time() if ts is None else ts
        with self._lock:
            self._last_ingest = ts
            if self._first_unreflected is None:
                self._first_unreflected = ts
        self._refresh_staleness()

    def note_train_read(self, ts: Optional[float] = None) -> None:
        ts = time.time() if ts is None else ts
        with self._lock:
            # the model being built covers everything ingested so far
            self._pending_horizon = (
                self._last_ingest if self._last_ingest is not None else ts)

    def note_publish(self, ts: Optional[float] = None) -> None:
        ts = time.time() if ts is None else ts
        with self._lock:
            horizon = (self._pending_horizon
                       if self._pending_horizon is not None else ts)
            self._model_horizon = horizon
            self._pending_horizon = None
            if self._first_unreflected is not None:
                if (self._last_ingest is None
                        or self._last_ingest <= horizon):
                    self._first_unreflected = None
                elif self._first_unreflected <= horizon:
                    # events landed during the train: they have waited
                    # at most since the horizon (boundary approximation)
                    self._first_unreflected = horizon
        self._refresh_staleness()

    def staleness_seconds(self, now: Optional[float] = None) -> float:
        now = time.time() if now is None else now
        with self._lock:
            first = self._first_unreflected
        value = 0.0 if first is None else max(0.0, now - first)  # graftlint: disable=JT15 — staleness spans processes: ingest horizons are wall timestamps serialized with the log, and tests drive synthetic ts/now clocks through the same arithmetic
        _MODEL_STALENESS.set(value)
        return value

    def _refresh_staleness(self) -> None:
        self.staleness_seconds()

    # -- reading ------------------------------------------------------------
    def snapshot(self, now: Optional[float] = None) -> Dict[str, Any]:
        staleness = self.staleness_seconds(now)
        with self._lock:
            runs = [dict(r, stages=dict(r["stages"])) for r in self._runs]
            last_ingest = self._last_ingest
            horizon = self._model_horizon
        return {
            "staleness_seconds": round(staleness, 3),
            "last_ingest_unix": (round(last_ingest, 3)
                                 if last_ingest is not None else None),
            "model_horizon_unix": (round(horizon, 3)
                                   if horizon is not None else None),
            "runs": runs,
        }

    def clear(self) -> None:
        with self._lock:
            self._runs.clear()
            self._current = None
            self._last_ingest = None
            self._first_unreflected = None
            self._pending_horizon = None
            self._model_horizon = None
        _MODEL_STALENESS.set(0.0)
        _DATAPATH_STAGE_SECONDS.reset()


#: the process-global ledger every seam records into
LEDGER = DataPathLedger()


def note_ingest(ts: Optional[float] = None) -> None:
    """Module-level ingest hook (the storage writers and event server
    call this once per accepted event batch)."""
    LEDGER.note_ingest(ts)


# -- tail-latency attribution --------------------------------------------------

#: minimum sealed records for a meaningful tail split
MIN_TAIL_RECORDS = 4


def _stage_shares(records: List[Dict[str, Any]]) -> Tuple[
        Dict[str, float], float]:
    """(stage -> summed ms, total ms) over a record cohort."""
    sums: Dict[str, float] = {}
    total = 0.0
    for r in records:
        for stage, ms in (r.get("stages") or {}).items():
            if isinstance(ms, (int, float)) and ms > 0:
                sums[stage] = sums.get(stage, 0.0) + float(ms)
        total += float(r.get("duration_ms") or 0.0)
    return sums, total


def tail_report(records: Optional[List[Dict[str, Any]]] = None,
                q: float = 0.95) -> Dict[str, Any]:
    """Where does the time of above-p``q`` requests go, stage by stage,
    and how does that differ from the median request?

    For both cohorts — the tail (duration >= the q-quantile) and the
    median half (duration <= p50) — each stage's share of the cohort's
    total request time is reported; ``delta_share`` (tail - median) is
    the attribution answer: the stage whose share GROWS in the tail is
    what the p99 is made of. Shares are never negative (flight clamps
    the unattributed remainder at 0), and the named stages plus
    ``unattributed`` sum to ~1 by the recorder's construction."""
    if not 0.0 < q < 1.0:
        raise ValueError(f"quantile {q} outside (0, 1)")
    if records is None:
        records = flight.RECORDER.records()
    timed = [r for r in records
             if isinstance(r.get("duration_ms"), (int, float))]
    out: Dict[str, Any] = {"quantile": q, "total_count": len(timed)}
    if len(timed) < MIN_TAIL_RECORDS:
        out.update({"tail_count": 0, "stages": {},
                    "note": f"need >= {MIN_TAIL_RECORDS} recorded "
                            "requests for a tail split"})
        return out
    durations = sorted(r["duration_ms"] for r in timed)
    threshold = durations[min(len(durations) - 1,
                              int(len(durations) * q))]
    p50 = durations[len(durations) // 2]
    tail = [r for r in timed if r["duration_ms"] >= threshold]
    median = [r for r in timed if r["duration_ms"] <= p50]
    tail_sums, tail_total = _stage_shares(tail)
    med_sums, med_total = _stage_shares(median)
    stages: Dict[str, Dict[str, float]] = {}
    for stage in sorted(set(tail_sums) | set(med_sums)):
        t_share = (tail_sums.get(stage, 0.0) / tail_total
                   if tail_total > 0 else 0.0)
        m_share = (med_sums.get(stage, 0.0) / med_total
                   if med_total > 0 else 0.0)
        stages[stage] = {
            "tail_ms_total": round(tail_sums.get(stage, 0.0), 3),
            "tail_share": round(t_share, 4),
            "median_share": round(m_share, 4),
            "delta_share": round(t_share - m_share, 4),
        }
    unattributed = stages.get("unattributed", {}).get("tail_share", 0.0)
    named = {s: v for s, v in stages.items() if s != "unattributed"}
    top = max(named, key=lambda s: named[s]["tail_share"]) if named else None
    out.update({
        "threshold_ms": round(threshold, 3),
        "p50_ms": round(p50, 3),
        "tail_count": len(tail),
        "stages": stages,
        "attributed_tail_share": round(max(0.0, 1.0 - unattributed), 4),
        "dominant_tail_stage": top,
    })
    return out
