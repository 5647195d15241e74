"""JAX runtime instrumentation: compile cache, compile time, transfers,
train steps, device memory.

The TPU economics the metrics must surface (SURVEY.md §3.1): XLA
compile time is the job-startup tax, the persistent compile cache
(parallel/compile_cache.py) is what waives it, and host<->device
transfer bytes are the serving path's hidden cost. jax.monitoring
already emits the compile/cache events; ``install()`` bridges them into
the obs registry so they show up on every server's ``/metrics``:

  pio_jax_compile_cache_total{result="hit"|"miss"}  persistent-cache outcome
  pio_jax_compile_seconds_bucket{phase=...}         trace/lower/backend compile
  pio_transfer_bytes_total{direction="h2d"|"d2h"}   explicit hot-path counts
  pio_train_step_seconds_bucket                     per-train-step wall time
  pio_train_seconds_bucket{engine=...}              whole-train wall time
  pio_device_memory_bytes{device,kind}              allocator stats per device
                                                    (owned by obs/memacct.py)
  pio_pallas_kernel_enabled{kernel=}                Pallas vs XLA path choice

The module never imports jax at import time (event-tier servers import
it for the registry only).
"""

from __future__ import annotations

import logging
import os
import re
from typing import Optional

from predictionio_tpu.obs import metrics

log = logging.getLogger(__name__)

COMPILE_CACHE_TOTAL = metrics.counter(
    "pio_jax_compile_cache_total",
    "Persistent XLA compile-cache lookups by outcome",
    ("result",),
)

#: compile phases run 0.1s..minutes; coarser buckets than serving latency
_COMPILE_BUCKETS = (0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
                    30.0, 60.0, 120.0, 300.0)

COMPILE_SECONDS = metrics.histogram(
    "pio_jax_compile_seconds",
    "XLA compilation phase wall time (jaxpr trace / lowering / backend)",
    ("phase",),
    buckets=_COMPILE_BUCKETS,
)

TRANSFER_BYTES = metrics.counter(
    "pio_transfer_bytes_total",
    "Host<->device bytes moved on instrumented hot paths",
    ("direction",),
)

TRAIN_STEP_SECONDS = metrics.histogram(
    "pio_train_step_seconds",
    "Per-train-step wall time (dispatch + device compute)",
    buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
             1.0, 2.5, 5.0, 10.0, 30.0),
)

TRAIN_SECONDS = metrics.histogram(
    "pio_train_seconds",
    "Whole engine.train wall time per training run",
    ("engine",),
    buckets=(0.1, 0.5, 1.0, 5.0, 10.0, 30.0, 60.0, 300.0, 600.0,
             1800.0, 3600.0),
)

PALLAS_KERNEL_ENABLED = metrics.gauge(
    "pio_pallas_kernel_enabled",
    "Whether a Pallas kernel path (ops/pallas/) is engaged for the "
    "current trainer (1) or its XLA fallback is active (0)",
    ("kernel",),
)

#: jax.monitoring event keys -> our series (jax 0.9.0 names)
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
_COMPILE_DURATION_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
}

_installed = False


def _on_event(event: str, **kwargs) -> None:
    result = _CACHE_EVENTS.get(event)
    if result is not None:
        COMPILE_CACHE_TOTAL.labels(result).inc()


def _on_event_duration(event: str, duration_secs: float, **kwargs) -> None:
    phase = _COMPILE_DURATION_PHASES.get(event)
    if phase is not None:
        COMPILE_SECONDS.labels(phase).observe(duration_secs)


def install() -> bool:
    """Register the jax.monitoring bridge once per process
    (idempotent)."""
    global _installed
    if _installed:
        return True
    from jax import monitoring

    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_event_duration)
    _installed = True
    return True


def record_kernel_plan(plan: dict) -> None:
    """Export a trainer's kernel-selection decision (ops/pallas/) so a
    capture or dashboard always says which path produced its
    numbers — a step-time comparison across runs is meaningless without
    it."""
    for kernel in ("flash_ce", "embed_update"):
        if kernel in plan:
            PALLAS_KERNEL_ENABLED.labels(kernel).set(float(bool(plan[kernel])))


#: what each trainer of this process last reported (``pio train``
#: prints them): kernel plan, step time, losses, placement
TRAINER_REPORTS: dict = {}


def record_trainer_report(trainer: str, report: dict) -> None:
    """Merge ``report`` into the trainer's entry (a trainer reports
    from more than one seam: placement, then each dispatch)."""
    TRAINER_REPORTS.setdefault(trainer, {}).update(report)


#: per compiled program (HLO module name, as a device trace's "XLA
#: Modules" line gives it): instruction name -> innermost named scope.
#: A Pallas kernel's instruction carries its ``name=``; XLA's own
#: fusions stay ``%fusion.N`` and are told apart only through this map
SCOPE_MAPS: dict = {}

_HLO_MODULE_RE = re.compile(r"^HloModule\s+([^\s,]+)", re.M)
_HLO_OP_NAME_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?\bop_name=\"([^\"]*)\"", re.M)
#: what a named scope of this program looks like as one element of an
#: op_name path: lower-case and dotted (``twotower.adagrad_user``).
#: JAX's own elements are not (``jit(epoch)``, ``while``, ``body``,
#: ``TwoTowerTrainer._make_epoch.<locals>.epoch``); under autodiff a
#: scope arrives wrapped (``transpose(jvp(twotower.flash_ce))``)
_SCOPE_RE = re.compile(r"[a-z][a-z0-9_]*(?:\.[a-z][a-z0-9_]*)+")
_WRAPPERS_RE = re.compile(r"^(?:\w+\()+|\)+$")


def scope_map_of(hlo_text: str) -> dict:
    """instruction name -> innermost program scope, for every
    instruction of an optimised HLO module whose ``op_name`` metadata
    lies under one (``jax.named_scope("twotower.step")``)."""
    out = {}
    for instr, op_name in _HLO_OP_NAME_RE.findall(hlo_text):
        # the last path element is the primitive, not a scope
        for element in reversed(op_name.split("/")[:-1]):
            element = _WRAPPERS_RE.sub("", element)
            if _SCOPE_RE.fullmatch(element):
                out[instr] = element
                break
    return out


def record_scope_map(compiled) -> None:
    """Keep a compiled program's instruction -> scope map, read once
    from its text, under its HLO module's name. On a device trace an
    event's name is the instruction's text and carries no scope: the
    trace's readers (obs/profiler.py, the benchmark) resolve
    ``%fusion.108`` through this map."""
    text = compiled.as_text()
    module = _HLO_MODULE_RE.search(text)
    if module is not None:
        SCOPE_MAPS[module.group(1)] = scope_map_of(text)


def compile_cache_counts() -> dict:
    """{"hit": n, "miss": n} of this process's persistent-cache
    lookups so far (``pio_jax_compile_cache_total``)."""
    return {result: int(COMPILE_CACHE_TOTAL.labels(result).value)
            for result in ("hit", "miss")}


def device_report() -> dict:
    """What this process runs on, as jax reports it — the block
    ``pio train`` prints and the engine server's ``GET /`` carries, so
    a result always names its device. Only for processes that own a
    backend (it initialises one)."""
    import jax

    local = jax.local_devices()
    stats = [d.memory_stats() or {} for d in local]
    return {
        "platform": local[0].platform,
        "device_kind": local[0].device_kind,
        "device_count": jax.device_count(),
        "device_ids": [d.id for d in local],
        # the chip the fleet gave this process (serving/fleet.py)
        "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
        "bytes_in_use": [s.get("bytes_in_use") for s in stats],
        "peak_bytes_in_use": [s.get("peak_bytes_in_use") for s in stats],
        "compile_cache": compile_cache_counts(),
    }


def record_transfer(nbytes: Optional[int], direction: str) -> None:
    """Count one host<->device transfer (direction: 'h2d' | 'd2h')."""
    if nbytes:
        TRANSFER_BYTES.labels(direction).inc(int(nbytes))


def observe_train_step(seconds: float) -> None:
    TRAIN_STEP_SECONDS.observe(seconds)
    # feed the train-step deadman (obs/health.py): each completed step
    # both extends its duration history and pushes the stall deadline
    # out; silence beyond factor x trailing median fires the watchdog
    from predictionio_tpu.obs import health

    health.TRAIN_WATCHDOG.beat(seconds)


def update_device_memory_gauges() -> int:
    """Refresh pio_device_memory_bytes from each local device's
    ``memory_stats()``; returns the number of devices reporting. CPU
    backends often report nothing — that is a 0, not an error.

    Thin delegate: the gauge moved to obs/memacct.py (the one owner of
    device-memory accounting, which also refreshes it continuously on
    the flight-recorder snapshot cadence instead of only post-train)."""
    from predictionio_tpu.obs import memacct

    return memacct.update_device_memory_gauges()
