"""Training workflow: train an engine, persist models + instance metadata.

Behavior contract from the reference (workflow/CoreWorkflow.runTrain:42
and CreateWorkflow.scala:232-255): create an EngineInstance metadata row
(INIT), run Engine.train, serialize the per-algorithm models into the
Models repo under the instance id (the reference Kryo-serializes;
here: pickle, with PersistentModel models saving themselves and leaving
a manifest), snapshot the full params into the instance, and mark it
COMPLETED — or FAILED on error.
"""

from __future__ import annotations

import contextlib
import datetime as _dt
import json
import logging
import os
import pickle
import uuid
from typing import Any, List, Optional

from predictionio_tpu.core.engine import Engine, TrainResult
from predictionio_tpu.core.params import EngineParams, params_to_dict
from predictionio_tpu.core.persistent_model import PersistentModel, manifest_for
from predictionio_tpu.data.metadata import EngineInstance, Model
from predictionio_tpu.data.storage import Storage, get_storage
from predictionio_tpu.obs import (dataobs, health, jaxmon, memacct, perfacct,
                                  profiler)
from predictionio_tpu.parallel.mesh import MeshContext, create_mesh
from predictionio_tpu.workflow.config import WorkflowParams

log = logging.getLogger(__name__)
UTC = _dt.timezone.utc


def _now() -> _dt.datetime:
    return _dt.datetime.now(tz=UTC)


def serialize_models(
    engine: Engine,
    engine_params: EngineParams,
    models: List[Any],
    instance_id: str,
    ctx: MeshContext,
) -> bytes:
    """Models -> bytes for the Models repo (ref: CoreWorkflow.scala:69-74).

    PersistentModel models save themselves under the instance id and are
    replaced by a manifest (ref: Engine.makeSerializableModels:260 +
    PAlgorithm.makePersistentModel:98).
    """
    algorithms = engine.make_algorithms(engine_params)
    persisted = []
    for algo, model in zip(algorithms, models):
        pm = algo.make_persistent_model(model)
        if isinstance(pm, PersistentModel):
            pm.save(instance_id, algo.params, ctx)
            pm = manifest_for(pm)
        persisted.append(pm)
    return pickle.dumps(persisted)


@contextlib.contextmanager
def _maybe_profile(instance_id: str):
    """First-party training profiler (beyond the reference, whose only
    training observability is the Spark UI — SURVEY.md §5.1): set
    ``PIO_PROFILE_DIR`` to capture a JAX/XLA device trace of the whole
    train into ``<dir>/<instance_id>`` (open with TensorBoard or
    xprof; obs/profiler.py owns the capture machinery). After a
    successful capture the PER-STEP device-time breakdown is computed
    (obs/profiler.parse_xplane: busy as a union, self time by the
    program's scopes and kernel names, idle by ``pio:`` span) and
    logged as a structured record plus a ``breakdown.json`` beside the
    trace. Profiling failures never fail training."""
    profile_dir = os.environ.get("PIO_PROFILE_DIR")
    if not profile_dir:
        yield
        return
    out = os.path.join(profile_dir, instance_id)
    steps_before = jaxmon.TRAIN_STEP_SECONDS.labels().count
    with profiler.trace_capture(out) as started:
        yield
    if started:
        steps = jaxmon.TRAIN_STEP_SECONDS.labels().count - steps_before
        _log_step_breakdown(out, steps)


def _log_step_breakdown(profile_dir: str, steps: int) -> None:
    """Parse the captured trace into device ms/step by scope and kernel
    (best effort: on CPU tier-1 or without the parser deps this logs
    the parse error and moves on). A train whose loop never feeds
    ``pio_train_step_seconds`` has ``steps == 0``: the TOTAL device
    time is logged instead — a whole-train number must never be
    presented as a per-step one."""
    try:
        breakdown = (profiler.step_breakdown(profile_dir, steps)
                     if steps > 0 else profiler.parse_xplane(profile_dir))
    except Exception as e:  # noqa: BLE001 — observability must not break train
        breakdown = {"error": str(e)}
    if "error" in breakdown:
        log.info("train profile captured at %s (device-time breakdown "
                 "unavailable: %s)", profile_dir, breakdown["error"])
        return
    if steps > 0:
        log.info(
            "train device time: %.3f ms/step over %d step(s)",
            breakdown["device_ms_per_step"], breakdown["steps"],
            extra={"pio": {"profile_dir": profile_dir, **{
                k: breakdown[k] for k in ("device_ms_per_step",
                                          "by_category_ms_per_step",
                                          "steps")}}},
        )
    else:
        log.info(
            "train device time: %.3f s total (no per-step timings "
            "observed)", breakdown["device_time_sec"],
            extra={"pio": {"profile_dir": profile_dir,
                           "device_time_sec": breakdown["device_time_sec"],
                           "by_category": breakdown.get("by_category")}},
        )
    try:
        with open(os.path.join(profile_dir, "breakdown.json"), "w") as f:
            json.dump(breakdown, f, indent=1, sort_keys=True)
    except OSError as e:
        log.warning("could not persist %s/breakdown.json: %s",
                    profile_dir, e)


def run_train(
    engine: Engine,
    engine_params: EngineParams,
    engine_id: str,
    engine_version: str = "0",
    engine_variant: str = "default",
    engine_factory: str = "",
    batch: str = "",
    ctx: Optional[MeshContext] = None,
    workflow_params: Optional[WorkflowParams] = None,
    storage: Optional[Storage] = None,
) -> EngineInstance:
    """ref: CoreWorkflow.runTrain:42. Returns the COMPLETED instance.

    Multi-host: every process runs the same engine.train (its jitted
    steps carry the cross-host collectives), but storage is
    single-writer — process 0 owns the EngineInstance row and the model
    blob; the instance id is broadcast so all hosts return the same
    instance, and a final barrier guarantees the COMPLETED row is
    visible to every host before any of them proceeds to deploy.

    Failure semantics under multi-host: an exception on any process
    (including a storage failure on the writer) kills THAT process;
    peers blocked in collectives or the final barrier are then failed
    by jax.distributed's coordination service when the dead process
    misses its heartbeat — the job errors out rather than hanging
    forever, but detection is timeout-based, not an immediate clean
    broadcast (same model as a lost Spark driver failing its
    executors).
    """
    # multi-host opt-in: PIO_COORDINATOR_ADDRESS brings up jax.distributed
    # before any mesh is built, so ctx meshes span all hosts (§7.9)
    from predictionio_tpu.parallel.compile_cache import enable_persistent_cache
    from predictionio_tpu.parallel import multihost as mh

    distributed = mh.initialize_from_env()
    enable_persistent_cache()
    storage = storage or get_storage()
    if ctx is None:
        # the default mesh: every visible device on ``data`` (a lone
        # device needs none) — without it a `pio train` on a four-chip
        # host used one chip and left three idle
        import jax

        ctx = MeshContext(
            mesh=create_mesh() if jax.device_count() > 1 else None)
    wp = workflow_params or WorkflowParams()
    writer = not distributed or mh.process_index() == 0
    instance_id = mh.broadcast_string(uuid.uuid4().hex)

    ep_json = engine_params.to_json_dict()
    instance = EngineInstance(
        id=instance_id,
        status="INIT",
        start_time=_now(),
        end_time=_now(),
        engine_id=engine_id,
        engine_version=engine_version,
        engine_variant=engine_variant,
        engine_factory=engine_factory,
        batch=batch or wp.batch,
        data_source_params=json.dumps(ep_json["dataSourceParams"]),
        preparator_params=json.dumps(ep_json["preparatorParams"]),
        algorithms_params=json.dumps(ep_json["algorithmParamsList"]),
        serving_params=json.dumps(ep_json["servingParams"]),
    )
    inserted = False
    if writer:
        storage.engine_instances().insert(instance)
        inserted = True
    log.info("training instance %s (engine %s)", instance.id, engine_id)
    # data-path ledger: this run's stage wall-times accumulate under
    # the instance id (Engine.train notes read/prepare/fit, the ALS
    # trainer notes compile, bincache notes its loads/saves)
    perfacct.LEDGER.start_run(instance.id)

    try:
        instance.status = "TRAINING"
        if writer:
            storage.engine_instances().update(instance)
        import time as _time

        t_train = _time.perf_counter()
        # deadman watchdog over the training steps: the loops beat it
        # via jaxmon.observe_train_step, so a step hanging beyond
        # PIO_STALL_FACTOR x the trailing median fires a pio.stall log
        # and an all-thread stack dump (PIO_FLIGHT_DIR) while the hang
        # is still alive — not after the eventual kill
        with health.TRAIN_WATCHDOG.deadman(), _maybe_profile(instance.id):
            # chaos seam: an injected train fault exercises the FAILED
            # instance path below; an injected hang sits under the
            # deadman (once step beats have built its history)
            from predictionio_tpu.resilience import chaos

            chaos.inject("train")
            result: TrainResult = engine.train(ctx, engine_params, wp)
        # whole-train wall time + post-train device memory (the peak a
        # donation/HBM regression would move) on /metrics and `pio
        # metrics`; step-level timing comes from the training loops
        # themselves via jaxmon.observe_train_step
        train_sec = _time.perf_counter() - t_train
        jaxmon.TRAIN_SECONDS.labels(engine_id).observe(train_sec)
        perfacct.LEDGER.note_stage("train", train_sec)
        # device-memory plane (obs/memacct.py, the single owner of the
        # gauges): post-train refresh of allocator stats, ledger and
        # headroom — the continuous cadence rides the flight snapshots
        memacct.refresh()
        if result.stopped_after:
            # debug interruption (ref: Engine.scala:624-648): no model persisted
            instance.status = "COMPLETED"
            instance.batch = (instance.batch + f" [stopped after {result.stopped_after}]").strip()
            instance.end_time = _now()
            if writer:
                storage.engine_instances().update(instance)
            mh.barrier("pio_train_" + instance.id)
            return instance
        if wp.save_model:
            # serialization runs on EVERY process: materializing device
            # arrays (and any PersistentModel save hooks) may involve
            # collectives all hosts must join; only the writer stores
            blob = serialize_models(engine, engine_params, result.models, instance.id, ctx)
            if writer:
                storage.models().insert(Model(id=instance.id, models=blob))
        instance.status = "COMPLETED"
        instance.end_time = _now()
        if writer:
            storage.engine_instances().update(instance)
        # the model is now servable: move the freshness horizon —
        # pio_model_staleness_seconds drops to the age of whatever
        # arrived during the train (0 when nothing did)
        perfacct.LEDGER.note_publish()
        # data plane: the live schema profile becomes the
        # trained-against baseline — drift after THIS point is what
        # schema_change events report
        dataobs.DATAOBS.freeze_schemas(instance.id)
        # one structured line with the events->model stage split (the
        # zero-copy lane's read/bin/transfer sub-stages land here, so
        # a `pio train` log answers "where did the minutes go" without
        # a measurement run; pio_datapath_stage_seconds carries it live)
        runs = perfacct.LEDGER.snapshot().get("runs") or []
        if runs:
            stages = runs[-1].get("stages") or {}
            log.info(
                "events->model stages (sec): %s",
                " ".join(f"{k}={v:.2f}" for k, v in sorted(stages.items())),
                extra={"pio": {"instance": instance.id,
                               "datapath_stages": stages}},
            )
        # every host sees the COMPLETED row before anyone deploys from it
        mh.barrier("pio_train_" + instance.id)
        log.info("training completed: instance %s", instance.id)
        return instance
    except Exception:
        instance.status = "FAILED"
        instance.end_time = _now()
        if inserted:
            # never update a row that was never inserted (the insert
            # itself may be what failed)
            storage.engine_instances().update(instance)
        raise
