"""Deploy-time model + params reload.

Behavior contract from the reference
(workflow/CreateServer.createServerActorWithEngine:190 +
controller/Engine.prepareDeploy:174 + engineInstanceToEngineParams:387):
given a COMPLETED EngineInstance, rebuild the EngineParams from the
instance's params snapshot, load the model blob from the Models repo,
resolve PersistentModel manifests through their loader classes, and
instantiate algorithms + serving ready to answer queries.
"""

from __future__ import annotations

import json
import pickle
from dataclasses import dataclass
from typing import Any, List, Optional

from predictionio_tpu.core.controller import Algorithm, Serving
from predictionio_tpu.core.engine import Engine, _declared_params_class
from predictionio_tpu.core.params import EngineParams, params_from_dict
from predictionio_tpu.core.persistent_model import (
    PersistentModelManifest,
    load_from_manifest,
)
from predictionio_tpu.data.metadata import EngineInstance
from predictionio_tpu.data.storage import Storage, get_storage
from predictionio_tpu.obs import trace
from predictionio_tpu.parallel.mesh import MeshContext


def engine_params_from_instance(engine: Engine, instance: EngineInstance) -> EngineParams:
    """Instance params snapshot -> EngineParams (ref: Engine.scala:387)."""

    def slot(raw: str, classes):
        block = json.loads(raw) if raw else {"name": "", "params": {}}
        name = block.get("name", "")
        cls = classes.get(name)
        if cls is None:
            raise KeyError(f"component {name!r} from instance not in engine")
        return (name, params_from_dict(_declared_params_class(cls), block.get("params")))

    algo_blocks = json.loads(instance.algorithms_params) if instance.algorithms_params else []
    algo_list = []
    for block in algo_blocks:
        name = block.get("name", "")
        cls = engine.algorithm_classes.get(name)
        if cls is None:
            raise KeyError(f"algorithm {name!r} from instance not in engine")
        algo_list.append(
            (name, params_from_dict(_declared_params_class(cls), block.get("params")))
        )
    return EngineParams(
        data_source_params=slot(instance.data_source_params, engine.data_source_classes),
        preparator_params=slot(instance.preparator_params, engine.preparator_classes),
        algorithm_params_list=algo_list,
        serving_params=slot(instance.serving_params, engine.serving_classes),
    )


@dataclass
class Deployment:
    """Everything the engine server needs to answer /queries.json."""

    instance: EngineInstance
    engine_params: EngineParams
    algorithms: List[Algorithm]
    models: List[Any]
    serving: Serving

    @property
    def stepwise(self) -> bool:
        """Whether this deployment answers in steps (``Algorithm.stepwise``):
        one algorithm, and it says so."""
        return len(self.algorithms) == 1 and bool(self.algorithms[0].stepwise)

    def query(self, q: Any) -> Any:
        """One query through all algorithms + serving
        (ref: CreateServer.scala:472-475)."""
        predictions = [
            algo.predict(model, q) for algo, model in zip(self.algorithms, self.models)
        ]
        with trace.device_span("engine.decode"):
            return self.serving.serve(q, predictions)

    def query_batch(self, payloads: List[Any]) -> List[Any]:
        """Many queries through each algorithm's vectorized
        ``batch_predict`` (one device dispatch per algorithm instead of
        one per query), then per-query Serving. The serve-time analogue
        of the evaluation batch path (SURVEY.md §7.5 micro-batching)."""
        indexed = list(enumerate(payloads))
        per_algo = [
            dict(algo.batch_predict(model, indexed))
            for algo, model in zip(self.algorithms, self.models)
        ]
        with trace.device_span("engine.decode"):
            return [
                self.serving.serve(q, [preds[i] for preds in per_algo])
                for i, q in indexed
            ]


def latest_completed_instance_id(
    storage: Storage,
    engine_id: str,
    engine_version: str = "0",
    engine_variant: str = "default",
) -> Optional[str]:
    """The newest COMPLETED instance id for an engine, or None.

    The fleet supervisor's swap trigger: a train run publishing a new
    COMPLETED instance moves this id, and the fleet rolls replicas onto
    it one at a time (serving/fleet.py) — the multi-replica analogue of
    the single server's ``GET /reload``."""
    instance = storage.engine_instances().get_latest_completed(
        engine_id, engine_version, engine_variant)
    return None if instance is None else instance.id


def prepare_deploy(
    engine: Engine,
    instance: EngineInstance,
    ctx: Optional[MeshContext] = None,
    storage: Optional[Storage] = None,
) -> Deployment:
    """ref: Engine.prepareDeploy:174."""
    from predictionio_tpu.parallel.compile_cache import enable_persistent_cache

    enable_persistent_cache()  # deploy warm-ups reuse cached executables
    storage = storage or get_storage()
    ctx = ctx or MeshContext()
    engine_params = engine_params_from_instance(engine, instance)
    algorithms = engine.make_algorithms(engine_params)

    blob = storage.models().get(instance.id)
    if blob is None:
        raise RuntimeError(f"no model stored for engine instance {instance.id}")
    persisted_list = pickle.loads(blob.models)
    if len(persisted_list) != len(algorithms):
        raise RuntimeError(
            f"instance {instance.id}: {len(persisted_list)} models for "
            f"{len(algorithms)} algorithms"
        )
    models = []
    for algo, persisted in zip(algorithms, persisted_list):
        if isinstance(persisted, PersistentModelManifest):
            persisted = load_from_manifest(persisted, instance.id, algo.params, ctx)
        models.append(algo.load_persistent_model(persisted, ctx))
    serving = engine.make_serving(engine_params)
    return Deployment(
        instance=instance,
        engine_params=engine_params,
        algorithms=algorithms,
        models=models,
        serving=serving,
    )
