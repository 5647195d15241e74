"""Engine variant (engine.json) loading.

Behavior contract from the reference (CreateWorkflow.scala:152-177 +
Engine.scala:328-384): an engine variant JSON names the engine factory
and fills each DASE slot with ``{name, params}`` blocks:

    {
      "id": "default",
      "description": "...",
      "engineFactory": "myengine.RecommendationEngine",
      "datasource": {"name": "", "params": {...}},
      "preparator": {"name": "", "params": {...}},
      "algorithms": [{"name": "als", "params": {...}}],
      "serving": {"name": "", "params": {...}}
    }

The reference's `sparkConf` passthrough becomes `runtimeConf` (mesh
axes, seeds, XLA options) forwarded into MeshContext.config.
"""

from __future__ import annotations

import hashlib
import json
from dataclasses import dataclass, field
from typing import TYPE_CHECKING, Any, Dict, Optional, Tuple

# predictionio_tpu.core pulls in jax; a variant is also read by the
# fleet's router process, which must stay off it (serving/fleet.py) —
# so the engine machinery is imported where an engine is made
if TYPE_CHECKING:
    from predictionio_tpu.core.engine import Engine
    from predictionio_tpu.core.params import EngineParams


def _load_project_module(path: str):
    """Load a project-local engine module by file path.

    The sys.modules key is derived from the absolute path, so it is (a)
    unique per project — no cross-project shadowing, (b) deterministic
    across processes — classes pickled out of the module (custom models)
    unpickle in a later deploy process once create_engine has loaded the
    module again."""
    import importlib.util
    import os
    import sys

    path = os.path.abspath(path)
    key = "_pio_project_" + hashlib.md5(path.encode()).hexdigest()[:12]
    mtime = os.path.getmtime(path)
    cached = sys.modules.get(key)
    if (
        cached is not None
        and getattr(cached, "__file__", None) == path
        and getattr(cached, "__pio_mtime__", None) == mtime
    ):
        return cached
    spec = importlib.util.spec_from_file_location(key, path)
    module = importlib.util.module_from_spec(spec)
    module.__pio_mtime__ = mtime
    sys.modules[key] = module
    try:
        spec.loader.exec_module(module)
    except BaseException:
        sys.modules.pop(key, None)
        raise
    return module


@dataclass
class EngineVariant:
    id: str
    engine_factory: str
    description: str = ""
    raw: Dict[str, Any] = field(default_factory=dict)
    #: directory of the engine.json; local scaffolded engine modules
    #: (`pio template get`) resolve from here — the analogue of the
    #: reference building the project dir onto the classpath
    #: (Console.scala:772 `pio build` before train/deploy)
    base_dir: Optional[str] = None

    @staticmethod
    def from_dict(d: Dict[str, Any], base_dir: Optional[str] = None) -> "EngineVariant":
        if "engineFactory" not in d:
            raise ValueError("engine variant requires 'engineFactory'")
        return EngineVariant(
            id=d.get("id", "default"),
            engine_factory=d["engineFactory"],
            description=d.get("description", ""),
            raw=dict(d),
            base_dir=base_dir,
        )

    @staticmethod
    def load(path: str) -> "EngineVariant":
        import os

        with open(path) as f:
            return EngineVariant.from_dict(
                json.load(f), base_dir=os.path.dirname(os.path.abspath(path))
            )

    def create_engine(self) -> Engine:
        # a factory module living next to the engine.json (scaffolded
        # project) loads from FILE under a path-keyed module name — two
        # projects both named `recommendation_engine` can never shadow
        # each other, and sys.path is never mutated
        if self.base_dir:
            import os

            mod_name, _, attr = self.engine_factory.rpartition(".")
            candidate = (
                os.path.join(self.base_dir, *mod_name.split(".")) + ".py"
                if mod_name else None
            )
            if candidate and os.path.isfile(candidate):
                module = _load_project_module(candidate)
                from predictionio_tpu.core.engine import factory_from_object

                return factory_from_object(
                    getattr(module, attr), self.engine_factory
                )()
        from predictionio_tpu.core.engine import resolve_engine_factory

        return resolve_engine_factory(self.engine_factory)()

    def engine_params(self, engine: Optional[Engine] = None) -> EngineParams:
        engine = engine or self.create_engine()
        return engine.engine_params_from_variant(self.raw)

    def runtime_conf(self) -> Dict[str, str]:
        return dict(self.raw.get("runtimeConf") or self.raw.get("sparkConf") or {})

    def slo_conf(self) -> Optional[Dict[str, Any]]:
        """The variant's declarative ``"slo"`` block (objectives +
        shedding thresholds, obs/slo.py module docstring), applied by
        `pio deploy` so operators page — and shed — on their own
        numbers. None when the variant declares none."""
        block = self.raw.get("slo")
        if block is None:
            return None
        if not isinstance(block, dict):
            raise ValueError('engine variant "slo" must be a JSON object')
        return dict(block)
