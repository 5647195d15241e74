"""DASE component base classes.

Behavior contracts from the reference controller layer:

  - DataSource  (ref: controller/PDataSource.scala:34, LDataSource.scala:35)
  - Preparator  (ref: controller/PPreparator.scala:30, IdentityPreparator.scala:31)
  - Algorithm   (ref: controller/PAlgorithm.scala:45, P2LAlgorithm.scala:42,
                 LAlgorithm.scala:41 — collapsed into one class; see
                 predictionio_tpu.core.__doc__ for why)
  - Serving     (ref: controller/LServing.scala:26 + LFirstServing/LAverageServing)
  - SanityCheck (ref: controller/SanityCheck.scala:24)

Generic type roles (kept as documentation; Python stays duck-typed):
TD training data, EI evaluation info, PD prepared data, Q query,
P predicted result, A actual result, M model.
"""

from __future__ import annotations

import abc
from typing import Any, Dict, Generic, List, Optional, Sequence, Tuple, TypeVar

from predictionio_tpu.core.params import EmptyParams, Params
from predictionio_tpu.parallel.mesh import MeshContext

TD = TypeVar("TD")
EI = TypeVar("EI")
PD = TypeVar("PD")
Q = TypeVar("Q")
P = TypeVar("P")
A = TypeVar("A")
M = TypeVar("M")


class Doer:
    """Base for components instantiated with their Params.

    ref: core/AbstractDoer.scala:24 — the reference reflects on a
    constructor taking (Params) or zero args; here components store
    their params on construction via `create`.
    """

    params: Params

    def __init__(self, params: Optional[Params] = None):
        self.params = params if params is not None else EmptyParams()

    @classmethod
    def create(cls, params: Optional[Params] = None) -> "Doer":
        """Instantiate with params if the ctor accepts them, else bare.

        Mirrors Doer.apply's two-ctor protocol so user classes may
        define `__init__(self)` without params.
        """
        import inspect

        sig = inspect.signature(cls.__init__)
        if len(sig.parameters) > 1:  # beyond self
            return cls(params)
        inst = cls()
        if params is not None and not isinstance(params, EmptyParams):
            inst.params = params
        return inst


class SanityCheck(abc.ABC):
    """Opt-in hook: TrainingData / PreparedData / models implementing
    this get checked after each pipeline stage (ref: SanityCheck.scala:24,
    called from Engine.scala:610-666)."""

    @abc.abstractmethod
    def sanity_check(self) -> None:
        """Raise on inconsistent data."""


class DataSource(Doer, Generic[TD, EI, Q, A]):
    """Reads training and evaluation data from the event store."""

    @abc.abstractmethod
    def read_training(self, ctx: MeshContext) -> TD:
        """ref: PDataSource.readTraining"""

    def read_eval(self, ctx: MeshContext) -> List[Tuple[TD, EI, List[Tuple[Q, A]]]]:
        """k folds of (training data, eval info, (query, actual) pairs).

        ref: PDataSource.readEval — default: no eval data.
        """
        return []


class Preparator(Doer, Generic[TD, PD]):
    @abc.abstractmethod
    def prepare(self, ctx: MeshContext, training_data: TD) -> PD:
        """ref: PPreparator.prepare"""


class IdentityPreparator(Preparator):
    """Pass-through (ref: IdentityPreparator.scala:31)."""

    def prepare(self, ctx: MeshContext, training_data):
        return training_data


class Algorithm(Doer, Generic[PD, M, Q, P]):
    """One trainable + servable algorithm.

    Collapses the reference's PAlgorithm / P2LAlgorithm / LAlgorithm
    split: `train` computes on the mesh when its data is sharded,
    `predict` answers one query at serve time, `batch_predict`
    vector-scores query batches for evaluation (override it with a
    jitted scorer — the default is the per-query loop the reference
    uses in P2LAlgorithm.scala:63).
    """

    #: an algorithm that answers IN STEPS sets this and adds
    #: ``begin(model, query) -> ticket | None`` (None: no room yet, ask again
    #: after a step), ``step(model, tickets, done)`` (a bounded amount of
    #: work over the pending tickets; ``done(ticket, prediction)`` the moment
    #: one is answered, ``prediction`` as ``predict`` gives it) and
    #: ``cancel(model, ticket)``. The engine server then drives it with its
    #: step worker instead of the batcher (``serving/engine_server.py``).
    stepwise = False

    @abc.abstractmethod
    def train(self, ctx: MeshContext, prepared_data: PD) -> M:
        ...

    @abc.abstractmethod
    def predict(self, model: M, query: Q) -> P:
        ...

    def batch_predict(self, model: M, queries: Sequence[Tuple[int, Q]]) -> List[Tuple[int, P]]:
        """ref: P2LAlgorithm.batchPredict default — mapValues(predict)."""
        return [(i, self.predict(model, q)) for i, q in queries]

    # -- persistence (ref: PAlgorithm.makePersistentModel + CoreWorkflow Kryo path)
    def make_persistent_model(self, model: M) -> Any:
        """Convert the in-memory model to its persisted form.

        Default: the model itself (pickled into the Models repo).
        Return a `PersistentModelManifest` from
        predictionio_tpu.core.persistent_model to take over persistence
        (custom checkpoint dirs, the reference's PersistentModel path).
        """
        return model

    def load_persistent_model(self, persisted: Any, ctx: MeshContext) -> M:
        """Inverse of make_persistent_model at deploy time."""
        return persisted

    def warmup(self, model: M, ctx: MeshContext) -> None:
        """Pre-compile the serve path's standard shape buckets.

        Called by the engine server right after deploy/reload so the
        FIRST live query doesn't pay XLA compile (SURVEY.md §7.5 hard
        part #2 — the reference has no compile step to warm; a jitted
        scorer does). Default: no-op. Implementations should drive the
        same compiled functions ``predict`` uses, at the default
        (B, k, ...) buckets, and must tolerate empty models."""

    def apply_patch(self, model: M, patch: dict) -> bool:
        """Apply a streaming model patch (workflow/stream.py fold-in)
        to the LIVE model in place — the lightweight alternative to a
        full ``/reload`` when only a few rows of the model moved.

        Returns False when this algorithm does not support patching
        (the default): the engine server then answers 400 and the
        streaming path falls back to the rolling-reload lane. An
        implementation must leave concurrent ``predict`` calls
        consistent (copy-on-write swaps, never torn in-place rows)."""
        return False


class Serving(Doer, Generic[Q, P]):
    """Combines the per-algorithm predictions into one response."""

    @abc.abstractmethod
    def serve(self, query: Q, predictions: Sequence[P]) -> P:
        """ref: LServing.serve"""


class FirstServing(Serving):
    """Head of the predictions (ref: LFirstServing.scala:25)."""

    def serve(self, query, predictions):
        return predictions[0]


class AverageServing(Serving):
    """Arithmetic mean of numeric predictions (ref: LAverageServing.scala:25)."""

    def serve(self, query, predictions):
        return sum(predictions) / len(predictions)
