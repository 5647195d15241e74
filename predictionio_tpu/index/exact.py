"""Exact on-device retrieval: fused Pallas dot+top-k, XLA reference.

The hot path is ``ops/pallas/topk_dot.py`` — the item table kept on the
device ONCE, in the ``[D, Ip]`` layout the kernel reads (items on the
lanes), streamed through VMEM in tiles of thousands of items; a tile
is merged into the running [B, k] top-k only if it can change it, and
the full [B, I] logits matrix never exists in HBM. Every search a
factor model makes comes here, a lone query and a micro-batch alike
(``models/als.py ALSModel.retrieve``), and on a TPU every one inside the
kernel's caps (``_kernel_eligible``: 128 rows, k 128, 64 exclusions)
is the kernel's. The XLA brute-force scorer (``ops.topk.TopKScorer``)
remains the numerical reference and this index's own fallback: the CPU
backend, and the shapes beyond the caps. An engaged kernel that the
chip's compiler refuses raises from ``search`` — the ``ops/pallas``
design contract, applied to serving instead of training. The route
each search took is counted (``routes``) and written into the trace
(``pio:index.route``, a marker inside ``pio:index.search``).

Kernel selection mirrors ``flash_ce_kernel`` exactly: a per-index
``kernel`` flag ("auto"/"on"/"off", wired from the model params'
``index_kernel``), ``auto`` engaging only on a real TPU backend, and
``on`` running interpret mode on the CPU for the tier-1 equivalence
tests.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Dict, Optional, Tuple

import numpy as np

from predictionio_tpu.index import AnnIndex, MEASURED_RECALL
from predictionio_tpu.obs import trace
from predictionio_tpu.ops import pallas as plk

log = logging.getLogger(__name__)


class ExactIndex(AnnIndex):
    """Exact top-k by dot product over the full table.

    Results are pinned to ``ops.topk.TopKScorer.score`` (identical
    scores; identical indices modulo exact score ties when the Pallas
    kernel is engaged — tests/test_index.py).
    """

    backend = "exact"

    def __init__(self, kernel: str = "auto", max_exclude: int = 64,
                 block_items: Optional[int] = None,
                 placement: Optional[str] = None):
        self.kernel_flag = kernel
        self.max_exclude = int(max_exclude)
        #: items per kernel tile; None = ``topk_dot.tile_items``' rule
        self.block_items = block_items
        self._placement = placement
        self._scorer = None          # lazy TopKScorer fallback
        self._vectors = np.zeros((0, 1), np.float32)
        self._device_table = None    # device copy in the kernel's layout
        self._fns: Dict[Tuple[int, int, int], object] = {}
        #: (tiles, merged-tile count still on the device) of the newest
        #: kernel search; fetched by ``stats()`` alone
        self._last_merge = None
        self._lock = threading.Lock()
        self.kernel_plan: Dict[str, object] = {"engaged": False,
                                               "reason": "no build yet"}
        self.build_seconds = 0.0
        self.searches = 0
        #: which side answered each search: the Pallas kernel, or the
        #: XLA scorer on the device / its host scan (ops/topk.py).
        #: Shared with every TopKScorer this index builds, so the
        #: counts survive the scorer being dropped on build/upsert
        self.routes = {"kernel": 0, "device": 0, "host": 0}
        #: where each search's query vectors were when they arrived:
        #: host data rides the one compiled call, a ``jax.Array`` (a
        #: program's output) stays on the device
        self.inputs = {"host": 0, "device": 0}

    # -- build / upsert -------------------------------------------------------
    def build(self, item_vectors: np.ndarray) -> None:
        t0 = time.perf_counter()
        with self._lock:
            self._vectors = np.ascontiguousarray(item_vectors,
                                                 dtype=np.float32)
            self._scorer = None
            self._device_table = None
            self._fns.clear()
            self._plan_kernel()
        self.build_seconds = time.perf_counter() - t0
        self._note_build(self.build_seconds)
        self._register_mem(self._mem_nbytes())
        MEASURED_RECALL.labels(self.backend).set(1.0)  # exact by design

    def upsert(self, rows: np.ndarray, vectors: np.ndarray) -> None:
        """Overwrite/append rows copy-on-write: readers of
        ``self._vectors`` see old-or-new tables, never torn rows — the
        same publication discipline as ``ALSModel.upsert_rows``, which
        is this method's only production caller."""
        rows = np.asarray(rows, np.int64).ravel()
        vectors = np.atleast_2d(np.asarray(vectors, np.float32))
        if len(rows) == 0:
            return
        with self._lock:
            table = self._vectors
            n, d = table.shape if table.size else (0, vectors.shape[1])
            grow = int(rows.max()) + 1 - n if rows.size else 0
            if grow > 0:
                table = np.vstack(
                    [table.reshape(n, d),
                     np.zeros((grow, d), np.float32)])
            else:
                table = table.copy()
            table[rows] = vectors
            self._vectors = table
            # the kernel/fallback paths hold device copies of the OLD
            # table; drop them — a same-shape re-put hits the compile
            # cache, only appends change shapes
            self._scorer = None
            self._device_table = None
            if grow > 0:
                self._fns.clear()   # n_items is a static kernel arg
            self._note_build(self.build_seconds)
        self._register_mem(self._mem_nbytes())

    def __len__(self) -> int:
        return int(self._vectors.shape[0])

    def _mem_nbytes(self) -> int:
        """Resident bytes this index owns: the host table plus, once
        materialized, the device copy the kernel streams."""
        table = self._device_table
        return int(self._vectors.nbytes
                   + (table.nbytes if table is not None else 0))

    @property
    def vectors(self) -> np.ndarray:
        return self._vectors

    # -- kernel selection -----------------------------------------------------
    def _plan_kernel(self) -> None:
        import jax

        interpret = plk.interpret_mode()
        n = self._vectors.shape[0]
        eligible = n > 0
        reason = "empty table" if not eligible else ""
        engaged, why = plk.decide(
            self.kernel_flag, eligible=eligible, ineligible_reason=reason,
            auto_default=jax.default_backend() == "tpu",
        )
        self.kernel_plan = {"engaged": engaged, "reason": why,
                            "interpret": interpret}

    def _kernel_eligible(self, B: int, E: int, k: int) -> bool:
        """Whether the kernel answers this (bucketed) shape. The rule is
        the measured one: on the chip the kernel took less device time
        than the XLA scorer at every batch, ``k`` and exclusion bucket
        inside its caps, at D = 64 and 128 (``topk_dot.MAX_BATCH``), so
        the caps are the whole rule and ``D`` does not enter it."""
        from predictionio_tpu.ops.pallas import topk_dot as tkd

        return (bool(self.kernel_plan.get("engaged"))
                and B <= tkd.MAX_BATCH and E <= tkd.MAX_EXCLUDE
                and k <= tkd.MAX_K and k <= len(self))

    def _fn(self, B: int, E: int, k: int):
        from predictionio_tpu.ops.pallas import topk_dot as tkd

        key = (B, E, k)
        fn = self._fns.get(key)
        if fn is None:
            n, d = self._vectors.shape
            fn = tkd.make_topk_dot(
                n, d, B, k, E, block_items=self.block_items,
                interpret=bool(self.kernel_plan.get("interpret")))
            self._fns[key] = fn
        return fn

    def _device_items(self):
        from predictionio_tpu.ops.pallas import topk_dot as tkd

        # read-once: a concurrent upsert nulls the cache mid-call (the
        # patch lane runs while queries are in flight); the local ref
        # keeps this search on a consistent (old-or-new) table
        table = self._device_table
        if table is None:
            table = tkd.to_kernel_layout(self._vectors, self.block_items)
            self._device_table = table  # graftlint: disable=JT18 — lock-free lazy init by design: the store is atomic, racing fills compute identical tables and the last write wins; readers above took one local ref
            # a NEW long-lived device allocation: re-price the ledger
            # footprint with the device copy included (JT16 contract)
            self._register_mem(self._mem_nbytes())
        return table

    def _fallback(self):
        from predictionio_tpu.ops.topk import TopKScorer

        scorer = self._scorer
        if scorer is None:
            scorer = TopKScorer(self._vectors,
                                max_exclude=self.max_exclude,
                                placement=self._placement)
            scorer.routed = self.routes
            self._scorer = scorer  # graftlint: disable=JT18 — lock-free lazy init by design: racing fills build equivalent scorers over the same read-only vectors; last write wins, readers hold their local ref
        return scorer

    # -- search ---------------------------------------------------------------
    def search(self, query_vecs: np.ndarray, k: int,
               exclude: Optional[np.ndarray] = None,
               ) -> Tuple[np.ndarray, np.ndarray]:
        self._note_query()
        self.searches += 1
        from predictionio_tpu.ops.topk import (_batch_rows, _fetch,
                                               _inputs_kind,
                                               _prepare_score_inputs)

        if len(self) == 0:
            B = _batch_rows(query_vecs)
            return (np.zeros((B, 0), np.float32),
                    np.zeros((B, 0), np.int32))
        inputs = _inputs_kind(query_vecs)
        self.inputs[inputs] += 1
        # enqueue: pad in numpy, the jitted call (it carries the
        # transfer of host inputs) returning; fetch: the one wait for
        # the device and the copies back
        with trace.device_span("index.search"):
            with trace.device_span("index.enqueue"):
                q2, excl, k_eff, k_bucket, B = _prepare_score_inputs(
                    query_vecs, k, exclude, len(self), self.max_exclude)
                eligible = self._kernel_eligible(
                    q2.shape[0], excl.shape[1], k_bucket)
                if eligible:
                    fn = self._fn(q2.shape[0], excl.shape[1], k_bucket)
                    table = self._device_items()
                    scores, idx, merged = fn(q2, table, excl)
                    self._last_merge = (fn.tiles, merged)
            if not eligible:
                return self._fallback().score_unspanned(
                    query_vecs, k, exclude)
            self.routes["kernel"] += 1
            # a span's attributes are set as it opens, and the route is
            # known only once the inputs are bucketed: it rides on a
            # marker (the fallback writes its own, ops/topk.py)
            with trace.device_span("index.route", route="kernel", rows=B,
                                   inputs=inputs):
                pass
            with trace.device_span("index.fetch"):
                return _fetch(scores, idx, B, k_eff)

    def stats(self) -> Dict[str, object]:
        out = super().stats()
        kernel = dict(self.kernel_plan)
        last = self._last_merge
        if last is not None:
            # the one place the merged-tile count leaves the device
            kernel["tiles"] = last[0]
            kernel["merged_tiles"] = int(np.asarray(last[1])[0, 0])
        out.update({
            "kernel": kernel,
            "build_seconds": round(self.build_seconds, 4),
            "searches": self.searches,
            "routes": {"kernel": self.routes["kernel"],
                       "xla_device": self.routes["device"],
                       "host": self.routes["host"]},
            "inputs": dict(self.inputs),
            "max_exclude": self.max_exclude,
        })
        return out
