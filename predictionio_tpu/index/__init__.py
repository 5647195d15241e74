"""ANN candidate-generation indexes: the retrieval subsystem.

Until this subsystem, "recommend" meant SCORING — every serve path
(``ops.topk``, the templates' predict methods) ranks candidates the
caller already has. Candidate GENERATION at catalog scale ("user ->
top-k of millions of items", "item -> top-k similar items") is what the
reference's MLlib ancestry never had (ALS serving ends at
``predict(user, item)``) and what this package adds:

  :class:`AnnIndex`     the one retrieval interface every backend
                        implements: ``build`` / ``search`` / ``upsert``
                        / ``stats``.
  ``index/exact.py``    exact on-device retrieval: a fused Pallas
                        dot+top-k kernel (``ops/pallas/topk_dot.py`` —
                        item table read once per search in the layout
                        it is stored in, never a [B, I] logits matrix
                        in HBM) with the XLA brute-force scorer
                        (``ops.topk``) as the reference and fallback.
  ``index/ivf.py``      approximate CPU fallback: k-means coarse
                        quantizer + ``nprobe`` inverted-list search,
                        optional int8 per-dim quantization — gated at
                        build time by measured recall@k against brute
                        force (``PIO_INDEX_RECALL_FLOOR``, default
                        0.95).
  ``index/recall.py``   recall@k measurement vs brute force — the
                        equivalence currency of the whole subsystem
                        (IVF build gate, the streaming drift
                        probe in workflow/stream.py).

Models expose ``retrieval_index()`` (ALS / two-tower / similarproduct
share the factor-table container); the engine server builds and warms
the index at model load, and the streaming ``POST /model/patch`` lane
lands fold-in rows in the index via ``upsert`` — freshness reaches
retrieval, not just scoring.

Backend selection: ``make_index(vectors, backend=...)`` with
``PIO_INDEX_BACKEND`` (``auto`` | ``exact`` | ``ivf``) overriding the
argument. ``auto`` = exact: on an accelerator the fused
kernel IS the fast path, and on CPU the exact fallback is still the
correct default — IVF is the explicit opt-in for host-only serving of
catalogs where brute force can't hold latency.
"""

from __future__ import annotations

import abc
import os
from typing import Dict, Optional, Tuple

import numpy as np

from predictionio_tpu.obs import metrics

BUILD_SECONDS = metrics.gauge(
    "pio_index_build_seconds",
    "Wall seconds of the last ANN index build, per backend",
    ("backend",),
)
SIZE_ITEMS = metrics.gauge(
    "pio_index_size_items",
    "Items currently held by the ANN index, per backend",
    ("backend",),
)
QUERIES_TOTAL = metrics.counter(
    "pio_index_queries_total",
    "ANN index search calls, per backend",
    ("backend",),
)
MEASURED_RECALL = metrics.gauge(
    "pio_index_recall",
    "Last measured recall@k of the index against brute force, per "
    "backend (exact backends pin 1.0; IVF measures at build)",
    ("backend",),
)

BACKENDS = ("exact", "ivf")


class AnnIndex(abc.ABC):
    """One retrieval index over a ``[I, D]`` float32 vector table.

    Contract shared by every backend:

      - ``search`` scores by DOT PRODUCT (cosine when the caller's
        table is row-normalized — two-tower towers are, ALS factors are
        not) and returns ``(scores [B, k], idx [B, k])`` with masked /
        unfillable slots at ``score <= NEG_INF`` — identical to the
        ``ops.topk`` scorer's contract, because that scorer IS the
        equivalence reference;
      - ``exclude`` entries are row indices (-1 padded, per the
        ``ops.topk`` wire format) or None;
      - ``upsert`` lands streaming fold-in rows (overwrite existing
        rows, append brand-new ones) without a rebuild — the
        ``POST /model/patch`` freshness lane ends here;
      - ``stats()`` is the operator surface (engine-server status
        page).
    """

    backend: str = "abstract"

    #: device-memory ledger attribution (obs/memacct.py): the owning
    #: model sets this to ITS label before build, so the index's bytes
    #: land under pio_model_device_bytes{model=<owner>,component=index}
    mem_model: Optional[str] = None

    @abc.abstractmethod
    def build(self, item_vectors: np.ndarray) -> None:
        """(Re)build over the full table; records build metrics."""

    @abc.abstractmethod
    def search(self, query_vecs: np.ndarray, k: int,
               exclude: Optional[np.ndarray] = None,
               ) -> Tuple[np.ndarray, np.ndarray]:
        """Top-``k`` rows by dot product -> (scores [B,k], idx [B,k])."""

    @abc.abstractmethod
    def upsert(self, rows: np.ndarray, vectors: np.ndarray) -> None:
        """Overwrite (or append, when ``rows == len(index)``) the given
        row indices with new vectors — the streaming patch lane."""

    @abc.abstractmethod
    def __len__(self) -> int:
        ...

    def stats(self) -> Dict[str, object]:
        return {"backend": self.backend, "size": len(self)}

    # -- shared bookkeeping ---------------------------------------------------
    def _note_build(self, seconds: float) -> None:
        BUILD_SECONDS.labels(self.backend).set(seconds)
        SIZE_ITEMS.labels(self.backend).set(float(len(self)))

    def _register_mem(self, nbytes: int) -> None:
        """Price this index's resident tables in the device-memory
        ledger (obs/memacct.py) — build/upsert/device-copy seams call
        it with their current total, re-pricing under the same owner."""
        from predictionio_tpu.obs import memacct

        memacct.LEDGER.register(
            self, self.mem_model or f"index:{self.backend}", "index",
            int(nbytes))

    def _note_query(self) -> None:
        QUERIES_TOTAL.labels(self.backend).inc()


def resolve_backend(backend: Optional[str] = None) -> str:
    """``PIO_INDEX_BACKEND`` beats the argument (the deployment's
    choice over the engine.json's); ``auto`` -> exact."""
    value = os.environ.get("PIO_INDEX_BACKEND") or backend or "auto"
    value = str(value).strip().lower()
    if value in ("auto", ""):
        return "exact"
    if value not in BACKENDS:
        raise ValueError(
            f"unknown index backend {value!r} — one of auto/exact/ivf")
    return value


def make_index(item_vectors: Optional[np.ndarray] = None,
               backend: Optional[str] = None,
               kernel: str = "auto",
               **kwargs) -> AnnIndex:
    """Build an index over ``item_vectors`` (or an empty one to fill
    later). ``kernel`` is the exact backend's Pallas flag
    (``index_kernel`` on the model params: on/off/auto, exactly like
    ``flash_ce_kernel``)."""
    name = resolve_backend(backend)
    if name == "exact":
        from predictionio_tpu.index.exact import ExactIndex

        index: AnnIndex = ExactIndex(kernel=kernel, **kwargs)
    else:
        from predictionio_tpu.index.ivf import IVFIndex

        index = IVFIndex(**kwargs)
    if item_vectors is not None:
        index.build(np.asarray(item_vectors, np.float32))
    return index


__all__ = [
    "AnnIndex",
    "BACKENDS",
    "make_index",
    "resolve_backend",
    "BUILD_SECONDS",
    "SIZE_ITEMS",
    "QUERIES_TOTAL",
    "MEASURED_RECALL",
]
