"""IVF: approximate CPU retrieval — k-means coarse quantizer + nprobe.

The host-only fallback for catalogs where brute force can't hold
serving latency without an accelerator: items are partitioned into
``nlist`` inverted lists by a k-means coarse quantizer; a query scores
the ``nprobe`` nearest lists' members only (classic IVF-Flat), with
optional per-dimension int8 quantization of the stored vectors
(IVF-SQ8: 4x less memory traffic on the scan, plus a full-precision
re-rank of the top ~4k shortlist so quantization error can't cost
recall at the k-th boundary).

Approximation is GATED, not assumed: ``build`` measures recall@k
against brute force on a sample of self-queries and raises ``nprobe``
until the measured recall clears ``PIO_INDEX_RECALL_FLOOR`` (default
0.95) or every list is probed (== brute force). The measured value is
exported on the ``pio_index_recall{backend="ivf"}`` gauge and in
``stats()`` — an operator never has to take the approximation on
faith.

Everything here is numpy partial-sorts (``np.argpartition``) — the
graftlint JT14 rule exists precisely because a stray ``argsort(...)[:k]``
on this path would silently pay O(n log n) per query.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from predictionio_tpu.index import AnnIndex, MEASURED_RECALL
from predictionio_tpu.obs import metrics
from predictionio_tpu.ops.topk import NEG_INF

log = logging.getLogger(__name__)

#: recall@k floor the build-time autotune must clear (vs brute force)
RECALL_FLOOR_ENV = "PIO_INDEX_RECALL_FLOOR"
DEFAULT_RECALL_FLOOR = 0.95


def _kmeans(vectors: np.ndarray, nlist: int, iters: int, seed: int
            ) -> np.ndarray:
    """Lloyd's k-means on (a sample of) the vectors -> [nlist, D]
    centroids. Assignment by the expanded-L2 trick (argmax of
    v.c - |c|^2/2) so each iteration is one matmul + argmax."""
    rng = np.random.default_rng(seed)
    n = vectors.shape[0]
    sample = vectors
    if n > 20_000:
        sample = vectors[rng.choice(n, 20_000, replace=False)]
    pick = rng.choice(sample.shape[0], nlist, replace=False)
    centroids = sample[pick].copy()
    for _ in range(iters):
        assign = _assign(sample, centroids)
        for c in range(nlist):
            members = sample[assign == c]
            if len(members):
                centroids[c] = members.mean(axis=0)
            else:
                # dead list: reseed on a random vector so capacity
                # isn't silently wasted
                centroids[c] = sample[rng.integers(sample.shape[0])]
    return centroids


def _assign(vectors: np.ndarray, centroids: np.ndarray) -> np.ndarray:
    """Nearest centroid per vector under L2 -> [n] int32."""
    # argmin ||v - c||^2 == argmax (v.c - |c|^2 / 2); one GEMM
    logits = vectors @ centroids.T - 0.5 * (centroids ** 2).sum(axis=1)
    return np.argmax(logits, axis=1).astype(np.int32)


def _partial_topk(scores: np.ndarray, k: int) -> Tuple[np.ndarray, np.ndarray]:
    """(top-k scores desc, their positions) over a 1-D score vector —
    the reference scorer's partial-sort idiom (argpartition +
    canonicalize + stable rank), one row at a time."""
    from predictionio_tpu.ops.topk import TopKScorer

    k = min(k, scores.shape[0])
    if k <= 0:
        return np.zeros(0, np.float32), np.zeros(0, np.int64)
    s, i = TopKScorer._host_topk(scores[None, :], k)
    return s[0], i[0]


class IVFIndex(AnnIndex):
    """IVF-Flat / IVF-SQ8 over a host vector table."""

    backend = "ivf"

    def __init__(self, nlist: Optional[int] = None,
                 nprobe: Optional[int] = None,
                 quantize: Optional[str] = None,
                 kmeans_iters: int = 8, seed: int = 17,
                 recall_floor: Optional[float] = None,
                 recall_sample: int = 64, recall_k: int = 10):
        self.nlist = nlist if nlist is None else int(nlist)
        self.nprobe = nprobe if nprobe is None else int(nprobe)
        import os

        if quantize is None:
            quantize = os.environ.get("PIO_INDEX_QUANT", "off")
        self.quantize = str(quantize).strip().lower() in ("int8", "1",
                                                          "on", "true")
        self.kmeans_iters = int(kmeans_iters)
        self.seed = int(seed)
        self.recall_floor = (
            recall_floor if recall_floor is not None
            else metrics.env_float(RECALL_FLOOR_ENV, DEFAULT_RECALL_FLOOR))
        self.recall_sample = int(recall_sample)
        self.recall_k = int(recall_k)
        self._lock = threading.Lock()
        self._vectors = np.zeros((0, 1), np.float32)
        self._centroids: Optional[np.ndarray] = None
        self._lists: List[np.ndarray] = []
        self._codes: Optional[np.ndarray] = None   # int8 [I, D]
        self._scale: Optional[np.ndarray] = None   # f32 [D]
        self.measured_recall: Optional[float] = None
        self.build_seconds = 0.0
        self.searches = 0

    # -- build ----------------------------------------------------------------
    def build(self, item_vectors: np.ndarray) -> None:
        t0 = time.perf_counter()
        vectors = np.ascontiguousarray(item_vectors, dtype=np.float32)
        n = vectors.shape[0]
        with self._lock:
            self._vectors = vectors
            if n == 0:
                self._centroids, self._lists = None, []
                self._codes = self._scale = None
                self.measured_recall = 1.0
            else:
                nlist = self.nlist or max(1, min(
                    int(round(np.sqrt(n))), n, 4096))
                nlist = min(nlist, n)
                self._centroids = _kmeans(vectors, nlist,
                                          self.kmeans_iters, self.seed)
                assign = _assign(vectors, self._centroids)
                self._lists = [
                    np.flatnonzero(assign == c).astype(np.int64)
                    for c in range(nlist)]
                self._requantize()
        if n:
            self._autotune_nprobe()
        self.build_seconds = time.perf_counter() - t0
        self._note_build(self.build_seconds)
        self._register_mem(self._mem_nbytes())
        if self.measured_recall is not None:
            MEASURED_RECALL.labels(self.backend).set(self.measured_recall)

    def _requantize(self) -> None:
        if not self.quantize:
            self._codes = self._scale = None
            return
        v = self._vectors
        self._scale = np.maximum(np.abs(v).max(axis=0), 1e-12) / 127.0
        self._codes = np.clip(np.round(v / self._scale), -127, 127
                              ).astype(np.int8)

    def _autotune_nprobe(self) -> None:
        """Raise nprobe until sampled recall@k vs brute force clears
        the floor (or every list is probed — exact). An explicitly
        configured nprobe is still MEASURED (the gauge must tell the
        truth) but never overridden."""
        from predictionio_tpu.index.recall import recall_at_k

        rng = np.random.default_rng(self.seed + 1)
        n = self._vectors.shape[0]
        sample = self._vectors[
            rng.choice(n, min(self.recall_sample, n), replace=False)]
        k = min(self.recall_k, n)
        if self.nprobe is not None:
            self.measured_recall = recall_at_k(
                self, sample, k, vectors=self._vectors)
            return
        nprobe = 1
        nlist = len(self._lists)
        while True:
            self.nprobe = nprobe
            self.measured_recall = recall_at_k(
                self, sample, k, vectors=self._vectors)
            if self.measured_recall >= self.recall_floor or nprobe >= nlist:
                break
            nprobe = min(nprobe * 2, nlist)
        if self.measured_recall < self.recall_floor:
            log.warning(
                "ivf index recall@%d %.3f below floor %.2f even at "
                "nprobe=nlist=%d — vectors may be degenerate",
                k, self.measured_recall, self.recall_floor, nlist)

    # -- upsert ---------------------------------------------------------------
    def upsert(self, rows: np.ndarray, vectors: np.ndarray) -> None:
        rows = np.asarray(rows, np.int64).ravel()
        vectors = np.atleast_2d(np.asarray(vectors, np.float32))
        if len(rows) == 0:
            return
        if self._centroids is None:
            # first rows into an empty index: a real build (and its
            # recall gate) is the only honest path
            table = np.zeros((int(rows.max()) + 1, vectors.shape[1]),
                             np.float32)
            table[rows] = vectors
            self.build(table)
            return
        with self._lock:
            table = self._vectors
            n, d = table.shape
            grow = int(rows.max()) + 1 - n
            if grow > 0:
                table = np.vstack([table, np.zeros((grow, d), np.float32)])
            else:
                table = table.copy()
            table[rows] = vectors
            self._vectors = table
            # re-list the touched rows under the FIXED quantizer (the
            # standard IVF upsert: centroids move only on rebuild)
            new_assign = _assign(vectors, self._centroids)
            self._lists = [
                lst[~np.isin(lst, rows)] for lst in self._lists]
            for r, c in zip(rows, new_assign):
                self._lists[int(c)] = np.append(self._lists[int(c)], r)
            if self.quantize:
                # per-dim scales track the global max — recompute from
                # the updated table so a hot new row can't clip
                self._requantize()
            self._note_build(self.build_seconds)
        self._register_mem(self._mem_nbytes())

    def __len__(self) -> int:
        return int(self._vectors.shape[0])

    def _mem_nbytes(self) -> int:
        """Resident bytes: full-precision table + coarse quantizer +
        (when int8 is on) the code table."""
        total = int(self._vectors.nbytes)
        for arr in (self._centroids, self._codes, self._scale):
            if arr is not None:
                total += int(arr.nbytes)
        return total

    @property
    def vectors(self) -> np.ndarray:
        return self._vectors

    # -- search ---------------------------------------------------------------
    def _row_scores(self, q: np.ndarray, cand: np.ndarray) -> np.ndarray:
        if self.quantize:
            return (self._codes[cand].astype(np.float32)
                    * self._scale) @ q
        return self._vectors[cand] @ q

    def search(self, query_vecs: np.ndarray, k: int,
               exclude: Optional[np.ndarray] = None,
               ) -> Tuple[np.ndarray, np.ndarray]:
        self._note_query()
        self.searches += 1
        q = np.atleast_2d(np.asarray(query_vecs, np.float32))
        B = q.shape[0]
        n = len(self)
        if n == 0:
            return (np.zeros((B, 0), np.float32),
                    np.zeros((B, 0), np.int32))
        k = min(int(k), n)
        with self._lock:
            centroids, lists = self._centroids, self._lists
        nprobe = min(self.nprobe or 1, len(lists))
        excl = None
        if exclude is not None:
            excl = np.atleast_2d(np.asarray(exclude, np.int64))
            if excl.shape[0] == 1 and B > 1:
                excl = np.broadcast_to(excl, (B, excl.shape[1]))
        out_s = np.full((B, k), float(NEG_INF), np.float32)
        out_i = np.full((B, k), -1, np.int32)
        cent_scores = q @ centroids.T          # [B, nlist]
        for b in range(B):
            _, probe_lists = _partial_topk(cent_scores[b], nprobe)
            cand = np.concatenate([lists[int(c)] for c in probe_lists]) \
                if len(probe_lists) else np.zeros(0, np.int64)
            if cand.size == 0:
                continue
            scores = self._row_scores(q[b], cand)
            drop = np.zeros(0, np.int64)
            if excl is not None:
                drop = excl[b]
                drop = drop[(drop >= 0) & (drop < n)]
                if drop.size:
                    scores = np.where(np.isin(cand, drop),
                                      float(NEG_INF), scores)
            if self.quantize:
                # SQ8-with-refine: the int8 scan picks a shortlist, a
                # full-precision re-rank of the top ~4k fixes the
                # orderings quantization flipped at the k-th boundary
                # (without it measured recall stalls ~0.93 on the
                # tier-1 fixture)
                m = min(scores.shape[0], max(4 * k, 32))
                _, pos = _partial_topk(scores, m)
                shortlist = cand[pos]
                rescored = self._vectors[shortlist] @ q[b]
                if drop.size:
                    rescored = np.where(np.isin(shortlist, drop),
                                        float(NEG_INF), rescored)
                s, pos2 = _partial_topk(rescored, k)
                out_s[b, :len(s)] = s
                out_i[b, :len(s)] = shortlist[pos2]
            else:
                s, pos = _partial_topk(scores, k)
                out_s[b, :len(s)] = s
                out_i[b, :len(s)] = cand[pos]
        return out_s, out_i

    def stats(self) -> Dict[str, object]:
        out = super().stats()
        out.update({
            "nlist": len(self._lists),
            "nprobe": self.nprobe,
            "quantize": "int8" if self.quantize else "off",
            "measured_recall": (None if self.measured_recall is None
                                else round(self.measured_recall, 4)),
            "recall_floor": self.recall_floor,
            "build_seconds": round(self.build_seconds, 4),
            "searches": self.searches,
        })
        return out
