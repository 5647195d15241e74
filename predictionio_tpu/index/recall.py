"""Recall@k against brute force — the subsystem's equivalence currency.

Every approximate (or patched) index in this package is judged by one
number: of the true top-k items under exact dot-product scoring, what
fraction did the index return? The IVF build gate and the streaming drift
probe (``pio_stream_index_recall``) both call :func:`recall_at_k` so
they can never disagree about what "recall" means.

Ties are handled the only honest way: a retrieved item counts if its
TRUE score is >= the k-th true score (minus a float epsilon), so an
index returning a different-but-equal-scoring item is not punished for
the arbitrary half of a tie.
"""

from __future__ import annotations

from typing import Optional

import numpy as np


def brute_force_topk(vectors: np.ndarray, queries: np.ndarray, k: int):
    """(scores [B, k], idx [B, k]) by exact dot product — ONE matmul
    into the reference scorer's partial-sort (``TopKScorer._host_topk``
    owns the argpartition + canonicalize + stable-rank idiom; a copy
    here could drift from the thing recall is measured against)."""
    from predictionio_tpu.ops.topk import TopKScorer

    queries = np.atleast_2d(np.asarray(queries, np.float32))
    scores = queries @ np.asarray(vectors, np.float32).T    # [B, I]
    k = min(int(k), scores.shape[1])
    if k <= 0:
        return (np.zeros((queries.shape[0], 0), np.float32),
                np.zeros((queries.shape[0], 0), np.int64))
    return TopKScorer._host_topk(scores, k)


def recall_at_k(index, queries: np.ndarray, k: int,
                vectors: Optional[np.ndarray] = None,
                eps: float = 1e-6) -> float:
    """Mean recall@k of ``index.search`` vs brute force over
    ``vectors`` (default: the index's own table — pass the
    authoritative factor table when probing a PATCHED index for
    drift)."""
    queries = np.atleast_2d(np.asarray(queries, np.float32))
    if vectors is None:
        vectors = index.vectors
    vectors = np.asarray(vectors, np.float32)
    n = vectors.shape[0]
    k = min(int(k), n)
    if k == 0 or queries.shape[0] == 0:
        return 1.0
    true_s, _ = brute_force_topk(vectors, queries, k)
    _, got_i = index.search(queries, k)
    hits = 0
    total = queries.shape[0] * k
    for b in range(queries.shape[0]):
        kth = true_s[b, -1]
        got = got_i[b]
        got = got[(got >= 0) & (got < n)]
        if got.size == 0:
            continue
        got_true_scores = vectors[got] @ queries[b]
        hits += int(np.sum(got_true_scores >= kth - eps))
    return hits / total
