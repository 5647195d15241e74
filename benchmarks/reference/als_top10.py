"""Plain reference for ALS top-k serving: float32 numpy ``X[u] @ Y.T``.

It imports nothing of the program and takes nothing the program made: the
factors are the benchmark's own seeded arrays. Two numbers come out of a
sample of served answers, each relative to the query's best reference score:

* ``score_err``: the widest gap between a served score and the reference's
  score for that same item;
* ``rank_gap``: the widest gap by which a served item's reference score lies
  below the reference's k-th best (0 when every served item is a true top-k
  item).

``lower_precision_answers`` is the control: the same reference computed with
its inputs rounded to a lower precision (products of rounded inputs,
accumulated in float32 — what a matrix unit does with such inputs), put in
the program's place.
"""

from __future__ import annotations

import numpy as np

BLOCK_ITEMS = 1 << 19


def compare(X, Y, sample, k: int) -> dict:
    """``sample``: [(user_row, [(item_row, served_score), ...]), ...].

    One pass over the items in blocks: a served answer's lowest reference
    score is a threshold, and only reference scores above it can belong to
    the reference's top k — a handful per query — so no [Q, n_items] sort or
    partition is needed."""
    Y = np.asarray(Y, np.float32)
    n_items = Y.shape[0]
    good, malformed = [], 0
    for u, answer in sample:
        items = [i for i, _ in answer]
        if (len(items) != k or len(set(items)) != k
                or min(items) < 0 or max(items) >= n_items):
            malformed += 1
        else:
            good.append((u, answer))
    if not good:
        return {"score_err": 0.0, "rank_gap": 0.0, "malformed": malformed,
                "compared": 0}
    Xq = np.ascontiguousarray(X[np.array([u for u, _ in good], np.int64)],
                              np.float32)
    items = np.array([[i for i, _ in a] for _, a in good], np.int64)
    served = np.array([[s for _, s in a] for _, a in good], np.float32)
    ref = np.einsum("qkd,qd->qk", Y[items], Xq)        # [Q, k]
    floor = ref.min(axis=1)
    above = [[] for _ in good]      # reference scores above the threshold
    for lo in range(0, n_items, BLOCK_ITEMS):
        S = Xq @ Y[lo:lo + BLOCK_ITEMS].T
        qi, ji = np.nonzero(S > floor[:, None])
        for q, v in zip(qi.tolist(), S[qi, ji].tolist()):
            above[q].append(v)
    score_err = rank_gap = 0.0
    for q in range(len(good)):
        # the reference's k best: everything above the served minimum,
        # and the served minimum itself
        top = np.sort(np.array(above[q] + [floor[q]], np.float32))[::-1][:k]
        scale = max(float(abs(top[0])), 1e-30)
        score_err = max(score_err,
                        float(np.abs(served[q] - ref[q]).max()) / scale)
        rank_gap = max(rank_gap,
                       float(max(0.0, top[-1] - floor[q])) / scale)
    return {"score_err": score_err, "rank_gap": rank_gap,
            "malformed": malformed, "compared": len(good)}


def round_to(a: np.ndarray, dtype_name: str) -> np.ndarray:
    import ml_dtypes

    dt = {"bfloat16": ml_dtypes.bfloat16,
          "float8_e4m3fn": ml_dtypes.float8_e4m3fn}[dtype_name]
    return np.asarray(a, np.float32).astype(dt).astype(np.float32)


def lower_precision_answers(X, Y, user_rows, k: int, dtype_name: str):
    """The control's answers for ``user_rows``: inputs rounded to
    ``dtype_name``, exact top-k of the resulting scores."""
    Xq = round_to(X[np.asarray(user_rows, np.int64)], dtype_name)
    best_s = best_i = None
    for lo in range(0, Y.shape[0], BLOCK_ITEMS):
        S = Xq @ round_to(Y[lo:lo + BLOCK_ITEMS], dtype_name).T
        idx = np.argpartition(-S, min(k, S.shape[1]) - 1, axis=1)[:, :k]
        s = np.take_along_axis(S, idx, axis=1)
        idx = idx + lo
        if best_s is not None:
            s = np.concatenate([best_s, s], axis=1)
            idx = np.concatenate([best_i, idx], axis=1)
            keep = np.argpartition(-s, k - 1, axis=1)[:, :k]
            s = np.take_along_axis(s, keep, axis=1)
            idx = np.take_along_axis(idx, keep, axis=1)
        best_s, best_i = s, idx
    order = np.argsort(-best_s, axis=1)
    best_s = np.take_along_axis(best_s, order, axis=1)
    best_i = np.take_along_axis(best_i, order, axis=1)
    return [(int(u), [(int(i), float(s)) for i, s in zip(bi, bs)])
            for u, bi, bs in zip(user_rows, best_i, best_s)]


def control(bench) -> dict:
    """The control's readings at the cell's own size: for each lower
    precision, what ``compare`` says of the control's answers to a seeded
    sample of users (host numpy; needs no device)."""
    builder = bench.load_module("models", bench.config["engine"])
    X, Y = builder.make_factors(bench)
    k = int(bench.traffic["num"])
    n = int(bench.traffic["check_sample"])
    rows = bench.lib("seeded").rng(bench.seed, 99).integers(
        0, X.shape[0], size=n).tolist()
    out = {}
    for dtype_name in ("bfloat16", "float8_e4m3fn"):
        answers = lower_precision_answers(X, Y, rows, k, dtype_name)
        out[dtype_name] = compare(X, Y, answers, k)
    return out
