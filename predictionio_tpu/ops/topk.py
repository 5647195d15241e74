"""Top-k scoring by brute force: the XLA scorer and its mesh form.

One query is an embedding row, a [B, K] x [K, I] product and a
fixed-shape ``lax.top_k`` over the whole catalogue (SURVEY.md §7.5; the
reference's analogue is ALSModel.recommendProducts' driver-side
dot-product scan, MLlib MatrixFactorizationModel). What stands here:

* ``TopKScorer``: the numerical reference of the retrieval subsystem
  (``tests/test_index.py`` pins ``index/exact.py``'s kernel to it) and
  that index's own fallback for what its kernel does not take: the CPU
  backend (tier-1, ``pio eval`` on a host) and shapes beyond the
  kernel's caps. The factor models (``models/als.py``) hold none of
  their own: their every retrieval, lone or batched, is the index's.
  ``score_masked`` serves the templates whose candidates are a
  business-rule mask (``similarproduct``, ``ecommerce``).
* ``ShardedTopKScorer`` / ``make_sharded_topk``: the item table
  row-sharded over a mesh axis, for catalogues beyond one chip's HBM;
  ``ALSModel.enable_sharded_serving`` puts it in the index's place.

A ``TopKScorer`` call answers on the device or on the host (numpy
matvec + partial sort, the reference's scan): with a small catalogue a
lone query's product is microseconds of compute and a device dispatch
is all overhead, so each call is routed by a cost model (batch x
catalogue FLOPs against the backend's measured dispatch round trip;
``PIO_SERVE_PLACEMENT=device|host|auto`` overrides). The route is
counted (``routed``, the index's ``routes``), written into the trace
(``pio:index.route``) and shown in the serving status, so a 200 never
hides which side answered.
"""

from __future__ import annotations

import functools
import os
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.obs import trace

# plain numpy scalar, NOT jnp: a module-level jnp constant would
# materialize a device array at import time, initializing the XLA
# backend — which forbids a later jax.distributed.initialize() and
# breaks every multi-host entry point that imports a template first
# (the CLI train path does). jnp ops weakly-type-promote it the same.
NEG_INF = np.float32(-1e30)

# assumed host throughput for the routing cost model (conservative
# single-core sgemv); only the CROSSOVER matters, not the estimate's
# absolute accuracy, so order-of-magnitude is enough
_HOST_FLOPS = 5e9
_DEVICE_FLOPS = 5e13

_dispatch_latency: Optional[float] = None


def measured_dispatch_latency() -> float:
    """Seconds for one tiny jit dispatch + scalar readback on the
    default backend — the serving latency floor of the DEVICE path.
    Measured once per process."""
    global _dispatch_latency
    if _dispatch_latency is None:
        f = jax.jit(lambda a: a.sum())
        x = jnp.zeros((8, 128), jnp.float32)
        float(f(x))  # compile outside the timed region
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            float(f(x))
            best = min(best, time.perf_counter() - t0)
        _dispatch_latency = best
    return _dispatch_latency


@functools.partial(jax.jit, static_argnames=("k",))
def _topk_scores(
    user_vecs: jax.Array,      # [B, K]
    item_factors: jax.Array,   # [I, K]
    exclude_idx: jax.Array,    # [B, E] int32, -1 = no exclusion
    k: int,
) -> Tuple[jax.Array, jax.Array]:
    scores = user_vecs @ item_factors.T                      # [B, I] MXU
    # mask excluded items (seen items / business rules); -1 slots are
    # routed to a scratch column then dropped
    B, I = scores.shape
    padded = jnp.concatenate([scores, jnp.zeros((B, 1), scores.dtype)], axis=1)
    excl = jnp.where(exclude_idx < 0, I, exclude_idx)
    masked = jax.vmap(lambda row, e: row.at[e].set(NEG_INF))(padded, excl)
    masked = masked[:, :I]
    return jax.lax.top_k(masked, k)


@functools.partial(jax.jit, static_argnames=("k",))
def _topk_scores_masked(
    user_vecs: jax.Array,      # [B, K]
    item_factors: jax.Array,   # [I, K]
    mask: jax.Array,           # [B, I] or [I] bool, True = candidate
    k: int,
) -> Tuple[jax.Array, jax.Array]:
    """Top-k over arbitrary candidate masks (business-rule filters —
    category/whitelist predicates — computed host-side as one bool
    vector instead of per-item Python checks, ref: isCandidateItem in
    examples/scala-parallel-similarproduct/multi/.../ALSAlgorithm.scala:239)."""
    scores = user_vecs @ item_factors.T                      # [B, I] MXU
    masked = jnp.where(mask, scores, NEG_INF)
    return jax.lax.top_k(masked, k)


def _pow2_bucket(n: int, lo: int, hi: int) -> int:
    b = lo
    while b < min(n, hi):
        b *= 2
    return b


def _batch_rows(vecs) -> int:
    """Rows of a ``[D]`` or ``[B, D]`` batch, read from its shape alone
    (a device array is not copied to the host to be counted)."""
    shape = np.shape(vecs)
    return shape[0] if len(shape) > 1 else 1


def _inputs_kind(vecs) -> str:
    """``device`` for a ``jax.Array`` (a program's output, which stays
    where it is), ``host`` for everything else: what the
    ``pio:index.route`` marker and ``ExactIndex.stats()`` report."""
    return "device" if isinstance(vecs, jax.Array) else "host"


def _pad_rows(vecs, b_bucket: int):
    """``vecs`` as float32 ``[b_bucket, D]``, zero rows added. Host data
    is padded in numpy and comes back numpy: it crosses to the device
    inside the compiled call it is handed to, and no eager program runs
    for it. A ``jax.Array`` stays on the device; a program's output
    already has a bucketed shape, and one that does not is padded
    there."""
    if isinstance(vecs, jax.Array):
        vecs = jnp.asarray(vecs, dtype=jnp.float32)
        if vecs.ndim < 2:
            vecs = vecs[None, :]
        B = vecs.shape[0]
        if B < b_bucket:
            vecs = jnp.concatenate(
                [vecs, jnp.zeros((b_bucket - B, vecs.shape[1]), vecs.dtype)])
        return vecs
    vecs = np.atleast_2d(np.asarray(vecs, dtype=np.float32))
    B = vecs.shape[0]
    if B < b_bucket:
        padded = np.zeros((b_bucket, vecs.shape[1]), np.float32)
        padded[:B] = vecs
        vecs = padded
    return vecs


def _prepare_score_inputs(user_vecs, k: int, exclude_idx, n_items: int,
                          max_exclude: int):
    """Shared serve-path shape discipline for the scorers: bucket the
    BATCH to a power of two (zero-row padding — micro-batched serving
    produces arbitrary batch sizes, and every novel B would otherwise
    compile a fresh program), default/broadcast/bucket the exclusion
    lists (capped at ``max_exclude``, oldest dropped first), bucket k to
    powers of two. All of it is numpy: the exclusions always, the
    vectors when they arrive as host data (:func:`_pad_rows`), so the
    caller's one compiled call carries the transfer. Returns
    (user_vecs [B_bucket, K], exclude [B_bucket, E_bucket] int32 numpy,
    k, k_bucket, true_batch)."""
    B = _batch_rows(user_vecs)
    b_bucket = _pow2_bucket(B, 1, 1 << 30)
    user_vecs = _pad_rows(user_vecs, b_bucket)
    if exclude_idx is None:
        width, e_bucket = 0, 1
    else:
        exclude_idx = np.asarray(exclude_idx, dtype=np.int32)
        if exclude_idx.ndim == 1:
            exclude_idx = exclude_idx[None, :]     # one list for every row
        exclude_idx = exclude_idx[:, -max_exclude:]
        width = exclude_idx.shape[1]
        e_bucket = _pow2_bucket(width, 1, max_exclude)
    excl = np.full((b_bucket, e_bucket), -1, dtype=np.int32)
    if width:
        excl[:B, :width] = exclude_idx
    k = min(k, n_items)
    k_bucket = min(_pow2_bucket(k, 8, 1 << 20), n_items)
    return user_vecs, excl, k, k_bucket, B


def _fetch(scores, idx, B: int, k: int) -> Tuple[np.ndarray, np.ndarray]:
    """Both results of a search on the host, the padding cut off, after
    ONE wait: ``device_get`` starts the two copies together, so the
    second is there when the first is."""
    scores, idx = jax.device_get((scores, idx))
    return scores[:B, :k], idx[:B, :k]


class TopKScorer:
    """Precompiled scorer over a fixed item-factor matrix.

    Serve-path shape discipline: ``k``, the exclusion width and the
    batch size are bucketed to powers of two (exclusions capped at
    ``max_exclude``) so arbitrary per-request values hit a handful of
    compiled shapes instead of retracing per novel (B, E, k).

    ``placement``: "device", "host", or "auto" (default, overridable
    via PIO_SERVE_PLACEMENT) — see the module docstring. "auto" routes
    per CALL: the device path needs batch*catalog FLOPs large enough to
    amortize the measured dispatch floor, otherwise the host matvec
    answers in microseconds.
    """

    def __init__(self, item_factors: np.ndarray, max_exclude: int = 64,
                 placement: Optional[str] = None):
        self.placement = (placement
                          or os.environ.get("PIO_SERVE_PLACEMENT", "auto"))
        if self.placement not in ("auto", "device", "host"):
            raise ValueError(f"bad placement {self.placement!r}")
        self._host_factors = np.asarray(item_factors, dtype=np.float32)
        # device copy made lazily: a host-routed deployment never pays
        # HBM for the catalog
        self._device_factors: Optional[jax.Array] = None
        self.max_exclude = max_exclude
        #: calls answered by each side (plain ints: a lost increment
        #: under a race only blurs a status counter)
        self.routed = {"device": 0, "host": 0}

    @property
    def item_factors(self) -> jax.Array:
        if self._device_factors is None:
            self._device_factors = jnp.asarray(self._host_factors)
        return self._device_factors

    def _route(self, batch: int) -> str:
        route = self.placement
        if route == "auto":
            n_items, rank = self._host_factors.shape
            flops = 2.0 * batch * n_items * rank
            host_est = flops / _HOST_FLOPS + batch * n_items * 1e-9  # + partial sort
            device_est = measured_dispatch_latency() + flops / _DEVICE_FLOPS
            route = "host" if host_est < device_est else "device"
        self.routed[route] += 1
        return route

    @staticmethod
    def _host_topk(scores: np.ndarray, k: int) -> Tuple[np.ndarray, np.ndarray]:
        """Partial-sort top-k over host scores [B, I] -> ([B,k], [B,k]).

        Edge contracts pinned by tests/test_topk_edges.py (this scorer
        is the equivalence reference for predictionio_tpu/index):
        ``k >= n_items`` clamps, ``k == 0`` and empty tables return
        [B, 0], and the final k-element sort is STABLE so exact ties
        rank deterministically across calls (argpartition's arbitrary
        partition order must not leak into the answer)."""
        n_items = scores.shape[1]
        k = min(k, n_items)
        if k < n_items:
            part = np.argpartition(-scores, k - 1, axis=1)[:, :k]
            # canonicalize the partition's arbitrary order before the
            # stable rank so tied scores resolve by position, not luck
            part.sort(axis=1)
        else:
            part = np.broadcast_to(np.arange(n_items), scores.shape).copy()
        part_scores = np.take_along_axis(scores, part, axis=1)
        order = np.argsort(-part_scores, axis=1, kind="stable")
        idx = np.take_along_axis(part, order, axis=1)
        return np.take_along_axis(part_scores, order, axis=1), idx

    def _score_host(self, user_vecs, k, exclude_idx):
        """The reference's driver-side scan (MatrixFactorizationModel
        .recommendProducts), vectorized: matvec + argpartition. Same
        contract as the device path, including the max_exclude cap."""
        uv = np.atleast_2d(np.asarray(user_vecs, dtype=np.float32))
        scores = uv @ self._host_factors.T             # [B, I]
        if exclude_idx is not None:
            excl = np.asarray(exclude_idx, dtype=np.int64)
            if excl.ndim == 1:
                excl = np.broadcast_to(excl, (uv.shape[0], excl.shape[0]))
            excl = excl[:, -self.max_exclude:]
            rows = np.repeat(np.arange(uv.shape[0]), excl.shape[1])
            cols = excl.reshape(-1)
            # drop out-of-range ids too (stale blacklist after a catalog
            # shrink) — the device path's scatter silently drops them,
            # and the two routes must behave identically
            keep = (cols >= 0) & (cols < scores.shape[1])
            scores[rows[keep], cols[keep]] = float(NEG_INF)
        return self._host_topk(scores, k)

    def score(
        self,
        user_vecs: np.ndarray,
        k: int,
        exclude_idx: Optional[np.ndarray] = None,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """(scores [B, k], item_indices [B, k]); exclude_idx [B, E] with -1 padding.

        Excluded entries beyond ``max_exclude`` are dropped (oldest
        first) — callers needing exact long blacklists should filter
        host-side on the returned ranking.
        """
        with trace.device_span("index.search"):
            return self.score_unspanned(user_vecs, k, exclude_idx)

    def score_unspanned(self, user_vecs, k, exclude_idx=None):
        """:meth:`score` for a caller that holds ``pio:index.search``
        open itself (``index/exact.py`` when its kernel is not
        eligible)."""
        B_in = _batch_rows(user_vecs)
        route = self._route(B_in)
        # the same marker ``index/exact.py`` writes for its kernel
        with trace.device_span(
                "index.route", rows=B_in, inputs=_inputs_kind(user_vecs),
                route="host" if route == "host" else "xla_device"):
            pass
        if route == "host":
            return self._score_host(user_vecs, k, exclude_idx)
        # enqueue: pad in numpy, the jitted call (it carries the
        # transfer) returning; fetch: the one wait for the device and
        # the copies back
        with trace.device_span("index.enqueue"):
            user_vecs, exclude_idx, k, k_bucket, B = _prepare_score_inputs(
                user_vecs, k, exclude_idx, self.item_factors.shape[0],
                self.max_exclude)
            scores, idx = _topk_scores(
                user_vecs, self.item_factors, exclude_idx, k_bucket
            )
        with trace.device_span("index.fetch"):
            return _fetch(scores, idx, B, k)

    def score_masked(
        self,
        user_vecs: np.ndarray,
        k: int,
        mask: np.ndarray,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """(scores, item_indices) over candidates where ``mask`` is True.

        ``mask`` is [I] or [B, I] bool. Masked-out entries that still
        make the top-k (fewer candidates than k) come back with score
        <= NEG_INF — callers drop them by score threshold.
        """
        B = _batch_rows(user_vecs)
        if self._route(B) == "host":
            uv = np.atleast_2d(np.asarray(user_vecs, dtype=np.float32))
            scores = uv @ self._host_factors.T
            m = np.asarray(mask, dtype=bool)
            scores = np.where(m if m.ndim == 2 else m[None, :],
                              scores, float(NEG_INF))
            return self._host_topk(scores, k)
        b_bucket = _pow2_bucket(B, 1, 1 << 30)
        user_vecs = _pad_rows(user_vecs, b_bucket)
        mask = np.asarray(mask, dtype=bool)
        if B < b_bucket and mask.ndim == 2:
            mask = np.concatenate(
                [mask, np.zeros((b_bucket - B, mask.shape[1]), bool)])
        n_items = self.item_factors.shape[0]
        k = min(k, n_items)
        k_bucket = min(_pow2_bucket(k, 8, 1 << 20), n_items)
        scores, idx = _topk_scores_masked(
            user_vecs, self.item_factors, mask, k_bucket)
        return _fetch(scores, idx, B, k)


def make_sharded_topk(mesh, axis: str, n_items_global: int, k: int,
                      n_valid: Optional[int] = None):
    """Compile a top-k scorer whose item-factor matrix is row-sharded
    over mesh axis ``axis`` (model parallelism for catalogs larger than
    one chip's HBM — the capability the reference's driver-resident
    MatrixFactorizationModel scan can never reach).

    Per shard: score the local item slab [I/n, K] on the MXU, take a
    local top-k over GLOBAL item ids, then all-gather the [B, k]
    candidate lists over ICI and re-rank the n*k survivors — the merge
    traffic is O(n * B * k), independent of catalog size.

    Returns ``fn(user_vecs [B, K], item_shard [I/n, K], exclude [B, E])
    -> (scores [B, k], global_idx [B, k])``, replicated outputs.

    ``n_valid``: real item count when the matrix was zero-padded up to a
    shard multiple — padded rows are masked to NEG_INF so a zero score
    can never outrank genuine negatives.
    """
    from jax.sharding import PartitionSpec as P

    n_shards = mesh.shape[axis]
    if n_items_global % n_shards:
        raise ValueError(
            f"n_items_global={n_items_global} not divisible by "
            f"{n_shards} '{axis}' shards (pad the factor matrix)"
        )
    i_loc = n_items_global // n_shards

    def shard_fn(user_vecs, item_shard, exclude_idx):
        shard = jax.lax.axis_index(axis)
        offset = shard * i_loc
        scores = user_vecs @ item_shard.T                    # [B, I/n]
        B = scores.shape[0]
        if n_valid is not None and n_valid < n_items_global:
            gid = offset + jax.lax.iota(jnp.int32, i_loc)
            scores = jnp.where(gid[None, :] < n_valid, scores, NEG_INF)
        # exclusions arrive as global ids; route ones outside this
        # shard (and -1 pads) to a scratch column
        local_excl = jnp.where(
            (exclude_idx >= offset) & (exclude_idx < offset + i_loc),
            exclude_idx - offset, i_loc,
        )
        padded = jnp.concatenate(
            [scores, jnp.zeros((B, 1), scores.dtype)], axis=1)
        masked = jax.vmap(lambda row, e: row.at[e].set(NEG_INF))(
            padded, local_excl)[:, :i_loc]
        k_loc = min(k, i_loc)
        loc_scores, loc_idx = jax.lax.top_k(masked, k_loc)    # [B, k_loc]
        glob_idx = loc_idx + offset
        # ICI merge: every shard sees all candidates, re-ranks locally
        all_scores = jax.lax.all_gather(loc_scores, axis, axis=1)  # [B, n, k_loc]
        all_idx = jax.lax.all_gather(glob_idx, axis, axis=1)
        flat_s = all_scores.reshape(B, n_shards * k_loc)
        flat_i = all_idx.reshape(B, n_shards * k_loc)
        top_s, pos = jax.lax.top_k(flat_s, min(k, n_shards * k_loc))
        top_i = jnp.take_along_axis(flat_i, pos, axis=1)
        return top_s, top_i

    fn = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(), P(axis, None), P()),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return jax.jit(fn)


class ShardedTopKScorer:
    """TopKScorer drop-in whose item-factor matrix is row-sharded over a
    mesh axis — serving for catalogs larger than one chip's HBM. Same
    ``score`` signature/bucketing as TopKScorer; compiled merge kernels
    cached per k bucket."""

    def __init__(self, item_factors: np.ndarray, mesh, axis: str = "data",
                 max_exclude: int = 64):
        from predictionio_tpu.parallel.mesh import named_sharding

        self.mesh, self.axis, self.max_exclude = mesh, axis, max_exclude
        item_factors = np.asarray(item_factors, dtype=np.float32)
        self.n_items = item_factors.shape[0]
        n_shards = mesh.shape[axis]
        pad = (-self.n_items) % n_shards
        if pad:
            item_factors = np.concatenate(
                [item_factors,
                 np.zeros((pad, item_factors.shape[1]), np.float32)])
        self.n_padded = item_factors.shape[0]
        self.item_factors = jax.device_put(
            jnp.asarray(item_factors), named_sharding(mesh, axis, None))
        self._fns = {}

    def _fn(self, k: int):
        if k not in self._fns:
            self._fns[k] = make_sharded_topk(
                self.mesh, self.axis, self.n_padded, k, n_valid=self.n_items)
        return self._fns[k]

    def score(
        self,
        user_vecs: np.ndarray,
        k: int,
        exclude_idx: Optional[np.ndarray] = None,
    ) -> Tuple[np.ndarray, np.ndarray]:
        user_vecs, exclude_idx, k, k_bucket, B = _prepare_score_inputs(
            user_vecs, k, exclude_idx, self.n_items, self.max_exclude)
        scores, idx = self._fn(k_bucket)(
            user_vecs, self.item_factors, exclude_idx)
        return _fetch(scores, idx, B, k)


def cosine_normalize(m: np.ndarray, eps: float = 1e-8) -> np.ndarray:
    """Row-normalize so dot products become cosine similarities
    (similarproduct-template scoring)."""
    norms = np.linalg.norm(m, axis=1, keepdims=True)
    return m / np.maximum(norms, eps)
