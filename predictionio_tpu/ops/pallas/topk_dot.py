"""Fused dot + top-k: exact retrieval's hot path as one Pallas kernel.

What it replaces: the XLA brute-force scorer (``ops.topk._topk_scores``)
computes the FULL ``[B, I]`` logits matrix — at millions of items that
is the one array the whole retrieval design cannot afford to
materialize in HBM (the JAMPI lesson from PAPERS.md restated for
tall-skinny retrieval matmuls: the matmul is cheap, the intermediate is
not). Here the item table streams through VMEM in ``[bi, D]`` tiles;
each grid step computes its tile's partial dots ON the MXU and merges
them into a running ``[B, k]`` top-k held in VMEM — the only HBM
traffic is the item table read (once) and the final ``[B, k]`` pair.

Merge strategy: a tournament between the running top-k ``R`` and the
tile scores ``S`` — ``k`` unrolled rounds of (row-max of each side,
take the winner, retire its slot). Only max / where / iota / reductions
— no sort primitive, nothing Mosaic can't lower. Ties resolve to the
earliest retired candidate (the running side wins a tied round), which
matches ``jax.lax.top_k``'s lowest-index preference across tiles but
not necessarily within one — the equivalence contract is therefore
"identical scores, identical indices modulo exact score ties"
(tests/test_index.py pins it).

Exclusions arrive as GLOBAL item ids (``[B, E]``, -1 padding, the
``ops.topk`` wire format) and are compared against the tile's global-id
iota — one unrolled ``where`` per exclusion column, so the kernel
never needs a scatter.

Selection contract (ops/pallas/__init__.py): the XLA scorer REMAINS
the reference; ``index/exact.py`` engages this kernel per-index via
:func:`predictionio_tpu.ops.pallas.decide` (``index_kernel="auto"`` +
``PIO_INDEX_KERNEL``): compiled on a TPU, interpret-mode on CPU for
tier-1.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from predictionio_tpu.ops.topk import NEG_INF

#: default item-tile rows: 512 x D=128 f32 = 256 KB in VMEM, a few
#: MXU passes per tile — small enough to double-buffer, big enough to
#: amortize the k-round merge
BLOCK_ITEMS = 512

#: eligibility caps — beyond these the unrolled merge/exclusion loops
#: outgrow their usefulness and the XLA fallback wins anyway
MAX_K = 128
MAX_EXCLUDE = 64
MAX_BATCH = 128


def _row_max_take(scores, idx, pos, n):
    """One tournament step over a [B, n] candidate row: (max score
    [B,1], its candidate's idx [B,1], scores with that slot retired).
    The winner among equal maxima is the LOWEST position — stable the
    way ``lax.top_k`` is."""
    m = jnp.max(scores, axis=1, keepdims=True)
    first = jnp.min(jnp.where(scores == m, pos, n), axis=1, keepdims=True)
    sel = pos == first
    won_idx = jnp.sum(jnp.where(sel, idx, 0), axis=1, keepdims=True)
    return m, won_idx, jnp.where(sel, NEG_INF, scores)


def _topk_dot_kernel(q_ref, it_ref, excl_ref, s_ref, i_ref,
                     *, bi, k, n_excl, n_valid):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _():
        s_ref[...] = jnp.full_like(s_ref, NEG_INF)
        i_ref[...] = jnp.full_like(i_ref, -1)

    # [B, bi] partial dots on the MXU, f32 accumulation
    S = jax.lax.dot_general(
        q_ref[...], it_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    B = S.shape[0]
    gid = j * bi + jax.lax.broadcasted_iota(jnp.int32, (1, bi), 1)
    # padded tail rows (table padded up to the tile multiple) can never
    # win a slot
    S = jnp.where(gid < n_valid, S, NEG_INF)
    ex = excl_ref[...]
    for e in range(n_excl):
        # -1 pads never match a gid >= 0
        S = jnp.where(gid == ex[:, e:e + 1], NEG_INF, S)
    SI = jnp.broadcast_to(gid, (B, bi)).astype(jnp.int32)

    # tournament merge: k rounds of running-top-k R vs tile S; ties go
    # to R (earlier tiles = lower global ids retire first)
    R, RI = s_ref[...], i_ref[...]
    pos_s = jax.lax.broadcasted_iota(jnp.int32, (B, bi), 1)
    pos_r = jax.lax.broadcasted_iota(jnp.int32, (B, k), 1)
    out_s, out_i = [], []
    for _ in range(k):
        ms, si, S_next = _row_max_take(S, SI, pos_s, bi)
        mr, ri, R_next = _row_max_take(R, RI, pos_r, k)
        use_r = mr >= ms
        out_s.append(jnp.where(use_r, mr, ms))
        out_i.append(jnp.where(use_r, ri, si))
        S = jnp.where(use_r, S, S_next)
        R = jnp.where(use_r, R_next, R)
    s_ref[...] = jnp.concatenate(out_s, axis=1)
    i_ref[...] = jnp.concatenate(out_i, axis=1)


def make_topk_dot(n_items, D, B, k, n_excl, *, block_items=BLOCK_ITEMS,
                  interpret=False):
    """Build ``fn(q [B, D], items [Ip, D], excl [B, E]) -> (scores
    [B, k], idx [B, k])`` for one set of static shapes.

    ``items`` must be pre-padded to the ``block_items`` multiple
    (``pad_items``); padded rows and excluded ids come back as
    ``NEG_INF`` score / real-or--1 index exactly like the XLA scorer's
    masked entries. ``k`` must be <= ``n_items`` (the caller buckets)."""
    bi = int(block_items)
    Ip = -(-n_items // bi) * bi
    grid = (Ip // bi,)
    kernel = functools.partial(
        _topk_dot_kernel, bi=bi, k=int(k), n_excl=int(n_excl),
        n_valid=int(n_items))
    vm = pltpu.VMEM
    fn = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((B, D), lambda j: (0, 0), memory_space=vm),
            pl.BlockSpec((bi, D), lambda j: (j, 0), memory_space=vm),
            pl.BlockSpec((B, n_excl), lambda j: (0, 0), memory_space=vm),
        ],
        out_specs=[
            pl.BlockSpec((B, k), lambda j: (0, 0), memory_space=vm),
            pl.BlockSpec((B, k), lambda j: (0, 0), memory_space=vm),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, k), jnp.float32),
            jax.ShapeDtypeStruct((B, k), jnp.int32),
        ],
        interpret=interpret,
        # the compiled instruction's name (%topk_dot.N): how a device
        # trace's reader finds this kernel's events
        name="topk_dot",
    )
    return jax.jit(fn)


def pad_items(items, block_items=BLOCK_ITEMS):
    """Zero-pad the item table's rows up to the tile multiple (the
    kernel masks them via ``n_valid``)."""
    n = items.shape[0]
    pad = (-n) % block_items
    if pad == 0:
        return items
    return jnp.pad(items, ((0, pad), (0, 0)))


def topk_dot(q, items, exclude_idx, k, *, block_items=BLOCK_ITEMS,
             interpret=False):
    """One-call form for tests: (scores [B, k], idx [B, k]) over the
    unpadded ``items`` table."""
    q = jnp.asarray(q, jnp.float32)
    items = jnp.asarray(items, jnp.float32)
    excl = jnp.asarray(exclude_idx, jnp.int32)
    fn = make_topk_dot(items.shape[0], items.shape[1], q.shape[0], k,
                       excl.shape[1], block_items=block_items,
                       interpret=interpret)
    return fn(q, pad_items(items, block_items), excl)
