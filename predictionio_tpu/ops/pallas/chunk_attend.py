"""A prefill chunk's latent attention in one kernel: the walk over a slot's
cached latents with scores and probabilities in VMEM only.

What it replaces for ``ops/mla.prefill_chunk`` is the ``fori_loop`` of
``ops/attention.attend_over_blocks`` over ``ops/mla.expand``: a string of XLA
fusions a block, with a block's ``[64, 512, 512]`` float32 scores, its
probabilities and the running numerator ``o [512, 64, 256]`` crossing HBM
between them (134 MB of scores a block a layer at GLM-5's widths, where the
products of the same block take 0.16 ms at the bf16 peak). Here the grid walks
the HEADS (:data:`HEADS_A_STEP` a step); inside a step a loop walks the slot's
blocks as far as the chunk reaches (``n_blocks``, a prefetched scalar: ONE compiled program for
every history length, and a chunk at offset 0 pays for one block): the
block's latents arrive by a double-buffered copy from where the cache holds
them, are expanded by each head's columns of ``w_ukv`` (keys and values in
the weights' type, as ``ops/mla.expand`` rounds them), and are folded into the
head's running softmax by :func:`fold`, which is ``ops/attention._accum_block``
for one head: ``s = (q . k^T) * scale`` accumulated in float32; the causal mask
from the positions, and-ed with the row's own ``keep`` where an index gives
one; ``m``, ``alpha``, ``p = exp(s - m)``, ``l`` in float32; ``p`` cast to the
values' type before ``p . v``; a masked score is ``_NEG``, not ``-inf``, so a
row that keeps nothing of its first blocks is wiped by its first kept key's
``alpha`` as ``attend_over_blocks`` states. ``m``, ``l`` and ``o`` of a head
stay in VMEM over its blocks; ``o / l`` leaves once a head.

The shared RoPE key is not copied to every head in HBM: a block's is cut from
its latents in VMEM. It goes to lanes ``[rope_at, rope_at + d_rope)`` of the
key, ``rope_at`` the last whole 128-lane row inside ``d_nope`` (the latent
holds it at the start of such a row, so the move shifts no lane); the caller
lays the queries out the same way and, where ``rope_at < d_nope``, hands
``w_uk`` with ``d_rope`` zero columns there (:func:`key_layout`). A product
does not care in which order its terms are laid out.

Selection (ops/pallas/__init__.py's contract, without a flag): ``ops/mla
.prefill_chunk`` calls it, with and without an index; compiled on a TPU, under
the interpreter elsewhere. ``_accum_block`` under ``attend_over_blocks`` stays
the reference of the tests and the path of every other caller.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from predictionio_tpu.ops.attention import _NEG

#: lanes of a vector register row
LANES = 128


def fold(q, k, v, m, l, o, mask, scale):
    """One head's online-softmax update, ``ops/attention._accum_block`` with
    its roundings: ``q`` [C, Dk], ``k`` [block, Dk], ``v`` [block, Dv] in one
    type, ``m`` / ``l`` [C, 1] and ``o`` [C, Dv] float32, ``mask`` [C, block]
    bool. ``(m, l, o)`` after the block."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask, s, _NEG)
    m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new)
    return (m_new, l * alpha + p.sum(axis=-1, keepdims=True),
            o * alpha + jnp.dot(p.astype(v.dtype), v,
                                preferred_element_type=jnp.float32))


def key_layout(d_nope: int, d_rope: int):
    """``(rope_at, width of w_uk as handed over)``: where a key holds the
    shared RoPE key, and how wide the key expansion's weights are once the
    zero columns are in (``d_nope`` where none are needed)."""
    rope_at = d_nope // LANES * LANES if d_nope >= LANES else d_nope
    return rope_at, d_nope + (d_rope if rope_at < d_nope else 0)


def laid_out(x_nope, x_rope, rope_at: int):
    """``[..., d_nope]`` and ``[..., d_rope]`` in the key's layout: the RoPE
    part at ``rope_at`` (queries: their own; ``w_uk``: zero columns)."""
    return jnp.concatenate(
        [x_nope[..., :rope_at], x_rope, x_nope[..., rope_at:]], axis=-1)


#: heads a grid step takes: a block's latents and mask are copied in once
#: for all of them. One mixer's chunk at the cells' last offsets, ms a call
#: for 1 | 2 | 4 heads a step (tools/chunk_attend_probe.py, PERF.md section
#: 5): GLM-5 17.03 | 16.45 | 16.69, A.X-K1 9.40 | 7.73 | 7.74, LongCat 3.13 |
#: 2.71 | 2.72 (at two the copies hide behind the products); folding the
#: chunk's rows 256 or 128 at a time instead of all 512 lost 5-18%
HEADS_A_STEP = 2


def _walk_kernel(meta_ref, q_ref, wk_ref, wv_ref, lat_hbm, *rest, kv_rank,
                 d_rope, rope_at, scale, masked):
    if masked:
        keep_hbm, o_ref, lat_buf, keep_buf, k_buf, m_ref, l_ref, sem = rest
    else:
        o_ref, lat_buf, k_buf, m_ref, l_ref, sem = rest
    slot, offset, n_blocks = meta_ref[0], meta_ref[1], meta_ref[2]
    G, C, _ = q_ref.shape
    block, wide = lat_buf.shape[1], wk_ref.shape[-1]

    def copies(j, b):
        at = pl.ds(pl.multiple_of(j * block, block), block)
        out = [pltpu.make_async_copy(lat_hbm.at[slot, at, :], lat_buf.at[b],
                                     sem.at[0, b])]
        if masked:
            out.append(pltpu.make_async_copy(keep_hbm.at[:, at],
                                             keep_buf.at[b], sem.at[1, b]))
        return out

    for copy in copies(0, 0):
        copy.start()
    m_ref[...] = jnp.full_like(m_ref, _NEG)
    l_ref[...] = jnp.zeros_like(l_ref)
    o_ref[...] = jnp.zeros_like(o_ref)
    q_pos = offset + jax.lax.broadcasted_iota(jnp.int32, (C, block), 0)
    k_in_block = jax.lax.broadcasted_iota(jnp.int32, (C, block), 1)

    def one_block(j, _):
        b = j % 2

        @pl.when(j + 1 < n_blocks)
        def _():
            for copy in copies(j + 1, 1 - b):
                copy.start()

        for copy in copies(j, b):
            copy.wait()  # graftlint: disable=JT12 — a DMA's semaphore inside the kernel, no thread
        c = lat_buf[b, :, :kv_rank]
        for g in range(G):
            k_buf[:, :wide] = jnp.dot(
                c, wk_ref[g], preferred_element_type=jnp.float32
            ).astype(k_buf.dtype)
            k_buf[:, rope_at:rope_at + d_rope] = lat_buf[
                b, :, kv_rank:kv_rank + d_rope]
            v = jnp.dot(c, wv_ref[g], preferred_element_type=jnp.float32
                        ).astype(k_buf.dtype)
            mask = q_pos >= j * block + k_in_block
            if masked:
                mask = mask & (keep_buf[b].astype(jnp.int32) != 0)
            m_ref[g], l_ref[g], o_ref[g] = fold(
                q_ref[g], k_buf[...], v, m_ref[g], l_ref[g], o_ref[g], mask,
                scale)

    jax.lax.fori_loop(0, n_blocks, one_block, None)
    o_ref[...] = o_ref[...] / jnp.maximum(l_ref[...], 1e-30)


def chunk_attend(q, w_uk, w_uv, latents, slot, offset, n_blocks, *,
                 block: int, kv_rank: int, d_rope: int, rope_at: int,
                 scale: float, keep=None, interpret=False):
    """Causal attention of one chunk over ITS slot of cached latents,
    :data:`HEADS_A_STEP` heads a grid step: ``q`` [H, C, Dk] (the chunk's
    queries at positions ``offset + arange(C)``, in the key's layout:
    :func:`laid_out`), ``w_uk`` [H, kv_rank, :func:`key_layout`'s width] and
    ``w_uv`` [H, kv_rank, Dv] (a head's columns of ``w_ukv``), ``latents`` [slots, P, >= kv_rank + d_rope] in the
    weights' type, read where they lie, ``n_blocks`` blocks of ``block``
    positions of slot ``slot`` (all three traced scalars); ``keep`` [C, P]
    int8, nonzero where a row keeps a position, or None. ``[H, C, Dv]``
    float32, normalised."""
    H, C, Dk = q.shape
    Dv = w_uv.shape[-1]
    dtype = w_uv.dtype
    width = latents.shape[-1]
    masked = keep is not None
    G = HEADS_A_STEP if H % HEADS_A_STEP == 0 else 1
    meta = jnp.stack([jnp.asarray(v, jnp.int32)
                      for v in (slot, offset, n_blocks)])

    def head(h, meta):
        return h, 0, 0

    vm, f32 = pltpu.VMEM, jnp.float32
    item = jnp.dtype(dtype).itemsize
    call = pl.pallas_call(
        functools.partial(_walk_kernel, kv_rank=kv_rank, d_rope=d_rope,
                          rope_at=rope_at, scale=scale, masked=masked),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(H // G,),
            in_specs=[
                pl.BlockSpec((G, C, Dk), head, memory_space=vm),
                pl.BlockSpec((G, kv_rank, w_uk.shape[-1]), head,
                             memory_space=vm),
                pl.BlockSpec((G, kv_rank, Dv), head, memory_space=vm),
                pl.BlockSpec(memory_space=pl.ANY),
            ] + ([pl.BlockSpec(memory_space=pl.ANY)] if masked else []),
            out_specs=pl.BlockSpec((G, C, Dv), head, memory_space=vm),
            scratch_shapes=(
                [pltpu.VMEM((2, block, width), dtype)]
                + ([pltpu.VMEM((2, C, block), jnp.int8)] if masked else [])
                + [pltpu.VMEM((block, Dk), dtype),
                   pltpu.VMEM((G, C, 1), f32), pltpu.VMEM((G, C, 1), f32),
                   pltpu.SemaphoreType.DMA((2, 2))]),
        ),
        out_shape=jax.ShapeDtypeStruct((H, C, Dv), f32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # two buffers of a step's queries, weights and result; two of a
            # block's latents and mask; a block's keys and values, scores
            # and probabilities in float32 and in the values' type; room for
            # the compiler's own
            vmem_limit_bytes=(
                2 * G * (C * Dk * item + kv_rank * (Dk + Dv) * item
                         + C * Dv * 4)
                + 2 * block * (width * item + C)
                + block * (Dk + Dv) * (item + 4)
                + 4 * C * block * 4 + 2 * C * Dv * 4 + (8 << 20))),
        interpret=interpret,
        # %chunk_attend.N in a device trace
        name="chunk_attend",
    )
    args = (meta, q, w_uk, w_uv, latents) + ((keep,) if masked else ())
    return call(*args)
