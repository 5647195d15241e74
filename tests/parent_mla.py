"""The latent-attention paths as the commit before the learned index had
them (``ops/mla.py`` ``project``, ``prefill_chunk``, ``extend`` and
``ops/attention.py`` ``attend_over_blocks`` with its block update, verbatim
from 0f3031b): what ``tests/test_seqglm.py`` holds a stack WITHOUT an index
to, bit for bit and primitive for primitive. Nothing else may import this."""

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from predictionio_tpu.ops.attention import _NEG
from predictionio_tpu.ops.mla import (MLADims, _out, _to_cache, expand, mm,
                                      rms_norm, rope)


def _accum_block(
    q: jax.Array,        # [B, Lq, H, D] float32
    k: jax.Array,        # [B, Lk, H, D]
    v: jax.Array,        # [B, Lk, H, D]
    m: jax.Array,        # [B, H, Lq]   running max
    l: jax.Array,        # [B, H, Lq]   running denominator
    o: jax.Array,        # [B, Lq, H, D] running numerator
    q_pos: jax.Array,    # [Lq] global positions
    k_pos: jax.Array,    # [Lk] global positions
    causal: bool,
    scale: Optional[float] = None,   # None: q's head width ** -0.5
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One online-softmax update: fold the (q, k/v-block) partial into
    the (m, l, o) accumulators. The rescaling trick is the standard
    flash-attention recurrence."""
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    # products accumulate in float32 whatever the inputs' type (bfloat16
    # keys beside float32 accumulators: the MLA prefill); the values'
    # head width is its own (192-wide keys beside 128-wide values)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale   # MXU
    if causal:
        # ``q_pos``/``k_pos`` are [L] (one set of positions for the batch)
        # or [B, L] (each row its own: sessions of different lengths)
        mask = q_pos[..., :, None] >= k_pos[..., None, :]
        s = jnp.where(mask[None, None] if mask.ndim == 2 else mask[:, None],
                      s, _NEG)
    m_new = jnp.maximum(m, s.max(axis=-1))                   # [B, H, Lq]
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])                        # [B, H, Lq, Lk]
    l_new = l * alpha + p.sum(axis=-1)
    o_new = o * alpha.transpose(0, 2, 1)[..., None] + jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return m_new, l_new, o_new


def _finish(m, l, o, dtype):
    return (o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]).astype(dtype)


def attend_over_blocks(q, q_pos, kv_block, n_blocks, block_size: int,
                       v_dim: int, dtype=None,
                       scale: Optional[float] = None) -> jax.Array:
    """Causal attention of ``q`` [B, Lq, H, Dk] (positions ``q_pos``, [Lq]
    or [B, Lq]) over keys and values that ``kv_block(j)`` produces one block
    at a time — ``(k [B, block, H, Dk], v [B, block, H, Dv])`` for the key
    positions ``j * block_size + arange(block_size)`` — so the caller can
    read them from a cache, or expand them from latents, only as far as the
    history reaches. ``n_blocks`` may be traced: the loop runs that many
    times in ONE compiled program for every history length."""
    B, Lq, H, _ = q.shape
    carry = (jnp.full((B, H, Lq), _NEG, jnp.float32),
             jnp.zeros((B, H, Lq), jnp.float32),
             jnp.zeros((B, Lq, H, v_dim), jnp.float32))

    def body(j, carry):
        k, v = kv_block(j)
        k_pos = j * block_size + jnp.arange(block_size)
        return _accum_block(q, k, v, *carry, q_pos, k_pos, True, scale)

    m, l, o = jax.lax.fori_loop(0, n_blocks, body, carry)
    return _finish(m, l, o, dtype or q.dtype)


def project(p, dims: MLADims, x, pos):
    """Queries and latents of the positions ``x`` [..., T, dim]:
    ``(qN [..., T, H, d_nope], qR [..., T, H, d_rope] after RoPE,
    latent [..., T, kv_rank + d_rope])``, float32."""
    d = dims
    cq = rms_norm(mm(x, p["w_dq"]), p["q_norm"], d.eps)
    if d.scale_q:
        cq = cq * math.sqrt(d.dim / d.q_rank)
    q = mm(cq, p["w_uq"]).reshape(x.shape[:-1] + (d.heads, d.d_qk))
    freqs, amp = d.rope_freqs(), d.rope_amplitude
    qn, qr = q[..., :d.d_nope], rope(q[..., d.d_nope:], pos, freqs, amp)
    down = mm(x, p["w_dkv"])
    ckv = rms_norm(down[..., :d.kv_rank], p["kv_norm"], d.eps)
    if d.scale_kv:
        ckv = ckv * math.sqrt(d.dim / d.kv_rank)
    kr = rope(down[..., d.kv_rank:], pos, freqs, amp)
    return qn, qr, jnp.concatenate([ckv, kr], axis=-1)


def prefill_chunk(p, dims: MLADims, x, offset, cache, slot, block: int):
    """A chunk ``x`` [C, dim] of ONE session, at positions ``offset +
    arange(C)``, against that session's slot of ``cache`` [slots, P, latent].
    Writes the chunk's latents into the slot, then attends over the slot's
    blocks up to the chunk's end. ``(out [C, dim] float32, cache)``."""
    d = dims
    C = x.shape[0]
    pos = offset + jnp.arange(C, dtype=jnp.int32)
    qn, qr, latent = project(p, d, x, pos)
    cache = jax.lax.dynamic_update_slice(
        cache, _to_cache(latent, cache)[None], (slot, offset, 0))
    q = jnp.concatenate([qn, qr], axis=-1).astype(p["w_ukv"].dtype)[None]

    def kv_block(j):
        lat = jax.lax.dynamic_slice(
            cache, (slot, j * block, 0), (1, block, cache.shape[-1]))
        return expand(p, d, lat[..., :d.latent])

    n_blocks = (offset + C + block - 1) // block
    o = attend_over_blocks(q, pos, kv_block, n_blocks, block, d.d_v,
                           dtype=jnp.float32, scale=d.softmax_scale)[0]
    return _out(p, d, o), cache


def extend(p, dims: MLADims, x, pos, cache, slots, n_blocks, block: int):
    """A few new positions of several sessions, absorbed form: ``x``
    [B, S, dim] at positions ``pos`` [B, S] of the slots ``slots`` [B].
    Writes their latents, then attends over the cached LATENTS themselves
    (one shared key/value "head" of ``latent`` / ``kv_rank`` values; the
    heads are folded into the query axis). ``n_blocks`` (traced) covers the
    longest session of the batch. ``(out [B, S, dim] float32, cache)``."""
    d = dims
    B, S, _ = x.shape
    qn, qr, latent = project(p, d, x, pos)
    latent = _to_cache(latent, cache)
    for b in range(B):
        cache = jax.lax.dynamic_update_slice(
            cache, latent[b][None], (slots[b], pos[b, 0], 0))
    w = p["w_ukv"].reshape(d.kv_rank, d.heads, d.d_nope + d.d_v)
    q_abs = jnp.einsum("bshd,chd->bshc", qn.astype(w.dtype),
                       w[..., :d.d_nope],
                       preferred_element_type=jnp.float32)
    q = jnp.concatenate([q_abs, qr], axis=-1).astype(cache.dtype)
    q = q.reshape(B, S * d.heads, 1, d.latent)
    q_pos = jnp.repeat(pos, d.heads, axis=1)                 # [B, S*H]

    def kv_block(j):
        lat = jax.vmap(lambda s: jax.lax.dynamic_slice(
            cache, (s, j * block, 0), (1, block, cache.shape[-1]))[0])(slots)
        return lat[:, :, None, :d.latent], lat[:, :, None, :d.kv_rank]

    o = attend_over_blocks(q, q_pos, kv_block, n_blocks, block, d.kv_rank,
                           dtype=jnp.float32, scale=d.softmax_scale)
    o = o.reshape(B, S, d.heads, d.kv_rank)
    o = jnp.einsum("bshc,chd->bshd", o.astype(w.dtype), w[..., d.d_nope:],
                   preferred_element_type=jnp.float32)
    return _out(p, d, o), cache
