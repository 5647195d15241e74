"""Grouped-query attention as the commit before windows, sinks and a value
head size of its own had it (``ops/gqa.py`` whole, and ``ops/attention.py``'s
``attend_over_blocks`` with its block update, verbatim from 4ab1035): what
``tests/test_seqmimo.py`` holds the two stacks that ran it then (a
block-diffusion stack, a causal layer among recurrent ones) to, primitive for
primitive and bit for bit. Nothing else may import this."""

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from predictionio_tpu.ops.attention import _NEG
from predictionio_tpu.ops.mla import mm, rms_norm


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
    keep: Optional[jax.Array] = None,  # [Lq, Lk] or [B, Lq, Lk]: each
                                       # row's own set of key positions
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
        if keep is not None:
            mask = mask & keep
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
                       scale: Optional[float] = None,
                       keep_block=None) -> jax.Array:
    """Causal attention of ``q`` [B, Lq, H, Dk] (positions ``q_pos``, [Lq]
    or [B, Lq]) over keys and values that ``kv_block(j)`` produces one block
    at a time — ``(k [B, block, H, Dk], v [B, block, H, Dv])`` for the key
    positions ``j * block_size + arange(block_size)`` — so the caller can
    read them from a cache, or expand them from latents, only as far as the
    history reaches. ``n_blocks`` may be traced: the loop runs that many
    times in ONE compiled program for every history length.

    ``keep_block(j)``, where given, is each query row's OWN set of key
    positions inside block ``j`` (bool ``[Lq, block]`` or ``[B, Lq,
    block]``: a learned index's selection), on top of the causal mask. A row
    that keeps nothing of its first blocks carries a running maximum of
    ``_NEG`` through them, and the first kept key's ``alpha`` (``exp(_NEG -
    m)``, exactly 0) wipes what they added: every row must keep some key."""
    B, Lq, H, _ = q.shape
    carry = (jnp.full((B, H, Lq), _NEG, jnp.float32),
             jnp.zeros((B, H, Lq), jnp.float32),
             jnp.zeros((B, Lq, H, v_dim), jnp.float32))

    def body(j, carry):
        k, v = kv_block(j)
        k_pos = j * block_size + jnp.arange(block_size)
        keep = None if keep_block is None else keep_block(j)
        return _accum_block(q, k, v, *carry, q_pos, k_pos, True, scale, keep)

    m, l, o = jax.lax.fori_loop(0, n_blocks, body, carry)
    return _finish(m, l, o, dtype or q.dtype)


@dataclasses.dataclass(frozen=True)
class GQADims:
    dim: int
    heads: int
    kv_heads: int
    head_dim: int
    block_len: int = 4          # positions of one block of the mask; 1: causal
    rope_theta: float = 1e6
    eps: float = 1e-6
    rope: bool = True           # False: no position encoding
    qk_norm: bool = True        # False: queries and keys as projected
    scale: Optional[float] = None    # of the scores; None: head_dim ** -0.5

    @property
    def group(self) -> int:
        return self.heads // self.kv_heads

    @property
    def cache_width(self) -> int:
        """Values a cached position takes: keys, then values."""
        return 2 * self.kv_heads * self.head_dim


def init(key, dims: GQADims, dtype=jnp.float32) -> dict:
    """N(0, 1 / fan_in) matrices, unit norms."""
    d = dims
    shapes = {"w_q": (d.dim, d.heads * d.head_dim),
              "w_k": (d.dim, d.kv_heads * d.head_dim),
              "w_v": (d.dim, d.kv_heads * d.head_dim),
              "w_o": (d.heads * d.head_dim, d.dim)}
    out = {n: (jax.random.normal(k, s, jnp.float32) / math.sqrt(s[0])
               ).astype(dtype)
           for (n, s), k in zip(shapes.items(),
                                jax.random.split(key, len(shapes)))}
    if d.qk_norm:
        out["q_norm"] = jnp.ones((d.head_dim,), dtype)
        out["k_norm"] = jnp.ones((d.head_dim,), dtype)
    return out


def rope_half(x, pos, theta):
    """``x`` [..., T, H, d] (float32), ``pos`` [..., T]: dimensions
    ``(i, i + d/2)`` turned by ``pos * theta^(-2i/d)``."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[..., None, None] * inv     # [..., T, 1, d/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def project(p, dims: GQADims, x, pos):
    """Queries, keys and values of the positions ``x`` [..., T, dim]:
    ``(q [..., T, heads, d], k [..., T, kv_heads, d], v alike)``, float32,
    queries and keys normed and turned where the stack's are."""
    d = dims
    lead = x.shape[:-1]
    q = mm(x, p["w_q"]).reshape(lead + (d.heads, d.head_dim))
    k = mm(x, p["w_k"]).reshape(lead + (d.kv_heads, d.head_dim))
    v = mm(x, p["w_v"]).reshape(lead + (d.kv_heads, d.head_dim))
    if d.qk_norm:
        q = rms_norm(q, p["q_norm"], d.eps)
        k = rms_norm(k, p["k_norm"], d.eps)
    if d.rope:
        q = rope_half(q, pos, d.rope_theta)
        k = rope_half(k, pos, d.rope_theta)
    return q, k, v


def block_end(pos, block_len: int):
    """The last position of each position's block: every key a position
    sees under the block-causal mask lies at or before it."""
    return (pos // block_len) * block_len + (block_len - 1)


def _out(p, o):
    return mm(o.reshape(o.shape[:-2] + (-1,)), p["w_o"])


def attend_full(p, dims: GQADims, x, pos):
    """Every position of ``x`` [T, dim] against every one its block may
    see, scores materialised: the plain form."""
    d = dims
    T = x.shape[0]
    q, k, v = project(p, d, x, pos)
    q = q.reshape(T, d.kv_heads, d.group, d.head_dim)
    s = jnp.einsum("tkgd,ukd->kgtu", q, k,
                   precision=jax.lax.Precision.HIGHEST)
    s = s / math.sqrt(d.head_dim) if d.scale is None else s * d.scale
    sees = block_end(pos, d.block_len)[:, None] >= pos[None, :]
    prob = jax.nn.softmax(jnp.where(sees[None, None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("kgtu,ukd->tkgd", prob, v,
                   precision=jax.lax.Precision.HIGHEST)
    return _out(p, o.reshape(T, d.heads, d.head_dim))


def _to_cache(k, v, cache):
    """``k``, ``v`` [..., T, kv_heads, d] as cache rows [..., T, width]."""
    lead = k.shape[:-2]
    return jnp.concatenate([k.reshape(lead + (-1,)), v.reshape(lead + (-1,))],
                           axis=-1).astype(cache.dtype)


def _attend(dims: GQADims, q, pos, cache, slots, n_blocks, block: int):
    """``q`` [B, S, heads, d] at ``pos`` [B, S] over the slots ``slots`` [B]
    of ``cache`` [slots, P, width], ``n_blocks`` (traced) rounds of
    ``block`` cached positions. The queries of a key/value head's group are
    folded into the query axis, so that a round is one product per
    key/value head. ``[B, S, heads, d]`` float32."""
    d = dims
    B, S = pos.shape
    half = d.kv_heads * d.head_dim
    q = q.reshape(B, S, d.kv_heads, d.group, d.head_dim)
    q = q.transpose(0, 1, 3, 2, 4).reshape(B, S * d.group, d.kv_heads,
                                           d.head_dim).astype(cache.dtype)
    q_pos = jnp.repeat(block_end(pos, d.block_len), d.group, axis=1)

    def kv_block(j):
        rows = jax.vmap(lambda s: jax.lax.dynamic_slice(
            cache, (s, j * block, 0), (1, block, cache.shape[-1]))[0])(slots)
        shape = (B, block, d.kv_heads, d.head_dim)
        return rows[..., :half].reshape(shape), rows[..., half:].reshape(shape)

    o = attend_over_blocks(q, q_pos, kv_block, n_blocks, block, d.head_dim,
                           dtype=jnp.float32, scale=d.scale)
    o = o.reshape(B, S, d.group, d.kv_heads, d.head_dim)
    return o.transpose(0, 1, 3, 2, 4).reshape(B, S, d.heads, d.head_dim)


def prefill_chunk(p, dims: GQADims, x, offset, cache, slot, block: int):
    """Whole blocks ``x`` [C, dim] of ONE session, at positions ``offset +
    arange(C)`` (``offset`` and ``C`` multiples of the block length), against
    that session's slot of ``cache`` [slots, P, width]. ``(out [C, dim]
    float32, cache)``."""
    C = x.shape[0]
    pos = offset + jnp.arange(C, dtype=jnp.int32)
    q, k, v = project(p, dims, x, pos)
    cache = jax.lax.dynamic_update_slice(
        cache, _to_cache(k, v, cache)[None], (slot, offset, 0))
    n_blocks = (offset + C + block - 1) // block
    o = _attend(dims, q[None], pos[None], cache, jnp.reshape(slot, (1,)),
                n_blocks, block)[0]
    return _out(p, o), cache


def block_step(p, dims: GQADims, x, pos, cache, slots, n_blocks, block: int):
    """One block of each of several sessions: ``x`` [B, block_len, dim] at
    positions ``pos`` [B, block_len] of the slots ``slots`` [B] (two rows may
    name one slot, at consecutive blocks: every row's keys are written
    before any row attends). ``n_blocks`` (traced) covers the longest
    session of the batch. ``(out [B, block_len, dim] float32, cache)``."""
    q, k, v = project(p, dims, x, pos)
    rows = _to_cache(k, v, cache)
    for b in range(x.shape[0]):
        cache = jax.lax.dynamic_update_slice(
            cache, rows[b][None], (slots[b], pos[b, 0], 0))
    return _out(p, _attend(dims, q, pos, cache, slots, n_blocks, block)), cache


#: under the causal mask (``block_len`` 1) a row's positions are a few new
#: positions of its session, each seeing the ones before it: an extension
extend = block_step
