"""Grouped-query attention over a per-session cache of keys and values, as
its ``GQADims`` say: the mixer of a block-diffusion stack (a block-causal
mask, per-head norms, RoPE) and the attention layer of a stack of recurrent
mixers (a causal mask, no position encoding, no norms, a score scale of the
model's own).

``heads`` query heads share ``kv_heads`` key/value heads (``heads //
kv_heads`` to one). Where the stack says so (``qk_norm``, ``rope``), queries
and keys are RMS-normed per head over the head's own dimensions (a learned
weight of ``head_dim`` each) and turned by RoPE on all of them, pairs ``(i,
i + head_dim / 2)`` (rotate-half), at absolute positions. Scores are scaled
by ``scale`` (``1 / sqrt(head_dim)`` where none is given). Position ``i``
sees ``j`` iff ``j // block_len <= i // block_len``: causal from block to
block, bidirectional inside one; a block length of 1 is the causal mask.
Every key a position sees therefore lies at or before the LAST position of
its own block, which is the position the causal loop of
``ops.attention.attend_over_blocks`` is given for it.

What a cache holds is a position's keys and values themselves, ``2 *
kv_heads * head_dim`` values side by side (keys first), in the cache's type.
Two paths over one set of weights, as in ``ops/mla.py``:

* :func:`prefill_chunk`: whole blocks of ONE session against its slot;
* :func:`block_step`: one block (``block_len`` positions) of each of several
  sessions against their slots — a block being denoised or a finished block
  being committed, the program is the same. Under the causal mask the same
  function is an EXTENSION (:data:`extend`): a few new positions a row.

Both WRITE the keys and values of their positions and then attend over the
slot up to the end of those positions. A block's keys and values depend on
every position of the block, so what a forward over an unfinished block
writes is provisional: it lies beyond what the slot's owner counts as held,
and the forward over the finished block writes over it before anything
later attends to it. Both give the numbers of :func:`attend_full` (scores
materialised, no cache), which is the plain form the tests hold them to.

Matrix products take their inputs in the weights' type and accumulate in
float32; norms, RoPE and softmax are float32.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from predictionio_tpu.ops.attention import attend_over_blocks
from predictionio_tpu.ops.mla import mm, rms_norm


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
