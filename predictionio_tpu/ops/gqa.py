"""Grouped-query attention over a per-session cache of keys and values, as
its ``GQADims`` say. Three stacks run it: a block-diffusion stack (a
block-causal mask, per-head norms, RoPE on every dimension), the attention
layer of a stack of recurrent mixers (a causal mask, no position encoding, no
norms, a score scale of the model's own), and a stack that MIXES full and
sliding-window layers under two dims of its own (heads of 192 for queries and
keys beside values of 128, RoPE on the head's leading 64 dimensions at two
bases, the values scaled, 4 and 8 key/value heads, a window of 128 positions
with a learned sink a head).

``heads`` query heads share ``kv_heads`` key/value heads (``heads //
kv_heads`` to one). Keys and queries are ``head_dim`` wide, values ``v_dim``
(``v_head_dim``, or ``head_dim`` where none is given), multiplied by
``value_scale`` where they are made. Where the stack says so (``qk_norm``,
``rope``), queries and keys are RMS-normed per head over the head's own
dimensions (a learned weight of ``head_dim`` each) and turned by RoPE on their
leading ``rope_dims`` (all of them where none is given), pairs ``(i, i +
rope_dims / 2)`` (rotate-half), at absolute positions; the other dimensions go
as projected. Scores are scaled by ``scale`` (``1 / sqrt(head_dim)`` where
none is given). Position ``i`` sees ``j`` iff ``j // block_len <= i //
block_len``: causal from block to block, bidirectional inside one; a block
length of 1 is the causal mask, and under it a ``window`` (> 0) narrows the
sight to ``0 <= i - j < window``. Every key a position sees lies at or before
the LAST position of its own block, which is the position the causal loop of
``ops.attention.attend_over_blocks`` is given for it. A ``sink`` is a learned
logit a query head (the parameter leaf ``sink [heads]``, there only where the
dims say so) that joins the softmax's normaliser and nothing else: ``P_ij =
exp(s_ij - m) / (sum_j exp(s_ij - m) + exp(b_h - m))``.

What a cache ROW holds is a position's keys and values themselves,
``kv_heads * (head_dim + v_dim)`` values side by side (keys first), in the
cache's type. Two layouts:

* a SPAN ``[slots, P, width]``: position ``t`` at row ``t``, as long as the
  longest session; attention walks its blocks from 0 to the reach
  (:func:`prefill_chunk`, :func:`block_step`, :func:`extend`);
* a RING ``[slots, R, width]`` for a WINDOW layer (:func:`ring_len`: the
  window and one chunk, in whole blocks of ``window`` positions): position
  ``t`` at row ``t mod R``, real positions only, so a slot holds its last ``R``
  positions; attention walks the blocks that hold the queries' windows and no
  other, at most ``ceil((C + window - 1) / window) + 1`` rounds whatever the
  reach (:func:`window_prefill_chunk`, :func:`window_extend`).

Two paths over one set of weights, as in ``ops/mla.py``:

* a chunk: whole blocks of ONE session against its slot;
* :func:`block_step`: one block (``block_len`` positions) of each of several
  sessions against their slots — a block being denoised or a finished block
  being committed, the program is the same: every row walks as far as the
  batch's longest (``ops.attention.attend_over_blocks``);
* :func:`extend`, under the causal mask: a few new positions of each of
  several sessions, written as a block step writes them; then each row walks
  the blocks of ITS OWN reach, in place, in one kernel
  (``ops/pallas/span_walk.py``), and a padding row walks none.

Both WRITE the keys and values of their positions and then attend over the
slot up to the end of those positions. A block's keys and values depend on
every position of the block, so what a forward over an unfinished block
writes is provisional: it lies beyond what the slot's owner counts as held,
and the forward over the finished block writes over it before anything
later attends to it. All give the numbers of :func:`attend_full` (scores
materialised, no cache, the window a mask), which is the plain form the
tests hold them to.

DIFFERENTIAL attention (``diff``; a fourth stack's, with projection biases
``bias``, no position encoding, a window of 512): the query, key and value
heads pair up ``(2i, 2i + 1)`` into ``q1, q2``, ``k1, k2``, ``v1, v2``
(grouped-query between the PAIRS: ``heads / kv_heads`` query pairs to a
key/value pair); ``A_a = softmax(q_a k_a^T * scale)`` under the mask, ``o_a =
[A_a v1, A_a v2]`` (``2 * v_dim`` wide); ``lambda = exp(l_q1 . l_k1) -
exp(l_q2 . l_k2) + lambda_init`` (four learned vectors of ``head_dim`` a
layer), ``lambda_init = 0.8 - 0.6 exp(-0.3 depth)`` (``depth``: the layer's
place in the stack, from 0); ``o = RMS(o_1 - lambda o_2) w * (1 -
lambda_init)`` (``subln`` [2 * v_dim]); the ``heads / 2`` double heads side by
side, then ``W_o``. On the walks a key head ``2p + a`` is read with BOTH of
its pair's values behind it (:func:`_split`), so one walk gives ``o_1`` and
``o_2``.

A CROSS mixer (``cross``: ``W_q`` and ``W_o`` alone, with its own ``lambda``s
and sub-norm) writes nothing: :func:`cross_rows` walks the span ANOTHER mixer
of the same head shapes wrote, for one position a session, by the same kernel
as :func:`extend`.

Matrix products take their inputs in the weights' type and accumulate in
float32; norms, RoPE, softmax, sinks and ``lambda`` are float32.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from predictionio_tpu.ops import pallas
from predictionio_tpu.ops.attention import attend_over_blocks
from predictionio_tpu.ops.mla import mm, rms_norm
from predictionio_tpu.ops.pallas import span_walk


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
    v_head_dim: Optional[int] = None     # of a value head; None: head_dim
    rope_dims: Optional[int] = None  # leading dims RoPE turns; None: all
    window: int = 0             # positions a query sees, itself among them; 0: all
    sink: bool = False          # a learned logit a head in the normaliser
    value_scale: float = 1.0    # what the values are multiplied by
    bias: bool = False          # the four projections add a learned bias
    diff: bool = False          # differential attention over pairs of heads
    cross: bool = False         # W_q and W_o alone: another mixer's span

    def __post_init__(self):
        if self.window and self.block_len != 1:
            raise ValueError("a window is a causal mask's: block_len 1")
        if self.diff and (self.kv_heads % 2 or self.heads % 2 or self.sink):
            raise ValueError("differential attention pairs its heads and "
                             "knows no sink")

    @property
    def group(self) -> int:
        return self.heads // self.kv_heads

    @property
    def v_dim(self) -> int:
        return self.head_dim if self.v_head_dim is None else self.v_head_dim

    @property
    def cache_width(self) -> int:
        """Values a cached position takes: keys, then values."""
        return self.kv_heads * (self.head_dim + self.v_dim)

    @property
    def walk_v_dim(self) -> int:
        """What a walk's key head carries behind it: its value, or under
        differential attention its pair's two."""
        return 2 * self.v_dim if self.diff else self.v_dim


def init(key, dims: GQADims, dtype=jnp.float32) -> dict:
    """N(0, 1 / fan_in) matrices, unit norms, N(0, 1) sinks, zero biases,
    N(0, 0.1^2) ``lambda`` vectors; a cross mixer has no ``w_k``, ``w_v``."""
    d = dims
    shapes = {"w_q": (d.dim, d.heads * d.head_dim),
              "w_k": (d.dim, d.kv_heads * d.head_dim),
              "w_v": (d.dim, d.kv_heads * d.v_dim),
              "w_o": (d.heads * d.v_dim, d.dim)}
    if d.cross:
        del shapes["w_k"], shapes["w_v"]
    out = {n: (jax.random.normal(k, s, jnp.float32) / math.sqrt(s[0])
               ).astype(dtype)
           for (n, s), k in zip(shapes.items(),
                                jax.random.split(key, len(shapes)))}
    if d.bias:
        out.update(("b" + n[1:], jnp.zeros((s[1],), dtype))
                   for n, s in shapes.items())
    if d.diff:
        for i, n in enumerate(LAMBDAS):
            out[n] = 0.1 * jax.random.normal(
                jax.random.fold_in(key, 16 + i), (d.head_dim,), jnp.float32)
        out["subln"] = jnp.ones((2 * d.v_dim,), dtype)
    if d.qk_norm:
        out["q_norm"] = jnp.ones((d.head_dim,), dtype)
        out["k_norm"] = jnp.ones((d.head_dim,), dtype)
    if d.sink:
        out["sink"] = jax.random.normal(
            jax.random.fold_in(key, len(shapes)), (d.heads,),
            jnp.float32).astype(dtype)
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


#: the four learned vectors of a differential layer's ``lambda``
LAMBDAS = ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")


def _mmb(p, dims: GQADims, x, name: str):
    """``x W_<name>``, and the projection's bias where the dims say so."""
    out = mm(x, p["w_" + name])
    return out + p["b_" + name].astype(jnp.float32) if dims.bias else out


def project(p, dims: GQADims, x, pos):
    """Queries, keys and values of the positions ``x`` [..., T, dim]:
    ``(q [..., T, heads, d], k [..., T, kv_heads, d], v alike)``, float32,
    queries and keys normed and turned where the stack's are."""
    d = dims
    lead = x.shape[:-1]
    q = _mmb(p, d, x, "q").reshape(lead + (d.heads, d.head_dim))
    k = _mmb(p, d, x, "k").reshape(lead + (d.kv_heads, d.head_dim))
    v = _mmb(p, d, x, "v").reshape(lead + (d.kv_heads, d.v_dim))
    if d.value_scale != 1.0:
        v = d.value_scale * v
    if d.qk_norm:
        q = rms_norm(q, p["q_norm"], d.eps)
        k = rms_norm(k, p["k_norm"], d.eps)
    if d.rope:
        q = _turned(d, q, pos)
        k = _turned(d, k, pos)
    return q, k, v


def _turned(dims: GQADims, x, pos):
    """RoPE on the head's leading ``rope_dims`` dimensions (all of them
    where none is given), the others as projected."""
    r = dims.rope_dims
    if r is None or r == dims.head_dim:
        return rope_half(x, pos, dims.rope_theta)
    return jnp.concatenate(
        [rope_half(x[..., :r], pos, dims.rope_theta), x[..., r:]], axis=-1)


def block_end(pos, block_len: int):
    """The last position of each position's block: every key a position
    sees under the block-causal mask lies at or before it."""
    return (pos // block_len) * block_len + (block_len - 1)


def lambda_init(depth: int) -> float:
    """A differential layer's ``lambda_init``, by its place in the stack."""
    return 0.8 - 0.6 * math.exp(-0.3 * depth)


def diff_lambda(p, depth: int):
    """``lambda = exp(l_q1 . l_k1) - exp(l_q2 . l_k2) + lambda_init``."""
    l_q1, l_k1, l_q2, l_k2 = (p[n].astype(jnp.float32) for n in LAMBDAS)
    return (jnp.exp(jnp.sum(l_q1 * l_k1)) - jnp.exp(jnp.sum(l_q2 * l_k2))
            + lambda_init(depth))


def _out(p, dims: GQADims, o, depth: int = 0):
    """The heads' outputs side by side through ``W_o``: ``o`` [..., heads,
    v_dim], or under differential attention [..., heads / 2, 2, 2 * v_dim]
    (a double head's ``o_1`` and ``o_2``), combined here."""
    if dims.diff:
        o = rms_norm(o[..., 0, :] - diff_lambda(p, depth) * o[..., 1, :],
                     p["subln"], dims.eps) * (1.0 - lambda_init(depth))
    return _mmb(p, dims, o.reshape(o.shape[:-2] + (-1,)), "o")


def sees(dims: GQADims, q_pos, k_pos):
    """Whether the query at ``q_pos`` [T] sees the key at ``k_pos`` [U],
    ``[T, U]``: the block-causal mask, and inside a window the query's own
    position and the ``window - 1`` before it."""
    out = block_end(q_pos, dims.block_len)[:, None] >= k_pos[None, :]
    if dims.window:
        out = out & (q_pos[:, None] - k_pos[None, :] < dims.window)
    return out


def attend_full(p, dims: GQADims, x, pos, depth: int = 0, kv=None):
    """Every position of ``x`` [T, dim] against every one its mask lets it
    see, scores materialised: the plain form. A sink joins the softmax's
    normaliser and nothing else: ``P_ij = exp(s_ij - m) / (sum_j exp(s_ij -
    m) + exp(b_h - m))``. ``kv``: the ``(k, v)`` of another mixer over the
    same positions, for a cross mixer (:func:`project`'s)."""
    d = dims
    T = x.shape[0]
    if d.cross:
        q = _mmb(p, d, x, "q").reshape(T, d.heads, d.head_dim)
        k, v = kv
    else:
        q, k, v = project(p, d, x, pos)
    if d.diff:
        return _out(p, d, _diff_full(d, q, k, v, pos), depth)
    q = q.reshape(T, d.kv_heads, d.group, d.head_dim)
    s = jnp.einsum("tkgd,ukd->kgtu", q, k,
                   precision=jax.lax.Precision.HIGHEST)
    s = s / math.sqrt(d.head_dim) if d.scale is None else s * d.scale
    s = jnp.where(sees(d, pos, pos)[None, None], s, -jnp.inf)
    if d.sink:
        b = p["sink"].astype(jnp.float32).reshape(d.kv_heads, d.group)
        s = jnp.concatenate(
            [s, jnp.broadcast_to(b[:, :, None, None], s.shape[:3] + (1,))],
            axis=-1)
    prob = jax.nn.softmax(s, axis=-1)[..., :T]
    o = jnp.einsum("kgtu,ukd->tkgd", prob, v,
                   precision=jax.lax.Precision.HIGHEST)
    return _out(p, d, o.reshape(T, d.heads, d.v_dim))


def _diff_full(dims: GQADims, q, k, v, pos):
    """The equations of differential attention as they stand, scores
    materialised: ``[T, heads / 2, 2, 2 * v_dim]``."""
    d, T, hi = dims, q.shape[0], jax.lax.Precision.HIGHEST
    pairs = d.kv_heads // 2
    v12 = jnp.concatenate([v[:, 0::2], v[:, 1::2]], axis=-1)  # [T, pairs, 2v]
    seen = sees(d, pos, pos)[None, None]
    o = []
    for a in (0, 1):
        q_a = q[:, a::2].reshape(T, pairs, d.group, d.head_dim)
        s = jnp.einsum("tpgd,upd->pgtu", q_a, k[:, a::2], precision=hi)
        s = s / math.sqrt(d.head_dim) if d.scale is None else s * d.scale
        prob = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        o.append(jnp.einsum("pgtu,upd->tpgd", prob, v12, precision=hi
                            ).reshape(T, d.heads // 2, 2 * d.v_dim))
    return jnp.stack(o, axis=-2)


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
    q = _folded(d, q, cache.dtype)
    q_pos = jnp.repeat(block_end(pos, d.block_len), d.group, axis=1)

    def kv_block(j):
        rows = jax.vmap(lambda s: jax.lax.dynamic_slice(
            cache, (s, j * block, 0), (1, block, cache.shape[-1]))[0])(slots)
        return _split(d, rows)

    o = attend_over_blocks(q, q_pos, kv_block, n_blocks, block, d.walk_v_dim,
                           dtype=jnp.float32, scale=d.scale)
    return _unfolded(d, o, B, S)


def _split(dims: GQADims, rows):
    """Cache rows [B, block, width] as ``(k [B, block, kv_heads, d], v [B,
    block, kv_heads, v_dim])``; under differential attention ``v [B, block,
    kv_heads, 2 * v_dim]``: behind key head ``2p + a`` both values of pair
    ``p``."""
    d, (B, block) = dims, rows.shape[:2]
    half = d.kv_heads * d.head_dim
    k = rows[..., :half].reshape(B, block, d.kv_heads, d.head_dim)
    if not d.diff:
        return k, rows[..., half:].reshape(B, block, d.kv_heads, d.v_dim)
    # key head 2p + a with its pair's two values behind it
    v = rows[..., half:].reshape(B, block, d.kv_heads // 2, 1, 2 * d.v_dim)
    return k, jnp.broadcast_to(
        v, (B, block, d.kv_heads // 2, 2, 2 * d.v_dim)).reshape(
            B, block, d.kv_heads, 2 * d.v_dim)


def _folded(dims: GQADims, q, dtype):
    """``q`` [B, S, heads, d] with each key/value head's group of queries
    folded into the query axis: ``[B, S * group, kv_heads, d]``."""
    d, (B, S) = dims, q.shape[:2]
    if d.diff:      # query head (p * group + g) * 2 + a reads key head 2p + a
        q = q.reshape(B, S, d.kv_heads // 2, d.group, 2, d.head_dim)
        return q.transpose(0, 1, 3, 2, 4, 5).reshape(
            B, S * d.group, d.kv_heads, d.head_dim).astype(dtype)
    q = q.reshape(B, S, d.kv_heads, d.group, d.head_dim)
    return q.transpose(0, 1, 3, 2, 4).reshape(
        B, S * d.group, d.kv_heads, d.head_dim).astype(dtype)


def _unfolded(dims: GQADims, o, B: int, S: int):
    """A walk's output with the heads back in their places: ``[B, S, heads,
    v_dim]``, or ``[B, S, heads / 2, 2, 2 * v_dim]`` (:func:`_out`'s)."""
    d = dims
    if d.diff:
        o = o.reshape(B, S, d.group, d.kv_heads // 2, 2, 2 * d.v_dim)
        return o.transpose(0, 1, 3, 2, 4, 5).reshape(
            B, S, d.heads // 2, 2, 2 * d.v_dim)
    o = o.reshape(B, S, d.group, d.kv_heads, d.v_dim)
    return o.transpose(0, 1, 3, 2, 4).reshape(B, S, d.heads, d.v_dim)


def prefill_chunk(p, dims: GQADims, x, offset, cache, slot, block: int,
                  scope: str = "gqa", depth: int = 0):
    """Whole blocks ``x`` [C, dim] of ONE session, at positions ``offset +
    arange(C)`` (``offset`` and ``C`` multiples of the block length), against
    that session's slot of ``cache`` [slots, P, width]. The walk lies under
    the device scope ``<scope>.attend``. ``(out [C, dim] float32, cache)``."""
    C = x.shape[0]
    pos = offset + jnp.arange(C, dtype=jnp.int32)
    q, k, v = project(p, dims, x, pos)
    cache = jax.lax.dynamic_update_slice(
        cache, _to_cache(k, v, cache)[None], (slot, offset, 0))
    n_blocks = (offset + C + block - 1) // block
    with jax.named_scope(scope + ".attend"):
        o = _attend(dims, q[None], pos[None], cache, jnp.reshape(slot, (1,)),
                    n_blocks, block)[0]
    return _out(p, dims, o, depth), cache


def block_step(p, dims: GQADims, x, pos, cache, slots, n_blocks, block: int,
               scope: str = "gqa", depth: int = 0):
    """One block of each of several sessions: ``x`` [B, block_len, dim] at
    positions ``pos`` [B, block_len] of the slots ``slots`` [B] (two rows may
    name one slot, at consecutive blocks: every row's keys are written
    before any row attends). ``n_blocks`` (traced) covers the longest
    session of the batch. ``(out [B, block_len, dim] float32, cache)``."""
    q, cache = _written(p, dims, x, pos, cache, slots)
    with jax.named_scope(scope + ".attend"):
        o = _attend(dims, q, pos, cache, slots, n_blocks, block)
    return _out(p, dims, o, depth), cache


def _written(p, dims: GQADims, x, pos, cache, slots):
    """The queries of ``x`` [B, S, dim], and ``cache`` with every row's keys
    and values written at its slot's positions ``pos`` [B, S]."""
    q, k, v = project(p, dims, x, pos)
    rows = _to_cache(k, v, cache)
    for b in range(x.shape[0]):
        cache = jax.lax.dynamic_update_slice(
            cache, rows[b][None], (slots[b], pos[b, 0], 0))
    return q, cache


def extend(p, dims: GQADims, x, pos, cache, slots, n_blocks, block: int,
           scope: str = "gqa", depth: int = 0):
    """An EXTENSION, under the causal mask (``block_len`` 1): ``x`` [B, S,
    dim], a few new positions of each of several sessions at ``pos`` [B, S]
    of the slots ``slots`` [B], each seeing the ones before it. The rows'
    keys and values are written as :func:`block_step` writes them; then row
    ``b`` walks ``n_blocks[b]`` blocks of its slot ([B] traced: the blocks
    that hold the row's own reach, 0 for a padding row, whose output is
    zeros; a scalar: that many for every row). ``(out [B, S, dim] float32,
    cache)``."""
    q, cache = _written(p, dims, x, pos, cache, slots)
    with jax.named_scope(scope + ".attend"):
        o = _walk(dims, q, pos, cache, slots, n_blocks, block)
    return _out(p, dims, o, depth), cache


def cross_rows(p, dims: GQADims, x, pos, cache, slots, n_blocks, block: int,
               scope: str = "gqa_cross", depth: int = 0):
    """A CROSS mixer for ONE position a session: ``x`` [B, dim] at positions
    ``pos`` [B] against the slots ``slots`` [B] of ``cache`` [slots, P,
    width], the span ANOTHER mixer wrote (its keys and values at positions
    ``<= pos`` are there already); nothing is written. Row ``b`` walks
    ``n_blocks[b]`` blocks of its slot, as :func:`extend`'s rows do. ``out
    [B, dim]`` float32."""
    d, B = dims, x.shape[0]
    q = _mmb(p, d, x, "q").reshape(B, 1, d.heads, d.head_dim)
    with jax.named_scope(scope + ".attend"):
        o = _walk(d, q, pos[:, None], cache, slots, n_blocks, block)
    return _out(p, d, o[:, 0], depth)


def walk_groups(dims: GQADims):
    """The groups of key heads ``span_walk`` folds, as the lanes of a cached
    row: a key head and its value; under differential attention a PAIR's two
    key heads (side by side in the row) and the pair's two values, which
    both of them read."""
    d = dims
    n = 2 if d.diff else 1
    half = d.kv_heads * d.head_dim
    return tuple((g * n * d.head_dim, n * d.head_dim,
                  half + g * n * d.v_dim, n * d.v_dim)
                 for g in range(d.kv_heads // n))


def _walk(dims: GQADims, q, pos, cache, slots, n_blocks, block: int):
    """:func:`_attend` under the causal mask with a round count A ROW
    (``n_blocks`` [B], or one for all), in the ``span_walk`` kernel: the
    folded queries a group of key heads at a time, ``[B, groups, rows,
    lanes of the group's keys]``. A differential pair is one group: its two
    heads' queries lie block-diagonally (head ``2p``'s rows over the pair's
    first ``head_dim`` lanes, head ``2p + 1``'s over the last, zeros
    elsewhere), so one product scores both against their own keys and one
    gives both the pair's two values. ``[B, S, heads, d]`` float32."""
    d = dims
    if d.block_len != 1:
        raise ValueError("rows that walk their own reach are causal rows: "
                         "a block-causal stack's block forward is "
                         "block_step")
    B, S = pos.shape
    groups = walk_groups(d)
    M, G = S * d.group, len(groups)
    n = d.kv_heads // G                             # key heads a group
    q = _folded(d, q, cache.dtype).reshape(B, M, G, n, d.head_dim)
    q = q.transpose(0, 2, 3, 1, 4)                  # [B, G, n, M, d]
    if n > 1:
        q = q[:, :, :, :, None] * jnp.eye(n, dtype=q.dtype)[:, None, :, None]
    q = q.reshape(B, G, n * M, n * d.head_dim)
    q_pos = jnp.tile(jnp.repeat(pos, d.group, axis=1), (1, n))[..., None]
    o = span_walk.span_walk(
        q, q_pos.astype(jnp.int32), cache, slots,
        jnp.broadcast_to(n_blocks, (B,)), groups=groups, block=block,
        scale=d.head_dim ** -0.5 if d.scale is None else d.scale,
        interpret=pallas.interpret_mode())
    o = o.reshape(B, G, n, M, d.walk_v_dim).transpose(0, 3, 1, 2, 4)
    return _unfolded(d, o.reshape(B, M, d.kv_heads, d.walk_v_dim), B, S)


# -- a window layer's ring -----------------------------------------------------

def ring_len(window: int, chunk: int) -> int:
    """Rows of a window layer's ring: the window and one chunk, in whole
    blocks of ``window`` positions (the blocks its walk takes)."""
    return -(-(window + chunk) // window) * window


def window_rounds(dims: GQADims, first, last):
    """``(first block, rounds)`` of the walk of queries at positions
    ``first`` .. ``last`` (traced, any equal shapes): the blocks of ``window``
    positions that hold ``[first - (window - 1), last]``. A walk from 0
    would take ``last // window + 1`` rounds."""
    w = dims.window
    at = jnp.maximum(first - (w - 1), 0) // w
    return at, last // w - at + 1


def _attend_ring(p, dims: GQADims, q, pos, last, ring, slots):
    """``q`` [B, S, heads, d] at ``pos`` [B, S] over the slots ``slots`` [B]
    of ``ring`` [slots, R, width]: position ``t`` lies at row ``t mod R``, so
    block ``j`` of ``window`` positions lies at the ring's block ``j mod (R /
    window)``, and each row of the batch walks the blocks that hold its own
    ``[pos[b, 0] - (window - 1), last[b]]`` and no other: a row read under a
    position it does not hold (an older lap's, or one not yet written) lies
    outside every query's window and is masked. ``([B, S, heads, v_dim]
    float32, the rounds walked)``."""
    d, block = dims, dims.window
    B, S = pos.shape
    n_ring = ring.shape[1] // block
    first, rounds = window_rounds(d, pos[:, 0], last)
    rounds = rounds.max()

    def kv_block(t):
        at = ((first + t) % n_ring) * block
        rows = jax.vmap(lambda s, a: jax.lax.dynamic_slice(
            ring, (s, a, 0), (1, block, ring.shape[-1]))[0])(slots, at)
        return _split(d, rows)

    sink = None
    if d.sink:      # a query head's logit, at its place in the folded axis
        b = p["sink"].astype(jnp.float32).reshape(d.kv_heads, 1, d.group)
        sink = jnp.broadcast_to(b, (d.kv_heads, S, d.group)).reshape(
            d.kv_heads, S * d.group)
    o = attend_over_blocks(
        _folded(d, q, ring.dtype), jnp.repeat(pos, d.group, axis=1),
        kv_block, rounds, block, d.walk_v_dim, dtype=jnp.float32,
        scale=d.scale, first_block=first, window=d.window, sink=sink)
    return _unfolded(d, o, B, S), rounds


def window_prefill_chunk(p, dims: GQADims, x, n_valid, offset, ring, slot,
                         scope: str = "gqa_window", depth: int = 0):
    """:func:`prefill_chunk` of a WINDOW layer: ``x`` [C, dim] of one session
    at positions ``offset + arange(C)``, the first ``n_valid`` of them real,
    against that session's slot of ``ring`` [slots, R, width] (``R >= window
    + C``). Only the real positions are written (position ``t`` at row ``t
    mod R``), so a slot always holds the last ``R`` real positions written to
    it. The walk lies under the device scope ``<scope>.attend``. ``(out [C,
    dim] float32, ring, rounds walked)``."""
    C, R = x.shape[0], ring.shape[1]
    pos = offset + jnp.arange(C, dtype=jnp.int32)
    q, k, v = project(p, dims, x, pos)
    # the chunk's rows in the ring's order: row r takes position offset + i,
    # i = (r - offset) mod R, where that one is real
    new = jnp.pad(_to_cache(k, v, ring), ((0, R - C), (0, 0)))
    i = (jnp.arange(R, dtype=jnp.int32) - offset) % R
    held = jax.lax.dynamic_slice(ring, (slot, 0, 0), (1,) + ring.shape[1:])
    held = jnp.where((i < n_valid)[None, :, None],
                     jnp.roll(new, offset % R, axis=0)[None], held)
    ring = jax.lax.dynamic_update_slice(ring, held, (slot, 0, 0))
    with jax.named_scope(scope + ".attend"):
        o, rounds = _attend_ring(
            p, dims, q[None], pos[None],
            jnp.reshape(offset + n_valid - 1, (1,)), ring,
            jnp.reshape(slot, (1,)))
    return _out(p, dims, o[0], depth), ring, rounds


def window_extend(p, dims: GQADims, x, n_new, pos, ring, slots,
                  scope: str = "gqa_window", depth: int = 0):
    """:data:`extend` of a WINDOW layer: ``x`` [B, S, dim] at positions
    ``pos`` [B, S] of the slots ``slots`` [B], the first ``n_new`` [B] of
    each row real and written. ``(out [B, S, dim] float32, ring, rounds
    walked)``: every row walks as many rounds as the row that needs most."""
    B, S = pos.shape
    R = ring.shape[1]
    q, k, v = project(p, dims, x, pos)
    real = jnp.arange(S, dtype=jnp.int32)[None] < n_new[:, None]
    ring = ring.at[slots[:, None], jnp.where(real, pos % R, R)].set(
        _to_cache(k, v, ring), mode="drop")
    with jax.named_scope(scope + ".attend"):
        o, rounds = _attend_ring(
            p, dims, q, pos, pos[:, 0] + jnp.maximum(n_new, 1) - 1, ring,
            slots)
    return _out(p, dims, o, depth), ring, rounds
