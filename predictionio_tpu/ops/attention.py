"""Long-context attention: blockwise (flash-style) and ring attention.

The reference has no sequence dimension at all (SURVEY.md §5.7 — it
predates LLMs), so there is no Scala counterpart to cite; this module is
the TPU-native capability the rebuild adds so DASE engines can model
*event sequences* (session/next-item recommendation) at histories far
longer than fit in one device's HBM:

  - ``blockwise_attention``: causal attention computed as an online-
    softmax scan over key/value blocks — O(block) memory instead of
    O(L^2), compiler-friendly (`lax.scan`, static shapes, MXU matmuls).
  - ``ring_attention``: sequence/context parallelism. The sequence axis
    is sharded over a mesh axis; each step every device computes one
    q-shard x kv-block partial and rotates the kv block to its ring
    neighbour with `lax.ppermute` — the collective rides ICI, and the
    online-softmax accumulators merge the partials exactly. This is the
    all-to-all-free formulation of Ring Attention (blockwise parallel
    transformers).

All shapes are [batch, seq, heads, head_dim]. Masking uses a large
finite negative (not -inf) so fully-masked blocks stay NaN-free.

NOTE on Pallas: the reference TPU flash-attention kernel
(jax.experimental.pallas.ops.tpu.flash_attention) was measured on-chip
against this module's XLA blockwise path at sessionrec-relevant shapes
(f32 and bf16, L in {512, 2048, 8192}, H in {2,4}, D in {32,64}):
4.9-7.2 TF/s blockwise vs 5.1-8.0 TF/s for the Pallas kernel — within
~10% everywhere, crossing over only at L >= 8k. At those margins the
dependency-free lax.scan formulation wins on maintainability, so the
compute path ships XLA; revisit if the model family moves to long-L
high-H regimes where the kernel's edge compounds.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

_NEG = -0.7 * jnp.finfo(jnp.float32).max


def mha_reference(
    q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = True,
    scale: Optional[float] = None,   # None: q's head width ** -0.5
    keep: Optional[jax.Array] = None,  # [B, Lq, Lk]: each row's own keys
) -> jax.Array:
    """Materialized-softmax attention, the correctness oracle for the
    blockwise/ring paths (and fine for short sequences)."""
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
    s = s * scale
    if causal:
        L_q, L_k = q.shape[1], k.shape[1]
        # supports q being a suffix of k's sequence (decode-style)
        q_pos = jnp.arange(L_q) + (L_k - L_q)
        mask = q_pos[:, None] >= jnp.arange(L_k)[None, :]
        s = jnp.where(mask[None, None], s, _NEG)
    if keep is not None:
        s = jnp.where(keep[:, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(q.dtype)


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
    window: int = 0,     # > 0: a query sees itself and the window - 1 before
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
        if window:
            mask = mask & (q_pos[..., :, None] - k_pos[..., None, :] < window)
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


def blockwise_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    block_size: int = 512,
    causal: bool = True,
) -> jax.Array:
    """Causal attention as a `lax.scan` over kv blocks — peak memory
    O(L * block) instead of O(L^2); each block partial is one MXU matmul
    pair. Shapes [B, L, H, D]; L must be divisible by block_size (pad
    upstream — the framework's fixed-shape discipline)."""
    B, L, H, D = q.shape
    if L % block_size:
        raise ValueError(f"seq len {L} not divisible by block_size {block_size}")
    n_blocks = L // block_size
    dtype = q.dtype
    qf = q.astype(jnp.float32)
    kb = k.astype(jnp.float32).reshape(B, n_blocks, block_size, H, D)
    vb = v.astype(jnp.float32).reshape(B, n_blocks, block_size, H,
                                       v.shape[-1])
    q_pos = jnp.arange(L)

    m0 = jnp.full((B, H, L), _NEG, jnp.float32)
    l0 = jnp.zeros((B, H, L), jnp.float32)
    o0 = jnp.zeros((B, L, H, v.shape[-1]), jnp.float32)

    def body(carry, blk):
        m, l, o = carry
        kblk, vblk, idx = blk
        k_pos = idx * block_size + jnp.arange(block_size)
        m, l, o = _accum_block(qf, kblk, vblk, m, l, o, q_pos, k_pos, causal)
        return (m, l, o), None

    (m, l, o), _ = jax.lax.scan(
        body,
        (m0, l0, o0),
        (kb.transpose(1, 0, 2, 3, 4), vb.transpose(1, 0, 2, 3, 4),
         jnp.arange(n_blocks)),
    )
    return _finish(m, l, o, dtype)


def attend_over_blocks(q, q_pos, kv_block, n_blocks, block_size: int,
                       v_dim: int, dtype=None,
                       scale: Optional[float] = None,
                       keep_block=None, first_block=None, window: int = 0,
                       sink=None) -> jax.Array:
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
    m)``, exactly 0) wipes what they added: every row must keep some key.

    A WINDOW (``window`` > 0: a query sees its own position and the ``window
    - 1`` before it) needs no walk from 0: ``first_block`` (a scalar, or [B]:
    each row of the batch its own) is the block of keys that round 0 holds,
    ``kv_block(j)`` gives round ``j``'s, and ``n_blocks`` counts rounds. A
    SINK (``sink`` [H, Lq] float32: a learned logit that joins each head's
    normaliser and nothing else) is where the running maximum starts, with
    the normaliser at ``exp(sink - sink)``."""
    B, Lq, H, _ = q.shape
    if sink is None:
        carry = (jnp.full((B, H, Lq), _NEG, jnp.float32),
                 jnp.zeros((B, H, Lq), jnp.float32))
    else:
        carry = (jnp.broadcast_to(sink, (B, H, Lq)),
                 jnp.ones((B, H, Lq), jnp.float32))
    carry += (jnp.zeros((B, Lq, H, v_dim), jnp.float32),)

    def body(j, carry):
        k, v = kv_block(j)
        at = j if first_block is None else jnp.asarray(
            first_block + j)[..., None]
        k_pos = at * block_size + jnp.arange(block_size)
        keep = None if keep_block is None else keep_block(j)
        return _accum_block(q, k, v, *carry, q_pos, k_pos, True, scale, keep,
                            window)

    m, l, o = jax.lax.fori_loop(0, n_blocks, body, carry)
    return _finish(m, l, o, dtype or q.dtype)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis: str,
    causal: bool = True,
) -> jax.Array:
    """Per-shard ring attention body — call INSIDE `shard_map` with the
    sequence dimension sharded over mesh axis ``axis``.

    Each of the S devices holds a [B, L/S, H, D] shard. S steps: compute
    the partial against the resident kv block, then rotate kv to the
    next device with `ppermute` (ICI neighbour exchange — no all-to-all,
    no O(S) memory). After step s, device i holds the block that
    originated at device (i - s - 1) mod S; global positions for causal
    masking are reconstructed from the origin index.
    """
    size = jax.lax.psum(1, axis)
    my = jax.lax.axis_index(axis)
    B, Lq, H, D = q.shape
    dtype = q.dtype
    qf = q.astype(jnp.float32)
    q_pos = my * Lq + jnp.arange(Lq)

    m0 = jnp.full((B, H, Lq), _NEG, jnp.float32)
    l0 = jnp.zeros((B, H, Lq), jnp.float32)
    o0 = jnp.zeros((B, Lq, H, D), jnp.float32)
    perm = [(j, (j + 1) % size) for j in range(size)]

    def body(step, carry):
        m, l, o, kc, vc = carry
        src = (my - step) % size                       # block's origin device
        k_pos = src * kc.shape[1] + jnp.arange(kc.shape[1])
        m, l, o = _accum_block(qf, kc, vc, m, l, o, q_pos, k_pos, causal)
        kc = jax.lax.ppermute(kc, axis, perm)
        vc = jax.lax.ppermute(vc, axis, perm)
        return m, l, o, kc, vc

    # S-1 rotate-and-accumulate steps, then the final block accumulates
    # WITHOUT rotating — the last ppermute's output is dead, and a ring
    # exchange per layer per step is too expensive to waste
    m, l, o, kc, vc = jax.lax.fori_loop(
        0, size - 1, body,
        (m0, l0, o0, k.astype(jnp.float32), v.astype(jnp.float32)),
    )
    src = (my - (size - 1)) % size
    k_pos = src * kc.shape[1] + jnp.arange(kc.shape[1])
    m, l, o = _accum_block(qf, kc, vc, m, l, o, q_pos, k_pos, causal)
    return _finish(m, l, o, dtype)


def ring_attention_sharded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    axis: str = "seq",
    causal: bool = True,
    batch_axis: Optional[str] = None,
) -> jax.Array:
    """Convenience wrapper: shard the sequence dim over ``axis`` (and
    optionally batch over ``batch_axis``) and run ring attention under
    `shard_map`. Inputs may be unsharded host arrays; GSPMD lays them
    out and inserts the transfers."""
    spec = P(batch_axis, axis, None, None)
    fn = functools.partial(ring_attention, axis=axis, causal=causal)
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)
