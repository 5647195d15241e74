"""Multi-head latent attention (MLA): two paths over one set of weights.

A token's keys and values are expanded from one small latent: ``c_kv``
(``kv_rank`` values, after its norm and scale) and one RoPE'd key head shared
by all heads (``d_rope`` values). That latent — not the heads' keys and values
— is what a cache holds: ``kv_rank + d_rope`` values a position.

* **prefill** (:func:`prefill_chunk`): a chunk of new positions against the
  slot's cache, with keys and values EXPANDED from the cached latents one
  block at a time (``ops.attention.attend_over_blocks``: 192-wide keys beside
  128-wide values, online softmax, only as many blocks as the history has);
* **extension** (:func:`extend`): a few new positions of several sessions in
  the ABSORBED form, straight over the cached latents: the key expansion is
  folded into the query (``q~_h = qN_h W_uk,h^T``) and the value expansion is
  applied after the weighted sum, so a step reads each latent once and
  expands nothing.

Both give the numbers of :func:`attend_full` (every position against every
earlier one, no cache), which is the plain form the tests hold them to.

Matrix products take their inputs in the weights' type and accumulate in
float32; norms, RoPE and softmax are float32. Padding positions of a chunk or
of an extension write latents beyond the session's real length: a later
position is written before anything attends to it, so they are never read.

Positions past the length a model was trained at (YaRN, ``rope_factor`` > 1):
pair ``i`` of the RoPE dimensions turns at ``f_i (1 - r_i) + f_i / factor *
r_i``, ``f_i = theta^(-2i/d)``, where ``r_i`` ramps from 0 at the pair that
makes ``beta_fast`` turns over the original length to 1 at the pair that
makes ``beta_slow`` (fast pairs keep their frequency, slow ones are
interpolated); cos and sin are multiplied by ``m(mscale) / m(mscale_all_dim)``
and the softmax scale by ``m(mscale_all_dim)^2``, ``m(s) = 0.1 s ln(factor)
+ 1``. One scale (:attr:`MLADims.softmax_scale`) reaches all three paths.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from predictionio_tpu.ops.attention import attend_over_blocks, mha_reference


@dataclasses.dataclass(frozen=True)
class MLADims:
    dim: int
    heads: int
    d_nope: int
    d_rope: int
    d_v: int
    q_rank: int
    kv_rank: int
    rope_theta: float = 1e7
    eps: float = 1e-5
    scale_q: bool = True        # cQ * sqrt(dim / q_rank)
    scale_kv: bool = True       # cKV * sqrt(dim / kv_rank)
    rope_factor: float = 1.0    # YaRN: positions stretched this many times
    rope_original_max: int = 0  # over the length the model was trained at
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0

    @property
    def latent(self) -> int:
        return self.kv_rank + self.d_rope

    @property
    def d_qk(self) -> int:
        return self.d_nope + self.d_rope

    def _yarn_m(self, s: float) -> float:
        return (0.1 * s * math.log(self.rope_factor) + 1.0
                if self.rope_factor > 1 else 1.0)

    @property
    def softmax_scale(self) -> float:
        """What a query-key product is multiplied by before the softmax."""
        m = self._yarn_m(self.rope_mscale_all_dim)
        return self.d_qk ** -0.5 * m * m

    @property
    def rope_amplitude(self) -> float:
        """What cos and sin are multiplied by."""
        return (self._yarn_m(self.rope_mscale)
                / self._yarn_m(self.rope_mscale_all_dim))

    def rope_freqs(self):
        """The angle a position adds to each pair of the RoPE dimensions,
        ``[d_rope / 2]`` float32."""
        d = self.d_rope
        f = self.rope_theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        if self.rope_factor <= 1:
            return f

        def pair_of(turns):     # the pair that makes ``turns`` turns over
            return (d * math.log(self.rope_original_max     # the original
                                 / (turns * 2 * math.pi))   # length
                    / (2 * math.log(self.rope_theta)))

        lo = max(math.floor(pair_of(self.rope_beta_fast)), 0)
        hi = min(math.ceil(pair_of(self.rope_beta_slow)), d - 1)
        r = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - lo)
                     / max(hi - lo, 1e-3), 0.0, 1.0)
        return f * (1.0 - r) + f / self.rope_factor * r


def init(key, dims: MLADims, dtype=jnp.float32) -> dict:
    """N(0, 1 / fan_in) matrices, unit norms."""
    d = dims
    shapes = {"w_dq": (d.dim, d.q_rank),
              "w_uq": (d.q_rank, d.heads * d.d_qk),
              "w_dkv": (d.dim, d.latent),
              "w_ukv": (d.kv_rank, d.heads * (d.d_nope + d.d_v)),
              "w_o": (d.heads * d.d_v, d.dim)}
    out = {n: (jax.random.normal(k, s, jnp.float32) / math.sqrt(s[0])
               ).astype(dtype)
           for (n, s), k in zip(shapes.items(),
                                jax.random.split(key, len(shapes)))}
    out["q_norm"] = jnp.ones((d.q_rank,), dtype)
    out["kv_norm"] = jnp.ones((d.kv_rank,), dtype)
    return out


def mm(x, w):
    """``x @ w`` with the inputs in the weight's type, float32 out."""
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=jnp.float32)


def rms_norm(x, w, eps):
    x = x.astype(jnp.float32)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * w.astype(jnp.float32))


def rope(x, pos, freqs, amplitude: float = 1.0):
    """``x`` [..., T, H, d] or [..., T, d] (float32), ``pos`` [..., T]:
    dimensions (2i, 2i+1) turned by ``pos * freqs[i]`` (``freqs`` [d / 2]:
    :meth:`MLADims.rope_freqs`), cos and sin times ``amplitude``."""
    ang = pos.astype(jnp.float32)[..., None] * freqs         # [..., T, d/2]
    if x.ndim == pos.ndim + 2:                               # a head axis
        ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if amplitude != 1.0:
        cos, sin = amplitude * cos, amplitude * sin
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


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


def expand(p, dims: MLADims, latent):
    """Keys and values of cached latents [..., T, latent]:
    ``(k [..., T, H, d_nope + d_rope], v [..., T, H, d_v])`` in the weights'
    type, the shared RoPE key copied to every head."""
    d = dims
    w = p["w_ukv"]
    kv = mm(latent[..., :d.kv_rank], w).astype(w.dtype).reshape(
        latent.shape[:-1] + (d.heads, d.d_nope + d.d_v))
    kr = jnp.broadcast_to(
        latent[..., None, d.kv_rank:].astype(w.dtype),
        latent.shape[:-1] + (d.heads, d.d_rope))
    return (jnp.concatenate([kv[..., :d.d_nope], kr], axis=-1),
            kv[..., d.d_nope:])


def cache_width(dims: MLADims) -> int:
    """Values a cached position takes: the latent, padded to whole 128-lane
    rows. At 576 values the chip's compiler kept the cache with POSITIONS
    on the lanes inside the programs and copied all of it in and out of
    every call (8 x 0.7 ms a run: my chip run, PR 27); at 640 it leaves
    the buffer as it is handed over."""
    return -(-dims.latent // 128) * 128


def _to_cache(latent, cache):
    pad = cache.shape[-1] - latent.shape[-1]
    return jnp.pad(latent.astype(cache.dtype),
                   [(0, 0)] * (latent.ndim - 1) + [(0, pad)])


def _out(p, dims: MLADims, o):
    return mm(o.reshape(o.shape[:-2] + (dims.heads * dims.d_v,)), p["w_o"])


def attend_full(p, dims: MLADims, x, pos):
    """Every position of ``x`` [B, T, dim] against every earlier one, keys
    and values expanded in full, scores materialised: the plain form."""
    qn, qr, latent = project(p, dims, x, pos)
    k, v = expand(p, dims, latent)
    q = jnp.concatenate([qn, qr], axis=-1)
    return _out(p, dims, mha_reference(q, k, v, causal=True,
                                       scale=dims.softmax_scale))


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
