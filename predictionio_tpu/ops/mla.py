"""Multi-head latent attention (MLA): two paths over one set of weights.

A token's keys and values are expanded from one small latent: ``c_kv``
(``kv_rank`` values, after its norm and scale) and one RoPE'd key head shared
by all heads (``d_rope`` values). That latent — not the heads' keys and values
— is what a cache holds: ``kv_rank + d_rope`` values a position.

* **prefill** (:func:`prefill_chunk`): a chunk of new positions against the
  slot's cache, with keys and values EXPANDED from the cached latents one
  block at a time (192-wide keys beside 128-wide values, online softmax, only
  as many blocks as the history has), in ONE kernel a mixer
  (``ops/pallas/chunk_attend.py``: a head a grid step, the blocks in a loop
  inside it, scores and probabilities in VMEM only; :func:`attend_blocks` is
  the same walk as XLA's own fusions, ``ops.attention.attend_over_blocks``
  over :func:`expand`: the tests' reference and the probe's baseline);
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

**A learned index** (``MLADims.index_heads`` > 0; DeepSeek-V3.2's "lightning
indexer"): beside its latent every position keeps an index key ``kI =
LayerNorm(x W_kI)`` (``index_dim`` values, a SECOND cached array), and a query
row scores every earlier position with ``I[t, s] = sum_j w[t, j] ReLU(qI[t, j]
. kI[s])`` (``qI = cQ W_qI`` as ``index_heads`` heads, ``w = x W_w`` times
``index_heads^-0.5 index_dim^-0.5``; the first ``d_rope`` values of every
``qI`` head and of ``kI`` under the same RoPE) and attends ONLY the
``min(index_topk, t + 1)`` positions of largest ``I[t, .]`` (ties: the earlier
position, ``jax.lax.top_k``'s rule). The cache of such a mixer is ``{"latent",
"index_k"}`` (:func:`init_cache`); the same two cached paths serve it, the
index inside them, and :func:`attend_full` is the plain form of both. A chunk
whose reach is at most ``index_topk`` attends all of it and scores nothing
(it still writes its index keys); past that it scores the slot's cached index
keys block by block, finds each row's exact set (:func:`topk_mask`: the k-th
largest score by bisection over the scores' bits, no sort) and walks the
blocks under that per-row mask: exact, and no saving over attending all
(512 rows' sets together cover every block; gathering 512 x 2,048 latents
instead measured 44.5 ms a layer against 3.0-19.5 at offsets of 2,048-32,256:
PERF.md section 5). An extension does the same in the absorbed form: its rows
score their slots' index keys (256 B a position at 128 bfloat16 values), find
their sets by the same bisection (:func:`extension_sets`) and walk the slots'
latents in place, each row under its own mask, nothing sorted and nothing
gathered (sorting 16 rows of 33,792 scores and gathering 16 x 2,048 latents
instead took 2.3-2.8 ms a layer where this takes 0.8-1.7 and the walk
without an index 0.7-1.5: PERF.md section 5).
"""

from __future__ import annotations

import contextlib
import dataclasses
import math

import jax
import jax.numpy as jnp

from predictionio_tpu.ops import pallas as pallas_ops
from predictionio_tpu.ops.attention import attend_over_blocks, mha_reference
from predictionio_tpu.ops.pallas import chunk_attend


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
    #: the learned index that picks the cached positions a row attends
    #: (0 heads: none, and every path is the one it was): heads and width of
    #: its queries and keys, positions a row keeps, its LayerNorm's epsilon
    index_heads: int = 0
    index_dim: int = 0
    index_topk: int = 0
    index_eps: float = 1e-6

    def __post_init__(self):
        if self.index_heads and not (
                self.index_topk > 0 and self.d_rope <= self.index_dim):
            raise ValueError(
                "an index needs index_topk > 0 and index_dim >= d_rope "
                "(its first d_rope values are under RoPE)")

    @property
    def has_index(self) -> bool:
        return self.index_heads > 0

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
    if d.has_index:
        shapes = {"w_qi": (d.q_rank, d.index_heads * d.index_dim),
                  "w_ki": (d.dim, d.index_dim), "w_w": (d.dim, d.index_heads)}
        out.update({n: (jax.random.normal(k, s, jnp.float32)
                        / math.sqrt(s[0])).astype(dtype)
                    for (n, s), k in zip(
                        shapes.items(),
                        jax.random.split(jax.random.fold_in(key, 1), 3))})
        out["ki_norm"] = {"scale": jnp.ones((d.index_dim,), dtype),
                          "bias": jnp.zeros((d.index_dim,), dtype)}
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


def compress_q(p, dims: MLADims, x):
    """The queries' latent ``cQ`` [..., T, q_rank] (float32), from which the
    heads' queries and an index's are expanded."""
    cq = rms_norm(mm(x, p["w_dq"]), p["q_norm"], dims.eps)
    if dims.scale_q:
        cq = cq * math.sqrt(dims.dim / dims.q_rank)
    return cq


def project(p, dims: MLADims, x, pos, cq):
    """Queries and latents of the positions ``x`` [..., T, dim] (``cq``:
    their :func:`compress_q`): ``(qN [..., T, H, d_nope], qR [..., T, H,
    d_rope] after RoPE, latent [..., T, kv_rank + d_rope])``, float32."""
    d = dims
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
    cq = compress_q(p, dims, x)
    qn, qr, latent = project(p, dims, x, pos, cq)
    k, v = expand(p, dims, latent)
    q = jnp.concatenate([qn, qr], axis=-1)
    keep = None
    if dims.has_index:
        qi, ki, w = project_index(p, dims, x, cq, pos)
        keep = selected_full(dims, index_scores(qi, w, ki), pos)
    return _out(p, dims, mha_reference(q, k, v, causal=True,
                                       scale=dims.softmax_scale, keep=keep))


# ---------------------------------------------------------------------------
# The learned index
# ---------------------------------------------------------------------------

def layer_norm(x, p, eps):
    x = x.astype(jnp.float32)
    mean = x.mean(axis=-1, keepdims=True)
    var = jnp.square(x - mean).mean(axis=-1, keepdims=True)
    return ((x - mean) * jax.lax.rsqrt(var + eps)
            * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32))


def project_index(p, dims: MLADims, x, cq, pos):
    """The index's view of the positions ``x`` [..., T, dim] (``cq``: their
    :func:`compress_q`): ``(qI [..., T, Hi, di], kI [..., T, di], w [..., T,
    Hi])``, float32; the first ``d_rope`` values of every ``qI`` head and of
    ``kI`` under the latents' RoPE, ``w`` with both of the score's scales."""
    d = dims
    freqs, amp = d.rope_freqs(), d.rope_amplitude

    def turned(v):
        return jnp.concatenate(
            [rope(v[..., :d.d_rope], pos, freqs, amp), v[..., d.d_rope:]],
            axis=-1)

    qi = turned(mm(cq, p["w_qi"]).reshape(
        x.shape[:-1] + (d.index_heads, d.index_dim)))
    ki = turned(layer_norm(mm(x, p["w_ki"]), p["ki_norm"], d.index_eps))
    w = mm(x, p["w_w"]) * (d.index_heads ** -0.5 * d.index_dim ** -0.5)
    return qi, ki, w


def index_scores(qi, w, k):
    """``I[t, s] = sum_j w[t, j] ReLU(qI[t, j] . kI[s])``: ``qi`` [..., T,
    Hi, di] and ``k`` [..., Lk, di] in one type (the cached keys'), ``w``
    [..., T, Hi] float32 -> [..., T, Lk] float32. The heads are summed on
    the vector unit: a second matrix product would round ``ReLU(.)`` and
    ``w`` to the chip's default precision, and move the cut."""
    s = jnp.einsum("...thd,...kd->...thk", qi, k,
                   preferred_element_type=jnp.float32)
    return (jax.nn.relu(s) * w[..., None]).sum(axis=-2)


def selected_full(dims: MLADims, scores, pos):
    """Every row's set as a mask [..., T, T] over all positions, from the
    full ``scores`` [..., T, T] (the plain form: ``jax.lax.top_k`` itself)."""
    T = scores.shape[-1]
    causal = pos[..., :, None] >= pos[..., None, :]
    idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf),
                        min(dims.index_topk, T))[1]
    return jnp.any(idx[..., None] == jnp.arange(T), axis=-2) & causal


def topk_mask(scores, k: int):
    """The ``k`` largest of each row of ``scores`` [R, W] (float32, ``-inf``
    where a position is out of reach) as a mask [R, W], ties to the earlier
    position: what ``jax.lax.top_k`` selects, without a sort. A float's bits
    order as the floats do once the negatives' are flipped; the k-th largest
    key of a row is then found bit by bit, from the top (32 counts over the
    row). Only where a tie straddles the cut are the tied positions ranked
    (one running count more)."""
    bits = jax.lax.bitcast_convert_type(scores + 0.0, jnp.int32)  # no -0.0
    key = bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))
    top = jnp.uint32(0x80000000)

    def signed(t):                  # the keys' order, from the unsigned one
        return jax.lax.bitcast_convert_type(t ^ top, jnp.int32)

    def bit(i, t):
        cand = t | (top >> i.astype(jnp.uint32))
        enough = (key >= signed(cand)[:, None]).sum(axis=1) >= k
        return jnp.where(enough, cand, t)

    kth = signed(jax.lax.fori_loop(
        0, 32, bit, jnp.zeros(scores.shape[0], jnp.uint32)))[:, None]
    above, tie = key > kth, key == kth
    room = k - above.sum(axis=1)                # of the ties, at least one
    return jax.lax.cond(
        (tie.sum(axis=1) > room).any(),
        lambda: above | (tie & (jnp.cumsum(tie, axis=1) <= room[:, None])),
        lambda: above | tie)


# ---------------------------------------------------------------------------
# The cached paths
# ---------------------------------------------------------------------------

def init_cache(dims: MLADims, slots: int, positions: int, dtype):
    """What one mixer keeps: ``[slots, positions, cache_width]`` latents, a
    bare array; under an index ``{"latent": that, "index_k": [slots,
    positions, index_dim]}`` (the key stands and falls with the latent,
    position by position, and is a second array because the scorer reads
    keys alone), ``positions`` then a whole number of attention blocks."""
    latent = jnp.zeros((slots, positions, cache_width(dims)), dtype)
    if not dims.has_index:
        return latent
    return {"latent": latent,
            "index_k": jnp.zeros((slots, positions, dims.index_dim), dtype)}


def _held(dims: MLADims, cache, block: int):
    """``(latents, index keys or None)`` of a mixer's cache."""
    if not dims.has_index:
        return cache, None
    if cache["latent"].shape[1] % block:
        raise ValueError("a slot of an indexed cache holds whole blocks")
    return cache["latent"], cache["index_k"]


def _part(dims: MLADims, name: str):
    """Under an index a mixer's parts have scopes of their own (a trace's
    readers tell the scorer's time from the attention's); else its caller's."""
    if dims.has_index:
        return jax.named_scope(name)
    return contextlib.nullcontext()


def attend_blocks(p, dims: MLADims, q, offset, latents, slot, n_blocks,
                  block: int, keep=None):
    """A chunk's queries ``q`` [C, H, d_qk] (the weights' type, positions
    ``offset + arange(C)``) over ``n_blocks`` blocks of slot ``slot`` of the
    cached ``latents``, each expanded (:func:`expand`) and folded into the
    running softmax by XLA's own fusions; ``keep`` [C, P] bool: each row's
    own set, or None. ``[C, H, d_v]`` float32. The plain form of
    :func:`attend_kernel`, which is what :func:`prefill_chunk` runs."""
    d = dims
    C = q.shape[0]

    def kv_block(j):
        lat = jax.lax.dynamic_slice(
            latents, (slot, j * block, 0), (1, block, latents.shape[-1]))
        return expand(p, d, lat[..., :d.latent])

    return attend_over_blocks(
        q[None], offset + jnp.arange(C, dtype=jnp.int32), kv_block, n_blocks,
        block, d.d_v, dtype=jnp.float32, scale=d.softmax_scale,
        keep_block=None if keep is None else lambda j: jax.lax.dynamic_slice(
            keep, (0, j * block), (C, block)))[0]


def attend_kernel(p, dims: MLADims, q, offset, latents, slot, n_blocks,
                  block: int, keep=None):
    """:func:`attend_blocks` as ONE kernel (``ops/pallas/chunk_attend.py``):
    queries and the two halves of ``w_ukv`` a head at a time, the RoPE part
    of a key where the kernel's layout has it; compiled on a TPU, under the
    Pallas interpreter elsewhere."""
    d = dims
    rope_at, wide = chunk_attend.key_layout(d.d_nope, d.d_rope)
    w = jnp.moveaxis(
        p["w_ukv"].reshape(d.kv_rank, d.heads, d.d_nope + d.d_v), 1, 0)
    o = chunk_attend.chunk_attend(
        jnp.moveaxis(chunk_attend.laid_out(
            q[..., :d.d_nope], q[..., d.d_nope:], rope_at), 1, 0),
        chunk_attend.laid_out(      # zero columns where the layout asks
            w[..., :d.d_nope],
            jnp.zeros(w.shape[:-1] + (wide - d.d_nope,), w.dtype), rope_at),
        w[..., d.d_nope:], latents, slot, offset, n_blocks, block=block,
        kv_rank=d.kv_rank, d_rope=d.d_rope, rope_at=rope_at,
        scale=d.softmax_scale,
        keep=None if keep is None else keep.astype(jnp.int8),
        interpret=pallas_ops.interpret_mode())
    return jnp.moveaxis(o, 0, 1)


def prefill_chunk(p, dims: MLADims, x, offset, cache, slot, block: int,
                  scope: str = "mla"):
    """A chunk ``x`` [C, dim] of ONE session, at positions ``offset +
    arange(C)``, against that session's slot of ``cache`` (:func:`init_cache`).
    Writes the chunk's latents (and index keys) into the slot, then attends
    over the slot's blocks up to the chunk's end, under each row's mask where
    an index's chunk reaches past ``index_topk`` positions. ``(out [C, dim]
    float32, cache, blocks of index keys scanned: 0 without an index)``."""
    d = dims
    C = x.shape[0]
    lat_c, idx_c = _held(d, cache, block)
    offset = jnp.asarray(offset, jnp.int32)
    pos = offset + jnp.arange(C, dtype=jnp.int32)
    cq = compress_q(p, d, x)
    if d.has_index:
        with jax.named_scope(scope + ".index"):
            qi, ki, w = project_index(p, d, x, cq, pos)
    qn, qr, latent = project(p, d, x, pos, cq)
    lat_c = jax.lax.dynamic_update_slice(
        lat_c, _to_cache(latent, lat_c)[None], (slot, offset, 0))
    if d.has_index:
        idx_c = jax.lax.dynamic_update_slice(
            idx_c, ki.astype(idx_c.dtype)[None], (slot, offset, 0))
    reach = offset + C
    n_blocks = (reach + block - 1) // block

    def attend(keep=None):
        with _part(d, scope + ".attend"):
            q = jnp.concatenate([qn, qr], axis=-1).astype(p["w_ukv"].dtype)
            return _out(p, d, attend_kernel(
                p, d, q, offset, lat_c, slot, n_blocks, block, keep))

    if not d.has_index:
        return attend(), lat_c, 0

    def under_the_mask():
        P = idx_c.shape[1]
        qi_c = qi.astype(idx_c.dtype)

        def score_block(j, scores):
            kb = jax.lax.dynamic_slice(
                idx_c, (slot, j * block, 0), (1, block, idx_c.shape[-1]))[0]
            k_pos = j * block + jnp.arange(block)
            s = jnp.where(pos[:, None] >= k_pos[None],
                          index_scores(qi_c, w, kb), -jnp.inf)
            return jax.lax.dynamic_update_slice(scores, s, (0, j * block))

        with jax.named_scope(scope + ".index"):
            scores = jax.lax.fori_loop(
                0, n_blocks, score_block,
                jnp.full((C, P), -jnp.inf, jnp.float32))
        with jax.named_scope(scope + ".select"):
            keep = topk_mask(scores, d.index_topk)
        return attend(keep)

    sparse = reach > d.index_topk
    out = jax.lax.cond(sparse, under_the_mask, attend)
    return (out, {"latent": lat_c, "index_k": idx_c},
            jnp.where(sparse, n_blocks, 0).astype(jnp.int32))


#: the float32 scores one step of an indexed extension's walk may hold, every
#: query row and head against one run of cached positions: 6 MiB is 16 rows x
#: 64 heads against 1,536 positions. Steps of 512 | 1,024 | 1,536 | 3,072 |
#: 5,632 positions read 1.92 | 1.70 | 1.65 | 1.73 | 1.85 ms a mixer at a reach
#: of 32,256 and 0.77 | 0.78 | 0.79 | 0.81 | 0.97 at 2,048 (PERF.md section
#: 5): fewer steps take their latency off the loops, wider ones walk further
#: past a short reach, and the scores cross HBM as often either way.
_WALK_SCORE_BYTES = 6 << 20


def _walk_block(P: int, block: int, rows: int) -> int:
    """Positions a step of an indexed extension's loops takes: the widest
    whole number of ``block``s that divides a slot's ``P`` positions (no step
    reaches past the slot's end) and whose float32 scores against ``rows``
    query rows fit :data:`_WALK_SCORE_BYTES`; ``block`` where none does."""
    n = P // block
    most = max(_WALK_SCORE_BYTES // (4 * rows * block), 1)
    return block * max(m for m in range(1, min(n, most) + 1) if n % m == 0)


def _of_slots(held, slots, j, wide: int):
    """Step ``j`` (``wide`` positions) of each of ``slots`` [B] of a cached
    array ``held`` [slots, P, width]: [B, wide, width]."""
    return jax.vmap(lambda s: jax.lax.dynamic_slice(
        held, (s, j * wide, 0), (1, wide, held.shape[-1]))[0])(slots)


def extension_sets(dims: MLADims, qi, w, index_k, slots, pos, n_wide,
                   wide: int, scope: str = "mla"):
    """Each extension row's set as a mask [B, S, P] over ITS slot of
    ``index_k`` [slots, P, index_dim]: its index's queries ``qi`` [B, S, Hi,
    di] (in the keys' type) and head weights ``w`` [B, S, Hi] against the
    slot's keys, ``n_wide`` steps of ``wide`` positions, out of the row's
    reach ``-inf``; then the row's exact ``index_topk`` (:func:`topk_mask`).
    A row that reaches no further than its set keeps all in reach, and what
    fills the set up lies past its reach: the walk's causal mask drops it."""
    B, S = pos.shape
    P = index_k.shape[1]

    def score_block(j, scores):
        k_pos = j * wide + jnp.arange(wide)
        s = jnp.where(
            pos[..., None] >= k_pos,
            index_scores(qi, w, _of_slots(index_k, slots, j, wide)), -jnp.inf)
        return jax.lax.dynamic_update_slice(scores, s, (0, 0, j * wide))

    with jax.named_scope(scope + ".index"):
        scores = jax.lax.fori_loop(
            0, n_wide, score_block,
            jnp.full((B, S, P), -jnp.inf, jnp.float32))
    with jax.named_scope(scope + ".select"):
        return topk_mask(scores.reshape(B * S, P),
                         min(dims.index_topk, P)).reshape(B, S, P)


def extend(p, dims: MLADims, x, pos, cache, slots, n_blocks, block: int,
           scope: str = "mla"):
    """A few new positions of several sessions, absorbed form: ``x``
    [B, S, dim] at positions ``pos`` [B, S] of the slots ``slots`` [B] of
    ``cache`` (:func:`init_cache`). Writes their latents (and index keys),
    then attends over the cached LATENTS themselves (one shared key/value
    "head" of ``latent`` / ``kv_rank`` values; the heads are folded into the
    query axis), ``block`` positions a step; ``n_blocks`` (traced) covers
    the longest session of the batch. Under an index each row attends under
    the mask of ITS set, and scorer and walk step :func:`_walk_block`
    positions (what a step reads past the batch's reach is past every
    row's). ``(out [B, S, dim] float32, cache, blocks of index keys in the
    batch's reach, what a row scans: 0 without an index)``."""
    d = dims
    B, S, _ = x.shape
    lat_c, idx_c = _held(d, cache, block)
    cq = compress_q(p, d, x)
    if d.has_index:
        with jax.named_scope(scope + ".index"):
            qi, ki, w = project_index(p, d, x, cq, pos)
    qn, qr, latent = project(p, d, x, pos, cq)
    latent = _to_cache(latent, lat_c)
    if d.has_index:
        ki = ki.astype(idx_c.dtype)
    for b in range(B):
        lat_c = jax.lax.dynamic_update_slice(
            lat_c, latent[b][None], (slots[b], pos[b, 0], 0))
        if d.has_index:
            idx_c = jax.lax.dynamic_update_slice(
                idx_c, ki[b][None], (slots[b], pos[b, 0], 0))
    wide, n_wide, keep_block = block, n_blocks, None
    if d.has_index:
        wide = _walk_block(idx_c.shape[1], block, B * S * d.heads)
        n_wide = (n_blocks * block + wide - 1) // wide
        keep = extension_sets(d, qi.astype(idx_c.dtype), w, idx_c, slots, pos,
                              n_wide, wide, scope)

        def keep_block(j):              # a row's set, for each of its heads
            return jnp.repeat(jax.lax.dynamic_slice(
                keep, (0, 0, j * wide), (B, S, wide)), d.heads, axis=1)

    with _part(d, scope + ".attend"):
        w_ukv = p["w_ukv"].reshape(d.kv_rank, d.heads, d.d_nope + d.d_v)
        q_abs = jnp.einsum("bshd,chd->bshc", qn.astype(w_ukv.dtype),
                           w_ukv[..., :d.d_nope],
                           preferred_element_type=jnp.float32)
        q = jnp.concatenate([q_abs, qr], axis=-1).astype(lat_c.dtype)

        def kv_block(j):
            lat = _of_slots(lat_c, slots, j, wide)
            return lat[:, :, None, :d.latent], lat[:, :, None, :d.kv_rank]

        o = attend_over_blocks(
            q.reshape(B, S * d.heads, 1, d.latent),
            jnp.repeat(pos, d.heads, axis=1), kv_block, n_wide, wide,
            d.kv_rank, dtype=jnp.float32, scale=d.softmax_scale,
            keep_block=keep_block).reshape(B, S, d.heads, d.kv_rank)
        o = jnp.einsum("bshc,chd->bshd", o.astype(w_ukv.dtype),
                       w_ukv[..., d.d_nope:],
                       preferred_element_type=jnp.float32)
        out = _out(p, d, o)
    if not d.has_index:
        return out, lat_c, 0
    return out, {"latent": lat_c, "index_k": idx_c}, n_blocks
