"""Plain reference for GLM-5's forward pass over an item history.

``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``: no cache,
no chunked prefill, no kernel, no batching, nothing of the program. The
equations (source: https://huggingface.co/zai-org/GLM-5/blob/main/config.json,
``model_type: glm_moe_dsa``; latent attention and expert layers are the
DeepSeek-V3 family's, the index DeepSeek-V3.2's, whose three keys
``index_n_heads``, ``index_head_dim``, ``index_topk`` the file carries):

* ``RMS(x) = x / sqrt(mean(x^2) + eps) * w``; ``LN(x) = (x - mean) / sqrt(var
  + eps_i) * w + b``;
* **model**: ``x0 = E[ids]``; layer ``l``: ``h = x + MLA(RMS(x))``, ``out = h +
  FFN_l(RMS(h))``; final ``RMS``; logits ``= h_last W_head^T`` (untied);
* **MLA** over ``u = RMS(x)``: ``cQ = RMS_q(u W_dq)`` (no LoRA scale); per
  head ``[qN ; qR] = cQ W_uq`` with RoPE on ``qR``; ``[cKV_raw ; kR_raw] = u
  W_dkv``, ``cKV = RMS_kv(cKV_raw)``, ``kR = RoPE(kR_raw)`` (one head shared
  by all); per head ``[kN ; v] = cKV W_ukv``; position ``t`` attends ``S_t``
  ONLY: ``o_t,h = sum_{s in S_t} softmax_s((qN.kN + qR.kR) / sqrt(d_nope +
  d_rope)) v_s,h``; heads concatenated, ``W_o``;
* **the index**: ``qI = cQ W_qI`` as ``index_n_heads`` heads of
  ``index_head_dim``, ``kI = LN(u W_kI)``, the FIRST ``d_rope`` dimensions of
  each under the same RoPE; ``w = u W_w * index_n_heads^-0.5 *
  index_head_dim^-0.5``; ``I[t, s] = sum_j w[t, j] ReLU(qI[t, j] . kI[s])`` for
  ``s <= t``; ``S_t`` = the ``min(index_topk, t + 1)`` positions of largest
  ``I[t, .]`` (the set ``jax.lax.top_k`` names, ties to the earlier
  position; :func:`largest` finds it by counting);
* **RoPE**: plain, pairs ``(2i, 2i+1)``, angle ``t theta^(-2i/d_rope)``;
* **FFN_l, l < first_k_dense_replace**: ``(silu(x W_g) * (x W_u)) W_d``;
  **later**: ``Shared(u) + sum_{i in S} g_i Expert_i(u)``, the shared expert
  and every routed one the same SwiGLU, narrower;
* **router** (``noaux_tc``, one group): ``s = sigmoid(u W_r)`` over the routed
  experts, ``S`` = the ``top_k`` largest ``s + b`` (``b``: the selection
  bias, which chooses and does not weigh), ``g_i = scale * s_i / sum_{j in S}
  s_j``. **The share**: ``held = (e0, n)`` says which routed experts exist
  here; what the others would add is left out (``moe_parts`` returns the
  routed part and the shared expert's apart, so that a test can add shares
  up and count the shared expert once).

Departures from the published description, each listed under ``assumed`` in
the configuration's file: the index's form is DeepSeek-V3.2's; its Hadamard
rotation of ``qI`` and ``kI`` (orthogonal: the same scores in exact
arithmetic) and their FP8 quantisation are left out; the multi-token-
prediction layer is not run; weights are seeded.

So that 32,795 positions fit beside 8 GB of weights, a block of :data:`BLOCK`
QUERIES at a time is scored against every index key (a head at a time) and
given its set (kept as a mask over all positions), then a group of heads at
a time is attended, a block of queries at a time, against the blocks of keys
up to its own (causal: a later block holds nothing it may attend), the
softmax carried from block to block as its running maximum and sum;
position-wise parts run a block of rows at a time, a routed expert over the
rows routed to it alone (:func:`gated`): each number is the one the unblocked
form gives, to float32 rounding. A history is padded to ONE length a
cell (:func:`shapes`: what the traffic's longest history can reach; causal:
positions after the last real one change nothing before them and are never
selected) and its REAL length goes in as an argument that bounds every loop
over blocks: the work follows the real length, and each layer kind compiles
once. Weights arrive as the benchmark's seeded arrays (bfloat16-valued) and
are widened inside each jitted part.

``compare`` holds an answer to the reference's forward, and where the router's
cut at the answer's last position is open (a held expert within
:data:`CUT_TOL` of it) to the forward on either side of that cut. The other
side is recomputed as ONE ROW (:func:`crossed_row`): the forward keeps every
layer's keys (latents, RoPE keys, index keys) and its last position's input,
and beyond the crossed layer only that position's row differs.

``hold``: the control. ``(exponent_bits, mantissa_bits)`` rounds every weight
and every matrix product's input to that format (``lax.reduce_precision``);
``None`` is the reference proper.
"""

from __future__ import annotations

import functools
import math

import numpy as np

FORMATS = {"bfloat16": (8, 7), "float8_e4m3fn": (4, 3)}
#: queries of one attention block, rows of one position-wise block
BLOCK = 1024
#: [heads of a group, BLOCK, BLOCK] float32 scores held at once
SCORE_BYTES = 1 << 29
#: [positions, heads of a group, head width] float32 queries, keys and values
#: of one group held at once
HEAD_BYTES = 1 << 30
#: routed rows of a block that one expert's matrices see at once
ROUTED_ROWS = 128
#: histories past these are reported: past the first the index selects, past
#: the second it drops three quarters of what is in reach
LONG = (2048, 8192)
#: the router's ``top_k``-th pick and the next lie this close, relative: a
#: near tie (counted and reported)
NEAR_TOL = 1e-3
#: ... and this close: a cut that roundings upstream may cross. The program's
#: products take bfloat16 inputs and the router's scores arrive a little off
#: the reference's: over 160 answers of two seeds on the chip, every held
#: expert within 1.14e-3 of the cut at an answer's last position was found
#: on the other side in five cases of eight, none of the 35 between 1.2e-3
#: and 6e-3 (PERF.md section 4, PR 43); three times the widest crossing
CUT_TOL = 3e-3
#: ranks on either side of the cut that may take part in one near tie
CUT_WINDOW = 3
#: other sides :func:`compare` tries of one answer's open cuts, at most
CROSSINGS = 8


def dims_of(cfg: dict) -> dict:
    """The sizes the equations need, from the configuration's own keys."""
    rope = cfg["rope_parameters"]
    if rope["rope_type"] != "default" or cfg["topk_method"] != "noaux_tc" \
            or int(cfg["n_group"]) != 1:
        raise ValueError("the reference writes out plain RoPE and one "
                         "group's noaux_tc selection only")
    return {
        "D": int(cfg["hidden_size"]), "H": int(cfg["num_attention_heads"]),
        "dn": int(cfg["qk_nope_head_dim"]), "dr": int(cfg["qk_rope_head_dim"]),
        "dv": int(cfg["v_head_dim"]), "rq": int(cfg["q_lora_rank"]),
        "rkv": int(cfg["kv_lora_rank"]), "theta": float(rope["rope_theta"]),
        "eps": float(cfg["rms_norm_eps"]),
        "Hi": int(cfg["index_n_heads"]), "di": int(cfg["index_head_dim"]),
        "topk": int(cfg["index_topk"]),
        "eps_i": float(cfg["assumed_sizes"]["index_layernorm_eps"]),
        "n_routed": int(cfg["n_routed_experts_published"]),
        "top_k": int(cfg["num_experts_per_tok"]),
        "scale": float(cfg["routed_scaling_factor"]),
        "held": tuple(int(v) for v in cfg["experts_held"]),
        # the leading dense layers are one layer and count once here
        "first_dense": int(cfg["first_k_dense_replace_held"]),
    }


def _hold(x, hold):
    import jax

    return x if hold is None else jax.lax.reduce_precision(x, *hold)


def _mm(x, w, hold):
    import jax.numpy as jnp

    return jnp.dot(_hold(x, hold), _hold(w.astype(jnp.float32), hold))


def _blocked(T: int) -> bool:
    return T > BLOCK and T % BLOCK == 0


def blocks_of(n):
    """How many blocks of :data:`BLOCK` the first ``n`` positions lie in
    (``n``: an int or a traced scalar); None, every block, for None."""
    return None if n is None else (n + BLOCK - 1) // BLOCK


def _rows(fn, x, nb=None):
    """``fn`` over ``x`` [T, ...] a block of rows at a time (each row's
    result, an array or several, is its own): the first ``nb`` blocks only
    (a traced count; None: all), the rows of the others left zero."""
    import jax
    import jax.numpy as jnp

    T = x.shape[0]
    if not _blocked(T):
        return fn(x)
    blocks = T // BLOCK
    xb = x.reshape((blocks, BLOCK) + x.shape[1:])
    like = jax.eval_shape(fn, jax.ShapeDtypeStruct(xb.shape[1:], xb.dtype))

    def body(i, acc):
        got = fn(jax.lax.dynamic_index_in_dim(xb, i, keepdims=False))
        return jax.tree_util.tree_map(
            lambda a, v: jax.lax.dynamic_update_index_in_dim(a, v, i, 0),
            acc, got)

    out = jax.lax.fori_loop(
        0, blocks if nb is None else jnp.minimum(nb, blocks), body,
        jax.tree_util.tree_map(
            lambda s: jnp.zeros((blocks,) + s.shape, s.dtype), like))
    return jax.tree_util.tree_map(
        lambda a: a.reshape((T,) + a.shape[2:]), out)


def rms(x, w, eps):
    import jax.numpy as jnp

    return (x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * w.astype(jnp.float32))


def layer_norm(x, p, eps):
    import jax.numpy as jnp

    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return ((x - mean) / jnp.sqrt(var + eps) * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32))


def rope(x, pos, dm):
    """``x`` [T, ..., d]: dimensions (2i, 2i+1) turned by ``pos *
    theta^(-2i/d)``."""
    import jax.numpy as jnp

    d = x.shape[-1]
    freqs = dm["theta"] ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(freqs, jnp.float32)
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def latents(p, x, pos, dm, hold=None, nb=None):
    """The position-wise part of latent attention over ``x`` [T, D] at
    ``pos``: ``(cq [T, rq], keys, (qI [T, Hi, di], w [T, Hi]))``, ``keys =
    (cKV [T, rkv], kR [T, dr], kI [T, di])``: what a position leaves for
    later ones to attend and to select by."""
    import jax.numpy as jnp

    rkv, dr = dm["rkv"], dm["dr"]

    def turned(v):
        return jnp.concatenate([rope(v[..., :dr], pos, dm), v[..., dr:]],
                               axis=-1)

    cq = _rows(lambda r: rms(_mm(r, p["w_dq"], hold), p["q_norm"],
                             dm["eps"]), x, nb)
    down = _rows(lambda r: _mm(r, p["w_dkv"], hold), x, nb)
    ckv = rms(down[:, :rkv], p["kv_norm"], dm["eps"])
    kr = rope(down[:, rkv:], pos, dm)                        # [T, dr]
    qi = turned(_rows(lambda r: _mm(r, p["w_qi"], hold), cq, nb).reshape(
        x.shape[0], dm["Hi"], dm["di"]))
    ki = turned(layer_norm(_rows(lambda r: _mm(r, p["w_ki"], hold), x, nb),
                           p["ki_norm"], dm["eps_i"]))
    w = _mm(x, p["w_w"], hold) * (dm["Hi"] ** -0.5 * dm["di"] ** -0.5)
    return cq, (ckv, kr, ki), (qi, w)


def largest(s, k: int):
    """The ``k`` largest of each row of ``s`` [R, T] as a mask, ties to the
    earlier position: the set ``jax.lax.top_k(s, k)`` names, found by
    COUNTING instead of sorting (a sort of 36,864 scores a row was the
    larger part of a forward). A float's bits, flipped so that they grow with
    it, are an unsigned integer; the k-th largest is built bit by bit, from
    the top, as the largest value that ``k`` scores reach (32 counts over
    the row); the scores above it are in, and the earliest of those equal to
    it fill what room is left."""
    import jax
    import jax.numpy as jnp

    bits = jax.lax.bitcast_convert_type(s + 0.0, jnp.uint32)   # -0.0: +0.0
    u = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))

    def bit(i, reached):
        higher = reached | jnp.left_shift(jnp.uint32(1),
                                          (31 - i).astype(jnp.uint32))
        enough = jnp.sum(u >= higher[:, None], axis=-1) >= k
        return jnp.where(enough, higher, reached)

    kth = jax.lax.fori_loop(0, 32, bit, jnp.zeros(s.shape[0], jnp.uint32))
    above, tie = u > kth[:, None], u == kth[:, None]
    room = k - jnp.sum(above, axis=-1)
    return above | (tie & (jnp.cumsum(tie, axis=-1) <= room[:, None]))


def selected(qi, w, q_pos, ki, k_pos, dm, hold=None, nkb=None):
    """The sets of the query rows ``qi`` [Tq, Hi, di] (weights ``w``,
    positions ``q_pos``) over the keys ``ki`` [T, di] as a mask [Tq, T]:
    ``I`` a head at a time (over the first ``nkb`` blocks of keys; None:
    all), the ``index_topk`` :func:`largest` of each row, causal."""
    import jax
    import jax.numpy as jnp

    Tq, T = qi.shape[0], ki.shape[0]
    heads = (qi.transpose(1, 0, 2), w.T)

    def against(keys):                                    # [Tk, di]
        def head(acc, args):
            q_j, w_j = args                               # [Tq, di], [Tq]
            s = jnp.dot(_hold(q_j, hold), _hold(keys, hold).T)
            return acc + w_j[:, None] * jnp.maximum(s, 0.0), None

        return jax.lax.scan(head, jnp.zeros((Tq, keys.shape[0]),
                                            jnp.float32), heads)[0]

    if _blocked(T):
        ki_b = ki.reshape(T // BLOCK, BLOCK, -1)
        scores = jax.lax.fori_loop(
            0, T // BLOCK if nkb is None else jnp.minimum(nkb, T // BLOCK),
            lambda c, acc: jax.lax.dynamic_update_slice_in_dim(
                acc, against(jax.lax.dynamic_index_in_dim(
                    ki_b, c, keepdims=False)), c * BLOCK, 1),
            jnp.zeros((Tq, T), jnp.float32))
    else:
        scores = against(ki)
    causal = q_pos[:, None] >= k_pos[None, :]
    return largest(jnp.where(causal, scores, -jnp.inf),
                   min(dm["topk"], T)) & causal


def attend(p, cq, index_q, q_pos, keys, k_pos, dm, hold=None, nqb=None,
           nkb=None):
    """The query rows ``cq`` [Q, rq] (``index_q = (qI, w)``, positions
    ``q_pos``) over ``keys`` (:func:`latents`) at ``k_pos``, every row over
    its own set: ``(out [Q, D], the sets as a mask [Q, T])``. ``nqb`` /
    ``nkb``: the blocks of queries / of keys in use (traced counts; None:
    all); a block of queries walks the blocks of keys up to its own last
    position's."""
    import jax
    import jax.numpy as jnp

    ckv, kr, ki = keys
    qi, w = index_q
    Q, T = cq.shape[0], ckv.shape[0]
    H, dn, dr, dv, rkv = dm["H"], dm["dn"], dm["dr"], dm["dv"], dm["rkv"]
    scale = 1.0 / math.sqrt(dn + dr)
    Tq = BLOCK if _blocked(Q) else Q
    Tk = BLOCK if _blocked(T) else T
    qblocks, kblocks = Q // Tq, T // Tk
    nqb = qblocks if nqb is None else jnp.minimum(nqb, qblocks)
    group = max(1, min(H, SCORE_BYTES // (4 * Tq * Tk),
                       HEAD_BYTES // (4 * (Q + T) * (dn + max(dr, dv)))))
    while H % group:
        group -= 1
    w_uq = p["w_uq"].reshape(dm["rq"], H // group, group, dn + dr)
    w_ukv = p["w_ukv"].reshape(rkv, H // group, group, dn + dv)
    w_o = p["w_o"].reshape(H // group, group * dv, dm["D"])
    q_pos_b = q_pos.reshape(qblocks, Tq)

    def block(a, i):
        return jax.lax.dynamic_index_in_dim(a, i, keepdims=False)

    # every row's set first, a block of queries at a time ([Q, T] as a mask:
    # 1.4 GB at 36,864 positions), then the heads a group at a time, each
    # group's keys and values expanded once
    qi_b, w_b = qi.reshape(qblocks, Tq, dm["Hi"], -1), w.reshape(
        qblocks, Tq, -1)
    def reach(b):
        """The blocks of keys up to the last position of queries' block b."""
        return jnp.minimum(jnp.max(block(q_pos_b, b)) // Tk + 1, kblocks)

    keep = jax.lax.fori_loop(0, nqb, lambda b, acc: (
        jax.lax.dynamic_update_index_in_dim(acc, selected(
            block(qi_b, b), block(w_b, b), block(q_pos_b, b), ki, k_pos, dm,
            hold, reach(b)), b, 0)), jnp.zeros((qblocks, Tq, T), bool))

    def heads(out, args):                                   # [qblocks, Tq, D]
        uq, ukv, wo = args                                  # one group's
        q = _rows(lambda r: _mm(r, uq.reshape(dm["rq"], -1), hold), cq,
                  nqb).reshape(qblocks, Tq, group, dn + dr)
        kv = _rows(lambda r: _mm(r, ukv.reshape(rkv, -1), hold), ckv,
                   nkb).reshape(kblocks, Tk, group, dn + dv)
        kr_b = kr.reshape(kblocks, Tk, dr)

        def queries(b, out):
            # a block's own slices: nothing of the whole length is copied
            q_q, keep_q = block(q, b), block(keep, b).reshape(Tq, kblocks, Tk)
            qn_q, qr_q = q_q[..., :dn], rope(q_q[..., dn:],
                                             block(q_pos_b, b), dm)

            def keys_block(c, carry):
                m, l, acc = carry               # [g, Tq] twice, [g, Tq, dv]
                kv_c = block(kv, c)
                s = (jnp.einsum("tgd,ugd->gtu", _hold(qn_q, hold),
                                _hold(kv_c[..., :dn], hold))
                     + jnp.einsum("tgd,ud->gtu", _hold(qr_q, hold),
                                  _hold(block(kr_b, c), hold)))
                s = jnp.where(keep_q[:, c][None], s * scale, -jnp.inf)
                top = jnp.maximum(m, s.max(axis=-1))
                at = jnp.where(jnp.isfinite(top), top, 0.0)
                e = jnp.exp(s - at[..., None])
                shrink = jnp.exp(m - at)         # 0 while nothing was seen
                return (top, shrink * l + e.sum(axis=-1),
                        shrink[..., None] * acc + jnp.einsum(
                            "gtu,ugd->gtd", _hold(e, hold),
                            _hold(kv_c[..., dn:], hold)))

            _, l, acc = jax.lax.fori_loop(0, reach(b), keys_block, (
                jnp.full((group, Tq), -jnp.inf), jnp.zeros((group, Tq)),
                jnp.zeros((group, Tq, dv))))
            o_q = jnp.where(l[..., None] > 0, acc / l[..., None], 0.0)
            return jax.lax.dynamic_update_index_in_dim(
                out, block(out, b) + _mm(o_q.transpose(1, 0, 2).reshape(
                    Tq, group * dv), wo, hold), b, 0)

        return jax.lax.fori_loop(0, nqb, queries, out), None

    out = jax.lax.scan(heads, jnp.zeros((qblocks, Tq, dm["D"])), (
        w_uq.transpose(1, 0, 2, 3), w_ukv.transpose(1, 0, 2, 3), w_o))[0]
    out = out.reshape(Q, dm["D"])
    return out, keep.reshape(Q, T)


def mla(p, x, pos, dm, hold=None, with_sets: bool = False, n=None):
    """Latent attention over one sequence ``x`` [T, D], every row over its
    own set; with ``with_sets`` also the sets, as a mask [T, T]. ``n``: the
    real length (a traced scalar; None: all of ``T``): the blocks past it are
    not computed."""
    nb = blocks_of(n)
    cq, keys, index_q = latents(p, x, pos, dm, hold, nb)
    out, keep = attend(p, cq, index_q, pos, keys, pos, dm, hold, nb, nb)
    return (out, keep) if with_sets else out


def ffn(p, x, hold=None, nb=None):
    import jax

    return _rows(lambda r: _mm(
        jax.nn.silu(_mm(r, p["w_g"], hold)) * _mm(r, p["w_u"], hold),
        p["w_d"], hold), x, nb)


def gated(p, x, gates, hold=None, nb=None):
    """``sum_e gates[:, e, None] * ffn(p_e, x)`` over the experts whose
    matrices ``p`` stacks, each computed for the rows whose gate is not 0
    alone (an expert held here sees one token in thirty-two): a block of rows
    at a time, an expert at a time, its routed rows :data:`ROUTED_ROWS` at a
    time in position order, as many times as it takes. Each row's number is
    the one the whole block's product gives it."""
    import jax
    import jax.numpy as jnp

    D = x.shape[1]

    def rows(xg):                                          # [B, D + n]
        xb = xg[:, :D]
        B = xb.shape[0]
        C = min(ROUTED_ROWS, B)

        def one(acc, args):
            w_g, w_u, w_d, gb = args                       # gb [B]
            routed = gb != 0
            n = jnp.sum(routed)
            order = jnp.pad(jnp.argsort(~routed, stable=True), (0, -B % C))

            def some(j, out):
                at = jax.lax.dynamic_slice_in_dim(order, j * C, C)
                live = j * C + jnp.arange(C) < n
                y = ffn({"w_g": w_g, "w_u": w_u, "w_d": w_d}, xb[at], hold)
                return out.at[at].add(
                    jnp.where(live, gb[at], 0.0)[:, None] * y)

            return jax.lax.fori_loop(0, (n + C - 1) // C, some, acc), None

        return jax.lax.scan(one, jnp.zeros_like(xb), (
            p["w_g"], p["w_u"], p["w_d"], xg[:, D:].T))[0]

    return _rows(rows, jnp.concatenate([x, gates], axis=-1), nb)


def route(p, x, dm, hold=None):
    """(gates [T, n_routed]: ``scale * s_i / sum_S s`` for the picked, 0
    elsewhere; near [T]: whether the position's ``top_k``-th pick and the
    next lie within :data:`NEAR_TOL` of each other, relative; ranked [T,
    top_k + CUT_WINDOW], order [T, top_k + CUT_WINDOW]: the largest ``s + b``
    and whose they are, best first: the cut lies after the first
    ``top_k``)."""
    import jax
    import jax.numpy as jnp

    T, k = x.shape[0], dm["top_k"]
    s = jax.nn.sigmoid(_mm(x, p["w_r"], hold))               # [T, n]
    ranked, order = jax.lax.top_k(s + p["bias"].astype(jnp.float32),
                                  k + CUT_WINDOW)
    picked = jnp.zeros_like(s).at[
        jnp.arange(T)[:, None], order[:, :k]].set(1.0)
    chosen = s * picked
    gates = dm["scale"] * chosen / chosen.sum(axis=-1, keepdims=True)
    near = (ranked[:, k - 1] - ranked[:, k]) <= NEAR_TOL * jnp.abs(
        ranked[:, k - 1])
    return gates, near, ranked, order


def moe_parts(p, x, dm, held, hold=None, cross=None, nb=None):
    """(what the routed experts ``held = (e0, n)`` add, what the shared
    expert adds, near ties [T]). ``p["w_g"|"w_u"|"w_d"]`` hold those ``n``
    experts' matrices, in order. ``cross = (row, picks [top_k], whether)``:
    that one row routed to ``picks`` instead of its own (where ``whether``),
    and a fourth result, the row's :func:`route` ranking ``(ranked, order)``:
    what :func:`sides` reads. ``nb``: the blocks of rows in use."""
    import jax
    import jax.numpy as jnp

    gates, near, ranked, order = _rows(lambda r: route(p, r, dm, hold), x,
                                       nb)
    e0, n = held
    if cross is not None:
        row, picks, whether = cross
        s = jax.nn.sigmoid(_mm(x[row][None], p["w_r"], hold))[0]
        chosen = jnp.zeros_like(s).at[picks].set(s[picks])
        gates = jnp.where(whether, gates.at[row].set(
            dm["scale"] * chosen / chosen.sum()), gates)

    routed = gated(p, x, gates[:, e0:e0 + n], hold, nb)
    out = routed, ffn(p["shared"], x, hold, nb), near
    return out if cross is None else out + ((ranked[row], order[row]),)


def sides(ranked, order, k: int, held) -> list:
    """The OTHER sides of one position's cut: pick sets [top_k] that a
    computation whose scores arrive within :data:`CUT_TOL` (relative) of the
    reference's may have taken instead of ``order[:k]``, as far as they
    differ in an expert HELD here (``held = (e0, n)``; a swap among absent
    experts moves nothing but a gate's last digits). ``ranked`` / ``order``:
    :func:`route`'s for the position. A pick within the tolerance of the
    first expert left out may drop out, an expert left out within it of the
    last pick may come in; every way of filling the open places counts once
    per set of held experts it keeps."""
    import itertools

    ranked, order = np.asarray(ranked, np.float64), np.asarray(order)
    tol = CUT_TOL * abs(ranked[k - 1])
    e0, n = held
    out_able = [r for r in range(max(0, k - CUT_WINDOW), k)
                if ranked[r] - ranked[k] <= tol]
    in_able = [r for r in range(k, k + CUT_WINDOW)
               if ranked[k - 1] - ranked[r] <= tol]
    is_held = {r: e0 <= int(order[r]) < e0 + n for r in out_able + in_able}
    if not out_able or not any(is_held.values()):
        return []
    sure = [r for r in range(k) if r not in out_able]
    own = frozenset(r for r in out_able if is_held[r])
    seen, found = {own}, []
    for filled in itertools.combinations(out_able + in_able, len(out_able)):
        kept = frozenset(r for r in filled if is_held[r])
        if kept not in seen:
            seen.add(kept)
            found.append(order[sure + list(filled)].astype(np.int32))
    return found


def _ffn_of(p, h, dm, held, hold, cross, nb=None):
    """What a layer's FFN adds to ``h`` [T, D], dense or expert by what
    ``p`` holds: (out, near ties [T], the ranking of ``cross``'s row:
    :func:`moe_parts`; zeros from a dense layer)."""
    import jax.numpy as jnp

    u = rms(h, p["norm_ffn_a"], dm["eps"])
    if "moe" not in p:
        width = dm["top_k"] + CUT_WINDOW
        return (h + ffn(p["ffn_a"], u, hold, nb), jnp.zeros(h.shape[0], bool),
                (jnp.zeros(width), jnp.zeros(width, jnp.int32)))
    routed, shared, near, cut = moe_parts(p["moe"], u, dm, held, hold, cross,
                                          nb)
    return h + routed + shared, near, cut


def _mixed(p, x, pos, dm, hold=None, n=None):
    """``x + MLA(RMS(x))`` over ``x`` [T, D] and the mixer's keys
    (:func:`latents`)."""
    nb = blocks_of(n)
    u = rms(x, p["norm_a"], dm["eps"])
    cq, keys, index_q = latents(p["mixer_a"], u, pos, dm, hold, nb)
    return x + attend(p["mixer_a"], cq, index_q, pos, keys, pos, dm, hold,
                      nb, nb)[0], keys


def _row_mixed(p, x_row, keys, n, dm, hold=None):
    """:func:`_mixed` for the LAST position alone, ``x_row`` [D] at position
    ``n - 1``, over the ``keys`` a forward kept of this layer: the row's own
    keys take their place among them (they differ once a layer before this
    one was crossed), every earlier position's are as they were. [1, D]."""
    import jax.numpy as jnp

    at = jnp.reshape(n - 1, (1,))
    k_pos = jnp.arange(keys[0].shape[0], dtype=jnp.int32)
    u = rms(x_row[None], p["norm_a"], dm["eps"])
    cq, own, index_q = latents(p["mixer_a"], u, at, dm, hold)
    keys = tuple(k.at[n - 1].set(r[0]) for k, r in zip(keys, own))
    return x_row[None] + attend(p["mixer_a"], cq, index_q, at, keys, k_pos,
                                dm, hold, None, blocks_of(n))[0]


@functools.lru_cache(maxsize=None)
def _jitted(dm_items, hold):
    """The jitted parts: the mixer and the FFN of a layer apart, so that the
    mixer (the same in a dense and in an expert layer) compiles once."""
    import jax
    import jax.numpy as jnp

    dm = dict(dm_items)
    held = dm["held"]

    def highest(fn):
        def run(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args)
        return jax.jit(run)

    def mixed(p, x, n):
        h, keys = _mixed(p, x, jnp.arange(x.shape[0], dtype=jnp.int32), dm,
                         hold, n)
        return h, keys, x[n - 1]

    def fed(p, h, n, picks, whether):
        out, near, cut = _ffn_of(p, h, dm, held, hold, (n - 1, picks,
                                                        whether), blocks_of(n))
        return out, jnp.sum(near & (jnp.arange(h.shape[0]) < n)), cut

    def one_layer(p, x, n, picks, whether):
        h, keys, row = parts["mixed"](_mixer_of(p), x, n)
        return parts["fed"](_ffn_part_of(p), h, n, picks, whether) + (keys,
                                                                     row)

    def one_row(p, x_row, keys, n, picks, whether):
        h = parts["row_mixed"](_mixer_of(p), x_row, keys, n)
        out, _, cut = parts["row_fed"](_ffn_part_of(p), h, picks, whether)
        return out[0], cut

    parts = {
        "mixed": highest(mixed),
        "fed": highest(fed),
        "row_mixed": highest(lambda p, x_row, keys, n: _row_mixed(
            p, x_row, keys, n, dm, hold)),
        "row_fed": highest(lambda p, h, picks, whether: _ffn_of(
            p, h, dm, held, hold, (0, picks, whether))),
    }
    return (jax.jit(lambda table, rows: table[rows].astype(jnp.float32)),
            one_layer, one_row,
            highest(lambda final_norm, table, h: _mm(
                rms(h, final_norm, dm["eps"])[None], table.T, hold)[0]))


def _mixer_of(p):
    return {"norm_a": p["norm_a"], "mixer_a": p["mixer_a"]}


def _ffn_part_of(p):
    return {k: v for k, v in p.items() if k not in ("norm_a", "mixer_a")}


def shapes(reach: int) -> tuple:
    """The padded lengths of a cell whose histories reach ``reach``
    positions, shortest first: ONE, whole fours of blocks (a block where one
    holds it)."""
    step = BLOCK if reach <= BLOCK else 4 * BLOCK
    return (-(-reach // step) * step,)


def padded_length(n: int, reach: int | None = None) -> int:
    """The first of :func:`shapes` that holds ``n`` positions (``reach``:
    None, the history's own length)."""
    return next(s for s in shapes(max(n, reach or n)) if s >= n)


def forward(weights, ids, dm, hold=None):
    """Logits [V] after the history ``ids`` (rows of the item table, oldest
    first), and how many of its (position, expert layer) pairs had a near tie
    at the router's cut: each is a place where a rounding can send a token to
    another expert than the reference's."""
    return _forward(weights, ids, dm, hold)[:2]


def _forward(weights, ids, dm, hold=None, crossed=None, reach=None,
             kept=None):
    """:func:`forward`, and the router's ranking at the LAST position in
    every expert layer ``{layer: (ranked, order)}``. ``crossed``: ``{layer:
    picks}``, the last position routed to ``picks`` there (:func:`sides`).
    ``reach``: :func:`padded_length`'s. ``kept``: a list that takes, layer by
    layer, ``(the last position's input row, the layer's keys)``: what
    :func:`crossed_row` needs."""
    import jax.numpy as jnp

    embed, one_layer, _, head = _jitted(tuple(sorted(dm.items())), hold)
    crossed = crossed or {}
    n = len(ids)
    rows = np.zeros(padded_length(n, reach), np.int32)
    rows[:n] = np.asarray(ids, np.int32)
    x = embed(weights["embed"], jnp.asarray(rows))
    own = np.zeros(dm["top_k"], np.int32)        # a layer not crossed
    near_ties, cuts = 0, {}
    for i, p in enumerate(weights["layers"]):
        if ("moe" in p) != (i >= dm["first_dense"]):
            raise ValueError(f"layer {i} is not of the kind the "
                             "configuration gives it")
        x, near, cut, keys, row = one_layer(
            p, x, jnp.int32(n), jnp.asarray(crossed.get(i, own)),
            jnp.bool_(i in crossed))
        near_ties += int(near)
        if kept is not None:
            kept.append((row, keys))
        if "moe" in p:
            cuts[i] = tuple(np.asarray(c) for c in cut)
    logits = head(weights["final_norm"], weights["head"],
                  x[jnp.int32(n - 1)])
    return np.asarray(logits, np.float32), near_ties, cuts


def crossed_row(weights, kept, n: int, dm, crossed, hold=None):
    """The logits of :func:`_forward` ``(..., crossed)`` from what a forward
    WITHOUT ``crossed`` kept: from the first crossed layer on, the last
    position's row alone (every other position is as it was: attention is
    causal)."""
    import jax.numpy as jnp

    _, _, one_row, head = _jitted(tuple(sorted(dm.items())), hold)
    own = np.zeros(dm["top_k"], np.int32)
    first = min(crossed)
    x_row = kept[first][0]
    for i in range(first, len(weights["layers"])):
        x_row, _ = one_row(weights["layers"][i], x_row, kept[i][1],
                           jnp.int32(n), jnp.asarray(crossed.get(i, own)),
                           jnp.bool_(i in crossed))
    return np.asarray(head(weights["final_norm"], weights["head"], x_row),
                      np.float32)


def measure(logits: np.ndarray, answer, k: int):
    """``(score_err, rank_gap)`` of one served ``answer`` [(item_row,
    served_score), ...] against the reference's ``logits`` [V], both over
    the reference's score range (max - min over the catalogue); None where
    the answer is not ``k`` distinct rows of the catalogue:

    * ``score_err``: the widest |served score - reference logit| over the
      served items;
    * ``rank_gap``: the widest gap by which a served item's reference logit
      lies below the reference's k-th best.
    """
    items = [i for i, _ in answer]
    if (len(items) != k or len(set(items)) != k or min(items) < 0
            or max(items) >= logits.shape[0]):
        return None
    span = max(float(logits.max() - logits.min()), 1e-30)
    served = np.array([s for _, s in answer], np.float32)
    ref = logits[np.array(items)]
    kth = np.sort(logits)[-k]
    return (float(np.abs(served - ref).max()) / span,
            float(max(0.0, kth - ref.min())) / span)


def compare(weights, sample, k: int, dm: dict, reach=None,
            stop=None) -> dict:
    """``sample``: [(ids, [(item_row, served_score), ...]), ...]: the widest
    :func:`measure` of each answer against the reference's full forward over
    its ``ids``, in the sample's order. ``reach``: the longest history the
    cell's traffic can send (:func:`shapes`; None: the sample's longest).
    ``stop(compared so far)``: asked before each answer, true where no
    further one is to be started (the driver's budget).

    **Both sides of an open cut.** Where the router's ``top_k``-th pick
    and the next lie within :data:`CUT_TOL` of each other at an answer's
    LAST position and one of the experts at the cut is held here,
    roundings upstream decide which side the token falls on: the
    reference's own side is no more the model's than the other, and the
    two differ by a whole gated expert's output at the one position the
    answer is read from (every other position is as it was: attention is
    causal). Such an answer is measured against the forward on the other
    sides of that cut too (:func:`sides`; every combination over the
    layers, at most :data:`CROSSINGS` of them, each the one row of
    :func:`crossed_row`), and the side it lies nearest is the one
    compared. A tie at any EARLIER position reaches the answer only
    through attention's weights and stays in the numbers, as do the
    index's own near ties at a row's 2,048th position."""
    import itertools

    score_err = rank_gap = 0.0
    malformed = compared = longest = near_ties = positions = 0
    open_answers = crossed_answers = 0
    widest = None
    n_long = [0] * len(LONG)
    expert_layers = sum(1 for p in weights["layers"] if "moe" in p)
    reach = reach or max((len(ids) for ids, _ in sample), default=1)
    for ids, answer in sample:
        if stop is not None and stop(compared + malformed):
            break
        kept = []
        logits, near, cuts = _forward(weights, ids, dm, reach=reach,
                                      kept=kept)
        near_ties += near
        positions += len(ids) * expert_layers
        own = got = measure(logits, answer, k)
        if got is None:
            malformed += 1
            continue
        other = {i: sides(*cut, dm["top_k"], dm["held"])
                 for i, cut in cuts.items()}
        other = {i: found for i, found in other.items() if found}
        open_answers += bool(other)
        layers = sorted(other)
        for choice in itertools.islice(itertools.product(
                *([None] + other[i] for i in layers)), 1, CROSSINGS + 1):
            there = measure(crossed_row(weights, kept, len(ids), dm, {
                i: picks for i, picks in zip(layers, choice)
                if picks is not None}), answer, k)
            if there is not None and there < got:
                got = there
        crossed_answers += got is not own
        if widest is None or got > widest[0]:
            margins = {i: float((r[dm["top_k"] - 1] - r[dm["top_k"]])
                                / abs(r[dm["top_k"] - 1]))
                       for i, (r, _) in cuts.items()}
            widest = (got, own, len(ids), margins, layers)
        score_err, rank_gap = max(score_err, got[0]), max(rank_gap, got[1])
        compared += 1
        longest = max(longest, len(ids))
        n_long = [n + (len(ids) > at) for n, at in zip(n_long, LONG)]
    # the driver's own note has no room for these
    print(f"# reference: of the {compared} compared histories "
          + ", ".join(f"{n} are longer than {at}"
                      for n, at in zip(n_long, LONG))
          + f"; {open_answers} had an open cut at the last position "
          f"(a held expert within {CUT_TOL:g} of the router's cut), "
          f"{crossed_answers} lay nearer another side of it", flush=True)
    if widest is not None:
        got, own, length, margins, layers = widest
        print(f"# reference: the widest answer: {length} positions, "
              f"score_err {got[0]:.3g}, rank_gap {got[1]:.3g} "
              f"({own[0]:.3g}, {own[1]:.3g} on the reference's own side); "
              "its last position's margin at the router's cut, by layer: "
              + ", ".join(f"{i}: {m:.2e}" for i, m in margins.items())
              + f"; open in layers {layers}", flush=True)
    return {"score_err": score_err, "rank_gap": rank_gap,
            "malformed": malformed, "compared": compared,
            "longest_history": longest, "long_histories": n_long,
            "near_ties": near_ties, "positions_x_layers": positions,
            "open_cuts": open_answers, "crossed": crossed_answers}


def top_k_answer(logits: np.ndarray, k: int):
    order = np.argsort(-logits)[:k]
    return [(int(i), float(logits[i])) for i in order]


def control(bench, variants=("bfloat16", "float8_e4m3fn")) -> dict:
    """The control's readings at the cell's own size: the reference computed
    with weights and matrix-product inputs rounded to a lower precision (the
    index's products too, so its sets move with it), put in the program's
    place for a seeded handful of the cell's own histories (its shortest,
    which do select: a precision needs no long history to fail)."""
    builder = bench.load_module("models", bench.config["engine"])
    weights = builder.make_weights(bench)
    dm = dims_of(bench.config)
    k = int(bench.traffic["num"])
    histories = builder.control_histories(bench)
    out = {}
    for name in variants:
        answers = [(ids, top_k_answer(forward(weights, ids, dm,
                                              FORMATS[name])[0], k))
                   for ids in histories]
        out[name] = compare(weights, answers, k, dm)
    return out
