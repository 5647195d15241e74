"""Plain reference for A.X-K1's forward pass over an item history.

``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``: no cache,
no chunked prefill, no kernel, no batching, nothing of the program. The
equations (source: https://huggingface.co/skt/A.X-K1/blob/main/config.json,
``model_type: axk1``; the layer is the DeepSeek-V3 family's):

* ``RMS(x) = x / sqrt(mean(x^2) + eps) * w``;
* **model**: ``x0 = E[ids]``; layer ``l``: ``h = x + MLA(RMS(x))``, ``out = h +
  FFN_l(RMS(h))``; final ``RMS``; logits ``= h_last W_head^T`` (untied);
* **MLA**: ``cQ = RMS_q(x W_dq)`` (no LoRA scale); per head ``[qN ; qR] = cQ
  W_uq`` with RoPE on ``qR``; ``[cKV_raw ; kR_raw] = x W_dkv``, ``cKV =
  RMS_kv(cKV_raw)``, ``kR = RoPE(kR_raw)`` (one head shared by all); per head
  ``[kN ; v] = cKV W_ukv``; causal softmax of ``m^2 (qN.kN + qR.kR) /
  sqrt(d_nope + d_rope)``, ``m = 0.1 * mscale_all_dim * ln(factor) + 1``;
  heads concatenated, ``W_o``;
* **YaRN RoPE** on the ``d_rope`` dims, pairs ``(2i, 2i+1)``: ``f_i =
  theta^(-2i/d_rope)``; ``pair(t) = d_rope ln(L0 / (2 pi t)) / (2 ln theta)``
  (the pair that makes ``t`` turns over the original length ``L0``); ``lo =
  floor(pair(beta_fast))``, ``hi = ceil(pair(beta_slow))``; ``r_i = clip((i -
  lo) / (hi - lo), 0, 1)``; the angle of pair ``i`` at position ``t`` is ``t
  (f_i (1 - r_i) + f_i r_i / factor)``; cos and sin times ``(0.1 mscale
  ln(factor) + 1) / m``;
* **FFN_l, l < first_k_dense_replace**: ``(silu(x W_g) * (x W_u)) W_d``;
  **later**: ``Shared(u) + sum_{i in S} g_i Expert_i(u)``, the shared expert
  and every routed one the same SwiGLU, narrower;
* **router**: ``s = sigmoid(u W_r)`` over the routed experts; group ``j`` is
  experts ``j n/G .. (j+1) n/G - 1``; a group's score is the sum of its two
  largest ``s``; the ``topk_group`` best groups are kept; ``S`` = the
  ``top_k`` largest ``s`` inside them; ``g_i = scale * s_i / sum_{j in S}
  s_j``. **The share**: ``held = (e0, n)`` says which routed experts exist
  here; what the others would add is left out (``moe_parts`` returns the
  routed part and the shared expert's apart, so that a test can add shares
  up and count the shared expert once).

Departures from the published description, each listed under ``assumed`` in
the configuration's file: ``topk_method`` reads ``"none"`` there, taken as
DeepSeek-V3's group-limited selection WITHOUT its correction bias (``s``
itself chooses); RoPE pairs neighbouring dimensions; weights are seeded.

So that 24,576 positions fit beside 8 GB of weights, attention runs a group of
heads and a block of :data:`BLOCK` QUERIES at a time against the blocks of
keys up to its own (causal: a later block holds nothing it may attend), the
softmax carried from block to block as its running maximum and sum, and
position-wise parts a block of rows at a time, a routed expert over the rows
routed to it alone (:func:`gated`): each number is the one the unblocked form
gives, to float32 rounding. A history is padded to ONE length a cell
(:func:`shapes`: what the traffic's longest history can reach; causal:
positions after the last real one change nothing before them) and its REAL
length goes in as an argument that bounds every loop over blocks: the work
follows the real length, and each layer kind compiles once. Weights arrive as
the benchmark's seeded arrays (bfloat16-valued) and are widened inside each
jitted part.

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
#: a history past this is reported (below it YaRN's angles barely differ
#: from plain RoPE's)
LONG = 4096


def dims_of(cfg: dict) -> dict:
    """The sizes the equations need, from the configuration's own keys."""
    yarn = cfg["rope_scaling"]
    if yarn["type"] != "yarn":
        raise ValueError("the reference writes out YaRN's frequencies only")
    return {
        "D": int(cfg["hidden_size"]), "H": int(cfg["num_attention_heads"]),
        "dn": int(cfg["qk_nope_head_dim"]), "dr": int(cfg["qk_rope_head_dim"]),
        "dv": int(cfg["v_head_dim"]), "rq": int(cfg["q_lora_rank"]),
        "rkv": int(cfg["kv_lora_rank"]), "theta": float(cfg["rope_theta"]),
        "eps": float(cfg["rms_norm_eps"]),
        "factor": float(yarn["factor"]),
        "L0": int(yarn["original_max_position_embeddings"]),
        "beta_fast": float(yarn["beta_fast"]),
        "beta_slow": float(yarn["beta_slow"]),
        "mscale": float(yarn["mscale"]),
        "mscale_all_dim": float(yarn["mscale_all_dim"]),
        "n_routed": int(cfg["n_routed_experts_published"]),
        "n_group": int(cfg["n_group"]), "topk_group": int(cfg["topk_group"]),
        "top_k": int(cfg["num_experts_per_tok"]),
        "scale": float(cfg["routed_scaling_factor"]),
        "held": tuple(int(v) for v in cfg["experts_held"]),
        "first_dense": int(cfg["first_k_dense_replace"]),
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


def yarn(dm: dict):
    """``(angle a position adds to each pair [dr / 2], what cos and sin are
    multiplied by, what a query-key product is multiplied by)``."""
    d, theta, factor = dm["dr"], dm["theta"], dm["factor"]
    i = np.arange(d // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / d)

    def pair(turns):
        return d * math.log(dm["L0"] / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    lo = max(math.floor(pair(dm["beta_fast"])), 0)
    hi = min(math.ceil(pair(dm["beta_slow"])), d - 1)
    r = np.clip((i - lo) / max(hi - lo, 1e-3), 0.0, 1.0)

    def m(s):
        return 0.1 * s * math.log(factor) + 1.0 if factor > 1 else 1.0

    freqs = f * (1 - r) + f / factor * r if factor > 1 else f
    m_all = m(dm["mscale_all_dim"])
    return (freqs.astype(np.float32), m(dm["mscale"]) / m_all,
            m_all * m_all / math.sqrt(dm["dn"] + dm["dr"]))


def rope(x, pos, freqs, amplitude):
    """``x`` [T, ..., d]: dimensions (2i, 2i+1) turned by ``pos * freqs[i]``."""
    import jax.numpy as jnp

    d = x.shape[-1]
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(freqs)
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,))
    cos, sin = amplitude * jnp.cos(ang), amplitude * jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def mla(p, x, pos, dm, hold=None, n=None):
    """Full causal latent attention over one sequence ``x`` [T, D]. ``n``:
    the real length (a traced scalar; None: all of ``T``): the blocks past it
    are not computed. A block of queries walks the blocks of keys up to its
    own, the softmax carried from block to block as its running maximum and
    sum."""
    import jax
    import jax.numpy as jnp

    T = x.shape[0]
    H, dn, dr, dv, rkv = dm["H"], dm["dn"], dm["dr"], dm["dv"], dm["rkv"]
    freqs, amplitude, scale = yarn(dm)
    nb = blocks_of(n)
    cq = _rows(lambda r: rms(_mm(r, p["w_dq"], hold), p["q_norm"],
                             dm["eps"]), x, nb)
    down = _rows(lambda r: _mm(r, p["w_dkv"], hold), x, nb)
    ckv = rms(down[:, :rkv], p["kv_norm"], dm["eps"])
    kr = rope(down[:, rkv:], pos, freqs, amplitude)          # [T, dr]
    Tq = BLOCK if _blocked(T) else T
    blocks = T // Tq
    nb = blocks if nb is None else jnp.minimum(nb, blocks)
    group = max(1, min(H, SCORE_BYTES // (4 * Tq * Tq),
                       HEAD_BYTES // (8 * T * (dn + max(dr, dv)))))
    while H % group:
        group -= 1
    w_uq = p["w_uq"].reshape(dm["rq"], H // group, group, dn + dr)
    w_ukv = p["w_ukv"].reshape(rkv, H // group, group, dn + dv)
    w_o = p["w_o"].reshape(H // group, group * dv, dm["D"])
    pos_b, kr_b = pos.reshape(blocks, Tq), kr.reshape(blocks, Tq, dr)

    def block(a, i):
        return jax.lax.dynamic_index_in_dim(a, i, keepdims=False)

    def heads(out, args):                                   # [blocks, Tq, D]
        uq, ukv, wo = args                                  # one group's
        q = _rows(lambda r: _mm(r, uq.reshape(dm["rq"], -1), hold), cq,
                  nb).reshape(blocks, Tq, group, dn + dr)
        kv = _rows(lambda r: _mm(r, ukv.reshape(rkv, -1), hold), ckv,
                   nb).reshape(blocks, Tq, group, dn + dv)

        def queries(b, out):
            # a block's own slices: nothing of the whole length is copied
            q_q, pos_q = block(q, b), block(pos_b, b)
            qn_q, qr_q = q_q[..., :dn], rope(q_q[..., dn:], pos_q, freqs,
                                             amplitude)

            def keys_block(c, carry):
                m, l, acc = carry               # [g, Tq] twice, [g, Tq, dv]
                kv_c = block(kv, c)
                s = (jnp.einsum("tgd,ugd->gtu", _hold(qn_q, hold),
                                _hold(kv_c[..., :dn], hold))
                     + jnp.einsum("tgd,ud->gtu", _hold(qr_q, hold),
                                  _hold(block(kr_b, c), hold)))
                s = jnp.where(
                    (pos_q[:, None] >= block(pos_b, c)[None, :])[None],
                    s * scale, -jnp.inf)
                top = jnp.maximum(m, s.max(axis=-1))
                at = jnp.where(jnp.isfinite(top), top, 0.0)
                e = jnp.exp(s - at[..., None])
                shrink = jnp.exp(m - at)         # 0 while nothing was seen
                return (top, shrink * l + e.sum(axis=-1),
                        shrink[..., None] * acc + jnp.einsum(
                            "gtu,ugd->gtd", _hold(e, hold),
                            _hold(kv_c[..., dn:], hold)))

            _, l, acc = jax.lax.fori_loop(0, b + 1, keys_block, (
                jnp.full((group, Tq), -jnp.inf), jnp.zeros((group, Tq)),
                jnp.zeros((group, Tq, dv))))
            o_q = (acc / l[..., None]).transpose(1, 0, 2).reshape(
                Tq, group * dv)
            return jax.lax.dynamic_update_index_in_dim(
                out, block(out, b) + _mm(o_q, wo, hold), b, 0)

        return jax.lax.fori_loop(0, nb, queries, out), None

    return jax.lax.scan(heads, jnp.zeros((blocks, Tq, dm["D"])), (
        w_uq.transpose(1, 0, 2, 3), w_ukv.transpose(1, 0, 2, 3),
        w_o))[0].reshape(T, dm["D"])


def ffn(p, x, hold=None, nb=None):
    import jax

    return _rows(lambda r: _mm(
        jax.nn.silu(_mm(r, p["w_g"], hold)) * _mm(r, p["w_u"], hold),
        p["w_d"], hold), x, nb)


def gated(p, x, gates, hold=None, nb=None):
    """``sum_e gates[:, e, None] * ffn(p_e, x)`` over the experts whose
    matrices ``p`` stacks, each computed for the rows whose gate is not 0
    alone (an expert held here sees one token in twenty-four): a block of rows
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
    elsewhere; near [T]: whether the position's last kept and first dropped
    group, or its ``top_k``-th and next pick, lie within a thousandth of each
    other, relative)."""
    import jax
    import jax.numpy as jnp

    T, n, G, k = x.shape[0], dm["n_routed"], dm["n_group"], dm["top_k"]
    s = jax.nn.sigmoid(_mm(x, p["w_r"], hold))               # [T, n]
    group_score = jax.lax.top_k(s.reshape(T, G, n // G), 2)[0].sum(axis=-1)
    order = jnp.argsort(-group_score, axis=-1, stable=True)
    kept = jnp.zeros((T, G), bool).at[
        jnp.arange(T)[:, None], order[:, :dm["topk_group"]]].set(True)
    inside = jnp.where(jnp.repeat(kept, n // G, axis=1), s, -jnp.inf)
    top, idx = jax.lax.top_k(inside, k + 1)
    picked = jnp.zeros_like(s).at[jnp.arange(T)[:, None], idx[:, :k]].set(1.0)
    chosen = s * picked
    gates = dm["scale"] * chosen / chosen.sum(axis=-1, keepdims=True)
    near = (top[:, k - 1] - top[:, k]) <= 1e-3 * jnp.abs(top[:, k - 1])
    if dm["topk_group"] < G:
        ranked = jnp.take_along_axis(group_score, order, axis=-1)
        last, nxt = ranked[:, dm["topk_group"] - 1], ranked[:, dm["topk_group"]]
        near = near | ((last - nxt) <= 1e-3 * jnp.abs(last))
    return gates, near


def moe_parts(p, x, dm, held, hold=None, nb=None):
    """(what the routed experts ``held = (e0, n)`` add, what the shared
    expert adds, near ties). ``p["w_g"|"w_u"|"w_d"]`` hold those ``n``
    experts' matrices, in order. ``nb``: the blocks of rows in use."""
    import jax
    import jax.numpy as jnp

    gates, near = _rows(lambda r: route(p, r, dm, hold), x, nb)
    e0, n = held

    routed = gated(p, x, gates[:, e0:e0 + n], hold, nb)
    return routed, ffn(p["shared"], x, hold, nb), near


def _fed(p, h, dm, held, hold=None, nb=None):
    """What a layer's FFN adds to ``h`` [T, D], dense or expert by what
    ``p`` holds: (out, near ties [T])."""
    import jax.numpy as jnp

    u = rms(h, p["norm_ffn_a"], dm["eps"])
    if "moe" not in p:
        return h + ffn(p["ffn_a"], u, hold, nb), jnp.zeros(h.shape[0], bool)
    routed, shared, near = moe_parts(p["moe"], u, dm, held, hold, nb)
    return h + routed + shared, near


@functools.lru_cache(maxsize=None)
def _jitted(dm_items, hold):
    """The jitted parts: the mixer and the FFN of a layer apart, so that the
    mixer (the same in a dense and in an expert layer) compiles once."""
    import jax
    import jax.numpy as jnp

    dm = dict(dm_items)

    def highest(fn):
        def run(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args)
        return jax.jit(run)

    mixed = highest(lambda norm, p, x, n: x + mla(
        p, rms(x, norm, dm["eps"]), jnp.arange(x.shape[0], dtype=jnp.int32),
        dm, hold, n))

    def fed(p, h, n):
        out, near = _fed(p, h, dm, dm["held"], hold, blocks_of(n))
        return out, jnp.sum(near & (jnp.arange(h.shape[0]) < n))

    fed = highest(fed)

    def one_layer(p, x, n):
        return fed({k: v for k, v in p.items()
                    if k not in ("norm_a", "mixer_a")},
                   mixed(p["norm_a"], p["mixer_a"], x, n), n)

    return (jax.jit(lambda table, rows: table[rows].astype(jnp.float32)),
            one_layer,
            highest(lambda final_norm, table, h: _mm(
                rms(h, final_norm, dm["eps"])[None], table.T, hold)[0]))


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


def forward(weights, ids, dm, hold=None, reach=None):
    """Logits [V] after the history ``ids`` (rows of the item table, oldest
    first), and how many of its (position, expert layer) pairs had a near tie
    at one of the router's two cuts: each is a place where a rounding can
    send a token to another expert than the reference's. ``reach``:
    :func:`padded_length`'s."""
    import jax.numpy as jnp

    embed, one_layer, head = _jitted(tuple(sorted(dm.items())), hold)
    n = len(ids)
    rows = np.zeros(padded_length(n, reach), np.int32)
    rows[:n] = np.asarray(ids, np.int32)
    x = embed(weights["embed"], jnp.asarray(rows))
    near_ties = 0
    for i, p in enumerate(weights["layers"]):
        if ("moe" in p) != (i >= dm["first_dense"]):
            raise ValueError(f"layer {i} is not of the kind the "
                             "configuration gives it")
        x, near = one_layer(p, x, jnp.int32(n))
        near_ties += int(near)
    logits = head(weights["final_norm"], weights["head"],
                  x[jnp.int32(n - 1)])
    return np.asarray(logits, np.float32), near_ties


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
    further one is to be started (the driver's budget)."""
    score_err = rank_gap = 0.0
    malformed = compared = longest = near_ties = positions = n_long = 0
    expert_layers = sum(1 for p in weights["layers"] if "moe" in p)
    reach = reach or max((len(ids) for ids, _ in sample), default=1)
    for ids, answer in sample:
        if stop is not None and stop(compared + malformed):
            break
        logits, near = forward(weights, ids, dm, reach=reach)
        near_ties += near
        positions += len(ids) * expert_layers
        got = measure(logits, answer, k)
        if got is None:
            malformed += 1
            continue
        score_err, rank_gap = max(score_err, got[0]), max(rank_gap, got[1])
        compared += 1
        longest = max(longest, len(ids))
        n_long += len(ids) > LONG
    # the driver's own note has no room for it
    print(f"# reference: {n_long} of the {compared} compared histories are "
          f"longer than {LONG}", flush=True)
    return {"score_err": score_err, "rank_gap": rank_gap,
            "malformed": malformed, "compared": compared,
            "longest_history": longest, "long_histories": n_long,
            "near_ties": near_ties, "positions_x_layers": positions}


def top_k_answer(logits: np.ndarray, k: int):
    order = np.argsort(-logits)[:k]
    return [(int(i), float(logits[i])) for i in order]


def control(bench, variants=("bfloat16", "float8_e4m3fn")) -> dict:
    """The control's readings at the cell's own size: the reference computed
    with weights and matrix-product inputs rounded to a lower precision, put
    in the program's place for a seeded handful of the cell's own histories
    (its shortest: a precision needs no long history to fail)."""
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
