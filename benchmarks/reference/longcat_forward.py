"""Plain reference for LongCat-Flash-Chat's forward pass over an item history.

``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``: no cache,
no chunked prefill, no kernel, no batching, nothing of the program. The
equations are those of the configuration's file (``equations``), written out
once more here:

* ``RMS(x) = x / sqrt(mean(x^2) + eps) * w``;
* **MLA**: ``cQ = RMS_q(x W_dq) * sqrt(D / q_rank)``; per head
  ``[qN ; qR] = cQ W_uq`` with RoPE on ``qR``; ``[cKV_raw ; kR_raw] = x W_dkv``,
  ``cKV = RMS_kv(cKV_raw) * sqrt(D / kv_rank)``, ``kR = RoPE(kR_raw)`` (one
  head shared by all); per head ``[kN ; v] = cKV W_ukv``; causal softmax of
  ``(qN.kN + qR.kR) / sqrt(d_nope + d_rope)``; heads concatenated, ``W_o``;
* **FFN**: ``(silu(x W_g) * (x W_u)) W_d``; an expert is the same, narrower;
* **MoE**: ``p = softmax(x W_r)`` over routed + zero-compute experts,
  ``S = top_k(p + b)``, ``g_i = scale * p_i`` for ``i`` in ``S``; a routed
  expert adds ``g_i Expert_i(x)``, a zero-compute (identity) one ``g_i x``.
  **The share**: ``held = (e0, n)`` says which routed experts exist here; what
  the others would add is left out (``moe_parts`` returns the routed part and
  the zero-compute part apart, so that a test can add shares up);
* **double-layer** (ScMoE): ``h1 = x + MLA_a(RMS(x))``; ``u = RMS(h1)``;
  ``m = MoE(u)``; ``h2 = h1 + FFN_a(u)``; ``h3 = h2 + MLA_b(RMS(h2))``;
  ``out = h3 + FFN_b(RMS(h3)) + m``;
* **model**: item embedding -> double-layers -> RMS -> ``h_last W_head^T``.

RoPE pairs neighbouring dimensions ``(2i, 2i+1)`` (``assumed`` in the
configuration's file). Weights arrive as the benchmark's seeded arrays
(bfloat16-valued) and are widened one matrix at a time inside each jitted
part, so that the reference fits beside the weights themselves.

``hold``: the control. ``(exponent_bits, mantissa_bits)`` rounds every weight
and every matrix product's input to that format (``lax.reduce_precision``);
``None`` is the reference proper.
"""

from __future__ import annotations

import functools
import math

import numpy as np

FORMATS = {"bfloat16": (8, 7), "float8_e4m3fn": (4, 3)}
#: a history is padded to one of :func:`shapes`' whole multiples of this
#: before the jitted parts see it (causal: positions after the last real one
#: change nothing before them)
PAD_TO = 1024
#: [heads, T, T] float32 scores held at once
SCORE_BYTES = 1 << 30


def dims_of(cfg: dict) -> dict:
    """The sizes the equations need, from the configuration's own keys."""
    return {
        "D": int(cfg["hidden_size"]), "H": int(cfg["num_attention_heads"]),
        "dn": int(cfg["qk_nope_head_dim"]), "dr": int(cfg["qk_rope_head_dim"]),
        "dv": int(cfg["v_head_dim"]), "rq": int(cfg["q_lora_rank"]),
        "rkv": int(cfg["kv_lora_rank"]), "theta": float(cfg["rope_theta"]),
        "eps": float(cfg["rms_norm_eps"]),
        "n_routed": int(cfg["n_routed_experts_published"]),
        "n_zero": int(cfg["zero_expert_num"]), "top_k": int(cfg["moe_topk"]),
        "scale": float(cfg["routed_scaling_factor"]),
        "held": tuple(int(v) for v in cfg["experts_held"]),
        "scale_q": bool(cfg["mla_scale_q_lora"]),
        "scale_kv": bool(cfg["mla_scale_kv_lora"]),
    }


def _hold(x, hold):
    import jax

    return x if hold is None else jax.lax.reduce_precision(x, *hold)


def _mm(x, w, hold):
    import jax.numpy as jnp

    return jnp.dot(_hold(x, hold), _hold(w.astype(jnp.float32), hold))


def rms(x, w, eps):
    import jax.numpy as jnp

    return (x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * w.astype(jnp.float32))


def rope(x, pos, theta):
    """``x`` [T, ..., d]: dimensions (2i, 2i+1) turned by ``pos * theta^(-2i/d)``."""
    import jax.numpy as jnp

    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv            # [T, d/2]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def mla(p, x, pos, dm, hold=None):
    """Full causal latent attention over one sequence ``x`` [T, D]."""
    import jax
    import jax.numpy as jnp

    T = x.shape[0]
    H, dn, dr, dv, rkv = dm["H"], dm["dn"], dm["dr"], dm["dv"], dm["rkv"]
    cq = rms(_mm(x, p["w_dq"], hold), p["q_norm"], dm["eps"])
    if dm["scale_q"]:
        cq = cq * math.sqrt(dm["D"] / dm["rq"])
    q = _mm(cq, p["w_uq"], hold).reshape(T, H, dn + dr)
    qn, qr = q[..., :dn], rope(q[..., dn:], pos, dm["theta"])
    down = _mm(x, p["w_dkv"], hold)
    ckv = rms(down[:, :rkv], p["kv_norm"], dm["eps"])
    if dm["scale_kv"]:
        ckv = ckv * math.sqrt(dm["D"] / rkv)
    kr = rope(down[:, rkv:], pos, dm["theta"])              # [T, dr]
    kv = _mm(ckv, p["w_ukv"], hold).reshape(T, H, dn + dv)
    kn, v = kv[..., :dn], kv[..., dn:]
    causal = pos[:, None] >= pos[None, :]
    group = max(1, min(H, SCORE_BYTES // (4 * T * T)))
    while H % group:
        group -= 1

    def heads(args):
        qn_g, qr_g, kn_g, v_g = args                        # [g, T, d]
        s = (jnp.einsum("htd,hud->htu", _hold(qn_g, hold), _hold(kn_g, hold))
             + jnp.einsum("htd,ud->htu", _hold(qr_g, hold), _hold(kr, hold)))
        s = jnp.where(causal[None], s / math.sqrt(dn + dr), -jnp.inf)
        prob = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("htu,hud->htd", _hold(prob, hold), _hold(v_g, hold))

    def grouped(a):                                         # [T,H,d]->[G,g,T,d]
        return a.transpose(1, 0, 2).reshape(H // group, group, T, a.shape[-1])

    o = jax.lax.map(heads, (grouped(qn), grouped(qr), grouped(kn),
                            grouped(v)))                    # [G, g, T, dv]
    o = o.reshape(H, T, dv).transpose(1, 0, 2).reshape(T, H * dv)
    return _mm(o, p["w_o"], hold)


def ffn(p, x, hold=None):
    import jax

    return _mm(jax.nn.silu(_mm(x, p["w_g"], hold)) * _mm(x, p["w_u"], hold),
               p["w_d"], hold)


def route(p, x, dm, hold=None):
    """(gates [T, routed + zero]: ``scale * p_i`` for the picked, 0 elsewhere;
    near [T]: whether the position's ``top_k``-th and next pick lie within a
    thousandth of each other, relative)."""
    import jax
    import jax.numpy as jnp

    prob = jax.nn.softmax(_mm(x, p["w_r"], hold), axis=-1)
    choice = prob + p["bias"].astype(jnp.float32)
    k = dm["top_k"]
    top, idx = jax.lax.top_k(choice, k + 1)
    picked = jnp.zeros_like(prob).at[
        jnp.arange(x.shape[0])[:, None], idx[:, :k]].set(1.0)
    near = (top[:, k - 1] - top[:, k]) <= 1e-3 * jnp.abs(top[:, k - 1])
    return dm["scale"] * prob * picked, near


def moe_parts(p, x, dm, held, hold=None):
    """(what the routed experts ``held = (e0, n)`` add, what the zero-compute
    experts add, near ties). ``p["w_g"|"w_u"|"w_d"]`` hold those ``n``
    experts' matrices, in order."""
    import jax
    import jax.numpy as jnp

    gates, near = route(p, x, dm, hold)
    e0, n = held

    def one(acc, args):
        w_g, w_u, w_d, g = args
        y = ffn({"w_g": w_g, "w_u": w_u, "w_d": w_d}, x, hold)
        return acc + g[:, None] * y, None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (p["w_g"], p["w_u"], p["w_d"], gates[:, e0:e0 + n].T))
    zero = gates[:, dm["n_routed"]:].sum(axis=-1, keepdims=True) * x
    return routed, zero, near


def double_layer(p, x, pos, dm, held, hold=None):
    """One ScMoE double-layer over ``x`` [T, D]; (out, near ties)."""
    eps = dm["eps"]
    h1 = x + mla(p["mixer_a"], rms(x, p["norm_a"], eps), pos, dm, hold)
    u = rms(h1, p["norm_ffn_a"], eps)
    routed, zero, near = moe_parts(p["moe"], u, dm, held, hold)
    h2 = h1 + ffn(p["ffn_a"], u, hold)
    h3 = h2 + mla(p["mixer_b"], rms(h2, p["norm_b"], eps), pos, dm, hold)
    out = h3 + ffn(p["ffn_b"], rms(h3, p["norm_ffn_b"], eps), hold) \
        + routed + zero
    return out, near


@functools.lru_cache(maxsize=None)
def _jitted(dm_items, hold):
    import jax
    import jax.numpy as jnp

    dm = dict(dm_items)

    def embed(table, rows):
        return table[rows].astype(jnp.float32)

    def layer(p, x, n):
        with jax.default_matmul_precision("highest"):
            pos = jnp.arange(x.shape[0], dtype=jnp.int32)
            out, near = double_layer(p, x, pos, dm, dm["held"], hold)
            return out, jnp.sum(near & (pos < n))

    def head(final_norm, table, x, n):
        with jax.default_matmul_precision("highest"):
            return _mm(rms(x[n - 1], final_norm, dm["eps"])[None], table.T,
                       hold)[0]

    return jax.jit(embed), jax.jit(layer), jax.jit(head)


def shapes(reach: int) -> tuple:
    """The padded lengths of a cell whose histories reach ``reach``
    positions, shortest first: a third, two thirds and the whole of it, in
    whole multiples of :data:`PAD_TO` (at most three shapes a layer kind
    where every multiple had its own; the real length goes in as an
    argument, so no length compiles anything of its own)."""
    top = -(-reach // PAD_TO)
    return tuple(sorted({-(-top * i // 3) * PAD_TO for i in (1, 2, 3)}))


def padded_length(n: int, reach: int | None = None) -> int:
    """The first of :func:`shapes` that holds ``n`` positions (``reach``:
    None, the history's own length)."""
    return next(s for s in shapes(max(n, reach or n)) if s >= n)


def forward(weights, ids, dm, hold=None, reach=None):
    """Logits [V] after the history ``ids`` (rows of the item table, oldest
    first), and how many of its (position, layer) pairs had a near tie at
    the router's cut: each is a place where a rounding can send a token to
    another expert than the reference's. ``reach``:
    :func:`padded_length`'s."""
    import jax.numpy as jnp

    embed, layer, head = _jitted(tuple(sorted(dm.items())), hold)
    n = len(ids)
    rows = np.zeros(padded_length(n, reach), np.int32)
    rows[:n] = np.asarray(ids, np.int32)
    x = embed(weights["embed"], jnp.asarray(rows))
    near_ties = 0
    for p in weights["layers"]:
        x, near = layer(p, x, jnp.int32(n))
        near_ties += int(near)
    logits = head(weights["final_norm"], weights["head"], x, jnp.int32(n))
    return np.asarray(logits, np.float32), near_ties


def compare(weights, sample, k: int, dm: dict, reach=None,
            stop=None) -> dict:
    """``sample``: [(ids, [(item_row, served_score), ...]), ...]. For each,
    the reference's full forward over ``ids``:

    * ``score_err``: the widest |served score - reference logit| over the
      served items, relative to the reference's score range (max - min over
      the catalogue);
    * ``rank_gap``: the widest gap by which a served item's reference logit
      lies below the reference's k-th best, relative to the same range.

    In the sample's order. ``reach``: the longest history the cell's traffic
    can send (:func:`shapes`; None: the sample's longest). ``stop(compared
    so far)``: asked before each answer, true where no further one is to be
    started (the driver's budget).
    """
    score_err = rank_gap = 0.0
    malformed = compared = longest = near_ties = positions = 0
    reach = reach or max((len(ids) for ids, _ in sample), default=1)
    for ids, answer in sample:
        if stop is not None and stop(compared + malformed):
            break
        items = [i for i, _ in answer]
        logits, near = forward(weights, ids, dm, reach=reach)
        near_ties += near
        positions += len(ids) * len(weights["layers"])
        if (len(items) != k or len(set(items)) != k or min(items) < 0
                or max(items) >= logits.shape[0]):
            malformed += 1
            continue
        span = max(float(logits.max() - logits.min()), 1e-30)
        served = np.array([s for _, s in answer], np.float32)
        ref = logits[np.array(items)]
        kth = np.sort(logits)[-k]
        score_err = max(score_err, float(np.abs(served - ref).max()) / span)
        rank_gap = max(rank_gap, float(max(0.0, kth - ref.min())) / span)
        compared += 1
        longest = max(longest, len(ids))
    return {"score_err": score_err, "rank_gap": rank_gap,
            "malformed": malformed, "compared": compared,
            "longest_history": longest, "near_ties": near_ties,
            "positions_x_layers": positions}


def top_k_answer(logits: np.ndarray, k: int):
    order = np.argsort(-logits)[:k]
    return [(int(i), float(logits[i])) for i in order]


def control(bench) -> dict:
    """The control's readings at the cell's own size: the reference computed
    with weights and matrix-product inputs rounded to a lower precision, put
    in the program's place for a seeded sample of the cell's own histories
    (short ones: a control needs no long history to fail)."""
    builder = bench.load_module("models", bench.config["engine"])
    weights = builder.make_weights(bench)
    dm = dims_of(bench.config)
    k = int(bench.traffic["num"])
    histories = builder.control_histories(bench)
    out = {}
    for name in ("bfloat16", "float8_e4m3fn"):
        answers = [(ids, top_k_answer(forward(weights, ids, dm,
                                              FORMATS[name])[0], k))
                   for ids in histories]
        out[name] = compare(weights, answers, k, dm)
    return out
