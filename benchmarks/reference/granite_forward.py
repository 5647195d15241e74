"""Plain reference for granite-4.0-h-small's forward pass over an item history.

``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``: no cache,
no chunked scan, no kernel, no batching, nothing of the program. The equations
are those of the configuration's file (``equations``), written out once more:

* ``RMS(x) = x / sqrt(mean(x^2) + eps) * w``;
* **model**: ``x0 = embedding_multiplier * E[ids]``; each layer ``h = x + m *
  Mix(RMS(x))``, ``out = h + m * (MoE(RMS(h)) + Shared(RMS(h)))`` with ``m =
  residual_multiplier``; after the last layer ``RMS``, then ``logits = h E^T /
  logits_scaling`` (the embedding, tied);
* **attention** (``layer_types[i] == "attention"``): ``q = a W_q`` (``H`` heads
  of ``d``), ``k = a W_k``, ``v = a W_v`` (``Hkv`` heads); NO position encoding,
  no norms; causal softmax of ``q . k * attention_multiplier``, ``H / Hkv``
  query heads to a key/value head; heads concatenated, ``W_o``;
* **Mamba-2** (``"mamba"``): ``[z, xBC, dt] = a W_in``; ``xBC = silu(conv(xBC))``
  (causal, depthwise, ``d_conv`` taps, with bias); ``[x, B, C] = xBC``; ``delta
  = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; per head ``h``, position by
  position from ``S = 0``: ``S = exp(delta_t A_h) S + delta_t x_t (x) B_t``,
  ``y_t = S C_t + D_h x_t`` — the RECURRENCE itself, a scan over positions, not
  the chunked form; ``y = RMS(y * silu(z)) * w``; ``y W_out``;
* **experts**: ``(silu(u W_g) * (u W_u)) W_d``; router ``l = u W_r`` over all the
  model's routed experts, ``S = top_k(l)``, ``g = softmax(l_S)`` over the picks.
  **The share**: ``held = (e0, n)`` says which routed experts exist here; what
  the others would add is left out (``moe_parts`` returns the routed part and
  the shared expert's apart, so that a test can add shares up).

Weights arrive as the benchmark's seeded arrays (bfloat16-valued) and are
widened one matrix at a time inside each jitted part, so that the reference
fits beside the weights themselves.

``hold``: the control. ``(exponent_bits, mantissa_bits)`` rounds every weight
and every matrix product's input to that format (``lax.reduce_precision``);
``None`` is the reference proper. ``state_hold``: the same rounding of the
carried ``S`` alone, after every position.
"""

from __future__ import annotations

import functools

import numpy as np

FORMATS = {"bfloat16": (8, 7), "float8_e4m3fn": (4, 3)}
#: a history is padded to one of :func:`shapes`' whole multiples of this
#: before the jitted parts see it (causal and recurrent: positions after the
#: last real one change nothing before them)
PAD_TO = 1024
#: [heads, T, T] float32 scores held at once
SCORE_BYTES = 1 << 30


def dims_of(cfg: dict) -> dict:
    """The sizes the equations need, from the configuration's own keys."""
    L = int(cfg["num_hidden_layers"])
    return {
        "D": int(cfg["hidden_size"]), "eps": float(cfg["rms_norm_eps"]),
        "layer_types": tuple(cfg["layer_types"][:L]),
        "embed_mult": float(cfg["embedding_multiplier"]),
        "attn_mult": float(cfg["attention_multiplier"]),
        "res_mult": float(cfg["residual_multiplier"]),
        "logits_scaling": float(cfg["logits_scaling"]),
        "H": int(cfg["num_attention_heads"]),
        "Hkv": int(cfg["num_key_value_heads"]),
        "d": int(cfg["hidden_size"]) // int(cfg["num_attention_heads"]),
        "m_heads": int(cfg["mamba_n_heads"]),
        "m_head": int(cfg["mamba_d_head"]),
        "m_state": int(cfg["mamba_d_state"]),
        "m_conv": int(cfg["mamba_d_conv"]),
        "n_routed": int(cfg["num_local_experts_published"]),
        "top_k": int(cfg["num_experts_per_tok"]),
        "held": tuple(int(v) for v in cfg["experts_held"]),
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


def attention(p, x, dm, hold=None):
    """Full causal grouped-query attention over one sequence ``x`` [T, D]."""
    import jax
    import jax.numpy as jnp

    T = x.shape[0]
    H, Hkv, d = dm["H"], dm["Hkv"], dm["d"]
    G = H // Hkv
    q = _mm(x, p["w_q"], hold).reshape(T, Hkv, G, d)
    k = _mm(x, p["w_k"], hold).reshape(T, Hkv, d)
    v = _mm(x, p["w_v"], hold).reshape(T, Hkv, d)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]

    def one_kv_head(args):
        q_h, k_h, v_h = args                     # [G, T, d], [T, d], [T, d]
        s = jnp.einsum("gtd,ud->gtu", _hold(q_h, hold), _hold(k_h, hold))
        s = jnp.where(causal[None], s * dm["attn_mult"], -jnp.inf)
        prob = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("gtu,ud->gtd", _hold(prob, hold), _hold(v_h, hold))

    assert 4 * G * T * T <= 2 * SCORE_BYTES, "history too long to materialise"
    o = jax.lax.map(one_kv_head, (q.transpose(1, 2, 0, 3),
                                  k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    o = o.transpose(2, 0, 1, 3).reshape(T, H * d)        # [Hkv,G,T,d] -> T
    return _mm(o, p["w_o"], hold)


def mamba2(p, x, dm, hold=None, state_hold=None):
    """The Mamba-2 mixer over one sequence ``x`` [T, D]: the recurrence,
    one position after another, from a zero state."""
    import jax
    import jax.numpy as jnp

    T = x.shape[0]
    Hm, P, N, K = dm["m_heads"], dm["m_head"], dm["m_state"], dm["m_conv"]
    inner = Hm * P
    zxbcdt = _mm(x, p["w_in"], hold)
    z, xbc, dt = (zxbcdt[:, :inner], zxbcdt[:, inner:2 * inner + 2 * N],
                  zxbcdt[:, 2 * inner + 2 * N:])
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xbc])
    w = p["conv_w"].astype(jnp.float32)
    xbc = jax.nn.silu(sum(w[k] * padded[k:k + T] for k in range(K))
                      + p["conv_b"].astype(jnp.float32))
    xs = xbc[:, :inner].reshape(T, Hm, P)
    B, C = xbc[:, inner:inner + N], xbc[:, inner + N:]
    delta = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))  # [T, Hm]
    A = -jnp.exp(p["a_log"].astype(jnp.float32))

    def step(S, args):
        x_t, d_t, B_t, C_t = args
        S = (jnp.exp(d_t * A)[:, None, None] * S
             + (d_t[:, None] * x_t)[:, :, None] * B_t[None, None, :])
        S = _hold(S, state_hold)
        return S, (S * C_t[None, None, :]).sum(axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((Hm, P, N), jnp.float32),
                        (xs, delta, B, C))
    y = y + p["d"].astype(jnp.float32)[None, :, None] * xs
    y = rms(y.reshape(T, inner) * jax.nn.silu(z), p["norm"], dm["eps"])
    return _mm(y, p["w_out"], hold)


def ffn(p, x, hold=None):
    import jax

    return _mm(jax.nn.silu(_mm(x, p["w_g"], hold)) * _mm(x, p["w_u"], hold),
               p["w_d"], hold)


def route(p, x, dm, hold=None):
    """(gates [T, n_routed]: the softmax over the picked logits, 0 elsewhere;
    near [T]: whether the position's ``top_k``-th and next pick lie within a
    thousandth of each other, relative, as probabilities)."""
    import jax
    import jax.numpy as jnp

    logits = _mm(x, p["w_r"], hold)
    k = dm["top_k"]
    top, idx = jax.lax.top_k(logits, k + 1)
    gates = jnp.zeros_like(logits).at[
        jnp.arange(x.shape[0])[:, None], idx[:, :k]].set(
            jax.nn.softmax(top[:, :k], axis=-1))
    prob = jax.nn.softmax(top, axis=-1)
    near = (prob[:, k - 1] - prob[:, k]) <= 1e-3 * prob[:, k - 1]
    return gates, near


def moe_parts(p, x, dm, held, hold=None):
    """(what the routed experts ``held = (e0, n)`` add, what the shared
    expert adds, near ties). ``p["w_g"|"w_u"|"w_d"]`` hold those ``n``
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
    return routed, ffn(p["shared"], x, hold), near


def layer(p, x, kind, dm, held, hold=None, state_hold=None):
    """One layer over ``x`` [T, D]; (out, near ties)."""
    eps, m = dm["eps"], dm["res_mult"]
    a = rms(x, p["norm_a"], eps)
    mix = (attention(p["mixer_a"], a, dm, hold) if kind == "attention"
           else mamba2(p["mixer_a"], a, dm, hold, state_hold))
    h = x + m * mix
    routed, shared, near = moe_parts(p["moe"], rms(h, p["norm_ffn_a"], eps),
                                     dm, held, hold)
    return h + m * (routed + shared), near


@functools.lru_cache(maxsize=None)
def _jitted(dm_items, hold, state_hold):
    import jax
    import jax.numpy as jnp

    dm = dict(dm_items)

    def embed(table, rows):
        return table[rows].astype(jnp.float32) * dm["embed_mult"]

    def one_layer(kind):
        def run(p, x, n):
            with jax.default_matmul_precision("highest"):
                out, near = layer(p, x, kind, dm, dm["held"], hold,
                                  state_hold)
                return out, jnp.sum(near & (jnp.arange(x.shape[0]) < n))
        return jax.jit(run)

    def head(final_norm, table, x, n):
        with jax.default_matmul_precision("highest"):
            return _mm(rms(x[n - 1], final_norm, dm["eps"])[None], table.T,
                       hold)[0] / dm["logits_scaling"]

    return (jax.jit(embed),
            {kind: one_layer(kind) for kind in set(dm["layer_types"])},
            jax.jit(head))


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


def forward(weights, ids, dm, hold=None, state_hold=None, reach=None):
    """Logits [V] after the history ``ids`` (rows of the item table, oldest
    first), and how many of its (position, layer) pairs had a near tie at
    the router's cut: each is a place where a rounding can send a token to
    another expert than the reference's. ``reach``:
    :func:`padded_length`'s."""
    import jax.numpy as jnp

    embed, layers, head = _jitted(tuple(sorted(dm.items())), hold,
                                  state_hold)
    n = len(ids)
    rows = np.zeros(padded_length(n, reach), np.int32)
    rows[:n] = np.asarray(ids, np.int32)
    x = embed(weights["embed"], jnp.asarray(rows))
    near_ties = 0
    for kind, p in zip(dm["layer_types"], weights["layers"]):
        x, near = layers[kind](p, x, jnp.int32(n))
        near_ties += int(near)
    logits = head(weights["final_norm"], weights["embed"], x, jnp.int32(n))
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
    with weights and matrix-product inputs rounded to a lower precision
    (``bfloat16``, ``float8_e4m3fn``), or with the carried state alone held
    in bfloat16 (``state_bfloat16``), put in the program's place for a seeded
    sample of the cell's own histories (short ones: a control needs no long
    history to fail)."""
    builder = bench.load_module("models", bench.config["engine"])
    weights = builder.make_weights(bench)
    dm = dims_of(bench.config)
    k = int(bench.traffic["num"])
    histories = builder.control_histories(bench)
    out = {}
    for name, held in (("bfloat16", {"hold": FORMATS["bfloat16"]}),
                       ("float8_e4m3fn", {"hold": FORMATS["float8_e4m3fn"]}),
                       ("state_bfloat16",
                        {"state_hold": FORMATS["bfloat16"]})):
        answers = [(ids, top_k_answer(forward(weights, ids, dm, **held)[0],
                                      k)) for ids in histories]
        out[name] = compare(weights, answers, k, dm)
    return out
