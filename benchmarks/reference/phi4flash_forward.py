"""Plain reference for Phi-4-mini-flash-reasoning's forward pass over an item
history (the SambaY decoder-hybrid-decoder with differential attention).

``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``: no cache,
no ring, no chunk program, no kernel, nothing of the program. The equations
are those of the configuration's file (``equations``), written out once more:

* ``LN(x) = (x - mean) / sqrt(var + eps) * w + b``; ``x0 = E[ids]`` (no scale,
  no position encoding anywhere); every layer ``h = x + Mix_l(LN x)``, ``out =
  h + W_d (silu(a W_g) * (a W_u))`` with ``a = LN h``; after the last layer
  ``LN``, then ``logits = h E^T`` (tied, no bias);
* **layer kinds** (:func:`layer_kinds`; ``L`` layers, ``half = L / 2``): below
  ``half`` even layers are Mamba-1 and odd ones differential attention under a
  window; layer ``half`` is Mamba-1 and yields the MEMORY ``m`` (its scan
  output before the gate); layer ``half + 1`` is full differential attention,
  whose keys and values every later attention layer shares; behind it even
  layers are gated memory units and odd ones cross attention;
* **Mamba-1**: ``[x, z] = a W_in``; ``x = silu(conv(x) + b)`` (causal,
  depthwise, ``d_conv`` taps); ``[dt, B, C] = x W_x``; ``delta = softplus(dt
  W_dt + b_dt)``; ``A = -exp(A_log)`` ``[d_inner, d_state]``; position by
  position from ``S = 0``: ``S = exp(delta_t A) S + (delta_t x_t) (x) B_t``,
  ``y_t = S C_t + D x_t`` -- the RECURRENCE itself, a loop over the real
  positions; out ``(y * silu(z)) W_out``;
* **differential attention**: ``q = a W_q + b_q`` (``H`` heads of ``d``),
  ``k``, ``v`` alike (``Hkv`` heads); heads pair up ``(2i, 2i + 1)`` into
  ``q1, q2``, ``k1, k2``, ``v1, v2`` (``H / Hkv`` query pairs to a key/value
  pair); ``A_a = softmax(q_a k_a^T / sqrt(d))`` over the positions the mask
  lets the query see (``j <= i``, and under a window ``i - j < window``);
  ``o_a = [A_a v1, A_a v2]``; ``lambda = exp(l_q1 . l_k1) - exp(l_q2 . l_k2) +
  lambda_init``, ``lambda_init = 0.8 - 0.6 exp(-0.3 layer)``; ``o = RMS_2d(o_1
  - lambda o_2) w * (1 - lambda_init)``; double heads side by side, ``W_o +
  b_o``. Scores are materialised a block of :data:`ROWS` queries at a time;
* **cross attention**: ``W_q``, ``W_o`` (and biases), its own ``lambda``s and
  sub-norm; keys and values are layer ``half + 1``'s;
* **GMU**: ``(silu(a W_1) * m) W_2``, ``m`` the same position's memory.

Row ``t`` of the layers behind ``half + 1`` reads row ``t`` of the layer
before, row ``t`` of ``m`` and the shared keys and values up to ``t``: the
forward evaluates them for the compared row alone (``rows="last"``) or for
every row (``rows="all"``); a test holds the two together.

Weights arrive as the benchmark's seeded arrays (bfloat16-valued) and are
widened one matrix at a time inside each jitted part. A history is padded to
ONE length a cell (:func:`shapes`) and its real length goes in as an argument
that bounds every loop, so each layer kind compiles once.

The controls (``control``): ``hold = (exponent_bits, mantissa_bits)`` rounds
every weight and every matrix product's input to that format; ``state_hold``
the carried ``S`` alone, after every position; ``no_lambda`` drops ``lambda
o_2``; ``window`` another window than the configuration's.
"""

from __future__ import annotations

import functools
import math

import numpy as np

FORMATS = {"bfloat16": (8, 7), "float8_e4m3fn": (4, 3)}
#: rows of one block: of a position-wise part, and of queries whose scores
#: against every key are held at once
ROWS = 512
#: a history is padded to whole multiples of this
PAD_TO = 1024


def layer_kinds(n_layers: int) -> tuple:
    """``"mamba" | "window" | "memory" | "full" | "gmu" | "cross"`` a layer."""
    half = n_layers // 2
    out = []
    for i in range(n_layers):
        if i < half:
            out.append("window" if i % 2 else "mamba")
        elif i <= half + 1:
            out.append("memory" if i == half else "full")
        else:
            out.append("cross" if i % 2 else "gmu")
    return tuple(out)


def dims_of(cfg: dict) -> dict:
    """The sizes the equations need, from the configuration's own keys."""
    sizes = cfg["assumed_sizes"]
    D, H = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return {"D": D, "F": int(cfg["intermediate_size"]),
            "eps": float(cfg["layer_norm_eps"]), "H": H,
            "Hkv": int(cfg["num_key_value_heads"]), "d": D // H,
            "window": int(cfg["sliding_window"]),
            "L": int(cfg["num_hidden_layers"]),
            "inner": int(sizes["expand"]) * D, "N": int(sizes["d_state"]),
            "R": int(sizes["dt_rank"]), "K": int(sizes["d_conv"])}


def _hold(x, hold):
    import jax

    return x if hold is None else jax.lax.reduce_precision(x, *hold)


def _mm(x, w, hold):
    import jax.numpy as jnp

    return jnp.dot(_hold(x, hold), _hold(w.astype(jnp.float32), hold))


def _f32(w):
    import jax.numpy as jnp

    return w.astype(jnp.float32)


def layernorm(x, p, eps):
    import jax.numpy as jnp

    mean = x.mean(axis=-1, keepdims=True)
    var = jnp.square(x - mean).mean(axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * _f32(p["scale"]) + _f32(
        p["bias"])


def blocks_of(n):
    return (n + ROWS - 1) // ROWS


def _rows(fn, x, nb):
    """``fn`` over the first ``nb`` (traced) blocks of :data:`ROWS` rows of
    ``x`` [T, ...] (``T`` a multiple of :data:`ROWS`, or shorter than one);
    zeros behind them. ``fn`` maps [ROWS, a] to [ROWS, b]."""
    import jax
    import jax.numpy as jnp

    T = x.shape[0]
    if T <= ROWS:
        return fn(x)
    width = jax.eval_shape(fn, x[:ROWS]).shape[1]

    def one(j, out):
        rows = jax.lax.dynamic_slice_in_dim(x, j * ROWS, ROWS)
        return jax.lax.dynamic_update_slice_in_dim(out, fn(rows), j * ROWS, 0)

    return jax.lax.fori_loop(0, nb, one, jnp.zeros((T, width), jnp.float32))


def mlp(p, a, hold=None, nb=None):
    import jax

    return _rows(lambda r: _mm(jax.nn.silu(_mm(r, p["w_g"], hold))
                               * _mm(r, p["w_u"], hold), p["w_d"], hold),
                 a, nb)


def mamba1(p, a, n, dm, hold=None, state_hold=None):
    """The Mamba-1 mixer over one sequence ``a`` [T, D], its first ``n``
    positions real: the recurrence, one position after another, from a zero
    state. ``(out [T, D], y [T, inner] before the gate)``; rows from ``n`` on
    are zeros."""
    import jax
    import jax.numpy as jnp

    T, nb = a.shape[0], blocks_of(n)
    D, inner, N, R, K = a.shape[1], dm["inner"], dm["N"], dm["R"], dm["K"]
    # (what is alive at once decides whether a history of 33,792 positions
    # fits beside the weights: ``z`` is made where it is used, ``delta`` and
    # ``[B, C]`` apart)
    x = _rows(lambda r: _mm(r, p["w_in"][:, :inner], hold), a, nb)
    padded = jnp.concatenate([jnp.zeros((K - 1, inner)), x])
    w = _f32(p["conv_w"])
    x = jax.nn.silu(sum(w[k] * padded[k:k + T] for k in range(K))
                    + _f32(p["conv_b"]))
    delta = _rows(lambda r: jax.nn.softplus(
        _mm(_mm(r, p["w_x"][:, :R], hold), p["w_dt"], hold)
        + _f32(p["b_dt"])), x, nb)
    BC = _rows(lambda r: _mm(r, p["w_x"][:, R:], hold), x, nb)
    B, C = BC[:, :N], BC[:, N:]
    A = -jnp.exp(_f32(p["a_log"]))                          # [inner, N]

    def step(t, carry):
        S, y = carry
        d_t, x_t = delta[t], x[t]
        S = (jnp.exp(d_t[:, None] * A) * S
             + (d_t * x_t)[:, None] * B[t][None, :])
        S = _hold(S, state_hold)
        return S, y.at[t].set((S * C[t][None, :]).sum(axis=-1))

    _, y = jax.lax.fori_loop(
        0, n, step, (jnp.zeros((inner, N), jnp.float32),
                     jnp.zeros((T, inner), jnp.float32)))
    y = jnp.where((jnp.arange(T) < n)[:, None], y + _f32(p["d"]) * x, 0.0)

    def gated(r):                                            # [R, D + inner]
        z = _mm(r[:, :D], p["w_in"][:, inner:], hold)
        return _mm(r[:, D:] * jax.nn.silu(z), p["w_out"], hold)

    return _rows(gated, _cat(a, y), nb), y


def keys_of(p, a, dm, hold=None, nb=None):
    """``(k [T, Hkv, d], v [T, Hkv, d])`` of the positions ``a`` [T, D]."""
    T, Hkv, d = a.shape[0], dm["Hkv"], dm["d"]
    kv = _rows(lambda r: _cat(_mm(r, p["w_k"], hold) + _f32(p["b_k"]),
                              _mm(r, p["w_v"], hold) + _f32(p["b_v"])),
               a, nb)
    return (kv[:, :Hkv * d].reshape(T, Hkv, d),
            kv[:, Hkv * d:].reshape(T, Hkv, d))


def _cat(*parts):
    import jax.numpy as jnp

    return jnp.concatenate(parts, axis=-1)


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def attend(p, a, q_pos, keys, dm, l0, window: int = 0, hold=None,
           nq=None, no_lambda: bool = False):
    """Differential attention of the query rows ``a`` [Q, D] at positions
    ``q_pos`` [Q] over ``keys = (k, v)`` [T, Hkv, d] at positions ``arange(T)``
    (this layer's own, or the shared ones), scores materialised for a block of
    :data:`ROWS` queries against every key at once. ``nq``: the blocks of
    queries in use (traced; None: all); ``l0``: the layer's ``lambda_init``
    (traced: every layer of a kind runs one compiled part). ``[Q, D]``."""
    import jax
    import jax.numpy as jnp

    k, v = keys
    T, (H, Hkv, d) = k.shape[0], (dm["H"], dm["Hkv"], dm["d"])
    pairs, G = Hkv // 2, H // Hkv
    v12 = _cat(v[:, 0::2], v[:, 1::2])                       # [T, pairs, 2d]
    l_q1, l_k1, l_q2, l_k2 = (_f32(p[n]) for n in (
        "lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"))
    lam = (0.0 if no_lambda else
           jnp.exp(jnp.sum(l_q1 * l_k1)) - jnp.exp(jnp.sum(l_q2 * l_k2)) + l0)

    # under a window a block of consecutive queries sees ``ROWS + window``
    # keys at most: those alone are sliced out (the mask is the same)
    span = ROWS + window if window and T > ROWS + window else T

    def block(rows):                                         # [R, D + 1]
        u, pos = rows[:, :-1], rows[:, -1].astype(jnp.int32)
        R = u.shape[0]
        q = (_mm(u, p["w_q"], hold) + _f32(p["b_q"])).reshape(R, H, d)
        lo = jnp.clip(pos[0] - window + 1, 0, T - span)
        k_b, v_b = (jax.lax.dynamic_slice_in_dim(a, lo, span)
                    for a in (k, v12))
        gap = pos[:, None] - (lo + jnp.arange(span))[None, :]
        seen = (gap >= 0) & ((gap < window) if window else True)
        o = []
        for part in (0, 1):
            q_a = q[:, part::2].reshape(R, pairs, G, d)
            s = jnp.einsum("rpgd,upd->pgru", _hold(q_a, hold),
                           _hold(k_b[:, part::2], hold)) / math.sqrt(d)
            prob = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf),
                                  axis=-1)
            o.append(jnp.einsum("pgru,upe->rpge", _hold(prob, hold),
                                _hold(v_b, hold)).reshape(R, H // 2, 2 * d))
        mixed = o[0] - lam * o[1]
        mixed = (mixed / jnp.sqrt(jnp.mean(mixed * mixed, axis=-1,
                                           keepdims=True) + dm["eps"])
                 * _f32(p["subln"]) * (1.0 - l0))
        return _mm(mixed.reshape(R, H * d), p["w_o"], hold) + _f32(p["b_o"])

    return _rows(block, _cat(a, q_pos.astype(jnp.float32)[:, None]), nq)


def gmu(p, a, memory, hold=None, nb=None):
    import jax

    D = a.shape[1]
    return _rows(lambda r: _mm(jax.nn.silu(_mm(r[:, :D], p["w_1"], hold))
                               * r[:, D:], p["w_2"], hold),
                 _cat(a, memory), nb)


def _ffn(p, h, dm, hold, nb):
    return h + mlp(p["ffn_a"], layernorm(h, p["norm_ffn_a"], dm["eps"]),
                   hold, nb)


@functools.lru_cache(maxsize=None)
def _jitted(dm_items, hold, state_hold, no_lambda, window):
    """The jitted parts, one a layer kind: the self-decoder's take all
    positions, the cross-decoder's whatever rows they are given."""
    import jax
    import jax.numpy as jnp

    dm = dict(dm_items)
    eps = dm["eps"]
    window = dm["window"] if window is None else window

    def highest(fn, **kw):
        def run(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args)
        return jax.jit(run, **kw)

    def mamba(p, x, n):
        out, y = mamba1(p["mixer_a"], layernorm(x, p["norm_a"], eps), n, dm,
                        hold, state_hold)
        return _ffn(p, x + out, dm, hold, blocks_of(n)), y

    def self_attention(p, x, n, l0, win):
        nb = blocks_of(n)
        a = layernorm(x, p["norm_a"], eps)
        keys = keys_of(p["mixer_a"], a, dm, hold, nb)
        pos = jnp.arange(x.shape[0])
        h = x + attend(p["mixer_a"], a, pos, keys, dm, l0, win, hold, nb,
                       no_lambda)
        return _ffn(p, h, dm, hold, nb), keys

    def cross(p, rows, pos, keys, nq, l0):
        a = layernorm(rows, p["norm_a"], eps)
        h = rows + attend(p["mixer_a"], a, pos, keys, dm, l0, 0, hold, nq,
                          no_lambda)
        return _ffn(p, h, dm, hold, nq)

    def gated(p, rows, memory, nq):
        a = layernorm(rows, p["norm_a"], eps)
        return _ffn(p, rows + gmu(p["mixer_a"], a, memory, hold, nq), dm,
                    hold, nq)

    return {
        "embed": jax.jit(lambda table, ids: table[ids].astype(jnp.float32)),
        "mamba": highest(mamba),
        "window": highest(lambda p, x, n, l0: self_attention(
            p, x, n, l0, window)[0]),
        "full": highest(lambda p, x, n, l0: self_attention(p, x, n, l0, 0)),
        "cross": highest(cross),
        "gmu": highest(gated),
        "head": highest(lambda final_norm, table, rows: _mm(
            layernorm(rows, final_norm, eps), table.T, hold))}


def shapes(reach: int) -> tuple:
    """The padded lengths of a cell whose histories reach ``reach``
    positions: ONE, in whole multiples of :data:`PAD_TO` (the real length
    goes in as an argument that bounds every loop)."""
    return (-(-reach // PAD_TO) * PAD_TO,)


def padded_length(n: int, reach=None) -> int:
    return shapes(max(n, reach or n))[0]


def forward(weights, ids, dm, hold=None, state_hold=None, reach=None,
            rows: str = "last", no_lambda: bool = False, window=None):
    """Logits after the history ``ids`` (rows of the item table, oldest
    first): ``[V]`` after its last position (``rows="last"``: the layers
    behind the shared keys run for that row alone), or ``[n, V]`` after every
    position (``"all"``)."""
    import jax.numpy as jnp

    part = _jitted(tuple(sorted(dm.items())), hold, state_hold,
                   bool(no_lambda), window)
    kinds = layer_kinds(dm["L"])
    if len(weights["layers"]) != len(kinds):
        raise ValueError("the weights hold another number of layers")
    n = len(ids)
    padded = np.zeros(padded_length(n, reach), np.int32)
    padded[:n] = np.asarray(ids, np.int32)
    x = part["embed"](weights["embed"], jnp.asarray(padded))
    n_ = jnp.int32(n)
    memory = keys = None
    for layer, (kind, p) in enumerate(zip(kinds, weights["layers"])):
        l0 = jnp.float32(lambda_init(layer))
        if kind in ("mamba", "memory"):
            x, y = part["mamba"](p, x, n_)
            memory = y if kind == "memory" else memory
        elif kind == "window":
            x = part["window"](p, x, n_, l0)
        elif kind == "full":
            x, keys = part["full"](p, x, n_, l0)
            if rows == "last":      # the cross-decoder: the answered row
                x, memory = x[n - 1][None], memory[n - 1][None]
                pos, nq = jnp.asarray([n - 1], jnp.int32), None
            else:
                pos, nq = jnp.arange(x.shape[0], dtype=jnp.int32), \
                    blocks_of(n_)
        elif kind == "gmu":
            x = part["gmu"](p, x, memory, nq)
        else:
            x = part["cross"](p, x, pos, keys, nq, l0)
    logits = part["head"](weights["final_norm"], weights["embed"],
                          x if rows == "last" else x[:n])
    logits = np.asarray(logits, np.float32)
    return logits[0] if rows == "last" else logits


def measure(logits: np.ndarray, answer, k: int):
    """``(score_err, rank_gap)`` of one served answer against the
    reference's logits, or None where the answer is malformed."""
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
    started (the driver's budget). The stack has no router: ``near_ties`` is
    0 of the positions x layers it reports.
    """
    score_err = rank_gap = 0.0
    malformed = compared = longest = positions = 0
    reach = reach or max((len(ids) for ids, _ in sample), default=1)
    for ids, answer in sample:
        if stop is not None and stop(compared + malformed):
            break
        got = measure(forward(weights, ids, dm, reach=reach), answer, k)
        positions += len(ids) * len(weights["layers"])
        if got is None:
            malformed += 1
            continue
        score_err, rank_gap = max(score_err, got[0]), max(rank_gap, got[1])
        compared += 1
        longest = max(longest, len(ids))
    return {"score_err": score_err, "rank_gap": rank_gap,
            "malformed": malformed, "compared": compared,
            "longest_history": longest, "near_ties": 0,
            "positions_x_layers": positions}


def top_k_answer(logits: np.ndarray, k: int):
    order = np.argsort(-logits)[:k]
    return [(int(i), float(logits[i])) for i in order]


#: the control's variants: the reference with this broken underneath, put in
#: the program's place
VARIANTS = {
    "bfloat16": {"hold": FORMATS["bfloat16"]},
    "float8_e4m3fn": {"hold": FORMATS["float8_e4m3fn"]},
    "state_bfloat16": {"state_hold": FORMATS["bfloat16"]},
    "no_lambda": {"no_lambda": True},
    "window_less_one": {"window": -1},
}


def control(bench, variants=tuple(VARIANTS)) -> dict:
    """The control's readings at the cell's own size: the reference computed
    with weights and matrix-product inputs rounded to a lower precision
    (``bfloat16``, ``float8_e4m3fn``), with the carried state alone held in
    bfloat16 (``state_bfloat16``), with ``lambda o_2`` dropped
    (``no_lambda``) or with a window one position short
    (``window_less_one``), put in the program's place for a seeded sample of
    the cell's own histories."""
    builder = bench.load_module("models", bench.config["engine"])
    weights = builder.make_weights(bench)
    dm = dims_of(bench.config)
    k = int(bench.traffic["num"])
    histories = builder.control_histories(bench)
    out = {}
    for name in variants:
        how = dict(VARIANTS[name])
        if how.get("window") == -1:
            how["window"] = dm["window"] - 1
        answers = [(ids, top_k_answer(forward(weights, ids, dm, **how), k))
                   for ids in histories]
        out[name] = compare(weights, answers, k, dm)
    return out
