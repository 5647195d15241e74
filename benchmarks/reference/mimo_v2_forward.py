"""Plain reference for MiMo-V2.5's forward pass over an item history.

``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``: no cache,
no ring, no chunked prefill, no kernel, no batching, nothing of the program.
The equations (source: https://huggingface.co/XiaomiMiMo/MiMo-V2.5/blob/main/
config.json, ``model_type: mimo_v2``):

* ``RMS(x) = x / sqrt(mean(x^2) + eps) * w``;
* **model**: ``x0 = E[ids]``; layer ``l``: ``h = x + Attn_l(RMS(x))``, ``out =
  h + FFN_l(RMS(h))``; final ``RMS``; logits ``= h_last W_head^T`` (untied);
* **attention**, full (``hybrid_layer_pattern`` 0) and window (1) alike: ``q =
  a W_q`` as 64 heads of 192, ``k = a W_k`` as ``K`` heads of 192, ``v =
  attention_value_scale * (a W_v)`` as ``K`` heads of 128 (``K`` 4 in a full
  layer, ``swa_num_key_value_heads`` 8 in a window layer; query head ``h``
  reads key/value head ``h // (64 / K)``); no bias, no q/k norm; RoPE on the
  FIRST 64 of the 192 dimensions (``partial_rotary_factor`` 0.334 x 192 = 64.1
  -> 64), pairs ``(i, i + 32)`` (rotate-half), angle ``t theta^(-i/32)``,
  ``theta`` ``rope_theta`` in a full layer and ``swa_rope_theta`` in a window
  layer; the other 128 dimensions as projected; ``s_ij = q_i . k_j /
  sqrt(192)``; ``o = P v``; out ``= concat_h(o_h) W_o`` (8192 -> 4096);
* **full layer**: ``i`` sees ``j`` iff ``j <= i``; ``P = softmax_j(s)``;
* **window layer**: ``i`` sees ``j`` iff ``0 <= i - j < sliding_window``, and
  a learned logit ``b_h`` a head joins the normaliser and nothing else:
  ``P_ij = exp(s_ij - m) / (sum_j exp(s_ij - m) + exp(b_h - m))``, ``m =
  max(max_j s_ij, b_h)``;
* **FFN_l**, ``moe_layer_freq[l]`` 0: ``(silu(x W_g) * (x W_u)) W_d`` of
  16,384; 1: ``sum_{i in S} g_i Expert_i(u)``, every expert the same SwiGLU of
  2,048, NO shared expert;
* **router** (``noaux_tc``, one group): ``s = sigmoid(u W_r)`` over the 256
  routed experts, ``S`` = the ``top_k`` largest ``s + b`` (``b``: the
  selection bias, which chooses and does not weigh), ``g_i = s_i / sum_{j in
  S} s_j`` (``norm_topk_prob``; ``routed_scaling_factor`` null: 1). **The
  share**: ``held = (e0, n)`` says which routed experts exist here; what the
  others would add is left out (``moe_parts``: a test adds the sixteen
  shares up).

Departures from the published description, each listed under ``assumed`` in
the configuration's file: the 64 rotary dimensions are the leading ones,
paired rotate-half; ``v`` is scaled where it is made; ``attention_chunk_size``
is not used; SwiGLU; the vision and audio towers and the three
multi-token-prediction layers are not run; weights, sinks and selection
biases are seeded.

So that 24,597 positions fit beside 7 GB of weights, attention runs ONE
key/value head (and its group of query heads) and a block of :data:`BLOCK`
QUERIES at a time against the blocks of keys up to its own (causal: a later
block holds nothing it may attend; in a window layer the mask empties every
block but the query block's own and the one before it, and a block the mask
empties whole is not computed: each number is the one the whole mask gives),
the softmax carried from block to block as its running maximum and sum, the
sink joining at the end; position-wise parts run a block of rows at a time, a
routed expert over the rows routed to it alone (:func:`gated`). A history is
padded to ONE length a cell (:func:`shapes`: what the traffic's longest
history can reach; causal: positions after the last real one change nothing
before them) and its REAL length goes in as an argument that bounds every
loop over blocks: the work follows the real length, and each layer kind
compiles once. Weights arrive as the benchmark's seeded arrays
(bfloat16-valued) and are widened inside each jitted part.

``compare`` holds an answer to the reference's forward, and where the router's
cut at the answer's last position is open (a held expert within
:data:`CUT_TOL` of it) to the forward on either side of that cut. The other
side is recomputed as ONE ROW (:func:`crossed_row`): the forward keeps every
layer's keys and values and its last position's input, and beyond the crossed
layer only that position's row differs.

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
#: routed rows of a block that one expert's matrices see at once
ROUTED_ROWS = 128
#: histories past these are reported: past the first a window layer drops
#: keys, past the second a full layer's walk is most of a chunk
LONG = (128, 4096)
#: the router's ``top_k``-th pick and the next lie this close, relative: a
#: near tie (counted and reported)
NEAR_TOL = 1e-3
#: ... and this close: a cut that roundings upstream may cross (the value
#: and the two below are GLM-5's, whose router this is: PERF.md section 4)
CUT_TOL = 3e-3
#: ranks on either side of the cut that may take part in one near tie
CUT_WINDOW = 3
#: other sides :func:`compare` tries of one answer's open cuts, at most
CROSSINGS = 8


def dims_of(cfg: dict) -> dict:
    """The sizes the equations need, from the configuration's own keys."""
    if cfg["topk_method"] != "noaux_tc" or int(cfg["n_group"]) != 1 \
            or cfg["scoring_func"] != "sigmoid" \
            or cfg["rope_scaling"]["rope_type"] != "default" \
            or cfg["add_full_attention_sink_bias"] \
            or not cfg["add_swa_attention_sink_bias"] \
            or cfg["n_shared_experts"]:
        raise ValueError("the reference writes out plain RoPE, one group's "
                         "sigmoid noaux_tc selection without a shared "
                         "expert, and a sink in the window layers only")
    scale = cfg["routed_scaling_factor"]
    return {
        "D": int(cfg["hidden_size"]), "H": int(cfg["num_attention_heads"]),
        "dk": int(cfg["head_dim"]), "dv": int(cfg["v_head_dim"]),
        "rot": int(float(cfg["partial_rotary_factor"])
                   * int(cfg["head_dim"])) // 2 * 2,
        "K_full": int(cfg["num_key_value_heads"]),
        "K_window": int(cfg["swa_num_key_value_heads"]),
        "theta_full": float(cfg["rope_theta"]),
        "theta_window": float(cfg["swa_rope_theta"]),
        "window": int(cfg["sliding_window"]),
        "value_scale": float(cfg["attention_value_scale"]),
        "eps": float(cfg["layernorm_epsilon"]),
        "n_routed": int(cfg["n_routed_experts_published"]),
        "top_k": int(cfg["num_experts_per_tok"]),
        "scale": 1.0 if scale is None else float(scale),
        "norm_topk": bool(cfg["norm_topk_prob"]),
        "held": tuple(int(v) for v in cfg["experts_held"]),
        # 1: a window layer / an expert layer, layer by layer as held here
        "pattern": tuple(int(v) for v in cfg["hybrid_layer_pattern_held"]),
        "moe": tuple(int(v) for v in cfg["moe_layer_freq_held"]),
    }


def kind_dims(dm: dict, window: bool) -> dict:
    """What differs between the two kinds of attention layer."""
    which = "window" if window else "full"
    return {"K": dm[f"K_{which}"], "theta": dm[f"theta_{which}"],
            "window": dm["window"] if window else 0, "sink": window}


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


def rope(x, pos, rot: int, theta: float):
    """``x`` [T, ..., d]: of its first ``rot`` dimensions, ``(i, i + rot/2)``
    turned by ``pos * theta^(-2i/rot)``; the others as they are."""
    import jax.numpy as jnp

    half = rot // 2
    inv = theta ** (-np.arange(half, dtype=np.float64) / half)
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], axis=-1)


def sees(q_pos, k_pos, window: int):
    """[Tq, Tk]: whether the query at ``q_pos`` sees the key at ``k_pos``."""
    d = q_pos[:, None] - k_pos[None, :]
    return (d >= 0) & (d < window) if window else d >= 0


def keys_of(p, u, pos, dm, kd, hold=None, nb=None):
    """A layer's keys and values of the normed positions ``u`` [T, D]: ``(k
    [T, K, dk]`` turned, ``v [T, K, dv]`` scaled)."""
    T, K = u.shape[0], kd["K"]
    k = _rows(lambda r: _mm(r, p["w_k"], hold), u, nb).reshape(T, K, dm["dk"])
    v = _rows(lambda r: _mm(r, p["w_v"], hold), u, nb).reshape(T, K, dm["dv"])
    return rope(k, pos, dm["rot"], kd["theta"]), dm["value_scale"] * v


def sink_of(p, kd, K: int, g: int):
    """The heads' sink logits as [K, g] (-inf where the kind has none)."""
    import jax.numpy as jnp

    if not kd["sink"]:
        return jnp.full((K, g), -jnp.inf)
    return p["sink"].astype(jnp.float32).reshape(K, g)


def attend(p, u, pos, keys, k_pos, dm, kd, hold=None, nq=None, nk=None):
    """Attention of the normed queries ``u`` [Tq, D] at ``pos`` over ``keys``
    (:func:`keys_of`'s, at ``k_pos``), one key/value head and its group of
    query heads at a time, a block of queries against the blocks of keys the
    mask leaves it. ``nq``, ``nk``: the blocks of queries and of keys in
    use (traced; None: all). [Tq, D]."""
    import jax
    import jax.numpy as jnp

    Tq, Tk = u.shape[0], keys[0].shape[0]
    H, dk, dv, K = dm["H"], dm["dk"], dm["dv"], kd["K"]
    g = H // K
    Bq = BLOCK if _blocked(Tq) else Tq
    Bk = BLOCK if _blocked(Tk) else Tk
    qb, kb = Tq // Bq, Tk // Bk
    nq = qb if nq is None else jnp.minimum(nq, qb)
    nk = kb if nk is None else jnp.minimum(nk, kb)
    scale = 1.0 / math.sqrt(dk)
    w_q = p["w_q"].reshape(dm["D"], K, g * dk).transpose(1, 0, 2)
    w_o = p["w_o"].reshape(K, g * dv, dm["D"])
    pos_q, pos_k = pos.reshape(qb, Bq), k_pos.reshape(kb, Bk)
    k_all = keys[0].reshape(kb, Bk, K, dk)
    v_all = keys[1].reshape(kb, Bk, K, dv)
    sinks = sink_of(p, kd, K, g)

    def block(a, i):
        return jax.lax.dynamic_index_in_dim(a, i, keepdims=False)

    def one_head(out, args):                               # [qb, Bq, D]
        wq, wo, sink, h = args                             # one kv head's
        q = _rows(lambda r: _mm(r, wq, hold), u,
                  None if Tq != Tk else nq).reshape(qb, Bq, g, dk)
        k_h = jax.lax.dynamic_index_in_dim(k_all, h, 2, keepdims=False)
        v_h = jax.lax.dynamic_index_in_dim(v_all, h, 2, keepdims=False)

        def queries(b, out):
            pq = block(pos_q, b)
            q_b = rope(block(q, b), pq, dm["rot"], kd["theta"])

            def keys_block(c, carry):
                m, l, acc = carry               # [g, Bq] twice, [g, Bq, dv]
                s = jnp.einsum("tgd,ud->gtu", _hold(q_b, hold),
                               _hold(block(k_h, c), hold)) * scale
                s = jnp.where(sees(pq, block(pos_k, c), kd["window"])[None],
                              s, -jnp.inf)
                top = jnp.maximum(m, s.max(axis=-1))
                at = jnp.where(jnp.isfinite(top), top, 0.0)
                e = jnp.exp(s - at[..., None])
                shrink = jnp.exp(m - at)         # 0 while nothing was seen
                return (top, shrink * l + e.sum(axis=-1),
                        shrink[..., None] * acc + jnp.einsum(
                            "gtu,ud->gtd", _hold(e, hold),
                            _hold(block(v_h, c), hold)))

            # the blocks of keys the mask leaves this block of queries: up
            # to the one that holds its last position, from the one that
            # holds its first position's window on (0 where all is seen)
            last = jnp.minimum(pq[-1] // Bk + 1, nk)
            first = (jnp.maximum(pq[0] - (kd["window"] - 1), 0) // Bk
                     if kd["window"] else 0)
            m, l, acc = jax.lax.fori_loop(first, last, keys_block, (
                jnp.full((g, Bq), -jnp.inf), jnp.zeros((g, Bq)),
                jnp.zeros((g, Bq, dv))))
            # the sink joins the normaliser, and nothing else
            top = jnp.maximum(m, sink[:, None])
            top = jnp.where(jnp.isfinite(top), top, 0.0)
            shrink = jnp.exp(m - top)
            l = shrink * l + jnp.exp(sink[:, None] - top)
            o_b = (shrink[..., None] * acc
                   / jnp.maximum(l, 1e-30)[..., None]).transpose(
                       1, 0, 2).reshape(Bq, g * dv)
            return jax.lax.dynamic_update_index_in_dim(
                out, block(out, b) + _mm(o_b, wo, hold), b, 0)

        return jax.lax.fori_loop(0, nq, queries, out), None

    return jax.lax.scan(one_head, jnp.zeros((qb, Bq, dm["D"])), (
        w_q, w_o, sinks, jnp.arange(K)))[0].reshape(Tq, dm["D"])


def attention(p, x, pos, dm, window: bool, hold=None, n=None):
    """One attention layer over the normed sequence ``x`` [T, D], full or
    window. ``n``: the real length (a traced scalar; None: all of ``T``)."""
    kd, nb = kind_dims(dm, window), blocks_of(n)
    keys = keys_of(p, x, pos, dm, kd, hold, nb)
    return attend(p, x, pos, keys, pos, dm, kd, hold, nb, nb)


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
    gates = dm["scale"] * (chosen / chosen.sum(axis=-1, keepdims=True)
                           if dm["norm_topk"] else chosen)
    near = (ranked[:, k - 1] - ranked[:, k]) <= NEAR_TOL * jnp.abs(
        ranked[:, k - 1])
    return gates, near, ranked, order


def moe_parts(p, x, dm, held, hold=None, cross=None, nb=None):
    """(what the routed experts ``held = (e0, n)`` add, near ties [T]).
    ``p["w_g"|"w_u"|"w_d"]`` hold those ``n`` experts' matrices, in order.
    ``cross = (row, picks [top_k], whether)``: that one row routed to
    ``picks`` instead of its own (where ``whether``), and a third result,
    the row's :func:`route` ranking ``(ranked, order)``: what :func:`sides`
    reads. ``nb``: the blocks of rows in use."""
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
            dm["scale"] * (chosen / chosen.sum() if dm["norm_topk"]
                           else chosen)), gates)
    out = gated(p, x, gates[:, e0:e0 + n], hold, nb), near
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
    routed, near, cut = moe_parts(p["moe"], u, dm, held, hold, cross, nb)
    return h + routed, near, cut


def _mixed(p, x, pos, dm, window: bool, hold=None, n=None):
    """``x + Attn(RMS(x))`` over ``x`` [T, D] and the layer's keys and values
    (:func:`keys_of`)."""
    kd, nb = kind_dims(dm, window), blocks_of(n)
    u = rms(x, p["norm_a"], dm["eps"])
    keys = keys_of(p["mixer_a"], u, pos, dm, kd, hold, nb)
    return x + attend(p["mixer_a"], u, pos, keys, pos, dm, kd, hold, nb,
                      nb), keys


def _row_mixed(p, x_row, keys, n, dm, window: bool, hold=None):
    """:func:`_mixed` for the LAST position alone, ``x_row`` [D] at position
    ``n - 1``, over the ``keys`` a forward kept of this layer: the row's own
    key and value take their place among them (they differ once a layer
    before this one was crossed), every earlier position's are as they were.
    [1, D]."""
    import jax.numpy as jnp

    kd = kind_dims(dm, window)
    at = jnp.reshape(n - 1, (1,))
    k_pos = jnp.arange(keys[0].shape[0], dtype=jnp.int32)
    u = rms(x_row[None], p["norm_a"], dm["eps"])
    own = keys_of(p["mixer_a"], u, at, dm, kd, hold)
    keys = tuple(k.at[n - 1].set(r[0]) for k, r in zip(keys, own))
    return x_row[None] + attend(p["mixer_a"], u, at, keys, k_pos, dm, kd,
                                hold, None, blocks_of(n))


@functools.lru_cache(maxsize=None)
def _jitted(dm_items, hold):
    """The jitted parts: the mixer of each kind and the FFN of a layer apart,
    so that each compiles once whatever the layers' order."""
    import jax
    import jax.numpy as jnp

    dm = dict(dm_items)
    held = dm["held"]

    def highest(fn, **kw):
        def run(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args)
        return jax.jit(run, **kw)

    def mixed(window):
        def run(p, x, n):
            h, keys = _mixed(p, x, jnp.arange(x.shape[0], dtype=jnp.int32),
                             dm, window, hold, n)
            return h, keys, x[n - 1]
        return highest(run)

    def fed(p, h, n, picks, whether):
        out, near, cut = _ffn_of(p, h, dm, held, hold, (n - 1, picks,
                                                        whether), blocks_of(n))
        return out, jnp.sum(near & (jnp.arange(h.shape[0]) < n)), cut

    def one_layer(p, window, x, n, picks, whether):
        h, keys, row = parts["mixed"][bool(window)](_mixer_of(p), x, n)
        return parts["fed"](_ffn_part_of(p), h, n, picks, whether) + (keys,
                                                                     row)

    def one_row(p, window, x_row, keys, n, picks, whether):
        h = parts["row_mixed"][bool(window)](_mixer_of(p), x_row, keys, n)
        out, _, cut = parts["row_fed"](_ffn_part_of(p), h, picks, whether)
        return out[0], cut

    def row_mixed(window):
        return highest(lambda p, x_row, keys, n: _row_mixed(
            p, x_row, keys, n, dm, window, hold))

    parts = {
        "mixed": {w: mixed(w) for w in (False, True)},
        "fed": highest(fed),
        "row_mixed": {w: row_mixed(w) for w in (False, True)},
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


def _check_layers(weights, dm) -> None:
    layers = weights["layers"]
    if len(layers) != len(dm["pattern"]) or len(layers) != len(dm["moe"]):
        raise ValueError("the held patterns name every layer held here")
    for i, p in enumerate(layers):
        if ("moe" in p) != bool(dm["moe"][i]) \
                or ("sink" in p["mixer_a"]) != bool(dm["pattern"][i]):
            raise ValueError(f"layer {i} is not of the kind the "
                             "configuration gives it")


def _forward(weights, ids, dm, hold=None, crossed=None, reach=None,
             kept=None):
    """:func:`forward`, and the router's ranking at the LAST position in
    every expert layer ``{layer: (ranked, order)}``. ``crossed``: ``{layer:
    picks}``, the last position routed to ``picks`` there (:func:`sides`).
    ``reach``: :func:`padded_length`'s. ``kept``: a list that takes, layer by
    layer, ``(the last position's input row, the layer's keys and values)``:
    what :func:`crossed_row` needs."""
    import jax.numpy as jnp

    embed, one_layer, _, head = _jitted(tuple(sorted(dm.items())), hold)
    _check_layers(weights, dm)
    crossed = crossed or {}
    n = len(ids)
    rows = np.zeros(padded_length(n, reach), np.int32)
    rows[:n] = np.asarray(ids, np.int32)
    x = embed(weights["embed"], jnp.asarray(rows))
    own = np.zeros(dm["top_k"], np.int32)        # a layer not crossed
    near_ties, cuts = 0, {}
    for i, p in enumerate(weights["layers"]):
        x, near, cut, keys, row = one_layer(
            p, dm["pattern"][i], x, jnp.int32(n),
            jnp.asarray(crossed.get(i, own)), jnp.bool_(i in crossed))
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
        x_row, _ = one_row(weights["layers"][i], dm["pattern"][i], x_row,
                           kept[i][1], jnp.int32(n),
                           jnp.asarray(crossed.get(i, own)),
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
    through attention's weights and stays in the numbers."""
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
    with weights and matrix-product inputs rounded to a lower precision, put
    in the program's place for a seeded handful of the cell's own histories
    (short ones: a precision needs no long history to fail)."""
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
