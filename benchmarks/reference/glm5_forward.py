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
  ``I[t, .]`` (``jax.lax.top_k``: ties to the earlier position);
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
a time is attended, a block of queries at a time, against every key under
that mask; position-wise parts run a block of rows at a time: each number is
the one the unblocked form gives. A history is padded to one of a few lengths
(:func:`padded_length`; causal: positions after the last real one change
nothing before them and are never selected) so that a sample compiles a
handful of shapes. Weights arrive as the benchmark's seeded arrays
(bfloat16-valued) and are widened inside each jitted part.

``compare`` holds an answer to the reference's forward, and where the router's
cut at the answer's last position is open (a held expert within
:data:`CUT_TOL` of it) to the forward on either side of that cut.

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
#: [heads of a group, BLOCK, T] float32 scores held at once
SCORE_BYTES = 1 << 29
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
#: forwards :func:`compare` spends on one answer's open cuts, at most
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


def _rows(fn, x):
    """``fn`` over ``x`` [T, ...] a block of rows at a time (each row's
    result, an array or several, is its own)."""
    import jax

    T = x.shape[0]
    if T <= BLOCK or T % BLOCK:
        return fn(x)
    out = jax.lax.map(fn, x.reshape((T // BLOCK, BLOCK) + x.shape[1:]))
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


def index_parts(p, x, cq, pos, dm, hold=None):
    """``(qI [T, Hi, di], kI [T, di], w [T, Hi])`` of the positions ``x``
    (``cq``: their queries' latent)."""
    import jax.numpy as jnp

    dr = dm["dr"]

    def turned(v):
        return jnp.concatenate([rope(v[..., :dr], pos, dm), v[..., dr:]],
                               axis=-1)

    qi = turned(_rows(lambda r: _mm(r, p["w_qi"], hold), cq).reshape(
        x.shape[0], dm["Hi"], dm["di"]))
    ki = turned(layer_norm(_rows(lambda r: _mm(r, p["w_ki"], hold), x),
                           p["ki_norm"], dm["eps_i"]))
    w = _mm(x, p["w_w"], hold) * (dm["Hi"] ** -0.5 * dm["di"] ** -0.5)
    return qi, ki, w


def selected(qi, w, q_pos, ki, k_pos, dm, hold=None):
    """The sets of the query rows ``qi`` [Tq, Hi, di] (weights ``w``,
    positions ``q_pos``) over the keys ``ki`` [T, di] as a mask [Tq, T]:
    ``I`` a head at a time, ``jax.lax.top_k`` of each row, causal."""
    import jax
    import jax.numpy as jnp

    def head(acc, args):
        q_j, w_j = args                                   # [Tq, di], [Tq]
        s = jnp.dot(_hold(q_j, hold), _hold(ki, hold).T)
        return acc + w_j[:, None] * jnp.maximum(s, 0.0), None

    Tq, T = qi.shape[0], ki.shape[0]
    scores, _ = jax.lax.scan(head, jnp.zeros((Tq, T), jnp.float32),
                             (qi.transpose(1, 0, 2), w.T))
    causal = q_pos[:, None] >= k_pos[None, :]
    idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf),
                        min(dm["topk"], T))[1]
    picked = jnp.zeros((Tq, T), bool).at[
        jnp.arange(Tq)[:, None], idx].set(True)
    return picked & causal


def mla(p, x, pos, dm, hold=None, with_sets: bool = False):
    """Latent attention over one sequence ``x`` [T, D], every row over its
    own set; with ``with_sets`` also the sets, as a mask [T, T]."""
    import jax
    import jax.numpy as jnp

    T = x.shape[0]
    H, dn, dr, dv, rkv = dm["H"], dm["dn"], dm["dr"], dm["dv"], dm["rkv"]
    scale = 1.0 / math.sqrt(dn + dr)
    cq = _rows(lambda r: rms(_mm(r, p["w_dq"], hold), p["q_norm"],
                             dm["eps"]), x)
    down = _rows(lambda r: _mm(r, p["w_dkv"], hold), x)
    ckv = rms(down[:, :rkv], p["kv_norm"], dm["eps"])
    kr = rope(down[:, rkv:], pos, dm)                        # [T, dr]
    qi, ki, w = index_parts(p, x, cq, pos, dm, hold)
    blocks = max(1, T // BLOCK) if T % BLOCK == 0 else 1
    Tq = T // blocks
    group = max(1, min(H, SCORE_BYTES // (4 * Tq * T)))
    while H % group:
        group -= 1
    w_uq = p["w_uq"].reshape(dm["rq"], H // group, group, dn + dr)
    w_ukv = p["w_ukv"].reshape(rkv, H // group, group, dn + dv)
    w_o = p["w_o"].reshape(H // group, group * dv, dm["D"])

    # every row's set first, a block of queries at a time ([T, T] as a mask:
    # 1.4 GB at 36,864 positions), then the heads a group at a time, each
    # group's keys and values expanded once
    keep = jax.lax.map(
        lambda args: selected(args[0], args[1], args[2], ki, pos, dm, hold),
        (qi.reshape(blocks, Tq, dm["Hi"], -1), w.reshape(blocks, Tq, -1),
         pos.reshape(blocks, Tq)))                          # [blocks, Tq, T]

    def heads(out, args):
        uq, ukv, wo = args                                  # one group's
        q = _mm(cq, uq.reshape(dm["rq"], -1), hold).reshape(
            T, group, dn + dr)
        qn, qr = q[..., :dn], rope(q[..., dn:], pos, dm)
        kv = _mm(ckv, ukv.reshape(rkv, -1), hold).reshape(T, group, dn + dv)
        kn, v = kv[..., :dn], kv[..., dn:]

        def queries(args):
            qn_b, qr_b, keep_b = args                       # one block's
            s = (jnp.einsum("tgd,ugd->gtu", _hold(qn_b, hold),
                            _hold(kn, hold))
                 + jnp.einsum("tgd,ud->gtu", _hold(qr_b, hold),
                              _hold(kr, hold)))
            prob = jax.nn.softmax(
                jnp.where(keep_b[None], s * scale, -jnp.inf), axis=-1)
            return jnp.einsum("gtu,ugd->tgd", _hold(prob, hold),
                              _hold(v, hold))

        o = jax.lax.map(queries, (
            qn.reshape(blocks, Tq, group, dn),
            qr.reshape(blocks, Tq, group, dr), keep))
        return out + _mm(o.reshape(T, group * dv), wo, hold), None

    out = jax.lax.scan(heads, jnp.zeros_like(x), (
        w_uq.transpose(1, 0, 2, 3), w_ukv.transpose(1, 0, 2, 3), w_o))[0]
    return (out, keep.reshape(T, T)) if with_sets else out


def ffn(p, x, hold=None):
    import jax

    return _rows(lambda r: _mm(
        jax.nn.silu(_mm(r, p["w_g"], hold)) * _mm(r, p["w_u"], hold),
        p["w_d"], hold), x)


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


def moe_parts(p, x, dm, held, hold=None, cross=None):
    """(what the routed experts ``held = (e0, n)`` add, what the shared
    expert adds, near ties [T]). ``p["w_g"|"w_u"|"w_d"]`` hold those ``n``
    experts' matrices, in order. ``cross = (row, picks [top_k], whether)``:
    that one row routed to ``picks`` instead of its own (where ``whether``),
    and a fourth result, the row's :func:`route` ranking ``(ranked, order)``:
    what :func:`sides` reads."""
    import jax
    import jax.numpy as jnp

    gates, near, ranked, order = _rows(lambda r: route(p, r, dm, hold), x)
    e0, n = held
    if cross is not None:
        row, picks, whether = cross
        s = jax.nn.sigmoid(_mm(x[row][None], p["w_r"], hold))[0]
        chosen = jnp.zeros_like(s).at[picks].set(s[picks])
        gates = jnp.where(whether, gates.at[row].set(
            dm["scale"] * chosen / chosen.sum()), gates)

    def one(acc, args):
        w_g, w_u, w_d, g = args
        y = ffn({"w_g": w_g, "w_u": w_u, "w_d": w_d}, x, hold)
        return acc + g[:, None] * y, None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (p["w_g"], p["w_u"], p["w_d"], gates[:, e0:e0 + n].T))
    out = routed, ffn(p["shared"], x, hold), near
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


def layer(p, x, pos, dm, held, hold=None, cross=None):
    """One layer over ``x`` [T, D], dense or expert by what ``p`` holds;
    (out, near ties [T], the ranking of ``cross``'s row: :func:`moe_parts`;
    zeros from a dense layer or without ``cross``)."""
    import jax.numpy as jnp

    eps = dm["eps"]
    h = x + mla(p["mixer_a"], rms(x, p["norm_a"], eps), pos, dm, hold)
    u = rms(h, p["norm_ffn_a"], eps)
    width = dm["top_k"] + CUT_WINDOW
    cut = jnp.zeros(width), jnp.zeros(width, jnp.int32)
    if "moe" not in p:
        return h + ffn(p["ffn_a"], u, hold), jnp.zeros(x.shape[0], bool), cut
    routed, shared, near, *ranking = moe_parts(p["moe"], u, dm, held, hold,
                                               cross)
    return h + routed + shared, near, (ranking[0] if ranking else cut)


@functools.lru_cache(maxsize=None)
def _jitted(dm_items, hold):
    import jax

    dm = dict(dm_items)

    def one_layer(p, x, pos, row, picks, whether):
        with jax.default_matmul_precision("highest"):
            return layer(p, x, pos, dm, dm["held"], hold,
                         (row, picks, whether))

    def head(final_norm, table, h):
        with jax.default_matmul_precision("highest"):
            return _mm(rms(h, final_norm, dm["eps"])[None], table.T, hold)[0]

    return jax.jit(one_layer), jax.jit(head)


def padded_length(n: int) -> int:
    """One block, or whole fours of blocks: at most ten shapes up to 32,795
    positions."""
    step = BLOCK if n <= BLOCK else 4 * BLOCK
    return -(-n // step) * step


def forward(weights, ids, dm, hold=None):
    """Logits [V] after the history ``ids`` (rows of the item table, oldest
    first), and how many of its (position, expert layer) pairs had a near tie
    at the router's cut: each is a place where a rounding can send a token to
    another expert than the reference's."""
    return _forward(weights, ids, dm, hold)[:2]


def _forward(weights, ids, dm, hold=None, crossed=None):
    """:func:`forward`, and the router's ranking at the LAST position in
    every expert layer ``{layer: (ranked, order)}``. ``crossed``: ``{layer:
    picks}``, the last position routed to ``picks`` there (:func:`sides`)."""
    import jax.numpy as jnp

    one_layer, head = _jitted(tuple(sorted(dm.items())), hold)
    crossed = crossed or {}
    n = len(ids)
    padded = padded_length(n)
    rows = np.zeros(padded, np.int32)
    rows[:n] = np.asarray(ids, np.int32)
    x = weights["embed"][jnp.asarray(rows)].astype(jnp.float32)
    pos = jnp.arange(padded, dtype=jnp.int32)
    own = np.zeros(dm["top_k"], np.int32)        # a layer not crossed
    near_ties, cuts = 0, {}
    for i, p in enumerate(weights["layers"]):
        if ("moe" in p) != (i >= dm["first_dense"]):
            raise ValueError(f"layer {i} is not of the kind the "
                             "configuration gives it")
        x, near, cut = one_layer(p, x, pos, jnp.int32(n - 1),
                                 jnp.asarray(crossed.get(i, own)),
                                 jnp.bool_(i in crossed))
        near_ties += int(near[:n].sum())
        if "moe" in p:
            cuts[i] = tuple(np.asarray(c) for c in cut)
    logits = head(weights["final_norm"], weights["head"], x[n - 1])
    return np.asarray(logits, np.float32), near_ties, cuts


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


def compare(weights, sample, k: int, dm: dict) -> dict:
    """``sample``: [(ids, [(item_row, served_score), ...]), ...]: the widest
    :func:`measure` of each answer against the reference's full forward over
    its ``ids``.

    **Both sides of an open cut.** Where the router's ``top_k``-th pick and
    the next lie within :data:`CUT_TOL` of each other at an answer's LAST
    position and one of the experts at the cut is held here, roundings
    upstream decide which side the token falls on: the reference's own side
    is no more the model's than the other, and the two differ by a whole
    gated expert's output at the one position the answer is read from (every
    other position is as it was: attention is causal). Such an answer is
    measured against the forward on the other sides of that cut too
    (:func:`sides`; every combination over the layers, at most
    :data:`CROSSINGS` forwards), and the side it lies nearest is the one
    compared. A tie at any EARLIER position reaches the answer only through
    attention's weights and stays in the numbers, as do the index's own near
    ties at a row's 2,048th position."""
    import itertools

    score_err = rank_gap = 0.0
    malformed = compared = longest = near_ties = positions = 0
    open_answers = crossed_answers = 0
    widest = None
    n_long = [0] * len(LONG)
    expert_layers = sum(1 for p in weights["layers"] if "moe" in p)
    for ids, answer in sample:
        logits, near, cuts = _forward(weights, ids, dm)
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
            there = measure(_forward(weights, ids, dm, None, {
                i: picks for i, picks in zip(layers, choice)
                if picks is not None})[0], answer, k)
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
