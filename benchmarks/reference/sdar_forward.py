"""Plain reference for SDAR-30B-A3B-Chat (``model_type: sdar_moe``): the
layer, the block-causal mask and the block-diffusion generation loop.

``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``: no cache,
no chunked prefill, no kernel, no batching, nothing of the program. Every
forward is a FULL forward over everything so far. The equations are those of
the configuration's file (``equations``), written out once more here:

* ``RMS(x) = x / sqrt(mean(x^2) + eps) * w``;
* **attention** (grouped-query): ``a = RMS(x)``; ``q = a W_q`` [T, 32, 128],
  ``k = a W_k``, ``v = a W_v`` [T, 4, 128]; ``q = RMS_128(q)``, ``k =
  RMS_128(k)`` per head, over the head's 128 dimensions, a learned weight of
  128 each; RoPE on all 128 dimensions, pairs ``(i, i + 64)`` (rotate-half),
  absolute positions; scores ``q k^T / sqrt(128)``, 8 query heads to one
  key/value head; mask **block-causal**: position ``i`` sees ``j`` iff
  ``j // B <= i // B`` (block length ``B`` = 4); ``h = x + softmax(s) v W_o``;
* **experts**: ``u = RMS(h)``; ``p = softmax(u W_r)`` over 128; ``S =
  top8(p)``; ``g_i = p_i / sum_{j in S} p_j`` (``norm_topk_prob``); ``out = h +
  sum_{i in S} g_i W_d,i (silu(W_g,i u) * W_u,i u)``. No dense FFN, no shared
  expert, no bias anywhere;
* **model**: item embedding -> layers -> RMS -> ``logits = h W_head^T``. **No
  shift**: the logits at position ``i`` score the item AT position ``i`` (a
  mask row is fed there).

**Generation** (:func:`block_diffusion_generate`, after the model's own
``generate.py``). ``x`` = the history, then mask rows up to the end of block
``ceil((H + generate) / B) - 1``. The history's ``H // B`` whole blocks are
context; the ``H mod B`` items left over open the first generated block,
already unmasked. For each block in order: a **denoise forward** over
everything up to the block's end gives, at each position still masked, the
best item and its softmax probability (greedy, temperature 0); the rule
unmasks some of them; when no mask is left a **commit forward** over the
finished block follows (the model's loop stores the block's keys and values
with it; this reference keeps no cache, so it computes nothing there and only
lists it). Rules: ``low_confidence_static`` — the ``n_s`` most confident
masked positions of the block's ``s``-th forward, ``n_s`` from ``B /
denoising_steps`` (remainder to the first forwards); ``low_confidence_dynamic``
— every masked position whose confidence exceeds ``threshold``, and at least
``n_s``. Two departures, in the program alike: the mask row's logit is -inf
before the softmax (a mask is never an answer), and a forward unmasks
``min(n_s, masks left)`` positions (the model's ``topk`` over a block with
fewer masks than ``n_s`` would reach into the history's items).

Weights arrive as the benchmark's seeded arrays (bfloat16-valued) under the
program's block names and are widened one matrix at a time inside each jitted
part. ``hold``: the control. ``(exponent_bits, mantissa_bits)`` rounds every
weight and every matrix product's input to that format; ``None`` is the
reference proper.
"""

from __future__ import annotations

import functools
import math

import numpy as np

FORMATS = {"bfloat16": (8, 7), "float8_e4m3fn": (4, 3)}
#: a sequence is padded to one of :func:`shapes`' whole multiples of this
#: before the jitted parts see it (block-causal: whole blocks after the last
#: real one change nothing before them)
PAD_TO = 512
#: experts whose matrices are widened to float32 at once
EXPERT_GROUP = 8


def dims_of(cfg: dict) -> dict:
    """The sizes the equations need, from the configuration's own keys."""
    return {"D": int(cfg["hidden_size"]), "H": int(cfg["num_attention_heads"]),
            "KV": int(cfg["num_key_value_heads"]), "hd": int(cfg["head_dim"]),
            "theta": float(cfg["rope_theta"]), "eps": float(cfg["rms_norm_eps"]),
            "top_k": int(cfg["num_experts_per_tok"]),
            "bl": int(cfg["generation"]["block_len"])}


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


def rope_half(x, pos, theta):
    """``x`` [T, heads, d]: dimensions ``(i, i + d/2)`` turned by ``pos *
    theta^(-2i/d)``."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / x.shape[-1])
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(p, a, pos, dm, hold=None):
    """Grouped-query attention of one sequence ``a`` [T, D] (already
    normed) under the block-causal mask; before ``W_o``'s residual add."""
    import jax
    import jax.numpy as jnp

    T = a.shape[0]
    H, KV, hd = dm["H"], dm["KV"], dm["hd"]
    q = _mm(a, p["w_q"], hold).reshape(T, H, hd)
    k = _mm(a, p["w_k"], hold).reshape(T, KV, hd)
    v = _mm(a, p["w_v"], hold).reshape(T, KV, hd)
    q = rope_half(rms(q, p["q_norm"], dm["eps"]), pos, dm["theta"])
    k = rope_half(rms(k, p["k_norm"], dm["eps"]), pos, dm["theta"])
    sees = (pos[None, :] // dm["bl"]) <= (pos[:, None] // dm["bl"])
    group = H // KV

    def head(args):
        q_h, k_h, v_h = args                                # [T, hd]
        s = jnp.dot(_hold(q_h, hold), _hold(k_h, hold).T) / math.sqrt(hd)
        prob = jax.nn.softmax(jnp.where(sees, s, -jnp.inf), axis=-1)
        return jnp.dot(_hold(prob, hold), _hold(v_h, hold))

    o = jax.lax.map(head, (q.transpose(1, 0, 2),
                           jnp.repeat(k.transpose(1, 0, 2), group, axis=0),
                           jnp.repeat(v.transpose(1, 0, 2), group, axis=0)))
    return _mm(o.transpose(1, 0, 2).reshape(T, H * hd), p["w_o"], hold)


def route(p, u, dm, hold=None):
    """Gates [T, experts]: ``p_i / sum_S p_j`` for the picked, 0 elsewhere."""
    import jax
    import jax.numpy as jnp

    prob = jax.nn.softmax(_mm(u, p["w_r"], hold), axis=-1)
    _, idx = jax.lax.top_k(prob, dm["top_k"])
    picked = jnp.zeros_like(prob).at[
        jnp.arange(u.shape[0])[:, None], idx].set(1.0)
    gates = prob * picked
    return gates / gates.sum(axis=-1, keepdims=True)


def experts(p, u, dm, hold=None):
    """Every token through its picked experts: each expert is computed for
    every token and weighed by its gate, 0 for the tokens that did not pick
    it."""
    import jax
    import jax.numpy as jnp

    gates = route(p, u, dm, hold)
    n = p["w_g"].shape[0]
    g = math.gcd(n, EXPERT_GROUP)

    def group(acc, args):
        w_g, w_u, w_d, gate = args                          # [g, ...]
        for e in range(g):
            y = _mm(jax.nn.silu(_mm(u, w_g[e], hold)) * _mm(u, w_u[e], hold),
                    w_d[e], hold)
            acc = acc + gate[e][:, None] * y
        return acc, None

    def grouped(a):
        return a.reshape((n // g, g) + a.shape[1:])

    out, _ = jax.lax.scan(group, jnp.zeros_like(u), (
        grouped(p["w_g"]), grouped(p["w_u"]), grouped(p["w_d"]),
        grouped(gates.T)))
    return out


def layer(p, x, pos, dm, hold=None):
    h = x + attention(p["mixer_a"], rms(x, p["norm_a"], dm["eps"]), pos, dm,
                      hold)
    return h + experts(p["moe"], rms(h, p["norm_ffn_a"], dm["eps"]), dm, hold)


@functools.lru_cache(maxsize=None)
def _jitted(dm_items, hold):
    import jax
    import jax.numpy as jnp

    dm = dict(dm_items)

    def one_layer(p, x, pos):
        with jax.default_matmul_precision("highest"):
            return layer(p, x, pos, dm, hold)

    def embed(table, rows):
        return table[rows].astype(jnp.float32)

    def head(final_norm, table, x, n, last):
        with jax.default_matmul_precision("highest"):
            h = jax.lax.dynamic_slice_in_dim(x, n - last, last)
            return _mm(rms(h, final_norm, dm["eps"]), table.T, hold)

    return (jax.jit(embed), jax.jit(one_layer),
            jax.jit(head, static_argnums=4))


def shapes(reach: int) -> tuple:
    """The padded lengths of a cell whose sequences reach ``reach``
    positions, shortest first: a third, two thirds and the whole of it, in
    whole multiples of :data:`PAD_TO` (at most three shapes where every
    multiple had its own; the real length goes in as an argument, so no
    length compiles anything of its own)."""
    top = -(-reach // PAD_TO)
    return tuple(sorted({-(-top * i // 3) * PAD_TO for i in (1, 2, 3)}))


def padded_length(n: int, reach: int | None = None) -> int:
    """The first of :func:`shapes` that holds ``n`` positions (``reach``:
    None, the sequence's own length)."""
    return next(s for s in shapes(max(n, reach or n)) if s >= n)


def forward(weights, ids, dm, hold=None, last=None, reach=None):
    """Logits [last, V] at the last ``last`` positions (default: one block)
    of the full forward over ``ids`` (rows of the item table; whole blocks).
    ``reach``: :func:`padded_length`'s."""
    import jax.numpy as jnp

    embed, one_layer, head = _jitted(tuple(sorted(dm.items())), hold)
    n, last = len(ids), last or dm["bl"]
    if n % dm["bl"]:
        raise ValueError("a forward runs over whole blocks")
    padded = padded_length(n, reach)
    rows = np.zeros(padded, np.int32)
    rows[:n] = np.asarray(ids, np.int32)
    x = embed(weights["embed"], jnp.asarray(rows))
    pos = jnp.arange(padded, dtype=jnp.int32)
    for p in weights["layers"]:
        x = one_layer(p, x, pos)
    logits = head(weights["final_norm"], weights["head"], x, jnp.int32(n),
                  int(last))
    return np.asarray(logits, np.float32)


# -- the generation loop ---------------------------------------------------------

def unmask_at_least(gen: dict, s: int) -> int:
    """``n_s`` of a block's ``s``-th denoise forward."""
    base, more = divmod(int(gen["block_len"]), int(gen["denoising_steps"]))
    s = min(s, int(gen["denoising_steps"]) - 1)
    return base + (1 if s < more else 0)


def decide(logits, masked, gen: dict, s: int):
    """What one denoise forward unmasks. ``logits`` [B, V] at the block's
    positions, ``masked``: the positions (0..B-1) still masked. ``(best [B],
    score [B], confidence [B], the positions unmasked)``."""
    logits = np.array(logits, np.float64)
    logits[:, int(gen["mask_row"])] = -np.inf
    best = logits.argmax(axis=-1)
    score = logits.max(axis=-1)
    conf = 1.0 / np.exp(logits - score[:, None]).sum(axis=-1)
    order = sorted(masked, key=lambda i: (-conf[i], i))
    chosen = set(order[:unmask_at_least(gen, s)])
    if gen["rule"] == "low_confidence_dynamic":
        chosen |= {i for i in masked if conf[i] > float(gen["threshold"])}
    elif gen["rule"] != "low_confidence_static":
        raise ValueError(f"unknown unmasking rule {gen['rule']!r}")
    return best, score, conf, sorted(chosen)


def block_diffusion_generate(weights, history, generate: int, gen: dict,
                             dm: dict, hold=None):
    """``(items, forwards)``: the generated item at every position from
    ``len(history)`` to the end of the last block, as ``(row, score,
    confidence, step)`` — ``step`` the index of the forward that unmasked it
    —, and every forward in order, ``{"block", "kind", "unmasked"}``."""
    B, mask = int(gen["block_len"]), int(gen["mask_row"])
    H = len(history)
    n_blocks = -(-(H + generate) // B)
    x = list(history) + [mask] * (n_blocks * B - H)
    found, forwards = {}, []
    for b in range(H // B, n_blocks):
        lo, s = b * B, 0
        while True:
            masked = [i for i in range(B) if x[lo + i] == mask]
            if not masked:
                forwards.append({"block": b, "kind": "commit",
                                 "unmasked": []})
                break
            logits = forward(weights, x[:lo + B], dm, hold)
            best, score, conf, chosen = decide(logits, masked, gen, s)
            for i in chosen:
                x[lo + i] = int(best[i])
                found[lo + i] = (int(best[i]), float(score[i]),
                                 float(conf[i]), len(forwards))
            forwards.append({"block": b, "kind": "denoise",
                             "unmasked": [lo + i for i in chosen]})
            s += 1
    return [found[p] for p in range(H, n_blocks * B)], forwards


# -- an answer of the program against the reference ---------------------------------

def rebuild(H: int, generate: int, items, gen: dict):
    """The forwards an answer's ``step``s describe, or ``ValueError`` where
    they describe none: ``items`` = ``(row, score, confidence, step)`` for
    every position from ``H`` to the end of the last block. Exact: every
    block's forwards follow each other and the one before; each denoise
    forward unmasks what the rule's count allows; every block ends in a
    commit; no item is the mask row."""
    B, mask = int(gen["block_len"]), int(gen["mask_row"])
    n_blocks = -(-(H + generate) // B)
    if len(items) != n_blocks * B - H:
        raise ValueError(f"{len(items)} items for {n_blocks * B - H} positions")
    if any(int(row) == mask for row, *_ in items):
        raise ValueError("the mask row was given as an answer")
    step = {H + j: int(it[3]) for j, it in enumerate(items)}
    forwards = []
    for b in range(H // B, n_blocks):
        masked = [p for p in range(b * B, (b + 1) * B) if p >= H]
        s = 0
        while masked:
            now = [p for p in masked if step[p] == len(forwards)]
            least = min(unmask_at_least(gen, s), len(masked))
            if len(now) < least or (gen["rule"] == "low_confidence_static"
                                    and len(now) != least):
                raise ValueError(
                    f"forward {len(forwards)} unmasks {len(now)} of block "
                    f"{b}'s positions, the rule says {least}")
            forwards.append({"block": b, "kind": "denoise",
                             "masked": list(masked), "unmasked": now})
            masked = [p for p in masked if p not in now]
            s += 1
        forwards.append({"block": b, "kind": "commit", "masked": [],
                         "unmasked": []})
    return forwards


def state_before(history, items, forward_index: int, block: int, gen: dict):
    """The sequence a forward saw: the history, the items of the blocks
    before, and in its own block the items unmasked by earlier forwards."""
    B, mask = int(gen["block_len"]), int(gen["mask_row"])
    x = list(history)
    for row, _, _, step in items[:(block + 1) * B - len(history)]:
        x.append(int(row) if (len(x) < block * B or step < forward_index)
                 else mask)
    return x


def compare(weights, sample, generate: int, gen: dict, dm: dict, pick,
            reach=None, stop=None) -> dict:
    """``sample``: [(history rows, items)], compared in its order;
    ``pick(n_denoise)`` chooses which of an answer's denoise forwards are
    recomputed. ``reach``: the longest sequence the cell's traffic can make
    (:func:`shapes`; None: each forward's own). ``stop(compared so far)``:
    asked before each answer, true where no further one is to be started (the
    driver's budget). For each chosen forward
    the reference's full forward over the sequence as it stood gives the
    logits at the block's positions; over the positions it unmasked:

    * ``score_err``: |served logit - reference logit of the served item|
      over the reference's logit range at that position;
    * ``confidence_err``: |served - reference confidence of the served
      item| over the reference's;
    * ``rank_gap``: how far the served item's reference logit lies under
      the reference's best at that position, over the range;
    * ``order_gap``: the most confident masked position NOT unmasked against
      the least confident one unmasked, in reference confidence of what was
      served there (positive part, over the latter).
    """
    out = {"score_err": 0.0, "confidence_err": 0.0, "rank_gap": 0.0,
           "order_gap": 0.0, "malformed": 0, "compared": 0, "forwards": 0,
           "longest_history": 0, "why_malformed": []}
    B, mask = int(gen["block_len"]), int(gen["mask_row"])
    for history, items in sample:
        if stop is not None and stop(out["compared"] + out["malformed"]):
            break
        H = len(history)
        try:
            forwards = rebuild(H, generate, items, gen)
        except ValueError as e:
            out["malformed"] += 1
            out["why_malformed"].append(str(e))
            continue
        denoise = [f for f, fw in enumerate(forwards)
                   if fw["kind"] == "denoise"]
        for f in sorted({denoise[i] for i in pick(len(denoise))}):
            fw = forwards[f]
            lo = fw["block"] * B
            logits = forward(weights, state_before(history, items, f,
                                                   fw["block"], gen), dm,
                             reach=reach)
            logits = logits.astype(np.float64)
            logits[:, mask] = -np.inf
            finite = np.where(np.isfinite(logits), logits, np.nan)
            span = np.maximum(np.nanmax(finite, -1) - np.nanmin(finite, -1),
                              1e-30)
            top = logits.max(axis=-1)
            lse = top + np.log(np.exp(logits - top[:, None]).sum(axis=-1))

            def ref_conf(p):
                return float(np.exp(logits[p - lo, int(items[p - H][0])]
                                    - lse[p - lo]))

            for p in fw["unmasked"]:
                row, score, conf, _ = items[p - H]
                ref = float(logits[p - lo, int(row)])
                out["score_err"] = max(out["score_err"],
                                       abs(score - ref) / span[p - lo])
                out["rank_gap"] = max(out["rank_gap"],
                                      (top[p - lo] - ref) / span[p - lo])
                out["confidence_err"] = max(
                    out["confidence_err"],
                    abs(conf - ref_conf(p)) / ref_conf(p))
            left = [p for p in fw["masked"] if p not in fw["unmasked"]]
            if left:
                # a position left masked is judged by what THIS forward
                # found there: the reference's own best
                worst = min(ref_conf(p) for p in fw["unmasked"])
                best_left = max(float(np.exp(top[p - lo] - lse[p - lo]))
                                for p in left)
                out["order_gap"] = max(out["order_gap"],
                                       max(0.0, best_left - worst) / worst)
            out["forwards"] += 1
        out["compared"] += 1
        out["longest_history"] = max(out["longest_history"], H)
    for k in ("score_err", "confidence_err", "rank_gap", "order_gap"):
        out[k] = float(out[k])
    return out


def control(bench) -> dict:
    """The control's readings at the cell's own size: this file's generation
    loop with weights and matrix-product inputs rounded to a lower
    precision, put in the program's place for a seeded handful of the
    cell's own short histories, then held against the reference proper."""
    builder = bench.load_module("models", bench.config["engine"])
    weights = builder.make_weights(bench)
    dm, gen = dims_of(bench.config), bench.config["generation"]
    generate = int(bench.traffic["generate"])
    histories = builder.control_histories(bench)
    out = {}
    for name in ("bfloat16", "float8_e4m3fn"):
        sample = [(h, block_diffusion_generate(
            weights, h, generate, gen, dm, FORMATS[name])[0])
            for h in histories]
        out[name] = compare(weights, sample, generate, gen, dm,
                            lambda n: {0, n - 1})
    return out
