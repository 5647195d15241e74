"""What the sequence engine's two serve programs NEED, from the
configuration's shapes and the engine's own counters, for their roofline
shares (``kernel_counts.least_seconds`` / ``roofline_pct`` do the rest).

Needed work only: a chunk's padding to 512 positions, an extension's padding
to 8 positions and 8 rows, expert tiles' padding rows, keys expanded again
for every later chunk, masked score blocks and the gather/scatter around the
experts are all on the measured side alone.

Parameters per double-layer at the published widths (D 6144, 64 heads of
128 + 64 / 128, ranks 1536 / 512, FFN 12288, experts 2048, router 768):
one MLA 6144*1536 + 1536*64*192 + 6144*576 + 512*64*256 + 8192*6144
= 90,570,752; one dense FFN 3*6144*12288 = 226,492,416; router 4,718,592;
one expert 3*6144*2048 = 37,748,736.
"""

from __future__ import annotations


def _dims(cfg: dict) -> dict:
    return {k: int(cfg[c]) for k, c in (
        ("D", "hidden_size"), ("H", "num_attention_heads"),
        ("dn", "qk_nope_head_dim"), ("dr", "qk_rope_head_dim"),
        ("dv", "v_head_dim"), ("rq", "q_lora_rank"), ("rkv", "kv_lora_rank"),
        ("F", "ffn_hidden_size"), ("E", "expert_ffn_hidden_size"),
        ("L", "num_layers"), ("V", "vocab_size"), ("k", "moe_topk"),
        ("routed", "n_routed_experts_published"), ("zero", "zero_expert_num"))}


def mla_params(cfg: dict) -> int:
    d = _dims(cfg)
    return (d["D"] * d["rq"] + d["rq"] * d["H"] * (d["dn"] + d["dr"])
            + d["D"] * (d["rkv"] + d["dr"])
            + d["rkv"] * d["H"] * (d["dn"] + d["dv"])
            + d["H"] * d["dv"] * d["D"])


def dense_ffn_params(cfg: dict) -> int:
    d = _dims(cfg)
    return 3 * d["D"] * d["F"]


def router_params(cfg: dict) -> int:
    d = _dims(cfg)
    return d["D"] * (d["routed"] + d["zero"])


def expert_params(cfg: dict) -> int:
    d = _dims(cfg)
    return 3 * d["D"] * d["E"]


def nonexpert_params(cfg: dict) -> int:
    """Every matrix a token passes whatever its routing, all layers."""
    return _dims(cfg)["L"] * (2 * mla_params(cfg) + 2 * dense_ffn_params(cfg)
                              + router_params(cfg))


def attention_flops(cfg: dict, offset: int, tokens: int) -> float:
    """Causal attention of ``tokens`` new positions from ``offset`` on, in
    the expanded form (192-wide scores, 128-wide values), every MLA block:
    position t attends to t + 1 keys."""
    d = _dims(cfg)
    pairs = tokens * offset + tokens * (tokens + 1) / 2
    per_pair = 2.0 * (d["dn"] + d["dr"]) + 2.0 * d["dv"]
    return pairs * per_pair * d["H"] * 2 * d["L"]


def prefill_flops(cfg: dict, chunks, held_picks: int) -> float:
    """``chunks``: [(offset, real tokens)] of the chunk programs run;
    ``held_picks``: (token, pick) pairs that reached a held expert in them.
    The program's active-parameter basis (two operations a parameter a
    token or pick: ``obs/perfacct.active_param_flops``), plus attention."""
    from predictionio_tpu.obs.perfacct import active_param_flops

    return (active_param_flops(sum(n for _, n in chunks),
                               nonexpert_params(cfg), expert_params(cfg),
                               held_picks)
            + sum(attention_flops(cfg, o, n) for o, n in chunks))


def extend_bytes(cfg: dict, runs: int, experts_touched: int,
                 latent_positions: int, weight_bytes: int = 2,
                 latent_bytes: int = 2) -> float:
    """``runs`` extension programs: the non-expert weights once each, every
    held expert that got a token (``experts_touched``: per layer, summed
    over the runs), and the cached latents their rows' attention read
    (``latent_positions``: summed history lengths; every MLA block)."""
    d = _dims(cfg)
    return (float(runs) * nonexpert_params(cfg) * weight_bytes
            + float(experts_touched) * expert_params(cfg) * weight_bytes
            + float(latent_positions) * (d["rkv"] + d["dr"]) * latent_bytes
            * 2 * d["L"])


def head_bytes(cfg: dict) -> float:
    """One search of the head (``topk_dot`` over the output embedding): the
    float32 table of ``vocab_size`` x ``hidden_size`` read once. The hidden
    states, at most 8 rows, and the [B, k] result are noise beside it."""
    d = _dims(cfg)
    return float(d["V"]) * d["D"] * 4.0


def head_flops(cfg: dict, rows: int = 1) -> float:
    """The score product of one search over ``rows`` hidden states."""
    d = _dims(cfg)
    return 2.0 * rows * d["V"] * d["D"]


def delta(ctx: dict, key: str, window: bool = False):
    """A counter's growth over the traced stretch — or, for a number that
    needs no trace beside it, over the whole measured WINDOW of the traced
    run (``window_stats0`` / ``window_stats1``, where the driver gives them:
    twenty seconds of sessions are steadier than the stretch's three) — or
    None."""
    s0, s1 = ctx.get("stats0"), ctx.get("stats1")
    if window and ctx.get("window_stats1"):
        s0, s1 = ctx.get("window_stats0"), ctx.get("window_stats1")
    if not s0 or not s1 or key not in s0 or key not in s1:
        return None
    return s1[key] - s0[key]


def program_busy_ns(spans_lib, trace, needle: str) -> float:
    """Device-busy nanoseconds of the compiled programs whose module name
    holds ``needle`` (``prefill_fn`` / ``extend_fn``)."""
    return spans_lib.busy_ns([o for o in spans_lib.all_ops(trace)
                              if needle in o.module])


def scope_share_pct(ctx: dict, parts) -> float:
    """Self time of the device operations traced under ``seq.layer<i>.<part>``
    for a ``part`` starting with one of ``parts``, as a share of the device's
    busy time over the traced stretch, in %."""
    bench, traced = ctx["bench"], ctx.get("traced")
    spans = bench.lib("program_spans")
    trace = spans.trace_of(ctx)
    if trace is None or not traced or not traced.get("busy_s"):
        return None

    def wanted(op):
        scope = op.scope or ""
        if not scope.startswith("seq.layer"):
            return False
        return scope.split(".", 2)[-1].startswith(tuple(parts))

    if not any(wanted(o) for o in spans.all_ops(trace)):
        return None
    ns = spans.self_ns_of_ops(trace, wanted)
    return 100.0 * ns / (traced["busy_s"] * 1e9 * max(1, len(trace.ops)))
