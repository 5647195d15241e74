"""What the block-diffusion engine's block program NEEDS, from the
configuration's shapes and the engine's own counters, for its roofline share
(``kernel_counts.least_seconds`` / ``roofline_pct`` do the rest), and the
head's share of the device's busy time.

Needed work only: a block forward reads, once, the attention and router
weights and the norms of every layer, the matrices of every expert that got a
token, the head's table, and the cached keys and values its rows attend over.
The batch's padding rows, expert tiles' padding rows (a tile is 64 rows, a
touched expert has two or three), the gather and scatter around the experts
and masked score blocks are all on the measured side alone. A block forward
is bound by bytes: 32 tokens against 8 GB of weights.

Parameters per layer at the published widths (hidden 2048, 32 query heads on 4
key/value heads of 128, 128 experts of 768): attention 2048 * 4096 + 2 * 2048 *
512 + 4096 * 2048 = 18,874,368; norms 2 * 2048 + 2 * 128 = 4,352; router
262,144; one expert 3 * 2048 * 768 = 4,718,592; the layer 623,120,640.
"""

from __future__ import annotations


def _dims(cfg: dict) -> dict:
    return {k: int(cfg[c]) for k, c in (
        ("D", "hidden_size"), ("H", "num_attention_heads"),
        ("KV", "num_key_value_heads"), ("hd", "head_dim"),
        ("E", "moe_intermediate_size"), ("L", "num_hidden_layers"),
        ("V", "vocab_size"), ("experts", "num_experts"))}


def attention_params(cfg: dict) -> int:
    d = _dims(cfg)
    return 2 * d["D"] * d["H"] * d["hd"] + 2 * d["D"] * d["KV"] * d["hd"]


def norm_params(cfg: dict) -> int:
    d = _dims(cfg)
    return 2 * d["D"] + 2 * d["hd"]


def router_params(cfg: dict) -> int:
    d = _dims(cfg)
    return d["D"] * d["experts"]


def expert_params(cfg: dict) -> int:
    d = _dims(cfg)
    return 3 * d["D"] * d["E"]


def layer_params(cfg: dict) -> int:
    return (attention_params(cfg) + norm_params(cfg) + router_params(cfg)
            + _dims(cfg)["experts"] * expert_params(cfg))


def head_params(cfg: dict) -> int:
    d = _dims(cfg)
    return d["V"] * d["D"]


def kv_bytes_per_position(cfg: dict, kv_bytes: int = 2) -> int:
    """Keys and values of one cached position, every layer."""
    d = _dims(cfg)
    return d["L"] * 2 * d["KV"] * d["hd"] * kv_bytes


def block_bytes(cfg: dict, runs: int, experts_touched: int,
                kv_positions: int, weight_bytes: int = 2) -> float:
    """``runs`` block forwards: every layer's non-expert weights and the
    head once each, every expert that got a token (``experts_touched``: per
    layer, summed over the runs), and the cached keys and values the rows'
    attention read (``kv_positions``: each row's reach, summed)."""
    d = _dims(cfg)
    once = d["L"] * (attention_params(cfg) + norm_params(cfg)
                     + router_params(cfg)) + head_params(cfg) + d["D"]
    return (float(runs) * once * weight_bytes
            + float(experts_touched) * expert_params(cfg) * weight_bytes
            + float(kv_positions) * kv_bytes_per_position(cfg))


def head_share_pct(ctx: dict):
    """Self time of the device operations traced under ``seq.head``, as a
    share of the device's busy time over the traced stretch, in %
    (``seq_counts.scope_share_pct`` reads the scopes of the layers; the head
    is no layer's); None where no such operation ran."""
    bench, traced = ctx["bench"], ctx.get("traced")
    spans = bench.lib("program_spans")
    trace = spans.trace_of(ctx)
    if trace is None or not traced or not traced.get("busy_s"):
        return None

    def in_head(op):
        return op.scope == "seq.head"

    if not any(in_head(o) for o in spans.all_ops(trace)):
        return None
    ns = spans.self_ns_of_ops(trace, in_head)
    return 100.0 * ns / (traced["busy_s"] * 1e9 * max(1, len(trace.ops)))
