"""What MiMo-V2.5's two serve programs and its window layers' walk NEED, from
the configuration's shapes and the engine's own counters, for their roofline
shares (``kernel_counts.least_seconds`` / ``roofline_pct`` do the rest), by
layer kind and whatever implements them.

Needed work only. A chunk's padding to 512 positions, an extension's padding
to 4 positions and 8 rows, the masked part of a score block (a chunk's walk
takes up to six blocks of 128 keys against all 512 rows where each row needs
128 keys; an extension's three where it needs 131 positions), a ring's rows
read back after they were written, expert tiles' padding rows and the sort
around the experts are all on the measured side alone. The head is a program
of its own (``index/exact.py``) and is not counted here.

Parameters at the published widths, matrices only (hidden 4096, 64 query
heads of 192, values of 128, 8 / 4 key/value heads, dense FFN 16,384, experts
2,048, router 256): window attention 4096*12288 + 4096*1536 + 4096*1024 +
8192*4096 = 94,371,840 (+64 sink logits); full attention 4096*12288 +
4096*768 + 4096*512 + 8192*4096 = 89,128,960; router 1,048,576; one expert
3*4096*2048 = 25,165,824; the dense FFN 3*4096*16384 = 201,326,592.
"""

from __future__ import annotations


def _dims(cfg: dict) -> dict:
    d = {k: int(cfg[c]) for k, c in (
        ("D", "hidden_size"), ("H", "num_attention_heads"),
        ("dk", "head_dim"), ("dv", "v_head_dim"),
        ("K_full", "num_key_value_heads"),
        ("K_window", "swa_num_key_value_heads"), ("window", "sliding_window"),
        ("F", "intermediate_size"), ("E", "moe_intermediate_size"),
        ("V", "vocab_size"), ("router", "n_routed_experts_published"))}
    pattern = [int(v) for v in cfg["hybrid_layer_pattern_held"]]
    d["n_window"] = sum(pattern)
    d["n_full"] = len(pattern) - d["n_window"]
    d["n_expert"] = sum(int(v) for v in cfg["moe_layer_freq_held"])
    d["n_dense"] = len(pattern) - d["n_expert"]
    return d


def attention_params(cfg: dict, window: bool) -> int:
    d = _dims(cfg)
    K = d["K_window"] if window else d["K_full"]
    return (d["D"] * d["H"] * d["dk"] + d["D"] * K * d["dk"]
            + d["D"] * K * d["dv"] + d["H"] * d["dv"] * d["D"])


def router_params(cfg: dict) -> int:
    d = _dims(cfg)
    return d["D"] * d["router"]


def expert_params(cfg: dict) -> int:
    d = _dims(cfg)
    return 3 * d["D"] * d["E"]


def dense_ffn_params(cfg: dict) -> int:
    d = _dims(cfg)
    return 3 * d["D"] * d["F"]


def dense_layer_params(cfg: dict) -> int:
    """The leading dense layer: full attention over the dense FFN."""
    return attention_params(cfg, False) + dense_ffn_params(cfg)


def expert_layer_params(cfg: dict, window: bool, experts: int = 0) -> int:
    """An expert layer of either kind with ``experts`` routed experts (0:
    outside them: attention and router)."""
    return (attention_params(cfg, window) + router_params(cfg)
            + experts * expert_params(cfg))


def model_params(cfg: dict) -> int:
    """The published model, matrices only: the dense layer, every expert
    layer of either kind whole, embedding and untied head."""
    pattern = [int(v) for v in cfg["hybrid_layer_pattern"]]
    freq = [int(v) for v in cfg["moe_layer_freq"]]
    n = int(cfg["n_routed_experts_published"])
    layers = sum(
        expert_layer_params(cfg, bool(w), n) if e
        else attention_params(cfg, bool(w)) + dense_ffn_params(cfg)
        for w, e in zip(pattern, freq))
    return layers + 2 * int(cfg["vocab_size_published"]) * int(
        cfg["hidden_size"])


def nonexpert_params(cfg: dict) -> int:
    """Every matrix a token passes whatever its routing, all layers here."""
    d = _dims(cfg)
    return (d["n_window"] * attention_params(cfg, True)
            + d["n_full"] * attention_params(cfg, False)
            + d["n_expert"] * router_params(cfg)
            + d["n_dense"] * dense_ffn_params(cfg))


def held_params(cfg: dict) -> int:
    d = _dims(cfg)
    return (nonexpert_params(cfg)
            + d["n_expert"] * int(cfg["experts_held"][1]) * expert_params(cfg)
            + 2 * d["V"] * d["D"])


def span_bytes_per_position(cfg: dict, value_bytes: int = 2) -> int:
    """Keys and values of one cached position, every FULL layer."""
    d = _dims(cfg)
    return d["n_full"] * d["K_full"] * (d["dk"] + d["dv"]) * value_bytes


def ring_bytes_per_position(cfg: dict, value_bytes: int = 2) -> int:
    """Keys and values of one cached position, every WINDOW layer."""
    d = _dims(cfg)
    return d["n_window"] * d["K_window"] * (d["dk"] + d["dv"]) * value_bytes


def ring_rows(cfg: dict) -> int:
    """A ring's rows: the window and one chunk, in whole blocks of the
    window (``ops/gqa.ring_len``)."""
    w, chunk = _dims(cfg)["window"], int(cfg["serve"]["chunk"])
    return -(-(w + chunk) // w) * w


def cache_bytes(cfg: dict, value_bytes: int = 2) -> int:
    """Every slot's spans and rings (one scratch slot, one chunk of slack a
    span)."""
    serve = cfg["serve"]
    slots = int(serve["n_slots"]) + 1
    return slots * (
        (int(serve["capacity"]) + int(serve["chunk"]))
        * span_bytes_per_position(cfg, value_bytes)
        + ring_rows(cfg) * ring_bytes_per_position(cfg, value_bytes))


def full_pairs(offset: int, tokens: int) -> float:
    """(query, key) pairs of ``tokens`` new positions from ``offset`` on
    under the causal mask: position t attends t + 1 keys."""
    return tokens * offset + tokens * (tokens + 1) / 2


def window_pairs(cfg: dict, offset: int, tokens: int) -> float:
    """... under the window: position t attends ``min(t + 1, window)``."""
    w = _dims(cfg)["window"]
    short = max(0, min(offset + tokens, w) - offset)    # rows under a window
    return (short * offset + short * (short + 1) / 2 + (tokens - short) * w)


def _pair_flops(cfg: dict) -> float:
    d = _dims(cfg)
    return (2.0 * d["dk"] + 2.0 * d["dv"]) * d["H"]


def window_positions(cfg: dict, offset: int, tokens: int) -> int:
    """The cached positions ``tokens`` new positions from ``offset`` on need
    in a window layer: themselves and the window before the first."""
    return tokens + min(offset, _dims(cfg)["window"] - 1)


def window_attend_flops(cfg: dict, spans) -> float:
    """The window layers' walk over ``spans`` [(offset, real tokens)] (a
    chunk's, or one extension row's): scores and weighted values of the pairs
    the mask leaves, every query head, every window layer."""
    d = _dims(cfg)
    return sum(window_pairs(cfg, o, n) for o, n in spans) * _pair_flops(
        cfg) * d["n_window"]


def window_attend_bytes(cfg: dict, spans) -> float:
    """... and its bytes: the keys and values of the positions each span
    needs, read once, every window layer."""
    return float(sum(window_positions(cfg, o, n) for o, n in spans)
                 ) * ring_bytes_per_position(cfg)


def attention_flops(cfg: dict, offset: int, tokens: int) -> float:
    """Both kinds of layer over ``tokens`` new positions from ``offset``."""
    d = _dims(cfg)
    return (full_pairs(offset, tokens) * d["n_full"]
            + window_pairs(cfg, offset, tokens) * d["n_window"]
            ) * _pair_flops(cfg)


def prefill_flops(cfg: dict, chunks, held_picks: int) -> float:
    """``chunks``: [(offset, real tokens)] of the chunk programs run;
    ``held_picks``: (token, pick) pairs that reached a held expert in them.
    The program's active-parameter basis (two operations a parameter a token
    or pick: ``obs/perfacct.active_param_flops``), causal attention in the
    full layers and the window's in the window layers."""
    from predictionio_tpu.obs.perfacct import active_param_flops

    return (active_param_flops(sum(n for _, n in chunks),
                               nonexpert_params(cfg), expert_params(cfg),
                               held_picks)
            + sum(attention_flops(cfg, o, n) for o, n in chunks))


def prefill_bytes(cfg: dict, chunks, experts_touched: int,
                  weight_bytes: int = 2) -> float:
    """The chunk programs' needed bytes: the non-expert weights once a chunk,
    every held expert that got a token, the full layers' keys and values up
    to each chunk's end and the window layers' over the chunk and the window
    before it."""
    return (float(len(chunks)) * nonexpert_params(cfg) * weight_bytes
            + float(experts_touched) * expert_params(cfg) * weight_bytes
            + sum(o + n for o, n in chunks) * span_bytes_per_position(cfg)
            + window_attend_bytes(cfg, chunks))


def extend_bytes(cfg: dict, runs: int, experts_touched: int,
                 kv_positions: int, window_positions: int,
                 weight_bytes: int = 2) -> float:
    """``runs`` extension programs: the non-expert weights once each, every
    held expert that got a token (``experts_touched``: per layer, summed over
    the runs), the full layers' cached keys and values the rows' attention
    read (``extend_kv_positions``: summed history lengths) and the window
    layers' (``extend_window_positions``: each row's new positions and the
    window before them)."""
    return (float(runs) * nonexpert_params(cfg) * weight_bytes
            + float(experts_touched) * expert_params(cfg) * weight_bytes
            + float(kv_positions) * span_bytes_per_position(cfg)
            + float(window_positions) * ring_bytes_per_position(cfg))

