"""What the hybrid stack's two serve programs NEED, from the configuration's
shapes and the engine's own counters, for their roofline shares
(``kernel_counts.least_seconds`` / ``roofline_pct`` do the rest).

Needed work only: a chunk's padding to 512 positions, an extension's padding
to 4 positions and 16 rows, expert tiles' padding rows, the scan's masked
half of each chunk's products, a state read a second time, the gather and
scatter around the experts are all on the measured side alone. The head is a
program of its own (``index/exact.py``: its time is not the extension
program's, so its bytes are not counted here either).

Parameters at the published widths (hidden 4096; Mamba-2: 128 heads of 64,
state 128, 4 taps; attention: 32 query heads on 8 key/value heads of 128;
experts 768, shared 1536, router 72): one Mamba-2 mixer 4096 * 16768 + 5 *
8448 + 3 * 128 + 8192 + 8192 * 4096 = 102,286,976; attention 2 * 4096 * 4096 +
2 * 4096 * 1024 = 41,943,040; shared expert 3 * 4096 * 1536 = 18,874,368;
router 294,912; a layer's two norms 8,192; one expert 3 * 4096 * 768 =
9,437,184.
"""

from __future__ import annotations


def _dims(cfg: dict) -> dict:
    d = {k: int(cfg[c]) for k, c in (
        ("D", "hidden_size"), ("H", "num_attention_heads"),
        ("KV", "num_key_value_heads"), ("E", "intermediate_size"),
        ("F", "shared_intermediate_size"), ("L", "num_hidden_layers"),
        ("Hm", "mamba_n_heads"), ("P", "mamba_d_head"),
        ("N", "mamba_d_state"), ("K", "mamba_d_conv"),
        ("router", "num_local_experts_published"))}
    d["hd"] = d["D"] // d["H"]
    kinds = list(cfg["layer_types"][:d["L"]])
    d["n_attn"] = kinds.count("attention")
    d["n_mamba"] = d["L"] - d["n_attn"]
    d["inner"] = d["Hm"] * d["P"]
    d["conv_dim"] = d["inner"] + 2 * d["N"]
    return d


def mamba_params(cfg: dict) -> int:
    d = _dims(cfg)
    return (d["D"] * (d["inner"] + d["conv_dim"] + d["Hm"])
            + (d["K"] + 1) * d["conv_dim"] + 3 * d["Hm"] + d["inner"]
            + d["inner"] * d["D"])


def attention_params(cfg: dict) -> int:
    d = _dims(cfg)
    return 2 * d["D"] * d["H"] * d["hd"] + 2 * d["D"] * d["KV"] * d["hd"]


def shared_params(cfg: dict) -> int:
    d = _dims(cfg)
    return 3 * d["D"] * d["F"]


def router_params(cfg: dict) -> int:
    d = _dims(cfg)
    return d["D"] * d["router"]


def expert_params(cfg: dict) -> int:
    d = _dims(cfg)
    return 3 * d["D"] * d["E"]


def nonexpert_params(cfg: dict) -> int:
    """Every matrix a token passes whatever its routing, all layers here."""
    d = _dims(cfg)
    per_layer = shared_params(cfg) + router_params(cfg) + 2 * d["D"]
    return (d["n_mamba"] * mamba_params(cfg)
            + d["n_attn"] * attention_params(cfg) + d["L"] * per_layer)


def state_bytes_per_row(cfg: dict, conv_bytes: int = 2) -> int:
    """One session's recurrent state, every Mamba-2 layer: ``S`` in float32
    and the carried rows of the convolution."""
    d = _dims(cfg)
    return d["n_mamba"] * (d["Hm"] * d["P"] * d["N"] * 4
                           + (d["K"] - 1) * d["conv_dim"] * conv_bytes)


def kv_bytes_per_position(cfg: dict, kv_bytes: int = 2) -> int:
    """Keys and values of one cached position, every attention layer."""
    d = _dims(cfg)
    return d["n_attn"] * 2 * d["KV"] * d["hd"] * kv_bytes


def extend_bytes(cfg: dict, runs: int, experts_touched: int,
                 state_rows: int, kv_positions: int,
                 weight_bytes: int = 2) -> float:
    """``runs`` extension programs: the non-expert weights once each, every
    held expert that got a token (``experts_touched``: per layer, summed
    over the runs), each row's recurrent state read once and written once
    (``state_rows``), and the cached keys and values the rows' attention
    read (``kv_positions``: summed history lengths)."""
    return (float(runs) * nonexpert_params(cfg) * weight_bytes
            + float(experts_touched) * expert_params(cfg) * weight_bytes
            + float(state_rows) * 2 * state_bytes_per_row(cfg)
            + float(kv_positions) * kv_bytes_per_position(cfg))


def attention_flops(cfg: dict, offset: int, tokens: int) -> float:
    """Causal attention of ``tokens`` new positions from ``offset`` on:
    position t attends to t + 1 keys, scores and weighted values of the
    head's width each, every query head of every attention layer."""
    d = _dims(cfg)
    pairs = tokens * offset + tokens * (tokens + 1) / 2
    return pairs * 4.0 * d["hd"] * d["H"] * d["n_attn"]


def scan_flops(cfg: dict, tokens: int) -> float:
    """The recurrence itself: a position multiplies and adds into every
    value of ``S`` and reads it out through ``C``, every Mamba-2 layer."""
    d = _dims(cfg)
    return 4.0 * tokens * d["Hm"] * d["P"] * d["N"] * d["n_mamba"]


def prefill_flops(cfg: dict, chunks, held_picks: int) -> float:
    """``chunks``: [(offset, real tokens)] of the chunk programs run;
    ``held_picks``: (token, pick) pairs that reached a held expert in them.
    The program's active-parameter basis (two operations a parameter a
    token or pick: ``obs/perfacct.active_param_flops``), the scan and the
    attention."""
    from predictionio_tpu.obs.perfacct import active_param_flops

    tokens = sum(n for _, n in chunks)
    return (active_param_flops(tokens, nonexpert_params(cfg),
                               expert_params(cfg), held_picks)
            + scan_flops(cfg, tokens)
            + sum(attention_flops(cfg, o, n) for o, n in chunks))


def prefill_bytes(cfg: dict, chunks, experts_touched: int,
                  weight_bytes: int = 2) -> float:
    """The chunk programs' needed bytes: the non-expert weights once a
    chunk, every held expert that got a token, the session's state in and
    out, the keys and values up to each chunk's end."""
    return (float(len(chunks)) * (nonexpert_params(cfg) * weight_bytes
                                  + 2 * state_bytes_per_row(cfg))
            + float(experts_touched) * expert_params(cfg) * weight_bytes
            + sum(o + n for o, n in chunks) * kv_bytes_per_position(cfg))
