"""What the decoder-hybrid-decoder stack's three serve programs NEED, from the
configuration's shapes and the engine's own counters, for their roofline
shares (``kernel_counts.least_seconds`` / ``roofline_pct`` do the rest).

Needed work only, whatever implements it: a chunk's padding to 512 positions,
an extension's padding to 4 positions and 8 rows, the masked part of a walk's
blocks, a span walked to the batch's longest row, the answered row carried
twice through the cross-decoder, the scan's per-position loop overhead and
anything a scan writes out beside ``y`` are all on the measured side alone.
The head is a program of its own (``index/exact.py``): counted apart
(:func:`head_bytes`, :func:`head_flops`), and in neither serve program's need.

Parameters at the published widths (hidden 2,560; Mamba-1: d_inner 5,120,
state 16, dt_rank 160, 4 taps; attention: 40 query heads on 20 key/value
heads of 64; MLP 10,240), matrices only: Mamba-1 2560*10240 + 5120*2560 +
5120*192 + 160*5120 = 41,123,840; attention 2*2560*2560 + 2*2560*1280 =
19,660,800; cross attention 2*2560*2560 = 13,107,200; GMU 2*2560*5120 =
26,214,400; MLP 3*2560*10240 = 78,643,200; embedding 200,064*2,560 =
512,163,840; the model 3,851,059,200.
"""

from __future__ import annotations

#: float32 operations a second of the chip's vector unit, as this file takes
#: it (``peaks.json`` has no such peak): the clock that 197e12 bf16 FLOP/s
#: over 4 MXUs of 128 x 128 multiply-adds implies (197e12 / (4 * 16384 * 2) =
#: 1.5e9 /s), times 8 x 128 lanes, times 4 issue slots, times 2 (a fused
#: multiply-add): an UPPER estimate, so a share of it is a lower one
F32_VECTOR_OPS_PER_S = 1.5e9 * 8 * 128 * 4 * 2


def _dims(cfg: dict) -> dict:
    s = cfg["assumed_sizes"]
    d = {"D": int(cfg["hidden_size"]), "F": int(cfg["intermediate_size"]),
         "H": int(cfg["num_attention_heads"]),
         "KV": int(cfg["num_key_value_heads"]),
         "L": int(cfg["num_hidden_layers"]), "V": int(cfg["vocab_size"]),
         "window": int(cfg["sliding_window"]), "N": int(s["d_state"]),
         "R": int(s["dt_rank"]), "K": int(s["d_conv"])}
    d["hd"] = d["D"] // d["H"]
    d["inner"] = int(s["expand"]) * d["D"]
    half = d["L"] // 2
    # Mamba-1 and window layers by turns below the middle, the memory layer
    # and the full layer there, GMUs and cross layers by turns behind them
    d["n_mamba"] = (half + 1) // 2 + 1
    d["n_window"] = half // 2
    d["n_full"] = 1
    d["n_gmu"] = (d["L"] - half - 2 + 1) // 2
    d["n_cross"] = (d["L"] - half - 2) // 2
    return d


def mamba_params(cfg: dict) -> int:
    d = _dims(cfg)
    return (d["D"] * 2 * d["inner"] + d["inner"] * d["D"]
            + d["inner"] * (d["R"] + 2 * d["N"]) + d["R"] * d["inner"])


def attention_params(cfg: dict) -> int:
    d = _dims(cfg)
    return 2 * d["D"] * d["H"] * d["hd"] + 2 * d["D"] * d["KV"] * d["hd"]


def cross_params(cfg: dict) -> int:
    d = _dims(cfg)
    return 2 * d["D"] * d["H"] * d["hd"]


def gmu_params(cfg: dict) -> int:
    d = _dims(cfg)
    return 2 * d["D"] * d["inner"]


def mlp_params(cfg: dict) -> int:
    d = _dims(cfg)
    return 3 * d["D"] * d["F"]


def embedding_params(cfg: dict) -> int:
    d = _dims(cfg)
    return d["V"] * d["D"]


def head_bytes(cfg: dict) -> float:
    """One search of the head (``topk_dot`` over the tied embedding): the
    float32 table of ``vocab_size`` x ``hidden_size`` read once (2.05 GB).
    The hidden states, at most 8 rows, and the ``[B, k]`` result are noise
    beside it; the table's padding to whole tiles is on the measured side."""
    return 4.0 * embedding_params(cfg)


def head_flops(cfg: dict, rows: int = 1) -> float:
    """The score product of one search over ``rows`` hidden states."""
    return 2.0 * rows * embedding_params(cfg)


def self_decoder_params(cfg: dict) -> int:
    """The layers that keep something per session (0-17), MLPs and all."""
    d = _dims(cfg)
    return (d["n_mamba"] * mamba_params(cfg)
            + (d["n_window"] + d["n_full"]) * attention_params(cfg)
            + (d["n_mamba"] + d["n_window"] + d["n_full"]) * mlp_params(cfg))


def cross_decoder_params(cfg: dict) -> int:
    """The layers behind them (18-31), which run for the answered rows."""
    d = _dims(cfg)
    return (d["n_gmu"] * gmu_params(cfg) + d["n_cross"] * cross_params(cfg)
            + (d["n_gmu"] + d["n_cross"]) * mlp_params(cfg))


def model_params(cfg: dict) -> int:
    return (self_decoder_params(cfg) + cross_decoder_params(cfg)
            + embedding_params(cfg))


def state_bytes_per_row(cfg: dict, conv_bytes: int = 2) -> int:
    """One session's recurrent state, every Mamba-1 layer: ``S`` in float32
    and the carried rows of the convolution."""
    d = _dims(cfg)
    return d["n_mamba"] * (d["inner"] * d["N"] * 4
                           + (d["K"] - 1) * d["inner"] * conv_bytes)


def kv_bytes_per_position(cfg: dict, kv_bytes: int = 2) -> int:
    """Keys and values of one cached position of ONE layer (a ring's row, a
    span's position): 5,120 B."""
    d = _dims(cfg)
    return 2 * d["KV"] * d["hd"] * kv_bytes


def span_readers(cfg: dict) -> int:
    """The layers that walk the one span for an answered row: the full layer
    that writes it and every cross layer."""
    d = _dims(cfg)
    return d["n_full"] + d["n_cross"]


def pair_flops(cfg: dict) -> float:
    """Differential attention of one (query, key) pair of positions in one
    layer: every query head's score (2 * head_dim) and its probability times
    the pair's two values (2 * 2 * head_dim)."""
    d = _dims(cfg)
    return 6.0 * d["hd"] * d["H"]


def causal_pairs(offset: int, tokens: int, window: int = 0) -> float:
    """(query, key) pairs of ``tokens`` new positions from ``offset`` on:
    position t sees t + 1 keys, under a window at most ``window``."""
    if not window:
        return tokens * offset + tokens * (tokens + 1) / 2.0
    return float(sum(min(offset + i + 1, window) for i in range(tokens)))


def scan_flops(cfg: dict, tokens: int) -> float:
    """The recurrence itself, one Mamba-1 layer: a position's decay
    (``delta * A`` and its exponential: 2), the update of every value of
    ``S`` (``delta x B``, the decay's product and the sum: 3) and the
    read-out through ``C`` (2)."""
    d = _dims(cfg)
    return 7.0 * tokens * d["inner"] * d["N"]


def scan_bytes(cfg: dict, tokens: int) -> float:
    """What a scan that keeps ``S`` on the chip moves, one layer: ``x`` and
    ``delta`` in and ``y`` out (float32 rows of ``d_inner``), ``B`` and ``C``
    in, the state in and out."""
    d = _dims(cfg)
    return (4.0 * tokens * (3 * d["inner"] + 2 * d["N"])
            + 2.0 * d["inner"] * d["N"] * 4)


def scan_least_seconds(cfg: dict, peaks: dict, chunks) -> float:
    """The least time the chunk programs' scans could take (``chunks``:
    [(offset, real tokens)]; every Mamba-1 layer): the larger of their bytes
    at the peak memory rate and their operations at
    :data:`F32_VECTOR_OPS_PER_S`."""
    d = _dims(cfg)
    tokens = sum(n for _, n in chunks)
    nbytes = sum(scan_bytes(cfg, n) for _, n in chunks)
    return d["n_mamba"] * max(
        scan_flops(cfg, tokens) / F32_VECTOR_OPS_PER_S,
        nbytes / float(peaks["hbm_bytes_per_s"]))


def prefill_flops(cfg: dict, chunks, cross_reaches) -> float:
    """``chunks``: [(offset, real tokens)] of the chunk programs run;
    ``cross_reaches``: the position + 1 of each row they carried through the
    cross-decoder. Two operations a parameter a token over the layers a token
    passes, the attention of each kind at each chunk's offset, and the
    recurrence (on the vector unit: counted, though the bf16 peak is not its
    roof)."""
    d = _dims(cfg)
    tokens = sum(n for _, n in chunks)
    pairs = sum(d["n_window"] * causal_pairs(o, n, d["window"])
                + d["n_full"] * causal_pairs(o, n) for o, n in chunks)
    return (2.0 * tokens * self_decoder_params(cfg)
            + 2.0 * len(cross_reaches) * cross_decoder_params(cfg)
            + pair_flops(cfg) * (pairs + d["n_cross"] * sum(cross_reaches))
            + d["n_mamba"] * scan_flops(cfg, tokens))


def prefill_bytes(cfg: dict, chunks, cross_reaches,
                  weight_bytes: int = 2) -> float:
    """The chunk programs' needed bytes: the self-decoder's weights once a
    chunk, the session's states in and out, the full layer's span up to each
    chunk's end and the rings' windows; for a carried row the cross-decoder's
    weights and the span once a cross layer."""
    d = _dims(cfg)
    row = kv_bytes_per_position(cfg)
    return (float(len(chunks)) * (self_decoder_params(cfg) * weight_bytes
                                  + 2 * state_bytes_per_row(cfg))
            + sum((o + n) * d["n_full"] * row
                  + min(o + n, d["window"] + n) * d["n_window"] * row
                  for o, n in chunks)
            + float(len(cross_reaches)) * cross_decoder_params(cfg)
            * weight_bytes
            + float(sum(cross_reaches)) * d["n_cross"] * row)


def extend_bytes(cfg: dict, runs: int, state_rows: int, kv_positions: int,
                 window_positions: int, weight_bytes: int = 2) -> float:
    """``runs`` extension programs: every layer's weights once each, each
    row's recurrent states read once and written once (``state_rows``), the
    span's positions in each row's OWN reach once a reader
    (``kv_positions``: summed history lengths), and the rings' rows the rows'
    windows hold (``window_positions``), every window layer."""
    d = _dims(cfg)
    row = kv_bytes_per_position(cfg)
    return (float(runs) * (self_decoder_params(cfg)
                           + cross_decoder_params(cfg)) * weight_bytes
            + float(state_rows) * 2 * state_bytes_per_row(cfg)
            + float(kv_positions) * span_readers(cfg) * row
            + float(window_positions) * d["n_window"] * row)


def span_walk_bytes(cfg: dict, kv_positions: int) -> float:
    """The span's bytes an extension batch's rows NEED: each row's own reach,
    once a reader."""
    return float(kv_positions) * span_readers(cfg) * kv_bytes_per_position(
        cfg)


def chunks_of(spans_lib, trace) -> list:
    """``[(offset, tokens, last)]`` of the traced ``pio:seq.prefill_chunk``
    spans (``last``: 1 where the chunk ended its history; None from a program
    that does not say)."""
    return [(int(s.attrs.get("offset", 0)), int(s.attrs.get("tokens", 0)),
             s.attrs.get("last"))
            for s in spans_lib.named(trace, "pio:seq.prefill_chunk")]
