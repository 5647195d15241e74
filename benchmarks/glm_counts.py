"""What GLM-5's two serve programs, its index's scorer and its sparse
attention NEED, from the configuration's shapes and the engine's own counters,
for their roofline shares (``kernel_counts.least_seconds`` / ``roofline_pct``
do the rest).

Needed work only. A chunk's padding to 512 positions, an extension's padding
to 4 positions and 4 rows, the masked part of a score block, a selection's
passes over the scores, the latents a chunk's masked walk expands and does
not attend, a cached latent's padding to 640 lanes: all on the measured side
alone. Attention is counted at what each row SELECTED (``min(reach,
index_topk)`` positions, the expanded form's operations a pair, no
expansion): a lower bound of either form the program may take. Two things are
counted as the programs do them, and the functions say so: the index's scores
as SCANNED (``index_score_flops``: whole blocks of index keys, a chunk's 512
rows or an extension's 4 against each), and the extension's latents as
GATHERED (``extend_latents_gathered``: each new position's own selected
ones). The head is a program of its own (``index/exact.py``) and is not
counted here.

Parameters at the published widths, matrices only (hidden 6144, 64 heads of
192 + 64 / 256, ranks 2048 / 512, 32 index heads of 128, dense FFN 12288,
experts 2048, router 256): MLA 6144*2048 + 2048*64*256 + 6144*576 +
512*64*448 + 16384*6144 = 165,019,648; indexer 2048*4096 + 6144*128 + 6144*32
= 9,371,648; one expert (and the shared one) 3*6144*2048 = 37,748,736; router
1,572,864; an expert layer outside its routed experts 213,712,896; a dense
layer 165,019,648 + 9,371,648 + 3*6144*12288 = 400,883,712.
"""

from __future__ import annotations


def _dims(cfg: dict) -> dict:
    d = {k: int(cfg[c]) for k, c in (
        ("D", "hidden_size"), ("H", "num_attention_heads"),
        ("dn", "qk_nope_head_dim"), ("dr", "qk_rope_head_dim"),
        ("dv", "v_head_dim"), ("rq", "q_lora_rank"), ("rkv", "kv_lora_rank"),
        ("Hi", "index_n_heads"), ("di", "index_head_dim"),
        ("topk", "index_topk"),
        ("F", "intermediate_size"), ("E", "moe_intermediate_size"),
        ("L", "num_hidden_layers"), ("dense", "first_k_dense_replace_held"),
        ("shared", "n_shared_experts"), ("V", "vocab_size"),
        ("router", "n_routed_experts_published"))}
    d["chunk"] = int(cfg["serve"]["chunk"])
    d["extend_len"] = int(cfg["serve"]["extend_len"])
    return d


def mla_params(cfg: dict) -> int:
    d = _dims(cfg)
    return (d["D"] * d["rq"] + d["rq"] * d["H"] * (d["dn"] + d["dr"])
            + d["D"] * (d["rkv"] + d["dr"])
            + d["rkv"] * d["H"] * (d["dn"] + d["dv"])
            + d["H"] * d["dv"] * d["D"])


def indexer_params(cfg: dict) -> int:
    d = _dims(cfg)
    return d["rq"] * d["Hi"] * d["di"] + d["D"] * d["di"] + d["D"] * d["Hi"]


def expert_params(cfg: dict) -> int:
    d = _dims(cfg)
    return 3 * d["D"] * d["E"]


def router_params(cfg: dict) -> int:
    d = _dims(cfg)
    return d["D"] * d["router"]


def expert_layer_params(cfg: dict) -> int:
    """An expert layer outside its routed experts: attention, the indexer,
    the shared expert(s), the router."""
    return (mla_params(cfg) + indexer_params(cfg)
            + _dims(cfg)["shared"] * expert_params(cfg) + router_params(cfg))


def dense_layer_params(cfg: dict) -> int:
    d = _dims(cfg)
    return mla_params(cfg) + indexer_params(cfg) + 3 * d["D"] * d["F"]


def expert_layers(cfg: dict) -> int:
    d = _dims(cfg)
    return d["L"] - d["dense"]


def nonexpert_params(cfg: dict) -> int:
    """Every matrix a token passes whatever its routing, all layers here."""
    d = _dims(cfg)
    return (d["dense"] * dense_layer_params(cfg)
            + expert_layers(cfg) * expert_layer_params(cfg))


def latent_bytes_per_position(cfg: dict, value_bytes: int = 2) -> int:
    """One cached position's latent (unpadded), every layer."""
    d = _dims(cfg)
    return d["L"] * (d["rkv"] + d["dr"]) * value_bytes


def index_key_bytes_per_position(cfg: dict, value_bytes: int = 2) -> int:
    """One cached position's index key, every layer."""
    d = _dims(cfg)
    return d["L"] * d["di"] * value_bytes


def selected_pairs(cfg: dict, offset: int, tokens: int) -> int:
    """(query row, attended position) pairs of ``tokens`` new positions from
    ``offset`` on: position t attends ``min(t + 1, index_topk)``."""
    k = _dims(cfg)["topk"]
    full = max(0, min(offset + tokens, k) - offset)     # rows that keep all
    return (full * offset + full * (full + 1) // 2
            + (tokens - full) * k)


def attention_flops(cfg: dict, offset: int, tokens: int) -> float:
    """The selected pairs' operations in the expanded form (256-wide scores,
    256-wide values, every head), every layer; no expansion is counted."""
    d = _dims(cfg)
    per_pair = 2.0 * (d["dn"] + d["dr"]) + 2.0 * d["dv"]
    return selected_pairs(cfg, offset, tokens) * per_pair * d["H"] * d["L"]


def index_score_flops(cfg: dict, prefill_blocks: int,
                      extend_blocks: int) -> float:
    """The index's score products AS SCANNED: ``prefill_blocks`` blocks of
    index keys each against a chunk's 512 rows, ``extend_blocks`` each against
    one session's ``extend_len`` rows (the engine's ``*_index_blocks``: per
    layer, summed), ``index_n_heads`` heads of ``index_head_dim``."""
    d = _dims(cfg)
    per_block = 2.0 * d["chunk"] * d["Hi"] * d["di"]    # one row, one block
    return per_block * (prefill_blocks * d["chunk"]
                        + extend_blocks * d["extend_len"])


def index_projection_flops(cfg: dict, tokens: int) -> float:
    """The index's three projections of ``tokens`` positions, every layer."""
    return 2.0 * tokens * indexer_params(cfg) * _dims(cfg)["L"]


def prefill_flops(cfg: dict, chunks, held_picks: int) -> float:
    """``chunks``: [(offset, real tokens)] of the chunk programs run;
    ``held_picks``: (token, pick) pairs that reached a held expert in them.
    The program's active-parameter basis (two operations a parameter a
    token or pick: ``obs/perfacct.active_param_flops``; the indexer's
    projections are among the parameters), every row that reaches past
    ``index_topk`` scored against all it reaches, and attention over what
    each row selected."""
    from predictionio_tpu.obs.perfacct import active_param_flops

    d = _dims(cfg)
    scored = sum((o + n) * (o + n + 1) // 2
                 - max(o, d["topk"]) * (max(o, d["topk"]) + 1) // 2
                 for o, n in chunks if o + n > d["topk"])
    return (active_param_flops(sum(n for _, n in chunks),
                               nonexpert_params(cfg), expert_params(cfg),
                               held_picks)
            + scored * 2.0 * d["Hi"] * d["di"] * d["L"]
            + sum(attention_flops(cfg, o, n) for o, n in chunks))


def prefill_bytes(cfg: dict, chunks, experts_touched: int,
                  weight_bytes: int = 2) -> float:
    """The chunk programs' needed bytes: the non-expert weights once a chunk,
    every held expert that got a token, and, up to each chunk's end, the
    index keys (where its rows reach past ``index_topk``) and the latents
    (every one of which some row of the chunk may select)."""
    d = _dims(cfg)
    return (float(len(chunks)) * nonexpert_params(cfg) * weight_bytes
            + float(experts_touched) * expert_params(cfg) * weight_bytes
            + sum(o + n for o, n in chunks) * latent_bytes_per_position(cfg)
            + sum(o + n for o, n in chunks if o + n > d["topk"])
            * index_key_bytes_per_position(cfg))


def gathered_bytes(cfg: dict, latents_gathered: int) -> float:
    """The latents an extension's rows GATHERED (``extend_latents_gathered``:
    a layer's, summed over the new positions), every layer."""
    return float(latents_gathered) * latent_bytes_per_position(cfg)


def extend_bytes(cfg: dict, runs: int, experts_touched: int,
                 index_blocks: int, latents_gathered: int,
                 weight_bytes: int = 2) -> float:
    """``runs`` extension programs: the non-expert weights once each, every
    held expert that got a token, the index keys AS SCANNED
    (``extend_index_blocks``: blocks of ``chunk`` positions, per layer and
    real session, summed) and the latents AS GATHERED."""
    d = _dims(cfg)
    return (float(runs) * nonexpert_params(cfg) * weight_bytes
            + float(experts_touched) * expert_params(cfg) * weight_bytes
            + float(index_blocks) * d["chunk"] * d["di"] * 2
            + gathered_bytes(cfg, latents_gathered))


def extend_flops(cfg: dict, tokens: int, held_picks: int, index_blocks: int,
                 latents_gathered: int) -> float:
    """The same runs' operations: the active-parameter basis, the scores as
    scanned, and the absorbed attention over the gathered latents (scores
    over the latent's 576, values over its 512, every head and layer)."""
    from predictionio_tpu.obs.perfacct import active_param_flops

    d = _dims(cfg)
    per_pair = 2.0 * (d["rkv"] + d["dr"]) + 2.0 * d["rkv"]
    return (active_param_flops(tokens, nonexpert_params(cfg),
                               expert_params(cfg), held_picks)
            + index_score_flops(cfg, 0, index_blocks)
            + float(latents_gathered) * per_pair * d["H"] * d["L"])


def scope_self_ns(spans_lib, trace, suffix: str, module: str = "") -> float:
    """Summed self time of the device operations traced under a scope that
    ends in ``suffix`` (``.mla_a.index``), in the compiled programs whose
    name holds ``module`` (every program: ""). An operation is known by its
    program AND its instruction: two programs number their fusions alike."""
    self_times = spans_lib._sibling("trace_reduce").self_times
    total = 0.0
    for plane in trace.ops.values():
        names = [f"{o.module}/{o.instr}" for o in plane]
        keep = {name: (o.scope or "").endswith(suffix) and module in o.module
                for name, o in zip(names, plane)}
        by_name = self_times([(name, o.start, o.end)
                              for name, o in zip(names, plane)])
        total += sum(ns for name, ns in by_name.items() if keep[name])
    return total
