"""What A.X-K1's two serve programs and its grouped expert kernel NEED, from
the configuration's shapes and the engine's own counters, for their roofline
shares (``kernel_counts.least_seconds`` / ``roofline_pct`` do the rest).

Needed work only: a chunk's padding to 512 positions, an extension's padding
to 4 positions and 4 rows, the masked half of a chunk's own score block, the
gather and scatter around the experts, an expert's rows passing under each of
its column chunks are all on the measured side alone. Two things ARE counted
as the programs do them, and the module says so where: a chunk expands the
cached latents it attends once (the cache holds latents, so one call of the
chunk program cannot attend without), and an extension batch reads latents as
far as its longest row reaches (``extend_latent_blocks_attended``;
``extend_blocks_over_own.axk`` says what that costs). The head is a program of
its own (``index/exact.py``) and is not counted here.

Parameters at the published widths, matrices only (hidden 7168, 64 heads of
128 + 64 / 128, ranks 1536 / 512, dense FFN 18432, experts 2048, router 192):
MLA 7168*1536 + 1536*64*192 + 7168*576 + 512*64*256 + 8192*7168 =
101,122,048; one expert (and the shared one) 3*7168*2048 = 44,040,192; router
1,376,256; an expert layer outside its routed experts 146,538,496; the dense
layer 101,122,048 + 3*7168*18432 = 497,483,776.
"""

from __future__ import annotations


def _dims(cfg: dict) -> dict:
    d = {k: int(cfg[c]) for k, c in (
        ("D", "hidden_size"), ("H", "num_attention_heads"),
        ("dn", "qk_nope_head_dim"), ("dr", "qk_rope_head_dim"),
        ("dv", "v_head_dim"), ("rq", "q_lora_rank"), ("rkv", "kv_lora_rank"),
        ("F", "intermediate_size"), ("E", "moe_intermediate_size"),
        ("L", "num_hidden_layers"), ("dense", "first_k_dense_replace"),
        ("shared", "n_shared_experts"), ("V", "vocab_size"),
        ("router", "n_routed_experts_published"))}
    d["chunk"] = int(cfg["serve"]["chunk"])
    return d


def mla_params(cfg: dict) -> int:
    d = _dims(cfg)
    return (d["D"] * d["rq"] + d["rq"] * d["H"] * (d["dn"] + d["dr"])
            + d["D"] * (d["rkv"] + d["dr"])
            + d["rkv"] * d["H"] * (d["dn"] + d["dv"])
            + d["H"] * d["dv"] * d["D"])


def expert_params(cfg: dict) -> int:
    d = _dims(cfg)
    return 3 * d["D"] * d["E"]


def router_params(cfg: dict) -> int:
    d = _dims(cfg)
    return d["D"] * d["router"]


def expert_layer_params(cfg: dict) -> int:
    """An expert layer outside its routed experts: attention, the shared
    expert(s), the router."""
    return (mla_params(cfg) + _dims(cfg)["shared"] * expert_params(cfg)
            + router_params(cfg))


def dense_layer_params(cfg: dict) -> int:
    d = _dims(cfg)
    return mla_params(cfg) + 3 * d["D"] * d["F"]


def expert_layers(cfg: dict) -> int:
    d = _dims(cfg)
    return d["L"] - d["dense"]


def nonexpert_params(cfg: dict) -> int:
    """Every matrix a token passes whatever its routing, all layers here."""
    d = _dims(cfg)
    return (d["dense"] * dense_layer_params(cfg)
            + expert_layers(cfg) * expert_layer_params(cfg))


def latent_bytes_per_position(cfg: dict, latent_bytes: int = 2) -> int:
    """One cached position's latent (unpadded), every layer."""
    d = _dims(cfg)
    return d["L"] * (d["rkv"] + d["dr"]) * latent_bytes


def attention_flops(cfg: dict, offset: int, tokens: int) -> float:
    """A chunk of ``tokens`` new positions from ``offset`` on, every layer:
    the latents up to its end expanded once (keys' 128 and values' 128 of
    every head from 512), then causal attention in the expanded form
    (192-wide scores, 128-wide values): position t attends to t + 1 keys."""
    d = _dims(cfg)
    pairs = tokens * offset + tokens * (tokens + 1) / 2
    per_pair = 2.0 * (d["dn"] + d["dr"]) + 2.0 * d["dv"]
    expand = 2.0 * (offset + tokens) * d["rkv"] * (d["dn"] + d["dv"])
    return (pairs * per_pair + expand) * d["H"] * d["L"]


def prefill_flops(cfg: dict, chunks, held_picks: int) -> float:
    """``chunks``: [(offset, real tokens)] of the chunk programs run;
    ``held_picks``: (token, pick) pairs that reached a held expert in them.
    The program's active-parameter basis (two operations a parameter a
    token or pick: ``obs/perfacct.active_param_flops``), plus attention at
    each chunk's own offset."""
    from predictionio_tpu.obs.perfacct import active_param_flops

    return (active_param_flops(sum(n for _, n in chunks),
                               nonexpert_params(cfg), expert_params(cfg),
                               held_picks)
            + sum(attention_flops(cfg, o, n) for o, n in chunks))


def prefill_bytes(cfg: dict, chunks, experts_touched: int,
                  weight_bytes: int = 2) -> float:
    """The chunk programs' needed bytes: the non-expert weights once a chunk,
    every held expert that got a token, the latents up to each chunk's
    end."""
    return (float(len(chunks)) * nonexpert_params(cfg) * weight_bytes
            + float(experts_touched) * expert_params(cfg) * weight_bytes
            + sum(o + n for o, n in chunks) * latent_bytes_per_position(cfg))


def extend_bytes(cfg: dict, runs: int, experts_touched: int,
                 latent_blocks: int, weight_bytes: int = 2) -> float:
    """``runs`` extension programs: the non-expert weights once each, every
    held expert that got a token (``experts_touched``: per layer, summed
    over the runs), and the cached latents AS ATTENDED (``latent_blocks``:
    blocks of ``chunk`` positions a layer's attention walked for the real
    rows, each as far as the batch's longest)."""
    d = _dims(cfg)
    return (float(runs) * nonexpert_params(cfg) * weight_bytes
            + float(experts_touched) * expert_params(cfg) * weight_bytes
            + float(latent_blocks) * d["chunk"]
            * latent_bytes_per_position(cfg))


def extend_flops(cfg: dict, tokens: int, held_picks: int,
                 latent_blocks: int, extend_len: int) -> float:
    """The same runs' operations: the active-parameter basis, and the
    absorbed attention of ``extend_len`` queries a row over the blocks
    attended (scores over the latent's 576, values over its 512, every
    head)."""
    from predictionio_tpu.obs.perfacct import active_param_flops

    d = _dims(cfg)
    per_pair = 2.0 * (d["rkv"] + d["dr"]) + 2.0 * d["rkv"]
    return (active_param_flops(tokens, nonexpert_params(cfg),
                               expert_params(cfg), held_picks)
            + float(latent_blocks) * d["chunk"] * extend_len * per_pair
            * d["H"] * d["L"])


def expert_groups_need(cfg: dict, experts_touched: int, held_picks: int,
                       weight_bytes: int = 2):
    """``(flops, bytes)`` of the grouped kernel over some chunks: every
    touched expert's three matrices once, two operations a parameter a
    (token, pick) pair."""
    return (2.0 * held_picks * expert_params(cfg),
            float(experts_touched) * expert_params(cfg) * weight_bytes)
