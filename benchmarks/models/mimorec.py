"""Model builder ``mimorec``: a seeded grouped-query stack whose layers MIX
two kinds of attention (one dense full-attention block, then five
sliding-window blocks of 128 positions with a learned sink a head, then a
full-attention block; 64 query heads of 192 on 8 / 4 key/value heads, values
of 128, RoPE on the first 64 dimensions at two bases) over expert layers (a
sigmoid router with a selection bias, top-8 of 256, 16 held, no shared
expert), behind the real EngineServer, through the ``items`` query of the
sequence engine.

As ``seqrec`` and ``glmrec``: the weights are made ON THE DEVICE from
``--seed`` (6.9 GB of bfloat16: no host copy, no pickle) and are the
benchmark's own; the same arrays go to the program and, after the window, to
the reference. They reach the server as a ``core.persistent_model`` manifest
naming ``seqrec``'s ``SeededStack``, whose ``load`` builds the program's
``SeqStackModel`` around them. The engine's warm-up compiles both serve
programs and every head batch.
"""

from __future__ import annotations

import datetime as dt
import json
import math
import os
import pickle
import sys
import time
import uuid

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import axkrec  # noqa: E402 — the sibling builders of the same engine
import seqrec  # noqa: E402

_generator, item_row, item_id = (seqrec._generator, seqrec.item_row,
                                 seqrec.item_id)
SeededStack, Deployed = seqrec.SeededStack, seqrec.Deployed

#: what ``stack_spec(ablate=)`` can break underneath a run (the builder's
#: ``benchmarks/tools/mimo_ablation.py``; no run of the benchmark sets one)
ABLATIONS = ("no_sink", "wide_window", "full_theta")
#: ``wide_window``: the positions a window layer then attends
WIDE_WINDOW = 2048


def rotary_dims(cfg: dict) -> int:
    """``partial_rotary_factor`` of the head's dimensions, a whole number of
    pairs: 0.334 x 192 = 64.1 -> 64."""
    return int(float(cfg["partial_rotary_factor"])
               * int(cfg["head_dim"])) // 2 * 2


def make_weights(bench) -> dict:
    """The seeded weights, in the reference's layout (``embed``, ``head``,
    ``final_norm``, ``layers``: the program's block names inside, a dense
    block holding ``ffn_a`` and an expert block ``moe``, a window layer's
    mixer its ``sink``), on the default device. The configuration's file says
    why each scale (``assumed.weights``)."""
    import jax
    import jax.numpy as jnp

    cfg = bench.config
    D, F, E = (int(cfg["hidden_size"]), int(cfg["intermediate_size"]),
               int(cfg["moe_intermediate_size"]))
    H, dk, dv = (int(cfg["num_attention_heads"]), int(cfg["head_dim"]),
                 int(cfg["v_head_dim"]))
    kv = {0: int(cfg["num_key_value_heads"]),
          1: int(cfg["swa_num_key_value_heads"])}
    n_held, n_router = (int(cfg["experts_held"][1]),
                        int(cfg["n_routed_experts_published"]))
    V = int(cfg["vocab_size"])
    dtype = jnp.dtype(cfg["weights_dtype"])
    # the chip's own generator (XLA's RngBitGenerator), as ``seqrec``
    state = np.random.SeedSequence([int(bench.seed)]).generate_state(2)
    root = jax.random.fold_in(jax.random.key(int(state[0]), impl="rbg"),
                              int(state[1]))
    count = iter(range(1 << 30))

    def make(shape, std, mean=0.0, out=dtype):
        return _generator(tuple(shape), float(std), float(mean),
                          jnp.dtype(out).name)(
            jax.random.fold_in(root, next(count)))

    def matrix(*shape):
        return make(shape, 1.0 / math.sqrt(shape[-2]))

    def norm(width):
        return make((width,), 0.1, mean=1.0)

    layers = []
    for window, expert in zip(cfg["hybrid_layer_pattern_held"],
                              cfg["moe_layer_freq_held"]):
        K = kv[int(window)]
        mixer = {"w_q": matrix(D, H * dk), "w_k": matrix(D, K * dk),
                 "w_v": matrix(D, K * dv), "w_o": matrix(H * dv, D)}
        if window:  # about as heavy as a whole window of equal scores
            mixer["sink"] = make((H,), 1.0,
                                 mean=math.log(int(cfg["sliding_window"])))
        layer = {"norm_a": norm(D), "mixer_a": mixer, "norm_ffn_a": norm(D)}
        if expert:
            layer["moe"] = {
                "w_r": matrix(D, n_router),
                "bias": make((n_router,), 1e-3, out=jnp.float32),
                "w_g": matrix(n_held, D, E), "w_u": matrix(n_held, D, E),
                "w_d": matrix(n_held, E, D)}
        else:
            layer["ffn_a"] = {"w_g": matrix(D, F), "w_u": matrix(D, F),
                              "w_d": matrix(F, D)}
        layers.append(layer)
    weights = {"embed": make((V, D), 1.0),
               "head": make((V, D), 1.0 / math.sqrt(D)),
               "final_norm": norm(D), "layers": layers}
    jax.block_until_ready(weights)
    return weights


def stack_spec(cfg: dict, *, ablate: str | None = None):
    """The configuration as the program's block stack; it raises on a
    program whose ``GQADims`` knows no window, before any weight is made.
    ``ablate`` is the builder's (:data:`ABLATIONS`): the same stack with the
    sinks left out of the normaliser, with the window layers attending
    :data:`WIDE_WINDOW` positions (full attention for every history up to
    there; spans for five more layers would take 16 GB), or with the window
    layers turned at the full layers' base."""
    from predictionio_tpu.ops.gqa import GQADims
    from predictionio_tpu.ops.moe import MoEDims
    from predictionio_tpu.ops.sessionrec import BlockSpec, StackSpec

    if ablate not in (None,) + ABLATIONS:
        raise ValueError(f"unknown ablation {ablate!r}")
    D, eps = int(cfg["hidden_size"]), float(cfg["layernorm_epsilon"])
    if cfg["rope_scaling"]["rope_type"] != "default" \
            or cfg["scoring_func"] != "sigmoid" or cfg["n_shared_experts"] \
            or cfg["attention_bias"] or cfg["add_full_attention_sink_bias"] \
            or not cfg["add_swa_attention_sink_bias"]:
        raise ValueError("plain RoPE, sigmoid scores, no shared expert, no "
                         "bias and a sink in the window layers alone are "
                         "what this builder hands the program")
    same = {"dim": D, "heads": int(cfg["num_attention_heads"]),
            "head_dim": int(cfg["head_dim"]), "block_len": 1, "eps": eps,
            "qk_norm": False, "v_head_dim": int(cfg["v_head_dim"]),
            "rope_dims": rotary_dims(cfg),
            "value_scale": float(cfg["attention_value_scale"])}
    full = GQADims(kv_heads=int(cfg["num_key_value_heads"]),
                   rope_theta=float(cfg["rope_theta"]), **same)
    window = GQADims(
        kv_heads=int(cfg["swa_num_key_value_heads"]),
        rope_theta=float(cfg["rope_theta" if ablate == "full_theta"
                             else "swa_rope_theta"]),
        window=(WIDE_WINDOW if ablate == "wide_window"
                else int(cfg["sliding_window"])),
        sink=ablate != "no_sink", **same)
    scale = cfg["routed_scaling_factor"]
    moe = MoEDims(
        dim=D, expert_dim=int(cfg["moe_intermediate_size"]),
        n_routed=int(cfg["n_routed_experts_published"]), n_zero=0,
        top_k=int(cfg["num_experts_per_tok"]),
        scale=1.0 if scale is None else float(scale),
        held=tuple(int(v) for v in cfg["experts_held"]),
        norm_topk=bool(cfg["norm_topk_prob"]),
        scoring=str(cfg["scoring_func"]), n_group=int(cfg["n_group"]),
        topk_group=int(cfg["topk_group"]))
    blocks = tuple(
        BlockSpec(mixer="gqa_window" if w else "gqa",
                  ffn="moe" if e else "swiglu", norm="rmsnorm",
                  topology="pre_ln")
        for w, e in zip(cfg["hybrid_layer_pattern_held"],
                        cfg["moe_layer_freq_held"]))
    if len(blocks) != int(cfg["num_hidden_layers"]):
        raise ValueError("the held patterns name every layer held here")
    return StackSpec(dim=D, ffn_dim=int(cfg["intermediate_size"]),
                     blocks=blocks, positions="rope", eps=eps,
                     tied_head=bool(cfg["tie_word_embeddings"]), gqa=full,
                     gqa_window=window, moe=moe)


#: the control's histories: one query of each of the mix's SHORTEST sessions
#: (128-544 events here: a precision needs no long history to fail, and
#: five of the six reach past the window)
control_histories = axkrec.control_histories


def deploy(bench) -> Deployed:
    from predictionio_tpu.core.params import EngineParams
    from predictionio_tpu.core.persistent_model import (
        PersistentModelManifest)
    from predictionio_tpu.data.metadata import EngineInstance, Model
    from predictionio_tpu.data.storage import Storage
    from predictionio_tpu.models.sessionrec import SeqStackParams
    from predictionio_tpu.serving.engine_server import EngineServer
    from predictionio_tpu.templates.sessionrec import (
        SeqDataSourceParams, sessionrec_engine)

    cfg = bench.config
    timings = {}
    t = time.perf_counter()
    # a program that knows no window fails here, before any weight is made
    spec = stack_spec(cfg)
    weights = make_weights(bench)
    timings["weights_s"] = time.perf_counter() - t

    t = time.perf_counter()
    storage = Storage.from_env({
        "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
        **{f"PIO_STORAGE_REPOSITORIES_{r}_{k}": v
           for r in ("METADATA", "EVENTDATA", "MODELDATA")
           for k, v in (("NAME", r.lower()), ("SOURCE", "MEM"))},
    })
    ep = EngineParams(
        data_source_params=("", SeqDataSourceParams(app_name="bench")),
        preparator_params=("", None),
        algorithm_params_list=[("seqstack", SeqStackParams(**cfg["serve"]))],
        serving_params=("", None),
    ).to_json_dict()
    now = dt.datetime.now(tz=dt.timezone.utc)
    instance = EngineInstance(
        id=uuid.uuid4().hex, status="COMPLETED", start_time=now,
        end_time=now, engine_id="bench_mimo", engine_version="0",
        engine_variant="default", engine_factory="bench", batch="bench",
        data_source_params=json.dumps(ep["dataSourceParams"]),
        preparator_params=json.dumps(ep["preparatorParams"]),
        algorithms_params=json.dumps(ep["algorithmParamsList"]),
        serving_params=json.dumps(ep["servingParams"]),
    )
    storage.engine_instances().insert(instance)
    seqrec._HANDOVER[instance.id] = (spec, weights)
    manifest = PersistentModelManifest(class_name="SeededStack",
                                       module_name=seqrec.__name__)
    storage.models().insert(Model(id=instance.id,
                                  models=pickle.dumps([manifest])))
    timings["store_s"] = time.perf_counter() - t

    t = time.perf_counter()
    server = EngineServer(
        sessionrec_engine(), "bench_mimo", host="127.0.0.1", port=0,
        storage=storage, slo_conf=cfg.get("slo"),
    ).start()
    timings["server_boot_s"] = time.perf_counter() - t
    return Deployed(server, weights, timings)
