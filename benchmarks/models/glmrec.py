"""Model builder ``glmrec``: a seeded latent-attention stack whose every mixer
carries a LEARNED INDEX (32 index heads of 128, a 128-wide index key cached
beside every latent, the 2,048 best cached positions a query row), one leading
dense block and four expert blocks (a sigmoid router with a selection bias,
top-8 of 256, 16 held, a shared expert), behind the real EngineServer, through
the ``items`` query of the sequence engine.

As ``seqrec`` and ``axkrec``: the weights are made ON THE DEVICE from
``--seed`` (7.8 GB of bfloat16: no host copy, no pickle) and are the
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


def make_weights(bench) -> dict:
    """The seeded weights, in the reference's layout (``embed``, ``head``,
    ``final_norm``, ``layers``: the program's block names inside, a dense
    block holding ``ffn_a`` and an expert block ``moe``, every mixer its
    index's three matrices and LayerNorm), on the default device. The
    configuration's file says why each scale (``assumed.weights``)."""
    import jax
    import jax.numpy as jnp

    cfg = bench.config
    D, F, E = (int(cfg["hidden_size"]), int(cfg["intermediate_size"]),
               int(cfg["moe_intermediate_size"]))
    H = int(cfg["num_attention_heads"])
    dn, dr, dv = (int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"]),
                  int(cfg["v_head_dim"]))
    rq, rkv = int(cfg["q_lora_rank"]), int(cfg["kv_lora_rank"])
    Hi, di = int(cfg["index_n_heads"]), int(cfg["index_head_dim"])
    n_held, n_router = (int(cfg["experts_held"][1]),
                        int(cfg["n_routed_experts_published"]))
    S = E * int(cfg["n_shared_experts"])
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

    def ffn(width):
        return {"w_g": matrix(D, width), "w_u": matrix(D, width),
                "w_d": matrix(width, D)}

    layers = []
    for i in range(int(cfg["num_hidden_layers"])):
        layer = {
            "norm_a": norm(D),
            "mixer_a": {"w_dq": matrix(D, rq), "q_norm": norm(rq),
                        "w_uq": matrix(rq, H * (dn + dr)),
                        "w_dkv": matrix(D, rkv + dr), "kv_norm": norm(rkv),
                        "w_ukv": matrix(rkv, H * (dn + dv)),
                        "w_o": matrix(H * dv, D),
                        "w_qi": matrix(rq, Hi * di), "w_ki": matrix(D, di),
                        "w_w": matrix(D, Hi),
                        "ki_norm": {"scale": norm(di),
                                    "bias": make((di,), 0.1)}},
            "norm_ffn_a": norm(D)}
        if i < int(cfg["first_k_dense_replace_held"]):
            layer["ffn_a"] = ffn(F)
        else:
            layer["moe"] = {
                "w_r": matrix(D, n_router),
                "bias": make((n_router,), 1e-3, out=jnp.float32),
                "w_g": matrix(n_held, D, E), "w_u": matrix(n_held, D, E),
                "w_d": matrix(n_held, E, D), "shared": ffn(S)}
        layers.append(layer)
    weights = {"embed": make((V, D), 1.0),
               "head": make((V, D), 1.0 / math.sqrt(D)),
               "final_norm": norm(D), "layers": layers}
    jax.block_until_ready(weights)
    return weights


def stack_spec(cfg: dict, *, no_index: bool = False):
    """The configuration as the program's block stack; it raises on a
    program whose ``MLADims`` knows no index, before any weight is made.
    ``no_index`` is the builder's ablation (``benchmarks/tools/
    glm_ablation.py``): the same stack attending every cached position; no
    run of the benchmark sets it."""
    from predictionio_tpu.ops.mla import MLADims
    from predictionio_tpu.ops.moe import MoEDims
    from predictionio_tpu.ops.sessionrec import BlockSpec, StackSpec

    D, eps = int(cfg["hidden_size"]), float(cfg["rms_norm_eps"])
    rope = cfg["rope_parameters"]
    if rope["rope_type"] != "default" or cfg["scoring_func"] != "sigmoid" \
            or int(cfg["moe_layer_freq"]) != 1 \
            or not cfg["rope_interleave"] \
            or not cfg["indexer_rope_interleave"]:
        raise ValueError("plain RoPE over neighbouring pairs, sigmoid "
                         "scores and an expert layer in every block after "
                         "the dense ones are what this builder hands the "
                         "program")
    index = {} if no_index else {
        "index_heads": int(cfg["index_n_heads"]),
        "index_dim": int(cfg["index_head_dim"]),
        "index_topk": int(cfg["index_topk"]),
        "index_eps": float(cfg["assumed_sizes"]["index_layernorm_eps"])}
    mla = MLADims(
        dim=D, heads=int(cfg["num_attention_heads"]),
        d_nope=int(cfg["qk_nope_head_dim"]),
        d_rope=int(cfg["qk_rope_head_dim"]), d_v=int(cfg["v_head_dim"]),
        q_rank=int(cfg["q_lora_rank"]), kv_rank=int(cfg["kv_lora_rank"]),
        rope_theta=float(rope["rope_theta"]), eps=eps, scale_q=False,
        scale_kv=False, **index)
    moe = MoEDims(
        dim=D, expert_dim=int(cfg["moe_intermediate_size"]),
        n_routed=int(cfg["n_routed_experts_published"]), n_zero=0,
        top_k=int(cfg["num_experts_per_tok"]),
        scale=float(cfg["routed_scaling_factor"]),
        held=tuple(int(v) for v in cfg["experts_held"]),
        norm_topk=bool(cfg["norm_topk_prob"]),
        shared_dim=int(cfg["moe_intermediate_size"])
        * int(cfg["n_shared_experts"]),
        scoring=str(cfg["scoring_func"]), n_group=int(cfg["n_group"]),
        topk_group=int(cfg["topk_group"]))
    dense = int(cfg["first_k_dense_replace_held"])
    blocks = tuple(BlockSpec(mixer="mla", ffn="swiglu" if i < dense else "moe",
                             norm="rmsnorm", topology="pre_ln")
                   for i in range(int(cfg["num_hidden_layers"])))
    return StackSpec(dim=D, ffn_dim=int(cfg["intermediate_size"]),
                     blocks=blocks, positions="rope", eps=eps,
                     tied_head=bool(cfg["tie_word_embeddings"]), mla=mla,
                     moe=moe)


#: the control's histories: one query of each of the mix's SHORTEST sessions
#: (2,048-3,841 events here: each past ``index_topk``, so the index selects in
#: every one; a precision needs no long history to fail, and the reference's
#: cost grows with the square of one). ``seqrec.control_histories`` asks for
#: histories of at most 1,024 and finds none in this mix.
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
    # a program that knows no index fails here, before any weight is made
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
        end_time=now, engine_id="bench_glm", engine_version="0",
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
        sessionrec_engine(), "bench_glm", host="127.0.0.1", port=0,
        storage=storage, slo_conf=cfg.get("slo"),
    ).start()
    timings["server_boot_s"] = time.perf_counter() - t
    return Deployed(server, weights, timings)
