"""Model builder ``phirec``: a seeded decoder-hybrid-decoder stack (SambaY:
Mamba-1 mixers and differential attention under a window of 512 by turns, one
full-attention layer whose keys and values every later cross-attention layer
shares, gated memory units over the last Mamba layer's scan output; dense
SwiGLU FFNs, LayerNorm with bias, a tied head over the whole published
vocabulary) behind the real EngineServer, through the ``items`` query of the
sequence engine.

As ``seqrec`` and ``mimorec``: the weights are made ON THE DEVICE from
``--seed`` (7.7 GB of bfloat16: no host copy, no pickle) and are the
benchmark's own; the same arrays go to the program and, after the window, to
the reference. They reach the server as a ``core.persistent_model`` manifest
naming ``seqrec``'s ``SeededStack``, whose ``load`` builds the program's
``SeqStackModel`` around them (the head is tied: ``head`` names the
embedding's own array). The engine's warm-up compiles the three serve
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
#: ``benchmarks/tools/phi_ablation.py``; no run of the benchmark sets one)
ABLATIONS = ("window_less_one",)
#: the program's mixer of each of the reference's layer kinds
MIXERS = {"mamba": "mamba1", "memory": "mamba1", "window": "gqa_window",
          "full": "gqa", "gmu": "gmu", "cross": "gqa_cross"}


def layer_kinds(cfg: dict) -> tuple:
    """``"mamba" | "window" | "memory" | "full" | "gmu" | "cross"`` a layer
    (``assumed.layers``): Mamba-1 and window attention by turns up to the
    middle, the memory layer and the full layer there, gated memory units and
    cross attention by turns behind them."""
    n = int(cfg["num_hidden_layers"])
    half = n // 2
    return tuple(
        ("window" if i % 2 else "mamba") if i < half
        else ("memory" if i == half else "full") if i <= half + 1
        else ("cross" if i % 2 else "gmu") for i in range(n))


def sizes(cfg: dict) -> dict:
    s = cfg["assumed_sizes"]
    D, H = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return {"D": D, "F": int(cfg["intermediate_size"]), "H": H,
            "KV": int(cfg["num_key_value_heads"]), "d": D // H,
            "inner": int(s["expand"]) * D, "N": int(s["d_state"]),
            "R": int(s["dt_rank"]), "K": int(s["d_conv"]),
            "V": int(cfg["vocab_size"])}


def make_weights(bench) -> dict:
    """The seeded weights, in the reference's layout (``embed``, ``head`` the
    same array again, ``final_norm``, ``layers``: the program's block names
    inside), on the default device. The configuration's file says why each
    scale (``assumed.weights``)."""
    import jax
    import jax.numpy as jnp

    cfg = bench.config
    z = sizes(cfg)
    D, F, H, KV, d = z["D"], z["F"], z["H"], z["KV"], z["d"]
    inner, N, R, K = z["inner"], z["N"], z["R"], z["K"]
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
        return {"scale": make((width,), 0.1, mean=1.0),
                "bias": make((width,), 0.1)}

    # the decays (``seeded_decays``, and ``assumed.weights`` for why): a
    # log-uniform step a channel, ``A[:, n] = (n + 1) / a_over``
    decays = cfg["seeded_decays"]
    step_lo, step_hi = (math.log(float(s)) for s in decays["step"])

    def mamba():
        step = jnp.exp(jax.random.uniform(
            jax.random.fold_in(root, next(count)), (inner,), jnp.float32,
            step_lo, step_hi))
        return {"w_in": matrix(D, 2 * inner),
                "conv_w": make((K, inner), 1.0 / math.sqrt(K)),
                "conv_b": make((inner,), 0.1),
                "w_x": matrix(inner, R + 2 * N), "w_dt": matrix(R, inner),
                "b_dt": step + jnp.log(-jnp.expm1(-step)),
                "a_log": jnp.log(jnp.broadcast_to(
                    jnp.arange(1, N + 1, dtype=jnp.float32)
                    / float(decays["a_over"]), (inner, N))),
                "d": jnp.ones((inner,), jnp.float32),
                "w_out": matrix(inner, D)}

    def attention(cross=False):
        p = {"w_q": matrix(D, H * d), "b_q": make((H * d,), 0.1),
             "w_o": matrix(H * d, D), "b_o": make((D,), 0.1),
             "subln": make((2 * d,), 0.1, mean=1.0)}
        if not cross:
            p.update(w_k=matrix(D, KV * d), b_k=make((KV * d,), 0.1),
                     w_v=matrix(D, KV * d), b_v=make((KV * d,), 0.1))
        for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
            p[name] = make((d,), 0.1, out=jnp.float32)
        return p

    mixer = {"mamba": mamba, "memory": mamba, "window": attention,
             "full": attention, "cross": lambda: attention(cross=True),
             "gmu": lambda: {"w_1": matrix(D, inner),
                             "w_2": matrix(inner, D)}}
    layers = [{"norm_a": norm(D), "mixer_a": mixer[kind](),
               "norm_ffn_a": norm(D),
               "ffn_a": {"w_g": matrix(D, F), "w_u": matrix(D, F),
                         "w_d": matrix(F, D)}}
              for kind in layer_kinds(cfg)]
    embed = make((z["V"], D), float(cfg["embedding_std"]))
    weights = {"embed": embed, "head": embed, "final_norm": norm(D),
               "layers": layers}
    jax.block_until_ready(weights)
    return weights


def stack_spec(cfg: dict, *, ablate: str | None = None):
    """The configuration as the program's block stack; it raises on a
    program that knows no such mixers, before any weight is made. ``ablate``
    is the builder's (:data:`ABLATIONS`): the same stack with the window one
    position short."""
    from predictionio_tpu.ops.gqa import GQADims
    from predictionio_tpu.ops.mamba1 import Mamba1Dims
    from predictionio_tpu.ops.sessionrec import BlockSpec, StackSpec

    if ablate not in (None,) + ABLATIONS:
        raise ValueError(f"unknown ablation {ablate!r}")
    if cfg["mlp_bias"] or cfg["lm_head_bias"] or not cfg[
            "tie_word_embeddings"] or int(cfg["mb_per_layer"]) != 2:
        raise ValueError("no bias in the MLP or the head, a tied head and a "
                         "Mamba layer every second layer are what this "
                         "builder hands the program")
    z, eps = sizes(cfg), float(cfg["layer_norm_eps"])
    same = {"dim": z["D"], "heads": z["H"], "kv_heads": z["KV"],
            "head_dim": z["d"], "block_len": 1, "eps": eps, "rope": False,
            "qk_norm": False, "bias": True, "diff": True}
    window = int(cfg["sliding_window"]) - (ablate == "window_less_one")
    kinds = layer_kinds(cfg)
    blocks = tuple(BlockSpec(mixer=MIXERS[kind], ffn="swiglu",
                             norm="layernorm", topology="pre_ln")
                   for kind in kinds)
    return StackSpec(
        dim=z["D"], ffn_dim=z["F"], blocks=blocks, positions="rope", eps=eps,
        tied_head=True, gqa=GQADims(**same),
        gqa_window=GQADims(window=window, **same),
        gqa_cross=GQADims(cross=True, **same),
        mamba1=Mamba1Dims(dim=z["D"], d_inner=z["inner"], d_state=z["N"],
                          dt_rank=z["R"], d_conv=z["K"]),
        memory_block=kinds.index("memory"))


def control_histories(bench) -> list:
    """The control's histories, from the mix itself: one query of each of its
    SHORTEST sessions (512-1,616 events: every one reaches past the window,
    and a product's precision needs no long history to fail), then
    ``control_long_histories`` more, the mix's LONGEST session first and the
    others at even steps of rank below it: a carried state that is held too
    coarsely, or a ring that is off by one, tells only over thousands of
    positions."""
    short = axkrec.control_histories(bench)
    more = int(bench.config.get("control_long_histories", 0))
    if not more:
        return short
    traffic, mix = bench.lib("session_traffic"), bench.traffic
    sessions = traffic.Sessions(mix, int(bench.config["vocab_size"]))
    order = sessions.order(0)
    by_length = sorted(range(len(order)), key=order.__getitem__,
                       reverse=True)
    rng = bench.lib("seeded").rng(bench.seed, 99)
    step = max(1, (len(order) - len(short)) // (2 * more))
    return short + [sessions.session(0, index)[
        int(rng.integers(0, int(mix["queries_per_session"])))]
        for index in by_length[:more * step:step]]


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
    # a program that knows no such mixers fails here, before any weight
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
        end_time=now, engine_id="bench_phi", engine_version="0",
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
        sessionrec_engine(), "bench_phi", host="127.0.0.1", port=0,
        storage=storage, slo_conf=cfg.get("slo"),
    ).start()
    timings["server_boot_s"] = time.perf_counter() - t
    return Deployed(server, weights, timings)
