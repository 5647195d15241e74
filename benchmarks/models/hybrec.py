"""Model builder ``hybrec``: a seeded hybrid stack (Mamba-2 mixers with a causal
grouped-query attention layer among them, an expert layer with a shared
expert as every block's FFN) behind the real EngineServer, through the
``items`` query of the sequence engine.

As ``seqrec``: the weights are made ON THE DEVICE from ``--seed`` (9.5 GB of
bfloat16: no host copy, no pickle) and are the benchmark's own; the same
arrays go to the program and, after the window, to the reference. They reach
the server as a ``core.persistent_model`` manifest naming ``seqrec``'s
``SeededStack``, whose ``load`` builds the program's ``SeqStackModel`` around
them (the head is tied: ``head`` names the embedding's own array). The
engine's warm-up compiles both serve programs and every head batch.
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
import seqrec  # noqa: E402 — the sibling builder of the same engine

_generator, item_row, item_id = (seqrec._generator, seqrec.item_row,
                                 seqrec.item_id)
SeededStack, Deployed = seqrec.SeededStack, seqrec.Deployed


def layer_kinds(cfg: dict) -> list:
    """``"mamba"`` / ``"attention"`` for each layer held here: the first
    ``num_hidden_layers`` of the published ``layer_types``."""
    return list(cfg["layer_types"][:int(cfg["num_hidden_layers"])])


def make_weights(bench) -> dict:
    """The seeded weights, in the reference's layout (``embed``,
    ``final_norm``, ``layers``: the program's block names inside; ``head`` is
    the embedding again, tied), on the default device. The configuration's
    file says why each scale (``assumed.weights``)."""
    import jax
    import jax.numpy as jnp

    cfg = bench.config
    D, E, F = (int(cfg["hidden_size"]), int(cfg["intermediate_size"]),
               int(cfg["shared_intermediate_size"]))
    H, KV = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    hd = D // H
    Hm, P, N, K = (int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"]),
                   int(cfg["mamba_d_state"]), int(cfg["mamba_d_conv"]))
    inner = Hm * P
    conv_dim = inner + 2 * N
    n_held, n_router = (int(cfg["experts_held"][1]),
                        int(cfg["num_local_experts_published"]))
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

    def uniform(lo, hi):
        return jax.random.uniform(jax.random.fold_in(root, next(count)),
                                  (Hm,), jnp.float32, lo, hi)

    def mamba():
        step = jnp.exp(uniform(math.log(1e-3), math.log(0.1)))
        return {"w_in": matrix(D, inner + conv_dim + Hm),
                "conv_w": make((K, conv_dim), 1.0 / math.sqrt(K)),
                "conv_b": make((conv_dim,), 0.1),
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "a_log": jnp.log(uniform(1.0, 16.0)),
                "d": jnp.ones((Hm,), jnp.float32),
                "norm": norm(inner), "w_out": matrix(inner, D)}

    def attention():
        return {"w_q": matrix(D, H * hd), "w_k": matrix(D, KV * hd),
                "w_v": matrix(D, KV * hd), "w_o": matrix(H * hd, D)}

    layers = []
    for kind in layer_kinds(cfg):
        layers.append({
            "norm_a": norm(D),
            "mixer_a": attention() if kind == "attention" else mamba(),
            "norm_ffn_a": norm(D),
            # the model's router has no bias: the program's is zero
            "moe": {"w_r": matrix(D, n_router),
                    "bias": jnp.zeros((n_router,), jnp.float32),
                    "w_g": matrix(n_held, D, E), "w_u": matrix(n_held, D, E),
                    "w_d": matrix(n_held, E, D),
                    "shared": {"w_g": matrix(D, F), "w_u": matrix(D, F),
                               "w_d": matrix(F, D)}}})
    embed = make((V, D), float(cfg["embedding_std"]))
    weights = {"embed": embed, "head": embed, "final_norm": norm(D),
               "layers": layers}
    jax.block_until_ready(weights)
    return weights


def stack_spec(cfg: dict):
    """The configuration as the program's block stack."""
    from predictionio_tpu.ops.gqa import GQADims
    from predictionio_tpu.ops.moe import MoEDims
    from predictionio_tpu.ops.sessionrec import BlockSpec, StackSpec
    from predictionio_tpu.ops.ssm import SSMDims

    D, eps = int(cfg["hidden_size"]), float(cfg["rms_norm_eps"])
    H = int(cfg["num_attention_heads"])
    if int(cfg["mamba_n_groups"]) != 1 or cfg["position_embedding_type"] != \
            "nope":
        raise ValueError("one group of B and C and no position encoding "
                         "are what the program's mixers compute")
    ssm = SSMDims(dim=D, heads=int(cfg["mamba_n_heads"]),
                  head_dim=int(cfg["mamba_d_head"]),
                  d_state=int(cfg["mamba_d_state"]),
                  d_conv=int(cfg["mamba_d_conv"]),
                  chunk=int(cfg["mamba_chunk_size"]), eps=eps)
    if ssm.d_inner != int(cfg["mamba_expand"]) * D:
        raise ValueError("mamba_expand * hidden_size != heads * head size")
    gqa = GQADims(dim=D, heads=H, kv_heads=int(cfg["num_key_value_heads"]),
                  head_dim=D // H, block_len=1, eps=eps, rope=False,
                  qk_norm=False, scale=float(cfg["attention_multiplier"]))
    moe = MoEDims(dim=D, expert_dim=int(cfg["intermediate_size"]),
                  n_routed=int(cfg["num_local_experts_published"]), n_zero=0,
                  top_k=int(cfg["num_experts_per_tok"]), scale=1.0,
                  held=tuple(int(v) for v in cfg["experts_held"]),
                  norm_topk=True,
                  shared_dim=int(cfg["shared_intermediate_size"]))
    blocks = tuple(BlockSpec(
        mixer="gqa" if kind == "attention" else "mamba2", ffn="moe",
        norm="rmsnorm", topology="pre_ln") for kind in layer_kinds(cfg))
    return StackSpec(
        dim=D, ffn_dim=0, blocks=blocks, positions="rope", eps=eps,
        embed_scale=float(cfg["embedding_multiplier"]),
        residual_scale=float(cfg["residual_multiplier"]),
        logits_scale=1.0 / float(cfg["logits_scaling"]),
        tied_head=bool(cfg["tie_word_embeddings"]), gqa=gqa, ssm=ssm,
        moe=moe)


def control_histories(bench) -> list:
    """A seeded handful of the cell's own SHORT histories, for the control
    (three forwards each, in a lower precision and in float32)."""
    traffic, mix = bench.lib("session_traffic"), bench.traffic
    sessions = traffic.Sessions(mix, int(bench.config["vocab_size"]))
    rng = bench.lib("seeded").rng(bench.seed, 98)
    out = []
    for c in range(int(mix["connections"])):
        order = sessions.order(c)
        short = [i for i, h in enumerate(order) if h <= 1024]
        index = short[int(rng.integers(0, len(short)))]
        out.append(sessions.session(c, index)[
            int(rng.integers(0, int(mix["queries_per_session"])))])
    return out[:int(bench.config.get("control_histories", 6))]


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
    # a program that knows no such stack fails here, before any weight
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
        end_time=now, engine_id="bench_hyb", engine_version="0",
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
        sessionrec_engine(), "bench_hyb", host="127.0.0.1", port=0,
        storage=storage, slo_conf=cfg.get("slo"),
    ).start()
    timings["server_boot_s"] = time.perf_counter() - t
    return Deployed(server, weights, timings)
