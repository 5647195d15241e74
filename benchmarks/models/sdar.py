"""Model builder ``sdar``: a seeded block-diffusion expert stack (grouped-query
attention under the block-causal mask, the expert layer as the whole FFN)
behind the real EngineServer, through the ``{items, generate}`` query of the
sequence engine.

As ``seqrec``: the weights are made ON THE DEVICE from ``--seed`` (10 GB of
bfloat16: no host copy, no pickle) and are the benchmark's own; the same
arrays go to the program and, after the window, to the reference. They reach
the server as a ``core.persistent_model`` manifest naming
:class:`SeededStack`, whose ``load`` builds the program's ``SeqStackModel``
around them. The engine's warm-up compiles both serve programs (the head is
inside the block program: no index is built).
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

#: shared with ``seqrec``: one compiled generator per distinct (shape,
#: scale), the answer's item names, the manifest's loader (it builds a
#: ``SeqStackModel`` around whatever spec and weights it is handed) and what
#: ``deploy`` returns
_generator, item_row = seqrec._generator, seqrec.item_row
SeededStack, Deployed = seqrec.SeededStack, seqrec.Deployed


def make_weights(bench) -> dict:
    """The seeded weights, in the reference's layout (``embed``, ``head``,
    ``final_norm``, ``layers``: the program's block names inside), on the
    default device."""
    import jax
    import jax.numpy as jnp

    cfg = bench.config
    D, E = int(cfg["hidden_size"]), int(cfg["moe_intermediate_size"])
    H, KV, hd = (int(cfg["num_attention_heads"]),
                 int(cfg["num_key_value_heads"]), int(cfg["head_dim"]))
    n_experts, V = int(cfg["experts_held"][1]), int(cfg["vocab_size"])
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
    for _ in range(int(cfg["num_hidden_layers"])):
        layers.append({
            "norm_a": norm(D),
            "mixer_a": {"w_q": matrix(D, H * hd), "w_k": matrix(D, KV * hd),
                        "w_v": matrix(D, KV * hd), "w_o": matrix(H * hd, D),
                        "q_norm": norm(hd), "k_norm": norm(hd)},
            "norm_ffn_a": norm(D),
            # the model's router has no bias: the program's is zero
            "moe": {"w_r": matrix(D, int(cfg["num_experts"])),
                    "bias": jnp.zeros((int(cfg["num_experts"]),),
                                      jnp.float32),
                    "w_g": matrix(n_experts, D, E),
                    "w_u": matrix(n_experts, D, E),
                    "w_d": matrix(n_experts, E, D)}})
    weights = {"embed": make((V, D), 1.0),
               "head": make((V, D), 1.0 / math.sqrt(D)),
               "final_norm": norm(D), "layers": layers}
    jax.block_until_ready(weights)
    return weights


def stack_spec(cfg: dict):
    """The configuration as the program's block stack."""
    from predictionio_tpu.ops.gqa import GQADims
    from predictionio_tpu.ops.moe import MoEDims
    from predictionio_tpu.ops.sessionrec import (
        BlockSpec, Generation, StackSpec)

    D = int(cfg["hidden_size"])
    gen = Generation(**cfg["generation"])
    gqa = GQADims(
        dim=D, heads=int(cfg["num_attention_heads"]),
        kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["head_dim"]), block_len=gen.block_len,
        rope_theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]))
    moe = MoEDims(
        dim=D, expert_dim=int(cfg["moe_intermediate_size"]),
        n_routed=int(cfg["num_experts"]), n_zero=0,
        top_k=int(cfg["num_experts_per_tok"]), scale=1.0,
        held=tuple(int(v) for v in cfg["experts_held"]),
        norm_topk=bool(cfg["norm_topk_prob"]))
    block = BlockSpec(mixer="gqa", ffn="moe", norm="rmsnorm",
                      topology="pre_ln")
    return StackSpec(dim=D, ffn_dim=0,
                     blocks=(block,) * int(cfg["num_hidden_layers"]),
                     positions="rope", eps=float(cfg["rms_norm_eps"]),
                     tied_head=bool(cfg["tie_word_embeddings"]), gqa=gqa,
                     moe=moe, generation=gen)


def n_traffic_items(cfg: dict) -> int:
    """The rows the traffic draws its items from: those below the mask's."""
    return int(cfg["generation"]["mask_row"])


def control_histories(bench) -> list:
    """A seeded handful of the cell's own SHORT first queries, for the
    control (a whole slate each, in a lower precision, forward by forward
    without a cache)."""
    traffic = bench.lib("slate_traffic")
    sessions = traffic.Sessions(bench.traffic, n_traffic_items(bench.config))
    rng = bench.lib("seeded").rng(bench.seed, 98)
    out = []
    for c in range(int(bench.traffic["connections"])):
        order = sessions.order(c)
        short = [i for i, h in enumerate(order) if h <= 256]
        index = short[int(rng.integers(0, len(short)))]
        out.append(sessions.session(c, index)[0])
    return out[:int(bench.config.get("control_histories", 4))]


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
        end_time=now, engine_id="bench_sdar", engine_version="0",
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
        sessionrec_engine(), "bench_sdar", host="127.0.0.1", port=0,
        storage=storage, slo_conf=cfg.get("slo"),
    ).start()
    timings["server_boot_s"] = time.perf_counter() - t
    return Deployed(server, weights, timings)
