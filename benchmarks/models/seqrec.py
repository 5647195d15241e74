"""Model builder ``seqrec``: a seeded latent-attention expert stack behind the
real EngineServer, through the ``items`` query of the sequence engine.

The weights are made ON THE DEVICE from ``--seed`` (10 GB of bfloat16: no host
copy, no pickle) and are the benchmark's own: the same arrays go to the
program and, after the window, to the reference. They reach the server the
way a deployment's own checkpoint loader would hand them over: the stored
model is a ``core.persistent_model`` manifest naming :class:`SeededStack`, and
its ``load`` builds the program's ``SeqStackModel`` around the arrays. The
engine's warm-up compiles both serve programs and every head batch.
"""

from __future__ import annotations

import datetime as dt
import functools
import json
import math
import pickle
import time
import uuid

import numpy as np

_HANDOVER = {}


def item_id(row: int) -> str:
    return f"i{row}"


def item_row(item: str) -> int:
    return int(item[1:])


@functools.lru_cache(maxsize=None)
def _generator(shape, std, mean, dtype_name):
    """One compiled generator per distinct (shape, scale): a layer's
    matrices repeat, and a fresh ``jit`` for each cost 50 s of compilation
    (my chip run, PR 27)."""
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda key: (mean + std * jax.random.normal(
        key, shape, jnp.float32)).astype(dtype_name))


def make_weights(bench) -> dict:
    """The seeded weights, in the reference's layout (``embed``, ``head``,
    ``final_norm``, ``layers``: the program's block names inside), on the
    default device."""
    import jax
    import jax.numpy as jnp

    cfg = bench.config
    D, F, E = (int(cfg["hidden_size"]), int(cfg["ffn_hidden_size"]),
               int(cfg["expert_ffn_hidden_size"]))
    H = int(cfg["num_attention_heads"])
    dn, dr, dv = (int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"]),
                  int(cfg["v_head_dim"]))
    rq, rkv = int(cfg["q_lora_rank"]), int(cfg["kv_lora_rank"])
    n_held = int(cfg["experts_held"][1])
    n_router = int(cfg["n_routed_experts_published"]) + int(
        cfg["zero_expert_num"])
    V = int(cfg["vocab_size"])
    dtype = jnp.dtype(cfg["weights_dtype"])
    # the generator is the chip's own (XLA's RngBitGenerator): threefry took
    # 54 s for these 5.2 G values (my chip run, PR 27). The same seed gives
    # the same weights on the same kind of device, which is all a run needs:
    # program and reference are handed the same arrays
    state = np.random.SeedSequence([int(bench.seed)]).generate_state(2)
    root = jax.random.fold_in(jax.random.key(int(state[0]), impl="rbg"),
                              int(state[1]))
    count = iter(range(1 << 30))

    def make(shape, std, mean=0.0, out=dtype):
        return _generator(tuple(shape), float(std), float(mean),
                          jnp.dtype(out).name)(
            jax.random.fold_in(root, next(count)))

    def matrix(*shape, scale=1.0):
        return make(shape, scale / math.sqrt(shape[-2]))

    def norm(width):
        return make((width,), 0.1, mean=1.0)

    # the up-projections' entries are divided by the latent's published
    # scale (mla_scale_*_lora), so that queries and keys come out at unit
    # scale and attention logits at about 1, as a trained model's are. At
    # N(0, 1/fan_in) everywhere the logits had a standard deviation of 5.7:
    # attention was nearly an argmax, and one bfloat16 rounding in the first
    # double-layer (2% of its output) had grown to 34% of the last hidden
    # state by the fourth, in the program and in the reference held in
    # bfloat16 alike (my chip run, PR 27) -- no limit could then tell
    # bfloat16 from float8
    q_scale = math.sqrt(rq / D) if cfg["mla_scale_q_lora"] else 1.0
    kv_scale = math.sqrt(rkv / D) if cfg["mla_scale_kv_lora"] else 1.0

    def mla():
        return {"w_dq": matrix(D, rq), "q_norm": norm(rq),
                "w_uq": matrix(rq, H * (dn + dr), scale=q_scale),
                "w_dkv": matrix(D, rkv + dr), "kv_norm": norm(rkv),
                "w_ukv": matrix(rkv, H * (dn + dv), scale=kv_scale),
                "w_o": matrix(H * dv, D)}

    def ffn():
        return {"w_g": matrix(D, F), "w_u": matrix(D, F),
                "w_d": matrix(F, D)}

    layers = []
    for _ in range(int(cfg["num_layers"])):
        layers.append({
            "norm_a": norm(D), "mixer_a": mla(), "norm_ffn_a": norm(D),
            "ffn_a": ffn(), "norm_b": norm(D), "mixer_b": mla(),
            "norm_ffn_b": norm(D), "ffn_b": ffn(),
            "moe": {"w_r": matrix(D, n_router),
                    "bias": make((n_router,), 1e-3, out=jnp.float32),
                    "w_g": matrix(n_held, D, E), "w_u": matrix(n_held, D, E),
                    "w_d": matrix(n_held, E, D)}})
    weights = {"embed": make((V, D), 1.0), "head": make((V, D), 1.0 / math.sqrt(D)),
               "final_norm": norm(D), "layers": layers}
    jax.block_until_ready(weights)
    return weights


def stack_spec(cfg: dict):
    """The configuration as the program's block stack."""
    from predictionio_tpu.ops.mla import MLADims
    from predictionio_tpu.ops.moe import MoEDims
    from predictionio_tpu.ops.sessionrec import BlockSpec, StackSpec

    D = int(cfg["hidden_size"])
    mla = MLADims(
        dim=D, heads=int(cfg["num_attention_heads"]),
        d_nope=int(cfg["qk_nope_head_dim"]),
        d_rope=int(cfg["qk_rope_head_dim"]), d_v=int(cfg["v_head_dim"]),
        q_rank=int(cfg["q_lora_rank"]), kv_rank=int(cfg["kv_lora_rank"]),
        rope_theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]),
        scale_q=bool(cfg["mla_scale_q_lora"]),
        scale_kv=bool(cfg["mla_scale_kv_lora"]))
    moe = MoEDims(
        dim=D, expert_dim=int(cfg["expert_ffn_hidden_size"]),
        n_routed=int(cfg["n_routed_experts_published"]),
        n_zero=int(cfg["zero_expert_num"]), top_k=int(cfg["moe_topk"]),
        scale=float(cfg["routed_scaling_factor"]),
        held=tuple(int(v) for v in cfg["experts_held"]))
    block = BlockSpec(mixer="mla", ffn="swiglu", norm="rmsnorm",
                      topology="scmoe")
    return StackSpec(dim=D, ffn_dim=int(cfg["ffn_hidden_size"]),
                     blocks=(block,) * int(cfg["num_layers"]),
                     positions="rope", eps=float(cfg["rms_norm_eps"]),
                     tied_head=False, mla=mla, moe=moe)


def control_histories(bench) -> list:
    """A seeded handful of the cell's own SHORT histories, for the control
    (two forwards each, in a lower precision and in float32)."""
    traffic = bench.lib("session_traffic")
    sessions = traffic.Sessions(bench.traffic, int(bench.config["vocab_size"]))
    rng = bench.lib("seeded").rng(bench.seed, 98)
    out = []
    for c in range(int(bench.traffic["connections"])):
        order = sessions.order(c)
        short = [i for i, h in enumerate(order) if h <= 1024]
        index = short[int(rng.integers(0, len(short)))]
        out.append(sessions.session(c, index)[int(rng.integers(0, 6))])
    return out[:int(bench.config.get("control_histories", 6))]


class SeededStack:
    """``core.persistent_model``'s loader protocol: the stored blob names
    this class, ``load`` hands the server the model."""

    @classmethod
    def load(cls, instance_id, params, ctx):
        from predictionio_tpu.data.bimap import BiMap
        from predictionio_tpu.models.sessionrec import SeqStackModel

        spec, weights = _HANDOVER.pop(instance_id)
        n_items = weights["embed"].shape[0]
        items = BiMap.from_vocab(list(map("i%d".__mod__, range(n_items))))
        stack = {"item_embed": {"embedding": weights["embed"]},
                 "head": weights["head"],
                 "final_norm": weights["final_norm"],
                 "blocks": weights["layers"]}
        return SeqStackModel(spec, stack, items, params.shape())


class Deployed:
    def __init__(self, server, weights, timings):
        self.server, self.weights, self.timings = server, weights, timings
        self.port = server.port
        self.model = server.deployment.models[0]

    @property
    def batcher(self):
        return getattr(self.server, "_batcher", None)

    def stop(self) -> None:
        self.server.stop()


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
        end_time=now, engine_id="bench_seq", engine_version="0",
        engine_variant="default", engine_factory="bench", batch="bench",
        data_source_params=json.dumps(ep["dataSourceParams"]),
        preparator_params=json.dumps(ep["preparatorParams"]),
        algorithms_params=json.dumps(ep["algorithmParamsList"]),
        serving_params=json.dumps(ep["servingParams"]),
    )
    storage.engine_instances().insert(instance)
    _HANDOVER[instance.id] = (stack_spec(cfg), weights)
    manifest = PersistentModelManifest(class_name="SeededStack",
                                       module_name=__name__)
    storage.models().insert(Model(id=instance.id,
                                  models=pickle.dumps([manifest])))
    timings["store_s"] = time.perf_counter() - t

    t = time.perf_counter()
    server = EngineServer(
        sessionrec_engine(), "bench_seq", host="127.0.0.1", port=0,
        storage=storage, slo_conf=cfg.get("slo"),
    ).start()
    timings["server_boot_s"] = time.perf_counter() - t
    return Deployed(server, weights, timings)
