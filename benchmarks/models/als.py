"""Model builder ``als``: seeded ALS factors behind the real EngineServer.

No ``pio`` child, no parquet, no import and no event store: the factors are
made from the seed, an ``ALSModel`` goes into an in-memory models repository,
and the program's own ``EngineServer`` loads it (``prepare_deploy``: unpickle,
index build, warm-up) and serves it on a local port, all in this process
(the pattern of the pre-chip benchmark script's serve stage; that script left
the tree at PR 29).
"""

from __future__ import annotations

import datetime as dt
import json
import pickle
import time
import uuid

USER_STREAM, ITEM_STREAM = 1, 2


def factor_scale(rank: int) -> float:
    # score = sum of `rank` products of N(0, s^2) pairs: s = rank^-1/4
    # gives scores of unit standard deviation whatever the rank
    return float(rank) ** -0.25


def make_factors(bench):
    """(X [n_users, rank], Y [n_items, rank]) float32 from the seed — the
    benchmark's own weights, handed to the program and to the reference."""
    seeded = bench.lib("seeded")
    cfg = bench.config
    rank = int(cfg["rank"])
    scale = factor_scale(rank)
    X = seeded.normal_table(bench.seed, USER_STREAM, int(cfg["n_users"]),
                            rank, scale)
    Y = seeded.normal_table(bench.seed, ITEM_STREAM, int(cfg["n_items"]),
                            rank, scale)
    return X, Y


def user_id(row: int) -> str:
    return f"u{row}"


def item_row(item_id: str) -> int:
    return int(item_id[1:])


class Deployed:
    """The deployed engine: ``server`` (the program's EngineServer), its
    ``port``, and the factors it was given."""

    def __init__(self, server, X, Y, timings):
        self.server, self.X, self.Y = server, X, Y
        self.port = server.port
        self.timings = timings

    @property
    def batcher(self):
        return getattr(self.server, "_batcher", None)

    def stop(self) -> None:
        self.server.stop()


def deploy(bench) -> Deployed:
    from predictionio_tpu.core.params import EngineParams
    from predictionio_tpu.data.bimap import BiMap
    from predictionio_tpu.data.metadata import EngineInstance, Model
    from predictionio_tpu.data.storage import Storage
    from predictionio_tpu.models.als import ALSModel, ALSParams
    from predictionio_tpu.ops.als import ALSFactors
    from predictionio_tpu.serving.engine_server import EngineServer
    from predictionio_tpu.templates.recommendation import (
        RecoDataSourceParams, recommendation_engine)

    cfg = bench.config
    timings = {}
    t = time.perf_counter()
    X, Y = make_factors(bench)
    timings["factors_s"] = time.perf_counter() - t

    t = time.perf_counter()
    users = BiMap.from_vocab(list(map("u%d".__mod__, range(X.shape[0]))))
    items = BiMap.from_vocab(list(map("i%d".__mod__, range(Y.shape[0]))))
    timings["id_maps_s"] = time.perf_counter() - t

    t = time.perf_counter()
    storage = Storage.from_env({
        "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
        **{f"PIO_STORAGE_REPOSITORIES_{r}_{k}": v
           for r in ("METADATA", "EVENTDATA", "MODELDATA")
           for k, v in (("NAME", r.lower()), ("SOURCE", "MEM"))},
    })
    engine = recommendation_engine()
    ep = EngineParams(
        data_source_params=("", RecoDataSourceParams(app_name="bench")),
        preparator_params=("", None),
        algorithm_params_list=[("als", ALSParams(
            rank=int(cfg["rank"]), lambda_=float(cfg["lambda_"])))],
        serving_params=("", None),
    ).to_json_dict()
    now = dt.datetime.now(tz=dt.timezone.utc)
    instance = EngineInstance(
        id=uuid.uuid4().hex, status="COMPLETED", start_time=now,
        end_time=now, engine_id="bench_reco", engine_version="0",
        engine_variant="default", engine_factory="bench", batch="bench",
        data_source_params=json.dumps(ep["dataSourceParams"]),
        preparator_params=json.dumps(ep["preparatorParams"]),
        algorithms_params=json.dumps(ep["algorithmParamsList"]),
        serving_params=json.dumps(ep["servingParams"]),
    )
    storage.engine_instances().insert(instance)
    model = ALSModel(ALSFactors(user_factors=X, item_factors=Y),
                     users, items)
    storage.models().insert(Model(
        id=instance.id,
        models=pickle.dumps([model], protocol=pickle.HIGHEST_PROTOCOL)))
    del model, users, items
    timings["store_s"] = time.perf_counter() - t

    t = time.perf_counter()
    server = EngineServer(
        engine, "bench_reco", host="127.0.0.1", port=0, storage=storage,
        slo_conf=cfg.get("slo"),
    ).start()
    timings["server_boot_s"] = time.perf_counter() - t
    return Deployed(server, X, Y, timings)
