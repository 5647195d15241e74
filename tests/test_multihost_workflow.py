"""The PRODUCT workflow across real process boundaries.

Two jax.distributed CPU processes drive the actual `pio train` path —
``workflow.train.run_train`` with the recommendation template — against
ONE shared storage server (rest backend), not ops-level calls:

  - each host reads only its entity-hash shard of the events
    (server-side filtered find_columnar; proven from the server's own
    scan counters) and reassembles full training data over the job's
    interconnect (exchange_columns);
  - storage writes are single-writer: process 0 owns the EngineInstance
    row and model blob, the instance id is broadcast, and both
    processes return the same COMPLETED instance;
  - process 1 then DEPLOYS the instance process 0 persisted
    (prepare_deploy from the shared store) and answers a query —
    train-on-A/deploy-on-B through the real workflow.

Reference equivalents: per-executor HBase region scans
(hbase/HBPEvents.scala:48) + driver-only metadata writes
(CoreWorkflow.scala:60-81) + cross-JVM deploy (CreateServer.scala:190).
"""

import datetime as _dt
import json
import os
import socket
import subprocess
import sys
import urllib.request

import numpy as np

from predictionio_tpu.data.event import Event
from predictionio_tpu.serving.storage_server import StorageServer

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
UTC = _dt.timezone.utc

N_USERS = 20
N_ITEMS = 8
EVENTS_PER_USER = 6

_WORKER = """
import jax
jax.config.update("jax_platforms", "cpu")

from predictionio_tpu.parallel import multihost as mh

assert mh.initialize_from_env() is True, "distributed init did not engage"
assert jax.process_count() == 2

from predictionio_tpu.core.params import EngineParams
from predictionio_tpu.models.als import ALSParams
from predictionio_tpu.templates import recommendation as reco_t
from predictionio_tpu.workflow.train import run_train

engine = reco_t.recommendation_engine()
ep = EngineParams(
    data_source_params=(
        "", reco_t.RecoDataSourceParams(app_name="mhapp", columnar=True)),
    algorithm_params_list=[
        ("als", ALSParams(rank=4, num_iterations=2, block_size=8,
                          compute_dtype="float32", cg_dtype="float32")),
    ],
)
inst = run_train(engine, ep, engine_id="mh-reco")
assert inst.status == "COMPLETED"
print("INSTANCE", inst.id)

if mh.process_index() == 1:
    from predictionio_tpu.data.storage import get_storage
    from predictionio_tpu.workflow.deploy import prepare_deploy

    stored = get_storage().engine_instances().get_latest_completed(
        "mh-reco", "0", "default")
    assert stored is not None, "COMPLETED instance not visible on host B"
    assert stored.id == inst.id
    dep = prepare_deploy(engine, stored)
    res = dep.query({"user": "user_1", "num": 3})
    assert res["itemScores"], res
    print("DEPLOY OK", res["itemScores"][0]["item"])

# keep process 0 (the distributed coordinator) alive until the deploy
# on process 1 has finished
mh.barrier("pio_test_done")
print(f"MHWF OK p{mh.process_index()}")
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _seed(storage):
    app = storage.apps().insert("mhapp")
    storage.events().init(app.id)
    rng = np.random.default_rng(7)
    events, m = [], 0
    for u in range(N_USERS):
        for i in rng.choice(N_ITEMS, size=EVENTS_PER_USER, replace=False):
            events.append(Event(
                event="rate",
                entity_type="user",
                entity_id=f"user_{u}",
                target_entity_type="item",
                target_entity_id=f"item_{i}",
                properties={"rating": float(1 + (u * int(i)) % 5)},
                event_time=_dt.datetime(2026, 1, 1, tzinfo=UTC)
                + _dt.timedelta(minutes=m),
            ))
            m += 1
    storage.events().insert_batch(events, app.id)
    return len(events)


def _worker_env(coord_port, pid, ports, replicas=None):
    env = dict(os.environ)
    env.pop("PYTEST_CURRENT_TEST", None)
    env.update({
        "PYTHONPATH": REPO_ROOT,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        "PIO_COORDINATOR_ADDRESS": f"127.0.0.1:{coord_port}",
        "PIO_NUM_PROCESSES": "2",
        "PIO_PROCESS_ID": str(pid),
        "PIO_STORAGE_SOURCES_CENTRAL_TYPE": "rest",
        "PIO_STORAGE_SOURCES_CENTRAL_HOSTS": "127.0.0.1",
        "PIO_STORAGE_SOURCES_CENTRAL_PORTS": ",".join(str(p) for p in ports),
        "PIO_STORAGE_REPOSITORIES_METADATA_NAME": "meta",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "CENTRAL",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_NAME": "events",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "CENTRAL",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_NAME": "models",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "CENTRAL",
    })
    if replicas is not None:
        env["PIO_STORAGE_SOURCES_CENTRAL_REPLICAS"] = str(replicas)
    return env


def _run_workers(coord_port, ports, replicas=None):
    procs, outs = [], []
    try:
        for pid in range(2):
            procs.append(subprocess.Popen(
                [sys.executable, "-c", _WORKER], cwd=REPO_ROOT,
                env=_worker_env(coord_port, pid, ports, replicas),
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            ))
        for p in procs:
            out, _ = p.communicate(timeout=420)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    return procs, outs


def test_two_process_train_and_deploy_via_shared_storage(memory_storage):
    n_events = _seed(memory_storage)
    server = StorageServer(storage=memory_storage, host="127.0.0.1",
                           port=0).start()
    try:
        procs, outs = _run_workers(_free_port(), [server.port])
    finally:
        server.stop()

    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"process {pid} failed:\n{out}"
        assert f"MHWF OK p{pid}" in out
    assert "DEPLOY OK" in outs[1]

    # both processes returned the SAME broadcast instance id
    ids = {
        line.split()[1]
        for out in outs for line in out.splitlines()
        if line.startswith("INSTANCE ")
    }
    assert len(ids) == 1, ids

    # single-writer: exactly one EngineInstance row, one model blob
    instances = memory_storage.engine_instances().get_all()
    assert len(instances) == 1 and instances[0].status == "COMPLETED"
    assert memory_storage.models().get(instances[0].id) is not None

    # host-sharded reads, proven by the server's own counters: one
    # sharded scan per host, together covering every row, each ~1/2
    stats = StorageServer.scan_stats(server)
    scans = stats["columnar_scans"]
    assert len(scans) == 2, scans
    by_shard = {s["shard_index"]: s["rows"] for s in scans}
    assert by_shard.keys() == {0, 1}
    assert all(s["shard_count"] == 2 for s in scans)
    assert sum(by_shard.values()) == n_events
    for rows in by_shard.values():
        assert 0.25 * n_events < rows < 0.75 * n_events, by_shard


def test_multihost_train_survives_dead_storage_replica():
    """The capstone composition: 2
    jax.distributed processes run the real train→deploy workflow
    against a 3-server REPLICATED (R=2) storage tier with one event
    replica KILLED before training — reads fail over to surviving
    replicas and the whole product path completes. THEN the METADATA
    HOME (server 0) is killed too: get_latest_completed, the model
    blob fetch and a fresh deploy+query all still answer from the
    surviving metadata replica, while metadata writes fail loudly
    naming the dead endpoint. The reference's analogue is HBase riding
    out a dead region server on HDFS replicas while Elasticsearch
    serves metadata from its replica shards."""
    backends = []
    servers = []
    for _ in range(3):
        from predictionio_tpu.data.storage import Storage

        b = Storage.from_env({
            "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
            "PIO_STORAGE_REPOSITORIES_METADATA_NAME": "meta",
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_NAME": "events",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_NAME": "models",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
        })
        backends.append(b)
        servers.append(StorageServer(storage=b, host="127.0.0.1",
                                     port=0).start())
    ports = [s.port for s in servers]
    try:
        # seed THROUGH the replicated client: event copies land on each
        # shard's successor pair, metadata/models on servers 0 AND 1
        from tests.test_sharded_storage import _client

        seeder = _client(ports, replicas=2)
        seeder.apps().insert("mhapp")
        import numpy as np

        rng = np.random.default_rng(7)
        events, m = [], 0
        seeder.events().init(1)
        for u in range(N_USERS):
            for i in rng.choice(N_ITEMS, size=EVENTS_PER_USER,
                                replace=False):
                events.append(Event(
                    event="rate", entity_type="user",
                    entity_id=f"user_{u}",
                    target_entity_type="item",
                    target_entity_id=f"item_{i}",
                    properties={"rating": float(1 + (u * int(i)) % 5)},
                    event_time=_dt.datetime(2026, 1, 1, tzinfo=UTC)
                    + _dt.timedelta(minutes=m),
                ))
                m += 1
        seeder.events().insert_batch(events, 1)
        assert backends[1].apps().get_by_name("mhapp") is not None  # meta
        # replicated onto the successor

        servers[2].stop()  # kill a pure event replica before training

        procs, outs = _run_workers(_free_port(), ports, replicas=2)
        for pid, (p, out) in enumerate(zip(procs, outs)):
            assert p.returncode == 0, f"process {pid} failed:\n{out}"
            assert f"MHWF OK p{pid}" in out
        assert "DEPLOY OK" in outs[1]
        # single-writer metadata landed on BOTH replicas
        for b in backends[:2]:
            instances = b.engine_instances().get_all()
            assert len(instances) == 1 and instances[0].status == "COMPLETED"
            assert b.models().get(instances[0].id) is not None

        # -- now kill the METADATA HOME ---------------------------------
        servers[0].stop()
        from predictionio_tpu.data.storage import StorageUnavailableError
        from predictionio_tpu.workflow.deploy import prepare_deploy
        from predictionio_tpu.core.params import EngineParams  # noqa: F401
        from predictionio_tpu.templates import recommendation as reco_t

        survivor = _client(ports, replicas=2)
        stored = survivor.engine_instances().get_latest_completed(
            "mh-reco", "0", "default")
        assert stored is not None, "metadata failover read failed"
        assert survivor.models().get(stored.id) is not None
        dep = prepare_deploy(reco_t.recommendation_engine(), stored,
                             storage=survivor)
        res = dep.query({"user": "user_1", "num": 3})
        assert res["itemScores"], res
        # writes fail loudly, naming the dead home
        import pytest as _pytest

        with _pytest.raises(StorageUnavailableError) as ei:
            survivor.apps().insert("postmortem")
        assert f"http://127.0.0.1:{ports[0]}" in str(ei.value)
    finally:
        for s in servers:
            s.stop()
