"""Replicated METADATA / MODELDATA + tier-resolved
`pio status` exit codes (item 9).

The reference's metadata tier survives machine loss because
Elasticsearch replicates every index across its cluster
(elasticsearch/StorageClient.scala:42) and HDFS keeps 3 copies of each
model blob (hdfs/HDFSModels.scala:28). Here `REPLICAS=R` replicates
apps/keys/channels/manifests/instances and model blobs across the
first R storage servers: synchronous all-replica writes (loud failure
naming the dead endpoint), owner-preferring read failover, and
owner-authoritative anti-entropy via `pio storagerepair`.
"""

import dataclasses
import datetime as _dt

import pytest

from predictionio_tpu.data.metadata import (
    AccessKey,
    EngineInstance,
    EngineManifest,
    Model,
)
from predictionio_tpu.data.storage import (
    StorageError,
    StorageUnavailableError,
    set_storage,
)
from predictionio_tpu.serving.storage_server import StorageServer

from tests.test_sharded_storage import _client, _memory_storage

UTC = _dt.timezone.utc


@pytest.fixture()
def three_replicated():
    """Three storage servers, REPLICAS=2: metadata + models live on
    servers 0 and 1; events shard k lives on servers k, k+1 (mod 3)."""
    backends = [_memory_storage() for _ in range(3)]
    servers = [
        StorageServer(storage=b, host="127.0.0.1", port=0).start()
        for b in backends
    ]
    try:
        yield backends, servers, _client([s.port for s in servers],
                                         replicas=2)
    finally:
        for s in servers:
            s.stop()


def _instance(id="inst-1", status="COMPLETED"):
    t = _dt.datetime(2026, 3, 1, tzinfo=UTC)
    return EngineInstance(
        id=id, status=status, start_time=t, end_time=t,
        engine_id="eng", engine_version="0", engine_variant="default",
        engine_factory="tests.fake",
    )


def _seed_meta(client):
    app = client.apps().insert("repl-app")
    key = AccessKey.generate(app.id)
    client.access_keys().insert(key)
    ch = client.channels().insert("live", app.id)
    client.engine_manifests().insert(
        EngineManifest(id="eng", version="0", name="eng"))
    client.engine_instances().insert(_instance())
    client.models().insert(Model(id="inst-1", models=b"\x01\x02\x03"))
    return app, key, ch


def test_metadata_replicates_to_first_r_endpoints(three_replicated):
    backends, _, client = three_replicated
    app, key, ch = _seed_meta(client)

    # every record on BOTH metadata replicas with the SAME ids; none on
    # the third endpoint (it is an event shard only)
    for b in backends[:2]:
        got = b.apps().get_by_name("repl-app")
        assert got is not None and got.id == app.id
        assert b.access_keys().get(key.key) is not None
        assert [c.id for c in b.channels().get_by_app_id(app.id)] == [ch.id]
        assert b.engine_manifests().get("eng", "0") is not None
        assert b.engine_instances().get("inst-1") is not None
        assert b.models().get("inst-1").models == b"\x01\x02\x03"
    assert backends[2].apps().get_by_name("repl-app") is None
    assert backends[2].models().get("inst-1") is None


def test_reads_survive_metadata_home_death_writes_fail_loudly(
        three_replicated):
    backends, servers, client = three_replicated
    app, key, _ = _seed_meta(client)
    dead_url = f"http://127.0.0.1:{servers[0].port}"

    servers[0].stop()  # kill the metadata HOME

    # every read path the serving/deploy stack needs still answers
    assert client.apps().get_by_name("repl-app").id == app.id
    assert client.access_keys().get(key.key) is not None
    latest = client.engine_instances().get_latest_completed(
        "eng", "0", "default")
    assert latest is not None and latest.id == "inst-1"
    assert client.models().get("inst-1").models == b"\x01\x02\x03"

    # writes fail loudly, naming the dead endpoint
    with pytest.raises(StorageUnavailableError) as ei:
        client.apps().insert("another")
    assert dead_url in str(ei.value)
    with pytest.raises(StorageUnavailableError):
        client.engine_instances().insert(_instance(id="inst-2"))
    with pytest.raises(StorageUnavailableError):
        client.models().insert(Model(id="mx", models=b"zz"))
    # the failed instance/model writes left nothing behind anywhere
    assert backends[1].engine_instances().get("inst-2") is None
    assert backends[1].models().get("mx") is None

    # `pio status`: DEGRADED exit code — every tier still serving
    from predictionio_tpu.tools.cli import STATUS_DEGRADED, main as cli_main

    try:
        set_storage(client)
        assert cli_main(["status"]) == STATUS_DEGRADED
    finally:
        set_storage(None)


def test_engine_server_reload_survives_metadata_home_death(three_replicated):
    """A serving host must be able to /reload after the metadata home
    dies: get_latest_completed + the model blob both answer from the
    surviving replica."""
    from tests.test_servers import http, train_const
    from predictionio_tpu.serving.engine_server import EngineServer

    _, servers, client = three_replicated
    engine, _ = train_const(client)  # writes instance+model through
    # the replicated tier (all replicas up)
    es = EngineServer(engine, "const", host="127.0.0.1", port=0,
                      storage=client).start()
    try:
        base = f"http://127.0.0.1:{es.port}"
        assert http("POST", f"{base}/queries.json", {"mult": 5})[1] == \
            {"result": 15.0}

        servers[0].stop()  # metadata home dies

        status, _ = http("GET", f"{base}/reload")
        assert status == 200
        assert http("POST", f"{base}/queries.json", {"mult": 2})[1] == \
            {"result": 6.0}
    finally:
        es.stop()


def test_failed_metadata_insert_rolls_back(three_replicated):
    """A write that cannot reach the full replica set must leave no
    copy a read would serve (the event tier's rollback contract,
    applied to metadata)."""
    backends, servers, client = three_replicated

    servers[1].stop()  # kill the SUCCESSOR metadata replica

    # id-assigning insert: owner assigned the id, successor failed,
    # owner copy rolled back
    with pytest.raises(StorageUnavailableError):
        client.apps().insert("doomed")
    assert backends[0].apps().get_by_name("doomed") is None

    # successors-first writes: nothing ever landed on the owner
    with pytest.raises(StorageUnavailableError):
        client.engine_instances().insert(_instance(id="doomed-inst"))
    assert backends[0].engine_instances().get("doomed-inst") is None
    with pytest.raises(StorageUnavailableError):
        client.models().insert(Model(id="doomed-m", models=b"x"))
    assert backends[0].models().get("doomed-m") is None


def test_repair_meta_reconciles_diverged_replicas(three_replicated):
    backends, _, client = three_replicated
    app, key, ch = _seed_meta(client)

    # diverge by hand: the states partial failures leave behind
    backends[1].access_keys().delete(key.key)            # missing record
    backends[1].engine_instances().insert(_instance(id="orphan"))  # orphan
    stale = dataclasses.replace(app, description="stale")
    backends[1].apps().update(stale)                     # stale content
    backends[1].models().insert(Model(id="inst-1", models=b"CORRUPT"))

    stats = client.client_for("METADATA").repair_meta()
    assert stats["copied"] >= 3 and stats["deleted"] >= 1

    # post-repair: replica 1 mirrors the owner exactly
    assert backends[1].access_keys().get(key.key) is not None
    assert backends[1].engine_instances().get("orphan") is None
    assert backends[1].apps().get(app.id).description == app.description
    assert backends[1].models().get("inst-1").models == b"\x01\x02\x03"

    # a second repair finds nothing to do
    assert client.client_for("METADATA").repair_meta() == {"copied": 0, "deleted": 0}


def test_repair_meta_refuses_unreplicated():
    from predictionio_tpu.tools.commands import CommandError, repair_metadata

    backend = _memory_storage()
    server = StorageServer(storage=backend, host="127.0.0.1", port=0).start()
    try:
        client = _client([server.port, server.port])  # sharded, REPLICAS=1
        with pytest.raises(StorageError):
            client.client_for("METADATA").repair_meta()
        # through the command layer BOTH unreplicated shapes are the
        # same "nothing to check" CommandError (the CLI then reports
        # the tier as skipped instead of failing a completed event
        # repair — code-review regression)
        with pytest.raises(CommandError):
            repair_metadata(storage=client)
        with pytest.raises(CommandError):
            repair_metadata(storage=backend)  # memory: no repair surface
    finally:
        server.stop()


def test_storagerepair_cli_covers_both_tiers(three_replicated, capsys):
    """`pio storagerepair` reconciles the app's events AND the
    metadata/model replica set in one run."""
    backends, _, client = three_replicated
    app, key, _ = _seed_meta(client)
    client.events().init(app.id)
    backends[1].access_keys().delete(key.key)  # metadata divergence

    from predictionio_tpu.tools.cli import main as cli_main

    try:
        set_storage(client)
        assert cli_main(["storagerepair", "--appname", "repl-app"]) == 0
        out = capsys.readouterr().out
        assert "Event replica repair" in out
        assert "Metadata/model replica repair" in out
    finally:
        set_storage(None)
    assert backends[1].access_keys().get(key.key) is not None


def test_status_exit_codes_distinguish_tiers(three_replicated):
    """0 = all endpoints up; 2 = degraded but every tier serving;
    1 = some tier cannot answer."""
    from predictionio_tpu.tools.cli import STATUS_DEGRADED, main as cli_main

    backends, servers, client = three_replicated
    try:
        set_storage(client)
        assert cli_main(["status"]) == 0

        # a pure event replica down: every shard still has a live
        # replica, metadata home untouched -> DEGRADED
        servers[2].stop()
        assert cli_main(["status"]) == STATUS_DEGRADED

        # two servers down: event shard 1 (replicas on 1 and 2) has no
        # live copy -> hard failure
        servers[1].stop()
        assert cli_main(["status"]) == 1
    finally:
        set_storage(None)


def test_status_exit_1_when_metadata_tier_dies():
    """Both metadata replicas down (events still fine on server 2 is
    impossible with R=2 over 3 servers — shard coverage also breaks —
    but the metadata tier must independently report FAILED)."""
    backends = [_memory_storage() for _ in range(3)]
    servers = [StorageServer(storage=b, host="127.0.0.1", port=0).start()
               for b in backends]
    client = _client([s.port for s in servers], replicas=2)
    try:
        set_storage(client)
        servers[0].stop()
        servers[1].stop()
        tiers = client.client_for("METADATA").health_tiers()
        assert tiers["metadata_serving"] is False
        from predictionio_tpu.tools.cli import main as cli_main

        assert cli_main(["status"]) == 1
    finally:
        set_storage(None)
        for s in servers:
            s.stop()


def test_repair_refuses_blank_owner(three_replicated):
    """Code-review regression: a re-provisioned BLANK metadata owner
    must never erase the surviving replicas records via repair."""
    backends, _, client = three_replicated
    app, key, _ = _seed_meta(client)

    # wipe the OWNER only (the re-provisioned-blank-host scenario)
    backends[0].apps().delete(app.id)
    backends[0].access_keys().delete(key.key)
    with pytest.raises(StorageError, match="repair refused"):
        client.client_for("METADATA").repair_meta()
    # the replica records survived
    assert backends[1].apps().get_by_name("repl-app") is not None

    # blank owner MODELS only: also refused
    backends[0].apps().put(app)           # restore records
    backends[0].access_keys().put(key)
    backends[0].models().delete("inst-1")
    with pytest.raises(StorageError, match="no model blobs"):
        client.client_for("METADATA").repair_meta()
    assert backends[1].models().get("inst-1") is not None
