"""EVENTDATA sharded across N storage servers.

The reference's event store scales horizontally because HBase splits
tables into regions by the MD5 rowkey prefix and spreads them across
region servers (hbase/HBEventsUtil.scala:47,96-108). Here the same
partition function (storage.stable_hash on entity id) routes the rest
client's writes across N storage servers; reads fan out and merge; a
down shard fails loudly naming its endpoint; `pio status` reports
per-shard health.
"""

import dataclasses
import datetime as _dt

import numpy as np
import pytest

from predictionio_tpu.data.event import Event
from predictionio_tpu.data.storage import (
    Storage,
    StorageUnavailableError,
    stable_hash,
)
from predictionio_tpu.serving.storage_server import StorageServer

from tests.test_sharded_reads import _decode

UTC = _dt.timezone.utc


def _memory_storage() -> Storage:
    return Storage.from_env({
        "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
        "PIO_STORAGE_REPOSITORIES_METADATA_NAME": "meta",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_NAME": "events",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_NAME": "models",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
    })


def _client(ports, replicas=None) -> Storage:
    env = {
        "PIO_STORAGE_SOURCES_SH_TYPE": "rest",
        "PIO_STORAGE_SOURCES_SH_HOSTS": "127.0.0.1",
        "PIO_STORAGE_SOURCES_SH_PORTS": ",".join(str(p) for p in ports),
        "PIO_STORAGE_SOURCES_SH_RETRIES": "0",
        "PIO_STORAGE_SOURCES_SH_TIMEOUT": "5",
        "PIO_STORAGE_REPOSITORIES_METADATA_NAME": "meta",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "SH",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_NAME": "events",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "SH",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_NAME": "models",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "SH",
    }
    if replicas is not None:
        env["PIO_STORAGE_SOURCES_SH_REPLICAS"] = str(replicas)
    return Storage.from_env(env)


@pytest.fixture()
def two_servers():
    """Two storage servers over independent backends + sharded client."""
    backends = [_memory_storage(), _memory_storage()]
    servers = [
        StorageServer(storage=b, host="127.0.0.1", port=0).start()
        for b in backends
    ]
    try:
        yield backends, servers, _client([s.port for s in servers])
    finally:
        for s in servers:
            s.stop()


def _events(n=80, users=13, items=6):
    out = []
    for i in range(n):
        out.append(Event(
            event="rate",
            entity_type="user",
            entity_id=f"user_{i % users}",
            target_entity_type="item",
            target_entity_id=f"item_{i % items}",
            properties={"rating": float(1 + i % 5)},
            event_time=_dt.datetime(2026, 2, 1, tzinfo=UTC)
            + _dt.timedelta(minutes=i),
        ))
    return out


def test_writes_route_by_entity_hash_and_reads_merge(two_servers):
    backends, _, client = two_servers
    store = client.events()
    store.init(1)
    events = _events()
    ids = store.insert_batch(events, 1)
    assert len(ids) == len(set(ids)) == len(events)

    # each backend holds exactly the entity-hash share; both non-empty
    per_server = [b.events().find(1) for b in backends]
    assert all(len(p) > 0 for p in per_server)
    assert sum(len(p) for p in per_server) == len(events)
    for s, part in enumerate(per_server):
        for e in part:
            assert stable_hash(e.entity_id) % 2 == s

    # merged find equals the oracle: same events, globally time-ordered
    merged = store.find(1)
    assert [e.event_time for e in merged] == sorted(e.event_time for e in events)
    assert {(e.entity_id, e.target_entity_id, e.event_time) for e in merged} \
        == {(e.entity_id, e.target_entity_id, e.event_time) for e in events}

    # limit + reversed apply AFTER the merge
    newest = store.find(1, limit=5, reversed=True)
    assert [e.event_time for e in newest] == sorted(
        (e.event_time for e in events), reverse=True)[:5]


def test_columnar_fanout_matches_single_store_oracle(two_servers):
    _, _, client = two_servers
    store = client.events()
    store.init(1)
    events = _events()
    store.insert_batch(events, 1)

    oracle = _memory_storage()
    oracle.events().init(1)
    oracle.events().insert_batch(events, 1)
    expected = oracle.events().find_columnar(
        1, value_property="rating", time_ordered=False)

    merged = store.find_columnar(1, value_property="rating",
                                 time_ordered=False)
    assert sorted(_decode(merged)) == sorted(_decode(expected))

    # host read shards compose with server shards: union of the host
    # shards == everything, each filtered consistently
    host_shards = [
        store.find_columnar(1, value_property="rating", time_ordered=False,
                            shard_index=h, shard_count=2)
        for h in range(2)
    ]
    assert sum(len(s) for s in host_shards) == len(expected)
    for h, s in enumerate(host_shards):
        for ent in s.entity_vocab:
            assert stable_hash(ent) % 2 == h


def test_columnar_limit_respects_reversed_across_shards(two_servers):
    """limit + reversed must keep the global NEWEST rows (find's
    order-then-truncate contract), not the head of the ascending merge
    (code-review regression)."""
    _, _, client = two_servers
    store = client.events()
    store.init(1)
    events = _events(n=40)
    store.insert_batch(events, 1)

    got = store.find_columnar(1, time_ordered=True, limit=7, reversed=True)
    newest = sorted((e.event_time for e in events), reverse=True)[:7]
    assert [int(t.timestamp() * 1e6) for t in newest] == list(got.times_us)

    got2 = store.find_columnar(1, time_ordered=True, limit=7)
    oldest = sorted(int(e.event_time.timestamp() * 1e6) for e in events)[:7]
    assert oldest == list(got2.times_us)


def test_columnar_bulk_ingest_shards(two_servers):
    backends, _, client = two_servers
    store = client.events()
    store.init(1)
    oracle = _memory_storage()
    oracle.events().init(1)
    oracle.events().insert_batch(_events(), 1)
    cols = oracle.events().find_columnar(1, value_property="rating",
                                         time_ordered=False)

    n = store.insert_columnar(cols, 1, entity_type="user",
                              target_entity_type="item",
                              value_property="rating")
    assert n == len(cols)
    per_server = [len(b.events().find(1)) for b in backends]
    assert all(c > 0 for c in per_server) and sum(per_server) == n
    back = store.find_columnar(1, value_property="rating",
                               time_ordered=False)
    assert sorted(_decode(back)) == sorted(_decode(cols))


def test_point_ops_across_shards(two_servers):
    _, _, client = two_servers
    store = client.events()
    store.init(1)
    events = _events(n=10)
    ids = store.insert_batch(events, 1)
    for eid, ev in zip(ids, events):
        got = store.get(eid, 1)
        assert got is not None and got.entity_id == ev.entity_id
    assert store.get("nonexistent", 1) is None
    assert store.delete(ids[0], 1) is True
    assert store.get(ids[0], 1) is None
    assert store.delete(ids[0], 1) is False


def test_down_shard_fails_loudly_naming_it(two_servers):
    backends, servers, client = two_servers
    store = client.events()
    store.init(1)
    store.insert_batch(_events(n=20), 1)

    dead_url = f"http://127.0.0.1:{servers[1].port}"
    servers[1].stop()

    with pytest.raises(StorageUnavailableError) as ei:
        store.find(1)
    assert dead_url in str(ei.value)
    with pytest.raises(StorageUnavailableError) as ei:
        store.find_columnar(1, time_ordered=False)
    assert dead_url in str(ei.value)

    # per-shard health names the down endpoint; repo health fails
    details = client.health_details()
    ev = details["EVENTDATA"]
    assert ev[f"http://127.0.0.1:{servers[0].port}"] is True
    assert ev[dead_url] is False
    assert client.verify_all_data_objects()["EVENTDATA"] is False


@pytest.fixture()
def three_servers_r2():
    """Three storage servers, REPLICAS=2: shard k lives on servers k and
    k+1 (mod 3) — any ONE server can die and reads stay complete."""
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


def test_replicated_writes_land_on_every_replica(three_servers_r2):
    backends, _, client = three_servers_r2
    store = client.events()
    store.init(1)
    events = _events(n=60)
    ids = store.insert_batch(events, 1)
    assert len(set(ids)) == len(events)

    # every row exists on exactly 2 of the 3 servers, same id on both
    per_server = [
        {e.event_id for e in b.events().find(1)} for b in backends
    ]
    assert sum(len(p) for p in per_server) == 2 * len(events)
    for eid, ev in zip(ids, events):
        holders = [s for s, p in enumerate(per_server) if eid in p]
        shard = stable_hash(ev.entity_id) % 3
        assert holders == sorted({shard, (shard + 1) % 3})

    # reads with all servers up: no duplicates
    assert len(store.find(1)) == len(events)
    cols = store.find_columnar(1, time_ordered=False)
    assert len(cols) == len(events)

    # delete removes every copy
    assert store.delete(ids[0], 1) is True
    assert all(ids[0] not in {e.event_id for e in b.events().find(1)}
               for b in backends)


def test_replicated_reads_survive_one_server_down(three_servers_r2):
    backends, servers, client = three_servers_r2
    store = client.events()
    store.init(1)
    events = _events(n=60)
    store.insert_batch(events, 1)
    oracle_rows = sorted(
        (e.entity_id, e.target_entity_id, e.event_time) for e in events)

    servers[1].stop()  # kill one replica; every shard still has a copy

    merged = store.find(1)
    assert sorted((e.entity_id, e.target_entity_id, e.event_time)
                  for e in merged) == oracle_rows
    cols = store.find_columnar(1, value_property="rating",
                               time_ordered=False)
    assert len(cols) == len(events)

    # limit + reversed still the global newest
    newest = store.find_columnar(1, time_ordered=True, limit=5,
                                 reversed=True)
    exp = sorted((e.event_time for e in events), reverse=True)[:5]
    assert [int(t.timestamp() * 1e6) for t in exp] == list(newest.times_us)

    # host read shards compose (client-side under replication)
    host_shards = [
        store.find_columnar(1, time_ordered=False, shard_index=h,
                            shard_count=2)
        for h in range(2)
    ]
    assert sum(len(s) for s in host_shards) == len(events)

    # point reads still answer from the surviving copy
    eid = merged[0].event_id
    assert store.get(eid, 1) is not None


def test_find_placement_filter_on_wire(two_servers):
    """The row find wire's placement filter: a server holding several
    shards' copies sends only the requested shards' rows, limit applied
    after the filter (code-review regression)."""
    backends, servers, _ = two_servers
    backends[0].events().init(1)
    backends[0].events().insert_batch(_events(n=40), 1)

    from predictionio_tpu.data.backends.rest import RestEventStore, _Transport

    st = RestEventStore(
        _Transport(f"http://127.0.0.1:{servers[0].port}", None, 10))
    full = st.find(1)
    only0 = st.find(1, placement_shards=[0], placement_count=2)
    assert 0 < len(only0) < len(full)
    assert all(stable_hash(e.entity_id) % 2 == 0 for e in only0)
    # limit applies AFTER the placement filter
    lim = st.find(1, placement_shards=[0], placement_count=2, limit=3)
    assert [e.event_id for e in lim] == [e.event_id for e in only0[:3]]


def test_multi_shard_batch_rolls_back_all_groups():
    """A failed multi-shard replicated batch must roll back EVERY shard
    group it committed, not just the failing one — a retry with fresh
    ids would otherwise duplicate the committed group's rows
    (code-review regression)."""
    backends = [_memory_storage(), _memory_storage()]
    servers = [
        StorageServer(storage=b, host="127.0.0.1", port=0).start()
        for b in backends
    ]
    try:
        client = _client([s.port for s in servers], replicas=2)
        store = client.events()
        store.init(1)
        # events spanning BOTH shards
        batch = _events(n=20)
        assert len({stable_hash(e.entity_id) % 2 for e in batch}) == 2
        servers[0].stop()
        with pytest.raises(StorageUnavailableError):
            store.insert_batch(batch, 1)
        # whichever shard group committed to the live server first was
        # rolled back when the dead server failed the other group
        assert backends[1].events().find(1) == []
    finally:
        for s in servers:
            s.stop()


def test_partial_replica_write_rolls_back():
    """A replica write that fails midway must not leave a copy that
    reads would serve: the already-written copies are deleted by their
    client-stamped ids (code-review regression)."""
    backends = [_memory_storage(), _memory_storage()]
    servers = [
        StorageServer(storage=b, host="127.0.0.1", port=0).start()
        for b in backends
    ]
    try:
        client = _client([s.port for s in servers], replicas=2)
        store = client.events()
        store.init(1)

        def uid_for_shard(s):
            i = 0
            while stable_hash(f"user_{i}") % 2 != s:
                i += 1
            return f"user_{i}"

        servers[0].stop()
        ev = _events(n=1)[0]

        # owner = dead server 0: the successor (server 1) is written
        # first, the owner write fails, and the rollback removes the
        # successor's copy — the live server serves nothing
        ev_owner_dead = dataclasses.replace(ev, entity_id=uid_for_shard(0))
        with pytest.raises(StorageUnavailableError):
            store.insert(ev_owner_dead, 1)
        assert backends[1].events().find(1) == []

        # owner = live server 1: its successor (server 0) is written
        # FIRST and is dead, so nothing lands anywhere
        ev_successor_dead = dataclasses.replace(
            ev, entity_id=uid_for_shard(1))
        with pytest.raises(StorageUnavailableError):
            store.insert(ev_successor_dead, 1)
        assert backends[1].events().find(1) == []

        # batch path rolls back too
        batch = [dataclasses.replace(e, entity_id=uid_for_shard(0))
                 for e in _events(n=5)]
        with pytest.raises(StorageUnavailableError):
            store.insert_batch(batch, 1)
        assert backends[1].events().find(1) == []
    finally:
        for s in servers:
            s.stop()


def test_repair_reconciles_diverged_replicas(three_servers_r2):
    """Owner-authoritative anti-entropy: after repair, every replica
    holds exactly its shards' owner rows — rollback leftovers and
    divergent copies are reconciled (the HDFS block-repair role)."""
    backends, _, client = three_servers_r2
    store = client.events()
    store.init(1)
    events = _events(n=45)
    store.insert_batch(events, 1)

    # diverge by hand: drop one REPLICA copy (server 1 replicates shard
    # 0 — deleting an owner copy would be authoritative, not
    # divergence), plant an orphan on another replica (the states
    # partial failures leave behind)
    victim = next(e for e in backends[1].events().find(1)
                  if stable_hash(e.entity_id) % 3 == 0)
    backends[1].events().delete(victim.event_id, 1)
    orphan_shard = next(s for s in range(3)
                        if stable_hash("orphan_u") % 3 == s)
    replica_of_orphan = (orphan_shard + 1) % 3
    backends[replica_of_orphan].events().insert(
        dataclasses.replace(events[0], entity_id="orphan_u"), 1)

    stats = store.repair(1)
    assert stats["copied"] >= 1 and stats["deleted"] >= 1

    # post-repair invariant: each server holds exactly the owner rows
    # of the shards it replicates
    for srv, b in enumerate(backends):
        rows = b.events().find(1)
        my_shards = {srv, (srv - 1) % 3}
        expected = {
            e.event_id for e in store.find(1)
            if stable_hash(e.entity_id) % 3 in my_shards
        }
        assert {e.event_id for e in rows} == expected
    # merged reads are clean and complete (no orphan, nothing missing)
    merged = store.find(1)
    assert len(merged) == len(events)
    assert all(e.entity_id != "orphan_u" for e in merged)


def test_repair_recognizes_columnar_ingested_copies(three_servers_r2):
    """Columnar-ingested replicas carry per-server ids; repair must
    match them by CONTENT and leave them alone, not rewrite every
    replica (code-review regression)."""
    _, _, client = three_servers_r2
    store = client.events()
    store.init(1)
    oracle = _memory_storage()
    oracle.events().init(1)
    oracle.events().insert_batch(_events(n=45), 1)
    cols = oracle.events().find_columnar(1, value_property="rating",
                                         time_ordered=False)
    store.insert_columnar(cols, 1, entity_type="user",
                          target_entity_type="item",
                          value_property="rating")
    stats = store.repair(1)
    assert stats == {"copied": 0, "deleted": 0}, stats
    assert len(store.find(1)) == 45


def test_repair_cli_refuses_unreplicated_backend(two_servers, memory_storage):
    """`pio storagerepair` must fail loudly when there is nothing to
    check — a zeros result would read as "consistent"."""
    from predictionio_tpu.data.storage import StorageError
    from predictionio_tpu.tools.commands import CommandError, repair_events

    # sharded but unreplicated: repair() itself owns the guard
    _, _, client = two_servers
    client.apps().insert("shapp2")
    with pytest.raises(StorageError):
        repair_events("shapp2", storage=client)
    # plain unsharded backend: no repair surface at all
    memory_storage.apps().insert("plain")
    with pytest.raises(CommandError):
        repair_events("plain", storage=memory_storage)


def test_replicas_exceeding_servers_rejected():
    from predictionio_tpu.data.storage import StorageError

    with pytest.raises(StorageError):
        _client([7001, 7002], replicas=3)
    with pytest.raises(StorageError):
        _client([7001], replicas=2)


def test_event_server_ingests_to_sharded_tier(two_servers):
    """Live traffic through the whole stack: HTTP POST /events.json on
    the Event Server, whose storage is the sharded rest client — rows
    hash-route across both storage servers, GET round-trips through
    the fan-out read path. (SDK -> event server -> sharded store, the
    reference's SDK -> EventAPI -> HBase regions pipeline, §3.3.)"""
    import json as _json
    import urllib.request

    from predictionio_tpu.data.metadata import AccessKey
    from predictionio_tpu.serving.event_server import EventServer

    backends, _, client = two_servers
    app = client.apps().insert("live-app")
    client.events().init(app.id)
    key = AccessKey.generate(app.id)
    client.access_keys().insert(key)
    es = EventServer(storage=client, host="127.0.0.1", port=0).start()
    try:
        base = f"http://127.0.0.1:{es.port}"
        ids = []
        for i in range(12):
            req = urllib.request.Request(
                f"{base}/events.json?accessKey={key.key}",
                data=_json.dumps({
                    "event": "rate", "entityType": "user",
                    "entityId": f"user_{i}", "targetEntityType": "item",
                    "targetEntityId": f"item_{i % 3}",
                    "properties": {"rating": float(1 + i % 5)},
                    "eventTime": "2026-03-01T00:00:00.000Z",
                }).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req) as resp:
                assert resp.status == 201
                ids.append(_json.loads(resp.read())["eventId"])
        # rows hash-routed across BOTH storage servers
        per_server = [b.events().find(app.id) for b in backends]
        assert all(len(p) > 0 for p in per_server)
        assert sum(len(p) for p in per_server) == 12
        for s, part in enumerate(per_server):
            for e in part:
                assert stable_hash(e.entity_id) % 2 == s
        # GET round-trips through the fan-out read path
        with urllib.request.urlopen(
            f"{base}/events/{ids[0]}.json?accessKey={key.key}"
        ) as resp:
            got = _json.loads(resp.read())
        assert got["entityId"] == "user_0"
    finally:
        es.stop()


def test_cli_compact_handles_per_shard_stats(two_servers, capsys):
    """`pio app compact` on a sharded source gets a LIST of per-shard
    stats and must print them instead of crashing (code-review
    regression)."""
    from predictionio_tpu.data.storage import set_storage
    from predictionio_tpu.tools.cli import main as cli_main

    _, _, client = two_servers
    try:
        set_storage(client)
        assert cli_main(["app", "new", "compactapp"]) == 0
        capsys.readouterr()
        # the regression was a TypeError on the list-of-stats return;
        # memory shards compact in place -> the collapsed no-op line
        assert cli_main(["app", "compact", "compactapp"]) == 0
        out = capsys.readouterr().out
        assert "nothing to compact" in out

        # a stats-returning sharded store prints one line per shard
        from predictionio_tpu.tools import cli as cli_mod

        class FakeShardedStore:
            def compact(self, app_id, channel_id=None):
                return [{"dropped": 1, "before_bytes": 10, "after_bytes": 5},
                        None]

        class FakeStorage:
            def events(self):
                return FakeShardedStore()

            def __getattr__(self, name):
                return getattr(client, name)

        set_storage(FakeStorage())  # type: ignore[arg-type]
        assert cli_main(["app", "compact", "compactapp"]) == 0
        out = capsys.readouterr().out
        assert "shard 0: Compacted: dropped 1" in out
        assert "shard 1: stores events in place" in out
    finally:
        set_storage(None)


def test_scan_ttl_slides_with_fetch_progress(memory_storage):
    """A resumed transfer must never die to the absolute scan TTL while
    it is making progress (code-review regression)."""
    import time as _time

    from predictionio_tpu.serving.storage_server import _ScanRegistry

    # generous margins: the sleeps stay well under the ttl so ordinary
    # CI scheduling delay cannot reap between a sleep and the assert
    reg = _ScanRegistry(ttl=2.0)
    scan = reg.create(lambda f: f.write(b"x" * 64))
    _time.sleep(1.2)
    assert reg.path_for(scan["scan_id"]) is not None  # refreshes the TTL
    _time.sleep(1.2)
    # absolute age (2.4s) > ttl, but the access above slid the window
    assert reg.path_for(scan["scan_id"]) is not None
    _time.sleep(2.5)  # idle past the ttl: reaped
    assert reg.path_for(scan["scan_id"]) is None
    reg.close()


def test_keepalive_connection_survives_streaming_then_bad_route(two_servers):
    """After a streamed NDJSON find on a keep-alive connection, the
    NEXT request's body must still be drained before answering — a
    stale body would desynchronize the connection (code-review
    regression)."""
    import http.client as _hc
    import json as _json

    _, servers, client = two_servers
    store = client.events()
    store.init(1)
    store.insert_batch(_events(n=6), 1)

    conn = _hc.HTTPConnection("127.0.0.1", servers[0].port, timeout=10)
    try:
        # 1. streamed NDJSON response (bypasses _send)
        conn.request("POST", "/storage/events/find",
                     _json.dumps({"app_id": 1}).encode(),
                     {"Content-Type": "application/json"})
        r1 = conn.getresponse()
        lines = [l for l in r1.read().split(b"\n") if l]
        assert len(lines) > 0
        # 2. unknown events method WITH a body -> short-circuit 404
        conn.request("POST", "/storage/events/bogus",
                     _json.dumps({"app_id": 1, "junk": "x" * 200}).encode(),
                     {"Content-Type": "application/json"})
        r2 = conn.getresponse()
        assert r2.status == 404
        r2.read()
        # 3. the SAME connection must still parse a clean request
        conn.request("GET", "/storage/stats")
        r3 = conn.getresponse()
        assert r3.status == 200
        assert "columnar_scan_count" in _json.loads(r3.read())
    finally:
        conn.close()


def test_metadata_and_models_pin_to_first_shard(two_servers):
    backends, _, client = two_servers
    app = client.apps().insert("shapp")
    assert backends[0].apps().get_by_name("shapp") is not None
    assert backends[1].apps().get_by_name("shapp") is None
    from predictionio_tpu.data.metadata import Model

    client.models().insert(Model(id="m1", models=b"\x00\x01"))
    assert backends[0].models().get("m1") is not None
    assert backends[1].models().get("m1") is None
    assert client.apps().get(app.id).name == "shapp"
