"""Entity-hash sharded columnar reads.

The reference's bulk read path is region-parallel: each Spark executor
scans only its HBase region slice (hbase/HBPEvents.scala:48), with
regions split by the MD5 rowkey prefix (HBEventsUtil.scala:96-108).
This file covers the TPU build's equivalent: ``stable_hash`` read
shards through ``find_columnar(shard_index=, shard_count=)`` on local
backends and over the REST wire (server-side filtering + scan
counters), plus the shard/merge column algebra they share.
"""

import datetime as _dt
import json
import math
import urllib.request

import numpy as np
import pytest

from predictionio_tpu.data.event import Event
from predictionio_tpu.data.storage import (
    EventColumns,
    Storage,
    merge_columns,
    shard_columns,
    stable_hash,
)
from predictionio_tpu.serving.storage_server import StorageServer

UTC = _dt.timezone.utc


def _decode(cols: EventColumns):
    """Rows as comparable tuples, independent of code assignment."""
    out = []
    for i in range(len(cols)):
        tc = int(cols.target_codes[i])
        v = float(cols.values[i])
        out.append((
            cols.entity_vocab[cols.entity_codes[i]],
            cols.target_vocab[tc] if tc >= 0 else "",
            cols.names[cols.name_codes[i]],
            -1.0 if math.isnan(v) else v,
            int(cols.times_us[i]),
        ))
    return out


def _synthetic_columns(n=200, n_entities=37, seed=0) -> EventColumns:
    rng = np.random.default_rng(seed)
    ent = rng.integers(0, n_entities, n).astype(np.int32)
    tgt = rng.integers(-1, 11, n).astype(np.int32)
    return EventColumns(
        entity_codes=ent,
        target_codes=tgt,
        name_codes=rng.integers(0, 3, n).astype(np.int32),
        values=rng.random(n),
        times_us=rng.integers(0, 10**9, n).astype(np.int64),
        entity_vocab=[f"u{i}" for i in range(n_entities)],
        target_vocab=[f"i{i}" for i in range(11)],
        names=["rate", "buy", "view"],
    )


def test_shard_columns_partitions_completely():
    cols = _synthetic_columns()
    full = _decode(cols)
    pieces = []
    for k in (4, 3):  # two shardings of the same data
        shards = [shard_columns(cols, i, k) for i in range(k)]
        rows = [r for s in shards for r in _decode(s)]
        assert sorted(rows) == sorted(full)
        for i, s in enumerate(shards):
            # every row routed by its entity's stable hash
            for ent in s.entity_vocab:
                assert stable_hash(ent) % k == i
            # vocabs compacted: every entry referenced by some row
            assert set(s.entity_vocab) == {r[0] for r in _decode(s)}
            used_targets = {r[1] for r in _decode(s)} - {""}
            assert set(s.target_vocab) == used_targets
        pieces.append(shards)
    # shard_count=1 is the identity
    assert shard_columns(cols, 0, 1) is cols


def test_shard_columns_no_targets():
    """Events without target entities ($set/view-style): target_vocab is
    empty and every target_code is -1 — sharding must not crash on the
    size-0 remap table (code-review regression)."""
    cols = _synthetic_columns(n=50)
    cols = EventColumns(
        entity_codes=cols.entity_codes,
        target_codes=np.full(len(cols), -1, np.int32),
        name_codes=cols.name_codes,
        values=cols.values,
        times_us=cols.times_us,
        entity_vocab=cols.entity_vocab,
        target_vocab=[],
        names=cols.names,
    )
    shards = [shard_columns(cols, i, 2) for i in range(2)]
    assert sum(len(s) for s in shards) == len(cols)
    for s in shards:
        assert s.target_vocab == []
        assert np.all(s.target_codes == -1)
    merged = merge_columns(shards)
    assert sorted(_decode(merged)) == sorted(_decode(cols))


def test_merge_columns_reassembles_shards():
    cols = _synthetic_columns()
    shards = [shard_columns(cols, i, 3) for i in range(3)]
    merged = merge_columns(shards)
    assert sorted(_decode(merged)) == sorted(_decode(cols))
    ordered = merge_columns(shards, time_ordered=True)
    times = ordered.times_us
    assert np.all(times[:-1] <= times[1:])
    assert sorted(_decode(ordered)) == sorted(_decode(cols))
    # empty merge
    empty = merge_columns([])
    assert len(empty) == 0 and empty.entity_vocab == []


def _seed_events(store, app_id=1, n=60):
    store.init(app_id)
    events = []
    for i in range(n):
        events.append(Event(
            event="rate",
            entity_type="user",
            entity_id=f"user_{i % 17}",
            target_entity_type="item",
            target_entity_id=f"item_{i % 7}",
            properties={"rating": float(1 + i % 5)},
            event_time=_dt.datetime(2026, 1, 1, tzinfo=UTC)
            + _dt.timedelta(minutes=i),
        ))
    store.insert_batch(events, app_id)
    return events


@pytest.fixture(params=["memory", "eventlog"])
def sharded_store(request, tmp_path):
    from tests.test_storage import make_storage

    storage = make_storage(request.param, tmp_path)
    yield storage.events()


def test_find_columnar_shards_union_to_full_scan(sharded_store):
    store = sharded_store
    _seed_events(store)
    full = store.find_columnar(1, value_property="rating",
                               time_ordered=False)
    shards = [
        store.find_columnar(1, value_property="rating", time_ordered=False,
                            shard_index=i, shard_count=2)
        for i in range(2)
    ]
    assert sum(len(s) for s in shards) == len(full)
    assert 0 < len(shards[0]) < len(full)  # both shards non-trivial
    assert sorted(_decode(merge_columns(shards))) == sorted(_decode(full))
    for i, s in enumerate(shards):
        for ent in s.entity_vocab:
            assert stable_hash(ent) % 2 == i


def test_find_columnar_shard_filter_precedes_limit(sharded_store):
    """A row limit applies AFTER the entity-hash shard filter — the
    shard's first `limit` rows, not the shard subset of the first
    `limit` rows overall (code-review regression)."""
    store = sharded_store
    _seed_events(store)
    full = store.find_columnar(1, time_ordered=True,
                               shard_index=0, shard_count=2)
    limited = store.find_columnar(1, time_ordered=True, limit=5,
                                  shard_index=0, shard_count=2)
    assert len(limited) == 5
    assert list(limited.times_us) == list(full.times_us[:5])
    for ent in limited.entity_vocab:
        assert stable_hash(ent) % 2 == 0

    newest = store.find_columnar(1, time_ordered=True, limit=5,
                                 reversed=True,
                                 shard_index=0, shard_count=2)
    assert list(newest.times_us) == list(full.times_us[-5:][::-1])


def test_find_columnar_shard_param_validation(sharded_store):
    store = sharded_store
    store.init(1)
    with pytest.raises(ValueError):
        store.find_columnar(1, shard_index=0)
    with pytest.raises(ValueError):
        store.find_columnar(1, shard_index=2, shard_count=2)


def test_rest_sharded_scan_and_server_counters(memory_storage):
    """Over the wire: the SERVER applies the shard filter (each host
    fetches ~1/N of the rows) and its /storage/stats log proves it."""
    from tests.test_rest_storage import _client_storage

    _seed_events(memory_storage.events())
    server = StorageServer(storage=memory_storage, host="127.0.0.1",
                           port=0).start()
    try:
        client = _client_storage(server.port).events()
        full = client.find_columnar(1, value_property="rating",
                                    time_ordered=False)
        shards = [
            client.find_columnar(1, value_property="rating",
                                 time_ordered=False,
                                 shard_index=i, shard_count=2)
            for i in range(2)
        ]
        assert sorted(_decode(merge_columns(shards))) == sorted(_decode(full))

        with urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/storage/stats"
        ) as resp:
            stats = json.loads(resp.read())
        scans = stats["columnar_scans"]
        assert len(scans) == 3
        assert scans[0]["shard_count"] is None
        assert scans[0]["rows"] == len(full)
        sharded = {s["shard_index"]: s["rows"] for s in scans[1:]}
        assert sharded.keys() == {0, 1}
        assert sum(sharded.values()) == len(full)
        # both shards carry a real fraction of the data (17 users split
        # by hash; neither side can be empty or everything)
        assert all(0 < r < len(full) for r in sharded.values())
    finally:
        server.stop()
