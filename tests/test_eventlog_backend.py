"""Native (C++) eventlog backend specifics: durability across reopen,
torn-tail WAL recovery, tombstone persistence, non-canonical id mapping.
The generic EventStore contract runs via tests/test_storage.py's
parametrized suite; these cover what only the native tier does.
Reference role: the HBase event store (SURVEY.md §2.5)."""

import datetime as dt
import os

import pytest

from predictionio_tpu.data.event import Event
from tests.test_storage import make_storage

UTC = dt.timezone.utc


def _mk(tmp_path):
    return make_storage("eventlog", tmp_path)


def ev(uid, minute=0, name="rate"):
    return Event(
        event=name,
        entity_type="user",
        entity_id=uid,
        target_entity_type="item",
        target_entity_id="i1",
        properties={"rating": 4.0},
        event_time=dt.datetime(2026, 3, 1, 12, minute, tzinfo=UTC),
    )


def test_reopen_persistence_and_tombstones(tmp_path):
    st = _mk(tmp_path)
    app = st.apps().insert("native")
    st.events().init(app.id)
    ids = st.events().insert_batch([ev("u1"), ev("u2", 1), ev("u3", 2)], app.id)
    assert st.events().delete(ids[1], app.id)
    st.events().close()

    st2 = _mk(tmp_path)
    got = st2.events().find(app.id)
    assert [e.entity_id for e in got] == ["u1", "u3"]
    # tz fidelity survives the binary round trip
    assert got[0].event_time == dt.datetime(2026, 3, 1, 12, 0, tzinfo=UTC)
    assert st2.events().get(ids[1], app.id) is None
    st2.events().close()


def test_torn_tail_recovery(tmp_path):
    """A crash mid-append leaves a partial record; reopen truncates it
    (WAL replay semantics, eventlog.cpp)."""
    st = _mk(tmp_path)
    app = st.apps().insert("torn")
    st.events().init(app.id)
    st.events().insert_batch([ev("u1"), ev("u2", 1)], app.id)
    st.events().close()

    log_dir = tmp_path / "store" / "events" / f"events_{app.id}"
    log_file = log_dir / "log.bin"
    with open(log_file, "ab") as f:
        f.write(b"\xff\xff\xff\x7f partial garbage")

    st2 = _mk(tmp_path)
    assert [e.entity_id for e in st2.events().find(app.id)] == ["u1", "u2"]
    # appends still work after recovery
    st2.events().insert(ev("u3", 2), app.id)
    assert len(st2.events().find(app.id)) == 3
    st2.events().close()


def test_non_hex_event_id_round_trip(tmp_path):
    st = _mk(tmp_path)
    app = st.apps().insert("ids")
    st.events().init(app.id)
    e = ev("u1").with_id("custom-id-not-hex")
    st.events().insert(e, app.id)
    got = st.events().get("custom-id-not-hex", app.id)
    assert got is not None and got.event_id == "custom-id-not-hex"
    assert st.events().find(app.id)[0].event_id == "custom-id-not-hex"
    st.events().close()


def test_time_window_and_limit(tmp_path):
    st = _mk(tmp_path)
    app = st.apps().insert("win")
    st.events().init(app.id)
    st.events().insert_batch([ev(f"u{i}", i) for i in range(10)], app.id)
    es = st.events()
    start = dt.datetime(2026, 3, 1, 12, 3, tzinfo=UTC)
    until = dt.datetime(2026, 3, 1, 12, 7, tzinfo=UTC)
    got = es.find(app.id, start_time=start, until_time=until)
    assert [e.entity_id for e in got] == ["u3", "u4", "u5", "u6"]  # half-open
    got = es.find(app.id, limit=3, reversed=True)
    assert [e.entity_id for e in got] == ["u9", "u8", "u7"]
    st.events().close()


def test_reinsert_after_delete_is_live(tmp_path):
    """Tombstones carry a log-offset cutoff: deleting id X then inserting
    a new event with id X must keep the new event visible — matching the
    memory/localfs/sqlite backends."""
    st = _mk(tmp_path)
    app = st.apps().insert("resurrect")
    es = st.events().__class__  # noqa: F841 (readability)
    st.events().init(app.id)
    e1 = ev("u1").with_id()
    st.events().insert(e1, app.id)
    assert st.events().delete(e1.event_id, app.id)
    assert st.events().get(e1.event_id, app.id) is None

    e2 = ev("u1-v2", 5).with_id(e1.event_id)
    st.events().insert(e2, app.id)
    got = st.events().get(e1.event_id, app.id)
    assert got is not None and got.entity_id == "u1-v2"
    assert [e.entity_id for e in st.events().find(app.id)] == ["u1-v2"]
    st.events().close()

    # survives reopen (tombstone cutoff is persistent)
    st2 = _mk(tmp_path)
    assert [e.entity_id for e in st2.events().find(app.id)] == ["u1-v2"]
    st2.events().close()


def test_second_process_gets_clean_lock_error(tmp_path):
    """A second OS process opening the same log fails with StorageError
    (flock single-writer guard) instead of corrupting the index."""
    import subprocess
    import sys
    import textwrap

    st = _mk(tmp_path)
    app = st.apps().insert("locked")
    st.events().init(app.id)
    st.events().insert(ev("u1"), app.id)

    code = textwrap.dedent(
        f"""
        from predictionio_tpu.data.backends.eventlog import EventLogEventStore
        from predictionio_tpu.data.storage import StorageError
        st = EventLogEventStore({str(tmp_path / "store" / "events")!r})
        try:
            st.find({app.id})
        except StorageError as e:
            assert "LOCK" in str(e), e
            print("LOCKED-OK")
        else:
            print("NO-LOCK")
        """
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, cwd="/root/repo"
    )
    assert "LOCKED-OK" in proc.stdout, (proc.stdout, proc.stderr)
    st.events().close()


def test_columnar_nul_bytes_in_ids_round_trip(tmp_path):
    """The native columnar dictionaries use exact prefix offsets, so ids
    containing embedded NUL bytes round-trip on the NATIVE path (a
    '\\0'-joined dictionary would silently shift every later vocab
    entry). Covers the native backend directly — the REST edge-case test
    only exercises the npz fallback."""
    st = _mk(tmp_path)
    app = st.apps().insert("nul")
    st.events().init(app.id)
    weird = ["a\0b", "plain", "\0lead", "trail\0", "double\0\0mid"]
    batch = [
        Event(
            event="rate",
            entity_type="user",
            entity_id=uid,
            target_entity_type="item",
            target_entity_id=f"i\0{i}",
            properties={"rating": float(i)},
            event_time=dt.datetime(2026, 3, 1, 12, i, tzinfo=UTC),
        )
        for i, uid in enumerate(weird)
    ]
    st.events().insert_batch(batch, app.id)
    cols = st.events().find_columnar(
        app.id, value_property="rating", time_ordered=True
    )
    got_ents = [cols.entity_vocab[c] for c in cols.entity_codes]
    got_tgts = [cols.target_vocab[c] for c in cols.target_codes]
    assert got_ents == weird
    assert got_tgts == [f"i\0{i}" for i in range(len(weird))]
    assert list(cols.values) == [float(i) for i in range(len(weird))]
    st.events().close()


def test_columnar_append_rejects_u16_overflow(tmp_path):
    """A string >= 65535 bytes would wrap the u16 wire header length (or
    alias the absent sentinel); insert_columnar must fail loudly like
    the row path's struct.pack('H'), never corrupt record framing."""
    import numpy as np

    from predictionio_tpu.data.storage import EventColumns, StorageError

    st = _mk(tmp_path)
    app = st.apps().insert("overflow")
    st.events().init(app.id)
    cols = EventColumns(
        entity_codes=np.array([0], np.int32),
        target_codes=np.array([0], np.int32),
        name_codes=np.array([0], np.int32),
        values=np.array([1.0]),
        times_us=np.array([0], np.int64),
        entity_vocab=["u" * 0xFFFF],
        target_vocab=["i1"],
        names=["rate"],
    )
    with pytest.raises(StorageError):
        st.events().insert_columnar(
            cols, app.id, entity_type="user", target_entity_type="item",
            value_property="rating",
        )
    # the log is untouched — no partially-framed record
    assert st.events().find(app.id) == []
    st.events().close()


def test_bulk_throughput_sanity(tmp_path):
    """50k events in one batch append + filtered scan — exercises the
    native index path at a size where Python-side filtering would show."""
    st = _mk(tmp_path)
    app = st.apps().insert("bulk")
    st.events().init(app.id)
    batch = [
        Event(
            event="buy" if i % 3 == 0 else "view",
            entity_type="user",
            entity_id=f"u{i % 500}",
            target_entity_type="item",
            target_entity_id=f"i{i % 100}",
            event_time=dt.datetime(2026, 3, 1, tzinfo=UTC) + dt.timedelta(seconds=i),
        )
        for i in range(50_000)
    ]
    ids = st.events().insert_batch(batch, app.id)
    assert len(set(ids)) == 50_000
    buys = st.events().find(app.id, event_names=["buy"])
    assert len(buys) == len([e for e in batch if e.event == "buy"])
    one_user = st.events().find(app.id, entity_type="user", entity_id="u7")
    assert len(one_user) == 100
    st.events().close()


def test_compaction_reclaims_space_and_preserves_data(tmp_path):
    """insert, delete half, compact: the log file shrinks, deleted
    records are physically gone (tombstone file emptied), remaining
    data and subsequent appends intact across reopen. Ref: the HBase
    major-compaction role (SURVEY.md §2.5)."""
    st = _mk(tmp_path)
    app = st.apps().insert("compact")
    st.events().init(app.id)
    ids = st.events().insert_batch([ev(f"u{i}", i % 60) for i in range(500)], app.id)
    for eid in ids[::2]:
        assert st.events().delete(eid, app.id)

    log_dir = tmp_path / "store" / "events" / f"events_{app.id}"
    before = (log_dir / "log.bin").stat().st_size
    stats = st.events().compact(app.id)
    assert stats["dropped"] == 250
    assert stats["after_bytes"] < stats["before_bytes"] == before
    # compaction commits a new generation (CURRENT protocol): the new
    # files carry the data, the old generation's files are removed
    assert (log_dir / "CURRENT").read_text().strip() == "1"
    assert (log_dir / "log.1.bin").stat().st_size == stats["after_bytes"]
    assert (log_dir / "tombstones.1.bin").stat().st_size == 0
    assert not (log_dir / "log.bin").exists()

    got = st.events().find(app.id)
    assert {e.entity_id for e in got} == {f"u{i}" for i in range(1, 500, 2)}
    # appends + deletes still work after the swap
    st.events().insert(ev("u-post", 59), app.id)
    assert st.events().delete(ids[1], app.id)
    st.events().close()

    st2 = _mk(tmp_path)
    got = st2.events().find(app.id)
    assert len(got) == 250  # 249 survivors + u-post
    assert got[-1].entity_id == "u-post"
    st2.events().close()


def test_index_snapshot_fast_reopen(tmp_path):
    """A clean close persists the index; reopen loads it (index.bin
    exists and queries return identical results to the pre-close state).
    The open-cost win is measured at scale by the bench's warm stage."""
    st = _mk(tmp_path)
    app = st.apps().insert("snap")
    st.events().init(app.id)
    st.events().insert_batch([ev(f"u{i}", i % 60) for i in range(1000)], app.id)
    st.events().close()

    log_dir = tmp_path / "store" / "events" / f"events_{app.id}"
    assert (log_dir / "index.bin").exists()

    st2 = _mk(tmp_path)
    got = st2.events().find(app.id, entity_id="u7", entity_type="user")
    assert len(got) == len([i for i in range(1000) if i % 1000 == 7 or f"u{i}" == "u7"])
    assert len(st2.events().find(app.id)) == 1000
    st2.events().close()


def test_index_snapshot_crash_suffix_replay(tmp_path):
    """Appends after the last snapshot (a crash: close() never ran) are
    replayed from the log on reopen; dupe/tombstone semantics stay
    exact (the lazily replayed suffix is id-verified on first need)."""
    import subprocess
    import sys
    import textwrap

    st = _mk(tmp_path)
    app = st.apps().insert("crash")
    st.events().init(app.id)
    ids = st.events().insert_batch([ev(f"u{i}") for i in range(10)], app.id)
    st.events().close()  # snapshot covers 10 records

    # "crash": a subprocess appends (incl. a re-used id — liveness must
    # pick the later record) and exits WITHOUT close: no new snapshot,
    # flock released by process exit
    code = textwrap.dedent(
        f"""
        import datetime as dt, os
        from predictionio_tpu.data.backends.eventlog import EventLogEventStore
        from predictionio_tpu.data.event import Event
        es = EventLogEventStore({str(tmp_path / "store" / "events")!r})
        def ev(uid, minute):
            return Event(event="rate", entity_type="user", entity_id=uid,
                         target_entity_type="item", target_entity_id="i1",
                         event_time=dt.datetime(2026, 3, 1, 12, minute,
                                                tzinfo=dt.timezone.utc))
        es.insert(ev("u1-v2", 30).with_id({ids[1]!r}), {app.id})
        es.insert(ev("u-extra", 31), {app.id})
        os._exit(0)  # crash: no el_close, no snapshot update
        """
    )
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, cwd="/root/repo")
    assert proc.returncode == 0, proc.stderr

    st3 = _mk(tmp_path)
    got = {e.entity_id for e in st3.events().find(app.id)}
    assert "u-extra" in got and "u1-v2" in got
    assert "u1" not in got  # superseded by the suffix record with same id
    assert st3.events().get(ids[1], app.id).entity_id == "u1-v2"
    st3.events().close()


def test_compaction_crash_orphans_are_ignored_and_cleaned(tmp_path):
    """A compaction that crashed BEFORE the CURRENT commit leaves
    next-generation files as orphans: reopen must serve the old
    generation untouched and remove the orphans (commit protocol,
    eventlog.cpp CURRENT)."""
    st = _mk(tmp_path)
    app = st.apps().insert("orphan")
    st.events().init(app.id)
    st.events().insert_batch([ev(f"u{i}", i % 60) for i in range(20)], app.id)
    st.events().close()

    log_dir = tmp_path / "store" / "events" / f"events_{app.id}"
    (log_dir / "log.1.bin").write_bytes(b"half-written garbage")
    (log_dir / "tombstones.1.bin").write_bytes(b"")
    assert not (log_dir / "CURRENT").exists()

    st2 = _mk(tmp_path)
    assert len(st2.events().find(app.id)) == 20
    assert not (log_dir / "log.1.bin").exists()
    assert not (log_dir / "tombstones.1.bin").exists()
    st2.events().close()


def test_compaction_relocated_reinsert_survives_reopen(tmp_path):
    """The data-loss scenario the generation protocol exists for: a
    record re-inserted after a delete (so a tombstone cutoff exceeds
    its compacted offset) must stay live across compact + reopen — the
    new generation's tombstone file is empty by construction."""
    st = _mk(tmp_path)
    app = st.apps().insert("reloc")
    st.events().init(app.id)
    e1 = ev("u-old").with_id()
    st.events().insert(e1, app.id)
    st.events().insert_batch([ev(f"f{i}", i % 60) for i in range(200)], app.id)
    assert st.events().delete(e1.event_id, app.id)  # cutoff = large offset
    st.events().insert(ev("u-new", 59).with_id(e1.event_id), app.id)
    stats = st.events().compact(app.id)
    assert stats["dropped"] == 1
    assert st.events().get(e1.event_id, app.id).entity_id == "u-new"
    st.events().close()

    st2 = _mk(tmp_path)
    got = st2.events().get(e1.event_id, app.id)
    assert got is not None and got.entity_id == "u-new"
    assert len(st2.events().find(app.id)) == 201
    st2.events().close()


def test_corrupt_index_snapshot_degrades_to_replay(tmp_path):
    """A corrupt index.bin (bit rot, partial write, bogus n_recs) must
    degrade to full-log replay — never crash the process or poison the
    index."""
    import struct as _struct

    st = _mk(tmp_path)
    app = st.apps().insert("rot")
    st.events().init(app.id)
    st.events().insert_batch([ev(f"u{i}", i % 60) for i in range(50)], app.id)
    st.events().close()
    log_dir = tmp_path / "store" / "events" / f"events_{app.id}"
    idx = log_dir / "index.bin"

    # 1) bogus n_recs in an otherwise-valid header (would resize(2^60)
    # and abort the process if trusted before the size bound-check)
    raw = bytearray(idx.read_bytes())
    raw[32:40] = _struct.pack("<Q", 1 << 60)  # n_recs field
    idx.write_bytes(bytes(raw))
    st2 = _mk(tmp_path)
    assert len(st2.events().find(app.id)) == 50
    st2.events().close()

    # 2) flipped bit in the RecMeta array (checksum must reject)
    raw = bytearray(idx.read_bytes())
    raw[len(raw) // 2] ^= 0xFF
    idx.write_bytes(bytes(raw))
    st3 = _mk(tmp_path)
    assert len(st3.events().find(app.id)) == 50
    st3.events().close()

    # 3) truncated file
    idx.write_bytes(idx.read_bytes()[: len(raw) // 3])
    st4 = _mk(tmp_path)
    assert len(st4.events().find(app.id)) == 50
    st4.events().close()


def test_parallel_columnar_scan_is_byte_identical(tmp_path, monkeypatch):
    """The multi-threaded fused scan (PIO_EVENTLOG_SCAN_THREADS) must
    produce EXACTLY the sequential scan's output — same rows in record
    order, same first-seen dictionary code assignment."""
    import numpy as np

    store = _mk(tmp_path).events()
    store.init(1)
    base = dt.datetime(2026, 3, 1, tzinfo=dt.timezone.utc)
    events = []
    for i in range(5000):
        has_target = i % 7 != 0
        events.append(Event(
            event=f"ev{i % 3}",
            entity_type="user",
            entity_id=f"user_{(i * 13) % 401}",
            target_entity_type="item" if has_target else None,
            target_entity_id=f"item_{(i * 7) % 97}" if has_target else None,
            properties={"rating": float(i % 9)} if i % 2 else {},
            event_time=base + dt.timedelta(seconds=i),
        ))
    store.insert_batch(events, 1)

    monkeypatch.setenv("PIO_EVENTLOG_SCAN_THREADS", "1")
    seq = store.find_columnar(1, value_property="rating", time_ordered=False)
    monkeypatch.setenv("PIO_EVENTLOG_SCAN_THREADS", "4")
    par = store.find_columnar(1, value_property="rating", time_ordered=False)

    assert par.entity_vocab == seq.entity_vocab
    assert par.target_vocab == seq.target_vocab
    assert par.names == seq.names
    np.testing.assert_array_equal(par.entity_codes, seq.entity_codes)
    np.testing.assert_array_equal(par.target_codes, seq.target_codes)
    np.testing.assert_array_equal(par.name_codes, seq.name_codes)
    np.testing.assert_array_equal(par.times_us, seq.times_us)
    np.testing.assert_array_equal(
        np.nan_to_num(par.values, nan=-1.0),
        np.nan_to_num(seq.values, nan=-1.0),
    )

    # filters compose with the parallel path too
    par_f = store.find_columnar(1, value_property="rating",
                                time_ordered=False, event_names=["ev1"])
    monkeypatch.setenv("PIO_EVENTLOG_SCAN_THREADS", "1")
    seq_f = store.find_columnar(1, value_property="rating",
                                time_ordered=False, event_names=["ev1"])
    assert len(par_f) == len(seq_f) > 0
    np.testing.assert_array_equal(par_f.entity_codes, seq_f.entity_codes)
    assert par_f.entity_vocab == seq_f.entity_vocab
    store.close()


def test_concurrent_appends_scans_and_compact(tmp_path, monkeypatch):
    """Thread-safety stress of the native store: writers appending row
    batches while readers run (multi-threaded) columnar scans and a
    compaction runs mid-stream. The C++ layer must serialize correctly
    (shared scan locks vs exclusive append/compact locks) — no crashes,
    no torn reads, and the final state exact. The reference leans on
    JVM memory safety here (SURVEY.md §5.2); this is the native
    equivalent's proof."""
    import threading

    import numpy as np

    monkeypatch.setenv("PIO_EVENTLOG_SCAN_THREADS", "2")
    store = _mk(tmp_path).events()
    store.init(1)
    base = dt.datetime(2026, 4, 1, tzinfo=dt.timezone.utc)

    def batch(writer, start, n):
        return [Event(
            event="rate", entity_type="user",
            entity_id=f"w{writer}_u{(start + i) % 50}",
            target_entity_type="item", target_entity_id=f"i{(start + i) % 20}",
            properties={"rating": float(1 + i % 5)},
            event_time=base + dt.timedelta(seconds=start + i),
        ) for i in range(n)]

    errors = []
    scan_counts = [[], []]  # per scanner thread: order is meaningful
    stop = threading.Event()

    def writer(w):
        try:
            for r in range(20):
                store.insert_batch(batch(w, r * 50, 50), 1)
        except Exception as e:  # noqa: BLE001
            errors.append(("writer", w, e))

    def scanner(slot):
        try:
            while not stop.is_set():
                cols = store.find_columnar(1, value_property="rating",
                                           time_ordered=False)
                n = len(cols)
                # torn-read guards: every code decodes, values sane
                if n:
                    assert int(cols.entity_codes.max()) < len(cols.entity_vocab)
                    vals = cols.values[~np.isnan(cols.values)]
                    assert vals.size == 0 or (vals.min() >= 1.0 and vals.max() <= 5.0)
                scan_counts[slot].append(n)
        except Exception as e:  # noqa: BLE001
            errors.append(("scanner", slot, e))

    writers = [threading.Thread(target=writer, args=(w,)) for w in range(3)]
    scans = [threading.Thread(target=scanner, args=(s,)) for s in range(2)]
    for t in scans:
        t.start()
    for t in writers:
        t.start()
    writers[0].join()
    store.compact(1)       # exclusive pass mid-stream
    for t in writers[1:]:
        t.join()
    stop.set()
    for t in scans:
        t.join()

    assert not errors, errors
    final = store.find_columnar(1, time_ordered=False)
    assert len(final) == 3 * 20 * 50
    # EACH scanner observed monotonically non-decreasing counts (no
    # deletes here, and compaction drops nothing) and never phantom rows
    assert any(scan_counts)
    for counts in scan_counts:
        assert counts == sorted(counts), counts
        assert not counts or counts[-1] <= len(final)
    store.close()


# ---------------------------------------------------------------------------
# Native JSON ingest lane: the event server's live
# lane without per-row Python objects — API-format JSON array bytes go
# straight to C++ (parse + EventValidation + wire packing + append, GIL
# released). Reference role: EventAPI's request pipeline
# (data/.../api/EventAPI.scala:209).
# ---------------------------------------------------------------------------

def test_json_lane_matches_python_path(tmp_path):
    """The native lane and the Event-object path must store identical
    events (every field, tz fidelity included)."""
    import json

    rows = [
        {"event": "rate", "entityType": "user", "entityId": "u1",
         "targetEntityType": "item", "targetEntityId": "i1",
         "properties": {"rating": 4.5},
         "eventTime": "2026-01-01T00:00:00.000Z"},
        {"event": "$set", "entityType": "user", "entityId": "ué中-\"q\"",
         "properties": {"age": 31, "tags": ["a", "b"], "n": {"x": [1, 2]}},
         "eventTime": "2026-01-02T10:30:00+05:30"},
        {"event": "view", "entityType": "user", "entityId": "u3",
         "targetEntityType": "item", "targetEntityId": "i9",
         "tags": ["t1", "t2"], "prId": "pr-1",
         "eventTime": 1767225600000},
    ]
    st_native = _mk(tmp_path / "native")
    st_native.events().init(1)
    ids, codes, names, etypes = st_native.events().insert_json_batch(
        json.dumps(rows).encode(), 1)
    assert codes == [0, 0, 0] and None not in ids
    assert names == ["rate", "$set", "view"]
    assert etypes == ["user"] * 3

    st_py = _mk(tmp_path / "py")
    st_py.events().init(1)
    st_py.events().insert_batch([Event.from_dict(r) for r in rows], 1)

    def canon(events):
        return sorted(
            (e.event, e.entity_type, e.entity_id, e.target_entity_type,
             e.target_entity_id, dict(e.properties.to_dict()), e.event_time,
             e.event_time.utcoffset(), e.tags, e.pr_id)
            for e in events
        )

    assert canon(st_native.events().find(1)) == canon(st_py.events().find(1))
    st_native.events().close()
    st_py.events().close()


def test_json_lane_validation_parity(tmp_path):
    """Every EventValidation rule fires with the right code, bad rows
    never land, and the Python path rejects the same rows."""
    import json

    from predictionio_tpu.data.backends.eventlog import _ROW_ERRORS
    from predictionio_tpu.data.event import (
        EventValidationError, validate_event,
    )

    bad = [
        ({"event": "", "entityType": "u", "entityId": "x"}, 4),
        ({"event": "$bogus", "entityType": "u", "entityId": "x"}, 11),
        ({"event": "r", "entityType": "u", "entityId": "x",
          "targetEntityType": "item"}, 7),
        ({"event": "$unset", "entityType": "u", "entityId": "x"}, 10),
        ({"event": "$set", "entityType": "u", "entityId": "x",
          "targetEntityType": "item", "targetEntityId": "i"}, 12),
        ({"event": "r", "entityType": "pio_x", "entityId": "x"}, 13),
        ({"event": "r", "entityType": "u", "entityId": "x",
          "properties": {"pio_k": 1}}, 15),
        ({"entityType": "u", "entityId": "x"}, 1),
    ]
    st = _mk(tmp_path)
    st.events().init(1)
    good = {"event": "rate", "entityType": "user", "entityId": "ok"}
    payload = [good] + [b for b, _ in bad]
    ids, codes, _, _ = st.events().insert_json_batch(
        json.dumps(payload).encode(), 1, strict=False)
    assert codes[0] == 0
    assert codes[1:] == [c for _, c in bad], codes
    assert all(c in _ROW_ERRORS for c in codes[1:])
    # only the good row landed
    assert [e.entity_id for e in st.events().find(1)] == ["ok"]
    # the Python path rejects the same rows
    for row, _ in bad:
        with pytest.raises((EventValidationError, ValueError)):
            validate_event(Event.from_dict(row))
    st.events().close()


def test_json_lane_strict_appends_nothing(tmp_path):
    import json

    from predictionio_tpu.data.storage import StorageError

    st = _mk(tmp_path)
    st.events().init(1)
    payload = [
        {"event": "rate", "entityType": "user", "entityId": "ok"},
        {"event": "", "entityType": "user", "entityId": "bad"},
    ]
    with pytest.raises(StorageError, match="event 1"):
        st.events().insert_json_batch(json.dumps(payload).encode(), 1)
    assert st.events().find(1) == []
    st.events().close()


def test_json_lane_unsupported_falls_back(tmp_path):
    import json

    from predictionio_tpu.data.backends.eventlog import JsonRowsUnsupported

    st = _mk(tmp_path)
    st.events().init(1)
    for rows in (
        # caller-stamped id (breaks the fresh-ids lazy-index invariant)
        [{"event": "r", "entityType": "u", "entityId": "x",
          "eventId": "abc"}],
        # compact ISO the fast parser declines (Python accepts it)
        [{"event": "r", "entityType": "u", "entityId": "x",
          "eventTime": "20260101"}],
        # non-object properties (Python shapes the error)
        [{"event": "r", "entityType": "u", "entityId": "x",
          "properties": "zz"}],
        # escaped property key could hide a reserved prefix
        [{"event": "r", "entityType": "u", "entityId": "x",
          "properties": {"pio_k": 1}}],
    ):
        raw = json.dumps(rows).encode()
        if "\\u0070" not in raw.decode() and "pio_k" in raw.decode():
            # ensure_ascii already resolved the escape: force it back
            raw = raw.replace(b'"pio_k"', b'"\\u0070io_k"')
        with pytest.raises(JsonRowsUnsupported):
            st.events().insert_json_batch(raw, 1)
    assert st.events().find(1) == []
    st.events().close()


def test_fsync_acked_event_survives_sigkill(tmp_path):
    """The HBase SYNC_WAL contract (hbase/HBLEvents.scala:42): with
    FSYNC=1 an acknowledged insert is on disk before the ack — the
    process being SIGKILLed right after the ack must not lose it, and
    reopen must replay it cleanly."""
    import subprocess
    import sys
    import textwrap

    script = textwrap.dedent(f"""
        import json, os
        from predictionio_tpu.data.backends.eventlog import EventLogEventStore
        store = EventLogEventStore({str(str(tmp_path / 'log'))!r}, fsync=True)
        store.init(1)
        ids, codes, _, _ = store.insert_json_batch(json.dumps([
            {{"event": "rate", "entityType": "user", "entityId": "durable",
              "eventTime": "2026-01-01T00:00:00Z"}},
        ]).encode(), 1)
        assert codes == [0]
        print("ACKED", ids[0], flush=True)
        os.kill(os.getpid(), 9)   # no close(), no snapshot, no atexit
    """)
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=120,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))))
    assert proc.returncode == -9, proc.stderr
    acked_id = proc.stdout.split()[1]

    from predictionio_tpu.data.backends.eventlog import EventLogEventStore

    store = EventLogEventStore(str(tmp_path / "log"))
    got = store.get(acked_id, 1)
    assert got is not None and got.entity_id == "durable"
    assert [e.entity_id for e in store.find(1)] == ["durable"]
    store.close()


def test_json_lane_calendar_and_encoding_parity(tmp_path):
    """Code-review regressions: impossible calendar dates are per-row
    400 (not silently normalized), non-object array elements are
    per-row 400 (not a whole-batch failure), invalid UTF-8 bodies are
    rejected up front (json.loads parity), and NUL-bearing names fall
    back to the Python path instead of desyncing the stats buffers."""
    import json

    from predictionio_tpu.data.backends.eventlog import JsonRowsUnsupported
    from predictionio_tpu.data.storage import StorageError

    st = _mk(tmp_path)
    st.events().init(1)

    # impossible date: rejected per-row like Python fromisoformat
    rows = [
        {"event": "ok", "entityType": "u", "entityId": "x",
         "eventTime": "2026-02-28T00:00:00Z"},
        {"event": "bad", "entityType": "u", "entityId": "x",
         "eventTime": "2026-02-31T00:00:00Z"},
        {"event": "leap", "entityType": "u", "entityId": "x",
         "eventTime": "2024-02-29T00:00:00Z"},  # 2024 IS a leap year
    ]
    ids, codes, _, _ = st.events().insert_json_batch(
        json.dumps(rows).encode(), 1, strict=False)
    assert codes == [0, 16, 0], codes

    # non-object element: per-row code 17, batchmates unaffected
    raw = (b'[{"event":"a","entityType":"u","entityId":"u1"}, 42, '
           b'{"event":"b","entityType":"u","entityId":"u2"}]')
    ids, codes, names, _ = st.events().insert_json_batch(raw, 1, strict=False)
    assert codes == [0, 17, 0], codes
    assert names == ["a", "", "b"]

    # invalid UTF-8 body: malformed (the Python json parser refuses it
    # too), nothing appended
    bad = b'[{"event":"a\xff","entityType":"u","entityId":"u1"}]'
    n_before = len(st.events().find(1))
    with pytest.raises(ValueError, match="malformed"):
        st.events().insert_json_batch(bad, 1, strict=False)
    assert len(st.events().find(1)) == n_before

    # STRICT value grammar (code-review regression): mismatched
    # brackets and trailing-junk literals json.loads would reject must
    # never be stored (a poison extra slice breaks every later read)
    for poison in (
        b'[{"event":"e","entityType":"u","entityId":"x","tags":[}]}]',
        b'[{"event":"e","entityType":"u","entityId":"x",'
        b'"properties":{"a":truex}}]',
        b'[{"event":"e","entityType":"u","entityId":"x",'
        b'"properties":{"a":1.5abc}}]',
        b'[{"event":"e","entityType":"u","entityId":"x",'
        b'"properties":{"a":[1,{]}}}]',
    ):
        with pytest.raises((ValueError, JsonRowsUnsupported)):
            st.events().insert_json_batch(poison, 1, strict=False)
    assert len(st.events().find(1)) == n_before
    # every stored record still parses
    for e in st.events().find(1):
        e.properties.to_dict()

    # an escaped NUL inside a name would desync the NUL-joined stats
    # buffers: Python path instead
    nul = b'[{"event":"a\\u0000b","entityType":"u","entityId":"u1"}]'
    with pytest.raises(JsonRowsUnsupported):
        st.events().insert_json_batch(nul, 1, strict=False)
    st.events().close()


def test_json_lane_differential_fuzz(tmp_path):
    """Randomized differential test: for generated API-format events,
    the native JSON lane must store EXACTLY what the Event-object path
    stores (field-for-field, tz fidelity included) or decline to the
    Python path — and arbitrary byte mutations of valid bodies must
    never corrupt the log (every surviving record still decodes)."""
    import json
    import random

    from predictionio_tpu.data.backends.eventlog import JsonRowsUnsupported
    from predictionio_tpu.data.storage import StorageError

    rng = random.Random(20260730)
    ENT = ["u1", "ué", "日本語", 'q"uote', "back\\slash", "tab\tchar",
           "a" * 200, "nul-adjacent\u0001"]
    PROPS = [
        {}, {"rating": 4.5}, {"n": {"deep": [1, 2, {"x": None}]}},
        {"unicode": "中文", "b": True, "f": False, "z": None},
        {"list": [1.5, "two", [3]], "neg": -12.75, "exp": 1.5e-3},
    ]
    # every generated row carries an explicit eventTime: the "absent ->
    # now()" default necessarily differs by microseconds between the
    # two paths (covered by test_json_lane_matches_python_path instead)
    TIMES = ["2026-01-01T00:00:00Z", "2026-06-15T23:59:59.999Z",
             "2024-02-29T12:00:00+05:30", "2026-01-01 08:30:00-02:00",
             1767225600000]

    def gen_event():
        e = {"event": rng.choice(["rate", "view", "$set"]),
             "entityType": "user", "entityId": rng.choice(ENT)}
        if e["event"] != "$set" and rng.random() < 0.7:
            e["targetEntityType"] = "item"
            e["targetEntityId"] = rng.choice(ENT)
        p = rng.choice(PROPS)
        if e["event"] == "$set" and not p:
            p = {"rating": 1.0}
        if p:
            e["properties"] = p
        e["eventTime"] = rng.choice(TIMES)
        if rng.random() < 0.3:
            e["tags"] = ["t1", "ü2"][: rng.randint(1, 2)]
        if rng.random() < 0.2:
            e["prId"] = "pr-9"
        return e

    def canon(events):
        # None-safe sort key (targets/prId are optional)
        return sorted(
            (e.event, e.entity_type, e.entity_id,
             e.target_entity_type or "", e.target_entity_id or "",
             json.dumps(e.properties.to_dict(), sort_keys=True),
             e.event_time, str(e.event_time.utcoffset()), e.tags,
             e.pr_id or "")
            for e in events
        )

    compared = 0
    for trial in range(15):
        rows = [gen_event() for _ in range(rng.randint(1, 12))]
        raw = json.dumps(rows).encode()
        st_n = _mk(tmp_path / f"n{trial}")
        st_n.events().init(1)
        st_p = _mk(tmp_path / f"p{trial}")
        st_p.events().init(1)
        try:
            try:
                ids, codes, _, _ = st_n.events().insert_json_batch(raw, 1)
                assert all(c == 0 for c in codes), (codes, rows)
            except JsonRowsUnsupported:
                continue  # declining is always allowed
            st_p.events().insert_batch([Event.from_dict(r) for r in rows], 1)
            got_n = canon(st_n.events().find(1))
            got_p = canon(st_p.events().find(1))
            assert got_n == got_p, (trial, rows)
            compared += 1
        finally:
            st_n.events().close()
            st_p.events().close()

    assert compared >= 5, "native lane declined too many valid batches"

    # directed poison probes (code-review regression): constructs
    # json.loads REJECTS must never be accepted into the log
    st = _mk(tmp_path / "mut")
    st.events().init(1)
    for poison in (
        b'[{"event":"r","entityType":"u","entityId":"x",'
        b'"properties":{"k":"a\\qb"}}]',          # invalid \q escape
        b'[{"event":"r","entityType":"u","entityId":"x",'
        b'"properties":{"k":"a\\uZZ00"}}]',       # bad \u hex
        b'[{"event":"r","entityType":"u","entityId":"x",'
        b'"properties":{"k":"a\x01b"}}]',          # raw control char
    ):
        with pytest.raises((ValueError, JsonRowsUnsupported, StorageError)):
            st.events().insert_json_batch(poison, 1, strict=False)
    assert st.events().find(1) == []

    # mutation fuzz: corrupting valid bodies must never poison the log
    base = json.dumps([gen_event() for _ in range(4)]).encode()
    for trial in range(120):
        body = bytearray(base)
        muts = rng.randint(1, 3)
        for _ in range(muts):
            pos = rng.randrange(len(body))
            # bias toward the dangerous classes: structural bytes,
            # backslashes and control chars
            body[pos] = rng.choice(
                [0x5C, 0x22, 0x7B, 0x7D, 0x5B, 0x5D, 0x01, 0x1F]
                + [rng.randrange(256)])
        try:
            st.events().insert_json_batch(bytes(body), 1, strict=False)
        except (ValueError, JsonRowsUnsupported, StorageError):
            pass
    # every record the log DID accept must still decode cleanly
    for e in st.events().find(1):
        e.properties.to_dict()
        assert e.event and e.entity_type and e.entity_id
    st.events().close()


def test_json_lane_strict_comma_grammar(tmp_path):
    """ADVICE r4 (high): the native lane's object walks must REQUIRE
    the member comma. A missing comma inside properties used to be
    acked 201 with the malformed raw slice stored verbatim — poisoning
    json.loads on EVERY later read of the app (get/find/training). Both
    loops (parse_row top level + the properties walk) must now reject
    exactly what json.loads rejects, falling back to the Python lane
    which 400s it."""
    import json

    from predictionio_tpu.data.backends.eventlog import JsonRowsUnsupported

    st = _mk(tmp_path)
    st.events().init(1)
    ok = [{"event": "rate", "entityType": "u", "entityId": "x",
           "properties": {"a": 1, "b": 2}}]
    ids, codes, _, _ = st.events().insert_json_batch(
        json.dumps(ok).encode(), 1)
    assert codes == [0]
    n_before = len(st.events().find(1))

    for poison in (
        # missing comma between properties members (the poisoned-read
        # reproduction from the advisor finding)
        b'[{"event":"rate","entityType":"u","entityId":"x",'
        b'"properties":{"a":1 "b":2}}]',
        # missing comma between top-level members (silent grammar
        # divergence: 201 where the Python lane 400s)
        b'[{"event":"rate" "entityType":"u","entityId":"x"}]',
        # missing comma straight after the properties object
        b'[{"event":"rate","entityType":"u","entityId":"x",'
        b'"properties":{"a":1} "targetEntityType":"i"}]',
        # trailing comma in the event array (json.loads rejects)
        b'[{"event":"rate","entityType":"u","entityId":"x"},]',
    ):
        # json.loads parity: the reference body must actually be bad
        with pytest.raises(json.JSONDecodeError):
            json.loads(poison)
        with pytest.raises((ValueError, JsonRowsUnsupported)):
            st.events().insert_json_batch(poison, 1, strict=False)

    # nothing stored, and — the real stake — every read still parses
    events = st.events().find(1)
    assert len(events) == n_before
    for e in events:
        assert e.properties.to_dict() == {"a": 1, "b": 2}
    assert st.events().get(ids[0], 1).properties.to_dict() == {"a": 1, "b": 2}
    st.events().close()


def test_fingerprint_distinguishes_apps_with_identical_content(tmp_path):
    """ADVICE r4 (medium): the machine-global bincache keys on the
    fingerprint, so two apps whose logs coincide on the content
    quadruple (same record sizes/counts — here byte-identical data)
    must still produce DIFFERENT fingerprints, or a retrain on app B
    silently loads app A's cached binned layout."""
    import json

    st = _mk(tmp_path)
    raw = json.dumps([
        {"event": "rate", "entityType": "u", "entityId": f"u{i}",
         "targetEntityType": "i", "targetEntityId": f"i{i}",
         "properties": {"rating": 3.5}}
        for i in range(50)
    ]).encode()
    st.events().init(1)
    st.events().init(2)
    st.events().insert_json_batch(raw, 1)
    st.events().insert_json_batch(raw, 2)
    fp1 = st.events().data_fingerprint(1)
    fp2 = st.events().data_fingerprint(2)
    # identical content quadruple...
    assert fp1.split("-", 1)[1] == fp2.split("-", 1)[1]
    # ...but distinct log identity
    assert fp1 != fp2
    # channels are distinct logs too
    st.events().init(1, 7)
    st.events().insert_json_batch(raw, 1, 7)
    assert st.events().data_fingerprint(1, 7) != fp1
    # and the fingerprint is stable for the same unchanged log
    assert st.events().data_fingerprint(1) == fp1
    st.events().close()


# -- vectorized row-lane append (el_append_rows) --------------------------------

def test_insert_batch_fast_lane_full_round_trip(tmp_path):
    """The vectorized pack (numpy struct assembly + one native bulk
    call) must preserve EVERY record field the per-row _pack lane
    carried: tz-offset times, properties, tags, prId, caller-stamped
    canonical and non-canonical ids, NUL bytes inside ids."""
    st = _mk(tmp_path)
    app = st.apps().insert("rows")
    st.events().init(app.id)
    tz = dt.timezone(dt.timedelta(hours=-7))
    evs = [
        Event(event="rate", entity_type="user", entity_id="u1",
              target_entity_type="item", target_entity_id="i1",
              properties={"rating": 4.5},
              event_time=dt.datetime(2026, 1, 1, tzinfo=UTC)),
        Event(event="$set", entity_type="user", entity_id="u2",
              properties={"a": [1, 2], "b": {"c": "x"}},
              tags=("t1", "t2"), pr_id="p9",
              event_time=dt.datetime(2026, 1, 2, 3, 4, 5, 123456, tzinfo=tz)),
        Event(event="view", entity_type="user", entity_id="u\x00weird",
              event_time=dt.datetime(2026, 2, 1, tzinfo=UTC),
              event_id="deadbeef" * 4),
        Event(event="view", entity_type="user", entity_id="u4",
              event_time=dt.datetime(2026, 2, 2, tzinfo=UTC),
              event_id="my-custom-id"),
    ]
    ids = st.events().insert_batch(evs, app.id)
    assert ids[2] == "deadbeef" * 4 and ids[3] == "my-custom-id"
    for eid, e in zip(ids, evs):
        got = st.events().get(eid, app.id)
        assert got is not None, eid
        assert got.event == e.event
        assert got.entity_id == e.entity_id
        assert got.target_entity_id == e.target_entity_id
        assert got.properties.to_dict() == dict(e.properties)
        assert got.event_time == e.event_time
        assert got.tags == e.tags and got.pr_id == e.pr_id
    # survives reopen (the packed wire records are well-formed)
    st.events().close()
    st2 = _mk(tmp_path)
    got = st2.events().get(ids[0], app.id)
    assert got is not None and got.properties.to_dict() == {"rating": 4.5}
    st2.events().close()


def test_insert_batch_fast_lane_wire_limit_error(tmp_path):
    from predictionio_tpu.data.storage import StorageError

    st = _mk(tmp_path)
    app = st.apps().insert("rows2")
    st.events().init(app.id)
    big = Event(event="rate", entity_type="user", entity_id="x" * 70_000,
                event_time=dt.datetime(2026, 1, 1, tzinfo=UTC))
    with pytest.raises(StorageError, match="65534"):
        st.events().insert_batch([big], app.id)
    # nothing appended: the batch is validated before any write
    assert st.events().find(app.id) == []
    st.events().close()


def test_insert_batch_fast_lane_moves_freshness_clock(tmp_path):
    from predictionio_tpu.obs import perfacct

    st = _mk(tmp_path)
    app = st.apps().insert("rows3")
    st.events().init(app.id)
    perfacct.LEDGER.clear()
    st.events().insert_batch([ev("u1")], app.id)
    assert perfacct.LEDGER.staleness_seconds() > 0.0
    perfacct.LEDGER.clear()
    st.events().close()
