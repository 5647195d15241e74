"""REST storage tier: DAO-level storage server + `rest` client backend.

The scale-out storage story (ref: the reference reaches HBase via client
RPC, Elasticsearch via the transport client, HDFS for model blobs —
SURVEY.md §2.5): N hosts configure a ``rest``-type storage source
pointing at one storage server and share one logical METADATA /
EVENTDATA / MODELDATA. Includes the cross-host proof: train in one
process, deploy from another, each with its own private localfs root,
sharing only the REST tiers.
"""

import datetime as _dt
import json
import os
import subprocess
import sys
import time

import pytest

from predictionio_tpu.data.event import Event
from predictionio_tpu.data.metadata import (
    AccessKey,
    EngineInstance,
    EngineManifest,
    Model,
)
from predictionio_tpu.data.storage import UNSET, Storage, StorageError
from predictionio_tpu.serving.storage_server import StorageServer

UTC = _dt.timezone.utc
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _client_env(port: int, auth_key=None) -> dict:
    env = {
        "PIO_STORAGE_SOURCES_CENTRAL_TYPE": "rest",
        "PIO_STORAGE_SOURCES_CENTRAL_HOSTS": "127.0.0.1",
        "PIO_STORAGE_SOURCES_CENTRAL_PORTS": str(port),
        "PIO_STORAGE_REPOSITORIES_METADATA_NAME": "meta",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "CENTRAL",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_NAME": "events",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "CENTRAL",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_NAME": "models",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "CENTRAL",
    }
    if auth_key:
        env["PIO_STORAGE_SOURCES_CENTRAL_AUTH_KEY"] = auth_key
    return env


def _client_storage(port: int, auth_key=None) -> Storage:
    return Storage.from_env(_client_env(port, auth_key))


@pytest.fixture()
def rest_storage(memory_storage):
    """(server over the in-memory storage, rest-client Storage)."""
    server = StorageServer(storage=memory_storage, host="127.0.0.1", port=0).start()
    try:
        yield memory_storage, _client_storage(server.port)
    finally:
        server.stop()


def _event(name="rate", eid="u1", tid=None, t=None, props=None):
    return Event(
        event=name,
        entity_type="user",
        entity_id=eid,
        target_entity_type="item" if tid else None,
        target_entity_id=tid,
        properties=props or {},
        event_time=t or _dt.datetime(2026, 1, 1, tzinfo=UTC),
    )


def test_event_roundtrip_and_filters(rest_storage):
    _, client = rest_storage
    store = client.events()
    store.init(1)
    t0 = _dt.datetime(2026, 1, 1, tzinfo=UTC)
    ids = store.insert_batch(
        [
            _event("rate", "u1", "i1", t0, {"rating": 4.5}),
            _event("buy", "u1", "i2", t0 + _dt.timedelta(hours=1)),
            _event("$set", "u2", None, t0 + _dt.timedelta(hours=2), {"a": 1}),
        ],
        1,
    )
    assert len(ids) == 3

    got = store.get(ids[0], 1)
    assert got.event == "rate"
    assert got.properties.get("rating") == 4.5
    assert got.event_time == t0

    assert len(store.find(1)) == 3
    assert [e.event for e in store.find(1, event_names=["buy"])] == ["buy"]
    # half-open [start, until) window over the wire
    win = store.find(1, start_time=t0, until_time=t0 + _dt.timedelta(hours=1))
    assert [e.event for e in win] == ["rate"]
    # tri-state target filter: None means "no target", UNSET means "any"
    assert len(store.find(1, target_entity_type=None)) == 1
    assert len(store.find(1, target_entity_type="item")) == 2
    assert store.find(1, target_entity_type=UNSET) == store.find(1)
    newest = store.find(1, limit=1, reversed=True)
    assert newest[0].event == "$set"

    assert store.delete(ids[1], 1) is True
    assert store.delete(ids[1], 1) is False
    assert len(store.find(1)) == 2

    # the derived aggregate_properties runs client-side over REST find
    props = store.aggregate_properties(1, "user")
    assert props["u2"].get("a") == 1


def test_event_errors_propagate(rest_storage):
    _, client = rest_storage
    with pytest.raises(StorageError):
        client.events().find(99)  # un-init()ed app table


def test_metadata_repos(rest_storage):
    _, client = rest_storage
    app = client.apps().insert("restapp", "desc")
    assert app.id >= 1
    assert client.apps().get_by_name("restapp").description == "desc"
    with pytest.raises(StorageError):
        client.apps().insert("restapp")  # duplicate name propagates

    key = client.access_keys().insert(AccessKey.generate(app.id, ["rate"]))
    assert client.access_keys().get(key).events == ["rate"]
    assert [k.key for k in client.access_keys().get_by_app_id(app.id)] == [key]

    ch = client.channels().insert("live", app.id)
    assert client.channels().get_by_app_id(app.id)[0].name == "live"
    with pytest.raises(StorageError):
        client.channels().insert("bad name!", app.id)

    manifest = EngineManifest(id="e1", version="1", name="engine one")
    client.engine_manifests().insert(manifest)
    assert client.engine_manifests().get("e1", "1").name == "engine one"
    assert client.engine_manifests().get("e1", "2") is None


def test_engine_instances_over_rest(rest_storage):
    _, client = rest_storage
    repo = client.engine_instances()
    t = _dt.datetime(2026, 1, 1, tzinfo=UTC)

    def make(i, status, start):
        return EngineInstance(
            id="", status=status, start_time=start, end_time=start,
            engine_id="e", engine_version="0", engine_variant="default",
            engine_factory="f", batch=f"b{i}",
        )

    id1 = repo.insert(make(1, "COMPLETED", t))
    id2 = repo.insert(make(2, "COMPLETED", t + _dt.timedelta(minutes=5)))
    repo.insert(make(3, "FAILED", t + _dt.timedelta(minutes=9)))
    latest = repo.get_latest_completed("e", "0", "default")
    assert latest.id == id2
    assert latest.start_time == t + _dt.timedelta(minutes=5)  # tz survives
    assert [i.id for i in repo.get_completed("e", "0", "default")] == [id1, id2][::-1]

    inst = repo.get(id1)
    inst.status = "FAILED"
    repo.update(inst)
    assert repo.get(id1).status == "FAILED"


def test_model_blobs_over_rest(rest_storage):
    _, client = rest_storage
    blob = bytes(range(256)) * 41  # binary, non-UTF8
    client.models().insert(Model(id="inst-1", models=blob))
    assert client.models().get("inst-1").models == blob
    assert client.models().get("missing") is None
    client.models().delete("inst-1")
    assert client.models().get("inst-1") is None


def test_auth_key_required(memory_storage):
    server = StorageServer(
        storage=memory_storage, host="127.0.0.1", port=0, auth_key="sekret"
    ).start()
    try:
        unauthed = _client_storage(server.port)
        with pytest.raises(StorageError):
            unauthed.apps().get_all()
        assert unauthed.client_for("METADATA").health_check() is False
        authed = _client_storage(server.port, auth_key="sekret")
        assert authed.apps().get_all() == []
        assert authed.client_for("METADATA").health_check() is True
    finally:
        server.stop()


def test_status_verifies_rest_repos(rest_storage):
    _, client = rest_storage
    assert client.verify_all_data_objects() == {
        "METADATA": True, "EVENTDATA": True, "MODELDATA": True,
    }
    dead = _client_storage(1)  # nothing listens on port 1
    assert not any(dead.verify_all_data_objects().values())


# ---------------------------------------------------------------------------
# Cross-host: train on host A, deploy on host B
# ---------------------------------------------------------------------------

_TRAIN_A = """
from predictionio_tpu.core import Engine, EngineParams
from predictionio_tpu.data.storage import get_storage
from predictionio_tpu.workflow.train import run_train
from tests.sample_engine import Algo0, DataSource0, IdParams, Preparator0, Serving0

engine = Engine(
    data_source_classes={"ds": DataSource0},
    preparator_classes={"prep": Preparator0},
    algorithm_classes={"algo": Algo0},
    serving_classes={"serve": Serving0},
)
ep = EngineParams(
    data_source_params=("ds", IdParams(id=1)),
    preparator_params=("prep", IdParams(id=2)),
    algorithm_params_list=[("algo", IdParams(id=7))],
    serving_params=("serve", IdParams(id=9)),
)
instance = run_train(engine, ep, engine_id="xhost", storage=get_storage())
print("TRAINED", instance.id)
"""

_DEPLOY_B = """
from predictionio_tpu.core import Engine
from predictionio_tpu.data.storage import get_storage
from predictionio_tpu.workflow.deploy import prepare_deploy
from tests.sample_engine import Algo0, DataSource0, Preparator0, Query, Serving0

storage = get_storage()
status = storage.verify_all_data_objects()
assert all(status.values()), status
engine = Engine(
    data_source_classes={"ds": DataSource0},
    preparator_classes={"prep": Preparator0},
    algorithm_classes={"algo": Algo0},
    serving_classes={"serve": Serving0},
)
instance = storage.engine_instances().get_latest_completed("xhost", "0", "default")
assert instance is not None, "instance trained on host A not visible on host B"
deployment = prepare_deploy(engine, instance, storage=storage)
prediction = deployment.query(Query(q=21))
print("SERVED", prediction.q, prediction.algo_id)
"""


def _host_env(tmp_path, name: str, port: int) -> dict:
    """Host env: private localfs root; METADATA+MODELDATA shared via rest."""
    root = tmp_path / name
    root.mkdir()
    env = dict(os.environ)
    env.pop("PYTEST_CURRENT_TEST", None)
    env.update(
        {
            "PYTHONPATH": REPO_ROOT,
            "JAX_PLATFORMS": "cpu",
            "PIO_STORAGE_SOURCES_LOCAL_TYPE": "localfs",
            "PIO_STORAGE_SOURCES_LOCAL_PATH": str(root),
            "PIO_STORAGE_SOURCES_CENTRAL_TYPE": "rest",
            "PIO_STORAGE_SOURCES_CENTRAL_HOSTS": "127.0.0.1",
            "PIO_STORAGE_SOURCES_CENTRAL_PORTS": str(port),
            "PIO_STORAGE_SOURCES_CENTRAL_AUTH_KEY": "xhost-secret",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_NAME": "events",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "LOCAL",
            "PIO_STORAGE_REPOSITORIES_METADATA_NAME": "meta",
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "CENTRAL",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_NAME": "models",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "CENTRAL",
        }
    )
    return env


def test_train_on_host_a_deploy_on_host_b(tmp_path):
    """Two processes, two private localfs roots, one shared REST tier:
    the workflow the reference runs over ES metadata + HDFS models
    (hdfs/HDFSModels.scala:28)."""
    shared = tmp_path / "shared"
    shared.mkdir()
    central = Storage.from_env(
        {
            "PIO_STORAGE_SOURCES_FS_TYPE": "localfs",
            "PIO_STORAGE_SOURCES_FS_PATH": str(shared),
            "PIO_STORAGE_REPOSITORIES_METADATA_NAME": "meta",
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "FS",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_NAME": "events",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "FS",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_NAME": "models",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "FS",
        }
    )
    server = StorageServer(
        storage=central, host="127.0.0.1", port=0, auth_key="xhost-secret"
    ).start()
    try:
        a = subprocess.run(
            [sys.executable, "-c", _TRAIN_A], cwd=REPO_ROOT, text=True,
            env=_host_env(tmp_path, "hostA", server.port),
            capture_output=True, timeout=120,
        )
        assert a.returncode == 0, a.stdout + a.stderr
        assert "TRAINED" in a.stdout

        b = subprocess.run(
            [sys.executable, "-c", _DEPLOY_B], cwd=REPO_ROOT, text=True,
            env=_host_env(tmp_path, "hostB", server.port),
            capture_output=True, timeout=120,
        )
        assert b.returncode == 0, b.stdout + b.stderr
        assert "SERVED 21 7" in b.stdout

        # the model blob physically lives in the shared tier, not A or B
        models_dir = shared / "models"
        assert any(models_dir.iterdir())
    finally:
        server.stop()


def test_two_writers_share_one_logical_eventdata(rest_storage):
    """Two rest clients (distinct client objects, same server) see one
    consistent event store — the multi-host EVENTDATA story (ref:
    HBEventsUtil.scala:47 shared HBase tables)."""
    _, client_a = rest_storage
    server_port = client_a.client_for("EVENTDATA").config["PORTS"]
    client_b = _client_storage(int(server_port))

    client_a.events().init(7)
    t0 = _dt.datetime(2026, 2, 1, tzinfo=UTC)
    for h, (client, uid) in enumerate([(client_a, "a"), (client_b, "b")] * 3):
        client.events().insert(
            _event("view", f"u-{uid}", f"i{h}", t0 + _dt.timedelta(hours=h)), 7
        )
    seen_a = client_a.events().find(7)
    seen_b = client_b.events().find(7)
    assert len(seen_a) == 6
    assert [e.event_id for e in seen_a] == [e.event_id for e in seen_b]
    # a delete through one host is immediately visible to the other
    assert client_b.events().delete(seen_a[0].event_id, 7)
    assert len(client_a.events().find(7)) == 5


def test_columnar_bulk_roundtrip_over_rest(rest_storage):
    """Bulk training reads/ingest travel as binary npz — 20M-row scale
    without per-event JSON (the region-scan role of HBPEvents.scala:48,
    over the wire)."""
    import numpy as np

    from predictionio_tpu.data.storage import EventColumns

    _, client = rest_storage
    client.events().init(3)
    cols = EventColumns(
        entity_codes=np.array([0, 1, 0], np.int32),
        target_codes=np.array([0, 1, -1], np.int32),
        name_codes=np.array([0, 0, 1], np.int32),
        values=np.array([4.5, np.nan, np.nan], np.float64),
        times_us=np.array([1_000_000, 2_000_000, 3_000_000], np.int64),
        entity_vocab=["anna", "bo"],
        target_vocab=["x1", "x2"],
        names=["rate", "$set"],
    )
    n = client.events().insert_columnar(
        cols, 3, entity_type="user", target_entity_type="item",
        value_property="rating",
    )
    assert n == 3

    back = client.events().find_columnar(
        3, value_property="rating", time_ordered=False
    )
    assert len(back) == 3
    resolved = {
        (back.entity_vocab[back.entity_codes[i]],
         back.target_vocab[back.target_codes[i]] if back.target_codes[i] >= 0 else None,
         back.names[back.name_codes[i]])
        for i in range(3)
    }
    assert resolved == {("anna", "x1", "rate"), ("bo", "x2", "rate"),
                        ("anna", None, "$set")}
    vals = sorted(back.values[~np.isnan(back.values)])
    assert vals == [4.5]
    # filters apply server-side on the bulk route too
    only_rate = client.events().find_columnar(3, event_names=["rate"])
    assert len(only_rate) == 2
    # and the row-level API sees the bulk-ingested events
    events = client.events().find(3)
    assert {e.entity_id for e in events} == {"anna", "bo"}


def test_columnar_rest_edge_cases(rest_storage):
    """Unicode entity types (query-string params), NUL bytes inside ids
    (exact-offset vocab wire format), and loud typo'd filters."""
    import numpy as np

    from predictionio_tpu.data.storage import EventColumns

    _, client = rest_storage
    client.events().init(9)
    cols = EventColumns(
        entity_codes=np.array([0, 1], np.int32),
        target_codes=np.array([0, 0], np.int32),
        name_codes=np.array([0, 0], np.int32),
        values=np.array([1.0, 2.0], np.float64),
        times_us=np.array([1, 2], np.int64),
        entity_vocab=["አበበ", "a\0b"],     # unicode + embedded NUL
        target_vocab=["商品-1"],
        names=["rate"],
    )
    n = client.events().insert_columnar(
        cols, 9, entity_type="ユーザー", target_entity_type="商品",
        value_property="rating",
    )
    assert n == 2
    back = client.events().find_columnar(9, value_property="rating",
                                         time_ordered=False)
    assert sorted(back.entity_vocab[c] for c in back.entity_codes) == \
        sorted(["አበበ", "a\0b"])
    assert back.target_vocab[back.target_codes[0]] == "商品-1"
    rows = client.events().find(9)
    assert {e.entity_type for e in rows} == {"ユーザー"}

    with pytest.raises(TypeError, match="unexpected filters"):
        client.events().find_columnar(9, event_name=["rate"])  # typo
    with pytest.raises(TypeError):   # find()'s fixed signature rejects
        client.events().find(9, entity_types="user")


def test_keepalive_survives_short_circuit_responses(memory_storage):
    """HTTP/1.1 keep-alive: responses sent before the handler reads the
    request body (auth denial, unknown route) must still drain it, or
    the next request on the same connection is parsed from leftover
    body bytes."""
    import http.client

    server = StorageServer(
        storage=memory_storage, host="127.0.0.1", port=0, auth_key="sekret"
    ).start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", server.port)
        body = json.dumps({"app_id": 1, "junk": "x" * 4096})
        # 1) denied POST with a body (no auth header)
        conn.request("POST", "/storage/events/init", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 401
        resp.read()
        # 2) unknown route with a body, authed
        conn.request("POST", "/storage/events/nope", body=body,
                     headers={"X-PIO-Storage-Key": "sekret"})
        resp = conn.getresponse()
        assert resp.status == 404
        resp.read()
        # 3) a real request on the SAME connection still parses cleanly
        conn.request("POST", "/storage/events/init", body=json.dumps({"app_id": 1}),
                     headers={"X-PIO-Storage-Key": "sekret"})
        resp = conn.getresponse()
        assert resp.status == 200
        assert json.loads(resp.read()) == {"ok": True}
        conn.close()
    finally:
        server.stop()


def test_compact_over_rest(tmp_path):
    """`pio app compact` against a rest-configured client must run the
    compaction ON the storage server's backend and return real stats
    (HBase major-compaction role reached through the network tier)."""
    from tests.test_storage import make_storage

    server_storage = make_storage("eventlog", tmp_path)
    server = StorageServer(storage=server_storage, host="127.0.0.1", port=0).start()
    try:
        client = _client_storage(server.port)
        app = client.apps().insert("rc")
        client.events().init(app.id)
        ids = client.events().insert_batch(
            [_event(eid=f"u{i}") for i in range(40)], app.id)
        for eid in ids[:30]:
            client.events().delete(eid, app.id)
        stats = client.events().compact(app.id)
        assert stats["dropped"] == 30
        assert stats["after_bytes"] < stats["before_bytes"]
        assert len(client.events().find(app.id)) == 10
    finally:
        server.stop()
        server_storage.events().close()


def test_scan_fetch_resumes_after_connection_drop(rest_storage, monkeypatch):
    """A connection that dies mid-transfer of a bulk scan must resume
    from the last received byte (offset fetch), not restart or fail
    (HBase client retry role)."""
    import urllib.request as _ur

    _, client = rest_storage
    client.events().init(5)
    client.events().insert_batch(
        [_event(eid=f"u{i}", tid=f"i{i % 7}", props={"rating": float(i)})
         for i in range(500)], 5)

    offsets_seen = []
    real_urlopen = _ur.urlopen

    class _DroppingResp:
        """Proxy that yields a first chunk then drops the connection."""

        def __init__(self, resp):
            self._resp = resp
            self._served = False

        def read(self, n=-1):
            if self._served:
                self._resp.close()
                raise ConnectionResetError("injected drop")
            self._served = True
            return self._resp.read(100)  # partial: 100 bytes then die

        def __enter__(self):
            return self

        def __exit__(self, *a):
            return False

    state = {"first": True}

    def flaky_urlopen(req, timeout=None):
        url = req.full_url if hasattr(req, "full_url") else req
        if "/storage/events/scan/" in url and "offset=" in url:
            offsets_seen.append(int(url.rsplit("offset=", 1)[1]))
            if state["first"]:
                state["first"] = False
                return _DroppingResp(real_urlopen(req, timeout=timeout))
        return real_urlopen(req, timeout=timeout)

    monkeypatch.setattr(_ur, "urlopen", flaky_urlopen)
    cols = client.events().find_columnar(5, value_property="rating",
                                         time_ordered=True)
    assert len(cols.entity_codes) == 500
    assert [cols.entity_vocab[c] for c in cols.entity_codes[:3]] == \
        ["u0", "u1", "u2"]
    # first fetch started at 0, the resume continued at the 100 received
    # bytes — never from scratch
    assert offsets_seen[0] == 0 and offsets_seen[1] == 100


def test_scan_survives_server_restart_mid_scan(tmp_path):
    """Kill the storage server after the scan was prepared but before
    the fetch, restart it (fresh scan registry), and the client must
    complete correctly by re-preparing."""
    from predictionio_tpu.data.backends.rest import RestEventStore

    server_storage = make_memory_storage()
    server1 = StorageServer(storage=server_storage, host="127.0.0.1", port=0).start()
    port = server1.port
    client = _client_storage(port)
    client.events().init(3)
    client.events().insert_batch(
        [_event(eid=f"u{i}", props={"rating": 1.0}) for i in range(50)], 3)

    holder = {"server": server1, "restarted": False}
    orig_fetch = RestEventStore._fetch_scan

    def fetch_with_restart(self, scan_id, total, spool):
        if not holder["restarted"]:
            holder["restarted"] = True
            holder["server"].stop()
            holder["server"] = StorageServer(
                storage=server_storage, host="127.0.0.1", port=port).start()
        return orig_fetch(self, scan_id, total, spool)

    try:
        RestEventStore._fetch_scan = fetch_with_restart
        cols = client.events().find_columnar(3, value_property="rating")
        assert len(cols.entity_codes) == 50
        assert holder["restarted"]
    finally:
        RestEventStore._fetch_scan = orig_fetch
        holder["server"].stop()


def make_memory_storage():
    from predictionio_tpu.data.storage import Storage

    return Storage.from_env({
        "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
        "PIO_STORAGE_REPOSITORIES_METADATA_NAME": "meta",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_NAME": "events",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_NAME": "models",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
    })


def test_idempotent_reads_retry_through_transient_outage(tmp_path):
    """An unreachable server raises StorageUnavailableError after
    bounded retries; a server that comes back inside the retry budget
    is transparent to idempotent reads."""
    import threading

    from predictionio_tpu.data.storage import StorageUnavailableError

    server_storage = make_memory_storage()
    probe = StorageServer(storage=server_storage, host="127.0.0.1", port=0).start()
    port = probe.port
    probe.stop()  # port now free; the client will find it dead

    client = _client_storage(port)
    with pytest.raises(StorageUnavailableError):
        client.apps().get_all()

    # bring the server up concurrently with the retried call. Backoff
    # is FULL-jitter now (resilience Policy): individual delays can be
    # ~0, so a generous retry budget — not delay arithmetic — is what
    # makes "comes back inside the budget" deterministic here.
    retry_env = dict(_client_env(port))
    retry_env["PIO_STORAGE_SOURCES_CENTRAL_RETRIES"] = "6"
    client = Storage.from_env(retry_env)
    started = {}

    def bring_up():
        time.sleep(0.05)
        started["server"] = StorageServer(
            storage=server_storage, host="127.0.0.1", port=port).start()

    t = threading.Thread(target=bring_up)
    t.start()
    try:
        assert client.apps().get_all() == []
    finally:
        t.join()
        started["server"].stop()


def test_insert_never_auto_retries(tmp_path):
    """Non-idempotent writes must fail fast on connection errors (a
    blind replay could double-write)."""
    from predictionio_tpu.data.storage import StorageUnavailableError

    probe = StorageServer(storage=make_memory_storage(),
                          host="127.0.0.1", port=0).start()
    port = probe.port
    probe.stop()
    client = _client_storage(port)
    t0 = time.time()
    with pytest.raises(StorageUnavailableError):
        client.events().insert(_event(), 1)
    # no backoff sleeps -> fails in well under the first retry delay
    assert time.time() - t0 < 0.2


def test_strict_json_row_error_maps_to_clean_storage_error(tmp_path):
    """ADVICE r4 (low): a strict=True row-validation failure on the
    server is a PERMANENT client-data error; the rest client must
    surface it as the same clean StorageError the local DAO raises
    synchronously — not a transport-wrapped, retryable-looking server
    fault — and malformed JSON must stay a ValueError (400 route)."""
    import json

    from predictionio_tpu.data.storage import StorageError
    from tests.test_storage import make_storage

    server_storage = make_storage("eventlog", tmp_path)
    server = StorageServer(storage=server_storage, host="127.0.0.1",
                           port=0).start()
    try:
        client = _client_storage(server.port)
        app = client.apps().insert("strictjson")
        client.events().init(app.id)
        bad = json.dumps([
            {"event": "ok", "entityType": "u", "entityId": "u1"},
            {"event": "$badspecial", "entityType": "u", "entityId": "u2"},
        ]).encode()
        with pytest.raises(StorageError) as ei:
            client.events().insert_json_batch(bad, app.id, strict=True)
        # the clean server-side message, not the HTTP-wrapped transport
        # string (local-path parity)
        assert "HTTP 400" not in str(ei.value)
        assert "event 1" in str(ei.value)
        # strict: nothing appended
        assert client.events().find(app.id) == []
        # a body malformed at the array level stays ValueError (the 400
        # ValueError-discriminator path); object-level grammar the
        # native lane declines (e.g. missing member comma) raises
        # JsonRowsUnsupported instead, routing to the Python lane
        with pytest.raises(ValueError):
            client.events().insert_json_batch(
                b'[{"event":"e","entityType":"u","entityId":"x"} '
                b'{"event":"f","entityType":"u","entityId":"y"}]',
                app.id, strict=True)
        # the server survived both client errors
        ids, codes, _, _ = client.events().insert_json_batch(
            json.dumps([{"event": "ok", "entityType": "u",
                         "entityId": "u1"}]).encode(), app.id)
        assert codes == [0]
    finally:
        server.stop()
        server_storage.events().close()
