"""Hours-shaped behavior in minutes form: the
event server, engine server and storage server under CONTINUOUS mixed
load — ingest + queries + reads + periodic hot /reload + scan spools —
asserting what only time surfaces: flat RSS (no leak), the scan-spool
TTL reaper actually firing, and zero 5xx across the whole run.

The burst/stress tests elsewhere cover correctness under contention;
this one covers RESOURCE behavior under sustained duty. Marked slow:
~2-3 minutes of wall clock by design.
"""

import json
import os
import time
import urllib.error
import urllib.request

import pytest

from predictionio_tpu.data.metadata import AccessKey
from predictionio_tpu.serving.event_server import EventServer


def _rss_anon_kb() -> int:
    """Anonymous (heap) RSS: excludes file-backed pages, because the
    ingest legitimately grows the mmap'd event log all soak long —
    log-file pages in the page cache are data, not a leak.

    ``RssAnon:`` only exists on Linux >= 4.5. On older kernels the only
    per-process RSS in /proc is ``VmRSS:``, which COUNTS the growing
    mmap'd log's resident pages — a flat-RSS assertion over it would
    flag legitimate data growth as a leak — so the test skips there
    with the reason instead of failing on a probe the kernel cannot
    answer (it failed at seed on pre-4.5 containers)."""
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("RssAnon:"):
                return int(line.split()[1])
    pytest.skip(
        "kernel /proc/self/status lacks RssAnon: (Linux < 4.5); VmRSS "
        "would count the mmap'd event log's resident pages as a leak, "
        "so the flat-RSS soak assertion cannot run here")


def _post(url, body, ok=(200, 201)):
    req = urllib.request.Request(
        url, data=body, headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        assert e.code < 500, (e.code, e.read()[:300])
        return e.code, b""


@pytest.mark.slow
def test_soak_servers_flat_rss_zero_5xx(tmp_path):
    """~2 minutes of continuous mixed duty against real servers over a
    real eventlog store; RSS sampled each cycle must stay flat."""
    _rss_anon_kb()  # probe EARLY: pre-4.5 kernels skip before any
    #                 server spins up, not two minutes into the soak
    import threading

    from predictionio_tpu.core import Engine
    from predictionio_tpu.core.params import EngineParams
    from predictionio_tpu.serving.engine_server import EngineServer
    from predictionio_tpu.serving.storage_server import StorageServer
    from predictionio_tpu.workflow.train import run_train
    from tests.test_servers import (
        ConstAlgo,
        ConstDataSource,
        ConstParams,
        FirstServing,
        IdentityPreparator,
    )
    from tests.test_storage import make_storage

    storage = make_storage("eventlog", tmp_path)
    app = storage.apps().insert("soak")
    key = AccessKey.generate(app.id)
    storage.access_keys().insert(key)
    storage.events().init(app.id)

    ev_srv = EventServer(storage=storage, host="127.0.0.1", port=0).start()
    # short-TTL storage server so the spool reaper provably fires
    # within the soak window
    st_srv = StorageServer(storage=storage, host="127.0.0.1", port=0,
                           scan_ttl=5.0).start()

    engine = Engine(ConstDataSource, IdentityPreparator,
                    {"c": ConstAlgo}, FirstServing)
    ep = EngineParams(
        data_source_params=("", ConstParams(value=1.0)),
        preparator_params=("", None),
        algorithm_params_list=[("c", ConstParams(value=2.0))],
        serving_params=("", None),
    )
    run_train(engine, ep, engine_id="soak", storage=storage)
    en_srv = EngineServer(engine, "soak", host="127.0.0.1", port=0,
                          storage=storage).start()

    ev_base = f"http://127.0.0.1:{ev_srv.port}"
    en_base = f"http://127.0.0.1:{en_srv.port}"
    st_base = f"http://127.0.0.1:{st_srv.port}"
    qs = f"?accessKey={key.key}"

    duration = float(os.environ.get("PIO_SOAK_SECONDS", "120"))
    deadline = time.monotonic() + duration
    errors = []
    counts = {"ingest": 0, "query": 0, "read": 0, "reload": 0, "scan": 0}
    stop = threading.Event()

    def ingest_loop():
        k = 0
        while not stop.is_set():
            batch = json.dumps([
                {"event": "rate", "entityType": "user",
                 "entityId": f"u{(k + j) % 500}",
                 "targetEntityType": "item",
                 "targetEntityId": f"i{(k * 7 + j) % 200}",
                 "properties": {"rating": float(1 + (k + j) % 5)}}
                for j in range(50)
            ]).encode()
            s, _ = _post(f"{ev_base}/batch/events.json{qs}", batch)
            assert s in (200, 201), s
            counts["ingest"] += 50
            k += 50
            time.sleep(0.01)

    def query_loop():
        while not stop.is_set():
            s, body = _post(f"{en_base}/queries.json",
                            json.dumps({"mult": 2}).encode())
            assert s == 200 and b"result" in body, (s, body[:200])
            counts["query"] += 1
            time.sleep(0.005)

    def read_loop():
        while not stop.is_set():
            try:
                with urllib.request.urlopen(
                        f"{ev_base}/events.json{qs}&limit=20") as r:
                    assert r.status == 200
                    r.read()
            except urllib.error.HTTPError as e:
                # empty result set is a 404 by reference parity
                # (EventAPI.scala:209); anything 5xx fails the soak
                assert e.code == 404, (e.code, e.read()[:200])
            counts["read"] += 1
            time.sleep(0.02)

    def guarded(fn):
        def run():
            try:
                fn()
            except Exception as e:  # noqa: BLE001
                errors.append(repr(e))
                stop.set()
        return run

    threads = [threading.Thread(target=guarded(f), daemon=True)
               for f in (ingest_loop, query_loop, read_loop)]
    for t in threads:
        t.start()

    rss_samples = []
    spool_reaped = False
    try:
        cycle = 0
        while time.monotonic() < deadline and not stop.is_set():
            time.sleep(5.0)
            cycle += 1
            # periodic hot reload (warm-before-swap path; GET route,
            # CreateServer.scala:592 parity)
            with urllib.request.urlopen(f"{en_base}/reload") as r:
                assert r.status == 200
                r.read()
            counts["reload"] += 1
            # open a columnar scan spool and DON'T fetch or release it:
            # the TTL reaper (5 s) must clean it up, not an explicit
            # close
            payload = json.dumps({"app_id": app.id, "channel_id": None,
                                  "event_names": ["rate"]}).encode()
            s, body = _post(f"{st_base}/storage/events/find_columnar",
                            payload)
            if s in (200, 201):
                counts["scan"] += 1
            with urllib.request.urlopen(f"{st_base}/storage/stats") as r:
                stats = json.loads(r.read())
            live = stats.get("live_scan_spools")
            if counts["scan"] >= 3 and live is not None and live < counts["scan"]:
                spool_reaped = True   # older spools were TTL-collected
            rss_samples.append(_rss_anon_kb())
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10)
        en_srv.stop()
        st_srv.stop()
        ev_srv.stop()
        storage.events().close()

    assert not errors, errors[0]
    # real duty happened
    assert counts["ingest"] > 1000 and counts["query"] > 500, counts
    assert counts["reload"] >= 3
    # the TTL reaper fired (spools opened every cycle, TTL 5 s)
    assert spool_reaped, (counts, stats)
    # bounded heap: anonymous RSS may grow with the DATA the soak
    # itself ingests (in-process eventlog indexes are data-proportional
    # by design) but never faster — growth beyond ~3x the ingested
    # bytes (+25 MB allocator slack) means a leak (spooled scans,
    # request objects, reload leaving the old deployment alive)
    assert len(rss_samples) >= 6, rss_samples
    early = min(rss_samples[:3])
    tail = rss_samples[-1]
    ingested_kb = counts["ingest"] * 150 // 1024   # ~150 B/event
    allowed = early + 3 * ingested_kb + 25_000
    assert tail < allowed, (
        f"anon RSS grew {early} kB -> {tail} kB with only "
        f"~{ingested_kb} kB ingested (samples: {rss_samples})")
