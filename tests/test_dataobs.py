"""Data & ingest observability plane (obs/dataobs.py): sketch accuracy
vs exact numpy, schema-drift detection, ingest-seam exactly-once
counting, fleet merge degradation, the serving-side unknown-entity
coverage seam, and the acceptance e2e pin — a Zipf hot-key storm with a
mid-stream schema change against a live event server, detected,
journaled, attributed by the anomaly sentinel and rendered by
``pio data --fleet`` with one dead member degraded."""

import collections
import json
import socket
import urllib.request

import numpy as np
import pytest

from predictionio_tpu.core import (Algorithm, DataSource, Engine,
                                   FirstServing, IdentityPreparator)
from predictionio_tpu.core.params import EmptyParams, EngineParams
from predictionio_tpu.data.event import Event
from predictionio_tpu.data.metadata import AccessKey
from predictionio_tpu.obs import collect, dataobs, journal
from predictionio_tpu.obs.dataobs import (DATAOBS, CountMinSketch,
                                          HyperLogLog, QuantileSketch,
                                          SpaceSaving, _hash_u64)
from predictionio_tpu.serving.engine_server import EngineServer
from predictionio_tpu.serving.event_server import EventServer
from predictionio_tpu.workflow.train import run_train


def http(method, url, body=None):
    data = json.dumps(body).encode() if body is not None else None
    headers = {"Content-Type": "application/json"} if data else {}
    req = urllib.request.Request(url, data=data, headers=headers,
                                 method=method)
    with urllib.request.urlopen(req, timeout=10) as resp:
        return resp.status, json.loads(resp.read() or b"null")


def zipf_keys(n=60_000, a=1.5, seed=42):
    rng = np.random.default_rng(seed)
    return [f"u{d}" for d in rng.zipf(a, n)]


# ---------------------------------------------------------------------------
# sketch accuracy vs exact numpy
# ---------------------------------------------------------------------------

class TestCountMin:
    def test_zipf_error_bounds(self):
        keys = zipf_keys()
        exact = collections.Counter(keys)
        cms = CountMinSketch(width=1024, depth=4)
        uniq = list(exact.keys())
        cms.update(_hash_u64(uniq),
                   np.fromiter(exact.values(), np.int64, len(exact)))
        assert cms.total == len(keys)
        # one-sided error: never an undercount, overcount bounded by
        # the standard 2N/width envelope on every probed key
        bound = 2 * len(keys) / 1024
        for key, true in exact.most_common(20):
            est = cms.estimate(key)
            assert est >= true
            assert est - true <= bound
        # a never-seen key collides to at most the same envelope
        assert cms.estimate("never-seen") <= bound

    def test_width_must_be_power_of_two(self):
        with pytest.raises(ValueError):
            CountMinSketch(width=1000)


class TestSpaceSaving:
    def test_zipf_top_k_and_error_certificates(self):
        keys = zipf_keys()
        exact = collections.Counter(keys)
        ss = SpaceSaving(capacity=128)
        # feed in update rounds, the way the worker drains batches
        for lo in range(0, len(keys), 4096):
            ss.offer_counts(collections.Counter(keys[lo:lo + 4096]))
        assert len(ss) <= 128  # bounded by construction
        top = {key: (count, err) for key, count, err in ss.top(32)}
        for key, true in exact.most_common(10):
            assert key in top  # every true heavy hitter is tracked
            count, err = top[key]
            # space-saving invariant: recorded count overestimates the
            # truth by at most the admission-floor error certificate
            assert count >= true
            assert count - err <= true

    def test_capacity_floor(self):
        assert SpaceSaving(capacity=2).capacity == 8


class TestHyperLogLog:
    def test_within_five_percent_on_zipf_stream(self):
        keys = zipf_keys(n=120_000, a=1.3)
        exact = len(set(keys))
        hll = HyperLogLog(p=11)
        for lo in range(0, len(keys), 8192):
            hll.add_hashes(_hash_u64(keys[lo:lo + 8192]))
        est = hll.estimate()
        assert abs(est - exact) / exact <= 0.05

    def test_small_sets_linear_counting(self):
        hll = HyperLogLog(p=11)
        hll.add_hashes(_hash_u64([f"k{i}" for i in range(100)]))
        assert abs(hll.estimate() - 100) <= 5


class TestQuantileSketch:
    def test_tracks_np_quantile_within_rank_tolerance(self):
        rng = np.random.default_rng(7)
        sample = rng.lognormal(3.0, 1.0, 50_000)
        qs = QuantileSketch(budget=256)
        for lo in range(0, sample.size, 4096):
            qs.update(sample[lo:lo + 4096])
        assert qs.n == sample.size
        for q in (0.5, 0.9, 0.99):
            est = qs.quantile(q)
            # rank-tolerance: the estimate must land between the exact
            # quantiles one rank-percent either side
            lo_v = np.quantile(sample, max(0.0, q - 0.01))
            hi_v = np.quantile(sample, min(1.0, q + 0.01))
            assert lo_v <= est <= hi_v
        assert qs.quantile(0.0) == sample.min()
        assert qs.quantile(1.0) == sample.max()

    def test_summary_shape(self):
        qs = QuantileSketch()
        assert qs.summary() == {"n": 0}
        qs.add(3.0)
        summ = qs.summary()
        assert summ["n"] == 1 and summ["min"] == summ["max"] == 3.0

    def test_non_finite_values_dropped(self):
        qs = QuantileSketch()
        qs.update(np.array([1.0, np.inf, np.nan, 2.0]))
        assert qs.n == 2


# ---------------------------------------------------------------------------
# schema drift matrix: added / vanished / retyped
# ---------------------------------------------------------------------------

def _rate_event(props, name="rate", entity="u1"):
    return Event(event=name, entity_type="user", entity_id=entity,
                 properties=props)


class TestSchemaDrift:
    def test_add_remove_retype_matrix(self, monkeypatch):
        monkeypatch.setenv("PIO_DATAOBS_VANISH_AFTER", "3")
        for _ in range(4):
            DATAOBS.observe_event(
                1, _rate_event({"rating": 4.0, "note": "x"}))
        DATAOBS.freeze_schemas("inst-1")

        # added: a field the frozen profile never saw
        DATAOBS.observe_event(
            1, _rate_event({"rating": 4.0, "note": "x", "source": "web"}))
        # retyped: rating flips float -> str
        DATAOBS.observe_event(
            1, _rate_event({"rating": "5", "note": "x"}))
        # vanished: 'note' absent for VANISH_AFTER samples
        for _ in range(4):
            DATAOBS.observe_event(1, _rate_event({"rating": 4.0}))

        changes = {(c["change"], c["field"])
                   for c in DATAOBS.report()["schema"]["changes"]}
        assert ("added", "source") in changes
        assert ("retyped", "rating") in changes
        assert ("vanished", "note") in changes
        # every drift is an ops-journal event the sentinel can attribute
        kinds = {(e["change"], e["field"])
                 for e in journal.JOURNAL.recent(kind="schema_change")}
        assert {("added", "source"), ("retyped", "rating"),
                ("vanished", "note")} <= kinds

    def test_changes_dedupe(self):
        DATAOBS.observe_event(1, _rate_event({"rating": 4.0}))
        DATAOBS.freeze_schemas("inst-1")
        for _ in range(5):
            DATAOBS.observe_event(1, _rate_event({"rating": 4.0,
                                                  "extra": 1}))
        report = DATAOBS.report()
        assert report["schema"]["changes_total"] == 1
        assert report["schema"]["frozen_instance"] == "inst-1"

    def test_no_frozen_profile_no_changes(self):
        DATAOBS.observe_event(1, _rate_event({"rating": 4.0}))
        DATAOBS.observe_event(1, _rate_event({"rating": "oops"}))
        assert DATAOBS.report()["schema"]["changes"] == []


# ---------------------------------------------------------------------------
# bounded state + exactly-once counting through the storage seams
# ---------------------------------------------------------------------------

class TestBoundedState:
    def test_rate_rows_overflow_to_other(self, monkeypatch):
        monkeypatch.setenv("PIO_DATAOBS_MAX_RATE_ROWS", "8")
        for i in range(40):
            DATAOBS.observe_event(1, _rate_event({}, name=f"ev{i}"))
        report = DATAOBS.report()
        assert len(report["rates"]) <= 9  # 8 rows + the (other) row
        other = [r for r in report["rates"] if r["event"] == "(other)"]
        assert other and other[0]["count"] == 32
        assert report["events_total"] == 40

    def test_queue_overflow_drops_never_blocks(self, monkeypatch):
        from predictionio_tpu.obs.dataobs import _QUEUE_DROPPED
        monkeypatch.setenv("PIO_DATAOBS_QUEUE", "8")
        before = _QUEUE_DROPPED.value
        with DATAOBS._q_cond:  # stall the worker's view: fill directly
            for _ in range(64):
                DATAOBS._q.append(("tail", 1, 0, {}, {}))
            DATAOBS._pending += 64
        for _ in range(16):
            DATAOBS.observe_batch(1, [b"rate"], entity_ids=[b"u1"])
        assert _QUEUE_DROPPED.value > before
        DATAOBS.reset()

    def test_disable_knob_gates_every_seam(self, monkeypatch):
        monkeypatch.setenv("PIO_DATAOBS_DISABLE", "1")
        DATAOBS.observe_event(1, _rate_event({"rating": 1.0}))
        DATAOBS.observe_batch(1, [b"rate"], entity_ids=[b"u1"])
        DATAOBS.note_query(4, 2)
        monkeypatch.delenv("PIO_DATAOBS_DISABLE")
        report = DATAOBS.report()
        assert report["events_total"] == 0
        assert report["queries_seen"] == 0


class TestIngestSeams:
    def test_memory_batch_lane_counts_once(self, memory_storage):
        app = memory_storage.apps().insert("obs-app")
        memory_storage.events().init(app.id)
        events = [Event(event="rate", entity_type="user",
                        entity_id=f"u{i % 7}", properties={"rating": 1.0})
                  for i in range(25)]
        memory_storage.events().insert_batch(events, app.id)
        assert DATAOBS.flush(timeout=5.0)
        report = DATAOBS.report()
        assert report["events_total"] == 25
        assert report["entities"]["cardinality"]["entityId"] >= 6

    def test_event_server_201_lane_counts_payload_bytes(self, memory_storage):
        app = memory_storage.apps().insert("obs-app")
        memory_storage.events().init(app.id)
        key = AccessKey.generate(app.id)
        memory_storage.access_keys().insert(key)
        server = EventServer(storage=memory_storage, host="127.0.0.1",
                             port=0).start()
        try:
            base = f"http://127.0.0.1:{server.port}"
            status, _ = http(
                "POST", f"{base}/events.json?accessKey={key.key}",
                {"event": "rate", "entityType": "user", "entityId": "u1",
                 "properties": {"rating": 4.5}})
            assert status == 201
        finally:
            server.stop()
        report = DATAOBS.report()
        assert report["events_total"] == 1
        assert report["bytes_total"] > 0  # stamped from len(body)
        assert report["quantiles"]["value"]["n"] == 1


# ---------------------------------------------------------------------------
# fleet merge: dead member degrades, never fails
# ---------------------------------------------------------------------------

def _dead_member(name="gone"):
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    return collect.Member(name, f"http://127.0.0.1:{port}")


class TestFederateData:
    def test_merge_degrades_on_dead_member(self):
        for _ in range(3):
            DATAOBS.observe_event(1, _rate_event({"rating": 2.0}))
        DATAOBS.freeze_schemas("inst-1")
        DATAOBS.observe_event(1, _rate_event({"rating": 2.0, "new": 1}))
        report = collect.federate_data(
            [collect.Member("local", None), _dead_member()])
        by_name = {m["name"]: m for m in report["members"]}
        assert by_name["local"]["ok"] is True
        assert by_name["gone"]["ok"] is False and by_name["gone"]["error"]
        assert report["merged_from"] == ["local"]
        assert report["totals"]["events_total"] == 4
        assert report["schema_changes"]
        assert all(c["fleet_member"] == "local"
                   for c in report["schema_changes"])

    def test_all_dead_still_returns_shape(self):
        report = collect.federate_data([_dead_member("a"), _dead_member("b")])
        assert report["merged_from"] == []
        assert report["totals"]["events_total"] == 0
        assert report["skew"] == 0.0


# ---------------------------------------------------------------------------
# serving-side unknown-entity coverage, e2e through a live engine server
# ---------------------------------------------------------------------------

class MapModel:
    def __init__(self):
        self.user_ids = {"u1": 0, "u2": 1}
        self.item_ids = {"i1": 0, "i2": 1}


class MapDataSource(DataSource):
    def read_training(self, ctx):
        return 0.0


class MapAlgo(Algorithm):
    def train(self, ctx, pd):
        return MapModel()

    def predict(self, model, query):
        return {"ok": True}


def _map_engine_server(storage):
    engine = Engine(MapDataSource, IdentityPreparator, {"m": MapAlgo},
                    FirstServing)
    ep = EngineParams(
        data_source_params=("", EmptyParams()),
        preparator_params=("", None),
        algorithm_params_list=[("m", EmptyParams())],
        serving_params=("", None),
    )
    run_train(engine, ep, engine_id="mapper", storage=storage)
    return EngineServer(engine, "mapper", host="127.0.0.1", port=0,
                        storage=storage).start()


class TestUnknownEntityCoverage:
    def test_query_decode_seam_e2e(self, memory_storage):
        server = _map_engine_server(memory_storage)
        try:
            base = f"http://127.0.0.1:{server.port}"
            # known user + known item: 2 refs, 0 unknown
            assert http("POST", f"{base}/queries.json",
                        {"user": "u1", "items": ["i1"]})[0] == 200
            # unknown user + one unknown of two items: 3 refs, 2 unknown
            assert http("POST", f"{base}/queries.json",
                        {"user": "ghost", "items": ["i2", "nope"]})[0] == 200
            status, report = http("GET", f"{base}/admin/data")
            assert status == 200
        finally:
            server.stop()
        assert report["queries_seen"] == 5
        assert report["unknown_ratio"] == pytest.approx(2 / 5)
        from predictionio_tpu.obs.dataobs import _UNKNOWN_RATIO
        assert _UNKNOWN_RATIO.value == pytest.approx(2 / 5)

    def test_queries_without_entity_refs_are_ignored(self, memory_storage):
        server = _map_engine_server(memory_storage)
        try:
            base = f"http://127.0.0.1:{server.port}"
            assert http("POST", f"{base}/queries.json",
                        {"mult": 3})[0] == 200
        finally:
            server.stop()
        assert DATAOBS.report()["queries_seen"] == 0

    # the window's two sums are kept beside it (PERF.md §6, PR 38): a
    # query costs O(1) under the lock and the ratio is what the sums over
    # the whole window gave, to the last bit (integers: nothing drifts)
    @pytest.mark.parametrize("window", [16, 1024])
    def test_running_sums_read_what_the_whole_window_sums_to(
            self, monkeypatch, window):
        import fractions

        from predictionio_tpu.obs.dataobs import _UNKNOWN_RATIO

        monkeypatch.setenv("PIO_DATAOBS_QUERY_WINDOW", str(window))
        # breach checks read the ratio too; keep them out of the loop
        monkeypatch.setenv("PIO_DATAOBS_BREACH_INTERVAL_SEC", "3600")
        DATAOBS.reset()
        rng = np.random.default_rng(window)
        refs = rng.integers(1, 40, 5_000)
        # stretches with no unknown entity, with a few, with all unknown
        share = rng.choice([0.0, 0.1, 1.0], 5_000)
        unknown = np.minimum(refs, (refs * share * rng.random(5_000) * 2)
                             .astype(np.int64))
        pairs = list(zip(refs.tolist(), unknown.tolist()))
        for n, (r, u) in enumerate(pairs, 1):
            DATAOBS.note_query(r, u)
            tail = pairs[max(0, n - window):n]
            seen = sum(r for r, _ in tail)
            missed = sum(u for _, u in tail)
            ratio = missed / float(seen)
            assert DATAOBS.unknown_ratio() == ratio == _UNKNOWN_RATIO.value
            assert fractions.Fraction(
                DATAOBS._query_unknown, DATAOBS._query_refs) == \
                fractions.Fraction(missed, seen)
        assert len(DATAOBS._queries) == window
        assert DATAOBS.report()["queries_seen"] == seen

    def test_reset_and_a_new_window_start_from_zero_sums(self, monkeypatch):
        for _ in range(40):
            DATAOBS.note_query(5, 5)
        assert DATAOBS.unknown_ratio() == 1.0
        DATAOBS.reset()
        assert (DATAOBS._query_refs, DATAOBS._query_unknown) == (0, 0)
        assert DATAOBS.unknown_ratio() == 0.0
        assert DATAOBS.report()["queries_seen"] == 0
        DATAOBS.note_query(4, 1)
        assert DATAOBS.unknown_ratio() == 0.25
        # the knob keeps its meaning: read at reset, floor of 16 pairs
        monkeypatch.setenv("PIO_DATAOBS_QUERY_WINDOW", "20")
        DATAOBS.reset()
        assert DATAOBS._queries.maxlen == 20
        assert (DATAOBS._query_refs, DATAOBS._query_unknown) == (0, 0)
        for _ in range(20):
            DATAOBS.note_query(2, 2)
        for _ in range(20):
            DATAOBS.note_query(2, 0)
        assert DATAOBS.unknown_ratio() == 0.0
        assert DATAOBS.report()["queries_seen"] == 40
        monkeypatch.setenv("PIO_DATAOBS_QUERY_WINDOW", "3")
        DATAOBS.reset()
        assert DATAOBS._queries.maxlen == 16

    def test_a_query_costs_the_same_at_a_full_window(self, monkeypatch):
        """Summing the window a query cost 70 us at 1,024 pairs and grew
        with the knob; a ratio of best times, not a wall-clock limit."""
        import time

        monkeypatch.setenv("PIO_DATAOBS_QUERY_WINDOW", "16384")
        monkeypatch.setenv("PIO_DATAOBS_BREACH_INTERVAL_SEC", "3600")

        def best_of_five(prepare):
            best = float("inf")
            for _ in range(5):
                prepare()
                t0 = time.perf_counter()
                for _ in range(200):
                    DATAOBS.note_query(3, 1)
                best = min(best, time.perf_counter() - t0)
            return best

        def full():
            if len(DATAOBS._queries) < 16384:
                for _ in range(16384):
                    DATAOBS.note_query(3, 1)

        empty = best_of_five(DATAOBS.reset)
        at_full = best_of_five(full)
        assert len(DATAOBS._queries) == DATAOBS._queries.maxlen == 16384
        # the old sums read 16,384 pairs a query here: some 80 times
        assert at_full < 5 * empty, (at_full, empty)


# ---------------------------------------------------------------------------
# pio top ingest row
# ---------------------------------------------------------------------------

def test_top_frame_ingest_row():
    from predictionio_tpu.tools.cli import _render_top_frame

    frame = _render_top_frame({"series": {
        "data.eps": [(0.0, 100.0), (15.0, 120.0)],
        "data.unknown_ratio": [(0.0, 0.0), (15.0, 0.25)],
        "data.skew": [(0.0, 0.0), (15.0, 1.4)],
    }})
    assert "ingest:" in frame
    assert "120 ev/s" in frame and "25.00%" in frame and "skew 1.4" in frame


def test_top_frame_without_data_series_has_no_ingest_row():
    from predictionio_tpu.tools.cli import _render_top_frame

    frame = _render_top_frame({"series": {
        "serve_p99_ms.eng": [(0.0, 10.0)]}})
    assert "ingest:" not in frame


def test_fleet_frame_ingest_row_sums_and_maxes():
    from predictionio_tpu.tools.cli import _render_fleet_frame

    frame = _render_fleet_frame({"samples": {
        'pio_data_events_total{app="1",event="rate",member="a"}': 700.0,
        'pio_data_events_total{app="1",event="rate",member="b"}': 300.0,
        'pio_data_entity_skew{member="a"}': 0.4,
        'pio_data_entity_skew{member="b"}': 1.7,
        'pio_query_unknown_entity_ratio{member="a"}': 0.25,
    }, "members": []})
    # counters sum across the merge; skew/unknown take the fleet max
    assert "fleet ingest: events 1000" in frame
    assert "skew 1.7" in frame
    assert "unknown-entity 25.00%" in frame


# ---------------------------------------------------------------------------
# acceptance e2e pin: Zipf hot-key storm + mid-stream schema change
# against a LIVE event server — detected, journaled, attributed,
# rendered fleet-wide with one dead member degraded, zero ingest errors
# ---------------------------------------------------------------------------

class TestAcceptanceStorm:
    def test_hot_key_storm_schema_change_end_to_end(
            self, memory_storage, monkeypatch, capsys):
        import predictionio_tpu.obs.timeline as timeline_mod
        from predictionio_tpu.obs import anomaly
        from predictionio_tpu.tools import cli

        monkeypatch.setenv("PIO_DATAOBS_BREACH_INTERVAL_SEC", "0")
        monkeypatch.setenv("PIO_DATAOBS_SKEW_BREACH", "1.0")
        app = memory_storage.apps().insert("storm-app")
        memory_storage.events().init(app.id)
        key = AccessKey.generate(app.id)
        memory_storage.access_keys().insert(key)
        server = EventServer(storage=memory_storage, host="127.0.0.1",
                             port=0).start()
        try:
            base = f"http://127.0.0.1:{server.port}"
            batch_url = f"{base}/batch/events.json?accessKey={key.key}"

            def post_batch(events):
                status, body = http("POST", batch_url, events)
                assert status == 200
                bad = [r for r in body if r.get("status") != 201]
                assert bad == []  # zero ingest errors

            def make(entity, props):
                return {"event": "rate", "entityType": "user",
                        "entityId": entity, "targetEntityType": "item",
                        "targetEntityId": "i1", "properties": props}

            # phase 1 — calm baseline traffic, schema frozen at a
            # "completed train": rating is a float
            post_batch([make(f"u{i}", {"rating": float(i % 5)})
                        for i in range(40)])
            DATAOBS.freeze_schemas("inst-storm-base")

            # phase 2 — the Zipf hot-key storm: counts ~ rank^-2 over
            # 24 entities, the top key dominating
            storm = []
            for rank in range(1, 25):
                count = max(1, int(1200 / rank ** 2))
                storm.extend(make(f"hot{rank}",
                                  {"rating": float(rank % 5)})
                             for _ in range(count))
            for lo in range(0, len(storm), 400):
                post_batch(storm[lo:lo + 400])

            # phase 3 — mid-stream schema change: rating flips to str
            # and a new field appears
            post_batch([make(f"hot{i % 4 + 1}",
                             {"rating": "5", "source": "web"})
                        for i in range(20)])

            skew = DATAOBS.skew()
            assert skew >= 1.0  # the storm registered in the gauge
            from predictionio_tpu.obs.dataobs import _SKEW
            assert _SKEW.value == pytest.approx(skew, rel=0.2)

            breaches = journal.JOURNAL.recent(kind="data_breach")
            assert any(b["breach"] == "entity_skew" and b["top_entity"]
                       == "hot1" for b in breaches)
            drifts = journal.JOURNAL.recent(kind="schema_change")
            changes = {(d["change"], d["field"]) for d in drifts}
            assert ("retyped", "rating") in changes
            assert ("added", "source") in changes

            # the anomaly sentinel sees the skew step on the data.skew
            # timeline and attributes it to the data_breach event
            tl = timeline_mod.Timeline()
            monkeypatch.setattr(timeline_mod, "TIMELINE", tl)
            ring = tl._series.setdefault(
                "data.skew", collections.deque(maxlen=360))
            baseline = [0.2 + (0.02 if i % 2 else -0.02)
                        for i in range(24)]
            for i, v in enumerate(baseline + [skew] * 12):
                ring.append((1000.0 + i * 15.0, float(v)))
            monkeypatch.setenv("PIO_ANOMALY_WINDOW_SEC", "60")
            # pin the breach event just before the onset (index 24 ->
            # ts 1360), the way the sentinel fixtures do
            for entry in journal.JOURNAL._ring:
                if entry["kind"] == "data_breach":
                    entry["ts"] = 1355.0
            report = anomaly.SENTINEL.scan(now=1540.0)
            verdict = report["active"].get("data.skew")
            assert verdict is not None
            assert verdict["direction"] == "up"
            assert verdict["cause"]["kind"] == "data_breach"
            onsets = journal.JOURNAL.recent(kind="anomaly")
            assert onsets and onsets[-1]["series"] == "data.skew"
            assert onsets[-1]["cause_kind"] == "data_breach"

            # the storm is visible in `pio anomalies` with attribution
            assert cli.main(["anomalies"]) == 1
            out = capsys.readouterr().out
            assert "data.skew" in out and "<- data_breach" in out

            # ... and in `pio data --fleet` through the live server's
            # /admin/fleet/data, with one dead member degraded
            dead = _dead_member()
            monkeypatch.setenv(
                "PIO_OBS_MEMBERS", f"self={base},gone={dead.url}")
            assert cli.main(["data", "--fleet", "--url", base]) == 0
            out = capsys.readouterr().out
            assert "member self" in out and "ok" in out
            assert "member gone" in out and "ERROR" in out
            assert "ACTIVE BREACH: entity_skew" in out
            assert "rate.rating retyped" in out

            # the single-server page shows the hot-entity table itself
            assert cli.main(["data", "--url", base]) == 0
            out = capsys.readouterr().out
            assert "hot entities:" in out and "hot1" in out

            # every accepted event was counted exactly once
            assert DATAOBS.report()["events_total"] == 40 + len(storm) + 20
        finally:
            server.stop()
