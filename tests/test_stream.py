"""Streaming events→model (ROADMAP item C): delta-tailer exactness,
ALS fold-in equivalence against a full retrain, freshness accounting,
the engine-server model-patch lane, the router worker pool, and the
hedge-rescue SLO credit."""

import datetime as _dt
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from predictionio_tpu.data.event import Event
from predictionio_tpu.data.storage import set_storage
from predictionio_tpu.obs import perfacct

from tests.test_storage import make_storage

UTC = _dt.timezone.utc


def _rate(user, item, rating, event="rate"):
    return Event(
        event=event, entity_type="user", entity_id=user,
        target_entity_type="item", target_entity_id=item,
        properties={"rating": float(rating)} if event == "rate" else {},
        event_time=_dt.datetime.now(tz=UTC))


def _seed_world(storage, app_id, n_users=40, n_items=25, n_events=1200,
                seed=3):
    """Structured synthetic ratings (planted rank-4 signal) so a fold-in
    vs full-retrain comparison measures solve quality, not noise."""
    rng = np.random.default_rng(seed)
    U = rng.normal(size=(n_users, 4)).astype(np.float32)
    V = rng.normal(size=(n_items, 4)).astype(np.float32)
    events = []
    for _ in range(n_events):
        u = int(rng.integers(0, n_users))
        i = int(rng.integers(0, n_items))
        z = float(U[u] @ V[i]) / 2.0
        r = float(np.clip(np.round((3.0 + z) * 2) / 2, 0.5, 5.0))
        events.append(_rate(f"u{u}", f"i{i}", r))
    storage.events().insert_batch(events, app_id)
    return U, V


# ---------------------------------------------------------------------------
# native delta reads
# ---------------------------------------------------------------------------

class TestDeltaReads:
    def _store(self, tmp_path):
        storage = make_storage("eventlog", tmp_path)
        app = storage.apps().insert("delta")
        storage.events().init(app.id)
        return storage, app.id

    def test_exactly_the_rows_since_the_cursor(self, tmp_path):
        storage, app_id = self._store(tmp_path)
        ev = storage.events()
        ev.insert_batch([_rate("a", "x", 1.0), _rate("b", "y", 2.0)], app_id)
        cursor = ev.delta_cursor(app_id)
        ev.insert_batch([_rate("c", "x", 3.0), _rate("a", "z", 4.5)], app_id)
        cols, cursor2, rebased = ev.find_columnar_since(
            app_id, cursor=cursor, value_property="rating",
            entity_type="user", event_names=["rate", "buy"],
            target_entity_type="item")
        assert not rebased
        assert [cols.entity_vocab[c] for c in cols.entity_codes] == ["c", "a"]
        assert [cols.target_vocab[c] for c in cols.target_codes] == ["x", "z"]
        assert list(cols.values) == [3.0, 4.5]
        # the advanced cursor yields an empty delta
        cols2, cursor3, rebased2 = ev.find_columnar_since(
            app_id, cursor=cursor2, value_property="rating")
        assert len(cols2) == 0 and not rebased2 and cursor3 == cursor2

    def test_cursor_survives_process_restart(self, tmp_path):
        storage, app_id = self._store(tmp_path)
        ev = storage.events()
        ev.insert_batch([_rate("a", "x", 1.0)], app_id)
        cursor = ev.delta_cursor(app_id)
        ev.insert_batch([_rate("b", "y", 2.0)], app_id)
        ev.close()  # releases the flock; a fresh handle replays/loads
        cols, cursor2, rebased = ev.find_columnar_since(
            app_id, cursor=cursor, value_property="rating")
        assert not rebased
        assert [cols.entity_vocab[c] for c in cols.entity_codes] == ["b"]
        ev.insert_batch([_rate("c", "z", 3.0)], app_id)
        cols2, _, rebased2 = ev.find_columnar_since(
            app_id, cursor=cursor2, value_property="rating")
        assert not rebased2
        assert [cols2.entity_vocab[c] for c in cols2.entity_codes] == ["c"]

    def test_compaction_rebases_the_cursor(self, tmp_path):
        storage, app_id = self._store(tmp_path)
        ev = storage.events()
        ids = ev.insert_batch([_rate("a", "x", 1.0), _rate("b", "y", 2.0)],
                              app_id)
        cursor = ev.delta_cursor(app_id)
        ev.delete(ids[0], app_id)
        ev.compact(app_id)
        cols, _, rebased = ev.find_columnar_since(
            app_id, cursor=cursor, value_property="rating")
        # the rescan returns the live set, flagged as NOT a delta
        assert rebased
        assert [cols.entity_vocab[c] for c in cols.entity_codes] == ["b"]

    def test_filters_and_deletes_apply_to_the_delta(self, tmp_path):
        storage, app_id = self._store(tmp_path)
        ev = storage.events()
        cursor = ev.delta_cursor(app_id)
        ids = ev.insert_batch(
            [_rate("a", "x", 1.0),
             Event(event="$set", entity_type="user", entity_id="a",
                   properties={"p": 1},
                   event_time=_dt.datetime.now(tz=UTC)),
             _rate("b", "y", 2.0)], app_id)
        ev.delete(ids[2], app_id)  # tombstoned before the read
        cols, _, rebased = ev.find_columnar_since(
            app_id, cursor=cursor, value_property="rating",
            entity_type="user", event_names=["rate", "buy"],
            target_entity_type="item")
        assert not rebased
        assert [cols.entity_vocab[c] for c in cols.entity_codes] == ["a"]

    def test_malformed_cursor_rejected(self, tmp_path):
        storage, app_id = self._store(tmp_path)
        with pytest.raises(ValueError, match="malformed delta cursor"):
            storage.events().find_columnar_since(app_id, cursor="nope")

    def test_unknown_filter_rejected(self, tmp_path):
        storage, app_id = self._store(tmp_path)
        ev = storage.events()
        cursor = ev.delta_cursor(app_id)
        with pytest.raises(TypeError, match="unexpected filters"):
            ev.find_columnar_since(app_id, cursor=cursor, limit=5)


# ---------------------------------------------------------------------------
# ALS fold-in equivalence + freshness
# ---------------------------------------------------------------------------

def _train_reco(storage, engine_id="stream_eq", iterations=15, **als_params):
    from predictionio_tpu.templates.recommendation import (
        recommendation_engine)
    from predictionio_tpu.workflow.train import run_train

    engine = recommendation_engine()
    ep = engine.engine_params_from_variant({
        "datasource": {"params": {"app_name": "stream"}},
        "algorithms": [{"name": "als", "params": {
            "rank": 8, "num_iterations": iterations, "lambda_": 0.1,
            "compute_dtype": "float32", "cg_dtype": "float32",
            "cg_iters": 12, **als_params}}],
    })
    instance = run_train(engine, ep, engine_id=engine_id, storage=storage)
    assert instance.status == "COMPLETED"
    return engine, instance


def _load_model(engine, instance, storage):
    from predictionio_tpu.workflow.deploy import prepare_deploy

    return prepare_deploy(engine, instance, storage=storage).models[0]


class TestALSFoldIn:
    @pytest.fixture()
    def world(self, tmp_path):
        storage = make_storage("eventlog", tmp_path)
        set_storage(storage)
        app = storage.apps().insert("stream")
        storage.events().init(app.id)
        _seed_world(storage, app.id)
        yield storage, app.id
        set_storage(None)

    def test_foldin_matches_full_retrain_within_tolerance(self, world):
        from predictionio_tpu.workflow.stream import StreamUpdater

        storage, app_id = world
        engine, instance = _train_reco(storage)
        updater = StreamUpdater(engine, "stream_eq", storage=storage,
                                instance=instance)
        rng = np.random.default_rng(9)
        # new users rating existing items, plus one existing user with
        # fresh ratings — both fold lanes (cold solve + warm re-solve)
        delta = []
        touched = []
        for k in range(4):
            uid = f"fresh{k}"
            touched.append(uid)
            for i in rng.integers(0, 25, size=6):
                delta.append(_rate(uid, f"i{int(i)}",
                                   float(rng.integers(2, 11)) / 2.0))
        touched.append("u3")
        for i in (1, 7, 19):
            delta.append(_rate("u3", f"i{i}", 4.5))
        storage.events().insert_batch(delta, app_id)
        stats = updater.poll_once()
        assert stats["events"] == len(delta) and stats["published"]
        folded = updater._folders[0].model

        # full retrain over base + delta: the ground truth
        engine2, instance2 = _train_reco(storage, engine_id="stream_eq2")
        retrained = _load_model(engine2, instance2, storage)

        for uid in touched:
            u_f = folded.user_factors[folded.user_ids[uid]]
            u_r = retrained.user_factors[retrained.user_ids[uid]]
            # compare PREDICTIONS (scores over the shared item set) —
            # factors themselves are only identified up to the data
            items = [f"i{i}" for i in range(25)]
            p_f = np.array([folded.item_factors[folded.item_ids[i]] @ u_f
                            for i in items])
            p_r = np.array([retrained.item_factors[retrained.item_ids[i]]
                            @ u_r for i in items])
            rmse = float(np.sqrt(np.mean((p_f - p_r) ** 2)))
            assert rmse < 0.12, (uid, rmse)
            assert float(np.max(np.abs(p_f - p_r))) < 0.35, uid

    def test_staleness_drops_to_zero_without_retrain(self, world):
        from predictionio_tpu.workflow.stream import StreamUpdater

        storage, app_id = world
        engine, instance = _train_reco(storage, engine_id="stream_fresh")
        updater = StreamUpdater(engine, "stream_fresh", storage=storage,
                                instance=instance)
        perfacct.LEDGER.clear()
        storage.events().insert_batch(
            [_rate("newbie", "i1", 5.0), _rate("newbie", "i2", 3.0)],
            app_id)
        time.sleep(0.05)
        assert perfacct.LEDGER.staleness_seconds() >= 0.05
        trains_before = storage.engine_instances().get_latest_completed(
            "stream_fresh", "0", "default").id
        stats = updater.poll_once()
        assert stats["published"] and stats["events"] == 2
        # freshness restored by the FOLD — no new trained instance
        assert perfacct.LEDGER.staleness_seconds() < 0.05
        assert storage.engine_instances().get_latest_completed(
            "stream_fresh", "0", "default").id == trains_before
        perfacct.LEDGER.clear()

    def test_rebase_skips_fold_and_warns(self, world):
        from predictionio_tpu.workflow.stream import StreamUpdater

        storage, app_id = world
        engine, instance = _train_reco(storage, engine_id="stream_rb")
        updater = StreamUpdater(engine, "stream_rb", storage=storage,
                                instance=instance)
        ev = storage.events()
        eid = ev.insert(_rate("gone", "i1", 1.0), app_id)
        ev.delete(eid, app_id)
        ev.compact(app_id)  # renumbers records -> cursor rebases
        stats = updater.poll_once()
        assert stats["rebased"] and stats["events"] == 0
        # after the reset the tail is clean again
        ev.insert_batch([_rate("after", "i2", 4.0)], app_id)
        stats2 = updater.poll_once()
        assert not stats2["rebased"] and stats2["events"] == 1

    def test_truncated_backlog_holds_staleness_debt(self, world,
                                                    monkeypatch):
        from predictionio_tpu.workflow.stream import StreamUpdater

        storage, app_id = world
        engine, instance = _train_reco(storage, engine_id="stream_tr",
                                       iterations=4)
        updater = StreamUpdater(engine, "stream_tr", storage=storage,
                                instance=instance)
        monkeypatch.setenv("PIO_STREAM_MAX_DELTA", "3")
        perfacct.LEDGER.clear()
        storage.events().insert_batch(
            [_rate(f"tr{k}", "i1", 4.0) for k in range(8)], app_id)
        time.sleep(0.02)
        stats = updater.poll_once()
        assert stats["truncated"] and stats["published"]
        # the dropped backlog is unreflected work: NOT credited
        assert perfacct.LEDGER.staleness_seconds() >= 0.02
        # ...and a LATER clean fold must not silently credit it either
        storage.events().insert_batch([_rate("tr_late", "i2", 4.0)],
                                      app_id)
        stats2 = updater.poll_once()
        assert stats2["published"] and not stats2["truncated"]
        assert perfacct.LEDGER.staleness_seconds() >= 0.02
        # only a NEW trained instance (the retrain lane) clears the debt
        _, instance2 = _train_reco(storage, engine_id="stream_tr",
                                   iterations=4)
        updater.resync()
        assert updater.instance_id == instance2.id
        storage.events().insert_batch([_rate("tr_post", "i3", 4.0)],
                                      app_id)
        stats3 = updater.poll_once()
        assert stats3["published"]
        assert perfacct.LEDGER.staleness_seconds() < 0.02
        perfacct.LEDGER.clear()

    def test_fold_failure_rewinds_cursor_for_retry(self, world):
        from predictionio_tpu.workflow.stream import StreamUpdater

        storage, app_id = world
        engine, instance = _train_reco(storage, engine_id="stream_err",
                                       iterations=4)
        updater = StreamUpdater(engine, "stream_err", storage=storage,
                                instance=instance)
        storage.events().insert_batch(
            [_rate("err_u", "i1", 4.0), _rate("err_u", "i2", 3.0)],
            app_id)
        folder = updater._folders[0]
        real_fold = folder.fold
        calls = {"n": 0}

        def flaky_fold(users, items, ratings):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("transient fold failure")
            return real_fold(users, items, ratings)

        folder.fold = flaky_fold
        before = updater.cursor
        with pytest.raises(RuntimeError, match="transient"):
            updater.poll_once()
        assert updater.cursor == before  # rewound: the delta survives
        stats = updater.poll_once()      # the next tick retries it
        assert stats["events"] == 2 and stats["published"]
        assert "err_u" in folder.model.user_ids

    def test_inprocess_stale_patch_triggers_resync(self, world):
        from predictionio_tpu.serving.engine_server import EngineServer
        from predictionio_tpu.workflow.stream import StreamUpdater

        storage, app_id = world
        engine, instance = _train_reco(storage, engine_id="stream_sp",
                                       iterations=4)
        server = EngineServer(engine, "stream_sp", host="127.0.0.1",
                              port=0, storage=storage).start()
        try:
            updater = StreamUpdater(engine, "stream_sp", storage=storage,
                                    instance=instance,
                                    patch_servers=[server])
            # a retrain lands and the server rolls to it behind the
            # streamer's back
            _, instance2 = _train_reco(storage, engine_id="stream_sp",
                                       iterations=4)
            server.reload()
            storage.events().insert_batch([_rate("sp_u", "i1", 4.0)],
                                          app_id)
            stats = updater.poll_once()
            # the stale patch is a counted failure AND the streamer
            # rebinds to the served instance, like the HTTP 409 lane
            assert not stats["published"]
            assert updater.instance_id == instance2.id
            storage.events().insert_batch([_rate("sp_u2", "i2", 4.5)],
                                          app_id)
            stats2 = updater.poll_once()
            assert stats2["published"]
        finally:
            server.stop()


# ---------------------------------------------------------------------------
# engine-server model-patch lane
# ---------------------------------------------------------------------------

class TestModelPatch:
    @pytest.fixture()
    def served(self, tmp_path):
        from predictionio_tpu.serving.engine_server import EngineServer

        storage = make_storage("eventlog", tmp_path)
        set_storage(storage)
        app = storage.apps().insert("stream")
        storage.events().init(app.id)
        _seed_world(storage, app.id, n_events=400)
        engine, instance = _train_reco(storage, engine_id="patch_e",
                                       iterations=4)
        server = EngineServer(engine, "patch_e", host="127.0.0.1", port=0,
                              storage=storage).start()
        yield server, instance
        server.stop()
        set_storage(None)

    @staticmethod
    def _post(port, payload, token=None):
        headers = {"Content-Type": "application/json"}
        if token:
            headers["Authorization"] = f"Bearer {token}"
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/model/patch",
            data=json.dumps(payload).encode(), headers=headers,
            method="POST")
        try:
            with urllib.request.urlopen(req, timeout=10) as resp:
                return resp.status, json.loads(resp.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read() or b"{}")

    @staticmethod
    def _query(port, user):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/queries.json",
            data=json.dumps({"user": user, "num": 3}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=10) as resp:
            return json.loads(resp.read())

    def test_patch_applies_new_user_row(self, served):
        server, instance = served
        assert self._query(server.port, "patched_u")["itemScores"] == []
        vec = [0.5] * 8
        status, body = self._post(server.port, {
            "instanceId": instance.id,
            "algorithms": [{"index": 0, "userRows": [["patched_u", vec]]}],
        })
        assert status == 200 and body["applied"] == 1
        assert self._query(server.port, "patched_u")["itemScores"]

    def test_stale_instance_answers_409(self, served):
        server, _ = served
        status, body = self._post(server.port, {
            "instanceId": "not_the_deployed_instance",
            "algorithms": [{"index": 0, "userRows": [["u", [0.0] * 8]]}],
        })
        assert status == 409
        assert "stale" in body["message"] or "instance" in body["message"]

    def test_malformed_patch_answers_400(self, served):
        server, instance = served
        for payload in (
                {"instanceId": instance.id, "algorithms": []},
                {"instanceId": instance.id,
                 "algorithms": [{"index": 99, "userRows": []}]},
                {"instanceId": instance.id,
                 "algorithms": [{"index": 0,
                                 "userRows": [["u", [0.0] * 3]]}]},
        ):
            status, _ = self._post(server.port, payload)
            assert status == 400, payload

    def test_patch_requires_bearer_token_when_set(self, served,
                                                  monkeypatch):
        server, instance = served
        monkeypatch.setenv("PIO_ADMIN_TOKEN", "s3cret")
        payload = {
            "instanceId": instance.id,
            "algorithms": [{"index": 0,
                            "userRows": [["tok_u", [0.1] * 8]]}],
        }
        status, _ = self._post(server.port, payload)
        assert status == 401
        status, _ = self._post(server.port, payload, token="s3cret")
        assert status == 200

    def test_unsupported_algorithm_answers_400(self, tmp_path):
        from predictionio_tpu.core import Engine
        from predictionio_tpu.core.params import EngineParams
        from predictionio_tpu.serving.engine_server import EngineServer
        from predictionio_tpu.workflow.train import run_train
        from tests.test_servers import (ConstAlgo, ConstDataSource,
                                        ConstParams, FirstServing,
                                        IdentityPreparator)

        storage = make_storage("memory", tmp_path)
        set_storage(storage)
        try:
            engine = Engine(ConstDataSource, IdentityPreparator,
                            {"c": ConstAlgo}, FirstServing)
            ep = EngineParams(
                data_source_params=("", ConstParams(value=1.0)),
                preparator_params=("", None),
                algorithm_params_list=[("c", ConstParams(value=2.0))],
                serving_params=("", None),
            )
            instance = run_train(engine, ep, engine_id="const",
                                 storage=storage)
            server = EngineServer(engine, "const", host="127.0.0.1",
                                  port=0, storage=storage,
                                  micro_batch=False).start()
            try:
                status, body = self._post(server.port, {
                    "instanceId": instance.id,
                    "algorithms": [{"index": 0, "userRows": []}],
                })
                assert status == 400
                assert "does not support" in body["message"]
            finally:
                server.stop()
        finally:
            set_storage(None)


# ---------------------------------------------------------------------------
# two-tower online delta steps
# ---------------------------------------------------------------------------

class TestTwoTowerOnline:
    def test_updates_only_touched_rows_and_reduces_delta_loss(self):
        from predictionio_tpu.ops.twotower import online_delta_step

        rng = np.random.default_rng(5)

        def unit_rows(n, d):
            v = rng.normal(size=(n, d)).astype(np.float32)
            return v / np.linalg.norm(v, axis=1, keepdims=True)

        U = unit_rows(20, 16)
        V = unit_rows(30, 16)
        u_rows = np.array([1, 1, 4, 7], np.int32)
        i_rows = np.array([2, 9, 9, 11], np.int32)
        uu, new_u, ii, new_v, losses = online_delta_step(
            U, V, u_rows, i_rows, lr=0.1, steps=6)
        assert list(uu) == [1, 4, 7] and list(ii) == [2, 9, 11]
        # bounded steps actually descend the delta-batch objective
        assert losses[-1] < losses[0]
        # updated rows stay unit-norm (the serving manifold)
        assert np.allclose(np.linalg.norm(new_u, axis=1), 1.0, atol=1e-4)
        assert np.allclose(np.linalg.norm(new_v, axis=1), 1.0, atol=1e-4)
        # untouched source tables are never mutated
        assert np.allclose(np.linalg.norm(U, axis=1), 1.0, atol=1e-5)

    def test_empty_delta_is_a_noop(self):
        from predictionio_tpu.ops.twotower import online_delta_step

        uu, new_u, ii, new_v, losses = online_delta_step(
            np.zeros((4, 8), np.float32), np.zeros((4, 8), np.float32),
            np.zeros(0, np.int32), np.zeros(0, np.int32))
        assert len(uu) == 0 and len(ii) == 0 and losses == []


# ---------------------------------------------------------------------------
# router worker pool (ROADMAP item B follow-up)
# ---------------------------------------------------------------------------

class TestRouterWorkerPool:
    def test_reuses_workers_and_counts_saturation(self):
        from predictionio_tpu.serving.router import (_POOL_SATURATED,
                                                     _WorkerPool)

        pool = _WorkerPool(2)
        gate = threading.Event()
        started = []
        done = []

        def blocker(k):
            started.append(k)
            gate.wait(5)
            done.append(k)

        base = _POOL_SATURATED.value
        pool.submit(blocker, 0)
        pool.submit(blocker, 1)
        deadline = time.monotonic() + 5
        while len(started) < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert pool.outstanding() == 2
        assert _POOL_SATURATED.value == base
        # third task: both workers busy -> overflow thread + counter
        pool.submit(blocker, 2)
        deadline = time.monotonic() + 5
        while len(started) < 3 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert len(started) == 3, "overflow task must run, not queue"
        assert _POOL_SATURATED.value == base + 1
        gate.set()
        deadline = time.monotonic() + 5
        while len(done) < 3 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert sorted(done) == [0, 1, 2]
        # pool workers drained their outstanding accounting
        deadline = time.monotonic() + 5
        while pool.outstanding() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert pool.outstanding() == 0
        pool.stop()

    def test_task_error_does_not_kill_the_worker(self):
        from predictionio_tpu.serving.router import _WorkerPool

        pool = _WorkerPool(1)
        results = []
        pool.submit(lambda: (_ for _ in ()).throw(RuntimeError("boom")))
        pool.submit(results.append, "alive")
        deadline = time.monotonic() + 5
        while not results and time.monotonic() < deadline:
            time.sleep(0.01)
        assert results == ["alive"]
        pool.stop()


# ---------------------------------------------------------------------------
# hedge-rescue SLO credit (ROADMAP item B remaining)
# ---------------------------------------------------------------------------

class TestHedgeRescueCredit:
    def test_rescued_requests_do_not_burn_latency_budget(self):
        import predictionio_tpu.serving.engine_server  # registers the hist
        from predictionio_tpu.obs import metrics, slo

        hist = metrics.REGISTRY.get("pio_serving_request_seconds")
        assert hist is not None
        child = hist.labels("credit_test")
        # a dedicated credit counter isolates this test from real
        # router traffic elsewhere in the suite; the real wiring (the
        # default SLO naming pio_router_hedge_rescues_total) is pinned
        # in the companion test below
        credit = metrics.counter(
            "pio_test_hedge_credit_total", "test credit counter")
        measured = slo.SLO(
            name="serving-latency", kind="latency",
            metric="pio_serving_request_seconds", objective=0.99,
            threshold_ms=100.0,
            good_credit_metric="pio_test_hedge_credit_total",
        )
        # 100 requests; 4 over the 100 ms threshold
        for _ in range(96):
            child.observe(0.005)
        for _ in range(4):
            child.observe(0.5)
        good0, total0 = measured.measure()
        # every slow primary was actually rescued by a hedge in time
        credit.inc(4)
        good1, total1 = measured.measure()
        assert total1 == total0
        assert good1 == pytest.approx(good0 + 4)
        # credit clamps at total — it can never manufacture good > total
        credit.inc(10_000)
        good2, total2 = measured.measure()
        assert good2 == total2

    def test_default_serving_slo_carries_the_credit_metric(self):
        from predictionio_tpu.obs import slo

        latency = [s for s in slo.default_slos()
                   if s.name == "serving-latency"][0]
        assert latency.good_credit_metric == "pio_router_hedge_rescues_total"
