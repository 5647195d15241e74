"""Device-memory accounting plane (obs/memacct.py): the per-model HBM
ledger, train high-water tracking, the OOM preflight, and their
surfaces (/admin/memory, pio mem, dashboard /memory, timeline).

Acceptance pinned here:
  - on CPU with the host-memory capacity steered by the test, GET /admin/memory attribution
    sums to within 1% of the ledger's registered nbytes for every
    loaded model;
  - a fleet serving a baseline REFUSES an oversized candidate at
    /reload (507 + reason surfaced through `pio fleet`) and via the
    canary lane, keeps answering with zero non-429 client errors, and
    accepts the same candidate under {"force": true}.
"""

from __future__ import annotations

import gc
import json
import pickle
import time

import numpy as np
import pytest

from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.data.metadata import Model
from predictionio_tpu.models.als import ALSModel
from predictionio_tpu.obs import memacct, metrics
from predictionio_tpu.ops.als import ALSFactors
from predictionio_tpu.serving.engine_server import EngineServer

from tests.test_canary import canary_fleet, _await, _load
from tests.test_fleet import post
from tests.test_health import get_json, train_const


@pytest.fixture(autouse=True)
def _clean_ledger():
    memacct.clear()
    yield
    memacct.clear()


def _als_model(n_users=16, n_items=24, rank=8) -> ALSModel:
    factors = ALSFactors(
        user_factors=np.random.default_rng(0).normal(
            size=(n_users, rank)).astype(np.float32),
        item_factors=np.random.default_rng(1).normal(
            size=(n_items, rank)).astype(np.float32),
    )
    return ALSModel(factors,
                    BiMap.from_vocab([f"u{i}" for i in range(n_users)]),
                    BiMap.from_vocab([f"i{i}" for i in range(n_items)]))


# -- ledger basics -------------------------------------------------------------

def test_register_release_and_gauge_retire():
    class Owner:
        pass

    o = Owner()
    memacct.LEDGER.register(o, "m1", "factors", 1000)
    memacct.LEDGER.register(o, "m1", "index", 500)
    assert memacct.LEDGER.model_bytes() == {
        "m1": {"factors": 1000, "index": 500}}
    gauge = metrics.REGISTRY.get("pio_model_device_bytes")
    assert gauge.labels("m1", "factors").value == 1000.0
    # re-register replaces (re-pricing under the same owner key)
    memacct.LEDGER.register(o, "m1", "factors", 1200)
    assert memacct.LEDGER.model_bytes()["m1"]["factors"] == 1200
    assert memacct.LEDGER.release(o) == 2
    assert memacct.LEDGER.model_bytes() == {}
    # the gauge children are REMOVED, not frozen at their last value
    assert ("m1", "factors") not in {
        values for values, _ in gauge.children()}


def test_dead_owner_is_swept_without_release():
    class Owner:
        pass

    o = Owner()
    memacct.LEDGER.register(o, "m2", "factors", 777)
    del o
    gc.collect()
    assert "m2" not in memacct.LEDGER.model_bytes()


def test_als_model_registration_matches_nbytes():
    """The factors footprint IS the tables' nbytes — the ledger is an
    accounting of real arrays, not a guess."""
    model = _als_model()
    components = memacct.LEDGER.model_bytes()["als"]
    expected = (model.user_factors.nbytes + model.item_factors.nbytes)
    assert components["factors"] == expected
    assert components["id_maps"] > 0
    # building the retrieval index adds its component under the SAME
    # model label (the owner wires mem_model before build)
    model.retrieval_index()
    components = memacct.LEDGER.model_bytes()["als"]
    assert components["index"] >= model.item_factors.nbytes


def test_release_model_retires_index_and_scorer_too():
    model = _als_model()
    model.retrieval_index()
    assert "index" in memacct.LEDGER.model_bytes()["als"]
    memacct.release_model(model)
    assert "als" not in memacct.LEDGER.model_bytes()


def test_upsert_rows_reprices_grown_tables():
    model = _als_model(n_users=4, n_items=4, rank=4)
    before = memacct.LEDGER.model_bytes()["als"]["factors"]
    model.upsert_rows(user_rows=[("brand-new", np.ones(4, np.float32))])
    after = memacct.LEDGER.model_bytes()["als"]["factors"]
    assert after == before + 4 * 4  # one new float32 row


def test_unpickle_registers_the_load_seam():
    model = _als_model()
    blob = pickle.dumps(model)
    memacct.clear()
    loaded = pickle.loads(blob)
    assert memacct.LEDGER.model_bytes()["als"]["factors"] == (
        loaded.user_factors.nbytes + loaded.item_factors.nbytes)


# -- capacity / headroom / probe ----------------------------------------------

def test_env_basis_headroom_and_probe(monkeypatch):
    monkeypatch.setattr(memacct, "host_memory_bytes", lambda: 10000)

    class Owner:
        pass

    o = Owner()
    memacct.LEDGER.register(o, "m", "factors", 4000)
    report = memacct.capacity_report()
    assert report["basis"] == "host_memory"
    assert report["capacity_bytes"] == 10000
    assert report["in_use_bytes"] == 4000
    assert report["headroom_bytes"] == 6000
    assert metrics.REGISTRY.get(
        "pio_device_headroom_bytes").value == 6000.0
    assert memacct.device_memory_probe().status == "ok"
    # push under the floor (5% of 10000 = 500): DEGRADED, not FAILED —
    # still serving, but the next deploy will be refused
    memacct.LEDGER.register(o, "m", "factors", 9800)
    result = memacct.device_memory_probe()
    assert result.status == "degraded"
    assert "preflight" in result.reason


def test_readyz_carries_the_device_memory_probe(memory_storage):
    engine, _ = train_const(memory_storage)
    server = EngineServer(engine, "const", host="127.0.0.1", port=0,
                          storage=memory_storage).start()
    try:
        status, body = get_json(
            f"http://127.0.0.1:{server.port}/readyz")
        assert status == 200
        assert "device_memory" in body["probes"]
    finally:
        server.stop()


# -- train high-water ----------------------------------------------------------

def test_peak_from_compiled_fallback_contract():
    class Attrs:
        def memory_analysis(self):
            class MA:
                argument_size_in_bytes = 100
                output_size_in_bytes = 50
                temp_size_in_bytes = 30
                alias_size_in_bytes = 20
            return MA()

    class AsDict:
        def memory_analysis(self):
            return {"argument_size_in_bytes": 10,
                    "output_size_in_bytes": 5,
                    "temp_size_in_bytes": 1,
                    "alias_size_in_bytes": 0}

    class Nothing:
        def memory_analysis(self):
            return None

    class Raises:
        def memory_analysis(self):
            raise NotImplementedError("backend says no")

    assert memacct.peak_from_compiled(Attrs()) == 160
    assert memacct.peak_from_compiled(AsDict()) == 16
    # None / raising / empty-total: analytic-fallback territory, never
    # an exception — accounting must not change whether training runs
    assert memacct.peak_from_compiled(Nothing()) is None
    assert memacct.peak_from_compiled(Raises()) is None


def test_peak_from_compiled_on_cpu_and_note():
    import jax

    fn = jax.jit(lambda x: x * 2.0)
    x = np.ones((16, 16), np.float32)
    peak = memacct.peak_from_compiled(fn.lower(x).compile())
    # CPU jax reports CompiledMemoryStats here; either way the
    # contract holds: an int or the analytic-fallback None
    assert peak is None or peak >= 2 * x.nbytes
    memacct.note_train_peak("als", 12345, source="analytic")
    assert memacct.train_peaks()["als"] == {"bytes": 12345,
                                            "source": "analytic"}
    assert metrics.REGISTRY.get("pio_train_peak_bytes").labels(
        "als").value == 12345.0


def test_als_trainer_registers_and_notes_peak():
    from predictionio_tpu.ops.als import ALSConfig, ALSTrainer

    rng = np.random.default_rng(7)
    n = 400
    u = rng.integers(0, 24, n).astype(np.int64)
    i = rng.integers(0, 32, n).astype(np.int64)
    r = rng.normal(size=n).astype(np.float32)
    trainer = ALSTrainer((u, i, r), 24, 32,
                         ALSConfig(rank=4, iterations=1, block_size=64))
    assert memacct.LEDGER.model_bytes()["als"]["train_data"] == (
        int(trainer.transfer_bytes))
    trainer.step_n(1)
    peak = memacct.train_peaks()["als"]
    assert peak["source"] == "analytic"
    assert peak["bytes"] >= trainer.transfer_bytes
    del trainer
    gc.collect()
    assert "als" not in memacct.LEDGER.model_bytes()


# -- OOM preflight -------------------------------------------------------------

def _store_blob(storage, instance_id: str, nbytes: int) -> None:
    storage.models().insert(Model(id=instance_id, models=b"x" * nbytes))


def test_preflight_refuses_forces_and_disables(memory_storage,
                                               monkeypatch):
    monkeypatch.setattr(memacct, "host_memory_bytes", lambda: 1000)
    _store_blob(memory_storage, "fat", 900)   # estimate 1800 > 1000
    with pytest.raises(memacct.PreflightRefused) as exc:
        memacct.preflight_check("fat", memory_storage)
    decision = exc.value.decision
    assert decision["result"] == "refused"
    assert decision["estimated_bytes"] == 1800
    assert decision["headroom_bytes"] == 1000
    assert memacct.last_preflight()["result"] == "refused"
    # force: allowed, recorded as forced
    assert memacct.preflight_check(
        "fat", memory_storage, force=True)["result"] == "forced"
    # a small candidate passes
    _store_blob(memory_storage, "thin", 100)
    assert memacct.preflight_check(
        "thin", memory_storage)["result"] == "allowed"
    # unknown blob: must not block (the ledger prices it after load)
    assert memacct.preflight_check(
        "missing", memory_storage)["result"] == "unknown_size"
    # kill switch
    monkeypatch.setenv("PIO_MEM_PREFLIGHT", "0")
    assert memacct.preflight_check(
        "fat", memory_storage)["result"] == "allowed"


def test_engine_server_reload_answers_507_then_force(memory_storage,
                                                     monkeypatch):
    engine, baseline = train_const(memory_storage)
    server = EngineServer(engine, "const", host="127.0.0.1", port=0,
                          storage=memory_storage).start()
    base = f"http://127.0.0.1:{server.port}"
    try:
        train_const(memory_storage)  # the candidate
        monkeypatch.setattr(memacct, "host_memory_bytes", lambda: 8)
        status, body = get_json(base + "/reload")
        assert status == 507, body
        assert body["preflight"]["result"] == "refused"
        assert body["preflight"]["headroom_bytes"] == 8
        # the serving model is untouched by the refusal
        status, info = get_json(base + "/")
        assert info["engineInstanceId"] == baseline.id
        # operator override
        status, body = get_json(base + "/reload?force=1")
        assert status == 200, body
        assert body["engineInstanceId"] != baseline.id
    finally:
        server.stop()


def test_hot_swap_releases_old_models(memory_storage):
    """Deregistration on /reload: the swapped-OUT deployment's
    footprints leave the ledger with the swap — gauges never leak a
    retired instance."""
    engine, _ = train_const(memory_storage)
    server = EngineServer(engine, "const", host="127.0.0.1", port=0,
                          storage=memory_storage).start()
    try:
        old_model = server.deployment.models[0]
        memacct.LEDGER.register(old_model, "const", "factors", 512)
        assert memacct.LEDGER.model_bytes()["const"]["factors"] == 512
        train_const(memory_storage)
        server.reload()
        assert "const" not in memacct.LEDGER.model_bytes()
    finally:
        server.stop()


def test_replica_stop_releases_models(memory_storage):
    engine, _ = train_const(memory_storage)
    server = EngineServer(engine, "const", host="127.0.0.1", port=0,
                          storage=memory_storage).start()
    model = server.deployment.models[0]
    memacct.LEDGER.register(model, "const", "factors", 256)
    server.stop()
    assert "const" not in memacct.LEDGER.model_bytes()


# -- the fleet preflight e2e (acceptance) --------------------------------------

def test_fleet_refuses_oversized_candidate_then_force(memory_storage,
                                                      monkeypatch,
                                                      capsys):
    """A 3-replica fleet on a baseline: the oversized candidate is
    refused at every replica's /reload (507 surfaced via `pio fleet`),
    refused on the canary lane too, the fleet keeps answering with
    zero non-429 errors throughout — and the SAME candidate deploys
    under {"force": true}."""
    from predictionio_tpu.tools import cli

    monkeypatch.setenv("PIO_DRAIN_TIMEOUT", "5")
    engine, baseline = train_const(memory_storage)
    with canary_fleet(memory_storage, engine) as (fleet, router, base):
        _, candidate = train_const(memory_storage)
        assert candidate.id != baseline.id
        # every const-model blob estimates far beyond 8 bytes
        monkeypatch.setattr(memacct, "host_memory_bytes", lambda: 8)
        failures, results = [], []
        with _load(base, failures, results):
            # rolling swap through the router: starts, then every
            # replica's preflight refuses — outcome partial, fleet
            # stays on the baseline
            status, body = get_json(base + "/reload")
            assert status == 202, body
            _await(lambda: (not fleet.snapshot()["swap"]["active"]
                            and fleet.snapshot()["swap"]["last"]),
                   message="refused swap to finish")
            last = fleet.snapshot()["swap"]["last"]
            assert last["outcome"] == "partial"
            assert last["swapped"] == []
            assert all("507" in e for e in last["errors"]), last
            assert any("preflight refused" in e
                       for e in last["errors"]), last
            assert fleet.version() == baseline.id
            # the refusal reason reaches the operator via `pio fleet`
            assert cli.main(["fleet", "--url", base]) == 0
            out = capsys.readouterr().out
            assert "preflight refused" in out and "507" in out
            # canary lane: same refusal, error verdict — the candidate
            # never reaches a replica
            status, body, _ = post(
                base + "/admin/fleet",
                body=json.dumps({"canary": "start"}).encode())
            assert status == 202, body
            _await(lambda: (fleet.canary().get("last") or {}).get(
                "outcome") == "error", message="canary refusal")
            canary_errors = " ".join(fleet.canary()["last"]["errors"])
            assert "507" in canary_errors
            assert not fleet.canary().get("active")
            # the SAME candidate under {"force": true}: accepted, the
            # whole fleet rolls onto it
            _await(lambda: not (fleet._canary_thread is not None
                                and fleet._canary_thread.is_alive()),
                   message="canary thread exit")
            status, body, _ = post(
                base + "/admin/fleet",
                body=json.dumps({"reload": True,
                                 "force": True}).encode())
            assert status == 202, body
            _await(lambda: fleet.version() == candidate.id,
                   message="forced swap onto the candidate")
        assert not failures, failures[:5]
        assert results.count(200) > 20


def test_force_started_canary_promotes_with_force(memory_storage,
                                                  monkeypatch):
    """A canary force-started past the preflight must PROMOTE with the
    same force — otherwise every other replica's 507 would strand the
    fleet permanently mixed (review regression)."""
    monkeypatch.setenv("PIO_CANARY_AUTO", "0")
    monkeypatch.setenv("PIO_DRAIN_TIMEOUT", "5")
    engine, baseline = train_const(memory_storage)
    with canary_fleet(memory_storage, engine, n=2) as (fleet, _r, _b):
        _, candidate = train_const(memory_storage)
        monkeypatch.setattr(memacct, "host_memory_bytes", lambda: 8)
        assert fleet.start_canary(force=True)
        _await(lambda: fleet.canary().get("active"),
               message="forced canary active")
        assert fleet.canary()["forced"] is True
        fleet.promote_canary()
        _await(lambda: fleet.version() == candidate.id,
               message="forced promotion converges")


# -- surfaces ------------------------------------------------------------------

def test_admin_memory_sums_match_ledger_within_1pct(memory_storage,
                                                    monkeypatch):
    """Acceptance: /admin/memory attribution vs the ledger's registered
    nbytes, per loaded model, on CPU with the host-memory capacity steered by the test."""
    monkeypatch.setattr(memacct, "host_memory_bytes", lambda: 1 << 30)
    model = _als_model()
    model.retrieval_index()
    engine, _ = train_const(memory_storage)
    server = EngineServer(engine, "const", host="127.0.0.1", port=0,
                          storage=memory_storage).start()
    try:
        status, served = get_json(
            f"http://127.0.0.1:{server.port}/admin/memory")
        assert status == 200
        ledger = {}
        for fp in memacct.LEDGER.footprints():
            ledger[fp.model] = ledger.get(fp.model, 0) + fp.nbytes
        assert served["models"], served
        for name, block in served["models"].items():
            assert block["total_bytes"] == pytest.approx(
                ledger[name], rel=0.01)
            assert block["total_bytes"] == sum(
                block["components"].values())
        assert served["basis"] == "host_memory"
        assert served["capacity_bytes"] == (1 << 30)
        assert served["headroom_bytes"] == (
            served["capacity_bytes"] - served["in_use_bytes"])
    finally:
        server.stop()


def test_pio_mem_cli_renders_both_modes(memory_storage, monkeypatch,
                                        capsys):
    from predictionio_tpu.tools import cli

    monkeypatch.setattr(memacct, "host_memory_bytes", lambda: 1 << 30)
    model = _als_model()  # kept referenced: the ledger holds weakrefs
    memacct.note_train_peak("als", 4096, source="analytic")
    # in-process
    assert cli.main(["mem"]) == 0
    out = capsys.readouterr().out
    assert "headroom" in out and "als" in out and "train peak" in out
    assert "preflight on" in out
    # over HTTP (any PIO server serves /admin/memory)
    engine, _ = train_const(memory_storage)
    server = EngineServer(engine, "const", host="127.0.0.1", port=0,
                          storage=memory_storage).start()
    try:
        base = f"http://127.0.0.1:{server.port}"
        assert cli.main(["mem", "--url", base, "--json"]) == 0
        payload = json.loads(capsys.readouterr().out)
        assert payload["models"]["als"]["components"]["factors"] > 0
        assert payload["train_peaks"]["als"]["bytes"] == 4096
    finally:
        server.stop()
        del model


def test_dashboard_memory_panel(memory_storage, monkeypatch):
    from predictionio_tpu.tools.dashboard import DashboardServer

    monkeypatch.setattr(memacct, "host_memory_bytes", lambda: 1 << 30)
    model = _als_model()  # kept referenced: the ledger holds weakrefs
    server = DashboardServer(storage=memory_storage, host="127.0.0.1",
                             port=0).start()
    try:
        from tests.test_health import get

        status, html, _ = get(
            f"http://127.0.0.1:{server.port}/memory")
        assert status == 200
        assert "Per-model ledger" in html and "als" in html
        assert "OOM preflight" in html
        # linked from the index
        status, index_html, _ = get(f"http://127.0.0.1:{server.port}/")
        assert '"/memory"' in index_html
    finally:
        server.stop()
        del model


def test_timeline_mem_series(monkeypatch):
    from predictionio_tpu.obs.timeline import Timeline

    monkeypatch.setattr(memacct, "host_memory_bytes", lambda: 1 << 20)
    model = _als_model()  # kept referenced: the ledger holds weakrefs
    tl = Timeline(interval=0.0)
    assert tl.sample(force=True)
    series = tl.series()["series"]
    assert "mem.headroom" in series
    assert "mem.model_bytes.als" in series
    # the headroom sample is capacity - ledger total (host_memory basis; the
    # ring stores 6 significant figures, hence the loose tolerance)
    assert series["mem.headroom"][-1][1] == pytest.approx(
        (1 << 20) - memacct.LEDGER.total_bytes(), rel=1e-4)
    del model


def test_snapshot_cadence_refreshes_gauges(monkeypatch):
    """Satellite: the device-memory gauges ride the flight-recorder
    snapshot cadence — a serving process reports continuously, not
    only post-train."""
    from predictionio_tpu.obs import flight

    monkeypatch.setattr(memacct, "host_memory_bytes", lambda: 5000)

    class Owner:
        pass

    o = Owner()
    memacct.LEDGER.register(o, "m", "factors", 1234)
    # stale on purpose
    memacct.DEVICE_HEADROOM_BYTES.set(0.0)
    assert memacct.refresh() >= 0  # the listener flight invokes
    assert refresh_headroom() == 5000 - 1234
    # and the listener is actually registered on the cadence
    assert ("memacct", memacct.refresh) in flight._snapshot_listeners


def refresh_headroom() -> float:
    return metrics.REGISTRY.get("pio_device_headroom_bytes").value


def test_jaxmon_delegate_still_answers():
    from predictionio_tpu.obs import jaxmon

    assert jaxmon.update_device_memory_gauges() >= 0
