"""chip_smoke.py and the one-process-per-chip rules it rests on.

  - the script itself: on the CPU at the test-only size it fails, names
    the phase and exits non-zero (the platform is not ``tpu``); its
    child-line parser is unit-tested;
  - the script's own data: its generator is pinned by a checksum, the
    split holds out every 20th row, the RMSE band binds at full size;
  - parents stay off jax: chip_smoke and the fleet's router process
    import no jax, and ``GET /readyz`` /
    ``GET /metrics`` on an event server, a storage server and a router
    leave it so (a process that imported jax could take the chip its
    children need) — checked in fresh interpreters, since this one has
    jax loaded;
  - subprocess replicas get one chip each, or the fleet refuses to
    start;
  - the compile cache can be placed from outside.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def _python(code: str, env: dict = None, cwd: str = None, timeout=240):
    base = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    return subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)], capture_output=True,
        text=True, timeout=timeout, cwd=cwd,
        env={**base, "PYTHONPATH": ROOT, "JAX_PLATFORMS": "cpu",
             **(env or {})})


# -- the script ----------------------------------------------------------------

def test_chip_smoke_on_cpu_fails_and_names_the_phase(tmp_path):
    """Run as the driver runs it, but held to the CPU and at the tiny
    size, from another directory: the env phase builds the libraries
    and finds platform ``cpu``; the als phase fails on that at once."""
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"), "--tiny"],
        capture_output=True, text=True, timeout=600, cwd=str(tmp_path),
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0, proc.stdout[-2000:]
    lines = chip_smoke.parse_child_lines(proc.stdout)
    assert lines[-1] == {"ok": False, "phase": "als"}, proc.stdout[-2000:]
    assert lines[0]["phase"] == "env" and lines[0]["ok"] is True
    failed = lines[-2]
    assert failed["phase"] == "als" and failed["ok"] is False
    assert "platform 'cpu'" in failed["why"]
    assert not any(line.get("ok") is True and "device" in line
                   for line in lines)


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    """A directory that holds chip_smoke.py and nothing else of the
    repo: no result, non-zero exit."""
    import shutil

    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], capture_output=True, text=True,
        timeout=120, cwd=str(tmp_path))
    assert proc.returncode != 0
    assert chip_smoke.parse_child_lines(proc.stdout)[-1]["ok"] is False


@pytest.mark.parametrize("text,expected", [
    ("", []),
    ("Training completed: engine instance abc (COMPLETED)\n"
     '{"train_report": {"platform": "tpu", "device_count": 1}}\n',
     [{"train_report": {"platform": "tpu", "device_count": 1}}]),
    ('INFO {not json}\n  {"a": 1}  \n[1, 2]\n{"b": {"c": [1]}}\n{broken}\n',
     [{"a": 1}, {"b": {"c": [1]}}]),
])
def test_parse_child_lines(text, expected):
    assert chip_smoke.parse_child_lines(text) == expected


def test_child_report_takes_the_last_and_fails_without_one():
    text = '{"train_report": {"n": 1}}\nnoise\n{"train_report": {"n": 2}}\n'
    assert chip_smoke.child_report(text, "train_report") == {"n": 2}
    with pytest.raises(chip_smoke.PhaseFailed, match="no 'train_report'"):
        chip_smoke.child_report("nothing here\n", "train_report")


def test_check_top10_against_numpy_reference():
    import numpy as np

    rng = np.random.default_rng(0)
    factors = {"X": rng.normal(size=(4, 8)).astype(np.float32),
               "Y": rng.normal(size=(50, 8)).astype(np.float32),
               "users": np.arange(4), "items": np.arange(50)}
    ref = factors["X"][2] @ factors["Y"].T
    top = np.argsort(-ref)[:10]
    good = [{"item": f"i{r}", "score": float(ref[r]) * 1.001} for r in top]
    chip_smoke.check_top10(factors, 2, good)
    bad = [dict(a) for a in good]
    bad[0]["score"] *= 1.5
    with pytest.raises(chip_smoke.PhaseFailed, match="served score"):
        chip_smoke.check_top10(factors, 2, bad)
    worst = np.argsort(ref)[:2]
    swapped = good[:8] + [{"item": f"i{r}", "score": float(ref[r])}
                          for r in worst]
    with pytest.raises(chip_smoke.PhaseFailed, match="8/10 items shared"):
        chip_smoke.check_top10(factors, 2, swapped)


# -- the script's own data ---------------------------------------------------

@pytest.mark.parametrize("seed,sizes,digest", [
    (7, (300, 120, 12_000),
     "7e108cfcb9066602a1a5485c0585e46168bbb86b1426a8157d09e5e863db623b"),
    (22, (1000, 400, 50_000),
     "b154e0f809b81da983fee3a633ffbdebf8699b13f222b14ea3314b64f47b3eaa"),
])
def test_synthesize_gives_the_arrays_it_always_gave(seed, sizes, digest):
    """The digests were taken from the generator this one replaced
    (PR 29's parent), so a smoke run's data, and with it the RMSE band,
    mean what they meant."""
    import hashlib

    import numpy as np

    arrays = chip_smoke.synthesize(*sizes, np.random.default_rng(seed))
    assert [a.dtype for a in arrays] == [np.int64, np.int64, np.float64]
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(a).tobytes())
    assert h.hexdigest() == digest


def test_als_data_holds_out_every_20th_row():
    import types

    import numpy as np

    size = dict(chip_smoke.TINY)
    run = types.SimpleNamespace(seed=5, size=size)
    kept, held = chip_smoke.als_data(run)
    whole = chip_smoke.synthesize(size["users"], size["items"],
                                  size["ratings"], np.random.default_rng(5))
    rows = np.arange(size["ratings"])
    for k, h, w in zip(kept, held, whole):
        assert np.array_equal(h, w[rows % 20 == 0])
        assert np.array_equal(k, w[rows % 20 != 0])
    assert len(held[0]) * 20 == size["ratings"]


def test_rmse_band_binds_at_full_size_only():
    full, tiny = chip_smoke.FULL["ratings"], chip_smoke.TINY["ratings"]
    lo, hi = chip_smoke.RMSE_BAND
    chip_smoke.check_rmse((lo + hi) / 2, 1.0, full)
    for outside in (lo - 0.01, hi + 0.01):
        with pytest.raises(chip_smoke.PhaseFailed, match="outside the band"):
            chip_smoke.check_rmse(outside, 1.0, full)
    # a reduced data set: the band says nothing, the global mean does
    chip_smoke.check_rmse(hi + 0.2, 1.0, tiny)
    chip_smoke.check_rmse(lo - 0.2, 1.0, tiny)
    with pytest.raises(chip_smoke.PhaseFailed, match="not 15% better"):
        chip_smoke.check_rmse(0.9, 1.0, tiny)


# -- parents stay off jax ----------------------------------------------------

def test_parent_modules_import_no_jax():
    proc = _python("""
        import sys
        import chip_smoke
        import predictionio_tpu.tools.cli
        import predictionio_tpu.serving.fleet, predictionio_tpu.serving.router
        import predictionio_tpu.serving.event_server
        import predictionio_tpu.serving.storage_server
        import predictionio_tpu.workflow.variant
        assert "jax" not in sys.modules, sorted(
            m for m in sys.modules if m.startswith("jax"))[:5]
    """)
    assert proc.returncode == 0, proc.stderr[-2000:]


_SERVERS = {
    "eventserver": """
        from predictionio_tpu.serving.event_server import EventServer
        server = EventServer(host="127.0.0.1", port=0)
    """,
    "storageserver": """
        from predictionio_tpu.serving.storage_server import StorageServer
        server = StorageServer(host="127.0.0.1", port=0)
    """,
    "router": """
        from predictionio_tpu.serving.fleet import (FleetSupervisor,
                                                   SubprocessReplica)
        from predictionio_tpu.serving.router import QueryRouter
        fleet = FleetSupervisor([SubprocessReplica("r0", ["true"])])
        server = QueryRouter(fleet, host="127.0.0.1", port=0)
    """,
}


@pytest.mark.parametrize("which", sorted(_SERVERS))
def test_readyz_and_metrics_leave_jax_out(which, tmp_path):
    """finding 2: a health check must not be what takes the chip."""
    proc = _python(textwrap.dedent("""
        import json, sys, urllib.error, urllib.request
        from predictionio_tpu.data.storage import Storage, set_storage
        set_storage(Storage.from_env({
            "PIO_STORAGE_SOURCES_M_TYPE": "memory",
            **{f"PIO_STORAGE_REPOSITORIES_{r}_{k}": v
               for r in ("METADATA", "EVENTDATA", "MODELDATA")
               for k, v in (("NAME", r.lower()), ("SOURCE", "M"))}}))
    """) + textwrap.dedent(_SERVERS[which]) + textwrap.dedent("""
        server.start()
        base = f"http://127.0.0.1:{server.port}"
        def get(path):
            try:
                with urllib.request.urlopen(base + path, timeout=30) as r:
                    return r.status, r.read()
            except urllib.error.HTTPError as e:
                return e.code, e.read()
        status, body = get("/readyz")
        probes = json.loads(body)["probes"]
        assert probes["devices"]["reason"] == "no device in this process", probes
        assert get("/metrics")[0] == 200
        server.stop()
        assert "jax" not in sys.modules
        print("OK", status)
    """), cwd=str(tmp_path))
    assert proc.returncode == 0 and "OK" in proc.stdout, (
        proc.stdout[-500:] + proc.stderr[-2000:])


def test_devices_probe_reports_devices_once_a_backend_is_up():
    """This process HAS initialised a backend (the tests run on it):
    the probe then looks at its devices."""
    import jax

    from predictionio_tpu.obs import health

    jax.devices()
    assert health.jax_backend_initialized()
    result = health._devices_probe()
    assert result.status == health.OK and "cpu device(s)" in result.reason


def test_memacct_does_not_initialise_a_backend():
    """obs/memacct.py in a process that imported jax for another reason
    (no backend yet) must not be what initialises it."""
    proc = _python("""
        import jax
        from jax._src import xla_bridge
        from predictionio_tpu.obs import memacct
        assert memacct._jax_device_stats() == []
        memacct.refresh()
        memacct.capacity_report()
        assert not xla_bridge.backends_are_initialized()
    """)
    assert proc.returncode == 0, proc.stderr[-2000:]


# -- one chip for each replica -------------------------------------------------

def test_subprocess_fleet_gives_each_replica_its_own_chip(monkeypatch):
    from predictionio_tpu.serving import fleet

    monkeypatch.setattr(fleet, "local_chip_count", lambda: 4)
    members = fleet.subprocess_fleet(4, ["pio"], env={"X": "1"})
    assert [m._env["TPU_VISIBLE_CHIPS"] for m in members] == list("0123")
    for m in members:
        assert m._env["X"] == "1"
        assert m._env["TPU_PROCESS_BOUNDS"] == "1,1,1"
        assert m._env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"


def test_more_replicas_than_chips_fails_at_start(monkeypatch, tmp_path,
                                                capsys):
    from predictionio_tpu.serving import fleet
    from predictionio_tpu.tools.cli import main as cli_main

    monkeypatch.setattr(fleet, "local_chip_count", lambda: 1)
    with pytest.raises(ValueError, match="need 4 TPU chips.*has 1"):
        fleet.subprocess_fleet(4, ["pio"])
    engine_json = tmp_path / "engine.json"
    engine_json.write_text(json.dumps({"engineFactory": "x.Y"}))
    rc = cli_main(["deploy", "--engine-json", str(engine_json),
                   "--replicas", "4", "--replica-mode", "subprocess",
                   "--port", "0"])
    assert rc != 0
    assert "need 4 TPU chips" in "".join(capsys.readouterr())


def test_on_the_cpu_nothing_is_assigned(monkeypatch):
    from predictionio_tpu.serving import fleet

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert fleet.local_chip_count() == 0
    members = fleet.subprocess_fleet(3, ["pio"])
    assert all("TPU_VISIBLE_CHIPS" not in m._env for m in members)


# -- compile cache placement ---------------------------------------------------

def test_compile_cache_dir_from_outside_is_left_to_jax(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: our code sets no directory."""
    where = str(tmp_path / "outside")
    proc = _python("""
        import os, jax
        from predictionio_tpu.parallel import compile_cache
        seen = []
        real = jax.config.update
        jax.config.update = lambda k, v: (seen.append(k), real(k, v))
        got = compile_cache.enable_persistent_cache()
        assert got == os.environ["JAX_COMPILATION_CACHE_DIR"], got
        assert "jax_compilation_cache_dir" not in seen, seen
        assert jax.config.jax_compilation_cache_dir == got
    """, env={"JAX_COMPILATION_CACHE_DIR": where})
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_compile_cache_default_is_the_checkouts_own(tmp_path):
    """Unset: <checkout>/.pio_run/compile_cache, wherever the process
    runs from and whatever PIO_FS_BASEDIR says."""
    proc = _python("""
        import jax
        from predictionio_tpu.parallel import compile_cache
        got = compile_cache.enable_persistent_cache()
        print(got)
        assert jax.config.jax_compilation_cache_dir == got
    """, env={"PIO_FS_BASEDIR": str(tmp_path / "base"),
              "PIO_COMPILE_CACHE_DIR": str(tmp_path / "gone-knob")},
        cwd=str(tmp_path))
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == os.path.join(
        ROOT, ".pio_run", "compile_cache")


# -- training over every visible device ----------------------------------------

def test_run_train_builds_the_default_mesh(memory_storage):
    """Found on four chips (PR 22): `pio train` passed no mesh, so the
    ALS trainer put every binned byte on device 0. Without a ctx,
    run_train now hands the engine every visible device on ``data``
    (the tests' eight virtual devices here); a ctx that is passed is
    used as it is."""
    import jax

    from predictionio_tpu.core import Engine, FirstServing, IdentityPreparator
    from predictionio_tpu.core.params import EngineParams
    from predictionio_tpu.parallel.mesh import MeshContext
    from predictionio_tpu.workflow.train import run_train
    from tests.test_health import ConstAlgo, ConstDataSource, ConstParams

    seen = []

    class MeshSeeingAlgo(ConstAlgo):
        def train(self, ctx, pd):
            seen.append(ctx.mesh)
            return super().train(ctx, pd)

    engine = Engine(ConstDataSource, IdentityPreparator,
                    {"const": MeshSeeingAlgo}, FirstServing)
    ep = EngineParams(
        data_source_params=("", ConstParams(value=1.0)),
        preparator_params=("", None),
        algorithm_params_list=[("const", ConstParams(value=2.0))],
        serving_params=("", None))
    run_train(engine, ep, engine_id="mesh_default", storage=memory_storage)
    run_train(engine, ep, engine_id="mesh_given", storage=memory_storage,
              ctx=MeshContext())
    default, given = seen
    assert jax.device_count() > 1
    assert dict(default.shape) == {"data": jax.device_count(), "model": 1}
    assert given is None
