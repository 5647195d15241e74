"""The benchmark harness, rehearsed on the CPU at a tiny size (a CPU run is
a control-flow check, never a device number)."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
RUN = os.path.join(REPO, "benchmarks", "run.py")
TINY = os.path.join(HERE, "tiny")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


@pytest.fixture(scope="module")
def harness():
    spec = importlib.util.spec_from_file_location("bench_run_under_test", RUN)
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return module


def run_cell(harness, capsys, workload, *extra, root=TINY, seed=5000000011):
    """One whole run in this process (the CPU stands in for the chip);
    returns (exit code, the parsed last line of stdout)."""
    code = harness.main(["--bench-root", root, "--rehearse-cpu",
                         "--workload", workload, "--seed", str(seed),
                         "--seconds", "1", *extra])
    lines = capsys.readouterr().out.strip().splitlines()
    return code, json.loads(lines[-1]), lines


def make_bench(harness, workload, seed=7, root=TINY):
    import argparse

    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cell = next(w for w in spec["workloads"] if w["name"] == workload)
    bench = harness.Bench(root, spec, cell, argparse.Namespace(
        seed=seed, seconds=1, trace=0))
    import jax
    bench.devices = jax.devices()[:1]
    return bench


@pytest.mark.parametrize("workload,metric", [
    ("als-tiny.serve-c4", "query_p95_ms"),
    ("twotower-tiny.train", "train_rate"),
])
def test_a_run_prints_the_contracts_last_line(harness, capsys, workload,
                                              metric):
    code, line, log = run_cell(harness, capsys, workload, "--trace", "0")
    assert code == 0
    assert set(line) == RESULT_KEYS
    assert set(line["device"]) == DEVICE_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert {metric, "setup_s"} <= set(line["metrics"])
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    # every number compared is printed beside its limit
    assert sum(1 for l in log if l.startswith("# check ")) >= 4
    assert any("compilations inside the window: 0" in l for l in log)


def test_a_traced_run_reports_per_layer_metrics(harness, capsys):
    code, line, _ = run_cell(harness, capsys, "als-tiny.serve-c4",
                             "--trace", "1")
    assert code == 0
    assert set(line) - {"breakdown"} == RESULT_KEYS
    assert {"busy_s", "window_s"} <= set(line["device"])
    # the CPU has no device plane: the trace readers find nothing and are
    # left out, the batcher's readers are there
    assert {"queue_wait_ms.serve", "batch_size_mean.serve",
            "lone_dispatch_share.serve",
            "dispatch_ms.serve"} <= set(line["metrics"])
    assert "setup_s" not in line["metrics"]


NEW_DRIVER = '''
def run(bench):
    import jax.numpy as jnp
    n = int(bench.traffic["n"]) * int(bench.config["width"])
    total = float(jnp.arange(n).sum())
    layer = bench.read_layer_metrics({"total": total})
    return {"attempted": n, "failed": 0, "window_compiles": 0,
            "checks": [{"name": "sum", "value": total,
                        "limit": n * (n - 1) / 2,
                        "ok": total == n * (n - 1) / 2}],
            "end_to_end": {"setup_s": 0.5, "sum_rate": 2.0},
            "layer_metrics": layer, "memory_peak_bytes": 1}
'''


def test_a_cell_of_new_files_only_needs_no_edit(harness, capsys, tmp_path):
    """A configuration, a mix, a driver and a per-layer metric as new
    files, a cell as a new entry: run.py finds them all by name."""
    tree = tmp_path / "newbench"
    for d in ("configs", "traffic", "drivers", "layer_metrics"):
        (tree / d).mkdir(parents=True)
    (tree / "configs" / "toy.json").write_text('{"width": 3}')
    (tree / "traffic" / "count.json").write_text(
        '{"driver": "counter", "n": 5}')
    (tree / "drivers" / "counter.py").write_text(NEW_DRIVER)
    (tree / "layer_metrics" / "total.toy.py").write_text(
        "def read(ctx):\n    return ctx['total']\n")
    (tree / "layer_metrics" / "absent.toy.py").write_text(
        "def read(ctx):\n    return None\n")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "command": ["python3", "benchmarks/run.py"], "paths": ["newbench"],
        "run_seconds": 1,
        "configs": [{"name": "toy", "source": "test", "reduced": [],
                     "file": "newbench/configs/toy.json", "why": "t"}],
        "workloads": [{"name": "toy.count", "config": "toy",
                       "traffic": "count", "chips": 1, "why": "t"}],
        "end_to_end": [
            {"name": "sum_rate", "unit": "x/s", "better": "higher",
             "bound": 0.1, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.1, "source": "host_clock"}],
        "per_layer": [
            {"name": "total.toy", "unit": "x", "better": "higher",
             "source": "program_counter", "layer": "toy",
             "moves": "sum_rate"},
            {"name": "absent.toy", "unit": "x", "better": "higher",
             "source": "program_counter", "layer": "toy",
             "moves": "sum_rate", "workloads": ["toy.count"]}],
    }))
    code, line, _ = run_cell(harness, capsys, "toy.count", "--trace", "0",
                             root=str(tmp_path))
    assert code == 0 and line["correct"] is True
    assert line["metrics"]["sum_rate"] == {"value": 2.0, "unit": "x/s"}
    code, line, _ = run_cell(harness, capsys, "toy.count", "--trace", "1",
                             root=str(tmp_path))
    # a reader that finds nothing to read is left out of the line
    assert line["metrics"] == {"total.toy": {"value": 105.0, "unit": "x"}}


def _run_py(args, cwd=REPO, env=None):
    e = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    e["JAX_PLATFORMS"] = "cpu"
    e.update(env or {})
    return subprocess.run([sys.executable, *args], cwd=cwd, env=e,
                          capture_output=True, text=True, timeout=300)


def test_without_a_tpu_the_run_fails_and_names_the_device():
    p = _run_py([RUN, "--bench-root", TINY, "--workload",
                 "als-tiny.serve-c4", "--seed", "1", "--seconds", "1",
                 "--trace", "0"])
    assert p.returncode not in (0, 1)
    assert "platform=cpu" in p.stderr and "no TPU" in p.stderr
    assert not any(l.startswith("{") for l in p.stdout.splitlines())


def test_without_the_program_the_run_fails(tmp_path):
    """A directory that holds only BENCHMARK.json and the files under
    ``paths`` has no system to measure: no result, another code than 0."""
    shutil.copytree(os.path.join(REPO, "benchmarks"),
                    tmp_path / "benchmarks")
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        cell = json.load(f)["workloads"][0]["name"]
    p = _run_py([str(tmp_path / "benchmarks" / "run.py"), "--workload", cell,
                 "--seed", "1", "--seconds", "1", "--trace", "0"],
                cwd=str(tmp_path))
    assert p.returncode != 0
    assert "not in this checkout" in p.stderr
    assert not any(l.startswith("{") for l in p.stdout.splitlines())


def test_the_load_generator_imports_no_jax():
    """The parent of a serve run holds the chip; its child must not touch
    JAX (nor anything beyond the standard library)."""
    code = ("import importlib.util, sys\n"
            "spec = importlib.util.spec_from_file_location('lg', sys.argv[1])\n"
            "m = importlib.util.module_from_spec(spec)\n"
            "spec.loader.exec_module(m)\n"
            "print(sorted(k for k in sys.modules if k.split('.')[0] in "
            "('jax', 'jaxlib', 'numpy', 'predictionio_tpu')))\n")
    p = _run_py(["-c", code, os.path.join(REPO, "benchmarks", "loadgen.py")])
    assert p.returncode == 0 and p.stdout.strip() == "[]", p.stdout + p.stderr


def test_the_load_generator_starts_in_the_waves_the_mix_gives():
    """``start`` in a traffic mix: one connection sends alone, the others
    begin ``delay_s`` later (a stub server records the arrivals)."""
    import socket
    import threading
    import time

    arrivals, lock = [], threading.Lock()
    srv = socket.create_server(("127.0.0.1", 0))
    port = srv.getsockname()[1]
    body = json.dumps({"itemScores": [{"item": "i1", "score": 1.0}]}).encode()
    reply = (b"HTTP/1.1 200 OK\r\nContent-Length: "
             + str(len(body)).encode() + b"\r\n\r\n" + body)

    def serve(conn, n):
        f = conn.makefile("rb")
        while True:
            length, line = 0, f.readline()
            if not line:
                return
            while line not in (b"\r\n", b""):
                if line.lower().startswith(b"content-length:"):
                    length = int(line.split(b":")[1])
                line = f.readline()
            f.read(length)
            with lock:
                arrivals.append((n, time.perf_counter()))
            time.sleep(0.01)
            conn.sendall(reply)

    def accept():
        for n in range(3):
            conn, _ = srv.accept()
            threading.Thread(target=serve, args=(conn, n),
                             daemon=True).start()

    threading.Thread(target=accept, daemon=True).start()
    cfg = {"port": port, "seed": 1, "connections": 3, "num": 1,
           "n_users": 10, "seconds": 0.2, "warmup_per_connection": 2,
           "check_sample": 4,
           "start": [{"connections": 1},
                     {"connections": 2, "delay_s": 0.2}]}
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "loadgen.py"),
         json.dumps(cfg)], input="GO\n", capture_output=True, text=True,
        timeout=60)
    srv.close()
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert p.returncode == 0 and out["n_errors"] == 0 and out["answered"] > 0
    # the lone connection is answered in 10 ms and sends again at once: it
    # gets several requests in before any other connection's first
    order = [n for n, _ in arrivals]
    first_other = next(i for i, n in enumerate(order) if n != order[0])
    assert first_other >= 3 and len(set(order)) == 3, order[:12]


def test_the_train_program_is_the_same_for_every_seed(harness):
    """The trainer compiles its examples into the epoch program; with
    ``positives_seed`` in the configuration two seeds lower to the same
    program, so the second run of a checkout finds it in the compile
    cache."""
    texts = []
    for seed in (11, 4300000077):
        bench = make_bench(harness, "twotower-tiny.train", seed=seed)
        builder = bench.load_module("models", "twotower")
        trainer = builder.build(bench).trainer
        import jax
        key = jax.random.PRNGKey(0)
        texts.append(trainer._epoch_fn.lower(*trainer._state, key).as_text())
    assert texts[0] == texts[1]


# -- the two references: agree with the program, reject a lower precision --

def test_als_reference_accepts_exact_and_rejects_bfloat16(harness):
    bench = make_bench(harness, "als-tiny.serve-c4")
    ref = bench.load_module("reference", "als_top10")
    builder = bench.load_module("models", "als")
    X, Y = builder.make_factors(bench)
    assert X.dtype == np.float32 and Y.shape == (3000, 8)
    X2, _ = builder.make_factors(bench)
    assert np.array_equal(X, X2)                 # same seed, same weights
    rows = list(range(0, 400, 7))
    limits = bench.config["limits"]
    exact = [(u, [(int(i), float(Y[i] @ X[u]))
                  for i in np.argsort(-(Y @ X[u]))[:10]]) for u in rows]
    got = ref.compare(X, Y, exact, 10)
    assert got["malformed"] == 0
    assert got["score_err"] <= limits["score_err"]
    assert got["rank_gap"] <= limits["rank_gap"]
    control = ref.compare(
        X, Y, ref.lower_precision_answers(X, Y, rows, 10, "bfloat16"), 10)
    assert control["score_err"] > limits["score_err"]
    # an answer with a repeated item is malformed, not compared
    bad = [(exact[0][0], exact[0][1][:9] + exact[0][1][:1])]
    assert ref.compare(X, Y, bad, 10)["malformed"] == 1


def test_twotower_reference_rejects_bfloat16(harness):
    bench = make_bench(harness, "twotower-tiny.train")
    ref = bench.load_module("reference", "twotower_step")
    limits = bench.config["limits"]
    sound = ref.first_epoch(bench)
    again = ref.gaps(ref.first_epoch(bench), sound)
    assert all(v == 0.0 for v in again.values())     # deterministic
    control = ref.gaps(ref.first_epoch(bench, "bfloat16"), sound)
    assert any(control[k] > limits[k] for k in control), control


# -- the timed path broken underneath: ``correct`` comes out false --------

def test_an_altered_answer_is_not_correct(harness, capsys, monkeypatch):
    from predictionio_tpu.models.als import ALSAlgorithm

    def alter(result):
        if result.get("itemScores"):
            result["itemScores"][0]["score"] *= 1.01
        return result

    predict, batch = ALSAlgorithm.predict, ALSAlgorithm.batch_predict
    monkeypatch.setattr(ALSAlgorithm, "predict",
                        lambda s, m, q: alter(predict(s, m, q)))
    monkeypatch.setattr(
        ALSAlgorithm, "batch_predict",
        lambda s, m, qs: [(i, alter(r)) for i, r in batch(s, m, qs)])
    code, line, log = run_cell(harness, capsys, "als-tiny.serve-c4",
                               "--trace", "0")
    assert code == 0 and line["correct"] is False
    assert any(l.startswith("# check score_err") and "FAILED" in l
               for l in log)


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        harness, capsys, monkeypatch):
    import jax
    from predictionio_tpu.ops.twotower import TwoTowerTrainer

    make_epoch = TwoTowerTrainer._make_epoch

    def broken(self):
        epoch = make_epoch(self)

        def unchanged(tables, acc, dense, opt_state, key):
            stats = epoch(tables, acc, dense, opt_state, key)[4]
            return tables, acc, dense, opt_state, stats

        return jax.jit(unchanged)

    monkeypatch.setattr(TwoTowerTrainer, "_make_epoch", broken)
    code, line, log = run_cell(harness, capsys, "twotower-tiny.train",
                               "--trace", "0")
    assert code == 0 and line["correct"] is False
    assert any(l.startswith("# check change_norm_gap") and "FAILED" in l
               for l in log)
