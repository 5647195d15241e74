"""``window_p95_ms.serve-c32`` (PR 48, after its refusal): the saturated closed
loop's 95th percentile is a per-layer number of its cell, read from the same
window and the same list as the end-to-end metrics; ``serve-c32`` no longer
reports ``query_p95_ms``, and nothing per layer there still names it."""

import json
import os
import shutil

import pytest

from tests.benchmarks import repo_spec
from tests.benchmarks.test_program_spans import BENCHMARKS, HERE, load_file

METRIC = "window_p95_ms.serve-c32"
CELL = "als-amazon14.serve-c32"
ENTRY = {"name": METRIC, "unit": "ms", "better": "lower",
         "source": "host_clock", "layer": "engine",
         "moves": "query_p50_ms", "workloads": [CELL]}


@pytest.fixture(scope="module")
def reader():
    return load_file(os.path.join(BENCHMARKS, "layer_metrics",
                                  METRIC + ".py"))


def test_the_reader_gives_the_windows_own_number(reader):
    window = {"setup_s": 40.0, "query_p50_ms": 25.1, "query_p95_ms": 31.7,
              "query_rate": 1200.0}
    assert reader.read({"window_end_to_end": window}) == 31.7


@pytest.mark.parametrize("ctx", [
    {}, {"window_end_to_end": None}, {"window_end_to_end": {}},
    {"window_end_to_end": {"train_rate": 2.4e6}}])
def test_nothing_to_read_is_none(reader, ctx):
    assert reader.read(ctx) is None


@pytest.mark.parametrize("case", repo_spec.CASES)
def test_benchmark_json_names_the_reader_and_its_cell(case):
    spec = repo_spec.load(case)
    repo_spec.assert_names_the_reader(spec, ENTRY)
    assert ENTRY["layer"] in [m["layer"] for m in spec["per_layer"]
                              if m["name"] != METRIC]


@pytest.mark.parametrize("case", repo_spec.CASES)
def test_the_cell_reports_no_tail_end_to_end_and_nothing_moves_one(case):
    """``query_p95_ms`` lists every serve cell but this one; the cell keeps
    two end-to-end metrics beside ``setup_s``; every per-layer entry that
    lists the cell moves a metric the cell reports."""
    spec = repo_spec.load(case)
    tail = repo_spec.by_name(spec["end_to_end"], "query_p95_ms")
    assert CELL not in tail["workloads"]
    assert "als-amazon14.serve-c1" in tail["workloads"]
    here = {m["name"] for m in spec["end_to_end"]
            if CELL in m.get("workloads", [CELL])}
    assert here == {"query_p50_ms", "query_rate", "setup_s"}
    for m in spec["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert m["moves"] in here, m["name"]


def test_a_traced_tiny_cell_reports_its_windows_tail_per_layer(
        tmp_path, capsys):
    """Through run.py on the CPU: the tiny ALS cell with the tail taken out
    of its end-to-end metrics and this entry appended. The traced line has
    the window's tail under the new name; the untraced line has none."""
    run = load_file(os.path.join(BENCHMARKS, "run.py"))
    shutil.copytree(os.path.join(HERE, "tiny"), tmp_path / "tiny")
    path = tmp_path / "tiny" / "BENCHMARK.json"
    spec = json.loads(path.read_text())
    repo_spec.by_name(spec["end_to_end"], "query_p95_ms")["workloads"] = []
    spec["per_layer"].append(dict(ENTRY, workloads=["als-tiny.serve-c4"]))
    path.write_text(json.dumps(spec))
    lines = {}
    for trace in ("1", "0"):
        code = run.main(["--bench-root", str(tmp_path / "tiny"),
                         "--rehearse-cpu", "--workload", "als-tiny.serve-c4",
                         "--seed", "5000000048", "--seconds", "1",
                         "--trace", trace])
        out = capsys.readouterr().out.strip().splitlines()
        lines[trace] = (code, json.loads(out[-1]), out)
    code, traced, log = lines["1"]
    assert code == 0 and traced["correct"] is True
    assert traced["metrics"][METRIC]["unit"] == "ms"
    said = next(l for l in log if l.startswith("# latency ms: "))
    assert f"p95 {traced['metrics'][METRIC]['value']:.3f} " in said
    # the notes that show a run's regimes and the generator's own pauses
    assert any(l.startswith("# latency ms by the second") for l in log)
    assert any(l.startswith("# load generator's own collections")
               for l in log)
    code, untraced, _ = lines["0"]
    assert code == 0 and untraced["correct"] is True
    assert set(untraced["metrics"]) == {"query_p50_ms", "query_rate",
                                        "setup_s"}
