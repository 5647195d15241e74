"""``first_query_p50_ms`` (PR 54, after PR 52's refusal): the tail of
``glm-5.lifelong32k-c4`` is the first queries' OWN nearest-rank median, read
from the same window and the same list as the other end-to-end metrics; the
cell no longer reports ``query_p95_ms`` (the same number stands per layer as
``window_p95_ms.lifelong32k-c4``), every per-layer entry that lists the cell
moves a metric the cell reports, and no other cell's line changes."""

import json
import os
import shutil

import pytest

from tests.benchmarks import repo_spec
from tests.benchmarks.test_program_spans import BENCHMARKS, HERE, load_file

METRIC = "first_query_p50_ms"
CELL = "glm-5.lifelong32k-c4"
ENTRY = {"name": METRIC, "unit": "ms", "better": "lower", "bound": 0.09,
         "source": "host_clock", "workloads": [CELL]}
TAIL = {"name": "window_p95_ms.lifelong32k-c4", "unit": "ms",
        "better": "lower", "source": "host_clock",
        "layer": "sequence engine", "moves": METRIC, "workloads": [CELL]}
TINY_CELL = "longcat-tiny.sessions-c4"


@pytest.fixture(scope="module")
def driver():
    return load_file(os.path.join(BENCHMARKS, "drivers",
                                  "session_queries.py"))


@pytest.fixture(scope="module")
def percentile():
    return load_file(os.path.join(BENCHMARKS, "drivers",
                                  "closed_loop_queries.py")).percentile


def load_of(latencies, firsts):
    return {"latencies_s": latencies, "first_query": firsts,
            "window_s": 20.0}


@pytest.mark.parametrize("firsts_s, median_ms", [
    ([3.0], 3000.0),
    ([1.0, 3.0], 1000.0),                 # nearest rank: the lower middle
    ([4.0, 1.0, 3.0], 3000.0),
    ([2.5, 4.0, 1.0, 3.0], 2500.0),
    ([0.9, 5.1, 2.2, 3.3, 4.4, 1.6, 2.7], 2700.0)])
def test_the_drivers_dict_holds_the_first_queries_own_median(
        driver, percentile, firsts_s, median_ms):
    """Nearest rank over the first queries alone, whatever the extensions
    around them count: the same list gives the same number with 9 or with
    90 extensions beside it, where the window's 95th percentile moves."""
    seen = []
    for n_later in (9, 90):
        later = [0.040 + 0.001 * (i % 7) for i in range(n_later)]
        # in the order the connections sent them, first queries among them
        lat = firsts_s[:1] + later + firsts_s[1:]
        flags = [True] + [False] * n_later + [True] * (len(firsts_s) - 1)
        numbers, all_s, first_s, later_s = driver.window_numbers(
            load_of(lat, flags), 40.0, percentile)
        assert numbers[METRIC] == pytest.approx(median_ms)
        assert numbers[METRIC] == percentile(sorted(firsts_s), 0.5) * 1e3
        assert first_s == sorted(firsts_s) and len(later_s) == n_later
        assert all_s == sorted(lat)
        assert numbers["query_rate"] == len(lat) / 20.0
        assert numbers["setup_s"] == 40.0
        seen.append(numbers["query_p95_ms"])
    if len(firsts_s) == 7:
        # 7 first queries of 16 requests | of 97: the window's tail is the
        # slowest of them | the 5th slowest
        assert seen == [5100.0, 2200.0]


def test_a_window_without_a_first_query_has_no_such_number(
        driver, percentile):
    numbers, _, first_s, _ = driver.window_numbers(
        load_of([0.05, 0.04, 0.06], [False] * 3), 40.0, percentile)
    assert first_s == [] and METRIC not in numbers
    assert set(numbers) == {"setup_s", "query_p50_ms", "query_p95_ms",
                            "query_rate"}


@pytest.mark.parametrize("case", repo_spec.CASES)
def test_benchmark_json_lists_the_cell_under_the_metric_and_not_the_tail(
        case):
    spec = repo_spec.load(case)
    assert repo_spec.by_name(spec["end_to_end"], METRIC) == ENTRY
    tail = repo_spec.by_name(spec["end_to_end"], "query_p95_ms")
    assert CELL not in tail["workloads"]
    # the other long-history cell keeps its tail: the middle of ~53 first
    # queries, which fell with the speed-up under PR 52
    assert "ax-k1.lifelong-c4" in tail["workloads"]
    assert tail["bound"] == ENTRY["bound"]
    here = {m["name"] for m in spec["end_to_end"]
            if CELL in m.get("workloads", [CELL])}
    assert here == {"query_p50_ms", "query_rate", METRIC, "setup_s"}
    # no other cell reports it, the added one neither
    assert [w["name"] for w in spec["workloads"]
            if w["name"] in ENTRY["workloads"]] == [CELL]
    # the bounds and lists of the other end-to-end metrics stand
    for name, bound in (("query_p50_ms", 0.1), ("query_rate", 0.1),
                        ("train_rate", 0.01), ("setup_s", 0.1)):
        assert repo_spec.by_name(spec["end_to_end"], name)["bound"] == bound


@pytest.mark.parametrize("case", repo_spec.CASES)
def test_every_entry_that_lists_the_cell_moves_a_metric_it_reports(case):
    spec = repo_spec.load(case)
    here = {m["name"] for m in spec["end_to_end"]
            if CELL in m.get("workloads", [CELL])}
    listed = [m for m in spec["per_layer"] if CELL in m.get("workloads", ())]
    assert len(listed) == 13
    for m in listed:
        assert m["moves"] in here, m["name"]
    assert {m["name"] for m in listed if m["moves"] == METRIC} == {
        "prefill_chunk_ms.glm", "prefill_roofline_pct.glm",
        "index_score_roofline_pct.glm", TAIL["name"]}
    repo_spec.assert_names_the_reader(spec, TAIL)
    # nothing per layer names the 95th percentile for a cell without one
    tail = repo_spec.by_name(spec["end_to_end"], "query_p95_ms")
    for m in spec["per_layer"]:
        if m["moves"] == "query_p95_ms":
            assert set(m["workloads"]) <= set(tail["workloads"]), m["name"]


def test_the_windows_tail_reader(driver):
    reader = load_file(os.path.join(BENCHMARKS, "layer_metrics",
                                    TAIL["name"] + ".py"))
    window = {"setup_s": 40.0, "query_p50_ms": 49.4, "query_p95_ms": 3455.7,
              "query_rate": 8.27, METRIC: 2842.6}
    assert reader.read({"window_end_to_end": window}) == 3455.7
    for ctx in ({}, {"window_end_to_end": None}, {"window_end_to_end": {}},
                {"window_end_to_end": {METRIC: 2842.6}}):
        assert reader.read(ctx) is None


def run_tiny(run, capsys, root, trace):
    code = run.main(["--bench-root", str(root), "--rehearse-cpu",
                     "--workload", TINY_CELL, "--seed", "5000000054",
                     "--seconds", "1", "--trace", trace])
    out = capsys.readouterr().out.strip().splitlines()
    return code, json.loads(out[-1]), out


def test_a_tiny_session_cell_prints_it_and_one_that_does_not_list_it_does_not(
        tmp_path, capsys):
    """Through run.py on the CPU: the tiny session cell as it stands prints
    the line it printed before; with the two entries appended its untraced
    line has the first queries' median, the number of the log's ``# first
    queries`` note, and its traced line the window's tail per layer."""
    run = load_file(os.path.join(BENCHMARKS, "run.py"))
    code, before, _ = run_tiny(run, capsys, os.path.join(HERE, "tiny_seq"),
                               "0")
    assert code == 0 and before["correct"] is True
    assert set(before["metrics"]) == {"query_p50_ms", "query_p95_ms",
                                      "query_rate", "setup_s"}

    shutil.copytree(os.path.join(HERE, "tiny_seq"), tmp_path / "tiny")
    path = tmp_path / "tiny" / "BENCHMARK.json"
    spec = json.loads(path.read_text())
    spec["end_to_end"].append(dict(ENTRY, workloads=[TINY_CELL]))
    spec["per_layer"].append(dict(TAIL, workloads=[TINY_CELL]))
    path.write_text(json.dumps(spec))
    code, untraced, log = run_tiny(run, capsys, tmp_path / "tiny", "0")
    assert code == 0 and untraced["correct"] is True
    assert set(untraced["metrics"]) == set(before["metrics"]) | {METRIC}
    assert untraced["metrics"][METRIC]["unit"] == "ms"
    said = next(l for l in log if l.startswith("# first queries "))
    assert f": p50 {untraced['metrics'][METRIC]['value']:.3f} ms" in said
    code, traced, log = run_tiny(run, capsys, tmp_path / "tiny", "1")
    assert code == 0 and traced["correct"] is True
    assert METRIC not in traced["metrics"]
    said = next(l for l in log if l.startswith("# latency ms: "))
    assert f" p95 {traced['metrics'][TAIL['name']]['value']:.3f} " in said
