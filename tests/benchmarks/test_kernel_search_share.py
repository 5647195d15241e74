"""``kernel_search_share.serve`` (ISSUE 28): the reader of the
``pio:index.route`` markers, against hand-built events, against the trace
recorded on the chip before the program wrote that marker, and through
``run.py`` on the CPU, where the program writes it."""

import json
import os
import shutil

import pytest

from tests.benchmarks import repo_spec
from tests.benchmarks.test_program_spans import (BENCHMARKS, FIXTURE, HERE,
                                                 SCOPES, load_file,
                                                 make_trace, read)

METRIC = "kernel_search_share.serve"


@pytest.fixture(scope="module")
def ps():
    return load_file(os.path.join(BENCHMARKS, "program_spans.py"))


def search(start, route, rows, line=1):
    """One search's spans: ``pio:index.search`` and the marker inside it."""
    return [("pio:index.search", start, start + 8, line),
            ("pio:index.route", start + 2, start + 2.001, line,
             {"route": route, "rows": rows})]


@pytest.mark.parametrize("routes,share", [
    (["kernel"] * 4, 100.0),
    (["kernel", "xla_device", "kernel", "host"], 50.0),
    (["xla_device", "xla_device"], 0.0),
    (["kernel", "kernel", "xla_device"], 200.0 / 3),
])
def test_the_share_counts_searches_not_rows(ps, routes, share):
    spans = []
    for n, route in enumerate(routes):
        spans += search(10 * n, route, rows=1 if n % 2 else 32)
    assert read(METRIC, make_trace(ps, spans)) == pytest.approx(share)


def test_no_marker_is_nothing_to_read(ps):
    # a program from before the marker: searches, and no route inside them
    bare = make_trace(ps, [("pio:index.search", 0, 8, 1),
                           ("pio:index.enqueue", 1, 3, 1)])
    assert read(METRIC, bare) is None
    # no trace at all
    assert read(METRIC, None) is None
    # the chip trace recorded at PR 25 holds searches and no marker
    with open(SCOPES) as f:
        recorded = ps.load(FIXTURE, json.load(f))
    assert ps.named(recorded, "pio:index.search")
    assert read(METRIC, recorded) is None


@pytest.mark.parametrize("case", repo_spec.CASES)
def test_benchmark_json_names_the_reader_and_its_two_cells(case):
    repo_spec.assert_names_the_reader(repo_spec.load(case), {
        "name": METRIC, "unit": "%", "better": "higher",
        "source": "program_span", "layer": "retrieval",
        "moves": "query_p50_ms",
        "workloads": ["als-amazon14.serve-c32", "als-amazon14.serve-c1"]})


def test_a_traced_tiny_cell_reads_every_search_off_the_kernel_on_the_cpu(
        tmp_path, capsys):
    """Through run.py: the tiny ALS cell with this metric appended. On the
    CPU backend the index's fallback answers, so the program's markers say
    ``host`` or ``xla_device`` and the share is a reading of 0, not None."""
    run = load_file(os.path.join(BENCHMARKS, "run.py"))
    shutil.copytree(os.path.join(HERE, "tiny"), tmp_path / "tiny")
    added = repo_spec.by_name(repo_spec.load()["per_layer"], METRIC)
    path = tmp_path / "tiny" / "BENCHMARK.json"
    spec = json.loads(path.read_text())
    spec["per_layer"].append(dict(added, workloads=["als-tiny.serve-c4"]))
    path.write_text(json.dumps(spec))
    code = run.main(["--bench-root", str(tmp_path / "tiny"), "--rehearse-cpu",
                     "--workload", "als-tiny.serve-c4", "--seed",
                     "5000000028", "--seconds", "1", "--trace", "1"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert code == 0 and line["correct"] is True
    assert line["metrics"][METRIC] == {"value": 0.0, "unit": "%"}
