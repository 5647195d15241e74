"""``index_enqueue_ms.serve`` (ISSUE 30): the reader of the
``pio:index.enqueue`` spans, against hand-built events, against the trace
recorded on the chip at PR 25 (which holds that span), where there is no trace
to read, and through ``run.py`` on the CPU."""

import json
import os
import shutil

import pytest

from tests.benchmarks import repo_spec
from tests.benchmarks.test_program_spans import (BENCHMARKS, FIXTURE, HERE,
                                                 SCOPES, load_file,
                                                 make_trace, read)

METRIC = "index_enqueue_ms.serve"
ENTRY = {"name": METRIC, "unit": "ms", "better": "lower",
         "source": "program_span", "layer": "retrieval",
         "moves": "query_p50_ms",
         "workloads": ["als-amazon14.serve-c32", "als-amazon14.serve-c1"]}


@pytest.fixture(scope="module")
def ps():
    return load_file(os.path.join(BENCHMARKS, "program_spans.py"))


def search(start, enqueue_ms, line=1):
    """One search's spans: enqueue, the route marker, fetch."""
    return [("pio:index.search", start, start + enqueue_ms + 4, line),
            ("pio:index.enqueue", start, start + enqueue_ms, line),
            ("pio:index.route", start + enqueue_ms, start + enqueue_ms + .001,
             line, {"route": "kernel", "rows": 1, "inputs": "host"}),
            ("pio:index.fetch", start + enqueue_ms + .002,
             start + enqueue_ms + 4, line)]


@pytest.mark.parametrize("enqueues,median", [
    ([0.5], 0.5),
    ([0.4, 12.0, 0.6], 0.6),
    ([2.0, 4.0], 3.0),
    ([1.5, 11.0, 12.0, 13.0], 11.5),
])
def test_the_median_enqueue_span_in_ms(ps, enqueues, median):
    spans = []
    for n, ms in enumerate(enqueues):
        spans += search(100 * n, ms)
    assert read(METRIC, make_trace(ps, spans)) == pytest.approx(median)


def test_no_span_and_no_trace_are_nothing_to_read(ps):
    bare = make_trace(ps, [("pio:batch.dispatch", 0, 8, 1),
                           ("pio:index.search", 1, 7, 1)])
    assert read(METRIC, bare) is None
    assert read(METRIC, None) is None


def test_the_recorded_trace_gives_a_number(ps):
    with open(SCOPES) as f:
        recorded = ps.load(FIXTURE, json.load(f))
    spans = ps.named(recorded, "pio:index.enqueue")
    assert spans
    value = read(METRIC, recorded)
    assert value == pytest.approx(ps.median_ms(
        [s.end - s.start for s in spans]))
    # inside its search, and shorter than the dispatch around both
    assert 0 < value < read("lone_dispatch_ms.serve", recorded)


@pytest.mark.parametrize("case", repo_spec.CASES)
def test_benchmark_json_names_the_reader_and_its_two_cells(case):
    """``ENTRY`` is in ``BENCHMARK.json`` since PR 31, appended at the END of
    ``per_layer`` as it stood then, and is found there by its name, in a
    layer the file knows beside it. Entries that later PRs append after it
    are none of its business."""
    spec = repo_spec.load(case)
    repo_spec.assert_names_the_reader(spec, ENTRY)
    assert ENTRY["layer"] in [m["layer"] for m in spec["per_layer"]
                              if m["name"] != METRIC]


def test_a_traced_tiny_cell_reads_its_enqueue_spans_on_the_cpu(
        tmp_path, capsys):
    """Through run.py: the tiny ALS cell with this metric appended. The
    index's fallback answers on the CPU and opens the same span."""
    run = load_file(os.path.join(BENCHMARKS, "run.py"))
    shutil.copytree(os.path.join(HERE, "tiny"), tmp_path / "tiny")
    path = tmp_path / "tiny" / "BENCHMARK.json"
    spec = json.loads(path.read_text())
    spec["per_layer"].append(dict(ENTRY, workloads=["als-tiny.serve-c4"]))
    path.write_text(json.dumps(spec))
    code = run.main(["--bench-root", str(tmp_path / "tiny"), "--rehearse-cpu",
                     "--workload", "als-tiny.serve-c4", "--seed",
                     "5000000030", "--seconds", "1", "--trace", "1"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert code == 0 and line["correct"] is True
    assert line["metrics"][METRIC]["unit"] == "ms"
    assert line["metrics"][METRIC]["value"] > 0
