"""``index_enqueue_p90_ms.serve`` (ISSUE 31): the 90th percentile of the
``pio:index.enqueue`` spans whose median is ``index_enqueue_ms.serve``,
against hand-built events, where there is nothing to read, and against the
trace recorded on the chip at PR 25."""

import json
import os

import pytest

from tests.benchmarks import repo_spec
from tests.benchmarks.test_index_enqueue_ms import ENTRY as MEDIAN_ENTRY
from tests.benchmarks.test_index_enqueue_ms import search
from tests.benchmarks.test_program_spans import (BENCHMARKS, FIXTURE, SCOPES,
                                                 load_file, make_trace, read)

METRIC = "index_enqueue_p90_ms.serve"
MEDIAN = MEDIAN_ENTRY["name"]


@pytest.fixture(scope="module")
def ps():
    return load_file(os.path.join(BENCHMARKS, "program_spans.py"))


@pytest.mark.parametrize("enqueues,p90", [
    ([0.5], 0.5),
    # nearest rank: the 9th of 10, the 18th of 20, the 10th of 11
    ([5.0] * 8 + [11.0, 30.0], 11.0),
    ([30.0, 11.0] + [5.0] * 8, 11.0),
    ([5.0] * 17 + [9.0, 12.0, 40.0], 9.0),
    ([1.0] * 9 + [6.0, 7.0], 6.0),
])
def test_the_p90_of_made_enqueue_spans_is_the_nearest_rank(ps, enqueues, p90):
    spans = []
    for n, ms in enumerate(enqueues):
        spans += search(100 * n, ms)
    made = make_trace(ps, spans)
    assert read(METRIC, made) == pytest.approx(p90)
    assert read(METRIC, made) >= read(MEDIAN, made)


def test_no_span_and_no_trace_are_nothing_to_read(ps):
    bare = make_trace(ps, [("pio:batch.dispatch", 0, 8, 1),
                           ("pio:index.search", 1, 7, 1)])
    assert read(METRIC, bare) is None
    assert read(METRIC, None) is None


def test_the_recorded_trace_gives_a_p90_at_or_above_its_median(ps):
    with open(SCOPES) as f:
        recorded = ps.load(FIXTURE, json.load(f))
    took = sorted(s.end - s.start
                  for s in ps.named(recorded, "pio:index.enqueue"))
    # seven lone dispatches and two batches: nine searches, and the nearest
    # rank of 0.9 x 9 is the ninth, the longest (3.397 ms on that chip)
    assert len(took) == 9
    value = read(METRIC, recorded)
    assert value == pytest.approx(took[-1] / 1e6)
    assert read(MEDIAN, recorded) == pytest.approx(took[4] / 1e6)
    assert took[4] / 1e6 < value
    assert value < read("lone_dispatch_ms.serve", recorded)


@pytest.mark.parametrize("case", repo_spec.CASES)
def test_benchmark_json_names_it_after_the_median_with_the_same_cells(case):
    spec = repo_spec.load(case)
    repo_spec.assert_names_the_reader(spec, dict(MEDIAN_ENTRY, name=METRIC))
    names = [m["name"] for m in spec["per_layer"]]
    assert names.index(MEDIAN) < names.index(METRIC)
