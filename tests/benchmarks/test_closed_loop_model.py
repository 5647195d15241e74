"""``benchmarks/tools/closed_loop_model.py`` on ``traffic/lifelong32k-c4.json``
(ISSUE 54): what a window of a closed-loop cell COUNTS decides which request
its 95th percentile is, so a faster extension moves that tail either way,
while the first queries' own median follows the work. A model on the CPU,
standard library only: it pins the order of work, never a device number.

The three numbers pinned over a sweep of the extension batch's cost, 17 to
7 ms in steps of 2, everything else fixed: ``query_p50_ms`` falls at EVERY
step; ``query_p95_ms`` over all requests RISES by more than its bound of 9%
at least once from one step to the next; ``first_query_p50_ms`` moves by at
most 3% between two steps."""

import json
import os

import pytest

from tests.benchmarks.test_program_spans import BENCHMARKS, load_file

#: a chunk at offset 0 and at the longest history, host time a step (ms):
#: the three of a grid that fit the chip's runs best (a window of 191-194
#: requests in 454 or 469 steps, p50 49 ms, first queries' median 2.8 s, the
#: slowest 6.8 s: PERF.md section 6, PR 54). Costs under which a window
#: holds more (20, 95, 6: 214 requests at 9 ms) meet the last test's step
COSTS = [(28.0, 80.0, 5.0), (24.0, 80.0, 8.0), (26.0, 85.0, 5.0)]
SWEEP = [17.0, 15.0, 13.0, 11.0, 9.0, 7.0]


@pytest.fixture(scope="module")
def model():
    return load_file(os.path.join(BENCHMARKS, "tools",
                                  "closed_loop_model.py"))


@pytest.fixture(scope="module")
def mix():
    with open(os.path.join(BENCHMARKS, "traffic",
                           "lifelong32k-c4.json")) as f:
        return json.load(f)


def steps_of(rows, name):
    return [(after[name] - before[name]) / before[name]
            for before, after in zip(rows, rows[1:])]


@pytest.mark.parametrize("chunk0, chunk_far, host", COSTS)
def test_a_faster_extension_moves_the_tail_over_all_and_not_the_first_queries(
        model, mix, chunk0, chunk_far, host):
    rows = model.sweep(mix, SWEEP, chunk0_ms=chunk0, chunk_far_ms=chunk_far,
                       host_ms=host, seconds=20.0)
    assert all(step < 0 for step in steps_of(rows, "query_p50_ms"))
    assert max(steps_of(rows, "query_p95_ms")) > 0.09
    assert max(abs(s) for s in steps_of(rows, "first_query_p50_ms")) <= 0.03
    # the window counts more as its extensions get faster, and its first
    # queries are about one request in nine
    counts = [r["answered"] for r in rows]
    assert counts == sorted(counts) and counts[0] < counts[-1]
    assert all(8 < r["answered"] / r["first_queries"] < 10 for r in rows)


def test_the_model_at_the_chips_costs_counts_what_the_chip_counted(model,
                                                                   mix):
    """The costs that fit: a window of the parent's program answered 191 |
    194 requests in 454 | 469 steps, 22 | 23 of them first queries, and
    seconds 8 to 11 after GO held no extension (the three ``null``
    readings since PR 50)."""
    run = model.simulate(mix, 28.0, 80.0, 13.0, 5.0, 20.0)
    said = model.summary(run, 20.0)
    assert (said["answered"], said["first_queries"], len(run["steps"])) == (
        192, 22, 454)
    assert 48 < said["query_p50_ms"] < 51
    assert 2700 < said["first_query_p50_ms"] < 2950
    by_second = model.by_second(run, 20.0)
    assert [e for s, e, _ in by_second if 8 <= s < 11] == [0, 0, 0]
    # every step runs a chunk: the FIFO of first queries is never empty
    assert all(offset is not None for _, _, _, offset in run["steps"])
    # the stretch the traffic file names holds extensions and chunks
    a = mix["trace_after_go_s"]
    b = a + mix["trace_seconds"]
    inside = [s for s in run["steps"] if a <= s[0] and s[1] <= b]
    assert sum(1 for s in inside if s[2]) >= 10
    assert sum(1 for s in inside if s[3] is not None) >= 20


def test_a_25th_first_query_moves_the_median_by_its_own_step(model, mix):
    """What the metric cannot do: at 214 requests a window holds its 25th
    first query (no window of 20 s holds more: the count stands from there),
    the median's rank goes from the 12th to the 13th, and a gap lies
    there. A window of 21.3 s read the same on the chip (PERF.md section
    7)."""
    rows = model.sweep(mix, [7.0, 5.0, 3.0], chunk0_ms=28.0,
                       chunk_far_ms=80.0, host_ms=5.0, seconds=20.0)
    assert [r["first_queries"] for r in rows] == [24, 25, 25]
    assert [r["answered"] for r in rows] == [211, 214, 214]
    first = [r["first_query_p50_ms"] for r in rows]
    assert 0.10 < first[1] / first[0] - 1 < 0.15
    assert first[2] < first[1]


def test_the_timeline_tool_finds_the_starts_the_model_made(model, mix):
    """``tools/window_timeline.py`` rebuilds every request's start from what
    the load generator says at the window's end (latencies by connection, in
    order; first query or not; the history carried): on the model's own
    window it finds the starts the model made."""
    timeline = load_file(os.path.join(BENCHMARKS, "tools",
                                      "window_timeline.py"))
    orders = model.session_orders(mix)
    run = model.simulate(mix, 28.0, 80.0, 13.0, 5.0, 20.0)
    # the load generator's lists: by connection, each in the order sent
    offsets = model.wave_offsets(mix)
    assert offsets == pytest.approx([0.0, 0.05, 0.10, 0.15])
    load = {"latencies_s": [], "first_query": [], "history_lengths": []}
    made = []
    for c in range(4):
        t, session = offsets[c], int(mix["warmup_sessions_per_connection"])
        mine = sorted(r[:3] for r in run["requests"] if r[3] == c)
        for start, latency, first in mine:
            assert start == pytest.approx(t)
            session += 1 if first else 0
            load["latencies_s"].append(latency)
            load["first_query"].append(first)
            load["history_lengths"].append(
                orders[c][(session - 1) % 32] + (0 if first else 2))
            made.append((start, latency, first))
            t = start + latency
    found = timeline.starts(load, mix, orders)
    assert [(round(t, 6), round(d, 6), f) for t, d, f, _ in found] == [
        (round(t, 6), round(d, 6), f) for t, d, f in sorted(made)]
    said = timeline.report(found, 20.0, 3.0)
    assert sum(1 for l in said if l.startswith("first query: ")) == 22
    assert said[-2].startswith("extensions started by the second after GO: "
                               "0:0 1:")
    assert " 8:0/" in said[-1] and said[-1].count("/") == 36

