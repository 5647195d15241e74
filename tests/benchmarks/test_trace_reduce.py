"""``benchmarks/trace_reduce.py`` against hand-made events and against a
small trace recorded on the chip (fixtures/tpu_small.xplane.pb, made by
benchmarks/tools/record_trace_fixture.py on a TPU v5 lite)."""

import importlib.util
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
FIXTURE = os.path.join(HERE, "fixtures", "tpu_small.xplane.pb")


@pytest.fixture(scope="module")
def tr():
    spec = importlib.util.spec_from_file_location(
        "bench_trace_reduce_under_test",
        os.path.join(REPO, "benchmarks", "trace_reduce.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_union_counts_nested_and_overlapping_intervals_once(tr):
    assert tr.union([(0, 10), (2, 5), (8, 12), (20, 21), (21, 22)]) == [
        (0, 12), (20, 22)]
    assert tr.union([(5, 5), (3, 2)]) == []


def test_self_time_takes_children_out_of_their_parent(tr):
    events = [("while", 0.0, 100.0), ("fusion", 10.0, 40.0),
              ("copy", 50.0, 60.0), ("fusion", 60.0, 90.0),
              ("tail", 100.0, 110.0)]
    assert tr.self_times(events) == {"while": 30.0, "fusion": 60.0,
                                     "copy": 10.0, "tail": 10.0}


def test_busy_is_the_union_and_gaps_go_to_the_innermost_span(tr):
    ms = 1e6
    events = {
        "devices": {"/device:TPU:0": [
            ("while", 10 * ms, 30 * ms), ("fusion", 12 * ms, 20 * ms),
            ("fusion", 60 * ms, 70 * ms)]},
        "spans": [("bench:window", 0.0, 100 * ms),
                  ("bench:call", 5 * ms, 75 * ms),
                  ("bench:pause", 35 * ms, 55 * ms)],
    }
    got = tr.reduce_events(events)
    assert got["window_s"] == pytest.approx(0.100)
    assert got["busy_s"] == pytest.approx(0.030)      # not 0.038: nested
    assert dict(map(tuple, got["device_ops"])) == pytest.approx(
        {"fusion": 0.018, "while": 0.012})
    gaps = dict(map(tuple, got["idle_gaps"]))
    assert gaps == pytest.approx({
        "bench:pause": 0.030,                          # 30..60, middle 45
        "bench:call": 0.010,                           # 0..10, middle 5
        "outside the benchmark's spans": 0.030})       # 70..100
    assert sum(gaps.values()) + got["busy_s"] == pytest.approx(
        got["window_s"])
    assert tr.count_spans(events, "bench:call") == 1


def test_no_device_plane_reduces_to_nothing(tr):
    got = tr.reduce_events({"devices": {}, "spans": []})
    assert got["busy_s"] == 0.0 and got["device_ops"] == []


def test_the_recorded_chip_trace(tr):
    events = tr.load_events(FIXTURE)
    assert list(events["devices"]) == ["/device:TPU:0"]
    assert tr.count_spans(events, "bench:dispatch") == 3
    assert tr.count_spans(events, "bench:pause") == 3
    got = tr.reduce_events(events)
    ops = events["devices"]["/device:TPU:0"]
    summed = sum(e - s for _, s, e in ops) / 1e9
    # the trace holds a loop and the ops inside it: the sum of durations
    # counts them twice, the union does not
    assert 0 < got["busy_s"] < summed
    assert got["busy_s"] < got["window_s"]
    idle = dict(map(tuple, got["idle_gaps"]))
    assert idle.get("bench:pause", 0) >= 3 * 0.005 * 0.9
    assert sum(idle.values()) + got["busy_s"] == pytest.approx(
        got["window_s"], rel=1e-6)
    assert sum(s for _, s in got["device_ops"]) <= got["busy_s"] * 1.0001
    # pinned, so that a change to the reduction shows
    assert got["busy_s"] == pytest.approx(PINNED["busy_s"], rel=1e-9)
    assert got["window_s"] == pytest.approx(PINNED["window_s"], rel=1e-9)
    assert got["device_ops"][0][0].startswith(PINNED["top_op"] + " = ")


PINNED = {"busy_s": 0.000208517, "window_s": 0.01946901, "top_op": "%fusion.8"}


def test_peaks_are_keyed_by_device_kind_and_an_unknown_kind_is_an_error():
    spec = importlib.util.spec_from_file_location(
        "bench_peaks_under_test", os.path.join(REPO, "benchmarks", "peaks.py"))
    peaks = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(peaks)
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9 and "source" in v5e
    with pytest.raises(KeyError, match="TPU v9"):
        peaks.peaks_for("TPU v9")
