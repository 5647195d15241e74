"""``benchmarks/program_spans.py``, ``kernel_counts.py`` and the per-layer
readers ISSUE 25 adds, against hand-built events and against a small trace
recorded on the chip (fixtures/pio_small.xplane.pb with the program's scope
maps beside it, made by benchmarks/tools/record_program_trace_fixture.py on a
TPU v5 lite: seven queries dispatched alone, two batches of two, two epochs
of four steps)."""

import importlib.util
import json
import os
import shutil
import sys
import types

import pytest

from tests.benchmarks import repo_spec

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
BENCHMARKS = os.path.join(REPO, "benchmarks")
FIXTURE = os.path.join(HERE, "fixtures", "pio_small.xplane.pb")
SCOPES = os.path.join(HERE, "fixtures", "pio_small.scopes.json")
MS = 1e6

NEW_METRICS = {
    "front_self_ms.serve", "worker_turnaround_ms.serve",
    "lone_dispatch_ms.serve", "batched_dispatch_ms.serve",
    "dispatch_host_ms.serve", "topk_dot_roofline_pct.serve",
    "flash_ce_roofline_pct.train", "adagrad_scatter_ms_per_step.train"}


def load_file(path):
    name = "_under_test_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path, REPO))
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        module = importlib.util.module_from_spec(spec)
        sys.modules[name] = module
        spec.loader.exec_module(module)
    return sys.modules[name]


@pytest.fixture(scope="module")
def ps():
    return load_file(os.path.join(BENCHMARKS, "program_spans.py"))


@pytest.fixture(scope="module")
def counts():
    return load_file(os.path.join(BENCHMARKS, "kernel_counts.py"))


@pytest.fixture(scope="module")
def recorded(ps):
    with open(SCOPES) as f:
        return ps.load(FIXTURE, json.load(f))


class FakeBench:
    """What a reader uses of run.py's Bench."""

    devices = [types.SimpleNamespace(device_kind="TPU v5 lite")]

    def __init__(self, config, scratch="/nonexistent"):
        self.config, self.scratch = config, scratch

    def lib(self, name):
        return load_file(os.path.join(BENCHMARKS, name + ".py"))


def read(metric, trace, config=None, **ctx):
    reader = load_file(os.path.join(BENCHMARKS, "layer_metrics",
                                    metric + ".py"))
    return reader.read({"bench": FakeBench(config or {}),
                        "_program_spans": trace, **ctx})


def config_of(name):
    with open(os.path.join(BENCHMARKS, "configs", name + ".json")) as f:
        return json.load(f)


def make_trace(ps, spans, ops=(), window=None):
    """A Trace of hand-built (name, start ms, end ms, line[, attrs]) spans
    and (instr, start ms, end ms[, scope]) operations."""
    flat = [(s[0], s[1] * MS, s[2] * MS, s[3], s[4] if len(s) > 4 else {})
            for s in spans]
    device = [ps.Op(o[0], o[1] * MS, o[2] * MS, "jit_f",
                    o[3] if len(o) > 3 else None) for o in ops]
    if window is None:
        window = (0.0, max(f[2] for f in flat))
    else:
        window = (window[0] * MS, window[1] * MS)
    return ps.Trace(ps.nest(flat), {"/device:TPU:0": device} if device
                    else {}, window)


# -- hand-built events -------------------------------------------------------

def test_spans_nest_per_thread_and_self_time_leaves_children_out(ps):
    trace = make_trace(ps, [
        ("pio:http.request", 0, 100, 1), ("pio:http.parse", 2, 5, 1),
        ("pio:serve.wait", 10, 90, 1), ("pio:http.respond", 92, 99, 1),
        # another thread at the same time is nobody's child here
        ("pio:batch.dispatch", 12, 88, 2), ("pio:index.search", 20, 80, 2),
        ("pio:index.fetch", 30, 80, 2)])
    by_name = {s.name: s for s in trace.spans}
    names = [s.name for s in trace.spans]

    def parent(name):
        at = by_name[name].parent
        return None if at is None else trace.spans[at].name

    assert parent("pio:http.request") is None
    assert parent("pio:serve.wait") == "pio:http.request"
    assert parent("pio:batch.dispatch") is None
    assert parent("pio:index.fetch") == "pio:index.search"
    request = names.index("pio:http.request")
    assert [c.name for c in ps.children(trace, request)] == [
        "pio:http.parse", "pio:serve.wait", "pio:http.respond"]
    assert ps.self_ns(trace, request) == pytest.approx(10 * MS)
    assert ps.self_ns(trace, names.index("pio:index.search")) == \
        pytest.approx(10 * MS)
    assert ps.less_children(trace, "pio:http.request",
                            "pio:serve.wait") == [pytest.approx(20 * MS)]
    assert read("front_self_ms.serve", trace) == pytest.approx(20.0)
    # a request that never waited for the batcher (GET /) is not a query
    assert ps.less_children(trace, "pio:batch.dispatch", "pio:serve.wait") \
        == []


def test_dispatches_are_read_per_path_and_a_deviceless_one_counts_in_full(ps):
    trace = make_trace(ps, [
        ("pio:batch.dispatch", 0, 90, 1, {"path": "lone", "seq": 1}),
        ("pio:batch.dispatch", 91, 136, 1, {"path": "batched", "seq": 2}),
        ("pio:batch.dispatch", 137, 139, 1, {"path": "lone", "seq": 3}),
        ("pio:batch.dispatch", 140, 230, 1, {"path": "lone", "seq": 4}),
    ], ops=[
        # a loop and what runs inside it: busy once
        ("while.1", 2, 82), ("topk_dot.1", 4, 80),
        ("fusion.5", 93, 133),
        # the third dispatch answered from the host: no device event
        ("topk_dot.1", 150, 226), ("copy.2", 226, 229.5),
        # seems to end after its span: the clocks differ
        ("copy.3", 229.6, 230.4)], window=(0, 231))
    assert read("lone_dispatch_ms.serve", trace) == pytest.approx(90.0)
    assert read("batched_dispatch_ms.serve", trace) == pytest.approx(45.0)
    dispatches = ps.named(trace, "pio:batch.dispatch")
    assert [len(own) for own in ps.ops_of_spans(trace, dispatches)] == [
        2, 1, 0, 3]
    assert ps.host_ns_per_span(trace, dispatches) == pytest.approx(
        [10 * MS, 5 * MS, 2 * MS, (90 - 76 - 3.5 - 0.8) * MS])
    assert read("dispatch_host_ms.serve", trace) == pytest.approx(
        (5 + 9.7) / 2)
    # no device plane at all (a CPU rehearsal): no host share to speak of
    no_device = make_trace(ps, [
        ("pio:batch.dispatch", 0, 90, 1, {"path": "lone"})])
    assert read("dispatch_host_ms.serve", no_device) is None
    assert read("batched_dispatch_ms.serve", no_device) is None
    assert read("lone_dispatch_ms.serve", no_device) == pytest.approx(90.0)


def test_turnaround_counts_only_while_a_request_waited(ps):
    spans = [
        ("pio:batch.dispatch", 0, 90, 1), ("pio:batch.dispatch", 91.5, 136, 1),
        ("pio:batch.dispatch", 300, 390, 1),
        # waited through the first dispatch and the second: the first pair
        ("pio:serve.wait", 5, 137, 2),
        # a member of the first dispatch, woken right after it
        ("pio:serve.wait", -1, 90.4, 3),
        # nobody waited between the second and the third
        ("pio:serve.wait", 299, 391, 4)]
    trace = make_trace(ps, spans, window=(-2, 400))
    assert read("worker_turnaround_ms.serve", trace) == pytest.approx(1.5)
    alone = make_trace(ps, [s for s in spans if s[3] != 2],
                       window=(-2, 400))
    assert read("worker_turnaround_ms.serve", alone) is None


def test_idle_goes_to_the_driving_thread_first_and_to_a_wait_last(ps):
    trace = make_trace(ps, [
        ("pio:http.request", 0, 100, 1), ("pio:http.parse", 1, 3, 1),
        ("pio:serve.wait", 4, 96, 1), ("pio:http.respond", 96, 99, 1),
        ("pio:batch.collect", 5, 6, 2), ("pio:batch.dispatch", 6, 94, 2),
        ("pio:index.enqueue", 7, 10, 2), ("pio:index.fetch", 10, 93, 2),
        ("pio:batch.deliver", 94, 95, 2),
    ], ops=[("copy", 9, 20), ("topk_dot.1", 20, 92)], window=(0, 110))
    idle = ps.idle_by_span(trace)
    assert idle == pytest.approx({
        "pio:http.request": 0.003,        # 0-1, 3-4, 99-100: its self time
        "pio:http.parse": 0.002,
        "pio:batch.collect": 0.001,
        "pio:batch.dispatch": 0.002,      # 6-7 and 93-94
        "pio:index.enqueue": 0.002,       # 7-9, then the device is busy
        "pio:index.fetch": 0.001,         # 92-93: the copy back
        "pio:batch.deliver": 0.001,
        "pio:http.respond": 0.003,
        # 4-5 and 95-96: one thread handing over to the other
        "pio:serve.wait": 0.002,
        # 100-110: no span, at the stretch's end
        ps.UNRECORDED_TAIL: 0.010})
    assert sum(idle.values()) == pytest.approx(0.110 - 0.083)
    lines = ps.report_lines(trace)
    assert lines[0].startswith("program spans: 9 pio: spans, 2 device")
    assert any(line.startswith("idle by pio: span: pio:index.enqueue "
                               "0.002000 s") for line in lines)


def test_a_roofline_share_above_100_is_not_a_reading(ps, counts):
    assert counts.roofline_pct(1.0, 4.0) == pytest.approx(25.0)
    assert counts.roofline_pct(1.0, 0.5) is None
    assert counts.roofline_pct(1.0, 0.0) is None
    cfg = config_of("als-amazon14")
    spans = [("pio:batch.dispatch", 0, 100, 1, {"path": "lone"})]
    fast = make_trace(ps, spans, ops=[("topk_dot.1", 1, 3)])
    assert read("topk_dot_roofline_pct.serve", fast, cfg) is None
    real = make_trace(ps, spans, ops=[("copy", 1, 12),
                                      ("topk_dot.1", 12, 86)])
    assert read("topk_dot_roofline_pct.serve", real, cfg) == pytest.approx(
        100 * 2.4064e9 / 819e9 / 0.074)
    # another kernel's events are not this one's
    other = make_trace(ps, spans, ops=[("fusion.5", 1, 12)])
    assert read("topk_dot_roofline_pct.serve", other, cfg) is None


# -- the counts, against hand arithmetic -------------------------------------

def test_kernel_counts_at_both_configurations_sizes(counts):
    peaks = load_file(os.path.join(BENCHMARKS, "peaks.py")).peaks_for(
        "TPU v5 lite")
    als = config_of("als-amazon14")
    assert counts.topk_dot_bytes(als) == 9_400_000 * 64 * 4 == 2_406_400_000
    assert counts.topk_dot_flops(als) == 2 * 9_400_000 * 64
    # memory-bound: 2.4064 GB at 819 GB/s, against 1.2 GFLOP at 197 TFLOP/s
    assert counts.least_seconds(
        peaks, flops=counts.topk_dot_flops(als),
        nbytes=counts.topk_dot_bytes(als)) == pytest.approx(2.938217e-3)
    tower = config_of("twotower-userbehavior")
    assert counts.flash_ce_flops_per_step(tower) == 6 * 8192 * 8192 * 128 \
        == 51_539_607_552
    assert counts.least_seconds(
        peaks, flops=counts.flash_ce_flops_per_step(tower)
    ) == pytest.approx(2.616224e-4)


# -- the recorded chip trace --------------------------------------------------

def test_the_fixture_is_small_enough_to_commit():
    assert os.path.getsize(FIXTURE) <= 256 * 1024


def test_recorded_spans_and_named_operations(ps, recorded):
    names = {s.name for s in recorded.spans}
    assert {"pio:http.request", "pio:http.parse", "pio:serve.admit",
            "pio:serve.wait", "pio:http.respond", "pio:batch.collect",
            "pio:batch.dispatch", "pio:batch.deliver", "pio:engine.prepare",
            "pio:index.search", "pio:index.enqueue", "pio:index.fetch",
            "pio:engine.decode", "pio:train.epoch",
            "pio:train.report"} <= names
    dispatches = ps.named(recorded, "pio:batch.dispatch")
    assert sorted(s.attrs["size"] for s in dispatches) == [1] * 7 + [2, 2]
    assert len(ps.named(recorded, "pio:batch.dispatch", path="lone")) == 7
    assert sum(s.attrs["size"] for s in dispatches) == len(
        ps.named(recorded, "pio:http.request")) == 11
    # the kernels carry their pallas_call's name, whatever XLA numbers them
    assert len(ps.ops_named(recorded, "topk_dot")) == 7
    assert {o.instr.rsplit(".", 1)[0]
            for o in ps.ops_named(recorded, "flash_ce")} == {
        "flash_ce_fwd", "flash_ce_bwd_du", "flash_ce_bwd_dv"}
    assert len(ps.ops_named(recorded, "flash_ce")) == 3 * 8
    # XLA's own fusions are told apart by the program's scope map alone
    scatters = ps.ops_in_scope(recorded, ("twotower.adagrad_user",
                                          "twotower.adagrad_item"))
    assert scatters and all(o.module == "jit_epoch" for o in scatters)
    assert not any(o.instr.startswith(("topk_dot", "flash_ce"))
                   for o in scatters)
    # every lone dispatch found its own kernel call, by order
    lone = sorted(ps.named(recorded, "pio:batch.dispatch", path="lone"),
                  key=lambda s: s.start)
    for own in ps.ops_of_spans(recorded, dispatches):
        assert own
    for own in ps.ops_of_spans(recorded, lone):
        assert sum(o.instr.startswith("topk_dot") for o in own) == 1
    # this chip's clock ran about two milliseconds behind its host's: the
    # second query's kernel seems to start before its search span does
    low, high = recorded.clock
    assert -2.5 * MS < low <= high < -1.5 * MS
    uncorrected = tr_ops(ps, FIXTURE)
    searches = sorted(ps.named(recorded, "pio:index.search"),
                      key=lambda s: s.start)
    assert min(o.start for o in uncorrected
               if o.instr.startswith("topk_dot")
               and o.start > searches[0].end) < searches[1].start
    for span, own in zip(searches, ps.ops_of_spans(recorded, searches)):
        assert all(span.start <= o.start and o.end <= span.end
                   for o in own)


def tr_ops(ps, path):
    """The trace's device operations on the device's own clock."""
    tr = load_file(os.path.join(BENCHMARKS, "trace_reduce.py"))
    return [ps.Op(ps.instruction(n), s, e, "", None) for n, s, e in
            tr.load_events(path)["devices"]["/device:TPU:0"]]


def test_the_clocks_offset_is_what_puts_every_program_inside_a_span(ps):
    # three dispatches; the device's clock 1.5 ms behind the host's: each
    # program starts 0.4 ms after its span and ends 0.6 ms before it
    launching = [(k * 10 * MS, (k * 10 + 6) * MS) for k in range(3)]
    execs = [(s + (0.4 - 1.5) * MS, e - (0.6 + 1.5) * MS)
             for s, e in launching]
    low, high = ps.clock_offset(execs, launching)
    # as far back as the copy back allows, as far forward as the launch
    assert low == pytest.approx((-1.5 - 0.6) * MS, abs=0.03 * MS)
    assert high == pytest.approx((-1.5 + 0.4) * MS, abs=0.03 * MS)
    assert ps.clock_offset([], launching) == (0.0, 0.0)
    assert ps.clock_offset(execs, []) == (0.0, 0.0)


def test_recorded_idle_seconds_add_up_and_have_names(ps, recorded):
    idle = ps.idle_by_span(recorded)
    ops = ps.all_ops(recorded)
    tr = load_file(os.path.join(BENCHMARKS, "trace_reduce.py"))
    t0, t1 = recorded.window
    busy = sum(e - s for s, e in tr.union(
        [(max(o.start, t0), min(o.end, t1)) for o in ops]))
    assert sum(idle.values()) == pytest.approx(((t1 - t0) - busy) / 1e9)
    assert idle["pio:index.enqueue"] > 0 and idle["pio:index.fetch"] > 0
    # and trace_reduce reads the same stretch and (the clocks' two
    # milliseconds at the stretch's edges apart) the same busy time
    reduced = tr.reduce_events(tr.load_events(FIXTURE))
    assert reduced["window_s"] == pytest.approx((t1 - t0) / 1e9)
    assert reduced["busy_s"] == pytest.approx(busy / 1e9, abs=1e-4)


def test_every_new_reader_reads_the_recorded_trace(ps, recorded):
    als = dict(config_of("als-amazon14"), n_items=30000, rank=64)
    tower = dict(config_of("twotower-userbehavior"), batch_size=1024,
                 dim=128)
    got = {m: read(m, recorded, als) for m in NEW_METRICS
           if m.endswith(".serve")}
    got.update({m: read(m, recorded, tower, traced={"steps": 8})
                for m in NEW_METRICS if m.endswith(".train")})
    # three connections of three: nobody waited across two dispatches
    # for long enough in so short a stretch? then there is nothing to read
    turnaround = got.pop("worker_turnaround_ms.serve")
    assert turnaround is None or 0 < turnaround < 50
    assert all(v is not None and v > 0 for v in got.values()), got
    assert got["lone_dispatch_ms.serve"] == pytest.approx(4.205, abs=1e-3)
    assert got["batched_dispatch_ms.serve"] == pytest.approx(
        (3.619 + 4.569) / 2, abs=1e-3)
    assert 0 < got["dispatch_host_ms.serve"] < got["lone_dispatch_ms.serve"]
    assert got["topk_dot_roofline_pct.serve"] < 100
    assert got["flash_ce_roofline_pct.train"] < 100
    per_step = got["adagrad_scatter_ms_per_step.train"]
    assert per_step * 8 == pytest.approx(ps.self_ns_of_ops(
        recorded, lambda o: o.scope in ("twotower.adagrad_user",
                                        "twotower.adagrad_item")) / 1e6)
    # without the program's scope map (the parent commit keeps none) the
    # scatter reader finds nothing, the kernels are still found by name
    bare = ps.load(FIXTURE, {})
    assert read("adagrad_scatter_ms_per_step.train", bare, tower,
                traced={"steps": 8}) is None
    assert read("flash_ce_roofline_pct.train", bare, tower,
                traced={"steps": 8}) == got["flash_ce_roofline_pct.train"]


# -- where there is nothing to read -------------------------------------------

@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_a_reader_returns_none_where_the_program_has_no_spans(
        ps, metric, tmp_path, capsys):
    # no trace directory at all
    assert read(metric, None, traced={"steps": 8}) is None
    # a trace with the benchmark's spans only, as the parent commit gives
    trace_dir = tmp_path / "trace" / "plugins" / "profile" / "x"
    trace_dir.mkdir(parents=True)
    shutil.copy(os.path.join(HERE, "fixtures", "tpu_small.xplane.pb"),
                trace_dir / "old.xplane.pb")
    reader = load_file(os.path.join(BENCHMARKS, "layer_metrics",
                                    metric + ".py"))
    ctx = {"bench": FakeBench({}, scratch=str(tmp_path)),
           "traced": {"steps": 8}}
    assert reader.read(ctx) is None
    assert ctx["_program_spans"] is None
    assert "idle by pio: span" not in capsys.readouterr().out


@pytest.mark.parametrize("case", repo_spec.CASES)
def test_benchmark_json_names_each_new_reader_with_its_cells(case):
    spec = repo_spec.load(case)
    by_name = {m["name"]: m for m in spec["per_layer"]}
    assert NEW_METRICS <= set(by_name)
    cells = {w["name"] for w in spec["workloads"]}
    for name in NEW_METRICS:
        entry = by_name[name]
        assert os.path.isfile(os.path.join(BENCHMARKS, "layer_metrics",
                                           name + ".py"))
        assert set(entry["workloads"]) <= cells
        assert all((".train" in w) == name.endswith(".train")
                   for w in entry["workloads"])
    assert by_name["batched_dispatch_ms.serve"]["workloads"] == [
        "als-amazon14.serve-c32"]
    assert by_name["worker_turnaround_ms.serve"]["moves"] == "query_rate"


# -- through run.py, on the CPU ------------------------------------------------

def test_a_traced_tiny_cell_prints_the_span_metrics_and_the_idle_lines(
        tmp_path, capsys):
    """The tiny serving cell of tests/benchmarks/tiny with this PR's metrics
    appended to its BENCHMARK.json: run.py finds the readers by name, and on
    the CPU (no device plane) the span readers still read."""
    run = load_file(os.path.join(BENCHMARKS, "run.py"))
    shutil.copytree(os.path.join(HERE, "tiny"), tmp_path / "tiny")
    added = [m for m in repo_spec.load()["per_layer"]
             if m["name"] in NEW_METRICS and m["name"].endswith(".serve")]
    path = tmp_path / "tiny" / "BENCHMARK.json"
    spec = json.loads(path.read_text())
    spec["per_layer"] += [dict(m, workloads=["als-tiny.serve-c4"])
                          for m in added]
    path.write_text(json.dumps(spec))
    code = run.main(["--bench-root", str(tmp_path / "tiny"), "--rehearse-cpu",
                     "--workload", "als-tiny.serve-c4", "--seed",
                     "5000000011", "--seconds", "1", "--trace", "1"])
    out = capsys.readouterr().out.strip().splitlines()
    line = json.loads(out[-1])
    assert code == 0 and line["correct"] is True
    assert {"front_self_ms.serve", "lone_dispatch_ms.serve",
            "batched_dispatch_ms.serve"} <= set(line["metrics"])
    # device readers find no device plane on the CPU and are left out
    assert "topk_dot_roofline_pct.serve" not in line["metrics"]
    assert "dispatch_host_ms.serve" not in line["metrics"]
    assert any(l.startswith("# idle by pio: span: ") for l in out)
