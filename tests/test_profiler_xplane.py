"""obs/profiler.parse_xplane and its per-step report, on hand-built events
and on the small trace recorded on the chip (tests/benchmarks/fixtures/
pio_small.xplane.pb: a deployed ALS engine and a two-tower trainer under the
program's own ``pio:`` spans). The operator's report and the benchmark's
reduction (benchmarks/trace_reduce.py, program_spans.py) must agree on it."""

import importlib.util
import json
import os
import shutil
import sys

import pytest

from predictionio_tpu.obs import jaxmon, profiler

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "benchmarks", "fixtures")


def bench_file(name):
    modname = "_profiler_test_" + name
    if modname not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            modname, os.path.join(REPO, "benchmarks", name + ".py"))
        module = importlib.util.module_from_spec(spec)
        sys.modules[modname] = module
        spec.loader.exec_module(module)
    return sys.modules[modname]


@pytest.fixture()
def capture_dir(tmp_path):
    """A capture as ``pio train`` with PIO_PROFILE_DIR leaves it: the trace
    under plugins/profile/<time>/, the scope maps beside it."""
    where = tmp_path / "plugins" / "profile" / "2026_09_27"
    where.mkdir(parents=True)
    shutil.copy(os.path.join(FIXTURES, "pio_small.xplane.pb"),
                where / "host.xplane.pb")
    shutil.copy(os.path.join(FIXTURES, "pio_small.scopes.json"),
                tmp_path / profiler.SCOPE_MAPS_FILE)
    return str(tmp_path)


def test_nested_events_count_once():
    events = [("while", 0.0, 100.0), ("fusion", 10.0, 40.0),
              ("copy", 50.0, 60.0), ("fusion", 60.0, 90.0),
              ("tail", 100.0, 110.0)]
    assert profiler._union([(s, e) for _, s, e in events]) == [[0.0, 110.0]]
    totals = {}
    for key, ns in profiler._self_times(events):
        totals[key] = totals.get(key, 0.0) + ns
    assert totals == {"while": 30.0, "fusion": 60.0, "copy": 10.0,
                      "tail": 10.0}
    assert sum(totals.values()) == 110.0          # not the 180 a sum gives


def test_an_operation_is_grouped_by_scope_and_kernel_name():
    assert profiler.group_of("topk_dot.1", None) == "topk_dot"
    assert profiler.group_of("fusion.108", "twotower.adagrad_user") == \
        "twotower.adagrad_user/fusion"
    assert profiler.group_of("flash_ce_bwd_du.6", "twotower.flash_ce") == \
        "twotower.flash_ce/flash_ce_bwd_du"
    assert profiler.group_of("while", None) == "while"
    assert profiler.group_of("copy-start.2", None) == "copy-start"


def test_idle_goes_to_the_innermost_span_of_the_driving_thread():
    worker = [("pio:batch.dispatch", 6.0, 94.0),
              ("pio:index.enqueue", 7.0, 10.0),
              ("pio:index.fetch", 10.0, 93.0)]
    handler = [("pio:http.request", 0.0, 100.0),
               ("pio:serve.wait", 4.0, 96.0),
               ("pio:http.respond", 96.0, 99.0)]
    got = profiler._idle_by_span([(0.0, 9.0), (92.0, 100.0)],
                                 [handler, worker])
    assert {k: round(v * 1e9, 6) for k, v in got.items()} == {
        "pio:batch.dispatch": 2.0, "pio:index.enqueue": 2.0,
        "pio:index.fetch": 1.0, "pio:http.request": 5.0,
        "pio:http.respond": 3.0, "pio:serve.wait": 4.0}


def test_the_recorded_trace_busy_is_a_union_and_groups_have_names(
        capture_dir):
    parsed = profiler.parse_xplane(capture_dir)
    assert "error" not in parsed, parsed
    tr = bench_file("trace_reduce")
    ops = tr.load_events(os.path.join(FIXTURES, "pio_small.xplane.pb"))[
        "devices"]["/device:TPU:0"]
    summed = sum(e - s for _, s, e in ops) / 1e9
    union = sum(e - s for s, e in tr.union(
        [(s, e) for _, s, e in ops])) / 1e9
    # the epoch program is a loop with its steps inside: a sum of
    # durations counts them twice, the report does not
    assert parsed["device_time_sec"] == pytest.approx(union, abs=1e-6)
    assert parsed["device_time_sec"] < 0.85 * summed
    assert parsed["window_sec"] == pytest.approx(
        parsed["device_time_sec"] + parsed["idle_sec"], abs=2e-6)
    groups = parsed["by_category"]
    assert sum(g["time_sec"] for g in groups.values()) <= \
        parsed["device_time_sec"] + 1e-6
    assert {"topk_dot", "twotower.flash_ce/flash_ce_fwd",
            "twotower.flash_ce/flash_ce_bwd_du",
            "twotower.flash_ce/flash_ce_bwd_dv",
            "twotower.adagrad_user/fusion",
            "twotower.adagrad_item/fusion"} <= set(groups)
    # the loop's own self time is what its body leaves, not its duration
    loop = sum(e - s for n, s, e in ops if n.startswith("%while")) / 1e9
    assert loop > 0 and groups.get("while", {"time_sec": 0.0})[
        "time_sec"] < 0.2 * loop


def test_the_operators_report_and_the_benchmarks_agree(capture_dir):
    parsed = profiler.parse_xplane(capture_dir)
    ps = bench_file("program_spans")
    with open(os.path.join(FIXTURES, "pio_small.scopes.json")) as f:
        scopes = json.load(f)
    trace = ps.load(os.path.join(FIXTURES, "pio_small.xplane.pb"), scopes)
    ops = ps.all_ops(trace)
    # the report's stretch: first operation to last
    trace = trace._replace(window=(min(o.start for o in ops),
                                   max(o.end for o in ops)))
    assert parsed["device_time_sec"] == pytest.approx(
        ps.busy_ns(ops) / 1e9, abs=1e-6)
    theirs = ps.idle_by_span(trace)
    assert set(parsed["idle_by_span"]) == set(theirs)
    for name, seconds in theirs.items():
        assert parsed["idle_by_span"][name] == pytest.approx(
            seconds, abs=2e-6), name
    scatter = ps.self_ns_of_ops(
        trace, lambda o: o.scope == "twotower.adagrad_user"
        and o.instr.startswith("fusion")) / 1e9
    assert parsed["by_category"]["twotower.adagrad_user/fusion"][
        "time_sec"] == pytest.approx(scatter, abs=1e-6)


def test_per_step_breakdown_and_the_documented_command(capture_dir, capsys):
    out = profiler.step_breakdown(capture_dir, steps=8)
    assert out["steps"] == 8
    assert out["device_ms_per_step"] == pytest.approx(
        out["trace"]["device_time_sec"] / 8 * 1e3, abs=1e-3)
    assert "twotower.adagrad_user/fusion" in out["by_category_ms_per_step"]
    assert profiler.main([capture_dir, "--steps", "8"]) == 0
    printed = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert printed["device_ms_per_step"] == out["device_ms_per_step"]
    assert profiler.main([capture_dir]) == 0
    assert "idle_by_span" in json.loads(capsys.readouterr().out)
    # a whole-train total never passes for a per-step number
    assert "error" in profiler.step_breakdown(capture_dir, steps=0)


def test_a_directory_without_a_trace_is_an_error_not_an_exception(tmp_path):
    assert profiler.parse_xplane(str(tmp_path)) == {
        "error": "no xplane trace found"}
    (tmp_path / "bad.xplane.pb").write_bytes(b"\xff\xff not a trace")
    assert "error" in profiler.parse_xplane(str(tmp_path))


def test_a_capture_leaves_the_scope_maps_beside_its_trace(
        tmp_path, monkeypatch):
    monkeypatch.setattr(jaxmon, "SCOPE_MAPS", {})
    profiler.save_scope_maps(str(tmp_path))
    assert not os.listdir(tmp_path)               # nothing to keep
    jaxmon.SCOPE_MAPS["jit_epoch"] = {"fusion.1": "twotower.adagrad_user"}
    profiler.save_scope_maps(str(tmp_path))
    with open(tmp_path / profiler.SCOPE_MAPS_FILE) as f:
        assert json.load(f) == {"jit_epoch": {
            "fusion.1": "twotower.adagrad_user"}}


def test_scope_map_reads_the_innermost_scope_of_each_instruction():
    hlo = '''HloModule jit_epoch, is_scheduled=true

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %add.3 = f32[8]{0} add(%p, %p), metadata={op_name="jit(epoch)/while/body/closed_call/twotower.step/twotower.adagrad_user/scatter-add" source_file="x.py" source_line=3}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0), metadata={op_name="args[0]"}
  %fusion.1 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(epoch)/while/body/closed_call/twotower.step/twotower.adagrad_user/scatter-add"}
  %flash_ce_bwd_du.6 = f32[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(epoch)/while/body/closed_call/twotower.step/transpose(twotower.step)/jvp(twotower.flash_ce)/flash_ce_bwd_du/pallas_call"}
  %xor.7 = u32[8]{0} xor(%a, %a), metadata={op_name="jit(epoch)/jit(_shuffle)/jit(_threefry_split)/TwoTowerTrainer._make_epoch.<locals>.epoch/xor"}
  %div.2 = f32[8]{0} divide(%a, %a), metadata={op_name="jit(epoch)/while/body/closed_call/twotower.step/transpose(jvp(jit(norm)))/div"}
  ROOT %copy.9 = f32[8]{0} copy(%fusion.1)
}
'''
    assert jaxmon.scope_map_of(hlo) == {
        "add.3": "twotower.adagrad_user",
        "fusion.1": "twotower.adagrad_user",
        "flash_ce_bwd_du.6": "twotower.flash_ce",
        "div.2": "twotower.step"}
