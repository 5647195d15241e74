"""Phi-4-mini-flash-reasoning's cell (ISSUE 53), rehearsed on the CPU at a tiny
size (tests/benchmarks/tiny_phi: new files and one entry, found by name), its
control, runs with the program broken underneath, the configuration against
the published one with its parameter count pinned, ``phi_counts`` at the
published widths, the reference's one padded length, and each new per-layer
reader on hand-built events. A CPU run is a control-flow check, never a
device number."""

import ast
import dataclasses
import json
import os
import sys

import numpy as np
import pytest

from tests.benchmarks import repo_spec
from tests.benchmarks.test_program_spans import (BENCHMARKS, FakeBench, HERE,
                                                 load_file, make_trace)
from tests.benchmarks.test_seq_cell import OLD_FIXTURE, harness  # noqa: F401

TINY = os.path.join(HERE, "tiny_phi")
CELL = "phi-tiny.longlived-c2"
REAL_CELL = "phi-4-mini-flash-reasoning.longlived-c8"
CONFIG = "phi-4-mini-flash-reasoning"


def entry(name, unit, better, source, layer, moves):
    return {"name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": moves, "workloads": [REAL_CELL]}


ENTRIES = [
    entry("extend_step_ms.phi", "ms", "lower", "program_span",
          "sequence engine", "query_p50_ms"),
    entry("prefill_chunk_ms.phi", "ms", "lower", "program_span",
          "sequence engine", "query_rate"),
    entry("cache_hit_tokens_pct.phi", "%", "higher", "program_counter",
          "latent cache", "query_rate"),
    entry("prefill_cross_rows_pct.phi", "%", "lower", "program_counter",
          "sequence programs", "query_rate"),
    entry("ssm_device_share_pct.phi", "%", "lower", "device_trace",
          "sequence programs", "query_rate"),
    entry("self_attn_device_share_pct.phi", "%", "lower", "device_trace",
          "sequence programs", "query_rate"),
    entry("cross_decoder_device_share_pct.phi", "%", "lower", "device_trace",
          "sequence programs", "query_rate"),
    entry("prefill_roofline_pct.phi", "%", "higher", "device_trace",
          "sequence programs", "query_rate"),
    entry("extend_roofline_pct.phi", "%", "higher", "device_trace",
          "sequence programs", "query_p50_ms"),
    entry("scan_roofline_pct.phi", "%", "higher", "device_trace",
          "sequence programs", "query_rate"),
    entry("cross_attend_roofline_pct.phi", "%", "higher", "device_trace",
          "sequence programs", "query_p50_ms"),
    entry("extend_span_blocks_over_own.phi", "blocks/block", "lower",
          "program_counter", "sequence programs", "query_rate"),
    entry("device_idle_pct.longlived-c8", "%", "lower", "device_trace",
          "device", "query_rate"),
    entry("topk_dot_roofline_pct.phi", "%", "higher", "device_trace",
          "retrieval", "query_p50_ms"),
    entry("front_self_ms.phi", "ms", "lower", "program_span",
          "serving host path", "query_p50_ms"),
    entry("extend_wait_ms.phi", "ms", "lower", "program_counter",
          "sequence engine", "query_p50_ms"),
]
NEW_METRICS = [e["name"] for e in ENTRIES]
#: the readers that need nothing of the device
ON_THE_CPU = {"extend_step_ms.phi", "prefill_chunk_ms.phi",
              "cache_hit_tokens_pct.phi", "prefill_cross_rows_pct.phi",
              "extend_span_blocks_over_own.phi", "front_self_ms.phi",
              "extend_wait_ms.phi"}


def run_cell(harness, capsys, *extra, seed=5300000011):
    code = harness.main(["--bench-root", TINY, "--rehearse-cpu",
                         "--workload", CELL, "--seed", str(seed),
                         "--seconds", "1", *extra])
    lines = capsys.readouterr().out.strip().splitlines()
    return code, json.loads(lines[-1]), lines


def make_bench(harness, seed=7):
    import argparse

    import jax

    with open(os.path.join(TINY, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    bench = harness.Bench(TINY, spec, cell, argparse.Namespace(
        seed=seed, seconds=1, trace=0))
    bench.devices = jax.devices()[:1]
    return bench


def real_config():
    with open(os.path.join(BENCHMARKS, "configs", CONFIG + ".json")) as f:
        return json.load(f)


def tiny_config():
    with open(os.path.join(TINY, "bench", "configs", "phi-tiny.json")) as f:
        return json.load(f)


def counted_in(log):
    line = next(l for l in log if "engine counters over the window" in l)
    return ast.literal_eval(line.split("window: ", 1)[1])


@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_cell_runs_from_new_files_and_prints_the_contracts_line(
        harness, capsys, trace):
    code, line, log = run_cell(harness, capsys, "--trace", trace)
    assert code == 0
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert any("compilations inside the window: 0" in l for l in log)
    assert sum(1 for l in log if l.startswith("# check ")) == 4
    if trace == "0":
        assert {"query_p50_ms", "query_p95_ms", "query_rate",
                "setup_s"} <= set(line["metrics"])
    else:
        assert {"busy_s", "window_s"} <= set(line["device"])
        # the tiny tree lists the cell's own per-layer entries: what needs
        # no device is read on the CPU too, the rest is left out
        assert set(line["metrics"]) == ON_THE_CPU
        # one row a history's last chunk over histories of 8-200: a few %
        assert 0 < line["metrics"]["prefill_cross_rows_pct.phi"][
            "value"] < 10
        assert line["metrics"]["extend_span_blocks_over_own.phi"][
            "value"] >= 1.0
    assert any("reference: 8 answers compared" in l
               and "(4 first queries" in l for l in log)
    assert any(l.startswith("# run: seconds by phase: deploy") for l in log)
    got = counted_in(log)
    for name in ("prefill_cross_rows", "extend_cross_rows",
                 "extend_span_blocks_walked", "extend_span_blocks_own",
                 "extend_state_rows", "state_resumes", "prefill_window_blocks",
                 "extend_window_positions", "extend_kv_positions"):
        assert got[name] > 0, name
    # sessions only grow here: every later query resumes its slot's state
    assert got["rewind_misses"] == 0 and got["ring_misses"] == 0
    assert got["extend_state_rows"] == got["extend_cross_rows"] \
        == got["extend_rows"]
    # no part of the mathematics is left out: every query the window answered
    # carried one row through the cross-decoder (the engine's counters run to
    # the window's very end, a few answers past the load generator's count)
    answered = got["prefill_cross_rows"] + got["extend_cross_rows"]
    assert line["attempted"] <= answered <= line["attempted"] + 4
    assert got["prefill_cross_rows"] <= got["prefill_tickets"]
    assert got["prefill_span_blocks_walked"] == 0


def test_the_tiny_tree_lists_the_cells_own_entries_under_its_own_cell():
    with open(os.path.join(TINY, "BENCHMARK.json")) as f:
        tiny = json.load(f)
    assert [dict(m, workloads=[REAL_CELL]) for m in tiny["per_layer"]] \
        == ENTRIES


def test_the_traffic_holds_the_parameters_the_issue_names():
    traffic = load_file(os.path.join(BENCHMARKS, "session_traffic.py"))
    with open(os.path.join(BENCHMARKS, "traffic", "longlived-c8.json")) as f:
        mix = json.load(f)
    named = {"connections": 8, "num": 10, "queries_per_session": 24,
             "grow_max": 3, "history_quantiles": 32, "history_median": 4096,
             "history_sigma": 1.0, "history_min": 512, "history_max": 32768,
             "topics": 64, "own_topic": 0.8, "zipf_exponent": 1.0,
             "sessions_seed": 53, "warmup_sessions_per_connection": 2,
             "prepared_sessions_per_connection": 32, "trace_seconds": 1.5}
    assert {k: mix[k] for k in named} == named
    assert set(mix) - set(named) == {
        "driver", "loop", "start", "start_why", "trace_after_go_s",
        "check_sample", "check_budget_s", "check_floor"}
    assert mix["driver"] == "session_queries"
    assert [w.get("delay_s", 0.0) for w in mix["start"]] == [0] + [.05] * 7
    assert sum(w["connections"] for w in mix["start"]) == 8
    floor = mix["check_floor"]
    assert floor["later_past"] == 8192 and floor["later_queries"] >= 1
    assert floor["first_queries"] >= 2
    assert floor["answers"] <= mix["check_sample"]
    lengths = traffic.history_lengths(mix)
    assert lengths[:4] == [512, 766, 992, 1197]
    assert lengths[15:17] == [3939, 4260]
    assert lengths[-4:] == [14011, 16908, 21888, 32768]
    assert sum(lengths) / 32 == pytest.approx(6454, abs=1)
    assert sum(1 for h in lengths if h > 8192) == 8
    assert sum(1 for h in lengths if h > 16384) == 3
    assert sum(-(-h // 512) for h in lengths) / 32 == pytest.approx(
        13.1, abs=0.05)
    cfg = real_config()
    sessions = traffic.Sessions(mix, cfg["vocab_size"])
    order = sessions.order(7)
    queries = sessions.session(7, order.index(32768))
    assert len(queries) == 24 and len(queries[0]) == 32768
    for before, after in zip(queries, queries[1:]):
        assert after[:len(before)] == before
        assert 1 <= len(after) - len(before) <= 3 <= cfg["serve"][
            "extend_len"]
    # the longest session, grown 23 times, fits its slot; every history
    # reaches past the window
    assert len(queries[-1]) <= 32837 <= cfg["serve"]["capacity"]
    assert min(lengths) >= cfg["sliding_window"]
    assert max(queries[-1]) < cfg["vocab_size"]
    driver = load_file(os.path.join(BENCHMARKS, "drivers",
                                    "session_queries.py"))
    assert driver.reach_of(mix) == 32837


def test_the_controls_histories_are_the_mixs_shortest(harness):
    bench = make_bench(harness)
    builder = bench.load_module("models", bench.config["engine"])
    histories = builder.control_histories(bench)
    assert len(histories) == 3
    lengths = bench.lib("session_traffic").history_lengths(bench.traffic)
    for want, got in zip(sorted(lengths)[:3], histories):
        assert want <= len(got) <= want + 5 * 3
        assert len(got) >= bench.config["sliding_window"]


def test_the_controls_long_histories_follow_the_short_ones(harness):
    """``control_long_histories``: the mix's longest session first, then at
    even steps of rank; the short ones stay as they were (the same draws)."""
    bench = make_bench(harness)
    builder = bench.load_module("models", bench.config["engine"])
    short = builder.control_histories(bench)
    bench.config["control_long_histories"] = 2
    histories = builder.control_histories(bench)
    assert histories[:3] == short and len(histories) == 5
    lengths = sorted(bench.lib("session_traffic").history_lengths(
        bench.traffic), reverse=True)
    grown = 5 * 3
    assert lengths[0] <= len(histories[3]) <= lengths[0] + grown
    assert lengths[1] <= len(histories[4]) + grown \
        and len(histories[4]) < lengths[0]
    # the cell's own mix: the longest session, and one past 8,192
    real = real_config()
    assert (real["control_histories"], real["control_long_histories"]) \
        == (6, 2)
    with open(os.path.join(BENCHMARKS, "traffic", "longlived-c8.json")) as f:
        bench.traffic = json.load(f)
    bench.config = dict(real)
    got = [len(ids) for ids in builder.control_histories(bench)]
    assert len(got) == 8 and max(got[:6]) <= 1589 + 23 * 3
    assert 32768 <= got[6] <= 32768 + 23 * 3
    assert 9398 <= got[7] <= 9398 + 23 * 3


@pytest.mark.parametrize("decays, slowest", [
    (None, 1e-3), ({"step": [1e-3, 0.1], "a_over": 16}, 1e-3 / 16),
    ({"step": [1e-4, 1e-2], "a_over": 1}, 1e-4)])
def test_the_decays_are_seeded_as_the_configurations_file_says(
        harness, decays, slowest):
    """``seeded_decays``: the step's log-uniform range and the divisor of
    ``A[:, n] = n + 1`` (the tiny file states the family's own
    initialisation). The slowest rate a channel and state can have is
    ``step_min / a_over``."""
    bench = make_bench(harness)
    assert bench.config["seeded_decays"] == {"step": [1e-3, 0.1],
                                             "a_over": 1}
    if decays is not None:
        bench.config["seeded_decays"] = decays
    decays = bench.config["seeded_decays"]
    builder = bench.load_module("models", bench.config["engine"])
    weights = builder.make_weights(bench)
    (lo, hi), over = decays["step"], decays["a_over"]
    rates = []
    for kind, layer in zip(builder.layer_kinds(bench.config),
                           weights["layers"]):
        if kind not in ("mamba", "memory"):
            continue
        p = layer["mixer_a"]
        step = np.log1p(np.exp(np.asarray(p["b_dt"], np.float64)))
        assert lo * 0.999 <= step.min() and step.max() <= hi * 1.001
        a = np.exp(np.asarray(p["a_log"], np.float64))
        np.testing.assert_allclose(
            a, np.broadcast_to(np.arange(1, a.shape[1] + 1) / over, a.shape),
            rtol=1e-6)
        rates.append((step[:, None] * a).min())
    assert len(rates) == 3 and slowest <= min(rates) <= 20 * slowest
    # the cell's own: the family's, as every reading of PR 53 was made
    assert real_config()["seeded_decays"] == {"step": [1e-3, 0.1],
                                              "a_over": 1}


def test_the_decays_tool_reads_every_variant_on_every_history(
        harness, capsys):
    """``tools/phi_decays.py`` at the tiny size: a line a seeding and
    variant, a reading a history beside the limits; the configured seeding
    leaves the configuration as it is."""
    tool = load_file(os.path.join(BENCHMARKS, "tools", "phi_decays.py"))
    argv = sys.argv
    sys.argv = ["phi_decays.py", "--rehearse-cpu", "--bench-root", TINY,
                "--workload", CELL, "--seedings", "configured,a16",
                "--variants", "state_bfloat16,window_less_one"]
    try:
        assert tool.main() == 0
    finally:
        sys.argv = argv
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    assert [(l["seeding"], l["variant"]) for l in lines] == [
        (s, v) for s in ("configured", "a16")
        for v in ("state_bfloat16", "window_less_one")]
    for l in lines:
        assert len(l["score_err"]) == len(l["rank_gap"]) == len(
            l["histories"]) == 3
        assert l["limits"] == {"score_err": 0.0001, "rank_gap": 0.0001}
    assert lines[0]["decays"] == {"step": [1e-3, 0.1], "a_over": 1}
    assert lines[2]["decays"] == {"step": [1e-3, 0.1], "a_over": 16}
    # a window one position short shows at any size
    assert min(lines[1]["score_err"]) > 1e-3


def test_the_chip_calls_are_in_the_tree():
    """``tools/phi_chip.sh``: the commands behind ``PERF.md``'s PR 53
    numbers, a phase a call; every tool it names is there."""
    import subprocess

    path = os.path.join(BENCHMARKS, "tools", "phi_chip.sh")
    assert subprocess.run(["sh", "-n", path]).returncode == 0
    with open(path) as f:
        text = f.read()
    for phase in ("cell)", "decays)", "guard)", "control)", "ablation)",
                  "pair)", "archive)", "parent_newcell)"):
        assert "\n" + phase in text, phase
    for tool in ("benchmarks/run.py", "benchmarks/control.py",
                 "benchmarks/tools/phi_decays.py",
                 "benchmarks/tools/phi_ablation.py"):
        assert tool in text and os.path.exists(os.path.join(
            os.path.dirname(BENCHMARKS), tool)), tool
    assert subprocess.run(["sh", path, "no_such_phase"],
                          capture_output=True).returncode == 2


def test_every_control_fails_a_limit(harness):
    """The reference in the program's place in a lower precision, with the
    carried state alone rounded, without ``lambda o_2`` or with a window one
    position short: each outside a limit (on the chip at the published size
    ``PERF.md`` section 4 gives the readings; the float32 tiny model's limits
    are tight enough for every one)."""
    bench = make_bench(harness)
    reference = bench.load_module("reference", bench.config["reference"])
    readings = reference.control(bench)
    limits = bench.config["limits"]
    assert set(readings) == {"bfloat16", "float8_e4m3fn", "state_bfloat16",
                             "no_lambda", "window_less_one"}
    for name, got in readings.items():
        assert got["compared"] == 3
        assert (got["score_err"] > limits["score_err"]
                or got["rank_gap"] > limits["rank_gap"]), (name, got)
    assert (readings["float8_e4m3fn"]["score_err"]
            > readings["bfloat16"]["score_err"])


@pytest.mark.parametrize("what", ["window_less_one"])
def test_a_program_broken_underneath_makes_the_run_incorrect(
        harness, capsys, what):
    """What the builder shows on the chip (``benchmarks/tools/
    phi_ablation.py``): the window layers one position short, and the
    comparison must notice."""
    ablation = load_file(os.path.join(BENCHMARKS, "tools",
                                      "phi_ablation.py"))
    run_builder = harness.load_file(
        os.path.join(BENCHMARKS, "models", "phirec.py"))
    with ablation.broken(what, run_builder):
        code, line, log = run_cell(harness, capsys, "--trace", "0")
    assert code == 0
    assert line["correct"] is False
    assert any(l.startswith("# check score_err") and "FAILED" in l
               for l in log)
    # and the sound program is back
    builder = load_file(os.path.join(BENCHMARKS, "models", "phirec.py"))
    assert dataclasses.asdict(builder.stack_spec(tiny_config())) \
        == dataclasses.asdict(run_builder.stack_spec(tiny_config()))


def test_a_program_that_knows_no_such_mixers_fails_before_any_weight(
        harness, monkeypatch):
    """The parent's program under this PR's benchmark files: the builder
    asks for ``ops/mamba1`` and ``GQADims`` fields it lacks, before 7.7 GB of
    weights are made."""
    from predictionio_tpu.ops import gqa

    new = {"bias", "diff", "cross"}
    old_fields = [f for f in dataclasses.fields(gqa.GQADims)
                  if f.name not in new]
    OldGQADims = dataclasses.make_dataclass(
        "OldGQADims", [(f.name, f.type, f) for f in old_fields], frozen=True)
    monkeypatch.setattr(gqa, "GQADims", OldGQADims)
    bench = make_bench(harness)
    builder = bench.load_module("models", bench.config["engine"])
    made = []
    monkeypatch.setattr(builder, "make_weights",
                        lambda bench: made.append(1))
    with pytest.raises(TypeError):
        builder.deploy(bench)
    assert not made


def test_phi_counts_at_the_published_widths():
    counts = load_file(os.path.join(BENCHMARKS, "phi_counts.py"))
    cfg = real_config()
    # ISSUE 53's figures (matrices only)
    assert counts.mamba_params(cfg) == 41_123_840 == (
        26_214_400 + 13_107_200 + 983_040 + 819_200)
    assert counts.attention_params(cfg) == 19_660_800
    assert counts.cross_params(cfg) == 13_107_200
    assert counts.gmu_params(cfg) == 26_214_400
    assert counts.mlp_params(cfg) == 78_643_200
    assert counts.embedding_params(cfg) == 512_163_840
    assert counts.self_decoder_params(cfg) == 9 * 119_767_040 + 9 * 98_304_000
    assert counts.cross_decoder_params(cfg) == 7 * 104_857_600 \
        + 7 * 91_750_400
    assert counts.model_params(cfg) == 3_851_059_200 == cfg["parameters"][
        "model"]
    for name in ("mamba_mixer", "attention", "cross_attention", "gmu", "mlp",
                 "embedding"):
        assert cfg["parameters"][name] == {
            "mamba_mixer": counts.mamba_params, "gmu": counts.gmu_params,
            "attention": counts.attention_params, "mlp": counts.mlp_params,
            "cross_attention": counts.cross_params,
            "embedding": counts.embedding_params}[name](cfg)
    d = counts._dims(cfg)
    assert (d["n_mamba"], d["n_window"], d["n_full"], d["n_gmu"],
            d["n_cross"]) == (9, 8, 1, 7, 7)
    assert counts.kv_bytes_per_position(cfg) == 5_120
    assert counts.span_readers(cfg) == 8
    # a state a layer: 5,120 x 16 float32 and three bfloat16 rows
    assert counts.state_bytes_per_row(cfg) == 9 * (327_680 + 30_720)
    # a pair of positions a layer: 40 heads' scores of 64 and values of 128
    assert counts.pair_flops(cfg) == 40 * (2 * 64 + 2 * 128)
    assert counts.causal_pairs(0, 512) == 512 * 513 / 2
    assert counts.causal_pairs(1024, 512, 512) == 512 * 512
    assert counts.causal_pairs(0, 4, 512) == 1 + 2 + 3 + 4
    # ISSUE 53's reckoning of an extension batch at a reach of 32 k: 8 rows
    # x 8 layers x 168 MB of span beside 6.7 GB of weights
    step = counts.extend_bytes(cfg, 1, 8, 8 * 32_768, 8 * 515)
    assert step == pytest.approx(
        3_338_895_360 * 2 + 8 * 2 * 3_225_600 + 8 * 32_768 * 8 * 5_120
        + 8 * 515 * 8 * 5_120)
    assert counts.span_walk_bytes(cfg, 8 * 32_768) == pytest.approx(10.74e9,
                                                                    rel=1e-3)
    # a chunk's scan, a layer: 31.5 MB of rows in and out bound it (38 us),
    # not its 294 M operations at the vector rate this file states (24 us)
    peaks = {"hbm_bytes_per_s": 819e9}
    flops_s = counts.scan_flops(cfg, 512) / counts.F32_VECTOR_OPS_PER_S
    bytes_s = counts.scan_bytes(cfg, 512) / 819e9
    assert counts.F32_VECTOR_OPS_PER_S == pytest.approx(12.288e12)
    assert bytes_s > flops_s > 0.5 * bytes_s
    assert counts.scan_least_seconds(cfg, peaks, [(0, 512)]) \
        == pytest.approx(9 * bytes_s)
    # a last chunk at offset 3,584: its operations bound it
    own, carried = [(3584, 512)], [4096]
    assert counts.prefill_flops(cfg, own, carried) / 197e12 \
        > counts.prefill_bytes(cfg, own, carried) / 819e9
    assert counts.prefill_flops(cfg, own, carried) \
        - counts.prefill_flops(cfg, own, []) == pytest.approx(
            2 * 1_376_256_000 + 7 * 4096 * 15_360)


@pytest.mark.parametrize("case", repo_spec.CASES)
def test_benchmark_json_names_the_configuration_the_cell_and_each_reader(
        case):
    spec = repo_spec.load(case)
    cell = repo_spec.by_name(spec["workloads"], REAL_CELL)
    assert cell == {"name": REAL_CELL, "config": CONFIG,
                    "traffic": "longlived-c8", "chips": 1,
                    "why": cell["why"]}
    assert len(cell["why"]) <= 200 and "24-query" in cell["why"]
    config = repo_spec.by_name(spec["configs"], CONFIG)
    assert config["reduced"] == []
    assert config["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert config["source"] == real_config()["source"].split(" ")[0] == (
        "https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/"
        "main/config.json")
    assert len(config["why"]) <= 200
    for e in ENTRIES:
        repo_spec.assert_names_the_reader(spec, e)
    for name in ("query_p50_ms", "query_rate"):
        assert REAL_CELL in repo_spec.by_name(
            spec["end_to_end"], name)["workloads"]
    # few first queries fall in a window of 24-query sessions: its 95th
    # percentile is left to the log (PERF.md section 2)
    assert REAL_CELL not in repo_spec.by_name(
        spec["end_to_end"], "query_p95_ms")["workloads"]
    # the cell joins no accepted per-layer metric's list (PHI_SPANS.md), and
    # none of four chips came with it
    for m in spec["per_layer"]:
        if m["name"] not in NEW_METRICS:
            assert REAL_CELL not in m.get("workloads", ()), m["name"]
    assert all(w["chips"] == 1 for w in spec["workloads"])
    # appended: the cell's entries stand after everything the benchmark had
    names = [m["name"] for m in spec["per_layer"]]
    assert max(names.index(n) for n in names
               if n.endswith((".mimo", ".mixed-c8"))) < min(
        names.index(n) for n in NEW_METRICS)
    # no share of a roofline can pass 100 %, and none is asked of another
    # cell
    for e in ENTRIES:
        if "roofline" in e["name"]:
            assert e["unit"] == "%" and e["workloads"] == [REAL_CELL]


def test_the_configuration_keeps_every_published_number():
    catalog = {
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 10240, "layer_norm_eps": 1e-05,
        "max_position_embeddings": 262144, "mb_per_layer": 2,
        "model_type": "phi4flash", "num_attention_heads": 40,
        "num_hidden_layers": 32, "num_key_value_heads": 20,
        "resid_pdrop": 0, "sliding_window": 512,
        "tie_word_embeddings": True, "mlp_bias": False,
        "lm_head_bias": False, "vocab_size": 200064}
    cfg = real_config()
    assert {k for k, v in catalog.items() if cfg[k] != v} == set()
    assert cfg["reduced"] == {}
    assert cfg["assumed_sizes"] == {"d_state": 16, "d_conv": 4, "expand": 2,
                                    "dt_rank": 160}
    assert cfg["limits"]["score_err"] > 0 and cfg["limits"]["rank_gap"] > 0
    assert {"mamba", "layers", "differential", "state_precision", "weights",
            "sessions", "cache", "slo"} <= set(cfg["assumed"])
    for key in ("source", "deployment", "precision", "equations",
                "limits_set_from"):
        assert cfg[key], key
    assert "one chip, one replica" in cfg["deployment"]
    serve = cfg["serve"]
    assert {k: serve[k] for k in ("capacity", "chunk", "extend_len",
                                  "extend_batch")} == {
        "capacity": 33280, "chunk": 512, "extend_len": 4, "extend_batch": 8}
    assert 12 <= serve["n_slots"] <= 16
    # the program's stack follows from these keys alone
    builder = load_file(os.path.join(BENCHMARKS, "models", "phirec.py"))
    spec = builder.stack_spec(cfg)
    mixers = [b.mixer for b in spec.blocks]
    assert mixers[:16] == ["mamba1", "gqa_window"] * 8
    assert mixers[16:18] == ["mamba1", "gqa"]
    assert mixers[18:] == ["gmu", "gqa_cross"] * 7
    assert {b.ffn for b in spec.blocks} == {"swiglu"}
    assert {b.norm for b in spec.blocks} == {"layernorm"}
    assert spec.memory_block == 16 and spec.cross_from == 18
    assert spec.ffn_dim == 10240 and spec.tied_head and spec.eps == 1e-5
    assert spec.embed_scale == 1.0 and spec.logits_scale == 1.0
    for dims in (spec.gqa, spec.gqa_window, spec.gqa_cross):
        assert (dims.heads, dims.kv_heads, dims.head_dim, dims.v_dim,
                dims.rope, dims.qk_norm, dims.bias, dims.diff,
                dims.block_len, dims.scale) == (
            40, 20, 64, 64, False, False, True, True, 1, None)
        assert dims.cache_width * 2 == 5120
    assert (spec.gqa.window, spec.gqa_window.window, spec.gqa_cross.window,
            spec.gqa_cross.cross, spec.gqa.cross) == (0, 512, 0, True, False)
    m = spec.mamba1
    assert (m.dim, m.d_inner, m.d_state, m.dt_rank, m.d_conv) == (
        2560, 5120, 16, 160, 4)
    assert builder.stack_spec(
        cfg, ablate="window_less_one").gqa_window.window == 511
    with pytest.raises(ValueError, match="unknown ablation"):
        builder.stack_spec(cfg, ablate="no_bias")
    assert builder.layer_kinds(cfg) == load_file(os.path.join(
        BENCHMARKS, "reference", "phi4flash_forward.py")).layer_kinds(32)
    reference = load_file(os.path.join(BENCHMARKS, "reference",
                                       "phi4flash_forward.py"))
    dm = reference.dims_of(cfg)
    assert (dm["D"], dm["F"], dm["H"], dm["Hkv"], dm["d"], dm["window"],
            dm["L"], dm["inner"], dm["N"], dm["R"], dm["K"]) == (
        2560, 10240, 40, 20, 64, 512, 32, 5120, 16, 160, 4)


def test_the_seeded_weights_hold_the_published_parameter_count(harness):
    """The shapes ``make_weights`` makes, at the tiny size and (by their
    formulas) at the published one: what the program's own ``init_stack``
    holds, leaf for leaf."""
    import jax

    from predictionio_tpu.ops.sessionrec import init_stack

    bench = make_bench(harness)
    builder = bench.load_module("models", bench.config["engine"])
    weights = builder.make_weights(bench)
    spec = builder.stack_spec(bench.config)
    want = init_stack(spec, jax.random.PRNGKey(0),
                      bench.config["vocab_size"])
    made = {"item_embed": {"embedding": weights["embed"]},
            "final_norm": weights["final_norm"], "blocks": weights["layers"]}
    assert jax.tree_util.tree_map(lambda a: a.shape, made) \
        == jax.tree_util.tree_map(lambda a: a.shape, want)
    assert weights["head"] is weights["embed"]
    # at the published size, from shapes alone: the matrices are 3.85 B
    cfg = real_config()
    shapes = jax.eval_shape(lambda k: init_stack(
        builder.stack_spec(cfg), k, cfg["vocab_size"]), jax.random.PRNGKey(0))
    matrices = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(
        shapes) if len(a.shape) == 2 and min(a.shape) >= 160)
    assert matrices == 3_851_059_200


# -- the reference's one padded length ------------------------------------------

def test_the_reference_pads_to_one_length_a_cell_and_compiles_each_part_once(
        harness):
    """Histories of 10 to 200 under ``reach`` 2,100 all pad to 3,072 (one
    shape a layer kind), and the real length bounds the loops."""
    import jax

    bench = make_bench(harness)
    reference = bench.load_module("reference", bench.config["reference"])
    assert reference.shapes(2100) == (3072,)
    assert reference.shapes(32837) == (33792,)
    assert reference.padded_length(10, 2100) == 3072
    builder = bench.load_module("models", bench.config["engine"])
    weights = builder.make_weights(bench)
    dm = reference.dims_of(bench.config)
    rng = np.random.default_rng(3)
    lone = reference.forward(weights, rng.integers(1, 256, 40), dm)
    counted = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, duration, **kw: counted.append(event)
        if event.endswith("backend_compile_duration") else None)
    first = reference.forward(weights, rng.integers(1, 256, 40), dm,
                              reach=2100)
    n_first = len(counted)
    for n in (10, 90, 200):
        reference.forward(weights, rng.integers(1, 256, n), dm, reach=2100)
    assert len(counted) == n_first      # no length compiled anything
    assert 0 < n_first <= 8             # one program a layer kind and part
    assert lone.shape == first.shape == (256,)


# -- the readers -----------------------------------------------------------------

def read(metric, trace, config=None, **ctx):
    reader = load_file(os.path.join(BENCHMARKS, "layer_metrics",
                                    metric + ".py"))
    return reader.read({"bench": FakeBench(config or real_config()),
                        "_program_spans": trace, **ctx})


@pytest.fixture(scope="module")
def ps():
    return load_file(os.path.join(BENCHMARKS, "program_spans.py"))


def stats(**kw):
    return {"stats0": {k: 0 for k in kw}, "stats1": kw,
            "window_stats0": {k: 0 for k in kw}, "window_stats1": kw}


def test_the_span_and_counter_readers_on_hand_built_events(ps):
    spans = [("pio:seq.step", 0, 70, 1),
             ("pio:seq.extend", 1, 17, 1, {"rows": 3}),
             ("pio:seq.prefill_chunk", 18, 69, 1,
              {"slot": 4, "offset": 512, "tokens": 512, "last": 0}),
             ("pio:seq.step", 80, 150, 1), ("pio:seq.extend", 81, 95, 1),
             ("pio:seq.prefill_chunk", 96, 149, 1),
             ("pio:seq.step", 160, 180, 1), ("pio:seq.extend", 161, 179, 1)]
    trace = make_trace(ps, spans)
    assert read("extend_step_ms.phi", trace) == pytest.approx(16.0)
    assert read("prefill_chunk_ms.phi", trace) == pytest.approx(52.0)
    ctx = stats(hit_tokens=2300, miss_tokens=100)
    assert read("cache_hit_tokens_pct.phi", None, **ctx) == \
        pytest.approx(100 * 23 / 24)
    ctx = stats(prefill_cross_rows=3, prefill_tokens=12_000)
    assert read("prefill_cross_rows_pct.phi", None, **ctx) == \
        pytest.approx(0.025)
    ctx = stats(extend_span_blocks_walked=65 * 16, extend_span_blocks_own=16
                * (2 + 65) // 2)
    assert read("extend_span_blocks_over_own.phi", None, **ctx) == \
        pytest.approx(65 * 16 / 536)
    assert read("device_idle_pct.longlived-c8", None,
                traced={"busy_s": 2.4, "window_s": 3.0}) == pytest.approx(
                    20.0)


def test_the_head_and_the_host_readers_on_hand_built_events(ps):
    """The two layers beside the serve programs that this cell's median
    query crosses: the head's kernel over the 2.05 GB table (``.seq``'s
    reading with this configuration's own count) and the host in front of
    the step worker and between its steps."""
    counts = load_file(os.path.join(BENCHMARKS, "phi_counts.py"))
    cfg = real_config()
    assert counts.head_bytes(cfg) == 200_064 * 2_560 * 4 == 2_048_655_360
    assert counts.head_flops(cfg, rows=8) == 2 * 8 * 200_064 * 2_560
    spans = [("pio:http.request", 0, 40, 2), ("pio:serve.wait", 5, 33, 2),
             ("pio:http.request", 50, 95, 3), ("pio:serve.wait", 52, 92, 3),
             ("pio:seq.extend", 6, 30, 1)]
    # three searches of 3, 4 and 5 ms: the median against 2.05 GB at 819 GB/s
    trace = make_trace(ps, spans, ops=[
        ("topk_dot.3", 30, 33), ("fusion.9", 33, 34), ("topk_dot.3", 60, 64),
        ("topk_dot.3", 80, 85)])
    assert read("topk_dot_roofline_pct.phi", trace) == pytest.approx(
        100 * 2_048_655_360 / 819e9 / 0.004)
    assert read("front_self_ms.phi", trace) == pytest.approx((12 + 5) / 2)
    # a search faster than the table can be read is no reading
    fast = make_trace(ps, spans, ops=[("topk_dot.3", 30, 32)])
    assert read("topk_dot_roofline_pct.phi", fast) is None
    none = make_trace(ps, spans, ops=[("fusion.9", 33, 34)])
    assert read("topk_dot_roofline_pct.phi", none) is None
    # (wait, step, was it an extension): first queries' waits are not read
    splits = [(0.010, 0.030, True), (0.500, 0.020, False),
              (0.030, 0.030, True), (0.020, 0.028, True)]
    assert read("extend_wait_ms.phi", None, splits=splits) == \
        pytest.approx(20.0)
    assert read("extend_wait_ms.phi", None, splits=[(0.5, 0.02, False)]) \
        is None
    for name in ("topk_dot_roofline_pct.phi", "front_self_ms.phi",
                 "extend_wait_ms.phi"):
        assert read(name, None) is None, name


def test_the_device_readers_on_hand_built_operations(ps):
    """Two extension programs of 10 ms, a chunk program of 60 ms and a last
    chunk of 40 ms on the device. An extension: 1 ms of a scan and 1 of a
    Mamba layer's projections, 1 of a window layer, 1 of the full layer's
    projections and 2 of its walk, 2 of a cross layer's walk, 1 of a GMU, 1
    outside any scope. The chunk: 20 of scans, 5 of a window layer, 15 of the
    full layer's walk, 20 of an MLP. The last chunk: 10 of scans, 20 of an
    MLP, 5 of a cross walk, 5 of a GMU. The programs number their fusions
    alike."""
    ops, modules = [], []
    for t in (0, 20):
        ops += [("%fusion.1", t, t + 1, "seq.layer0.mamba1_a.ssm.scan"),
                ("%fusion.2", t + 1, t + 2, "seq.layer0.mamba1_a.ssm.in_proj"),
                ("%fusion.3", t + 2, t + 3, "seq.layer1.gqa_window_a.attend"),
                ("%fusion.4", t + 3, t + 4, "seq.layer17.gqa_a"),
                ("%fusion.5", t + 4, t + 6, "seq.layer17.gqa_a.attend"),
                ("%fusion.6", t + 6, t + 8, "seq.layer19.gqa_cross_a.attend"),
                ("%fusion.7", t + 8, t + 9, "seq.layer18.gmu_a"),
                ("%copy.8", t + 9, t + 10, None)]
        modules += ["jit__extend_fn"] * 8
    ops += [("%fusion.1", 40, 60, "seq.layer0.mamba1_a.ssm.scan"),
            ("%fusion.3", 60, 65, "seq.layer1.gqa_window_a.attend"),
            ("%fusion.5", 65, 80, "seq.layer17.gqa_a.attend"),
            ("%fusion.9", 80, 100, "seq.layer0.ffn_a")]
    modules += ["jit__prefill_fn"] * 4
    ops += [("%fusion.1", 110, 120, "seq.layer2.mamba1_a.ssm.scan"),
            ("%fusion.9", 120, 140, "seq.layer0.ffn_a"),
            ("%fusion.6", 140, 145, "seq.layer19.gqa_cross_a.attend"),
            ("%fusion.7", 145, 150, "seq.layer18.gmu_a")]
    modules += ["jit__prefill_last_fn"] * 4
    trace = make_trace(ps, [
        ("pio:seq.prefill_chunk", 39, 101, 1,
         {"offset": 8192, "tokens": 512, "last": 0}),
        ("pio:seq.prefill_chunk", 109, 151, 1,
         {"offset": 8704, "tokens": 300, "last": 1})], ops)
    for dev in trace.ops.values():
        dev[:] = [o._replace(module=m) for o, m in zip(dev, modules)]
    traced = {"busy_s": 0.120, "window_s": 0.151}
    counts = load_file(os.path.join(BENCHMARKS, "phi_counts.py"))
    assert counts.chunks_of(ps, trace) == [(8192, 512, 0), (8704, 300, 1)]
    cfg = real_config()
    ctx = stats(extend_runs=2, extend_cross_rows=12, extend_state_rows=12,
                extend_kv_positions=60_000, extend_window_positions=12 * 514)
    assert read("ssm_device_share_pct.phi", trace, traced=traced,
                **ctx) == pytest.approx(100.0 * 34 / 120)
    assert read("self_attn_device_share_pct.phi", trace, traced=traced,
                **ctx) == pytest.approx(100.0 * (2 + 2 + 4 + 5 + 15) / 120)
    assert read("cross_decoder_device_share_pct.phi", trace, traced=traced,
                **ctx) == pytest.approx(100.0 * (4 + 2 + 5 + 5) / 120)
    # the span walks of the extension program alone: 8 ms
    need = 60_000 * 8 * 5_120
    assert read("cross_attend_roofline_pct.phi", trace, traced=traced,
                **ctx) == pytest.approx(100.0 * need / 819e9 / 0.008)
    need = counts.extend_bytes(cfg, 2, 12, 60_000, 12 * 514)
    assert read("extend_roofline_pct.phi", trace, traced=traced, **ctx) == \
        pytest.approx(100.0 * need / 819e9 / 0.020)
    # the scans of BOTH chunk programs (30 ms), and not the extensions'
    peaks = {"hbm_bytes_per_s": 819e9}
    least = counts.scan_least_seconds(cfg, peaks, [(8192, 512), (8704, 300)])
    assert read("scan_roofline_pct.phi", trace, traced=traced, **ctx) == \
        pytest.approx(100.0 * least / 0.030)
    # both chunk programs (100 ms): the second carried its row at 9,004
    own = [(8192, 512), (8704, 300)]
    flop_s = counts.prefill_flops(cfg, own, [9004]) / 197e12
    assert flop_s > counts.prefill_bytes(cfg, own, [9004]) / 819e9
    assert read("prefill_roofline_pct.phi", trace, traced=traced, **ctx) == \
        pytest.approx(100.0 * flop_s / 0.100)
    # counted too high, or part of the time left out: no reading
    far = stats(**dict(ctx["stats1"], extend_runs=40))
    assert read("extend_roofline_pct.phi", trace, traced=traced,
                **far) is None


def test_a_reader_returns_none_where_there_is_nothing_to_read(ps):
    """A trace of a program without this engine (PR 25's fixture), counters
    of a program that lacks what this PR counts (the PARENT's, under this
    PR's benchmark files), and a run that was not traced: no number, no
    error."""
    old = ps.load(OLD_FIXTURE, {})
    old_stats = stats(hit_tokens=0, miss_tokens=0, extend_runs=0)
    # the HTTP front and the head's kernel are shared with the ALS engine:
    # their readers find their spans and operations in its trace too
    shared = {"front_self_ms.phi", "topk_dot_roofline_pct.phi",
              "device_idle_pct.longlived-c8"}
    for name in NEW_METRICS:
        if name not in shared:
            assert read(name, old, traced={"busy_s": 1.0, "window_s": 2.0},
                        **old_stats) is None, name
        assert read(name, None) is None, name
    # the parent's granite trace (PR 34's fixture, recorded on the chip):
    # both serve programs, Mamba-2 scans and a causal gqa layer's scope, and
    # none of this stack's scopes or counters
    with open(os.path.join(HERE, "fixtures", "hyb_small.scopes.json")) as f:
        hyb = ps.load(os.path.join(HERE, "fixtures", "hyb_small.xplane.pb"),
                      json.load(f))
    with open(os.path.join(HERE, "fixtures", "hyb_small.ctx.json")) as f:
        ctx = json.load(f)
    ctx.update(window_stats0=ctx["stats0"], window_stats1=ctx["stats1"])
    for name in ("prefill_roofline_pct.phi", "extend_roofline_pct.phi",
                 "scan_roofline_pct.phi", "cross_attend_roofline_pct.phi",
                 "prefill_cross_rows_pct.phi",
                 "extend_span_blocks_over_own.phi",
                 "ssm_device_share_pct.phi",
                 "cross_decoder_device_share_pct.phi"):
        assert read(name, hyb, **ctx) is None, name
