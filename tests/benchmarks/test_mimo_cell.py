"""MiMo-V2.5's cell (ISSUE 49), rehearsed on the CPU at a tiny size
(tests/benchmarks/tiny_mimo: new files and one entry, found by name), its
control, runs with the window layers broken underneath, the configuration
against the published one, ``mimo_counts`` at the published widths, the
reference's one padded length and crossed row, and each new per-layer reader
on hand-built events. A CPU run is a control-flow check, never a device
number."""

import dataclasses
import json
import os

import numpy as np
import pytest

from tests.benchmarks import repo_spec
from tests.benchmarks.test_program_spans import (BENCHMARKS, FakeBench, HERE,
                                                 load_file, make_trace)
from tests.benchmarks.test_seq_cell import OLD_FIXTURE, harness  # noqa: F401

TINY = os.path.join(HERE, "tiny_mimo")
CELL = "mimo-tiny.mixed-c2"
REAL_CELL = "mimo-v2.5.mixed-c8"
CONFIG = "mimo-v2.5"


def entry(name, unit, better, source, layer, moves):
    return {"name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": moves, "workloads": [REAL_CELL]}


ENTRIES = [
    entry("extend_step_ms.mimo", "ms", "lower", "program_span",
          "sequence engine", "query_p50_ms"),
    entry("prefill_chunk_ms.mimo", "ms", "lower", "program_span",
          "sequence engine", "query_rate"),
    entry("cache_hit_tokens_pct.mimo", "%", "higher", "program_counter",
          "latent cache", "query_rate"),
    entry("window_attn_device_share_pct.mimo", "%", "lower", "device_trace",
          "sequence programs", "query_rate"),
    entry("full_attn_device_share_pct.mimo", "%", "lower", "device_trace",
          "sequence programs", "query_rate"),
    entry("moe_device_share_pct.mimo", "%", "lower", "device_trace",
          "sequence programs", "query_rate"),
    entry("window_blocks_walked_pct.mimo", "%", "lower", "program_counter",
          "sequence programs", "query_rate"),
    entry("prefill_roofline_pct.mimo", "%", "higher", "device_trace",
          "sequence programs", "query_rate"),
    entry("extend_roofline_pct.mimo", "%", "higher", "device_trace",
          "sequence programs", "query_p50_ms"),
    entry("window_attend_roofline_pct.mimo", "%", "higher", "device_trace",
          "sequence programs", "query_p50_ms"),
    entry("device_idle_pct.mixed-c8", "%", "lower", "device_trace",
          "device", "query_rate"),
]
NEW_METRICS = [e["name"] for e in ENTRIES]
#: the readers that need nothing of the device
ON_THE_CPU = {"extend_step_ms.mimo", "prefill_chunk_ms.mimo",
              "cache_hit_tokens_pct.mimo", "window_blocks_walked_pct.mimo"}


def run_cell(harness, capsys, *extra, seed=5000000011):
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
    with open(os.path.join(TINY, "bench", "configs", "mimo-tiny.json")) as f:
        return json.load(f)


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
        # histories of 10-200 over a window of 8: most walks skip blocks
        assert 5 < line["metrics"]["window_blocks_walked_pct.mimo"][
            "value"] < 60
    assert any("reference: 8 answers compared" in l
               and "(4 first queries" in l for l in log)
    # (how far a one-second window gets is the machine's: no length is asked)
    assert any("longest history served" in l for l in log)
    assert any(l.startswith("# reference: of the 8 compared histories")
               for l in log)
    assert any(l.startswith("# run: seconds by phase: deploy") for l in log)
    counted = next(l for l in log if "engine counters over the window" in l)
    for name in ("prefill_window_blocks", "extend_window_blocks",
                 "prefill_full_blocks", "extend_window_blocks_from0",
                 "extend_window_positions", "extend_kv_positions"):
        assert f"'{name}': 0" not in counted and f"'{name}'" in counted
    assert "'ring_misses': 0" in counted        # sessions only grow here


def test_the_tiny_tree_lists_the_cells_own_entries_under_its_own_cell():
    with open(os.path.join(TINY, "BENCHMARK.json")) as f:
        tiny = json.load(f)
    assert [dict(m, workloads=[REAL_CELL]) for m in tiny["per_layer"]] \
        == ENTRIES


def test_the_traffic_holds_the_parameters_the_issue_names():
    traffic = load_file(os.path.join(BENCHMARKS, "session_traffic.py"))
    with open(os.path.join(BENCHMARKS, "traffic", "mixed-c8.json")) as f:
        mix = json.load(f)
    named = {"connections": 8, "num": 10, "queries_per_session": 8,
             "grow_max": 3, "history_quantiles": 32, "history_median": 2048,
             "history_sigma": 1.4, "history_min": 128, "history_max": 24576,
             "topics": 64, "own_topic": 0.8, "zipf_exponent": 1.0,
             "sessions_seed": 49, "warmup_sessions_per_connection": 2,
             "prepared_sessions_per_connection": 32, "check_sample": 16,
             "check_budget_s": 100, "trace_seconds": 3.0,
             "check_floor": {"answers": 8, "first_queries": 2,
                             "later_queries": 1, "later_past": 4096}}
    assert {k: mix[k] for k in named} == named
    assert set(mix) - set(named) == {"driver", "loop", "start", "start_why",
                                     "trace_after_go_s"}
    assert mix["driver"] == "session_queries"
    assert [w.get("delay_s", 0.0) for w in mix["start"]] == [0] + [.05] * 7
    assert sum(w["connections"] for w in mix["start"]) == 8
    lengths = traffic.history_lengths(mix)
    # ISSUE 49's list
    assert lengths[:6] == [128, 196, 281, 366, 453, 544]
    assert lengths[21:23] == [3819, 4322] and lengths[26] == 7709
    assert lengths[-5:] == [9257, 11458, 14906, 21395, 24576]
    assert sum(lengths) / 32 == pytest.approx(4449, abs=1)
    assert sum(1 for h in lengths if h <= 512) == 5
    assert sum(1 for h in lengths if h > 4096) == 10
    assert sum(1 for h in lengths if h > 8192) == 5
    assert sum(-(-h // 512) for h in lengths) / 32 == pytest.approx(
        9.2, abs=0.05)
    cfg = real_config()
    sessions = traffic.Sessions(mix, cfg["vocab_size"])
    order = sessions.order(3)
    queries = sessions.session(3, order.index(24576))
    assert len(queries) == 8 and len(queries[0]) == 24576
    for before, after in zip(queries, queries[1:]):
        assert after[:len(before)] == before
        assert 1 <= len(after) - len(before) <= 3 <= cfg["serve"][
            "extend_len"]
    # the longest session, grown seven times, fits its slot; every history
    # reaches the window (an extension's new positions each attend 128)
    assert len(queries[-1]) <= 24597 <= cfg["serve"]["capacity"]
    assert min(lengths) >= cfg["sliding_window"]
    assert max(queries[-1]) < cfg["vocab_size"]
    driver = load_file(os.path.join(BENCHMARKS, "drivers",
                                    "session_queries.py"))
    assert driver.reach_of(mix) == 24597


def test_the_controls_histories_are_the_mixs_shortest(harness):
    bench = make_bench(harness)
    builder = bench.load_module("models", bench.config["engine"])
    histories = builder.control_histories(bench)
    assert len(histories) == 3
    lengths = bench.lib("session_traffic").history_lengths(bench.traffic)
    for want, got in zip(sorted(lengths)[:3], histories):
        assert want <= len(got) <= want + 3 * 3
        assert len(got) > bench.config["sliding_window"]


def test_the_control_in_a_lower_precision_fails_a_limit(harness):
    bench = make_bench(harness)
    reference = bench.load_module("reference", bench.config["reference"])
    readings = reference.control(bench)
    limits = bench.config["limits"]
    assert set(readings) == {"bfloat16", "float8_e4m3fn"}
    for name, got in readings.items():
        assert got["compared"] == 3
        assert (got["score_err"] > limits["score_err"]
                or got["rank_gap"] > limits["rank_gap"]), (name, got)
    assert (readings["float8_e4m3fn"]["score_err"]
            > readings["bfloat16"]["score_err"])


@pytest.mark.parametrize("what", ["no_sink", "wide_window", "full_theta"])
def test_a_program_whose_window_layers_are_broken_makes_the_run_incorrect(
        harness, capsys, what):
    """What the builder shows once on the chip (``benchmarks/tools/
    mimo_ablation.py``): the sink left out of the normaliser, the window
    layers attending far past their window, or turned at the full layers'
    base, and the comparison must notice."""
    ablation = load_file(os.path.join(BENCHMARKS, "tools",
                                      "mimo_ablation.py"))
    run_builder = harness.load_file(
        os.path.join(BENCHMARKS, "models", "mimorec.py"))
    with ablation.broken(what, run_builder):
        code, line, log = run_cell(harness, capsys, "--trace", "0")
    assert code == 0
    assert line["correct"] is False
    assert any(l.startswith("# check score_err") and "FAILED" in l
               for l in log)
    # and the sound program is back
    builder = load_file(os.path.join(BENCHMARKS, "models", "mimorec.py"))
    assert dataclasses.asdict(builder.stack_spec(tiny_config())) \
        == dataclasses.asdict(run_builder.stack_spec(tiny_config()))


def test_a_program_that_knows_no_window_fails_before_any_weight(
        harness, monkeypatch):
    """The parent's program under this PR's benchmark files: the builder
    hands ``GQADims`` fields it lacks, before 7 GB of weights are made."""
    from predictionio_tpu.ops import gqa

    new = {"v_head_dim", "rope_dims", "window", "sink", "value_scale"}
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


def test_mimo_counts_at_the_published_widths():
    counts = load_file(os.path.join(BENCHMARKS, "mimo_counts.py"))
    cfg = real_config()
    # ISSUE 49's figures (matrices only)
    assert counts.attention_params(cfg, True) == 94_371_840 == (
        50_331_648 + 6_291_456 + 4_194_304 + 33_554_432)
    assert counts.attention_params(cfg, False) == 89_128_960 == (
        50_331_648 + 3_145_728 + 2_097_152 + 33_554_432)
    assert counts.router_params(cfg) == 1_048_576
    assert counts.expert_params(cfg) == 25_165_824
    assert counts.dense_ffn_params(cfg) == 201_326_592
    assert counts.dense_layer_params(cfg) == 290_455_552
    assert counts.expert_layer_params(cfg, True, 256) == 6_537_871_360
    assert counts.expert_layer_params(cfg, False, 256) == 6_532_628_480
    whole = (290_455_552 + 39 * 6_537_871_360 + 8 * 6_532_628_480
             + 2 * 152_576 * 4096)
    assert counts.model_params(cfg) == whole == 308_778_369_024
    assert abs(whole / 1e9 - 308.78) < 0.01
    published = cfg["parameters_published"]
    assert (published["window_attention"], published["full_attention"],
            published["router"], published["expert"], published["dense_ffn"],
            published["dense_layer"], published["window_expert_layer"],
            published["full_expert_layer"], published["model"]) == (
        94_371_840, 89_128_960, 1_048_576, 25_165_824, 201_326_592,
        290_455_552, 6_537_871_360, 6_532_628_480, whole)
    held = cfg["parameters_held"]
    assert held["window_expert_layer"] == counts.expert_layer_params(
        cfg, True, 16) == 498_073_600
    assert held["full_expert_layer"] == counts.expert_layer_params(
        cfg, False, 16) == 492_830_720
    assert held["embedding_and_head"] == 2 * 19_072 * 4096 == 156_237_824
    assert held["all"] == counts.held_params(cfg) == (
        290_455_552 + 5 * 498_073_600 + 492_830_720 + 156_237_824)
    assert abs(held["all"] * 2 / 1e9 - 6.86) < 0.01
    # a whole expert layer is 13.1 GB: no layer fits uncut
    assert abs(6_537_871_360 * 2 / 1e9 - 13.1) < 0.05
    # the cache: spans of 25,600 x 2,560 B in two layers, rings of 640 x
    # 5,120 B in five, 25 slots
    assert counts.span_bytes_per_position(cfg) == 2 * 2_560
    assert counts.ring_bytes_per_position(cfg) == 5 * 5_120
    assert counts.ring_rows(cfg) == 640
    assert counts.cache_bytes(cfg) == (25 * 25_600 * 2 * 2_560
                                       + 25 * 640 * 5 * 5_120)
    assert abs(25 * 25_600 * 2 * 2_560 / 1e9 - 3.28) < 0.01
    assert abs(25 * 640 * 5 * 5_120 / 1e9 - 0.41) < 0.01
    # with one shape for every layer the same slots would take 19.7 GB
    assert abs(25 * 25_600 * (2 * 2_560 + 5 * 5_120) / 1e9 - 19.7) < 0.05
    # a window row attends min(t + 1, 128) keys
    assert counts.window_pairs(cfg, 0, 512) == 128 * 129 / 2 + 384 * 128
    assert counts.window_pairs(cfg, 24_064, 512) == 512 * 128
    assert counts.window_pairs(cfg, 100, 3) == 101 + 102 + 103
    assert counts.full_pairs(24_064, 512) == 512 * 24_064 + 512 * 513 / 2
    assert counts.window_positions(cfg, 24_064, 512) == 512 + 127
    assert counts.window_positions(cfg, 0, 512) == 512
    assert counts.attention_flops(cfg, 1023, 1) == (
        1024 * 2 + 128 * 5) * 640 * 64
    assert counts.window_attend_flops(cfg, [(24_064, 512)]) == (
        512 * 128 * 640 * 64 * 5)
    assert counts.window_attend_bytes(cfg, [(24_064, 512), (0, 200)]) == (
        (639 + 200) * 25_600)
    # a chunk's work in the window layers is the same at any offset past
    # the window, and all its growth lies in the full layers
    near = counts.prefill_flops(cfg, [(512, 512)], 0)
    far = counts.prefill_flops(cfg, [(24_064, 512)], 0)
    assert far - near == (24_064 - 512) * 512 * 640 * 64 * 2
    one = counts.prefill_flops(cfg, [(0, 512)], held_picks=256)
    assert one == pytest.approx(
        2 * (512 * 857_735_168 + 256 * 25_165_824)
        + (512 * 513 / 2 * 2 + (128 * 129 / 2 + 384 * 128) * 5) * 640 * 64)
    assert counts.nonexpert_params(cfg) == 857_735_168 == (
        5 * 94_371_840 + 2 * 89_128_960 + 6 * 1_048_576 + 201_326_592)
    assert counts.prefill_bytes(cfg, [(1024, 512), (0, 100)], 60) == \
        pytest.approx(2 * 857_735_168 * 2 + 60 * 50_331_648
                      + (1536 + 100) * 5_120 + (639 + 100) * 25_600)
    step = counts.extend_bytes(cfg, runs=1, experts_touched=20,
                               kv_positions=9_000, window_positions=262)
    assert step == pytest.approx(857_735_168 * 2 + 20 * 50_331_648
                                 + 9_000 * 5_120 + 262 * 25_600)


@pytest.mark.parametrize("case", repo_spec.CASES)
def test_benchmark_json_names_the_configuration_the_cell_and_each_reader(
        case):
    spec = repo_spec.load(case)
    cell = repo_spec.by_name(spec["workloads"], REAL_CELL)
    assert cell == {"name": REAL_CELL, "config": CONFIG,
                    "traffic": "mixed-c8", "chips": 1, "why": cell["why"]}
    assert cell["why"] == (
        "8 closed-loop connections, 8-query sessions, histories 128-24,576 "
        "in one queue (10 of 32 past 4,096): 5 window layers on rings of "
        "640 beside 2 full spans; an expert sees 1/16 of its tokens")
    assert len(cell["why"]) <= 200
    config = repo_spec.by_name(spec["configs"], CONFIG)
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert config["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert config["source"] == real_config()["source"].split(" ")[0]
    assert len(config["why"]) <= 200
    for e in ENTRIES:
        repo_spec.assert_names_the_reader(spec, e)
    for name in ("query_p50_ms", "query_rate"):
        assert REAL_CELL in repo_spec.by_name(
            spec["end_to_end"], name)["workloads"]
    # its 95th percentile sits at a gap between two clusters of first
    # queries and reads either side of it from run to run (PERF.md section
    # 2): the cell is not held to it
    assert REAL_CELL not in repo_spec.by_name(
        spec["end_to_end"], "query_p95_ms")["workloads"]
    # the cell joins no accepted per-layer metric's list (MIMO_SPANS.md), and
    # none of four chips came with it
    for m in spec["per_layer"]:
        if m["name"] not in NEW_METRICS:
            assert REAL_CELL not in m.get("workloads", ()), m["name"]
    assert all(w["chips"] == 1 for w in spec["workloads"])
    # appended: the cell's entries stand after everything the benchmark had
    # (``window_p95_ms.lifelong32k-c4`` came later, at PR 54, behind them)
    names = [m["name"] for m in spec["per_layer"]]
    assert max(names.index(n) for n in names
               if n.endswith(".glm") or n == "device_idle_pct.lifelong32k-c4"
               ) < min(names.index(n) for n in NEW_METRICS)


def test_the_configuration_keeps_every_published_number():
    pattern = [0, 1, 1, 1, 1] + [0, 1, 1, 1, 1, 1] * 7 + [0]
    catalog = {
        "attention_bias": False, "attention_chunk_size": 128,
        "attention_value_scale": 0.707,
        "attention_projection_layout": "fused_qkv",
        "add_full_attention_sink_bias": False,
        "add_swa_attention_sink_bias": True, "swa_num_key_value_heads": 8,
        "swa_num_attention_heads": 64, "swa_head_dim": 192,
        "swa_v_head_dim": 128, "head_dim": 192, "hidden_act": "silu",
        "hidden_size": 4096, "hybrid_block_size": None,
        "hybrid_layer_pattern": pattern, "intermediate_size": 16384,
        "layernorm_epsilon": 1e-05, "max_position_embeddings": 1048576,
        "model_type": "mimo_v2", "moe_intermediate_size": 2048,
        "moe_layer_freq": [0] + [1] * 47, "n_group": 1,
        "n_routed_experts": 256, "n_shared_experts": None,
        "norm_topk_prob": True, "num_attention_heads": 64,
        "num_experts_per_tok": 8, "num_hidden_layers": 48,
        "num_key_value_heads": 4, "partial_rotary_factor": 0.334,
        "rope_scaling": {"rope_type": "default", "type": "default"},
        "rope_theta": 10000000, "routed_scaling_factor": None,
        "scoring_func": "sigmoid", "sliding_window": 128,
        "sliding_window_size": 128, "swa_rope_theta": 10000,
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 152576}
    assert len(pattern) == 48 and sum(pattern) == 39
    cfg = real_config()
    differ = {k for k, v in catalog.items() if cfg[k] != v}
    assert differ == {"num_hidden_layers", "n_routed_experts",
                      "vocab_size"} == set(cfg["reduced"])
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (7, 16, 19072)
    assert (cfg["num_hidden_layers_published"],
            cfg["n_routed_experts_published"],
            cfg["vocab_size_published"]) == (48, 256, 152576)
    assert cfg["experts_held"] == [0, 16]
    # the held pattern: one dense full layer, then one whole period at its
    # published place and ratio (five window layers, then a full one)
    assert cfg["hybrid_layer_pattern_held"] == [0, 1, 1, 1, 1, 1, 0]
    assert cfg["moe_layer_freq_held"] == [0, 1, 1, 1, 1, 1, 1]
    places = cfg["layers_held_published_places"]
    assert places == [0, 6, 7, 8, 9, 10, 11]
    assert [pattern[i] for i in places] == cfg["hybrid_layer_pattern_held"]
    assert [cfg["moe_layer_freq"][i] for i in places] \
        == cfg["moe_layer_freq_held"]
    # the floors: a period and at least four layers after the dense one, at
    # least eight routed experts, at least an eighth of the vocabulary
    assert cfg["num_hidden_layers"] - 1 >= 6 >= 4
    assert cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= cfg["vocab_size_published"]
    assert cfg["limits"]["score_err"] > 0 and cfg["limits"]["rank_gap"] > 0
    assert {"rotary", "value_scale", "attention_chunk_size", "qk_norm",
            "hidden_act", "sink", "selection_bias", "towers_and_mtp",
            "weights", "sessions", "cache", "slo"} <= set(cfg["assumed"])
    assert "NOT run" in cfg["assumed"]["towers_and_mtp"]
    assert "NOT used" in cfg["assumed"]["attention_chunk_size"]
    for key in ("source", "deployment", "precision", "equations",
                "limits_set_from"):
        assert cfg[key], key
    assert "16-chip" in cfg["deployment"] and "stages of 7" in cfg[
        "deployment"]
    assert cfg["serve"] == {"n_slots": 24, "capacity": 25088, "chunk": 512,
                            "extend_len": 4, "extend_batch": 8}
    # the program's stack follows from these keys alone
    builder = load_file(os.path.join(BENCHMARKS, "models", "mimorec.py"))
    assert builder.rotary_dims(cfg) == 64 == cfg["assumed_sizes"][
        "rotary_dims"]
    spec = builder.stack_spec(cfg)
    assert [b.mixer for b in spec.blocks] == ["gqa"] + ["gqa_window"] * 5 + [
        "gqa"]
    assert [b.ffn for b in spec.blocks] == ["swiglu"] + ["moe"] * 6
    assert spec.ffn_dim == 16384 and not spec.tied_head
    full, window = spec.gqa, spec.gqa_window
    for dims in (full, window):
        assert (dims.heads, dims.head_dim, dims.v_dim, dims.rope_dims,
                dims.value_scale, dims.qk_norm, dims.block_len) == (
            64, 192, 128, 64, 0.707, False, 1)
        assert dims.scale is None               # 1 / sqrt(192)
    assert (full.kv_heads, full.rope_theta, full.window, full.sink) == (
        4, 1e7, 0, False)
    assert (window.kv_heads, window.rope_theta, window.window,
            window.sink) == (8, 1e4, 128, True)
    assert full.cache_width * 2 == 2560 and window.cache_width * 2 == 5120
    assert (spec.moe.scoring, spec.moe.n_group, spec.moe.topk_group,
            spec.moe.n_router, spec.moe.held, spec.moe.norm_topk,
            spec.moe.scale, spec.moe.shared_dim, spec.moe.top_k) == (
        "sigmoid", 1, 1, 256, (0, 16), True, 1.0, 0, 8)
    broken = builder.stack_spec(cfg, ablate="wide_window").gqa_window
    assert broken.window == builder.WIDE_WINDOW == 2048
    assert not builder.stack_spec(cfg, ablate="no_sink").gqa_window.sink
    assert builder.stack_spec(
        cfg, ablate="full_theta").gqa_window.rope_theta == 1e7
    reference = load_file(os.path.join(BENCHMARKS, "reference",
                                       "mimo_v2_forward.py"))
    dm = reference.dims_of(cfg)
    assert (dm["rot"], dm["K_full"], dm["K_window"], dm["window"],
            dm["scale"], dm["pattern"], dm["moe"]) == (
        64, 4, 8, 128, 1.0, (0, 1, 1, 1, 1, 1, 0), (0, 1, 1, 1, 1, 1, 1))


# -- the reference's one padded length and crossed row --------------------------

def test_the_reference_pads_to_one_length_a_cell_and_compiles_each_part_once(
        harness):
    """Histories of 10 to 200 under ``reach`` 2,100 all pad to 4,096 (one
    block would hold none, so whole fours of blocks): each jitted part of the
    reference compiles ONCE for every length, and the answer is the one the
    history's own padding gives."""
    bench = make_bench(harness)
    reference = bench.load_module("reference", bench.config["reference"])
    assert reference.shapes(24597) == (28672,)
    assert reference.shapes(700) == (1024,)
    assert reference.padded_length(10, 24597) == 28672
    builder = bench.load_module("models", bench.config["engine"])
    weights = builder.make_weights(bench)
    dm = reference.dims_of(bench.config)
    bench.compiles.install()
    rng = np.random.default_rng(3)
    lengths = (10, 57, 130, 200)
    histories = [rng.integers(0, 256, n).tolist() for n in lengths]
    first = reference._forward(weights, histories[0], dm, reach=2100)[0]
    compiled = bench.compiles.count
    rest = [reference._forward(weights, h, dm, reach=2100)[0]
            for h in histories[1:]]
    assert bench.compiles.count == compiled     # no length compiled anew
    for h, got in zip(histories, [first] + rest):
        own = reference.forward(weights, h, dm)[0]
        assert np.abs(got - own).max() <= 1e-5 * np.abs(own).max()


def test_a_crossed_cut_is_recomputed_as_one_row(harness):
    """``crossed_row`` from what a forward kept (every layer's keys and
    values, the last position's inputs) gives the logits of a whole forward
    with the last position routed to other picks in one expert layer, in a
    window layer's and in the full layer's; and ``compare`` holds an answer
    taken on the other side of an open cut to that side."""
    bench = make_bench(harness)
    reference = bench.load_module("reference", bench.config["reference"])
    builder = bench.load_module("models", bench.config["engine"])
    weights = builder.make_weights(bench)
    dm = reference.dims_of(bench.config)
    ids = np.random.default_rng(4).integers(0, 256, 90).tolist()
    kept = []
    logits, _, cuts = reference._forward(weights, ids, dm, reach=300,
                                         kept=kept)
    assert sorted(cuts) == [1, 2, 3, 4, 5, 6] and len(kept) == 7
    k = dm["top_k"]
    for layer in (2, 6):
        ranked, order = cuts[layer]
        picks = np.concatenate([order[:k - 1], order[k:k + 1]]).astype(
            np.int32)                  # the next expert in the last pick's
        whole = reference._forward(weights, ids, dm, reach=300,
                                   crossed={layer: picks})[0]
        row = reference.crossed_row(weights, kept, len(ids), dm,
                                    {layer: picks})
        assert np.abs(row - whole).max() <= 2e-5 * np.abs(whole).max()
        held = [e for e in (order[k - 1], order[k]) if e < 2]
        if held:                       # a held expert changed sides
            assert np.abs(whole - logits).max() > 1e-4 * np.abs(logits).max()
    # sides(): a held expert within the tolerance of the cut opens it
    ranked = np.array([.9, .8, .7, .6004, .6, .5, .4])
    order = np.array([5, 9, 7, 0, 11, 3, 2])
    found = reference.sides(ranked, order, 4, (0, 2))
    assert [sorted(f.tolist()) for f in found] == [[5, 7, 9, 11]]
    assert reference.sides(ranked, order, 4, (20, 2)) == []
    assert reference.sides(np.array([.9, .8, .7, .65, .6, .5, .4]), order, 4,
                           (0, 2)) == []


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
              {"slot": 4, "offset": 512, "tokens": 512}),
             ("pio:seq.step", 80, 150, 1), ("pio:seq.extend", 81, 95, 1),
             ("pio:seq.prefill_chunk", 96, 149, 1),
             ("pio:seq.step", 160, 180, 1), ("pio:seq.extend", 161, 179, 1)]
    trace = make_trace(ps, spans)
    assert read("extend_step_ms.mimo", trace) == pytest.approx(16.0)
    assert read("prefill_chunk_ms.mimo", trace) == pytest.approx(52.0)
    ctx = stats(hit_tokens=900, miss_tokens=100)
    assert read("cache_hit_tokens_pct.mimo", None, **ctx) == \
        pytest.approx(90)
    ctx = stats(prefill_window_blocks=300, extend_window_blocks=150,
                prefill_window_blocks_from0=2000,
                extend_window_blocks_from0=7000)
    assert read("window_blocks_walked_pct.mimo", None, **ctx) == \
        pytest.approx(5.0)
    assert read("device_idle_pct.mixed-c8", None,
                traced={"busy_s": 2.4, "window_s": 3.0}) == pytest.approx(
                    20.0)


def test_the_device_readers_on_hand_built_operations(ps):
    """Two extension programs of 10 ms and one chunk program of 60 ms on the
    device. An extension: 1 ms of a window layer's projections and 2 of its
    walk, 2 of a full layer, 1 + 2 of router and experts, 2 outside any
    scope. The chunk: 4 and 6 of a window layer, 20 of a full layer, 2 + 10
    of router and experts, 8 of the dense FFN, 10 outside. Both programs
    number their fusions alike."""
    ops, modules = [], []
    for t in (0, 20):
        ops += [("%fusion.1", t, t + 1, "seq.layer1.gqa_window_a"),
                ("%fusion.2", t + 1, t + 3, "seq.layer1.gqa_window_a.attend"),
                ("%fusion.3", t + 3, t + 5, "seq.layer6.gqa_a"),
                ("%fusion.4", t + 5, t + 6, "seq.layer1.moe.route"),
                ("%expert_stream.5", t + 6, t + 8, "seq.layer1.moe.experts"),
                ("%copy.6", t + 8, t + 10, None)]
        modules += ["jit__extend_fn"] * 6
    ops += [("%fusion.1", 40, 44, "seq.layer3.gqa_window_a"),
            ("%fusion.2", 44, 50, "seq.layer3.gqa_window_a.attend"),
            ("%fusion.3", 50, 70, "seq.layer0.gqa_a"),
            ("%fusion.4", 70, 72, "seq.layer3.moe.route"),
            ("%expert_groups.5", 72, 82, "seq.layer3.moe.experts"),
            ("%fusion.7", 82, 90, "seq.layer0.ffn_a"),
            ("%copy.6", 90, 100, None)]
    modules += ["jit__prefill_fn"] * 7
    trace = make_trace(ps, [("pio:seq.prefill_chunk", 39, 101, 1,
                             {"offset": 8192, "tokens": 512})], ops)
    for dev in trace.ops.values():
        dev[:] = [o._replace(module=m) for o, m in zip(dev, modules)]
    traced = {"busy_s": 0.080, "window_s": 0.101}
    counts = load_file(os.path.join(BENCHMARKS, "mimo_counts.py"))
    # by program AND instruction (GLM-5's helper): the chunk's %fusion.2 is
    # not the extension's
    by_scope = load_file(os.path.join(BENCHMARKS,
                                      "glm_counts.py")).scope_self_ns
    assert by_scope(ps, trace, ".gqa_window_a.attend") == pytest.approx(10e6)
    assert by_scope(ps, trace, ".gqa_window_a.attend",
                    "extend_fn") == pytest.approx(4e6)
    cfg = real_config()
    ctx = stats(extend_runs=2, extend_tokens=12, extend_held_picks=5,
                extend_experts_touched=9, extend_kv_positions=40_000,
                extend_window_positions=12 + 6 * 127,
                extend_window_blocks=60, prefill_window_blocks=30,
                prefill_tokens=512, prefill_held_picks=260,
                prefill_experts_touched=40)
    assert read("window_attn_device_share_pct.mimo", trace, traced=traced,
                **ctx) == pytest.approx(100.0 * 16 / 80)
    assert read("full_attn_device_share_pct.mimo", trace, traced=traced,
                **ctx) == pytest.approx(100.0 * 24 / 80)
    assert read("moe_device_share_pct.mimo", trace, traced=traced,
                **ctx) == pytest.approx(100.0 * 18 / 80)
    # the walk: the chunk's 512 x 128 pairs and the extensions' 12 x 128, a
    # head and layer; its bytes the 639 + (12 + 6 x 127) positions it needs
    flops = (512 + 12) * 128 * 640 * 64 * 5
    nbytes = (639 + 12 + 6 * 127) * 25_600
    assert flops / 197e12 > nbytes / 819e9
    assert read("window_attend_roofline_pct.mimo", trace, traced=traced,
                **ctx) == pytest.approx(100.0 * flops / 197e12 / 0.010)
    need = counts.extend_bytes(cfg, 2, 9, 40_000, 12 + 6 * 127)
    assert read("extend_roofline_pct.mimo", trace, traced=traced, **ctx) == \
        pytest.approx(100.0 * need / 819e9 / 0.020)
    # the chunk at offset 8,192: its operations bound it, not its bytes
    flop_s = counts.prefill_flops(cfg, [(8192, 512)], 260) / 197e12
    byte_s = counts.prefill_bytes(cfg, [(8192, 512)], 40) / 819e9
    assert flop_s > byte_s
    assert read("prefill_roofline_pct.mimo", trace, traced=traced, **ctx) == \
        pytest.approx(100.0 * flop_s / 0.060)
    # counted too high, or part of the time left out: no reading
    far = stats(**dict(ctx["stats1"], extend_runs=20))
    assert read("extend_roofline_pct.mimo", trace, traced=traced,
                **far) is None


def test_a_reader_returns_none_where_there_is_nothing_to_read(ps):
    """A trace of a program without this engine (PR 25's fixture), counters
    of a program that lacks what this PR counts (the PARENT's, under this
    PR's benchmark files), and a run that was not traced: no number, no
    error."""
    old = ps.load(OLD_FIXTURE, {})
    old_stats = stats(hit_tokens=0, miss_tokens=0, extend_runs=0)
    for name in NEW_METRICS:
        if name != "device_idle_pct.mixed-c8":
            assert read(name, old, traced={"busy_s": 1.0, "window_s": 2.0},
                        **old_stats) is None, name
        assert read(name, None) is None, name
    # the parent's granite trace (PR 34's fixture, recorded on the chip):
    # both serve programs, a causal gqa layer's scope, and none of the
    # window's scopes or counters
    with open(os.path.join(HERE, "fixtures", "hyb_small.scopes.json")) as f:
        hyb = ps.load(os.path.join(HERE, "fixtures", "hyb_small.xplane.pb"),
                      json.load(f))
    with open(os.path.join(HERE, "fixtures", "hyb_small.ctx.json")) as f:
        ctx = json.load(f)
    ctx.update(window_stats0=ctx["stats0"], window_stats1=ctx["stats1"])
    for name in ("prefill_roofline_pct.mimo", "extend_roofline_pct.mimo",
                 "window_attend_roofline_pct.mimo",
                 "window_blocks_walked_pct.mimo",
                 "window_attn_device_share_pct.mimo"):
        assert read(name, hyb, **ctx) is None, name
