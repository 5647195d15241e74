"""A.X-K1's cell (ISSUE 39), rehearsed on the CPU at a tiny size
(tests/benchmarks/tiny_axk: new files and one entry, found by name), its
control, runs with YaRN or the group rule taken out underneath, the
configuration against the published one, ``axk_counts`` at the published
widths, and each new per-layer reader on hand-built events and on a trace
recorded on the chip (fixtures/axk_small.*, made by
benchmarks/tools/record_axk_trace_fixture.py on a TPU v5 lite). A CPU run is
a control-flow check, never a device number."""

import dataclasses
import json
import os

import pytest

from tests.benchmarks import repo_spec
from tests.benchmarks.test_program_spans import (BENCHMARKS, FakeBench, HERE,
                                                 load_file, make_trace)
from tests.benchmarks.test_seq_cell import OLD_FIXTURE, harness  # noqa: F401

TINY = os.path.join(HERE, "tiny_axk")
CELL = "axk-tiny.lifelong-c2"
REAL_CELL = "ax-k1.lifelong-c4"
CONFIG = "ax-k1"
FIXTURE = os.path.join(HERE, "fixtures", "axk_small.xplane.pb")
SCOPES = os.path.join(HERE, "fixtures", "axk_small.scopes.json")
CTX = os.path.join(HERE, "fixtures", "axk_small.ctx.json")


def entry(name, unit, better, source, layer, moves):
    return {"name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": moves, "workloads": [REAL_CELL]}


ENTRIES = [
    entry("extend_step_ms.axk", "ms", "lower", "program_span",
          "sequence engine", "query_p50_ms"),
    entry("prefill_chunk_ms.axk", "ms", "lower", "program_span",
          "sequence engine", "query_p95_ms"),
    entry("cache_hit_tokens_pct.axk", "%", "higher", "program_counter",
          "latent cache", "query_rate"),
    entry("mla_device_share_pct.axk", "%", "lower", "device_trace",
          "sequence programs", "query_rate"),
    entry("moe_device_share_pct.axk", "%", "lower", "device_trace",
          "sequence programs", "query_rate"),
    entry("dense_ffn_device_share_pct.axk", "%", "lower", "device_trace",
          "sequence programs", "query_rate"),
    entry("prefill_roofline_pct.axk", "%", "higher", "device_trace",
          "sequence programs", "query_p95_ms"),
    entry("extend_roofline_pct.axk", "%", "higher", "device_trace",
          "sequence programs", "query_p50_ms"),
    entry("expert_groups_roofline_pct.axk", "%", "higher", "device_trace",
          "sequence programs", "query_p95_ms"),
    entry("device_idle_pct.lifelong-c4", "%", "lower", "device_trace",
          "device", "query_rate"),
    entry("group_hit_tokens_pct.axk", "%", "higher", "program_counter",
          "sequence programs", "query_rate"),
    entry("extend_blocks_over_own.axk", "ratio", "lower", "program_counter",
          "sequence engine", "query_p50_ms"),
]
NEW_METRICS = [e["name"] for e in ENTRIES]
#: the readers that need nothing of the device
ON_THE_CPU = {"extend_step_ms.axk", "prefill_chunk_ms.axk",
              "cache_hit_tokens_pct.axk", "group_hit_tokens_pct.axk",
              "extend_blocks_over_own.axk"}


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
    with open(os.path.join(TINY, "bench", "configs", "axk-tiny.json")) as f:
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
        assert 20 < line["metrics"]["group_hit_tokens_pct.axk"]["value"] < 80
        assert line["metrics"]["extend_blocks_over_own.axk"]["value"] >= 1
    assert any("reference: 8 answers compared" in l
               and "(4 first queries" in l for l in log)
    # the longest history served lies past the original length (64)
    assert any("longest history served 16" in l for l in log)
    counted = next(l for l in log if "engine counters over the window" in l)
    assert "'prefill_group_hit_tokens': 0" not in counted
    assert "'extend_latent_blocks_own': 0" not in counted


def test_the_tiny_tree_lists_the_cells_own_entries_under_its_own_cell():
    with open(os.path.join(TINY, "BENCHMARK.json")) as f:
        tiny = json.load(f)
    assert [dict(m, workloads=[REAL_CELL]) for m in tiny["per_layer"]] \
        == ENTRIES


def test_the_traffic_holds_the_parameters_the_issue_names():
    traffic = load_file(os.path.join(BENCHMARKS, "session_traffic.py"))
    with open(os.path.join(BENCHMARKS, "traffic", "lifelong-c4.json")) as f:
        mix = json.load(f)
    named = {"connections": 4, "queries_per_session": 10, "grow_max": 3,
             "history_quantiles": 32, "history_median": 4096,
             "history_sigma": 0.9, "history_min": 512, "history_max": 24576,
             "topics": 64, "own_topic": 0.8, "zipf_exponent": 1.0,
             "sessions_seed": 39, "warmup_sessions_per_connection": 2,
             "check_sample": 16, "trace_seconds": 3.0}
    assert {k: mix[k] for k in named} == named
    # what the accepted driver and load generator need beside them, and
    # the comparison's budget (ISSUE 48: test_check_budget.py pins the two)
    assert set(mix) - set(named) == {
        "driver", "loop", "num", "prepared_sessions_per_connection",
        "trace_after_go_s", "start", "start_why", "check_budget_s",
        "check_floor"}
    assert (mix["driver"], mix["num"]) == ("session_queries", 10)
    assert [w.get("delay_s", 0.0) for w in mix["start"]] == [0, .05, .05, .05]
    assert sum(w["connections"] for w in mix["start"]) == 4
    lengths = traffic.history_lengths(mix)
    assert (lengths[0], lengths[15], lengths[16], lengths[-2],
            lengths[-1]) == (590, 3954, 4243, 18511, 24576)
    assert sum(lengths) / 32 == pytest.approx(5869, abs=1)
    assert sum(1 for h in lengths if h > 4096) == 16
    assert sum(-(-h // 512) for h in lengths) / 32 == pytest.approx(12.0,
                                                                    abs=0.05)
    # one cycle of the 32 histories a connection is prepared
    assert mix["prepared_sessions_per_connection"] == 32
    cfg = real_config()
    sessions = traffic.Sessions(mix, cfg["vocab_size"])
    longest = lengths.index(24576)
    order = sessions.order(3)
    queries = sessions.session(3, order.index(24576))
    assert len(queries) == 10 and len(queries[0]) == lengths[longest]
    for before, after in zip(queries, queries[1:]):
        assert after[:len(before)] == before
        assert 1 <= len(after) - len(before) <= 3 <= cfg["serve"][
            "extend_len"]
    # the longest session, grown nine times, fits its slot
    assert len(queries[-1]) <= cfg["serve"]["capacity"]
    assert max(queries[-1]) < cfg["vocab_size"]


def test_the_controls_histories_are_the_mixs_shortest(harness):
    bench = make_bench(harness)
    builder = bench.load_module("models", bench.config["engine"])
    histories = builder.control_histories(bench)
    assert len(histories) == 3
    lengths = bench.lib("session_traffic").history_lengths(bench.traffic)
    for want, got in zip(sorted(lengths)[:3], histories):
        assert want <= len(got) <= want + 3 * 3


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


@pytest.mark.parametrize("flag", ["plain_rope", "plain_top_k"])
def test_a_program_without_yarn_or_the_group_rule_makes_the_run_incorrect(
        harness, capsys, monkeypatch, flag):
    """What the builder shows once on the chip (``benchmarks/tools/
    axk_ablation.py``): plain RoPE in YaRN's place, or plain top-k in the
    group rule's, and the comparison must notice."""
    builder = load_file(os.path.join(BENCHMARKS, "models", "axkrec.py"))
    run_builder = harness.load_file(
        os.path.join(BENCHMARKS, "models", "axkrec.py"))
    sound = run_builder.stack_spec
    monkeypatch.setattr(run_builder, "stack_spec",
                        lambda cfg: sound(cfg, **{flag: True}))
    assert dataclasses.asdict(builder.stack_spec(tiny_config())) \
        != dataclasses.asdict(run_builder.stack_spec(tiny_config()))
    code, line, log = run_cell(harness, capsys, "--trace", "0")
    assert code == 0
    assert line["correct"] is False
    assert any(l.startswith("# check score_err") and "FAILED" in l
               for l in log)


def test_a_program_that_lacks_the_router_fails_before_any_weight(
        harness, monkeypatch):
    """The parent's program under this PR's benchmark files: the builder
    hands ``MoEDims`` a field it lacks, before 8 GB of weights are made."""
    from predictionio_tpu.ops import moe

    @dataclasses.dataclass(frozen=True)
    class OldMoEDims:
        dim: int
        expert_dim: int
        n_routed: int
        n_zero: int
        top_k: int
        scale: float
        held: tuple
        norm_topk: bool = False
        shared_dim: int = 0

    monkeypatch.setattr(moe, "MoEDims", OldMoEDims)
    bench = make_bench(harness)
    builder = bench.load_module("models", bench.config["engine"])
    made = []
    monkeypatch.setattr(builder, "make_weights",
                        lambda bench: made.append(1))
    with pytest.raises(TypeError):
        builder.deploy(bench)
    assert not made


def test_axk_counts_at_the_published_widths():
    counts = load_file(os.path.join(BENCHMARKS, "axk_counts.py"))
    cfg = real_config()
    # ISSUE 39's figures (matrices only)
    assert counts.mla_params(cfg) == 101_122_048
    assert counts.expert_params(cfg) == 44_040_192
    assert counts.router_params(cfg) == 1_376_256
    assert counts.expert_layer_params(cfg) == 146_538_496
    assert counts.dense_layer_params(cfg) == 497_483_776
    assert counts.expert_layers(cfg) == 5
    assert counts.nonexpert_params(cfg) == 497_483_776 + 5 * 146_538_496
    published = cfg["parameters_published"]
    assert (published["mla"], published["expert"], published["router"],
            published["expert_layer_outside_routed_experts"],
            published["dense_layer"]) == (
        101_122_048, 44_040_192, 1_376_256, 146_538_496, 497_483_776)
    whole = (497_483_776 + 60 * (146_538_496 + 192 * 44_040_192)
             + 2 * 163_840 * 7168)
    assert abs(whole / 1e9 - 518.98) < 0.01
    held = cfg["parameters_held"]
    assert held["expert_layer"] == 146_538_496 + 12 * 44_040_192
    assert held["all"] == (counts.nonexpert_params(cfg)
                           + 5 * 12 * 44_040_192 + 2 * 20_480 * 7168)
    assert abs(held["all"] * 2 / 1e9 - 8.33) < 0.01
    # 17 slots x 25,600 positions x 6 layers x 640 bfloat16 values
    assert abs(17 * 25_600 * 6 * 640 * 2 / 1e9 - 3.34) < 0.01
    assert counts.latent_bytes_per_position(cfg) == 6 * 576 * 2
    # one position after 1,023 others: 1,024 keys x (2 x 192 + 2 x 128), and
    # 1,024 latents expanded to 256 a head from 512, 64 heads, 6 layers
    assert counts.attention_flops(cfg, 1023, 1) == (
        1024 * 640 + 2 * 1024 * 512 * 256) * 64 * 6
    # ISSUE 39's ~0.23 GFLOP a cached position for a chunk of 512 over six
    # layers (expanding it, and 512 queries attending it)
    per_position = (counts.attention_flops(cfg, 8192, 512)
                    - counts.attention_flops(cfg, 8191, 512))
    assert per_position / 1e9 == pytest.approx(0.226, abs=0.005)
    one = counts.prefill_flops(cfg, [(0, 512)], held_picks=256)
    assert 1.25e12 < one < 1.45e12          # the issue's 1.4 TFLOP of products
    assert counts.prefill_bytes(cfg, [(1024, 512)], 60) == pytest.approx(
        1_230_176_256 * 2 + 60 * 88_080_384 + 1536 * 6912)
    step = counts.extend_bytes(cfg, runs=1, experts_touched=20,
                               latent_blocks=98)
    assert step == pytest.approx(1_230_176_256 * 2 + 20 * 88_080_384
                                 + 98 * 512 * 6912)
    assert counts.extend_flops(cfg, 8, 3, 98, 4) == pytest.approx(
        2 * (8 * 1_230_176_256 + 3 * 44_040_192)
        + 98 * 512 * 4 * 2176 * 64 * 6)
    assert counts.expert_groups_need(cfg, 12, 100) == (
        2.0 * 100 * 44_040_192, 12.0 * 88_080_384)


@pytest.mark.parametrize("case", repo_spec.CASES)
def test_benchmark_json_names_the_configuration_the_cell_and_each_reader(
        case):
    spec = repo_spec.load(case)
    cell = repo_spec.by_name(spec["workloads"], REAL_CELL)
    assert cell == {"name": REAL_CELL, "config": CONFIG,
                    "traffic": "lifelong-c4", "chips": 1,
                    "why": cell["why"]}
    assert len(cell["why"]) <= 200 and "24,576" in cell["why"]
    config = repo_spec.by_name(spec["configs"], CONFIG)
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert config["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert config["source"] == real_config()["source"].split(" ")[0]
    assert len(config["why"]) <= 200
    for e in ENTRIES:
        repo_spec.assert_names_the_reader(spec, e)
    for name in ("query_p50_ms", "query_p95_ms", "query_rate"):
        assert REAL_CELL in repo_spec.by_name(
            spec["end_to_end"], name)["workloads"]
    # the cell joins no accepted per-layer metric's list (AXK_SPANS.md), and
    # none of four chips came with it
    for m in spec["per_layer"]:
        if m["name"] not in NEW_METRICS:
            assert REAL_CELL not in m.get("workloads", ()), m["name"]
    assert all(w["chips"] == 1 for w in spec["workloads"])


def test_the_configuration_keeps_every_published_number():
    catalog = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
        "hidden_act": "silu", "hidden_size": 7168,
        "intermediate_size": 18432, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "model_type": "axk1",
        "moe_intermediate_size": 2048, "moe_layer_freq": 1, "n_group": 8,
        "n_routed_experts": 192, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 64,
        "num_experts_per_tok": 8, "num_hidden_layers": 61,
        "num_key_value_heads": 64, "q_lora_rank": 1536,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_theta": 10000,
        "rope_scaling": {
            "beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 1,
            "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
            "type": "yarn"},
        "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
        "seq_aux": True, "tie_word_embeddings": False, "topk_group": 4,
        "topk_method": "none", "v_head_dim": 128, "vocab_size": 163840}
    cfg = real_config()
    differ = {k for k, v in catalog.items() if cfg[k] != v}
    assert differ == {"num_hidden_layers", "n_routed_experts",
                      "vocab_size"} == set(cfg["reduced"])
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (6, 12, 20480)
    assert (cfg["num_hidden_layers_published"],
            cfg["n_routed_experts_published"],
            cfg["vocab_size_published"]) == (61, 192, 163840)
    assert cfg["experts_held"] == [0, 12]
    # the floors: the dense layer + at least four expert layers, at least
    # eight routed experts, at least an eighth of the vocabulary
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= cfg["vocab_size_published"]
    assert cfg["limits"]["score_err"] > 0 and cfg["limits"]["rank_gap"] > 0
    assert {"topk_method", "hidden_act", "rope_pairing", "weights",
            "sessions", "cache", "slo"} <= set(cfg["assumed"])
    for key in ("source", "deployment", "precision", "equations",
                "limits_set_from"):
        assert cfg[key], key
    assert "16 chips" in cfg["deployment"]
    assert cfg["serve"] == {"n_slots": 16, "capacity": 25088, "chunk": 512,
                            "extend_len": 4, "extend_batch": 4}
    # the program's stack follows from these keys alone
    builder = load_file(os.path.join(BENCHMARKS, "models", "axkrec.py"))
    spec = builder.stack_spec(cfg)
    assert [b.ffn for b in spec.blocks] == ["swiglu"] + ["moe"] * 5
    assert spec.ffn_dim == 18432 and spec.mla.softmax_scale == pytest.approx(
        1.3466 ** 2 / 192 ** 0.5, rel=1e-4)
    assert (spec.moe.scoring, spec.moe.n_group, spec.moe.topk_group,
            spec.moe.n_router, spec.moe.held) == ("sigmoid", 8, 4, 192,
                                                  (0, 12))


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
    assert read("extend_step_ms.axk", trace) == pytest.approx(16.0)
    assert read("prefill_chunk_ms.axk", trace) == pytest.approx(52.0)
    ctx = stats(hit_tokens=900, miss_tokens=100)
    assert read("cache_hit_tokens_pct.axk", None, **ctx) == pytest.approx(90)
    # 1,000 + 200 tokens through five expert layers: 6,000 pairs
    ctx = stats(prefill_group_hit_tokens=2_600, extend_group_hit_tokens=400,
                prefill_tokens=1_000, extend_tokens=200)
    assert read("group_hit_tokens_pct.axk", None, **ctx) == pytest.approx(50)
    ctx = stats(extend_latent_blocks_attended=98, extend_latent_blocks_own=51)
    assert read("extend_blocks_over_own.axk", None, **ctx) == pytest.approx(
        98 / 51)
    assert read("device_idle_pct.lifelong-c4", None,
                traced={"busy_s": 2.4, "window_s": 3.0}) == pytest.approx(
                    20.0)


def test_the_device_readers_on_hand_built_operations(ps):
    """Two extension programs of 10 ms and one chunk program of 40 ms on the
    device: of the 60 ms, 24 under the mixers' scopes, 14 under the routed
    experts', 12 under the dense layer's and the shared expert's, the rest
    outside any."""
    ops, modules = [], {}
    for t in (0, 20):
        ops += [(f"%fusion.{t}", t, t + 4, "seq.layer0.mla_a"),
                (f"%fusion.{t + 1}", t + 4, t + 6, "seq.layer0.ffn_a"),
                (f"%fusion.{t + 2}", t + 6, t + 7, "seq.layer1.moe.route"),
                (f"%expert_stream.{t}", t + 7, t + 8,
                 "seq.layer1.moe.experts"),
                (f"%fusion.{t + 3}", t + 8, t + 9, "seq.layer1.moe.shared"),
                (f"%copy.{t}", t + 9, t + 10, None)]
        modules.update({o[0]: "jit__extend_fn" for o in ops[-6:]})
    ops += [("%while.90", 40, 56, "seq.layer3.mla_a"),
            ("%fusion.91", 56, 60, "seq.layer0.ffn_a"),
            ("%fusion.92", 60, 62, "seq.layer3.moe.route"),
            ("%expert_groups.93", 62, 70, "seq.layer3.moe.experts"),
            ("%fusion.94", 70, 72, "seq.layer3.moe.shared"),
            ("%copy.95", 72, 80, None)]
    trace = make_trace(ps, [("pio:seq.prefill_chunk", 39, 81, 1,
                             {"offset": 8192, "tokens": 512})], ops)
    for dev in trace.ops.values():
        dev[:] = [o._replace(module=modules.get(o.instr, "jit__prefill_fn"))
                  for o in dev]
    traced = {"busy_s": 0.060, "window_s": 0.081}
    assert read("mla_device_share_pct.axk", trace, traced=traced) == \
        pytest.approx(40.0)
    assert read("moe_device_share_pct.axk", trace, traced=traced) == \
        pytest.approx(100 * 14 / 60)
    assert read("dense_ffn_device_share_pct.axk", trace, traced=traced) == \
        pytest.approx(20.0)
    counts = load_file(os.path.join(BENCHMARKS, "axk_counts.py"))
    cfg = real_config()
    ctx = stats(extend_runs=2, extend_tokens=12, extend_held_picks=5,
                extend_experts_touched=9, extend_latent_blocks_attended=120,
                prefill_held_picks=260, prefill_experts_touched=40)
    need = counts.extend_bytes(cfg, 2, 9, 120)
    assert need / 819e9 > counts.extend_flops(cfg, 12, 5, 120, 4) / 197e12
    assert read("extend_roofline_pct.axk", trace, traced=traced, **ctx) == \
        pytest.approx(100.0 * need / 819e9 / 0.020)
    # the chunk at offset 8,192: its operations bound it, not its bytes
    flop_s = counts.prefill_flops(cfg, [(8192, 512)], 260) / 197e12
    byte_s = counts.prefill_bytes(cfg, [(8192, 512)], 40) / 819e9
    assert flop_s > byte_s
    assert read("prefill_roofline_pct.axk", trace, traced=traced, **ctx) == \
        pytest.approx(100.0 * flop_s / 0.040)
    # the grouped kernel: 40 experts' bytes over its own 8 ms
    assert read("expert_groups_roofline_pct.axk", trace, traced=traced,
                **ctx) == pytest.approx(
                    100.0 * 40 * 88_080_384 / 819e9 / 0.008)
    # counted too high, or part of the time left out: no reading
    ctx = stats(extend_runs=20, extend_tokens=0, extend_held_picks=0,
                extend_experts_touched=0, extend_latent_blocks_attended=0)
    assert read("extend_roofline_pct.axk", trace, traced=traced,
                **ctx) is None


@pytest.fixture(scope="module")
def recorded(ps):
    with open(SCOPES) as f:
        trace = ps.load(FIXTURE, json.load(f))
    with open(CTX) as f:
        ctx = json.load(f)
    ctx.update(window_stats0=ctx["stats0"], window_stats1=ctx["stats1"])
    return trace, ctx


def test_every_new_reader_reads_the_recorded_trace(recorded):
    assert os.path.getsize(FIXTURE) <= 1024 * 1024
    trace, ctx = recorded
    got = {name: read(name, trace, tiny_config(), **ctx)
           for name in NEW_METRICS}
    assert all(v is not None for v in got.values()), got
    assert 0 < got["extend_step_ms.axk"] < 1000
    assert 0 < got["prefill_chunk_ms.axk"] < 1000
    # two sessions of four queries: three of them find their history held
    assert 50 < got["cache_hit_tokens_pct.axk"] < 95
    shares = [got["mla_device_share_pct.axk"],
              got["moe_device_share_pct.axk"],
              got["dense_ffn_device_share_pct.axk"]]
    assert all(s > 0 for s in shares) and sum(shares) <= 100
    for name in ("prefill_roofline_pct.axk", "extend_roofline_pct.axk",
                 "expert_groups_roofline_pct.axk"):
        assert 0 < got[name] <= 100, (name, got[name])
    assert 0 < got["device_idle_pct.lifelong-c4"] < 100
    assert 0 < got["group_hit_tokens_pct.axk"] < 100
    assert got["extend_blocks_over_own.axk"] >= 1
    grown = {k: ctx["stats1"][k] - ctx["stats0"][k]
             for k in ("extend_latent_blocks_own",
                       "extend_latent_blocks_attended", "extend_runs")}
    assert grown["extend_latent_blocks_attended"] >= grown[
        "extend_latent_blocks_own"] >= grown["extend_runs"] > 0


def test_a_reader_returns_none_where_there_is_nothing_to_read(ps):
    """A trace of a program without this engine (PR 25's fixture), counters
    of a program that lacks what this PR counts (the PARENT's, under this
    PR's benchmark files), and a run that was not traced: no number, no
    error."""
    old = ps.load(OLD_FIXTURE, {})
    old_stats = stats(hit_tokens=0, miss_tokens=0, extend_runs=0)
    for name in NEW_METRICS:
        if name != "device_idle_pct.lifelong-c4":
            assert read(name, old, traced={"busy_s": 1.0, "window_s": 2.0},
                        **old_stats) is None, name
        assert read(name, None) is None, name
    # the latent-attention engine's trace of before this PR (PR 27's
    # fixture): an extension program, but none of the new counters
    seq = ps.load(os.path.join(HERE, "fixtures", "seq_small.xplane.pb"), {})
    with open(os.path.join(HERE, "fixtures", "seq_small.ctx.json")) as f:
        ctx = json.load(f)
    for name in ("extend_roofline_pct.axk", "group_hit_tokens_pct.axk",
                 "extend_blocks_over_own.axk",
                 "expert_groups_roofline_pct.axk"):
        assert read(name, seq, **ctx) is None, name
