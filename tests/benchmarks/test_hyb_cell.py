"""The hybrid stack's cell (ISSUE 34), rehearsed on the CPU at a tiny size
(tests/benchmarks/tiny_hyb: new files and one entry, found by name), its
control, runs with the state handling broken underneath, the configuration
against the published one, ``hyb_counts`` at the published widths, and each
new per-layer reader on hand-built events and on a trace recorded on the chip
(fixtures/hyb_small.*, made by benchmarks/tools/record_hyb_trace_fixture.py on
a TPU v5 lite). A CPU run is a control-flow check, never a device number."""

import json
import os

import pytest

from tests.benchmarks import repo_spec
from tests.benchmarks.test_program_spans import (BENCHMARKS, FakeBench, HERE,
                                                 load_file, make_trace)
from tests.benchmarks.test_seq_cell import OLD_FIXTURE, harness  # noqa: F401

TINY = os.path.join(HERE, "tiny_hyb")
CELL = "granite-tiny.sessions-c4"
REAL_CELL = "granite-4.0-h-small.sessions-c16"
CONFIG = "granite-4.0-h-small"
FIXTURE = os.path.join(HERE, "fixtures", "hyb_small.xplane.pb")
SCOPES = os.path.join(HERE, "fixtures", "hyb_small.scopes.json")
CTX = os.path.join(HERE, "fixtures", "hyb_small.ctx.json")


def entry(name, unit, better, source, layer, moves):
    return {"name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": moves, "workloads": [REAL_CELL]}


ENTRIES = [
    entry("extend_step_ms.hyb", "ms", "lower", "program_span",
          "sequence engine", "query_p50_ms"),
    entry("prefill_chunk_ms.hyb", "ms", "lower", "program_span",
          "sequence engine", "query_p95_ms"),
    entry("extend_rows_mean.hyb", "rows", "higher", "program_counter",
          "sequence engine", "query_rate"),
    entry("cache_hit_tokens_pct.hyb", "%", "higher", "program_counter",
          "latent cache", "query_rate"),
    entry("ssm_device_share_pct.hyb", "%", "lower", "device_trace",
          "sequence programs", "query_rate"),
    entry("moe_device_share_pct.hyb", "%", "lower", "device_trace",
          "sequence programs", "query_rate"),
    entry("extend_roofline_pct.hyb", "%", "higher", "device_trace",
          "sequence programs", "query_p50_ms"),
    entry("prefill_roofline_pct.hyb", "%", "higher", "device_trace",
          "sequence programs", "query_p95_ms"),
    entry("device_idle_pct.sessions-c16", "%", "lower", "device_trace",
          "device", "query_rate"),
]
NEW_METRICS = [e["name"] for e in ENTRIES]


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
    with open(os.path.join(TINY, "bench", "configs",
                           "granite-tiny.json")) as f:
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
    assert any("reference: 8 answers compared" in l
               and "(4 first queries" in l for l in log)
    # sessions only grow: every hit resumes a state, nothing is rewound
    counted = next(l for l in log if "engine counters over the window" in l)
    assert "'rewind_misses': 0" in counted
    assert "'state_resumes': 0" not in counted


def test_the_traffic_is_sessions_c8s_users_against_sixteen_connections():
    traffic = load_file(os.path.join(BENCHMARKS, "session_traffic.py"))
    with open(os.path.join(BENCHMARKS, "traffic", "sessions-c16.json")) as f:
        mix = json.load(f)
    with open(os.path.join(BENCHMARKS, "traffic", "sessions-c8.json")) as f:
        c8 = json.load(f)
    assert traffic.history_lengths(mix) == traffic.history_lengths(c8)
    assert (mix["connections"], mix["queries_per_session"],
            mix["sessions_seed"], mix["num"]) == (16, 8, 34, 10)
    assert len(mix["start"]) == 16
    assert sum(w["connections"] for w in mix["start"]) == 16
    assert {w.get("delay_s", 0.05) for w in mix["start"]} == {0.05}
    cfg = real_config()
    sessions = traffic.Sessions(mix, cfg["vocab_size"])
    queries = sessions.session(11, 5)
    assert len(queries) == 8
    for before, after in zip(queries, queries[1:]):
        assert after[:len(before)] == before
        assert 1 <= len(after) - len(before) <= 3 <= cfg["serve"][
            "extend_len"]
    assert max(queries[-1]) < cfg["vocab_size"]
    # an extension batch's tokens fit one expert tile: the streamed form
    assert cfg["serve"]["extend_batch"] * cfg["serve"]["extend_len"] == 64


def test_the_control_in_a_lower_precision_fails_a_limit(harness):
    bench = make_bench(harness)
    reference = bench.load_module("reference", bench.config["reference"])
    readings = reference.control(bench)
    limits = bench.config["limits"]
    assert set(readings) == {"bfloat16", "float8_e4m3fn", "state_bfloat16"}
    for name, got in readings.items():
        assert got["compared"] > 0
        if name != "state_bfloat16":
            assert (got["score_err"] > limits["score_err"]
                    or got["rank_gap"] > limits["rank_gap"]), (name, got)
    # a state carried in bfloat16 is a reading of its own, and not nothing
    assert readings["state_bfloat16"]["score_err"] > 0


@pytest.mark.parametrize("broken", ["fresh", "steps_of"])
def test_a_state_handled_wrongly_makes_the_run_incorrect(
        harness, capsys, monkeypatch, broken):
    """``fresh``: a miss resumes from whatever state its slot held;
    ``steps_of``: a chunk's padding positions advance the state. The answers
    then come from another history than the query's, and the comparison
    must notice."""
    from predictionio_tpu.ops import ssm

    if broken == "fresh":
        monkeypatch.setattr(ssm, "fresh", lambda state, is_start: state)
    else:
        monkeypatch.setattr(ssm, "steps_of", lambda dt, valid: dt)
    code, line, log = run_cell(harness, capsys, "--trace", "0")
    assert code == 0
    assert line["correct"] is False
    assert any(l.startswith("# check score_err") and "FAILED" in l
               for l in log)


def test_hyb_counts_at_the_published_widths():
    counts = load_file(os.path.join(BENCHMARKS, "hyb_counts.py"))
    cfg = real_config()
    assert counts.mamba_params(cfg) == 102_286_976
    assert counts.attention_params(cfg) == 41_943_040
    assert counts.shared_params(cfg) == 18_874_368
    assert counts.router_params(cfg) == 294_912
    assert counts.expert_params(cfg) == 9_437_184
    # ISSUE 34's table: 2.31 GB a step outside the experts, 1.84 of it the
    # nine Mamba-2 mixers; the period with 36 experts a layer 9.10 GB
    assert counts.nonexpert_params(cfg) == 1_154_300_544
    assert abs(9 * counts.mamba_params(cfg) * 2 / 1e9 - 1.84) < 0.01
    period = counts.nonexpert_params(cfg) + 10 * 36 * counts.expert_params(
        cfg)
    assert period == 4_551_686_784
    assert counts.state_bytes_per_row(cfg) == 9 * (128 * 64 * 128 * 4
                                                   + 3 * 8448 * 2)
    assert counts.kv_bytes_per_position(cfg) == 4096
    step = counts.extend_bytes(cfg, runs=1, experts_touched=250,
                               state_rows=4, kv_positions=6000)
    assert step == pytest.approx(
        1_154_300_544 * 2 + 250 * 18_874_368 + 8 * 38_204_928
        + 6000 * 4096)
    # one position after 1,023 others: 1,024 keys x 4 x 128 x 32 heads
    assert counts.attention_flops(cfg, 1023, 1) == 1024 * 512 * 32
    one = counts.prefill_flops(cfg, [(0, 512)], held_picks=25_600)
    assert 1.6e12 < one < 1.8e12
    assert counts.prefill_bytes(cfg, [(0, 512)], 360) == pytest.approx(
        1_154_300_544 * 2 + 2 * 38_204_928 + 360 * 18_874_368 + 512 * 4096)


@pytest.mark.parametrize("case", repo_spec.CASES)
def test_benchmark_json_names_the_configuration_the_cell_and_each_reader(
        case):
    spec = repo_spec.load(case)
    cell = repo_spec.by_name(spec["workloads"], REAL_CELL)
    assert cell == {"name": REAL_CELL, "config": CONFIG,
                    "traffic": "sessions-c16", "chips": 1,
                    "why": cell["why"]}
    assert len(cell["why"]) <= 200 and "half" in cell["why"]
    config = repo_spec.by_name(spec["configs"], CONFIG)
    assert config["reduced"] == ["num_hidden_layers", "num_local_experts",
                                 "vocab_size"]
    assert config["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert config["source"] == real_config()["source"].split(" ")[0]
    assert len(config["why"]) <= 200
    for e in ENTRIES:
        repo_spec.assert_names_the_reader(spec, e)
    for name in ("query_p50_ms", "query_p95_ms", "query_rate"):
        assert REAL_CELL in repo_spec.by_name(
            spec["end_to_end"], name)["workloads"]
    # the cell joins no accepted per-layer metric's list (HYB_SPANS.md)
    for m in spec["per_layer"]:
        if m["name"] not in NEW_METRICS:
            assert REAL_CELL not in m.get("workloads", ()), m["name"]


def test_the_configuration_keeps_every_published_number():
    kinds = (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4
    catalog = {
        "attention_bias": False, "attention_multiplier": 0.0078125,
        "embedding_multiplier": 12, "hidden_act": "silu",
        "hidden_size": 4096, "intermediate_size": 768, "layer_types": kinds,
        "logits_scaling": 16, "mamba_chunk_size": 256,
        "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64,
        "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
        "mamba_n_heads": 128, "mamba_proj_bias": False,
        "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
        "normalization_function": "rmsnorm", "num_attention_heads": 32,
        "num_experts_per_tok": 10, "num_hidden_layers": 40,
        "num_key_value_heads": 8, "num_local_experts": 72,
        "position_embedding_type": "nope", "residual_multiplier": 0.22,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "shared_intermediate_size": 1536, "tie_word_embeddings": True,
        "vocab_size": 100352}
    cfg = real_config()
    differ = {k for k, v in catalog.items() if cfg[k] != v}
    assert differ == {"num_hidden_layers", "num_local_experts",
                      "vocab_size"} == set(cfg["reduced"])
    assert (cfg["num_hidden_layers"], cfg["num_local_experts"],
            cfg["vocab_size"]) == (10, 36, 50176)
    assert (cfg["num_hidden_layers_published"],
            cfg["num_local_experts_published"],
            cfg["vocab_size_published"]) == (40, 72, 100352)
    assert cfg["experts_held"] == [0, 36]
    # one whole period: nine state-space layers and the attention layer
    held = cfg["layer_types"][:cfg["num_hidden_layers"]]
    assert held.count("mamba") == 9 and held[5] == "attention"
    assert cfg["limits"]["score_err"] > 0 and cfg["limits"]["rank_gap"] > 0
    assert {"state_precision", "weights", "sessions", "cache",
            "slo"} <= set(cfg["assumed"])
    for key in ("source", "deployment", "precision", "equations"):
        assert cfg[key], key
    assert cfg["serve"] == {"n_slots": 32, "capacity": 8192, "chunk": 512,
                            "extend_len": 4, "extend_batch": 16}


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
    assert read("extend_step_ms.hyb", trace) == pytest.approx(16.0)
    assert read("prefill_chunk_ms.hyb", trace) == pytest.approx(52.0)
    ctx = stats(extend_rows=270, extend_runs=120)
    assert read("extend_rows_mean.hyb", None, **ctx) == pytest.approx(2.25)
    ctx = stats(hit_tokens=700, miss_tokens=100)
    assert read("cache_hit_tokens_pct.hyb", None, **ctx) == pytest.approx(
        87.5)
    assert read("device_idle_pct.sessions-c16", None,
                traced={"busy_s": 2.1, "window_s": 3.0}) == pytest.approx(
                    30.0)


def test_the_device_readers_on_hand_built_operations(ps):
    """Two extension programs of 10 ms and one chunk program of 40 ms on the
    device: of the 60 ms, 15 under the state-space scopes, 30 under the
    expert layer's, 3 under attention's, the rest outside any."""
    ops, modules = [], {}
    for t in (0, 20):
        ops += [(f"%fusion.{t}", t, t + 2, "seq.layer0.mamba2_a.ssm.in_proj"),
                (f"%while.{t}", t + 2, t + 3, "seq.layer0.mamba2_a.ssm.scan"),
                (f"%expert_stream.{t}", t + 3, t + 8,
                 "seq.layer1.moe.experts"),
                (f"%fusion.{t + 1}", t + 8, t + 9, "seq.layer5.gqa_a"),
                (f"%copy.{t}", t + 9, t + 10, None)]
        modules.update({o[0]: "jit__extend_fn" for o in ops[-5:]})
    ops += [("%fusion.90", 40, 45, "seq.layer2.mamba2_a.ssm.out_proj"),
            ("%while.91", 45, 49, "seq.layer2.mamba2_a.ssm.scan"),
            ("%while.92", 49, 68, "seq.layer2.moe.experts"),
            ("%fusion.93", 68, 69, "seq.layer2.moe.shared"),
            ("%fusion.94", 69, 70, "seq.layer5.gqa_a"),
            ("%copy.95", 70, 80, None)]
    trace = make_trace(ps, [("pio:seq.prefill_chunk", 39, 81, 1,
                             {"offset": 1024, "tokens": 400})], ops)
    for dev in trace.ops.values():
        dev[:] = [o._replace(module=modules.get(o.instr, "jit__prefill_fn"))
                  for o in dev]
    traced = {"busy_s": 0.060, "window_s": 0.081}
    assert read("ssm_device_share_pct.hyb", trace, traced=traced) == \
        pytest.approx(25.0)
    assert read("moe_device_share_pct.hyb", trace, traced=traced) == \
        pytest.approx(50.0)
    counts = load_file(os.path.join(BENCHMARKS, "hyb_counts.py"))
    cfg = real_config()
    ctx = stats(extend_runs=2, extend_experts_touched=500,
                extend_state_rows=7, extend_kv_positions=9_000,
                prefill_held_picks=20_000, prefill_experts_touched=350)
    need = counts.extend_bytes(cfg, 2, 500, 7, 9_000)
    assert read("extend_roofline_pct.hyb", trace, traced=traced, **ctx) == \
        pytest.approx(100.0 * need / 819e9 / 0.020)
    # the chunk: the larger of its operations' time and its bytes' time
    flop_s = counts.prefill_flops(cfg, [(1024, 400)], 20_000) / 197e12
    byte_s = counts.prefill_bytes(cfg, [(1024, 400)], 350) / 819e9
    assert byte_s > flop_s          # 350 experts' weights for 400 tokens
    assert read("prefill_roofline_pct.hyb", trace, traced=traced, **ctx) == \
        pytest.approx(100.0 * byte_s / 0.040)
    # counted too high, or part of the time left out: no reading
    ctx = stats(extend_runs=20, extend_experts_touched=0,
                extend_state_rows=0, extend_kv_positions=0)
    assert read("extend_roofline_pct.hyb", trace, traced=traced,
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
    assert 0 < got["extend_step_ms.hyb"] < 1000
    assert 0 < got["prefill_chunk_ms.hyb"] < 1000
    # two connections: an extension program serves one or two sessions
    assert 1 <= got["extend_rows_mean.hyb"] <= 2
    # two sessions of eight queries: seven of them find their history held
    assert 60 < got["cache_hit_tokens_pct.hyb"] < 95
    shares = [got["ssm_device_share_pct.hyb"],
              got["moe_device_share_pct.hyb"]]
    assert all(s > 0 for s in shares) and sum(shares) <= 100
    for name in ("prefill_roofline_pct.hyb", "extend_roofline_pct.hyb"):
        assert 0 < got[name] <= 100, (name, got[name])
    assert 0 < got["device_idle_pct.sessions-c16"] < 100
    # sessions only grew under the window: every hit resumed a state
    grown = {k: ctx["stats1"][k] - ctx["stats0"][k]
             for k in ("state_resumes", "rewind_misses", "extend_state_rows",
                       "extend_rows")}
    assert grown["state_resumes"] == 14 and grown["rewind_misses"] == 0
    # the two histories' last few positions ran through the extension
    # program too, from the state their chunks left
    assert grown["extend_state_rows"] == grown["extend_rows"] == 16


def test_a_reader_returns_none_where_there_is_nothing_to_read(ps):
    """A trace of a program without this engine (PR 25's fixture), counters
    of a program that lacks what this PR counts, and a run that was not
    traced: no number, no error."""
    old = ps.load(OLD_FIXTURE, {})
    old_stats = stats(hit_tokens=0, miss_tokens=0, extend_runs=0)
    for name in NEW_METRICS:
        if name != "device_idle_pct.sessions-c16":
            assert read(name, old, traced={"busy_s": 1.0, "window_s": 2.0},
                        **old_stats) is None, name
        assert read(name, None) is None, name
    # the latent-attention engine's trace (PR 27's fixture) has an extension
    # program but no state rows: the hybrid roofline reads nothing there
    seq = ps.load(os.path.join(HERE, "fixtures", "seq_small.xplane.pb"), {})
    with open(os.path.join(HERE, "fixtures", "seq_small.ctx.json")) as f:
        ctx = json.load(f)
    assert read("extend_roofline_pct.hyb", seq, **ctx) is None
    assert read("ssm_device_share_pct.hyb", seq, **ctx) is None
