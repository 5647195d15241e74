"""The sequence engine's cell (ISSUE 27), rehearsed on the CPU at a tiny size
(tests/benchmarks/tiny_seq: new files and one entry, found by name), its
control, a run with the cache broken underneath, and each new per-layer
reader on a trace recorded on the chip (fixtures/seq_small.*, made by
benchmarks/tools/record_seq_trace_fixture.py on a TPU v5 lite). A CPU run is
a control-flow check, never a device number."""

import argparse
import importlib.util
import json
import os
import sys
import types

import pytest

from tests.benchmarks import repo_spec

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
BENCHMARKS = os.path.join(REPO, "benchmarks")
RUN = os.path.join(BENCHMARKS, "run.py")
TINY = os.path.join(HERE, "tiny_seq")
CELL = "longcat-tiny.sessions-c4"
REAL_CELL = "longcat-flash-chat.sessions-c8"
FIXTURE = os.path.join(HERE, "fixtures", "seq_small.xplane.pb")

NEW_METRICS = {
    "extend_step_ms.seq", "extend_wait_ms.seq", "prefill_chunk_ms.seq",
    "cache_hit_tokens_pct.seq", "zero_expert_pick_pct.seq",
    "expert_load_max_over_mean.seq", "mla_device_share_pct.seq",
    "moe_device_share_pct.seq", "dense_ffn_device_share_pct.seq",
    "prefill_roofline_pct.seq", "extend_roofline_pct.seq",
    "device_idle_pct.sessions-c8", "front_self_ms.seq",
    "topk_dot_roofline_pct.seq"}


def load_file(path):
    name = "_seq_under_test_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path, REPO))
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        module = importlib.util.module_from_spec(spec)
        sys.modules[name] = module
        spec.loader.exec_module(module)
    return sys.modules[name]


@pytest.fixture(scope="module")
def harness():
    return load_file(RUN)


def run_cell(harness, capsys, *extra, seed=5000000011):
    code = harness.main(["--bench-root", TINY, "--rehearse-cpu",
                         "--workload", CELL, "--seed", str(seed),
                         "--seconds", "1", *extra])
    lines = capsys.readouterr().out.strip().splitlines()
    return code, json.loads(lines[-1]), lines


def make_bench(harness, seed=7):
    with open(os.path.join(TINY, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    bench = harness.Bench(TINY, spec, cell, argparse.Namespace(
        seed=seed, seconds=1, trace=0))
    import jax
    bench.devices = jax.devices()[:1]
    return bench


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
    # half first queries, half extensions were held against the reference
    assert any("reference: 8 answers compared" in l
               and "(4 first queries" in l for l in log)


def test_the_sessions_are_the_same_for_every_seed_and_as_the_mix_says():
    traffic = load_file(os.path.join(BENCHMARKS, "session_traffic.py"))
    with open(os.path.join(BENCHMARKS, "traffic", "sessions-c8.json")) as f:
        mix = json.load(f)
    lengths = traffic.history_lengths(mix)
    assert len(lengths) == 32 and min(lengths) >= 128
    assert 5600 <= max(lengths) <= 5800
    assert 1350 <= sum(lengths) / 32 <= 1450
    sessions = traffic.Sessions(mix, 16384)
    assert sorted(sessions.order(3)) == sorted(lengths)
    assert sessions.order(3) != sessions.order(4)
    queries = sessions.session(2, 5)
    assert len(queries) == 6
    assert queries == traffic.Sessions(mix, 16384).session(2, 5)
    for before, after in zip(queries, queries[1:]):
        assert after[:len(before)] == before
        assert 1 <= len(after) - len(before) <= 3
    topic = max(set(r // 256 for r in queries[0]),
                key=[r // 256 for r in queries[0]].count)
    own = sum(1 for r in queries[0] if r // 256 == topic) / len(queries[0])
    assert 0.7 <= own <= 0.9
    assert max(queries[-1]) < 16384


def test_the_control_in_a_lower_precision_fails_a_limit(harness):
    bench = make_bench(harness)
    reference = bench.load_module("reference", bench.config["reference"])
    readings = reference.control(bench)
    limits = bench.config["limits"]
    for name in ("bfloat16", "float8_e4m3fn"):
        got = readings[name]
        assert got["compared"] > 0
        assert (got["score_err"] > limits["score_err"]
                or got["rank_gap"] > limits["rank_gap"]), (name, got)


def test_a_cache_that_returns_stale_latents_makes_the_run_incorrect(
        harness, capsys, monkeypatch):
    """Every slot claims to hold the whole query: the answers come from
    another session's latents, and the comparison must notice."""
    from predictionio_tpu.models import sessionrec

    monkeypatch.setattr(
        sessionrec.LatentCache, "common_prefix",
        staticmethod(lambda held, rows: min(len(held), len(rows))))
    code, line, log = run_cell(harness, capsys, "--trace", "0")
    assert code == 0
    assert line["correct"] is False
    assert any(l.startswith("# check score_err") and "FAILED" in l
               for l in log)


def test_seq_counts_at_the_published_widths():
    counts = load_file(os.path.join(BENCHMARKS, "seq_counts.py"))
    with open(os.path.join(BENCHMARKS, "configs",
                           "longcat-flash-chat.json")) as f:
        cfg = json.load(f)
    assert counts.mla_params(cfg) == 90_570_752
    assert counts.dense_ffn_params(cfg) == 226_492_416
    assert counts.router_params(cfg) == 4_718_592
    assert counts.expert_params(cfg) == 37_748_736
    # ISSUE 27's table: 5.1 GB of non-expert weights in bfloat16
    assert abs(counts.nonexpert_params(cfg) * 2 / 1e9 - 5.11) < 0.01
    # one position after 1,023 others: 1,024 keys x 640 x 64 heads x 8 blocks
    assert counts.attention_flops(cfg, 1023, 1) == 1024 * 640 * 64 * 8
    one = counts.prefill_flops(cfg, [(0, 512)], held_picks=128)
    assert 2.6e12 < one < 2.8e12
    step = counts.extend_bytes(cfg, runs=1, experts_touched=8,
                               latent_positions=3000)
    assert abs(step - (counts.nonexpert_params(cfg) * 2
                       + 8 * 75_497_472 + 3000 * 576 * 2 * 8)) < 1


@pytest.mark.parametrize("case", repo_spec.CASES)
def test_benchmark_json_names_the_cell_and_each_reader(case):
    spec = repo_spec.load(case)
    cell = repo_spec.by_name(spec["workloads"], REAL_CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    by_name = {m["name"]: m for m in spec["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [REAL_CELL], name
        assert os.path.isfile(os.path.join(BENCHMARKS, "layer_metrics",
                                           name + ".py")), name
    # no accepted metric without a list is asked of the new cell
    for m in spec["per_layer"]:
        if "workloads" not in m:
            assert m["moves"] == "train_rate", m["name"]
        elif m["name"] not in NEW_METRICS:
            assert REAL_CELL not in m["workloads"], m["name"]
    for m in spec["end_to_end"]:
        if m["name"] in ("query_p50_ms", "query_p95_ms", "query_rate"):
            assert REAL_CELL in m["workloads"]
    # the step worker's own splits are host-clock readings the program
    # took, not spans of the trace
    assert by_name["extend_wait_ms.seq"]["source"] == "program_counter"
    assert by_name["front_self_ms.seq"]["source"] == "program_span"
    assert by_name["topk_dot_roofline_pct.seq"]["source"] == "device_trace"
    config = repo_spec.by_name(spec["configs"], "longcat-flash-chat")
    assert config["reduced"] == ["num_layers", "n_routed_experts",
                                 "vocab_size"]


def test_the_configuration_keeps_every_published_number():
    catalog = {
        "attention_bias": False, "hidden_size": 6144,
        "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
        "num_attention_heads": 64, "kv_lora_rank": 512, "q_lora_rank": 1536,
        "qk_rope_head_dim": 64, "v_head_dim": 128, "qk_nope_head_dim": 128,
        "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
        "routed_scaling_factor": 6, "max_position_embeddings": 131072,
        "rms_norm_eps": 1e-05, "rope_theta": 10000000,
        "attention_method": "MLA", "zero_expert_num": 256,
        "zero_expert_type": "identity", "moe_topk": 12}
    with open(os.path.join(BENCHMARKS, "configs",
                           "longcat-flash-chat.json")) as f:
        cfg = json.load(f)
    for key, value in catalog.items():
        assert cfg[key] == value, key
    assert (cfg["num_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (4, 16, 16384)
    assert set(cfg["reduced"]) == {"num_layers", "n_routed_experts",
                                   "vocab_size"}
    assert cfg["limits"]["score_err"] > 0 and cfg["limits"]["rank_gap"] > 0


# -- the readers on a trace recorded on the chip -------------------------------

SCOPES = os.path.join(HERE, "fixtures", "seq_small.scopes.json")
CTX = os.path.join(HERE, "fixtures", "seq_small.ctx.json")
OLD_FIXTURE = os.path.join(HERE, "fixtures", "pio_small.xplane.pb")


class FakeBench:
    """What a reader uses of run.py's Bench."""

    devices = [types.SimpleNamespace(device_kind="TPU v5 lite")]

    def __init__(self, config, scratch="/nonexistent"):
        self.config, self.scratch = config, scratch

    def lib(self, name):
        return load_file(os.path.join(BENCHMARKS, name + ".py"))


def tiny_config():
    with open(os.path.join(TINY, "bench", "configs",
                           "longcat-tiny.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def recorded():
    ps = load_file(os.path.join(BENCHMARKS, "program_spans.py"))
    with open(SCOPES) as f:
        trace = ps.load(FIXTURE, json.load(f))
    with open(CTX) as f:
        ctx = json.load(f)
    return trace, ctx


def read(metric, trace, ctx):
    reader = load_file(os.path.join(BENCHMARKS, "layer_metrics",
                                    metric + ".py"))
    return reader.read({"bench": FakeBench(tiny_config()),
                        "_program_spans": trace, **ctx})


def test_the_fixture_is_small_enough_to_commit():
    assert os.path.getsize(FIXTURE) <= 1024 * 1024


def test_every_new_reader_reads_the_recorded_trace(recorded):
    trace, ctx = recorded
    got = {name: read(name, trace, ctx) for name in NEW_METRICS}
    assert all(v is not None for v in got.values()), got
    assert 0 < got["extend_step_ms.seq"] < 1000
    assert 0 < got["prefill_chunk_ms.seq"] < 1000
    assert 0 <= got["extend_wait_ms.seq"] < 1000
    # two sessions of six queries: the extensions find their history cached
    assert 50 < got["cache_hit_tokens_pct.seq"] < 95
    # 8 of the tiny router's 24 outputs are zero-compute
    assert 15 < got["zero_expert_pick_pct.seq"] < 55
    assert 1 <= got["expert_load_max_over_mean.seq"] <= 4
    shares = [got[f"{part}_device_share_pct.seq"]
              for part in ("mla", "moe", "dense_ffn")]
    assert all(s > 0 for s in shares) and sum(shares) <= 100
    for name in ("prefill_roofline_pct.seq", "extend_roofline_pct.seq"):
        assert 0 < got[name] <= 100, (name, got[name])
    assert 0 < got["device_idle_pct.sessions-c8"] < 100
    # twelve requests over HTTP, ten searches of the head's kernel
    assert 0 < got["front_self_ms.seq"] < 100
    assert 0 < got["topk_dot_roofline_pct.seq"] <= 100


def test_a_reader_returns_none_where_there_is_nothing_to_read():
    """A trace of a program without this engine (the parent commit's, PR
    25's fixture), and a run that was not traced: no number, no error."""
    ps = load_file(os.path.join(BENCHMARKS, "program_spans.py"))
    old = ps.load(OLD_FIXTURE, {})
    # the HTTP front and the head's kernel are shared with the ALS engine:
    # their readers find their spans and operations in its trace too
    shared = {"front_self_ms.seq", "topk_dot_roofline_pct.seq"}
    for name in NEW_METRICS - {"device_idle_pct.sessions-c8"}:
        if name not in shared:
            assert read(name, old, {"traced": {"busy_s": 1.0,
                                               "window_s": 2.0}}
                        ) is None, name
        assert read(name, None, {}) is None, name
