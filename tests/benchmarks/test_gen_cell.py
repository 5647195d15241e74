"""The block-diffusion cell (ISSUE 32), rehearsed on the CPU at a tiny size
(tests/benchmarks/tiny_gen: new files and one entry, found by name), its
control, runs with the timed path broken underneath (a commit that never
reaches the session's slot; the least confident positions unmasked), and each
new per-layer reader over hand-built events, on a trace recorded on the chip
(fixtures/gen_small.*, made by benchmarks/tools/record_gen_trace_fixture.py
on a TPU v5 lite), where there is nothing to read, and by its entry's name. A
CPU run is a control-flow check, never a device number."""

import json
import os

import pytest

from tests.benchmarks import repo_spec
from tests.benchmarks.test_program_spans import (BENCHMARKS, FakeBench, HERE,
                                                 load_file, make_trace)
from tests.benchmarks.test_seq_cell import OLD_FIXTURE, harness  # noqa: F401

REPO = os.path.dirname(os.path.dirname(HERE))
TINY = os.path.join(HERE, "tiny_gen")
CELL = "sdar-tiny.slates-c4"
REAL_CELL = "sdar-30b-a3b-chat.slates-c8"
CONFIG = "sdar-30b-a3b-chat"
FIXTURE = os.path.join(HERE, "fixtures", "gen_small.xplane.pb")
SCOPES = os.path.join(HERE, "fixtures", "gen_small.scopes.json")
CTX = os.path.join(HERE, "fixtures", "gen_small.ctx.json")


def entry(name, unit, better, source, layer, moves):
    return {"name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": moves, "workloads": [REAL_CELL]}


ENTRIES = [
    entry("block_step_ms.gen", "ms", "lower", "program_span",
          "sequence engine", "query_p50_ms"),
    entry("forwards_per_item.gen", "forwards/item", "lower",
          "program_counter", "sequence engine", "query_rate"),
    entry("experts_touched_pct.gen", "%", "lower", "program_counter",
          "sequence programs", "query_rate"),
    entry("gqa_device_share_pct.gen", "%", "lower", "device_trace",
          "sequence programs", "query_rate"),
    entry("head_device_share_pct.gen", "%", "lower", "device_trace",
          "sequence programs", "query_rate"),
    entry("moe_device_share_pct.gen", "%", "lower", "device_trace",
          "sequence programs", "query_rate"),
    entry("block_roofline_pct.gen", "%", "higher", "device_trace",
          "sequence programs", "query_p50_ms"),
    entry("cache_hit_tokens_pct.gen", "%", "higher", "program_counter",
          "latent cache", "query_rate"),
    entry("device_idle_pct.slates-c8", "%", "lower", "device_trace",
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


@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_cell_runs_from_new_files_and_prints_the_contracts_line(
        harness, capsys, trace):
    code, line, log = run_cell(harness, capsys, "--trace", trace)
    assert code == 0
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert any("compilations inside the window: 0" in l for l in log)
    checks = [l.split()[2].rstrip(":") for l in log
              if l.startswith("# check ")]
    assert checks == ["score_err", "confidence_err", "rank_gap", "order_gap",
                      "malformed_answers", "answers_compared"]
    if trace == "0":
        assert {"query_p50_ms", "query_p95_ms", "query_rate",
                "setup_s"} <= set(line["metrics"])
    else:
        assert {"busy_s", "window_s"} <= set(line["device"])
    # half first queries, half follow-ups, four forwards of each recomputed
    # (a short slate has fewer denoise forwards than four)
    said = next(l for l in log if l.startswith("# reference: "))
    assert "8 answers compared (4 first queries" in said
    assert 16 <= int(said.split(", ")[2].split()[0]) <= 32


def test_the_sessions_are_the_same_for_every_seed_and_as_the_mix_says():
    traffic = load_file(os.path.join(BENCHMARKS, "slate_traffic.py"))
    with open(os.path.join(BENCHMARKS, "traffic", "slates-c8.json")) as f:
        mix = json.load(f)
    lengths = traffic.history_lengths(mix)
    assert len(lengths) == 32 and min(lengths) == 91
    assert max(lengths) == 2868 and 680 <= sum(lengths) / 32 <= 700
    n_items = real_config()["generation"]["mask_row"]
    sessions = traffic.Sessions(mix, n_items)
    assert sorted(sessions.order(3)) == sorted(lengths)
    queries = sessions.session(2, 5)
    assert len(queries) == 4 and mix["generate"] == 16
    assert queries == traffic.Sessions(mix, n_items).session(2, 5)
    for before, after in zip(queries, queries[1:]):
        assert after[:len(before)] == before
        assert 4 <= len(after) - len(before) <= 8
    # no query carries the mask row, and every one fits a slot with its
    # slate and the last block's tail
    serve = real_config()["serve"]
    for c in range(8):
        for s in range(32):
            last = sessions.session(c, s)[-1]
            assert max(last) < n_items
            assert len(last) + 16 + 4 <= serve["capacity"]


def test_the_control_in_a_lower_precision_fails_a_limit(harness):
    bench = make_bench(harness)
    reference = bench.load_module("reference", bench.config["reference"])
    readings = reference.control(bench)
    limits = bench.config["limits"]
    for name in ("bfloat16", "float8_e4m3fn"):
        got = readings[name]
        assert got["compared"] > 0 and got["malformed"] == 0
        assert any(got[k] > limits[k] for k in limits), (name, got)


def test_a_commit_that_misses_the_slot_makes_the_run_incorrect(
        harness, capsys, monkeypatch):
    """Every commit row is sent to the scratch slot: the session's slot
    keeps what the last denoise forward wrote (keys and values of a block
    that still held masks), and every later block attends over those."""
    from predictionio_tpu.ops import sessionrec

    block = sessionrec.StackPrograms.block

    def skipped(self, rows):
        return block(self, [
            (ids, slot if denoise else self.shape.n_slots, at, denoise, n)
            for ids, slot, at, denoise, n in rows])

    monkeypatch.setattr(sessionrec.StackPrograms, "block", skipped)
    code, line, log = run_cell(harness, capsys, "--trace", "0")
    assert code == 0
    assert line["correct"] is False
    assert any(l.startswith("# check score_err") and "FAILED" in l
               for l in log)


def test_unmasking_the_least_confident_makes_the_run_incorrect(
        harness, capsys, monkeypatch):
    """The trajectory stays well formed (two positions a forward) and every
    served logit is right: only the ORDER is wrong, and ``order_gap`` says
    so."""
    from predictionio_tpu.ops import sessionrec

    by_rule = sessionrec.unmask_by_rule
    monkeypatch.setattr(
        sessionrec, "unmask_by_rule",
        lambda gen, masked, confidence, n: by_rule(gen, masked, -confidence,
                                                   n))
    code, line, log = run_cell(harness, capsys, "--trace", "0")
    assert code == 0
    assert line["correct"] is False
    failed = [l.split()[2].rstrip(":") for l in log
              if l.startswith("# check ") and "FAILED" in l]
    assert failed == ["order_gap"]


def test_an_answer_whose_steps_describe_no_run_of_forwards_is_malformed():
    ref = load_file(os.path.join(BENCHMARKS, "reference", "sdar_forward.py"))
    gen = {"mask_row": 9, "block_len": 4, "denoising_steps": 2,
           "rule": "low_confidence_static", "threshold": 0.9}
    # a history of 6 and a slate of 4: blocks 1 (two masks) and 2
    good = [(1, .1, .1, 0), (2, .1, .1, 0),
            (3, .1, .1, 2), (4, .1, .1, 3), (5, .1, .1, 2), (6, .1, .1, 3)]
    forwards = ref.rebuild(6, 4, good, gen)
    assert [f["kind"] for f in forwards] == [
        "denoise", "commit", "denoise", "denoise", "commit"]
    assert forwards[2]["unmasked"] == [8, 10]
    assert ref.state_before(list(range(10, 16)), good, 3, 2, gen) == [
        10, 11, 12, 13, 14, 15, 1, 2, 3, 9, 5, 9]

    def bad(items):
        with pytest.raises(ValueError):
            ref.rebuild(6, 4, items, gen)

    bad(good[:-1])                                    # a position short
    bad([(9, .1, .1, 0)] + good[1:])                  # the mask as an answer
    bad(good[:2] + [(3, .1, .1, 1)] + good[3:])       # no commit in between
    bad(good[:3] + [(4, .1, .1, 2)] + good[4:])       # three at one forward
    bad(good[:5] + [(6, .1, .1, 4)])                  # one, where two are due


def test_gen_counts_at_the_published_widths():
    counts = load_file(os.path.join(BENCHMARKS, "gen_counts.py"))
    cfg = real_config()
    assert counts.attention_params(cfg) == 18_874_368
    assert counts.norm_params(cfg) == 4_352
    assert counts.router_params(cfg) == 262_144
    assert counts.expert_params(cfg) == 4_718_592
    assert counts.layer_params(cfg) == 623_120_640
    assert counts.head_params(cfg) == 151_936 * 2048
    assert counts.kv_bytes_per_position(cfg) == 7 * 2048
    # ISSUE 32: about 9.3 GB a forward with every expert touched
    full = counts.block_bytes(cfg, runs=1, experts_touched=7 * 128,
                              kv_positions=0)
    assert abs(full / 1e9 - 9.35) < 0.05
    one = counts.block_bytes(cfg, 1, 10, 1000)
    assert one == pytest.approx(
        2 * (7 * 19_140_864 + 151_936 * 2048 + 2048)
        + 10 * 4_718_592 * 2 + 1000 * 14_336)


@pytest.mark.parametrize("case", repo_spec.CASES)
def test_benchmark_json_names_the_configuration_the_cell_and_each_reader(
        case):
    spec = repo_spec.load(case)
    cell = repo_spec.by_name(spec["workloads"], REAL_CELL)
    assert cell == {"name": REAL_CELL, "config": CONFIG,
                    "traffic": "slates-c8", "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200
    config = repo_spec.by_name(spec["configs"], CONFIG)
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert len(config["why"]) <= 200
    for e in ENTRIES:
        repo_spec.assert_names_the_reader(spec, e)
    for name in ("query_p50_ms", "query_p95_ms", "query_rate"):
        assert REAL_CELL in repo_spec.by_name(
            spec["end_to_end"], name)["workloads"]
    # the cell joins no accepted per-layer metric's list (GEN_SPANS.md)
    for m in spec["per_layer"]:
        if m["name"] not in NEW_METRICS:
            assert REAL_CELL not in m.get("workloads", ()), m["name"]


def test_the_configuration_keeps_every_published_number():
    catalog = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 32768, "max_window_layers": 48,
        "mlp_only_layers": [], "model_type": "sdar_moe",
        "moe_intermediate_size": 768, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 48,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936}
    cfg = real_config()
    differ = {k for k, v in catalog.items() if cfg[k] != v}
    assert differ == {"num_hidden_layers"} == set(cfg["reduced"])
    assert 4 <= cfg["num_hidden_layers"] <= 7
    assert cfg["experts_held"] == [0, cfg["num_experts"]]
    assert set(cfg["limits"]) == {"score_err", "confidence_err", "rank_gap",
                                  "order_gap"}
    assert {"block_length", "mask_row", "unmasking", "weights", "sessions",
            "cache", "slo"} <= set(cfg["assumed"])
    gen = cfg["generation"]
    assert gen["block_len"] == 4 and 0 <= gen["mask_row"] < cfg["vocab_size"]
    assert cfg["serve"]["chunk"] % gen["block_len"] == 0


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
    spans = [("pio:seq.step", 0, 40, 1),
             ("pio:seq.block_step", 1, 17, 1,
              {"rows": 8, "denoise_rows": 6, "commit_rows": 2}),
             ("pio:seq.prefill_chunk", 18, 39, 1),
             ("pio:seq.step", 50, 72, 1), ("pio:seq.block_step", 51, 71, 1),
             ("pio:seq.step", 80, 100, 1), ("pio:seq.block_step", 81, 99, 1)]
    assert read("block_step_ms.gen", make_trace(ps, spans)) == \
        pytest.approx(18.0)
    # 8 denoise + 4 commit rows of the slate, one known block: 13 / 16
    ctx = stats(denoise_rows=8, commit_rows=5, positions_unmasked=16)
    assert read("forwards_per_item.gen", None, **ctx) == pytest.approx(
        13 / 16)
    # 3 forwards, 7 layers of 128 experts each
    ctx = stats(block_runs=3, block_experts_touched=3 * 7 * 96)
    assert read("experts_touched_pct.gen", None, **ctx) == pytest.approx(75.0)
    ctx = stats(hit_tokens=300, miss_tokens=100)
    assert read("cache_hit_tokens_pct.gen", None, **ctx) == pytest.approx(
        75.0)
    assert read("device_idle_pct.slates-c8", None,
                traced={"busy_s": 2.4, "window_s": 3.0}) == pytest.approx(20.0)


def test_the_device_readers_on_hand_built_operations(ps):
    """Two block forwards of 10 ms each on the device: 6 ms under the expert
    scopes, 2 under attention's, 1 under the head's, 1 outside any."""
    ops = []
    for t in (0, 20):
        ops += [(f"%fusion.{t}", t, t + 2, "seq.layer0.gqa_a"),
                (f"%while.{t}", t + 2, t + 7, "seq.layer0.moe.experts"),
                (f"%sort.{t}", t + 7, t + 8, "seq.layer1.moe.route"),
                (f"%fusion.{t + 1}", t + 8, t + 9, "seq.head"),
                (f"%copy.{t}", t + 9, t + 10, None)]
    trace = make_trace(ps, [("pio:seq.block_step", 0, 31, 1)], ops)
    for dev in trace.ops.values():          # the block program's operations
        dev[:] = [o._replace(module="jit__block_fn") for o in dev]
    traced = {"busy_s": 0.020, "window_s": 0.031}
    got = {m: read(m, trace, traced=traced) for m in (
        "gqa_device_share_pct.gen", "moe_device_share_pct.gen",
        "head_device_share_pct.gen")}
    assert got == {"gqa_device_share_pct.gen": pytest.approx(20.0),
                   "moe_device_share_pct.gen": pytest.approx(60.0),
                   "head_device_share_pct.gen": pytest.approx(10.0)}
    # the bytes of two forwards at the peak rate, over 20 ms of device time
    counts = load_file(os.path.join(BENCHMARKS, "gen_counts.py"))
    need = counts.block_bytes(real_config(), 2, 2 * 7 * 100, 16_000)
    ctx = stats(block_runs=2, block_experts_touched=2 * 7 * 100,
                block_kv_positions=16_000)
    assert read("block_roofline_pct.gen", trace, traced=traced, **ctx) == \
        pytest.approx(100.0 * need / 819e9 / 0.020)
    # counted too high, or part of the time left out: no reading
    ctx = stats(block_runs=20, block_experts_touched=20 * 7 * 128,
                block_kv_positions=0)
    assert read("block_roofline_pct.gen", trace, traced=traced, **ctx) is None


@pytest.fixture(scope="module")
def recorded(ps):
    with open(SCOPES) as f:
        trace = ps.load(FIXTURE, json.load(f))
    with open(CTX) as f:
        ctx = json.load(f)
    ctx.update(window_stats0=ctx["stats0"], window_stats1=ctx["stats1"])
    return trace, ctx


def tiny_config():
    with open(os.path.join(TINY, "bench", "configs", "sdar-tiny.json")) as f:
        return json.load(f)


def test_every_new_reader_reads_the_recorded_trace(recorded):
    assert os.path.getsize(FIXTURE) <= 1024 * 1024
    trace, ctx = recorded
    got = {name: read(name, trace, tiny_config(), **ctx)
           for name in NEW_METRICS}
    assert all(v is not None for v in got.values()), got
    assert 0 < got["block_step_ms.gen"] < 1000
    # static rule, two positions a forward: 0.75 and the known blocks
    assert 0.75 <= got["forwards_per_item.gen"] <= 1.2
    # two rows of four tokens, four picks each, sixteen experts
    assert 40 < got["experts_touched_pct.gen"] <= 100
    shares = [got[f"{part}_device_share_pct.gen"]
              for part in ("gqa", "moe", "head")]
    assert all(s > 0 for s in shares) and sum(shares) <= 100
    assert 0 < got["block_roofline_pct.gen"] <= 100
    # two sessions of three queries: the follow-ups find whole blocks cached
    assert 30 < got["cache_hit_tokens_pct.gen"] < 80
    assert 0 < got["device_idle_pct.slates-c8"] < 100


def test_a_reader_returns_none_where_there_is_nothing_to_read(ps):
    """A trace of a program without this engine (PR 25's fixture), counters
    of an engine that generates nothing, and a run that was not traced: no
    number, no error."""
    old = ps.load(OLD_FIXTURE, {})
    old_stats = stats(hit_tokens=0, miss_tokens=0, extend_runs=4)
    for name in NEW_METRICS:
        if name != "device_idle_pct.slates-c8":
            assert read(name, old, traced={"busy_s": 1.0, "window_s": 2.0},
                        **old_stats) is None, name
        assert read(name, None) is None, name
