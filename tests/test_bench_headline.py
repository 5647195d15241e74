"""The bench's final stdout line must stay inside the driver's capture
window.

Round 4's lesson (VERDICT r4 weak #1): the single fat JSON line outgrew
the driver's ~2 KB tail capture and BENCH_r04.json recorded
``"parsed": null`` — the round's headline was unverifiable from the
scoreboard. ``bench.emit_headline`` now splits output: a compact line
(metric, gates, key numbers, detail-file pointer) on stdout, everything
else to BENCH_DETAIL.json. These tests feed it a representative detail
blob (the r4 shape: histograms, per-run arrays, roofline trace) and pin
the compact-line budget.
"""

import importlib.util
import json
import os

import pytest

_spec = importlib.util.spec_from_file_location(
    "bench", os.path.join(os.path.dirname(__file__), "..", "bench.py"))
bench = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench)


def _representative_detail():
    """A detail blob at least as fat as round 4's real one."""
    return {
        "n_users": 138_493, "n_items": 26_744, "n_ratings": 20_000_000,
        "rank": 64, "iterations": 5,
        "synth_sec": 21.3, "ingest_sec": 14.9,
        "ingest_events_per_sec": 1_341_000.1,
        "post_bulk_append_debt_sec": 2.1,
        "json_build_events_per_sec": 91_000.5,
        "row_lane_events_per_sec": 587_700.0,
        "row_lane_gate_passed": True,
        "row_lane_fsync_events_per_sec": 210_000.0,
        "event_build_events_per_sec": 120_000.0,
        "insert_batch_events_per_sec": 95_000.0,
        "python_row_lane_events_per_sec": 52_000.0,
        "read_sec": 4.2, "prepare_sec": 3.9, "bin_sec": 11.2,
        "bin_cache_hit": False, "transfer_sec": 7.1,
        "transfer_bytes": 219_725_824, "transfer_mb_per_sec": 30.9,
        "compile_sec": 24.2, "bin_compile_sec": 42.5,
        "train_sec": 1.52, "events_to_model_sec": 50.6,
        "events_to_model_events_per_sec": 395_000.0,
        "rmse_heldout": 0.4271, "rmse_global_mean_baseline": 1.2513,
        "rmse_gate_passed": True, "rmse_band": [0.38, 0.48],
        "rmse_band_passed": True,
        "updates_per_sec": 62_400_000.0,
        "roofline": {
            "model": "analytic counts from actual padded device shapes",
            "flops_per_iter": 10**12, "hbm_bytes_per_iter": 10**9,
            "achieved_tflops": 3.1, "achieved_hbm_gb_per_sec": 113.5,
            "peak_bf16_tflops": 197.0, "peak_hbm_gb_per_sec": 819.0,
            "mxu_fraction": 0.016, "hbm_fraction": 0.139,
            "measured": {
                "measured": True, "governing": "gather-issue",
                "profiled_step_sec": 0.31,
                "train_slots_per_sec": 0.43,
                "gather_roof_slots_per_sec": 6.1,
                "governing_fraction": 0.07,
                "trace": {
                    "device_time_sec": 0.29,
                    "flops_total": 5 * 10**12,
                    "bytes_total": 4 * 10**10,
                    "hbm_bytes_total": 3 * 10**10,
                    "by_category": {
                        c: {"time_frac": 0.1, "hbm_bytes": 4_000_000,
                            "flops": 9_000_000}
                        for c in ("while", "gather", "fusion", "convert",
                                  "all-reduce", "dot", "copy", "misc")
                    },
                },
            },
        },
        "serve_p50_ms": 0.96, "serve_p99_ms": 1.52, "serve_qps": 1222.7,
        "serve_gate_passed": True,
        "serve_qps_32conn": 2692.0,
        "serve_p50_ms_32conn": 11.63, "serve_p99_ms_32conn": 19.81,
        "serve_p50_ms_32conn_serverside": 10.64,
        "serve_p99_ms_32conn_serverside": 17.09,
        "serve_32conn_runs": [
            {"errors": 0, "qps": 2692.0, "p50_ms": 11.63, "p99_ms": 19.81,
             "srv_p50_ms": 10.64, "srv_p99_ms": 17.09},
            {"errors": 0, "qps": 2339.3, "p50_ms": 13.25, "p99_ms": 21.89,
             "srv_p50_ms": 11.89, "srv_p99_ms": 18.81},
        ],
        "serve_32conn_note": "x" * 300,
        "serve_batch_histogram": {str(k): 17 for k in range(1, 33)},
        "serve_32_gate_passed": True,
        "serve_sweep": [
            {"conns": c, "qps": 1000.0 + c, "p50_ms": 2.0 * c,
             "p99_ms": 3.0 * c, "srv_p50_ms": 1.5 * c, "srv_p99_ms": 2.5 * c,
             "srv_queue_p50_ms": 0.7 * c, "srv_dispatch_p50_ms": 0.9}
            for c in (1, 8, 32, 128)
        ],
        "twotower": {
            "step_ms": 14.2, "mfu": 0.41, "achieved_tflops": 80.0,
            "peak_basis": "197 TFLOP/s bf16 (public v5e peak)",
            "loss_first": 8.1, "loss_last": 2.2, "loss_gate_passed": True,
            "config": {"users": 1_000_000, "items": 1_000_000, "dim": 128,
                       "batch": 8192},
        },
        "warm": {
            "bin_sec": 4.0, "read_sec": 0.0, "prepare_sec": 0.0,
            "bin_cache_hit": True, "transfer_sec": 26.36,
            "transfer_bytes": 219_725_824, "transfer_mb_per_sec": 8.3,
            "compile_sec": 2.15, "bin_compile_sec": 32.51,
            "train_sec": 1.48, "events_to_model_sec": 33.99,
            "events_to_model_events_per_sec": 588_408.4,
        },
    }


def test_headline_fits_driver_window(tmp_path):
    detail = _representative_detail()
    line = bench.emit_headline(detail, detail_path=str(tmp_path / "d.json"))
    encoded = json.dumps(line).encode()
    assert len(encoded) <= bench.MAX_HEADLINE_BYTES
    # the driver parses json.loads(last stdout line): round-trip it
    parsed = json.loads(encoded)
    assert parsed["metric"] == "als_ml20m_rating_updates_per_sec_per_chip"
    assert parsed["value"] == 62_400_000.0
    assert parsed["vs_baseline"] == 62.4
    assert all(parsed["gates"].values())
    assert parsed["key"]["warm_events_to_model_sec"] == 33.99
    assert parsed["key"]["row_lane_events_per_sec"] == 587_700.0
    assert parsed["detail_file"] == "BENCH_DETAIL.json"
    # full detail file holds everything the line dropped
    full = json.loads((tmp_path / "d.json").read_text())
    assert full["serve_batch_histogram"]["32"] == 17
    assert full["roofline"]["measured"]["trace"]["by_category"]


def test_failed_gate_zeroes_value(tmp_path):
    detail = _representative_detail()
    detail["serve_32_gate_passed"] = False
    line = bench.emit_headline(detail, detail_path=str(tmp_path / "d.json"))
    assert line["value"] == 0.0
    assert line["gates"]["serve_32conn"] is False
    # the other gate flags still tell which gates held
    assert line["gates"]["rmse"] is True


def test_twotower_gate_zeroes_value(tmp_path):
    detail = _representative_detail()
    detail["twotower"]["loss_gate_passed"] = False
    line = bench.emit_headline(detail, detail_path=str(tmp_path / "d.json"))
    assert line["value"] == 0.0
    assert line["gates"]["twotower_loss"] is False


def test_oversize_line_prunes_but_always_prints(tmp_path, monkeypatch):
    """An over-budget line must NOT abort the run (that would reproduce
    the BENCH_r04 parsed:null failure): optional key entries are pruned
    until the line fits, and the pruning is recorded in the detail."""
    monkeypatch.setattr(bench, "MAX_HEADLINE_BYTES", 400)
    detail = _representative_detail()
    line = bench.emit_headline(detail, detail_path=str(tmp_path / "d.json"))
    assert len(json.dumps(line).encode()) <= 400
    # the headline value and gates survive pruning
    assert line["value"] == 62_400_000.0
    assert "gates" in line and line["gates"]["rmse"] is True
    full = json.loads((tmp_path / "d.json").read_text())
    assert full["headline_pruned_keys"]


@pytest.mark.parametrize("wire_hangs,compile_hangs,expect", [
    (True, True, "wire.*compile"),
    (True, False, r"wire \(async puts"),
    (False, True, r"compile \(ahead of time"),
])
def test_transfer_compile_overlap_times_out_with_side_attribution(
        monkeypatch, wire_hangs, compile_hangs, expect):
    """A hung transfer/compile overlap must surface as a diagnosable
    error naming WHICH side(s) were still pending at the deadline,
    instead of wedging the bench process forever — and the deadline
    must cover the compile thread too (advisor finding, r6)."""
    import threading

    monkeypatch.setattr(bench, "TRANSFER_JOIN_TIMEOUT_SEC", 0.05)
    release = threading.Event()

    class HungTrainer:
        put_start = 0.0
        transfer_bytes = 0

        def wait_device_timed(self):
            if wire_hangs:
                release.wait(5.0)
            return [0.0]

        def compile(self):
            if compile_hangs:
                release.wait(5.0)

    try:
        with pytest.raises(RuntimeError, match=expect):
            bench._transfer_and_compile({"bin_sec": 0.0}, HungTrainer(),
                                        iterations=1, n_read=1)
    finally:
        release.set()            # unblock the daemon threads


def test_transfer_timeout_surfaces_dead_side_error(monkeypatch):
    """When one side FAILED fast and the other hangs, the timeout
    message must carry the dead side's error — it is the root cause."""
    import threading

    monkeypatch.setattr(bench, "TRANSFER_JOIN_TIMEOUT_SEC", 0.05)
    release = threading.Event()

    class Trainer:
        put_start = 0.0
        transfer_bytes = 0

        def wait_device_timed(self):
            raise OSError("wire dropped")

        def compile(self):
            release.wait(5.0)

    try:
        with pytest.raises(RuntimeError,
                           match=r"compile \(ahead.*wire already failed.*"
                                 r"wire dropped"):
            bench._transfer_and_compile({"bin_sec": 0.0}, Trainer(),
                                        iterations=1, n_read=1)
    finally:
        release.set()


def test_lint_stage_key_lands_and_gates_lower_better(tmp_path):
    """The project-mode graftlint wall clock is a first-class gated
    number: stage_lint's measurement lands in key.lint_project_ms and
    bench-compare directions it lower-better (the _ms convention) — a
    super-linear regression in the whole-program analysis fails the
    compare gate instead of silently taxing every commit's tier-1."""
    from predictionio_tpu.tools import benchcmp

    detail = _representative_detail()
    detail["lint_project_ms"] = 5252.6
    line = bench.emit_headline(detail, detail_path=str(tmp_path / "d.json"))
    assert line["key"]["lint_project_ms"] == 5252.6
    assert len(json.dumps(line).encode()) <= bench.MAX_HEADLINE_BYTES
    assert benchcmp.lower_is_better("key.lint_project_ms")
    assert not benchcmp.is_config_key("key.lint_project_ms")


def test_dataobs_stage_keys_land_and_gate(tmp_path):
    """stage_dataobs' two numbers are first-class gated metrics:
    key.dataobs_update_us (the per-event sketch cost) and
    key.dataobs_overhead_pct (the hook's tax on the insert_batch bulk
    lane) land in the headline, bench-compare directions both
    lower-better, and a blown overhead gate (>3%) zeroes the headline
    value like any other hard gate."""
    from predictionio_tpu.tools import benchcmp

    detail = _representative_detail()
    detail["dataobs_update_us"] = 0.55
    detail["dataobs_overhead_pct"] = 0.25
    detail["dataobs_gate_passed"] = True
    line = bench.emit_headline(detail, detail_path=str(tmp_path / "d.json"))
    assert line["key"]["dataobs_update_us"] == 0.55
    assert line["key"]["dataobs_overhead_pct"] == 0.25
    assert line["gates"]["dataobs_overhead"] is True
    assert len(json.dumps(line).encode()) <= bench.MAX_HEADLINE_BYTES
    assert benchcmp.lower_is_better("key.dataobs_update_us")
    assert benchcmp.lower_is_better("key.dataobs_overhead_pct")
    assert not benchcmp.is_config_key("key.dataobs_update_us")

    detail = _representative_detail()
    detail["dataobs_update_us"] = 2.0
    detail["dataobs_overhead_pct"] = 4.8
    detail["dataobs_gate_passed"] = False
    line = bench.emit_headline(detail, detail_path=str(tmp_path / "d.json"))
    assert line["value"] == 0.0
    assert line["gates"]["dataobs_overhead"] is False


def test_benchcmp_dataobs_regression_exits_1(tmp_path, capsys):
    """A sketch-cost regression between rounds fails pio bench-compare
    with exit 1 (the CI contract), exactly like the serving metrics."""
    from predictionio_tpu.tools import benchcmp

    def round_file(name, update_us, overhead_pct):
        p = tmp_path / name
        p.write_text(json.dumps({"parsed": {
            "metric": "m", "value": 1.0,
            "key": {"dataobs_update_us": update_us,
                    "dataobs_overhead_pct": overhead_pct},
        }}))
        return str(p)

    base = round_file("BENCH_r01.json", 0.55, 0.25)
    worse = round_file("BENCH_r02.json", 1.60, 0.25)
    assert benchcmp.run([base, worse]) == 1
    out = capsys.readouterr().out
    assert "key.dataobs_update_us" in out and "REGRESSION" in out
    better = round_file("BENCH_r03.json", 0.50, 0.20)
    assert benchcmp.run([base, better]) == 0
