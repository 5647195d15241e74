"""Driver ``session_queries``: a deployed sequence engine under closed-loop
browsing sessions.

Set-up (counted in ``setup_s``): the model builder makes the seeded weights on
the device and deploys the engine in this process (its warm-up compiles every
program), the load generator (``session_loadgen.py``, a child with no JAX)
builds its request bodies, connects and plays its warm-up sessions. The
window: each connection plays sessions back to back for ``--seconds``
seconds. After the window the server is stopped, the engine's cache and
programs are freed, and a seeded sample of the window's own answers — half
first queries, half extensions, the longest history served and the longest
later query among them — is held against the reference's full forward over
the history each carried, in the order and under the budget of
``check_budget.py`` (the traffic file's ``check_budget_s`` and
``check_floor``). ``bench.mark`` notes where each phase ends, for the ``#
run:`` line of the log.

A traced run starts the profiler when the warm-up sessions are done and
every connection stands at its next session's boundary: the server is idle
then (started under load the profiler stalls the process:
``closed_loop_queries``). The traced stretch lies INSIDE the measured window,
``trace_after_go_s`` after ``GO`` (the eight first prefills that the start
lets go together are through by then) and ``trace_seconds`` long. The
per-layer metrics are taken over that stretch: spans and device operations
from the trace, counters as the difference of the engine's own between the
stretch's ends. A number that is counters alone needs no trace beside it and
is taken over the whole window (``window_stats0`` / ``window_stats1``):
twenty seconds of sessions instead of three.
"""

from __future__ import annotations

import gc
import json
import os
import statistics
import subprocess
import sys
import time


def run(bench) -> dict:
    cfg, mix = bench.config, bench.traffic
    say = print
    percentile = bench.load_module("drivers", "closed_loop_queries").percentile
    builder = bench.load_module("models", cfg["engine"])
    deployed = builder.deploy(bench)
    for k, v in deployed.timings.items():
        say(f"# set-up {k}: {v:.3f}", flush=True)
    model = deployed.model
    say(f"# device memory after deploy: {bench.memory_peak()} peak bytes",
        flush=True)
    bench.mark("deploy")
    child_cfg = {"port": deployed.port, "seed": bench.seed,
                 "seconds": bench.seconds, "mix": mix,
                 "n_items": int(cfg["vocab_size"])}
    child = subprocess.Popen(
        [sys.executable, bench.find(".", "session_loadgen", ".py"),
         json.dumps(child_cfg)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    traced = None
    try:
        said = child.stdout.readline().strip()
        if said == "STARTED":
            said = child.stdout.readline().strip()
        if said != "WARMUP_DONE":
            raise RuntimeError(f"load generator warm-up failed: {said!r}")
        compiles0 = bench.compiles.count
        stats0 = model.stats()
        setup_s = time.time() - bench.t_start
        bench.mark("warm-up sessions")
        if bench.trace:
            trace_dir = os.path.join(bench.scratch, "trace")
            bench.lib("trace_reduce").start_trace(trace_dir)
        child.stdin.write("GO\n")
        child.stdin.flush()
        if bench.trace:
            traced = _trace_stretch(bench, model, deployed.batcher, mix,
                                    trace_dir)
        out, _ = child.communicate(timeout=bench.seconds + 900)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    window_compiles = bench.compiles.count - compiles0
    stats1 = model.stats()
    load = json.loads(out.strip().splitlines()[-1])
    if "fatal" in load:
        raise RuntimeError(f"load generator: {load['fatal']}")
    mark_window(bench, traced)

    end_to_end, lat, firsts, later = window_numbers(load, setup_s,
                                                    percentile)
    answered = len(lat)
    # ``window_end_to_end``: as ``closed_loop_queries`` hands it over, for a
    # cell that keeps one of the window's own numbers in sight per layer
    layer_ctx = {"bench": bench, "traced": traced, **(traced or {}),
                 "window_stats0": stats0, "window_stats1": stats1,
                 "window_end_to_end": end_to_end}
    layer = bench.read_layer_metrics(layer_ctx) if bench.trace else {}
    peak = bench.memory_peak()
    counted = {k: stats1[k] - stats0[k] for k in stats1
               if isinstance(stats1[k], (int, float))}
    notes = [
        f"requests sent {load['sent']} answered {answered} errors "
        f"{load['n_errors']} malformed {load['malformed']} "
        f"first errors {load['errors']}",
        f"latency ms: min {lat[0] * 1e3:.3f} p50 "
        f"{end_to_end['query_p50_ms']:.3f} p95 "
        f"{end_to_end['query_p95_ms']:.3f} p99 "
        f"{percentile(lat, 0.99) * 1e3:.3f} max {lat[-1] * 1e3:.3f} "
        f"mean {statistics.fmean(lat) * 1e3:.3f}",
        f"first queries {len(firsts)}: p50 "
        f"{percentile(firsts, 0.5) * 1e3 if firsts else 0:.3f} ms, max "
        f"{firsts[-1] * 1e3 if firsts else 0:.3f}; extensions {len(later)}: "
        f"p50 {percentile(later, 0.5) * 1e3 if later else 0:.3f} ms, p95 "
        f"{percentile(later, 0.95) * 1e3 if later else 0:.3f}",
        f"sessions played per connection {load['sessions_played']}; longest "
        f"history served {max(load['history_lengths'], default=0)}",
        f"engine counters over the window: {counted}",
        f"step worker: {deployed.batcher.histogram()}",
        f"index: {stats1['index']}",
        f"device memory peak bytes: {peak}",
    ]

    # the program's state goes before the reference runs; the weights are
    # the benchmark's own and stay
    weights = deployed.weights
    deployed.stop()
    model._programs = model._index = None
    del deployed, model, layer_ctx
    gc.collect()
    bench.mark("readers, server stopped")

    reference = bench.load_module("reference", cfg["reference"])
    k = int(mix["num"])
    entries, unparsable = [], 0
    for entry in load["sample"]:
        try:
            scores = json.loads(entry["body"])["itemScores"]
            entries.append({**entry, "answer": [
                (builder.item_row(s["item"]), float(s["score"]))
                for s in scores]})
        except (ValueError, KeyError, TypeError):
            unparsable += 1
    budget = bench.lib("check_budget").Budget(
        mix, entries, answered, max(load["history_lengths"], default=0))
    t_ref = time.perf_counter()
    got = reference.compare(
        weights, [(e["rows"], e["answer"]) for e in budget.entries], k,
        reference.dims_of(cfg), reach=reach_of(mix), stop=budget.stop)
    bench.mark("comparison")
    notes.append(budget.note(got["compared"]))
    notes.append(
        f"reference: {got['compared']} answers compared in "
        f"{time.perf_counter() - t_ref:.2f} s "
        f"({sum(1 for e in load['sample'] if e['first'])} first queries, "
        f"longest history {got['longest_history']}); router near ties "
        f"(12th and 13th pick within 1e-3, relative) at {got['near_ties']} "
        f"of {got['positions_x_layers']} positions x layers")
    limits = cfg["limits"]
    checks = [{"name": n, "value": got[n], "limit": limits[n],
               "ok": got[n] <= limits[n]} for n in ("score_err", "rank_gap")]
    bad = got["malformed"] + unparsable + load["malformed"]
    checks.append({"name": "malformed_answers", "value": bad, "limit": 0,
                   "ok": bad == 0})
    checks.append(budget.check(got["compared"]))
    return {
        "attempted": load["sent"], "failed": load["n_errors"] + bad,
        "checks": checks, "window_compiles": window_compiles,
        "end_to_end": end_to_end, "layer_metrics": layer,
        "memory_peak_bytes": peak, "notes": notes, "traced": traced,
    }


def window_numbers(load: dict, setup_s: float, percentile) -> tuple:
    """The window's own end-to-end numbers from what the load generator
    said, and the sorted latencies behind them: all, the first queries',
    the extensions'."""
    lat = sorted(load["latencies_s"])
    firsts = sorted(d for d, f in zip(load["latencies_s"],
                                      load["first_query"]) if f)
    later = sorted(d for d, f in zip(load["latencies_s"],
                                     load["first_query"]) if not f)
    end_to_end = {
        "setup_s": setup_s,
        "query_p50_ms": percentile(lat, 0.50) * 1e3,
        "query_p95_ms": percentile(lat, 0.95) * 1e3,
        "query_rate": len(lat) / load["window_s"],
    }
    if firsts:
        # what a returning user waits for the first page, prefill and FIFO
        # included: the first queries' OWN median, whose rank does not move
        # with the window's count of extensions (PERF.md section 2); a window
        # without a first query has no such number, never 0
        end_to_end["first_query_p50_ms"] = percentile(firsts, 0.5) * 1e3
    return end_to_end, lat, firsts, later


def mark_window(bench, traced) -> None:
    """The window's phase ends; a traced run's trace was stopped and reduced
    on this thread inside it."""
    bench.mark("window and drain"
               + (f" (trace stopped in {traced['stop_s']:.1f} and reduced in "
                  f"{traced['reduce_s']:.1f} of them)" if traced else ""))


def reach_of(mix: dict) -> int:
    """The longest history the mix can send: its longest first query grown
    by every later one. The reference pads every history to it."""
    return int(mix["history_max"]) + (int(mix["queries_per_session"]) - 1) \
        * int(mix["grow_max"])


def _trace_stretch(bench, model, batcher, mix, trace_dir) -> dict:
    """The traced stretch, inside the window: the trace's reduction, and
    the engine's own counters at both of its ends."""
    import jax

    trace_reduce = bench.lib("trace_reduce")
    time.sleep(float(mix.get("trace_after_go_s", 0.0)))
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        stats0, h0 = model.stats(), batcher.histogram()
        time.sleep(float(mix.get("trace_seconds", 3.0)))
        stats1, h1 = model.stats(), batcher.histogram()
    splits = batcher.recent_splits(max(1, h1["answered"] - h0["answered"]))
    t0 = time.perf_counter()
    jax.profiler.stop_trace()
    t1 = time.perf_counter()
    out = trace_reduce.reduce_trace(trace_dir)
    out.update(stats0=stats0, stats1=stats1, splits=splits, stop_s=t1 - t0,
               reduce_s=time.perf_counter() - t1)
    return out
