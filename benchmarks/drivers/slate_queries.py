"""Driver ``slate_queries``: a deployed block-diffusion sequence engine under
closed-loop sessions whose every query asks for a slate (``{"items",
"generate"}``).

Set-up, window and traced stretch are ``session_queries``' (its
``_trace_stretch`` is used as it is): the model builder makes the seeded
weights on the device and deploys the engine in this process, the load
generator (``slate_loadgen.py``, a child with no JAX) plays its warm-up
sessions, then each connection plays sessions back to back for ``--seconds``
seconds; a traced run's stretch lies inside the window.

``correct`` is decided by the timed path's own answers. After the window the
engine's cache and programs are freed and a seeded sample of the window's
answers (half first queries, the longest history served and the longest
follow-up's among them, half follow-ups, which read reused blocks; compared in
the order and under the budget of ``check_budget.py``) is taken apart: an
answer's ``step``s
must describe a well-formed run of forwards (``reference.rebuild``: exact),
and for ``check_forwards`` of its denoise forwards — always the slate's first
and the last block's last, the others by seed — the plain reference computes
the full forward over the sequence as it stood and what the program served
at the positions that forward unmasked is held against it.
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
    sessions_driver = bench.load_module("drivers", "session_queries")
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
                 "n_items": builder.n_traffic_items(cfg)}
    child = subprocess.Popen(
        [sys.executable, bench.find(".", "slate_loadgen", ".py"),
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
            traced = sessions_driver._trace_stretch(
                bench, model, deployed.batcher, mix, trace_dir)
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
    sessions_driver.mark_window(bench, traced)

    lat = sorted(load["latencies_s"])
    answered = len(lat)
    end_to_end = {
        "setup_s": setup_s,
        "query_p50_ms": percentile(lat, 0.50) * 1e3,
        "query_p95_ms": percentile(lat, 0.95) * 1e3,
        "query_rate": answered / load["window_s"],
    }
    layer_ctx = {"bench": bench, "traced": traced, **(traced or {}),
                 "window_stats0": stats0, "window_stats1": stats1}
    layer = bench.read_layer_metrics(layer_ctx) if bench.trace else {}
    peak = bench.memory_peak()
    firsts = sorted(d for d, f in zip(load["latencies_s"],
                                      load["first_query"]) if f)
    later = sorted(d for d, f in zip(load["latencies_s"],
                                     load["first_query"]) if not f)
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
        f"{firsts[-1] * 1e3 if firsts else 0:.3f}; follow-ups {len(later)}: "
        f"p50 {percentile(later, 0.5) * 1e3 if later else 0:.3f} ms, p95 "
        f"{percentile(later, 0.95) * 1e3 if later else 0:.3f}",
        f"sessions played per connection {load['sessions_played']}; longest "
        f"history served {max(load['history_lengths'], default=0)}",
        f"engine counters over the window: {counted}",
        f"step worker: {deployed.batcher.histogram()}",
        f"device memory peak bytes: {peak}",
    ]

    # the program's state goes before the reference runs; the weights are
    # the benchmark's own and stay
    weights = deployed.weights
    deployed.stop()
    model._programs = None
    del deployed, model, layer_ctx
    gc.collect()
    bench.mark("readers, server stopped")

    reference = bench.load_module("reference", cfg["reference"])
    generate, gen = int(mix["generate"]), cfg["generation"]
    entries, unparsable = [], 0
    for entry in load["sample"]:
        try:
            body = json.loads(entry["body"])
            entries.append({**entry, "answer": [
                (builder.item_row(e["item"]), float(e["score"]),
                 float(e["confidence"]), int(e["step"]))
                for e in body["itemScores"] + body.get("blockTail", [])]})
        except (ValueError, KeyError, TypeError):
            unparsable += 1
    budget = bench.lib("check_budget").Budget(
        mix, entries, answered, max(load["history_lengths"], default=0))
    rng = bench.lib("seeded").rng(bench.seed, 97)
    n_check = int(mix["check_forwards"])

    def pick(n_denoise: int) -> set:
        """The slate's first denoise forward, the last block's last one,
        the rest by seed."""
        between = list(range(1, n_denoise - 1))
        more = rng.choice(between, size=min(n_check - 2, len(between)),
                          replace=False) if between else []
        return {0, n_denoise - 1, *(int(i) for i in more)}

    t_ref = time.perf_counter()
    # a slate's last forward runs over its history, what it generated and
    # the whole of its last block
    reach = (sessions_driver.reach_of(mix) + generate
             + int(gen["block_len"]))
    got = reference.compare(
        weights, [(e["rows"], e["answer"]) for e in budget.entries],
        generate, gen, reference.dims_of(cfg), pick, reach=reach,
        stop=budget.stop)
    bench.mark("comparison")
    notes.append(budget.note(got["compared"]))
    notes.append(
        f"reference: {got['compared']} answers compared "
        f"({sum(1 for e in load['sample'] if e['first'])} first queries, "
        f"longest history {got['longest_history']}), {got['forwards']} "
        f"forwards recomputed in {time.perf_counter() - t_ref:.2f} s; "
        f"malformed: {got['why_malformed'][:3]}")
    limits = cfg["limits"]
    checks = [{"name": n, "value": got[n], "limit": limits[n],
               "ok": got[n] <= limits[n]}
              for n in ("score_err", "confidence_err", "rank_gap",
                        "order_gap")]
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
