"""Driver ``closed_loop_queries``: a deployed engine under closed-loop load.

Set-up (counted in ``setup_s``): the model builder the configuration names
deploys the engine in this process, the load generator (a child with no JAX)
connects and sends its warm-up queries at the mix's own concurrency. The
window: the child's connections each send, wait for the answer, send again,
for ``--seconds`` seconds. After the window the server is stopped and freed,
and a seeded sample of the answers it gave is held against the reference.

A traced run traces the FIRST seconds of that same uninterrupted closed loop,
and starts the profiler while the server is still idle: starting it under load
stalls the process for long enough that every connection's next query piles
up, and the batcher then leaves the regime the untraced runs measure (read on
the chip, PR 24). Its per-layer metrics are taken over the traced stretch.
"""

from __future__ import annotations

import gc
import json
import os
import statistics
import subprocess
import sys
import time


def percentile(sorted_vals, q: float) -> float:
    """Nearest-rank percentile of an ascending list."""
    n = len(sorted_vals)
    return sorted_vals[min(n - 1, max(0, int(-(-q * n // 1)) - 1))]


def _wrap_dispatch(batcher) -> None:
    """Traced runs only: the benchmark's spans around the two calls the
    batcher makes into the engine, so idle gaps can be told apart (inside a
    dispatch / between dispatches). Spans inside the program are a later
    PR's; if these attributes go, the gaps just stay unattributed."""
    import jax

    for attr, span in (("_run_batch", "bench:dispatch_batch"),
                       ("_run_one", "bench:dispatch_one")):
        inner = getattr(batcher, attr, None)
        if inner is None:
            continue

        def wrapped(payload, _inner=inner, _span=span):
            with jax.profiler.TraceAnnotation(_span):
                return _inner(payload)

        setattr(batcher, attr, wrapped)


def run(bench) -> dict:
    cfg, mix = bench.config, bench.traffic
    say = print
    builder = bench.load_module("models", cfg["engine"])
    deployed = builder.deploy(bench)
    for k, v in deployed.timings.items():
        say(f"# set-up {k}: {v:.3f}", flush=True)
    try:
        from predictionio_tpu.ops.topk import measured_dispatch_latency
        say(f"# dispatch round trip (ops/topk.measured_dispatch_latency): "
            f"{measured_dispatch_latency() * 1e3:.4f} ms", flush=True)
    except Exception as e:  # noqa: BLE001 — a note, not a measurement
        say(f"# dispatch round trip: not read ({e})", flush=True)
    batcher = deployed.batcher
    if bench.trace and batcher is not None:
        _wrap_dispatch(batcher)
    bench.mark("deploy")

    k = int(mix["num"])
    # every batch size the window can form, once, before the window: the
    # engine pads a batch to its bucket with small programs of their own,
    # one per raw size
    t_warm = time.perf_counter()
    for b in range(2, int(mix["connections"]) + 1):
        deployed.server.deployment.query_batch(
            [{"user": builder.user_id(j), "num": k} for j in range(b)])
    say(f"# set-up batch_sizes_s: {time.perf_counter() - t_warm:.3f}",
        flush=True)
    child_cfg = {
        "port": deployed.port, "seed": bench.seed,
        "connections": int(mix["connections"]), "num": k,
        "n_users": int(cfg["n_users"]), "seconds": bench.seconds,
        "warmup_per_connection": int(mix["warmup_per_connection"]),
        "check_sample": int(mix["check_sample"]),
        "start": mix.get("start"),
    }
    if bench.trace:
        trace_dir = os.path.join(bench.scratch, "trace")
        bench.lib("trace_reduce").start_trace(trace_dir)
    child = subprocess.Popen(
        [sys.executable, bench.find(".", "loadgen", ".py"),
         json.dumps(child_cfg)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    traced = None
    try:
        said = child.stdout.readline().strip()
        if said == "STARTED":
            if bench.trace:
                traced = _trace_stretch(bench, batcher, mix, trace_dir)
            said = child.stdout.readline().strip()
        if said != "WARMUP_DONE":
            raise RuntimeError(f"load generator warm-up failed: {said!r}")
        compiles0 = bench.compiles.count
        setup_s = time.time() - bench.t_start
        bench.mark("batch sizes, warm-up queries"
                   + (", traced stretch" if traced else ""))
        child.stdin.write("GO\n")
        child.stdin.flush()
        out, _ = child.communicate(timeout=bench.seconds + 600)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    window_compiles = bench.compiles.count - compiles0
    load = json.loads(out.strip().splitlines()[-1])
    if "fatal" in load:
        raise RuntimeError(f"load generator: {load['fatal']}")
    bench.mark("window and drain")

    lat = sorted(load["latencies_s"])
    answered = len(lat)
    end_to_end = {
        "setup_s": setup_s,
        "query_p50_ms": percentile(lat, 0.50) * 1e3,
        "query_p95_ms": percentile(lat, 0.95) * 1e3,
        "query_rate": answered / load["window_s"],
    }
    # ``window_end_to_end``: the window's own numbers, for a cell that
    # reports one of them per layer (a traced run measures the same window)
    layer_ctx = {"bench": bench, "traced": traced, **(traced or {}),
                 "window_end_to_end": end_to_end}
    layer = bench.read_layer_metrics(layer_ctx) if bench.trace else {}
    peak = bench.memory_peak()
    notes = [
        f"requests sent {load['sent']} answered {answered} errors "
        f"{load['n_errors']} malformed {load['malformed']} "
        f"first errors {load['errors']}",
        f"latency ms: min {lat[0] * 1e3:.3f} p50 "
        f"{end_to_end['query_p50_ms']:.3f} p95 "
        f"{end_to_end['query_p95_ms']:.3f} p99 "
        f"{percentile(lat, 0.99) * 1e3:.3f} max {lat[-1] * 1e3:.3f} "
        f"mean {statistics.fmean(lat) * 1e3:.3f}",
        f"latency ms by the second a request ended in, p50/p95: "
        f"{_by_second(load)}",
        f"load generator's own collections [n, s, longest s] by generation:"
        f" {load.get('gc_pauses')}",
        f"batcher histogram over the run: "
        f"{batcher.histogram() if batcher is not None else None}",
        f"index: {_index_stats(deployed)}",
        f"device memory peak bytes: {peak}",
    ]

    # the program's state goes before the reference runs
    X, Y = deployed.X, deployed.Y
    deployed.stop()
    del deployed, batcher, layer_ctx
    gc.collect()

    reference = bench.load_module("reference", cfg["reference"])
    sample, unparsable = [], 0
    for row, body in load["sample"]:
        try:
            scores = json.loads(body)["itemScores"]
            sample.append((int(row), [(builder.item_row(s["item"]),
                                       float(s["score"])) for s in scores]))
        except (ValueError, KeyError, TypeError):
            unparsable += 1
    bench.mark("readers, server stopped")
    t_ref = time.perf_counter()
    got = reference.compare(X, Y, sample, k)
    bench.mark("comparison")
    notes.append(f"reference: {len(sample)} answers compared in "
                 f"{time.perf_counter() - t_ref:.2f} s")
    limits = cfg["limits"]
    checks = [
        {"name": n, "value": got[n], "limit": limits[n],
         "ok": got[n] <= limits[n]} for n in ("score_err", "rank_gap")
    ]
    bad = got["malformed"] + unparsable + load["malformed"]
    checks.append({"name": "malformed_answers", "value": bad, "limit": 0,
                   "ok": bad == 0})
    checks.append({"name": "answers_compared", "value": len(sample),
                   "limit": f">= {min(int(mix['check_sample']), answered)}",
                   "ok": len(sample) >= min(int(mix["check_sample"]),
                                            answered) > 0})
    return {
        "attempted": load["sent"], "failed": load["n_errors"] + bad,
        "checks": checks, "window_compiles": window_compiles,
        "end_to_end": end_to_end, "layer_metrics": layer,
        "memory_peak_bytes": peak, "notes": notes, "traced": traced,
    }


def _by_second(load) -> str:
    """The regimes a saturated closed loop passes through, second by second
    (a note for the log: no metric reads it)."""
    rows = {}
    for t, d in zip(load.get("done_s", ()), load["latencies_s"]):
        rows.setdefault(int(t), []).append(d)
    return " ".join(
        f"{percentile(sorted(v), 0.5) * 1e3:.1f}/"
        f"{percentile(sorted(v), 0.95) * 1e3:.1f}"
        for _, v in sorted(rows.items()))


def _index_stats(deployed):
    try:
        model = deployed.server.deployment.models[0]
        return model.retrieval_stats()
    except Exception as e:  # noqa: BLE001 — a note only
        return f"not read ({e})"


def _trace_stretch(bench, batcher, mix, trace_dir) -> dict:
    """The traced stretch, from the load's first request on: the trace's
    reduction, and what the batcher counted and timed over the same stretch
    (``hist0``/``hist1``: its histogram at both ends; ``splits``: its
    (queue wait, dispatch) seconds of the requests answered in between)."""
    import jax

    trace_reduce = bench.lib("trace_reduce")
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        h0 = batcher.histogram() if batcher is not None else None
        time.sleep(float(mix.get("trace_seconds", 3.0)))
        h1 = batcher.histogram() if batcher is not None else None
    splits = []
    if batcher is not None:
        answered = sum(int(k) * (n - h0["batchSizeHistogram"].get(k, 0))
                       for k, n in h1["batchSizeHistogram"].items())
        splits = batcher.recent_splits(answered) if answered else []
    jax.profiler.stop_trace()
    out = trace_reduce.reduce_trace(trace_dir)
    out.update(hist0=h0, hist1=h1, splits=splits)
    return out
