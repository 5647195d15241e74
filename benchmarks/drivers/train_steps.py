"""Driver ``train_steps``: a trainer driven back to back for the window.

Set-up (counted in ``setup_s``): the model builder the configuration names
builds ONE object — the compiled step program with its state — and drives it
through its first call; what that call produced is kept for the comparison.
The window: the same object is called again and again until ``--seconds``
have passed; each call ends with the result on the host. After the window
the object is freed, and the reference follows the first call.
"""

from __future__ import annotations

import gc
import os
import time


def run(bench) -> dict:
    import jax

    cfg, mix = bench.config, bench.traffic
    say = print
    builder = bench.load_module("models", cfg["engine"])
    built = builder.build(bench)
    first = built.first_call()
    for k, v in built.timings.items():
        say(f"# set-up {k}: {v:.3f}", flush=True)
    say(f"# first call: {first}", flush=True)
    compiles0 = bench.compiles.count
    setup_s = time.time() - bench.t_start
    bench.mark("build and first call")

    trace_reduce = bench.lib("trace_reduce") if bench.trace else None
    trace_from = int(mix.get("trace_from_call", 1))
    trace_calls = int(mix.get("trace_calls", 2))
    trace_dir = os.path.join(bench.scratch, "trace")
    traced, tracing, traced_calls = None, False, 0

    def stop_tracing():
        window_span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        reduced = trace_reduce.reduce_trace(trace_dir)
        reduced["steps"] = traced_calls * built.steps_per_call
        return reduced

    calls, loss = 0, first["loss_mean"]
    t0 = time.perf_counter()
    deadline = t0 + bench.seconds
    while time.perf_counter() < deadline:
        if bench.trace and not tracing and traced is None \
                and calls == trace_from:
            trace_reduce.start_trace(trace_dir)
            window_span = jax.profiler.TraceAnnotation(
                trace_reduce.WINDOW_SPAN)
            window_span.__enter__()
            tracing = True
        if tracing:
            with jax.profiler.TraceAnnotation("bench:call"):
                loss = built.call()
            traced_calls += 1
        else:
            loss = built.call()
        calls += 1
        if tracing and traced_calls >= trace_calls:
            traced, tracing = stop_tracing(), False
    elapsed = time.perf_counter() - t0
    if tracing:                      # the window ended inside the trace
        traced = stop_tracing()
    window_compiles = bench.compiles.count - compiles0
    bench.mark("window" + (", trace stopped and reduced in it"
                           if traced else ""))

    examples = calls * built.examples_per_call
    steps = calls * built.steps_per_call
    end_to_end = {"setup_s": setup_s, "train_rate": examples / elapsed}
    layer_ctx = {"bench": bench, "built": built, "traced": traced}
    layer = bench.read_layer_metrics(layer_ctx) if bench.trace else {}
    peak = bench.memory_peak()
    notes = built.notes() + [
        f"window: {calls} calls of {built.steps_per_call} steps, "
        f"{examples} examples in {elapsed:.4f} s; final mean loss {loss}",
        f"device memory peak bytes: {peak}",
    ]

    # the program's state goes before the reference runs
    built.free()
    del built, layer_ctx
    gc.collect()
    bench.mark("readers, state freed")

    reference = bench.load_module("reference", cfg["reference"])
    t_ref = time.perf_counter()
    got = reference.compare(bench, first)
    bench.mark("comparison")
    notes.append(f"reference: first call followed in "
                 f"{time.perf_counter() - t_ref:.2f} s")
    limits = cfg["limits"]
    checks = [{"name": n, "value": got[n], "limit": limits[n],
               "ok": got[n] <= limits[n]} for n in sorted(got)]
    checks.append({"name": "final_loss", "value": loss,
                   "limit": limits["final_loss"],
                   "ok": loss <= limits["final_loss"]})
    return {
        "attempted": steps,
        "failed": 0, "checks": checks, "window_compiles": window_compiles,
        "end_to_end": end_to_end, "layer_metrics": layer,
        "memory_peak_bytes": peak, "notes": notes, "traced": traced,
    }

