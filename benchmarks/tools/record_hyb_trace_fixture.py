"""Record the small trace that tests/benchmarks/test_hyb_cell.py checks the
hybrid stack's per-layer readers against (run once on the chip; committed as
tests/benchmarks/fixtures/hyb_small.xplane.pb, with hyb_small.scopes.json —
the program's instruction -> scope maps — and hyb_small.ctx.json — the
engine's counters at the stretch's two ends — beside it):

    python3 benchmarks/tools/record_hyb_trace_fixture.py <out_dir>

``record_seq_trace_fixture.py`` for the hybrid cell: one process deploys the
tiny rehearsal configuration (tests/benchmarks/tiny_hyb) as the benchmark's
builder does, and under one ``bench:window`` two connections play one session
each (a history of several chunks, then seven extensions), so that a step
holds an extension batch and a prefill chunk. What no reader reads is taken
out of the file (that tool's ``slim``).
"""

import argparse
import glob
import json
import os
import shutil
import sys
import threading

HERE = os.path.dirname(os.path.abspath(__file__))
BENCHMARKS = os.path.dirname(HERE)
CHECKOUT = os.path.dirname(BENCHMARKS)
TINY = os.path.join(CHECKOUT, "tests", "benchmarks", "tiny_hyb")
CELL = "granite-tiny.sessions-c4"


def main(out_dir: str) -> None:
    sys.path.insert(0, CHECKOUT)
    sys.path.insert(0, HERE)
    import jax
    import record_seq_trace_fixture as seq_tool

    run = seq_tool.load(os.path.join(BENCHMARKS, "run.py"))
    other = seq_tool.load(os.path.join(HERE,
                                       "record_program_trace_fixture.py"))
    with open(os.path.join(TINY, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    bench = run.Bench(TINY, spec, cell, argparse.Namespace(
        seed=7, seconds=1, trace=1))
    bench.devices = jax.devices()[:1]
    builder = bench.load_module("models", bench.config["engine"])
    deployed = builder.deploy(bench)
    model, port = deployed.model, deployed.port
    sessions = bench.lib("session_traffic").Sessions(
        bench.traffic, int(bench.config["vocab_size"]))
    seq_tool.query(port, sessions.session(3, 0)[0])      # one warm query

    def connection(c):
        for rows in sessions.session(c, 0):
            seq_tool.query(port, rows)

    trace_reduce = bench.lib("trace_reduce")
    trace_dir = os.path.join(out_dir, "trace_tmp")
    trace_reduce.start_trace(trace_dir)
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        stats0 = model.stats()
        threads = [threading.Thread(target=connection, args=(c,))
                   for c in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        stats1 = model.stats()
    jax.profiler.stop_trace()
    deployed.stop()

    from predictionio_tpu.obs import jaxmon

    src = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    dst = os.path.join(out_dir, "hyb_small.xplane.pb")
    with open(src, "rb") as f:
        kept = seq_tool.slim(f.read(), other._fields)
    if len(kept) > seq_tool.MAX_BYTES:
        raise SystemExit(f"{len(kept)} bytes: too large to commit")
    with open(dst, "wb") as f:
        f.write(kept)
    reduced = trace_reduce.reduce_trace(trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)
    with open(os.path.join(out_dir, "hyb_small.scopes.json"), "w") as f:
        json.dump({k: v for k, v in jaxmon.SCOPE_MAPS.items()
                   if "prefill_fn" in k or "extend_fn" in k},
                  f, indent=0, sort_keys=True)

    def plain(stats):
        return {k: v for k, v in stats.items()
                if isinstance(v, (int, float))}

    with open(os.path.join(out_dir, "hyb_small.ctx.json"), "w") as f:
        json.dump({"stats0": plain(stats0), "stats1": plain(stats1),
                   "traced": {"busy_s": reduced["busy_s"],
                              "window_s": reduced["window_s"]},
                   "device_kind": jax.devices()[0].device_kind}, f, indent=0)
    print(dst, os.path.getsize(dst), jax.devices()[0].device_kind)
    spans = bench.lib("program_spans")
    for line in spans.report_lines(spans.load(dst)):
        print(line)


if __name__ == "__main__":
    os.makedirs(sys.argv[1], exist_ok=True)
    main(sys.argv[1])
    sys.stdout.flush()
    os._exit(0)     # the server's threads are daemons
