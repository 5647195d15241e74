"""Record the small trace that tests/benchmarks/test_gen_cell.py checks the
block-diffusion engine's per-layer readers against (run once on the chip;
committed as tests/benchmarks/fixtures/gen_small.xplane.pb, with
gen_small.scopes.json — the program's instruction -> scope maps — and
gen_small.ctx.json — the engine's counters at the stretch's two ends —
beside it):

    python3 benchmarks/tools/record_gen_trace_fixture.py <out_dir>

``record_seq_trace_fixture.py`` for the slate cell: one process deploys the
tiny rehearsal configuration (tests/benchmarks/tiny_gen) as the benchmark's
builder does, and under one ``bench:window`` two connections play the first
two queries of a session each (a history of a few chunks, then a follow-up
over reused blocks), every query a slate of 6,
so that a step holds a block forward of two rows and a prefill chunk. What no
reader reads is taken out of the file (that tool's ``slim``).
"""

import argparse
import glob
import json
import os
import shutil
import sys
import threading
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
BENCHMARKS = os.path.dirname(HERE)
CHECKOUT = os.path.dirname(BENCHMARKS)
TINY = os.path.join(CHECKOUT, "tests", "benchmarks", "tiny_gen")
CELL = "sdar-tiny.slates-c4"


def query(port, rows, generate):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/queries.json",
        data=json.dumps({"items": [f"i{r}" for r in rows],
                         "generate": generate}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as resp:
        return json.loads(resp.read())


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
    model, batcher = deployed.model, deployed.batcher
    generate = int(bench.traffic["generate"])
    sessions = bench.lib("slate_traffic").Sessions(
        bench.traffic, builder.n_traffic_items(bench.config))
    query(deployed.port, sessions.session(3, 0)[0], generate)   # warm

    def connection(c):      # a first query and one follow-up
        for rows in sessions.session(c, 0)[:2]:
            query(deployed.port, rows, generate)

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
    dst = os.path.join(out_dir, "gen_small.xplane.pb")
    with open(src, "rb") as f:
        kept = seq_tool.slim(f.read(), other._fields)
    if len(kept) > seq_tool.MAX_BYTES:
        raise SystemExit(f"{len(kept)} bytes: too large to commit")
    with open(dst, "wb") as f:
        f.write(kept)
    reduced = trace_reduce.reduce_trace(trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)
    with open(os.path.join(out_dir, "gen_small.scopes.json"), "w") as f:
        json.dump({k: v for k, v in jaxmon.SCOPE_MAPS.items()
                   if "prefill_fn" in k or "block_fn" in k},
                  f, indent=0, sort_keys=True)

    def plain(stats):
        return {k: v for k, v in stats.items()
                if isinstance(v, (int, float))}

    with open(os.path.join(out_dir, "gen_small.ctx.json"), "w") as f:
        json.dump({"stats0": plain(stats0), "stats1": plain(stats1),
                   "traced": {"busy_s": reduced["busy_s"],
                              "window_s": reduced["window_s"]},
                   "device_kind": jax.devices()[0].device_kind}, f, indent=0)
    print(dst, os.path.getsize(dst), jax.devices()[0].device_kind,
          "histogram", batcher.histogram())
    spans = bench.lib("program_spans")
    for line in spans.report_lines(spans.load(dst)):
        print(line)


if __name__ == "__main__":
    os.makedirs(sys.argv[1], exist_ok=True)
    main(sys.argv[1])
    sys.stdout.flush()
    os._exit(0)     # the server's threads are daemons
