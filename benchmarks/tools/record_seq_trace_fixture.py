"""Record the small trace that tests/benchmarks/test_seq_cell.py checks the
sequence engine's per-layer readers against (run once on the chip; committed
as tests/benchmarks/fixtures/seq_small.xplane.pb, with seq_small.scopes.json —
the program's instruction -> scope maps — and seq_small.ctx.json — the
engine's counters at the stretch's two ends and the step worker's splits —
beside it):

    python3 benchmarks/tools/record_seq_trace_fixture.py <out_dir>

One process deploys the tiny rehearsal configuration
(tests/benchmarks/tiny_seq) as the benchmark's builder does, and under one
``bench:window`` two connections play one session each: a history of several
chunks, then extensions, so that a step holds an extension batch and a
prefill chunk. What no reader reads is taken out of the file (``slim``).
"""

import argparse
import glob
import importlib.util
import json
import os
import shutil
import sys
import threading
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
BENCHMARKS = os.path.dirname(HERE)
CHECKOUT = os.path.dirname(BENCHMARKS)
TINY = os.path.join(CHECKOUT, "tests", "benchmarks", "tiny_seq")
CELL = "longcat-tiny.sessions-c4"
MAX_BYTES = 1024 * 1024


def _varint_bytes(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(number: int, payload: bytes) -> bytes:
    """One length-delimited protobuf field."""
    return (_varint_bytes(number << 3 | 2) + _varint_bytes(len(payload))
            + payload)


def slim(xspace: bytes, fields, keep_host=("pio:", "bench:")) -> bytes:
    """The XSpace with what no reader here reads taken out, so that the
    file is small enough to commit: the ``/host:metadata`` plane; of a device
    plane every line but ``XLA Ops`` and ``XLA Modules``, and of an event's
    metadata everything but its id and the first word of its name (the
    instruction's name: the rest is the instruction's text and its HLO
    proto); of a host plane every event whose name does not start with one
    of ``keep_host``. (``XSpace.planes = 1``; ``XPlane``: ``name = 2``,
    ``lines = 3``, ``event_metadata = 4`` (map: key 1, value 2);
    ``XEventMetadata``: ``id = 1``, ``name = 2``; ``XLine``: ``name = 2``,
    ``events = 4``; ``XEvent.metadata_id = 1``.)"""
    out = bytearray()
    for number, plane, whole in fields(xspace):
        if number != 1 or plane is None:
            out += whole
            continue
        parts = list(fields(plane))
        name = next((p for n, p, _ in parts if n == 2), b"").decode()
        if name == "/host:metadata":
            continue
        device = name.startswith("/device:")
        names = {}                      # metadata id -> event name
        for n, entry, _ in parts:
            if n == 4:
                value = next(p for k, p, _ in fields(entry) if k == 2)
                ident = label = None
                for k, p, w in fields(value):
                    if k == 1:
                        ident = w
                    elif k == 2:
                        label = p.decode("utf-8", "replace")
                names[ident] = label or ""
        slimmed = bytearray()
        for n, payload, w in parts:
            if n == 4 and device:
                key = next(x for k, _, x in fields(payload) if k == 1)
                value = next(p for k, p, _ in fields(payload) if k == 2)
                ident = next(x for k, _, x in fields(value) if k == 1)
                short = names[ident].split(" ", 1)[0].encode()
                slimmed += _field(4, key + _field(2, ident + _field(2, short)))
            elif n == 3:
                line = list(fields(payload))
                line_name = next((p for k, p, _ in line if k == 2),
                                 b"").decode()
                if device:
                    if line_name in ("XLA Ops", "XLA Modules"):
                        slimmed += w
                    continue
                kept = bytearray()
                for k, event, ew in line:
                    if k == 4:
                        ident = next(x for j, _, x in fields(event) if j == 1)
                        if not names.get(ident, "").startswith(keep_host):
                            continue
                    kept += ew
                if any(k == 4 for k, _, _ in fields(bytes(kept))):
                    slimmed += _field(3, bytes(kept))
            else:
                slimmed += w
        out += _field(1, bytes(slimmed))
    return bytes(out)


def load(path):
    spec = importlib.util.spec_from_file_location(
        "_fixture_" + os.path.basename(path)[:-3], path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return module


def query(port, rows):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/queries.json",
        data=json.dumps({"items": [f"i{r}" for r in rows],
                         "num": 5}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as resp:
        return json.loads(resp.read())


def main(out_dir: str) -> None:
    sys.path.insert(0, CHECKOUT)
    import jax

    run = load(os.path.join(BENCHMARKS, "run.py"))
    other = load(os.path.join(HERE, "record_program_trace_fixture.py"))
    with open(os.path.join(TINY, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    bench = run.Bench(TINY, spec, cell, argparse.Namespace(
        seed=7, seconds=1, trace=1))
    bench.devices = jax.devices()[:1]
    builder = bench.load_module("models", bench.config["engine"])
    deployed = builder.deploy(bench)
    model, batcher = deployed.model, deployed.batcher
    sessions = bench.lib("session_traffic").Sessions(
        bench.traffic, int(bench.config["vocab_size"]))
    query(deployed.port, sessions.session(3, 0)[0])      # one warm query

    def connection(c):
        for s in range(1):
            for rows in sessions.session(c, s):
                query(deployed.port, rows)

    trace_reduce = bench.lib("trace_reduce")
    trace_dir = os.path.join(out_dir, "trace_tmp")
    trace_reduce.start_trace(trace_dir)
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        stats0, h0 = model.stats(), batcher.histogram()
        threads = [threading.Thread(target=connection, args=(c,))
                   for c in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        stats1, h1 = model.stats(), batcher.histogram()
    splits = batcher.recent_splits(h1["answered"] - h0["answered"])
    jax.profiler.stop_trace()
    deployed.stop()

    from predictionio_tpu.obs import jaxmon

    src = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    dst = os.path.join(out_dir, "seq_small.xplane.pb")
    with open(src, "rb") as f:
        kept = slim(f.read(), other._fields)
    if len(kept) > MAX_BYTES:
        raise SystemExit(f"{len(kept)} bytes: too large to commit")
    with open(dst, "wb") as f:
        f.write(kept)
    reduced = trace_reduce.reduce_trace(trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)
    with open(os.path.join(out_dir, "seq_small.scopes.json"), "w") as f:
        json.dump({k: v for k, v in jaxmon.SCOPE_MAPS.items()
                   if "prefill_fn" in k or "extend_fn" in k},
                  f, indent=0, sort_keys=True)

    def plain(stats):
        return {k: v for k, v in stats.items()
                if isinstance(v, (int, float))}

    with open(os.path.join(out_dir, "seq_small.ctx.json"), "w") as f:
        json.dump({"stats0": plain(stats0), "stats1": plain(stats1),
                   "splits": splits,
                   "traced": {"busy_s": reduced["busy_s"],
                              "window_s": reduced["window_s"]},
                   "device_kind": jax.devices()[0].device_kind}, f, indent=0)
    print(dst, os.path.getsize(dst), jax.devices()[0].device_kind,
          "histogram", h1)
    spans = bench.lib("program_spans")
    for line in spans.report_lines(spans.load(dst)):
        print(line)


if __name__ == "__main__":
    os.makedirs(sys.argv[1], exist_ok=True)
    main(sys.argv[1])
    sys.stdout.flush()
    os._exit(0)     # the server's threads are daemons
