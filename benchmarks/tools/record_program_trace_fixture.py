"""Record the small trace that tests/benchmarks/test_program_spans.py checks
``program_spans`` and the per-layer readers against (run once on the chip; the
result is committed as tests/benchmarks/fixtures/pio_small.xplane.pb with
pio_small.scopes.json, the program's instruction -> scope maps, beside it):

    python3 benchmarks/tools/record_program_trace_fixture.py <out_dir>

One process holds a small deployed ALS engine (the program's EngineServer,
built as the benchmark builds it) and a small two-tower trainer. Under one
``bench:window``: two queries alone, then three connections of three queries
each (so the batcher forms batches), then two epochs of four steps. Sizes are
the smallest at which the Pallas kernels are engaged; the traffic is a dozen
requests, and the ``/host:metadata`` plane (the compiled programs' HLO protos,
two thirds of the file, read by nothing here) is dropped, so that the file
stays small enough to commit (``MAX_BYTES``).
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
TINY = os.path.join(CHECKOUT, "tests", "benchmarks", "tiny")

MAX_BYTES = 256 * 1024

ALS = {"n_users": 500, "n_items": 30000, "rank": 64}
TWOTOWER = {"n_users": 3000, "n_items": 5000, "dim": 128, "batch_size": 1024,
            "n_positives": 4096, "compute_dtype": "bfloat16",
            "flash_ce_kernel": "on"}


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        out |= (b & 0x7F) << shift
        i += 1
        if not b & 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, payload or None, the field's bytes) of one protobuf
    message."""
    i = 0
    while i < len(buf):
        start = i
        tag, i = _varint(buf, i)
        wire, payload = tag & 7, None
        if wire == 0:
            _, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            payload = buf[i:i + n]
            i += n
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire}")
        yield tag >> 3, payload, buf[start:i]


def without_plane(xspace: bytes, plane_name: str) -> bytes:
    """An XSpace (``repeated XPlane planes = 1``; ``XPlane.name = 2``)
    without the plane of that name."""
    out = bytearray()
    for number, payload, whole in _fields(xspace):
        if number == 1 and payload is not None and any(
                n == 2 and p == plane_name.encode()
                for n, p, _ in _fields(payload)):
            continue
        out += whole
    return bytes(out)


def load(path):
    spec = importlib.util.spec_from_file_location(
        "_fixture_" + os.path.basename(path)[:-3], path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return module


def make_bench(run, workload, overrides):
    with open(os.path.join(TINY, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cell = next(w for w in spec["workloads"] if w["name"] == workload)
    bench = run.Bench(TINY, spec, cell, argparse.Namespace(
        seed=7, seconds=1, trace=1))
    bench.config.update(overrides)
    return bench


def query(port, user):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/queries.json",
        data=json.dumps({"user": user, "num": 10}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as resp:
        return json.loads(resp.read())


def main(out_dir: str) -> None:
    sys.path.insert(0, CHECKOUT)
    import jax

    run = load(os.path.join(BENCHMARKS, "run.py"))
    serve = make_bench(run, "als-tiny.serve-c4", ALS)
    train = make_bench(run, "twotower-tiny.train", TWOTOWER)
    serve.devices = train.devices = jax.devices()[:1]
    als = serve.load_module("models", "als")
    deployed = als.deploy(serve)
    users = [als.user_id(j) for j in range(16)]
    for size in (2, 3):                       # every shape the window uses
        deployed.server.deployment.query_batch(
            [{"user": u, "num": 10} for u in users[:size]])
    for u in users[:3]:
        query(deployed.port, u)
    built = train.load_module("models", "twotower").build(train)
    built.first_call()

    def connection(mine):
        for u in mine:
            query(deployed.port, u)

    trace_reduce = serve.lib("trace_reduce")
    trace_dir = os.path.join(out_dir, "trace_tmp")
    trace_reduce.start_trace(trace_dir)
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        connection(users[:2])
        threads = [threading.Thread(target=connection,
                                    args=(users[4 + 3 * c: 7 + 3 * c],))
                   for c in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        built.call()
        built.call()
    jax.profiler.stop_trace()
    deployed.stop()

    from predictionio_tpu.obs import jaxmon

    src = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    dst = os.path.join(out_dir, "pio_small.xplane.pb")
    with open(src, "rb") as f:
        kept = without_plane(f.read(), "/host:metadata")
    if len(kept) > MAX_BYTES:
        raise SystemExit(f"{len(kept)} bytes: too large to commit")
    with open(dst, "wb") as f:
        f.write(kept)
    shutil.rmtree(trace_dir, ignore_errors=True)
    with open(os.path.join(out_dir, "pio_small.scopes.json"), "w") as f:
        json.dump(jaxmon.SCOPE_MAPS, f, indent=0, sort_keys=True)
    print(dst, os.path.getsize(dst), jax.devices()[0].device_kind,
          "histogram", deployed.batcher.histogram())
    spans = serve.lib("program_spans")
    for line in spans.report_lines(spans.load(dst)):
        print(line)


if __name__ == "__main__":
    os.makedirs(sys.argv[1], exist_ok=True)
    main(sys.argv[1])
    sys.stdout.flush()
    os._exit(0)     # the server's threads are daemons
