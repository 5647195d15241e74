#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user would call
(``bin/pio`` children, exactly as the README's Quickstart does), on one
TPU chip:

  env       versions, g++, both native libraries built from the sources,
            and the platform jax finds (a child that exits at once)
  als       app new -> eventserver (batch events, /readyz, /metrics) ->
            import (parquet) -> train -> deploy -> queries, checked
            against a plain float32 numpy reference over the factors
            read back from MODELDATA
  twotower  import -> train of templates/twotower.py at the stretch
            width (1M x 1M ids, dim 128, batch 8192), flash_ce engaged

``--chips 4`` runs instead the two paths that exist only across chips
(training over the default four-device mesh against one chip, and four
subprocess replicas behind the router against one server).

This parent never imports jax: a chip belongs to one process, and the
children need it. Every phase prints one JSON line; the last line of
stdout is ``{"ok": true, "device": {...}}`` as the children reported
the device, or ``{"ok": false, "phase": "..."}`` with a non-zero exit.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import json
import os
import shutil
import signal
import statistics
import subprocess
import sys
import tempfile
import time
import traceback
import urllib.error
import urllib.request

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
PIO = os.path.join(ROOT, "bin", "pio")
sys.path.insert(0, ROOT)    # the fleet's chip_env

#: the recommendation engine at full width (the ML-20M shape, rank 64,
#: 5 iterations). 20M ratings: a cold run of the
#: whole script then took 253 s on the chip (PR 22), under a third of
#: the 1200 s limit
FULL = {"users": 138_493, "items": 26_744, "ratings": 20_000_000,
        "rank": 64, "iters": 5,
        # two-tower stretch configuration
        "tt_ids": 1_000_000, "tt_pos": 4_000_000, "tt_dim": 128,
        "tt_batch": 8192}
#: test-only size (--tiny): control flow on the CPU in seconds
TINY = {"users": 300, "items": 120, "ratings": 12_000, "rank": 8, "iters": 2,
        "tt_ids": 2_000, "tt_pos": 8_000, "tt_dim": 16, "tt_batch": 256}

LIMIT_SEC = 1150                # the whole run, failing ones included
EVENTS_OVER_HTTP = 300          # the rest goes through `pio import`
HOLD_EVERY = 20                 # every 20th rating is held out (5%)
#: the band for held-out RMSE of ALS over `synthesize` at FULL: set
#: around the 0.427 that CPU runs of this generator read before the
#: chip, it catches a solve that got worse yet still beats the mean
RMSE_BAND = (0.38, 0.48)


class PhaseFailed(Exception):
    def __init__(self, why: str, stderr: str = ""):
        super().__init__(why)
        self.why = why
        self.stderr_tail = stderr.splitlines()[-40:]


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def parse_child_lines(text: str) -> list:
    """The JSON objects a child printed, one per line, in order; every
    other line (banners, logs) is skipped."""
    out = []
    for line in text.splitlines():
        line = line.strip()
        if line.startswith("{") and line.endswith("}"):
            try:
                obj = json.loads(line)
            except ValueError:
                continue
            if isinstance(obj, dict):
                out.append(obj)
    return out


def child_report(text: str, key: str) -> dict:
    """The last ``{key: {...}}`` object among a child's lines."""
    found = [o[key] for o in parse_child_lines(text) if key in o]
    if not found:
        raise PhaseFailed(f"child printed no {key!r} line", text)
    return found[-1]


def http(method: str, url: str, body=None, timeout: float = 60.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(url, data=data, method=method, headers={
        "Content-Type": "application/json", "Accept": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, resp.read(), dict(resp.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read(), dict(e.headers)


class Run:
    """One smoke run: a temporary store, the children's environment and
    every process started (all stopped on the way out)."""

    def __init__(self, seed: int, size: dict):
        self.seed, self.size = seed, size
        self.tmp = tempfile.mkdtemp(prefix="pio_smoke_")
        self.deadline = time.monotonic() + LIMIT_SEC
        self.procs: list = []
        store = os.path.join(self.tmp, "store")
        self.env = {
            **os.environ,
            "PIO_PYTHON": sys.executable,
            "PYTHONUNBUFFERED": "1",
            # nothing lands in $HOME: bin cache, localfs models, etc.
            "PIO_FS_BASEDIR": os.path.join(self.tmp, "base"),
            "PIO_STORAGE_SOURCES_EL_TYPE": "eventlog",
            "PIO_STORAGE_SOURCES_EL_PATH": store,
            **{f"PIO_STORAGE_REPOSITORIES_{r}_{k}": v
               for r in ("METADATA", "EVENTDATA", "MODELDATA")
               for k, v in (("NAME", r.lower()), ("SOURCE", "EL"))},
        }

    def close(self) -> None:
        for proc in self.procs:
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=30)
        shutil.rmtree(self.tmp, ignore_errors=True)

    # -- children -----------------------------------------------------------
    def run(self, argv: list, timeout: float, env: dict = None) -> str:
        """Run a child to its end; its stdout. Non-zero / timeout fails
        the phase with the child's stderr. No child outlives the run's
        own limit."""
        timeout = max(1.0, min(timeout, self.deadline - time.monotonic()))
        try:
            proc = subprocess.run(argv, env={**self.env, **(env or {})},
                                  capture_output=True, text=True,
                                  timeout=timeout, cwd=self.tmp)
        except subprocess.TimeoutExpired as e:
            raise PhaseFailed(
                f"{' '.join(argv[:3])} timed out after {timeout:.0f}s",
                (e.stderr or b"").decode(errors="replace")
                if isinstance(e.stderr, bytes) else (e.stderr or ""))
        if proc.returncode != 0:
            raise PhaseFailed(
                f"{' '.join(argv[:3])} exited {proc.returncode}",
                proc.stdout[-2000:] + "\n" + proc.stderr)
        return proc.stdout

    def pio(self, *args: str, timeout: float = 600.0, env: dict = None) -> str:
        return self.run([PIO, *args], timeout, env)

    def python(self, code: str, *args: str, timeout: float = 300.0,
               env: dict = None) -> str:
        return self.run([sys.executable, "-c", code, *args], timeout,
                        {"PYTHONPATH": ROOT, **(env or {})})

    def serve(self, *args: str, env: dict = None, boot: float = 300.0):
        """Start a `pio` server child on an ephemeral port; returns
        (proc, base_url) once it printed where it listens."""
        stem = os.path.join(self.tmp, f"server{len(self.procs)}")
        err = open(stem + ".err", "w+")
        with open(stem + ".out", "w") as out:
            proc = subprocess.Popen(
                [PIO, *args], env={**self.env, **(env or {})}, stdout=out,
                stderr=err, cwd=self.tmp)
        proc.err_file = err
        self.procs.append(proc)
        deadline = min(time.monotonic() + boot, self.deadline)
        seen = ""
        while time.monotonic() < deadline:
            with open(stem + ".out") as f:
                seen = f.read()
            for line in seen.splitlines():
                if " on 127.0.0.1:" in line:
                    port = line.split(" on 127.0.0.1:")[1].split()[0]
                    return proc, f"http://127.0.0.1:{int(port)}"
            if proc.poll() is not None:
                break
            time.sleep(0.1)
        raise PhaseFailed(f"pio {args[0]} never said where it listens "
                          f"(rc {proc.poll()}): {seen[-500:]}",
                          self.stderr_of(proc))

    @staticmethod
    def stderr_of(proc) -> str:
        proc.err_file.flush()
        proc.err_file.seek(0)
        return proc.err_file.read()

    def stop(self, proc, what: str) -> None:
        """SIGTERM drains the server; it must exit 0."""
        proc.send_signal(signal.SIGTERM)
        try:
            rc = proc.wait(timeout=90)
        except subprocess.TimeoutExpired:
            raise PhaseFailed(f"{what} ignored SIGTERM", self.stderr_of(proc))
        if rc != 0:
            raise PhaseFailed(f"{what} exited {rc} after SIGTERM",
                              self.stderr_of(proc))


# -- data --------------------------------------------------------------------

def write_parquet(path: str, users, items, ratings, event: str) -> None:
    """An interaction-shaped parquet file `pio import` bulk-loads
    through its columnar path. Columns are built as dictionaries (20M
    rows of repeated strings, formatted once per distinct value) and
    decoded to the plain strings an exported file would hold."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    n = len(users)

    def const(value: str):
        return pa.DictionaryArray.from_arrays(
            pa.array(np.zeros(n, np.int32)), pa.array([value]))

    def ids(prefix: str, codes):
        uniq, inv = np.unique(codes, return_inverse=True)
        return pa.DictionaryArray.from_arrays(
            pa.array(inv.astype(np.int32)),
            pa.array([f"{prefix}{int(v)}" for v in uniq]))

    cols = {
        "event": const(event), "entityType": const("user"),
        "entityId": ids("u", users), "targetEntityType": const("item"),
        "targetEntityId": ids("i", items),
        "eventTime": pa.array(
            1_767_225_600_000_000 + np.arange(n, dtype=np.int64) * 1000,
            pa.timestamp("us", tz="UTC")),
    }
    if ratings is not None:
        uniq, inv = np.unique(ratings, return_inverse=True)
        cols["properties"] = pa.DictionaryArray.from_arrays(
            pa.array(inv.astype(np.int32)),
            pa.array([json.dumps({"rating": float(v)}) for v in uniq]))
    pq.write_table(pa.table({
        name: col.cast(pa.string()) if pa.types.is_dictionary(col.type)
        else col for name, col in cols.items()}), path)


def synthesize(n_users, n_items, n_ratings, rng):
    """Ratings with planted rank-8 structure: clip(3 + 1.2z + noise),
    in half stars, over uniform users and Zipf-popular items."""
    uu = rng.integers(0, n_users, size=n_ratings, dtype=np.int64)
    ii = (rng.zipf(1.2, size=n_ratings) % n_items).astype(np.int64)
    U = rng.normal(size=(n_users, 8)).astype(np.float32)
    V = rng.normal(size=(n_items, 8)).astype(np.float32)
    z = np.einsum("nk,nk->n", U[uu], V[ii]) / np.sqrt(8.0)
    raw = 3.0 + 1.2 * z + rng.normal(0, 0.35, size=n_ratings).astype(np.float32)
    vals = np.clip(np.round(raw * 2.0) / 2.0, 0.5, 5.0).astype(np.float64)
    return uu, ii, vals


def als_data(run: Run):
    """`synthesize` at the run's seed, split 95/5."""
    s = run.size
    uu, ii, vals = synthesize(s["users"], s["items"], s["ratings"],
                              np.random.default_rng(run.seed))
    hold = np.arange(len(uu)) % HOLD_EVERY == 0
    return (uu[~hold], ii[~hold], vals[~hold]), (uu[hold], ii[hold], vals[hold])


_READBACK = """
import pickle, sys
import numpy as np
from predictionio_tpu.data.storage import get_storage
st = get_storage()
inst = st.engine_instances().get_latest_completed(sys.argv[1], "0", "default")
model = pickle.loads(st.models().get(inst.id).models)[0]
def ids(bimap):
    inv = bimap.inverse()
    return np.array([int(inv[r][1:]) for r in range(len(inv))], np.int64)
np.savez(sys.argv[2], X=np.asarray(model.user_factors, np.float32),
         Y=np.asarray(model.item_factors, np.float32),
         users=ids(model.user_ids), items=ids(model.item_ids))
"""


def read_factors(run: Run, engine_id: str):
    """Factors + id maps read back from MODELDATA by a child held to
    the CPU backend (unpickling a model imports jax; this parent must
    not)."""
    out = os.path.join(run.tmp, f"{engine_id}.npz")
    run.python(_READBACK, engine_id, out, env={"JAX_PLATFORMS": "cpu"})
    with np.load(out) as z:
        return {k: z[k] for k in z.files}


def heldout_rmse(factors: dict, held, train_mean: float):
    """Held-out RMSE by plain numpy, and the global-mean baseline;
    a pair whose user or item the model never saw predicts 0."""
    hu, hi, hv = held
    urow = {int(u): r for r, u in enumerate(factors["users"])}
    irow = {int(i): r for r, i in enumerate(factors["items"])}
    ur = np.array([urow.get(int(u), -1) for u in hu])
    ir = np.array([irow.get(int(i), -1) for i in hi])
    known = (ur >= 0) & (ir >= 0)
    pred = np.zeros(len(hv), np.float64)
    pred[known] = np.einsum("nk,nk->n", factors["X"][ur[known]],
                            factors["Y"][ir[known]])
    rmse = float(np.sqrt(np.mean((pred - hv) ** 2)))
    base = float(np.sqrt(np.mean((hv - train_mean) ** 2)))
    return rmse, base


def check_top10(factors: dict, user: int, answer: list) -> None:
    """One served top-10 against float32 numpy ``X[u] @ Y.T``: each
    returned score within 1e-2 relative of the reference's score for
    THAT item, and at least 9 of 10 items shared (the chip's default
    matmul precision is not numpy's, so near-ties may swap)."""
    urow = int(np.nonzero(factors["users"] == user)[0][0])
    ref = factors["X"][urow] @ factors["Y"].T
    irow = {int(i): r for r, i in enumerate(factors["items"])}
    top = {int(factors["items"][r]) for r in np.argsort(-ref)[:10]}
    got = [(int(a["item"][1:]), float(a["score"])) for a in answer]
    if len(got) != 10:
        raise PhaseFailed(f"user u{user}: {len(got)} items, not 10")
    scale = float(np.abs(ref).max())
    for item, score in got:
        want = float(ref[irow[item]])
        if abs(score - want) > 1e-2 * max(abs(want), 1e-3 * scale):
            raise PhaseFailed(f"user u{user} item i{item}: served score "
                              f"{score} vs numpy {want}")
    shared = len(top & {i for i, _ in got})
    if shared < 9:
        raise PhaseFailed(f"user u{user}: only {shared}/10 items shared "
                          "with the numpy top-10")


def require_tpu(report: dict, count: int) -> None:
    if report.get("platform") != "tpu":
        raise PhaseFailed(f"a child found platform {report.get('platform')!r}, "
                          "not 'tpu'")
    if report.get("device_count") != count:
        raise PhaseFailed(f"child saw {report.get('device_count')} devices, "
                          f"expected {count}")


# -- phases ------------------------------------------------------------------

_ENV_CHILD = """
import importlib.metadata as md, json, platform, time
from predictionio_tpu.native import build_library
out = {"python": platform.python_version()}
for pkg in ("jax", "jaxlib", "libtpu", "flax", "numpy"):
    try:
        out[pkg] = md.version(pkg)
    except md.PackageNotFoundError:
        out[pkg] = None
t0 = time.time()
out["native"] = {name: build_library(name) for name in ("eventlog", "raggedbin")}
out["native_build_sec"] = round(time.time() - t0, 1)
import jax
devices = jax.devices()
out.update(platform=devices[0].platform, device_kind=devices[0].device_kind,
           device_count=len(devices))
print(json.dumps({"env_report": out}))
"""


def phase_env(run: Run) -> dict:
    gxx = subprocess.run(["g++", "--version"], capture_output=True, text=True)
    if gxx.returncode != 0:
        raise PhaseFailed("g++ --version failed", gxx.stderr)
    report = child_report(run.python(_ENV_CHILD, timeout=600), "env_report")
    # what jax finds here, asked by a child that has exited before the
    # next one needs the chip: the als / mesh phase fails on it at once,
    # before minutes of data are made for a platform that is not there
    run.found = report
    return {"gxx": gxx.stdout.splitlines()[0], **report}


def train(run: Run, engine_id: str, variant: dict, env: dict = None):
    """Write the engine.json and `pio train` it in a process of its
    own; (engine.json path, the train child's report)."""
    path = os.path.join(run.tmp, f"{engine_id}.json")
    with open(path, "w") as f:
        json.dump(variant, f)
    t0 = time.time()
    out = run.pio("train", "--engine-json", path, "--engine-id", engine_id,
                  timeout=900, env=env)
    report = child_report(out, "train_report")
    report["train_wall_sec"] = round(time.time() - t0, 1)
    return path, report


def import_and_train(run: Run, app: str, engine_id: str, parquet: str,
                     variant: dict):
    t0 = time.time()
    run.pio("import", "--appname", app, "--input", parquet)
    import_sec = round(time.time() - t0, 1)
    path, report = train(run, engine_id, variant)
    report["import_sec"] = import_sec
    return path, report


def als_variant(run: Run, app: str) -> dict:
    s = run.size
    return {
        "id": "default",
        "engineFactory":
            "predictionio_tpu.templates.recommendation.recommendation_engine",
        "datasource": {"params": {"app_name": app}},
        "algorithms": [{"name": "als", "params": {
            "rank": s["rank"], "num_iterations": s["iters"],
            "lambda_": 0.05, "block_size": 4096}}],
    }


def new_app(run: Run, name: str) -> str:
    out = run.pio("app", "new", name)
    for line in out.splitlines():
        if "Access Key:" in line:
            return line.split("Access Key:")[1].strip()
    raise PhaseFailed("pio app new printed no access key", out)


def query_all(base: str, queries: list, in_flight: int):
    """POST every query; ([answers], [seconds], [headers])."""
    def one(q):
        t0 = time.perf_counter()
        status, body, headers = http("POST", base + "/queries.json", q)
        if status != 200:
            raise PhaseFailed(f"query {q} answered {status}: {body[:200]}")
        return json.loads(body), time.perf_counter() - t0, headers

    if in_flight == 1:
        done = [one(q) for q in queries]
    else:
        with concurrent.futures.ThreadPoolExecutor(in_flight) as pool:
            done = list(pool.map(one, queries))
    return [d[0] for d in done], [d[1] for d in done], [d[2] for d in done]


def check_rmse(rmse: float, base_rmse: float, n_ratings: int) -> None:
    """The band holds for the full data set only; a reduced one has to
    beat the global mean by 15%."""
    if n_ratings == FULL["ratings"]:
        if not RMSE_BAND[0] <= rmse <= RMSE_BAND[1]:
            raise PhaseFailed(f"held-out RMSE {rmse:.4f} outside the "
                              f"band {RMSE_BAND}")
    elif not rmse <= 0.85 * base_rmse:
        raise PhaseFailed(f"held-out RMSE {rmse:.4f} not 15% better than the "
                          f"global mean's {base_rmse:.4f}")


def phase_als(run: Run) -> dict:
    require_tpu(run.found, 1)
    s = run.size
    t0 = time.time()
    kept, held = als_data(run)
    tu, ti, tv = kept
    parquet = os.path.join(run.tmp, "ratings.parquet")
    write_parquet(parquet, tu[EVENTS_OVER_HTTP:], ti[EVENTS_OVER_HTTP:],
                  tv[EVENTS_OVER_HTTP:], "rate")
    out = {"ratings": s["ratings"],
           "reduced": (None if s["ratings"] == FULL["ratings"] else
                       f"ratings {FULL['ratings']} -> {s['ratings']}"),
           "synth_sec": round(time.time() - t0, 1)}

    key = new_app(run, "smoke")
    # the event server: a few hundred events over HTTP, and it answers
    # /readyz and /metrics WITHOUT taking the chip
    proc, base = run.serve("eventserver", "--ip", "127.0.0.1", "--port", "0")
    for lo in range(0, EVENTS_OVER_HTTP, 50):
        batch = [{"event": "rate", "entityType": "user",
                  "entityId": f"u{int(tu[k])}", "targetEntityType": "item",
                  "targetEntityId": f"i{int(ti[k])}",
                  "properties": {"rating": float(tv[k])},
                  "eventTime": "2026-01-01T00:00:00.000Z"}
                 for k in range(lo, lo + 50)]
        status, body, _ = http(
            "POST", f"{base}/batch/events.json?accessKey={key}", batch)
        results = json.loads(body) if status == 200 else []
        if status != 200 or any(r.get("status") != 201 for r in results):
            raise PhaseFailed(f"batch events answered {status}: {body[:300]}",
                              run.stderr_of(proc))
    status, body, _ = http("GET", base + "/readyz")
    ready = json.loads(body)
    devices = ready.get("probes", {}).get("devices", {})
    if status != 200 or "no device in this process" not in json.dumps(devices):
        raise PhaseFailed(f"event server /readyz {status}: {body[:400]} — "
                          "it must answer without touching a device",
                          run.stderr_of(proc))
    status, body, _ = http("GET", base + "/metrics")
    if status != 200 or b"pio_" not in body:
        raise PhaseFailed(f"event server /metrics answered {status}",
                          run.stderr_of(proc))
    run.stop(proc, "pio eventserver")

    variant_path, report = import_and_train(
        run, "smoke", "smoke_als", parquet, als_variant(run, "smoke"))
    out["train"] = report
    require_tpu(report, 1)

    t0 = time.time()
    factors = read_factors(run, "smoke_als")
    rmse, base_rmse = heldout_rmse(factors, held, float(tv.mean()))
    out.update({"rmse_heldout": rmse, "rmse_global_mean": base_rmse,
                "readback_sec": round(time.time() - t0, 1)})
    check_rmse(rmse, base_rmse, s["ratings"])

    t0 = time.time()
    proc, base = run.serve("deploy", "--engine-json", variant_path,
                           "--engine-id", "smoke_als", "--ip", "127.0.0.1",
                           "--port", "0")
    out["deploy_boot_sec"] = round(time.time() - t0, 1)
    rng = np.random.default_rng(run.seed + 1)
    users = [int(u) for u in rng.choice(factors["users"], 44, replace=False)]
    items = [int(i) for i in rng.choice(factors["items"], 5, replace=False)]
    queries = ([{"user": f"u{u}", "num": 10} for u in users[:20]]
               + [{"item": f"i{i}", "num": 10} for i in items])
    answers, secs, _ = query_all(base, queries, in_flight=1)
    for u, a in zip(users[:5], answers[:5]):
        check_top10(factors, u, a["itemScores"])
    for q, a in zip(queries, answers):
        if len(a["itemScores"]) != 10:
            raise PhaseFailed(f"{q} answered {len(a['itemScores'])} items")
    burst = [{"user": f"u{u}", "num": 10} for u in users[20:]]
    _, burst_secs, _ = query_all(base, burst, in_flight=8)
    status, body, _ = http("GET", base + "/")
    st = json.loads(body)
    retrieval = (st.get("retrieval") or [None])[0] or {}
    kernel = retrieval.get("kernel") or {}
    routes = retrieval.get("routes") or {}
    counted = len(queries) + len(burst)
    out.update({
        "queries": counted,
        "query_p50_ms_1_in_flight": statistics.median(secs) * 1e3,
        "query_p50_ms_8_in_flight": statistics.median(burst_secs) * 1e3,
        "retrieval_kernel": kernel, "routes": routes,
        "dispatch_latency_ms": st["device"]["dispatch_latency_sec"] * 1e3,
        "serve_device": {k: st["device"].get(k) for k in
                         ("platform", "device_kind", "device_count",
                          "compile_cache")},
    })
    if not kernel.get("engaged") or kernel.get("interpret"):
        raise PhaseFailed(f"topk_dot not engaged compiled: {kernel}",
                          run.stderr_of(proc))
    # the kernel's own count of the tiles it merged, as GET / shows it
    if not 1 <= kernel.get("merged_tiles", 0) <= kernel.get("tiles", 0):
        raise PhaseFailed(f"topk_dot reported no merged-tile count: {kernel}",
                          run.stderr_of(proc))
    # the counters count dispatches (queries in flight together share
    # one): every search the index served must have gone through the
    # kernel, none to the XLA scorer or its host scan, and the queries
    # sent one at a time are one dispatch each
    if (routes.get("host") or routes.get("xla_device")
            or routes.get("kernel") != retrieval.get("searches")
            or routes.get("kernel", 0) < len(queries)):
        raise PhaseFailed(f"{counted} queries were sent but the server's "
                          f"route counters say {routes} of "
                          f"{retrieval.get('searches')} searches",
                          run.stderr_of(proc))
    require_tpu(st["device"], 1)
    run.stop(proc, "pio deploy")
    out["device"] = {"platform": report["platform"],
                     "kind": report["device_kind"],
                     "count": report["device_count"]}
    return out


def phase_twotower(run: Run) -> dict:
    s = run.size
    n, pos = s["tt_ids"], s["tt_pos"]
    t0 = time.time()
    # clustered positives (64 clusters, 80% of a user's items from its
    # own); the first n rows are a permutation on each side so every id
    # appears and the tables are exactly n rows wide
    rng = np.random.default_rng(run.seed + 2)
    n_clusters = 64
    user_cluster = rng.integers(0, n_clusters, size=n)
    uu = rng.integers(0, n, size=pos)
    per_cluster = n // n_clusters
    ii = np.where(rng.random(pos) < 0.8,
                  user_cluster[uu] + n_clusters * rng.integers(0, per_cluster, pos),
                  rng.integers(0, n, size=pos)).astype(np.int64)
    uu[:n], ii[:n] = rng.permutation(n), rng.permutation(n)
    parquet = os.path.join(run.tmp, "interactions.parquet")
    write_parquet(parquet, uu, ii, None, "buy")
    out = {"synth_sec": round(time.time() - t0, 1)}
    new_app(run, "smoke_tt")
    variant = {
        "id": "default",
        "engineFactory": "predictionio_tpu.templates.twotower.twotower_engine",
        "datasource": {"params": {"app_name": "smoke_tt"}},
        "algorithms": [{"name": "twotower", "params": {
            "dim": s["tt_dim"], "batch_size": s["tt_batch"], "epochs": 1,
            "learning_rate": 3e-3, "seed": 11}}],
    }
    _, report = import_and_train(run, "smoke_tt", "smoke_tt", parquet, variant)
    out["train"] = report
    require_tpu(report, 1)
    tt = (report.get("trainers") or {}).get("twotower")
    if not tt:
        raise PhaseFailed("pio train reported no two-tower trainer")
    plan = tt["kernel_plan"]
    if plan.get("flash_ce") is not True or plan.get("interpret") is not False:
        raise PhaseFailed(f"flash_ce not engaged compiled: {plan}")
    if not tt["last_step_loss"] < tt["first_step_loss"]:
        raise PhaseFailed(f"the loss did not fall: first step "
                          f"{tt['first_step_loss']}, last {tt['last_step_loss']}")
    out.update({"kernel_plan": plan, "steps": tt["steps_per_epoch"],
                "step_ms": tt["step_ms"],
                "step_window": "one epoch dispatch to block_until_ready, "
                               "compiled ahead of time",
                "first_step_loss": tt["first_step_loss"],
                "last_step_loss": tt["last_step_loss"]})
    return out


def phase_mesh(run: Run) -> dict:
    """--chips 4: `pio train` of the ALS engine with one chip visible,
    then with four (the default mesh puts every device on ``data``)."""
    from predictionio_tpu.serving.fleet import chip_env

    require_tpu(run.found, 4)
    kept, held = als_data(run)
    tu, ti, tv = kept
    parquet = os.path.join(run.tmp, "ratings.parquet")
    write_parquet(parquet, tu, ti, tv, "rate")
    new_app(run, "smoke")
    run.pio("import", "--appname", "smoke", "--input", parquet)
    out = {"ratings": run.size["ratings"]}
    got = {}
    for tag, env, count in (("one_chip", chip_env(0), 1), ("four_chips", {}, 4)):
        _, report = train(run, f"als_{tag}", als_variant(run, "smoke"), env)
        require_tpu(report, count)
        factors = read_factors(run, f"als_{tag}")
        rmse, _ = heldout_rmse(factors, held, float(tv.mean()))
        got[tag] = (factors, rmse)
        out[tag] = {"train": report, "rmse_heldout": rmse}
    placed = out["four_chips"]["train"]["trainers"]["als"]
    per_device = placed["placed_bytes_in_use"]
    out["placed_bytes_in_use"] = per_device
    if (len(per_device) != 4 or not all(per_device)
            or max(per_device) >= placed["transfer_bytes"]):
        raise PhaseFailed(f"binned arrays not shared over four devices: "
                          f"{per_device} of {placed['transfer_bytes']} bytes")
    (f1, r1), (f4, r4) = got["one_chip"], got["four_chips"]
    if abs(r1 - r4) > 1e-3:
        raise PhaseFailed(f"RMSE one chip {r1} vs four chips {r4}")
    rng = np.random.default_rng(run.seed + 1)
    for u in rng.choice(f1["users"], 5, replace=False):
        urow = int(np.nonzero(f4["users"] == u)[0][0])
        ref = f4["X"][urow] @ f4["Y"].T
        top = np.argsort(-ref)[:10]
        check_top10(f1, int(u), [{"item": f"i{int(f4['items'][r])}",
                                  "score": float(ref[r])} for r in top])
    out["device"] = {"platform": out["four_chips"]["train"]["platform"],
                     "kind": out["four_chips"]["train"]["device_kind"],
                     "count": out["four_chips"]["train"]["device_count"]}
    run.mesh_factors = f4
    return out


def phase_fleet(run: Run) -> dict:
    """--chips 4: four subprocess replicas behind the router, one chip
    each, against the single server's answers."""
    from predictionio_tpu.serving.fleet import chip_env

    factors = run.mesh_factors
    path = os.path.join(run.tmp, "als_four_chips.json")
    rng = np.random.default_rng(run.seed + 3)
    queries = [{"user": f"u{int(u)}", "num": 10}
               for u in rng.choice(factors["users"], 40, replace=False)]
    proc, base = run.serve("deploy", "--engine-json", path, "--engine-id",
                           "als_four_chips", "--ip", "127.0.0.1", "--port", "0",
                           env=chip_env(0))
    single, _, _ = query_all(base, queries, in_flight=1)
    run.stop(proc, "pio deploy (single)")

    proc, base = run.serve("deploy", "--engine-json", path, "--engine-id",
                           "als_four_chips", "--ip", "127.0.0.1", "--port", "0",
                           "--replicas", "4", "--replica-mode", "subprocess",
                           boot=600)
    deadline = time.monotonic() + 300
    fleet = {}
    while time.monotonic() < deadline:
        status, body, _ = http("GET", base + "/admin/fleet")
        fleet = json.loads(body) if status == 200 else {}
        if fleet.get("ready") == 4:
            break
        time.sleep(1.0)
    if fleet.get("ready") != 4:
        raise PhaseFailed(f"fleet never had four ready replicas: "
                          f"{json.dumps(fleet)[:600]}", run.stderr_of(proc))
    chips = {}
    for rep in fleet["replicas"]:
        _, body, _ = http("GET", f"http://127.0.0.1:{rep['port']}/")
        dev = json.loads(body)["device"]
        require_tpu(dev, 1)
        chips[rep["name"]] = dev["visible_chips"]
    if len(set(chips.values())) != 4:
        raise PhaseFailed(f"replicas do not hold four distinct chips: {chips}")
    answers, secs, headers = query_all(base, queries, in_flight=4)
    served_by = sorted({h.get("X-PIO-Replica") for h in headers})
    if len(served_by) != 4:
        raise PhaseFailed(f"40 queries reached only {served_by}")
    for q, a, b in zip(queries, answers, single):
        ia = [(x["item"], round(x["score"], 4)) for x in a["itemScores"]]
        ib = [(x["item"], round(x["score"], 4)) for x in b["itemScores"]]
        if ia != ib:
            raise PhaseFailed(f"{q}: fleet answered {ia}, single server {ib}")
    run.stop(proc, "pio deploy --replicas 4")
    return {"replica_chips": chips, "served_by": served_by,
            "query_p50_ms_4_in_flight": statistics.median(secs) * 1e3}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run the four-chip paths (mesh training, "
                         "replicas behind the router) and nothing else")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="test-only size (control flow on the CPU)")
    args = ap.parse_args(argv)
    if not os.path.isfile(PIO):
        emit({"ok": False, "phase": "env",
              "why": f"{PIO} is missing: chip_smoke.py runs from a checkout"})
        return 1
    phases = ([("env", phase_env), ("als", phase_als),
               ("twotower", phase_twotower)] if args.chips == 1 else
              [("env", phase_env), ("mesh", phase_mesh),
               ("fleet", phase_fleet)])
    run = Run(args.seed, TINY if args.tiny else FULL)
    device = None
    t_all = time.time()
    try:
        for name, fn in phases:
            t0 = time.time()
            try:
                result = fn(run)
            except Exception as e:  # noqa: BLE001 — reported, then exit 1
                tail = (e.stderr_tail if isinstance(e, PhaseFailed) else
                        traceback.format_exc().splitlines()[-40:])
                emit({"phase": name, "ok": False,
                      "seconds": round(time.time() - t0, 1),
                      "why": f"{type(e).__name__}: {e}", "stderr_tail": tail})
                emit({"ok": False, "phase": name})
                return 1
            device = result.pop("device", device)
            emit({"phase": name, "ok": True,
                  "seconds": round(time.time() - t0, 1), **result})
    finally:
        run.close()
    emit({"phase": "total", "ok": True,
          "seconds": round(time.time() - t_all, 1)})
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
