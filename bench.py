"""Headline benchmark: the full events->model->serving pipeline at
MovieLens-20M scale on one chip.

Prints ONE COMPACT JSON line (< MAX_HEADLINE_BYTES — the driver only
captures a ~2KB stdout tail, BENCH_r04 lesson):
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N,
   "gates": {...}, "key": {...}, "detail_file": "BENCH_DETAIL.json"}
The full detail blob (histograms, per-run arrays, roofline trace) is
written to BENCH_DETAIL.json beside this file and committed.

Unlike a kernel microbench, this drives the framework's own data path —
the `pio train` call stack (SURVEY.md §3.1) — TWICE, in two fresh
processes sharing one on-disk store and one persistent compilation
cache, so both halves of the compile story are measured:

  cold stage (fresh cache):
    synth   - structured ratings (latent-factor signal + noise, so the
              RMSE gates below measure real generalization, not luck)
    ingest  - 20M events into the native eventlog via the storage write
              API (columnar bulk path = PEvents.write role). The live
              row lane — raw API-format JSON array bytes through the
              native encoder (insert_json_batch, the POST
              /batch/events.json path) — is sampled separately with a
              hard gate: row_lane_events_per_sec >= 50k or the
              headline is zeroed. The FSYNC=1 (SYNC_WAL durability)
              lane and the legacy Event-object fallback are reported
              alongside.
    read    - RecoDataSource.read_training: native columnar scan
    prepare - RecoPreparator: BiMap id indexing
    bin     - ragged->segmented static blocks + device placement
    compile - XLA compile + one throwaway run (cache MISS: the full
              compile tax, persisted to the cache for the warm stage)
    train   - the timed region: pure device ALS alternations, synced by
              a scalar readback
    rmse    - quality gates on a 5% held-out split: beat the
              global-mean predictor by >=15% AND (at default knobs)
              land inside the absolute band for this fixed generator —
              a silent half-regression in solve quality zeroes the
              headline, not just total breakage
    serve   - the trained model is persisted through the models repo,
              deployed via the REAL EngineServer (prepare_deploy +
              warm-up), and driven over HTTP POST /queries.json:
              sequential p50/p99 + concurrent throughput, then a
              SATURATING stage: 32 keep-alive connections, p50/p99/qps
              with zero errors tolerated and the MicroBatcher's
              dispatch-size histogram recorded (batches > 1 must form).
              Gates: sequential p50 < 10 ms (BASELINE.json north-star)
              AND 32-conn p99 < 25 ms with real batching, or the
              headline is zeroed.

  warm stage (fresh process, same cache): read -> prepare -> bin ->
    compile -> train again. Compile becomes a disk-cache HIT; this is
    what every repeat train / deploy warm-up / /reload pays in
    production.

  twotower stage (fresh process): the stretch neural model at catalog
    scale (1M users/items, dim 128, batch 8192) — steady-state step
    time, a loss-learning gate, and a MEASURED MFU: analytic matmul
    FLOPs over xplane-traced device time vs the public bf16 peak
    (VERDICT r4 item 5). A failed loss gate zeroes the headline.

  retrieval stage (fresh process): candidate generation over the
    trained item factors (predictionio_tpu/index) — brute force vs the
    exact index vs an IVF nprobe sweep, queries/s at MEASURED recall
    vs brute force; ``key.retrieval_qps_recall95`` is the fastest arm
    clearing recall >= 0.95 and ``key.index_build_sec`` its build
    cost (detail.retrieval carries the full sweep).

  prof stage (host-only, runs early): the continuous profiler's cost
    and the first serve-path interpreter breakdown — an in-process
    event server under threaded HTTP load with the always-on sampler
    retained; ``key.prof_overhead_pct`` is the gated number
    (lower-better) and detail.prof_serve_breakdown the
    parse/json/socket/dispatch shares.

  stream stage: see stage_stream (runs LAST — it appends events).

Roofline: analytic FLOP/byte counts from the trainer's actual padded
device shapes (ALSTrainer.work_model — documented under-estimate of
bytes) against TPU v5e public peaks, recorded so the headline number is
grounded in what the chip can do: the train region is expected near the
HBM roof (gather-bound), which is also why the fused Pallas gather
kernel lost to XLA and was removed (ops/als.py measurement note).

Headline metric: rating-updates/sec/chip = n_train_ratings * iterations
/ train_sec (cold stage). ``vs_baseline`` divides by an ASSUMED PROXY
of 1e6 ratings*iters/sec for a Spark-MLlib-ALS CPU node — the reference
publishes no benchmark numbers at all (BASELINE.json "published": {});
the proxy is our own stated assumption, recorded in the detail file,
and the >=5x north-star (BASELINE.md) reads as vs_baseline >= 5.
If ANY gate fails (relative RMSE, absolute RMSE band, serving p50,
32-conn p99 + batching, row-lane >= 50k ev/s), value is reported as
0.0 with the gate flags telling which.

Scale knobs via env: PIO_BENCH_USERS/ITEMS/RATINGS/RANK/ITERS (the
absolute RMSE band only applies at the default knobs).

Telemetry (obs/): the measurements this script reports map onto the
framework's metric names, so a dashboard and a bench run agree on
vocabulary — serving latency is `pio_serving_request_seconds{engine=}`
(the engine server records it for every driven query), ingest and
device-transfer byte counts are `pio_transfer_bytes_total{direction=}`,
train-stage wall times are `pio_train_seconds{engine=}` /
`pio_train_step_seconds`, and the cold-vs-warm compile story is
`pio_jax_compile_cache_total{result=}` + `pio_jax_compile_seconds{phase=}`.
All are live in-process during a run (`bin/pio metrics` dumps them; the
serve stage's server also exposes `GET /metrics` over HTTP).
"""

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

import numpy as np

# the chip peaks live in ONE place (obs/perfacct.py — the live
# pio_train_mfu gauge divides by the same numbers, so a bench capture
# and a production dashboard can never disagree on the denominator);
# perfacct imports no jax at module level, so the orchestrating parent
# stays chip-free
from predictionio_tpu.obs.perfacct import DEVICE_PEAKS  # noqa: E402

V5E_PEAK_BF16_FLOPS = DEVICE_PEAKS["TPU v5 lite"].bf16_flops
V5E_PEAK_HBM_BYTES = DEVICE_PEAKS["TPU v5 lite"].hbm_bytes_per_sec

DEFAULT_KNOBS = (138_493, 26_744, 20_000_000, 64, 5)  # ML-20M + rank/iters
# absolute held-out RMSE band for the DEFAULT synthetic generator at the
# default knobs (measured 0.427 across rounds; the band catches silent
# solve-quality regressions that still beat the trivial 15% gate)
RMSE_BAND = (0.38, 0.48)


def knobs():
    return (
        int(os.environ.get("PIO_BENCH_USERS", DEFAULT_KNOBS[0])),
        int(os.environ.get("PIO_BENCH_ITEMS", DEFAULT_KNOBS[1])),
        int(os.environ.get("PIO_BENCH_RATINGS", DEFAULT_KNOBS[2])),
        int(os.environ.get("PIO_BENCH_RANK", DEFAULT_KNOBS[3])),
        int(os.environ.get("PIO_BENCH_ITERS", DEFAULT_KNOBS[4])),
    )


def synthesize(n_users, n_items, n_ratings, rng):
    """Ratings with planted rank-8 structure: clip(3 + 1.2z + noise)."""
    uu = rng.integers(0, n_users, size=n_ratings, dtype=np.int64)
    item_pop = rng.zipf(1.2, size=n_ratings) % n_items  # Zipf popularity
    ii = item_pop.astype(np.int64)
    U = rng.normal(size=(n_users, 8)).astype(np.float32)
    V = rng.normal(size=(n_items, 8)).astype(np.float32)
    z = np.einsum("nk,nk->n", U[uu], V[ii]) / np.sqrt(8.0)
    raw = 3.0 + 1.2 * z + rng.normal(0, 0.35, size=n_ratings).astype(np.float32)
    vals = np.clip(np.round(raw * 2.0) / 2.0, 0.5, 5.0).astype(np.float64)
    return uu, ii, vals


def _storage(base_dir):
    from predictionio_tpu.data.storage import Storage, set_storage

    st = Storage.from_env({
        "PIO_STORAGE_SOURCES_EL_TYPE": "eventlog",
        "PIO_STORAGE_SOURCES_EL_PATH": base_dir,
        **{f"PIO_STORAGE_REPOSITORIES_{r}_{k}": v
           for r in ("METADATA", "EVENTDATA", "MODELDATA")
           for k, v in (("NAME", r.lower()), ("SOURCE", "EL"))},
    })
    set_storage(st)
    return st


def _bench_cfg():
    from predictionio_tpu.ops.als import ALSConfig

    _, _, _, rank, iterations = knobs()
    return ALSConfig(rank=rank, iterations=iterations, reg=0.05,
                     block_size=4096)


#: bench derivation tag for the binned-layout cache: the 5% holdout
#: split below reshapes the COO, so the key must differ from the
#: template's full-data key
_HOLD_TAG = "|hold5pct"

#: generous bound on the transfer-watcher join: the worst observed
#: driver weather moved ~220 MB at ~2 MB/s (~110 s); 1800 s only fires
#: on a genuine wire hang, which must become a diagnosable error rather
#: than a silently wedged bench process
TRANSFER_JOIN_TIMEOUT_SEC = 1800.0


def _transfer_and_compile(detail, trainer, iterations, n_read):
    """Shared tail of both stages: transfer and compile OVERLAPPED
    (VERDICT r4 item 3 — warm cost should be ~max(transfer, bin+
    compile), not their sum). Device puts are async and started back in
    the constructor; here the host's ahead-of-time XLA compile runs
    WHILE the bytes are still crossing the wire (compilation needs
    only shapes) and a watcher thread timestamps wire completion.
    Honest attribution survives the overlap: transfer_sec is measured
    from the FIRST put dispatch (trainer.put_start) to wire
    completion, so bytes/MB-s still read as bandwidth and wire
    VARIANCE never masquerades as a pipeline regression (VERDICT r3
    weak #2)."""
    import threading

    t_enter = time.perf_counter()
    wire = {}
    comp = {}

    def watch():
        try:
            wire["dones"] = trainer.wait_device_timed()
        except Exception as e:  # noqa: BLE001 — surfaced after join
            wire["error"] = e

    def compile_run():
        # on its own thread, so the deadline below covers BOTH sides
        # of the overlap: a wedged compile must not hang the main
        # thread before any join-with-timeout runs (r6 advisor finding)
        try:
            trainer.compile()
        except Exception as e:  # noqa: BLE001 — surfaced after join
            comp["error"] = e

    th = threading.Thread(target=watch, daemon=True)
    tc = threading.Thread(target=compile_run, daemon=True)
    th.start()
    tc.start()   # host compile overlaps the transfer
    deadline = t_enter + TRANSFER_JOIN_TIMEOUT_SEC
    for t in (th, tc):
        t.join(timeout=max(0.0, deadline - time.perf_counter()))
    if th.is_alive() or tc.is_alive():
        pending = [side for side, t in (("wire (async puts never "
                                         "completed)", th),
                                        ("compile (ahead of time, from "
                                         "shapes)", tc))
                   if t.is_alive()]
        # a side that DIED with an error is often the root cause of the
        # other side's hang: surface it in the same message
        died = "; ".join(
            f"{side} already failed: {d['error']!r}"
            for side, d in (("wire", wire), ("compile", comp))
            if "error" in d)
        raise RuntimeError(
            "transfer/compile overlap still pending after "
            f"{TRANSFER_JOIN_TIMEOUT_SEC:.0f}s — side(s): "
            + "; ".join(pending) + (f" [{died}]" if died else ""))
    if "error" in comp:
        raise RuntimeError("host compile failed") from comp["error"]
    if "error" in wire:
        raise RuntimeError("device transfer failed") from wire["error"]
    overlap_wall = time.perf_counter() - t_enter
    transfer_sec = wire["dones"][-1] - trainer.put_start
    detail["transfer_sec"] = round(transfer_sec, 2)
    detail["transfer_bytes"] = int(trainer.transfer_bytes)
    detail["transfer_mb_per_sec"] = round(
        trainer.transfer_bytes / max(transfer_sec, 1e-9) / 1e6, 1)
    # pure-wire bandwidth: the LAST side's dispatch-done -> completion
    # span contains no host work (binning/compile done dispatching), so
    # a binning regression can never masquerade as a bandwidth drop
    tail_t0, tail_bytes = trainer._put_log[-1]
    tail_sec = max(wire["dones"][-1] - tail_t0, 1e-9)
    detail["transfer_tail_mb_per_sec"] = round(tail_bytes / tail_sec / 1e6, 1)
    detail["compile_host_sec"] = round(trainer.compile_host_sec, 2)
    detail["compile_sec"] = detail["compile_host_sec"]
    detail["overlap_note"] = (
        "transfer/compile run CONCURRENTLY (r5): transfer_sec is the "
        "wall window from first put dispatch (overlaps binning + host "
        "compile) — transfer_tail_mb_per_sec is the pure-wire "
        "bandwidth signal; the stage's wall cost is bin_compile_sec")
    # continuity with BENCH_r01/r02 (one one-time-costs number): now
    # bin + the OVERLAPPED wall, which is the point of the pipeline
    detail["bin_compile_sec"] = round(detail["bin_sec"] + overlap_wall, 2)
    t0 = time.perf_counter()
    trainer.step_n(iterations)
    train_sec = time.perf_counter() - t0
    detail["train_sec"] = round(train_sec, 2)
    detail["events_to_model_sec"] = round(
        detail["read_sec"] + detail["prepare_sec"]
        + detail["bin_compile_sec"] + train_sec, 2
    )
    detail["events_to_model_events_per_sec"] = round(
        n_read / detail["events_to_model_sec"], 1
    )
    return train_sec


def _read_prepare_bin_train(detail, n_expected):
    """The shared events->model path (both stages): returns everything
    the caller needs for quality gates / serving — (trainer, pd, ho,
    train_stats, cfg, train_sec) where train_stats = {"n_train",
    "train_mean"} (the COO itself no longer materializes on the
    zero-copy lane).

    Cold lane (PIO_BENCH_BINNED=0 restores the legacy path): the
    fused native scan+bin call (store.bin_columnar) replaces
    read_training -> prepare -> ALSTrainer binning — one pass off the
    mmap'd log straight into the device-ready compressed layout, with
    the 5%% holdout split applied natively. read_sec is the native
    scan share, bin_sec the resolve+plan+fill share plus the (async)
    put dispatch."""
    from predictionio_tpu.data.bimap import BiMap
    from predictionio_tpu.ops import bincache
    from predictionio_tpu.ops.als import (ALSTrainer, als_row_cost_slots,
                                          layout_cache_key,
                                          side_layout_from_binned)
    from predictionio_tpu.parallel.mesh import MeshContext
    from predictionio_tpu.templates.recommendation import (
        RecoDataSource,
        RecoDataSourceParams,
        RecoPreparator,
    )

    _, _, _, rank, iterations = knobs()
    ctx = MeshContext()
    # binned=False: the bench drives the two lanes EXPLICITLY (the
    # engine-path plumbing is exercised by tier-1; here each stage is
    # timed by hand), so the fallback read must stay columnar
    ds = RecoDataSource(RecoDataSourceParams(app_name="bench",
                                             binned=False))
    cfg = _bench_cfg()
    binned_lane = (os.environ.get("PIO_BENCH_BINNED", "1") != "0"
                   and ds._binned_supported())
    detail["zero_copy_lane"] = bool(binned_lane)
    if binned_lane:
        from predictionio_tpu.data import store as dstore
        from predictionio_tpu.models.als import PreparedRatings

        fp = ds.data_fingerprint()
        t0 = time.perf_counter()
        binned = dstore.bin_columnar(
            "bench", value_property="rating", overrides={"buy": 4.0},
            entity_type="user", event_names=["rate", "buy"],
            target_entity_type="item",
            skip_mod=20, skip_rem=0,            # the 5% holdout split
            seg_len=cfg.seg_len, block_size=cfg.block_size,
            row_cost_slots=als_row_cost_slots(cfg.rank))
        t1 = time.perf_counter()
        n_hold = 0 if binned.holdout is None else len(binned.holdout[0])
        assert binned.n_rows + n_hold == n_expected, (
            binned.n_rows, n_hold, n_expected)
        detail["read_sec"] = round(binned.scan_sec, 2)
        detail["prepare_sec"] = 0.0   # dict-encode fused into the scan
        user_side = side_layout_from_binned(binned.user_side)
        item_side = side_layout_from_binned(binned.item_side)
        trainer = ALSTrainer.from_sides(
            user_side, item_side, len(binned.entity_vocab),
            len(binned.target_vocab), binned.n_rows, cfg)
        # everything that is not the scan is the bin stage (native
        # resolve+plan+fill + vocab decode + async put dispatch)
        detail["bin_sec"] = round(
            (t1 - t0 - binned.scan_sec)
            + (time.perf_counter() - t1), 2)
        detail["bin_cache_hit"] = False
        if fp is not None:
            # persist under the SAME key the warm stage loads
            arrays = {**user_side.to_arrays("u_"),
                      **item_side.to_arrays("i_")}
            bincache.save(
                layout_cache_key(fp + _HOLD_TAG, cfg, 1), arrays, {
                    "n_users": len(binned.entity_vocab),
                    "n_items": len(binned.target_vocab),
                    "n_shards": 1, "total_entries": binned.n_rows,
                    **user_side.meta("u_"), **item_side.meta("i_"),
                })
        pd = PreparedRatings(
            user_ids=BiMap.from_vocab(binned.entity_vocab),
            item_ids=BiMap.from_vocab(binned.target_vocab),
            fingerprint=fp)
        ho = binned.holdout
        train_stats = {
            "n_train": binned.n_rows,
            "train_mean": (binned.user_side.kept_value_sum
                           / max(1, binned.user_side.kept_entries)),
        }
        train_sec = _transfer_and_compile(detail, trainer, iterations,
                                          n_expected)
        return trainer, pd, ho, train_stats, cfg, train_sec

    t0 = time.perf_counter()
    td = ds.read_training(ctx)
    read_sec = time.perf_counter() - t0
    detail["read_sec"] = round(read_sec, 2)
    n_read = len(td.columns.ratings)
    assert n_read == n_expected, (n_read, n_expected)

    t0 = time.perf_counter()
    pd = RecoPreparator(None).prepare(ctx, td)
    detail["prepare_sec"] = round(time.perf_counter() - t0, 2)

    hold = np.arange(n_read) % 20 == 0   # 5% held out
    tr_u, tr_i, tr_r = pd.user_idx[~hold], pd.item_idx[~hold], pd.ratings[~hold]
    ho = (pd.user_idx[hold], pd.item_idx[hold], pd.ratings[hold])

    cache_key = (pd.fingerprint + _HOLD_TAG) if pd.fingerprint else None
    t0 = time.perf_counter()
    trainer = ALSTrainer((tr_u, tr_i, tr_r), len(pd.user_ids),
                         len(pd.item_ids), cfg, cache_key=cache_key)
    detail["bin_sec"] = round(time.perf_counter() - t0, 2)
    detail["bin_cache_hit"] = bool(trainer.cache_hit)
    train_stats = {"n_train": len(tr_r), "train_mean": float(tr_r.mean())}
    train_sec = _transfer_and_compile(detail, trainer, iterations, n_read)
    return trainer, pd, ho, train_stats, cfg, train_sec


def _parse_train_profile(profile_dir):
    """Parse a profiled run's xplane trace into MEASURED occupancy
    numbers (VERDICT r3 item 4): per-HLO-category device time, XLA
    cost-model flops, and bytes split by memory space. The decoding now
    lives in the framework itself (obs/profiler.py — shared with
    workflow/train.py's post-train breakdown and `pio profile`
    artifacts); this stage keeps the subprocess boundary (tensorflow's
    proto stack must not share the bench process) and prints ONE JSON
    line."""
    from predictionio_tpu.obs.profiler import parse_xplane

    print(json.dumps(parse_xplane(profile_dir)))


def _step_device_breakdown(trace, steps):
    """detail.* per-step device-time breakdown from a parsed trace that
    covered ``steps`` steps — so future BENCH_r*.json carry where each
    step's device time went, not just its total. Delegates to the one
    shared implementation (obs/profiler.per_step), so bench captures
    and workflow/train.py logs can never disagree on the same trace."""
    from predictionio_tpu.obs.profiler import per_step

    return per_step(trace, steps)


def _roofline(trainer, train_sec, iterations):
    wm = trainer.work_model()
    achieved_flops = wm["flops_per_iter"] * iterations / train_sec
    achieved_bytes = wm["hbm_bytes_per_iter"] * iterations / train_sec
    return {
        "model": ("analytic counts from actual padded device shapes "
                  "(ALSTrainer.work_model); bytes are a documented "
                  "UNDER-estimate, so hbm fraction is a lower bound"),
        "flops_per_iter": wm["flops_per_iter"],
        "hbm_bytes_per_iter": wm["hbm_bytes_per_iter"],
        "achieved_tflops": round(achieved_flops / 1e12, 2),
        "achieved_hbm_gb_per_sec": round(achieved_bytes / 1e9, 1),
        "peak_bf16_tflops": V5E_PEAK_BF16_FLOPS / 1e12,
        "peak_hbm_gb_per_sec": V5E_PEAK_HBM_BYTES / 1e9,
        "mxu_fraction": round(achieved_flops / V5E_PEAK_BF16_FLOPS, 3),
        "hbm_fraction": round(achieved_bytes / V5E_PEAK_HBM_BYTES, 3),
    }


def _pct(sorted_vals, q):
    """Percentile by index over an already-sorted sample (shared by the
    serve and fleet stages — their quantile arithmetic must agree)."""
    return sorted_vals[min(len(sorted_vals) - 1,
                           int(len(sorted_vals) * q))]


def _run_loadgen(port, users_file, threads, per_thread, on_warmup=None):
    """One out-of-process loadgen run against ``port`` (the separate
    process keeps the clients' CPU off the server's GIL/tail): returns
    the parsed result dict, asserting a clean exit and zero errors.
    Shared by the serve and fleet sweeps — the invocation protocol and
    output parsing must not drift between them.

    ``on_warmup`` runs in THIS process at the loadgen's WARMUP_DONE
    marker — the instant every connection's warm-up requests have
    finished and the timed region begins. The fleet sweep snapshots
    per-replica request counters there to exclude warm-up traffic from
    server-side percentiles exactly (warm-ups strictly precede the
    marker; any timed request racing the snapshot only shrinks the
    measured window, it can never let a warm-up in)."""
    argv = [sys.executable, os.path.abspath(__file__),
            "--stage", "loadgen",
            "--base", json.dumps({
                "port": port, "users_file": users_file,
                "threads": threads, "per_thread": per_thread})]
    if on_warmup is None:
        proc = subprocess.run(argv, capture_output=True, text=True,
                              timeout=600)
        returncode, stdout, stderr = (proc.returncode, proc.stdout,
                                      proc.stderr)
    else:
        proc = subprocess.Popen(argv, stdout=subprocess.PIPE,
                                stderr=subprocess.PIPE, text=True)
        head = []
        # bounded by the loadgen's own internal deadlines (barrier
        # abort at 120s, worker joins at 540s): it always reaches EOF
        for line in proc.stdout:
            head.append(line)
            if line.strip() == "WARMUP_DONE":
                on_warmup()
                break
        rest, stderr = proc.communicate(timeout=600)
        returncode, stdout = proc.returncode, "".join(head) + rest
    lines = [l for l in stdout.splitlines() if l.startswith("{")]
    assert returncode == 0 and lines, (
        returncode, stdout[-500:], stderr[-500:])
    load = json.loads(lines[-1])
    assert load["errors"] == 0, load
    return load


def _serve_stage(storage, factors, pd, cfg, detail):
    """Persist the trained model through the models repo, deploy it via
    the REAL EngineServer (prepare_deploy + warm-up), and measure the
    live HTTP route (ref: CreateServer.scala:552-559 serving path)."""
    import datetime as dt
    import http.client
    import pickle
    import threading
    import uuid

    from predictionio_tpu.core.params import EngineParams
    from predictionio_tpu.data.metadata import EngineInstance, Model
    from predictionio_tpu.models.als import ALSModel, ALSParams
    from predictionio_tpu.serving.engine_server import EngineServer
    from predictionio_tpu.templates.recommendation import (
        RecoDataSourceParams,
        recommendation_engine,
    )

    engine = recommendation_engine()
    ep = EngineParams(
        data_source_params=("", RecoDataSourceParams(app_name="bench")),
        preparator_params=("", None),
        algorithm_params_list=[("als", ALSParams(
            rank=cfg.rank, num_iterations=cfg.iterations, lambda_=cfg.reg))],
        serving_params=("", None),
    )
    ep_json = ep.to_json_dict()
    now = dt.datetime.now(tz=dt.timezone.utc)
    instance = EngineInstance(
        id=uuid.uuid4().hex, status="COMPLETED", start_time=now, end_time=now,
        engine_id="bench_reco", engine_version="0", engine_variant="default",
        engine_factory="bench", batch="bench",
        data_source_params=json.dumps(ep_json["dataSourceParams"]),
        preparator_params=json.dumps(ep_json["preparatorParams"]),
        algorithms_params=json.dumps(ep_json["algorithmParamsList"]),
        serving_params=json.dumps(ep_json["servingParams"]),
    )
    storage.engine_instances().insert(instance)
    model = ALSModel(factors, pd.user_ids, pd.item_ids)
    storage.models().insert(Model(id=instance.id, models=pickle.dumps([model])))

    server = EngineServer(
        engine, "bench_reco", host="127.0.0.1", port=0, storage=storage,
    ).start()
    try:
        rng = np.random.default_rng(7)
        inv = pd.user_ids.inverse()
        users = [inv[int(u)]
                 for u in rng.integers(0, len(pd.user_ids), size=512)]

        import socket

        def connect():
            c = http.client.HTTPConnection("127.0.0.1", server.port,
                                           timeout=60)
            c.connect()
            # what every production HTTP client (curl, urllib3) does;
            # stdlib http.client leaves Nagle on
            c.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            return c

        def one(conn, user):
            body = json.dumps({"user": user, "num": 10})
            conn.request("POST", "/queries.json", body=body,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            data = resp.read()
            assert resp.status == 200 and b"itemScores" in data, data[:200]

        conn = connect()
        for u in users[:16]:            # settle connection + code paths
            one(conn, u)
        laps = []
        for u in users[16:376]:         # 360 timed sequential requests
            t0 = time.perf_counter()
            one(conn, u)
            laps.append(time.perf_counter() - t0)
        conn.close()
        laps.sort()
        p50 = laps[len(laps) // 2]
        p99 = laps[int(len(laps) * 0.99)]

        # concurrent throughput: 4 keep-alive connections
        n_threads, per_thread = 4, 120
        errs = []

        def worker(tid):
            try:
                c = connect()
                for j in range(per_thread):  # graftlint: disable=JT09 — except below hands the error to errs[]; the stage fails loudly on it
                    one(c, users[(tid * per_thread + j) % len(users)])
                c.close()
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        # daemon: a wedged worker must not block interpreter shutdown
        # after the bounded join already failed the stage loudly
        threads = [threading.Thread(target=worker, args=(t,), daemon=True)
                   for t in range(n_threads)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            # bounded join (JT12): a wedged worker must fail the stage
            # loudly, not hang the whole bench run
            t.join(timeout=600)
            assert not t.is_alive(), "serve worker wedged past 600s"
        wall = time.perf_counter() - t0
        assert not errs, errs[0]

        detail["serve_p50_ms"] = round(p50 * 1e3, 2)
        detail["serve_p99_ms"] = round(p99 * 1e3, 2)
        detail["serve_qps"] = round(n_threads * per_thread / wall, 1)
        detail["serve_gate_passed"] = bool(p50 * 1e3 < 10.0)  # BASELINE north-star

        # device-memory ledger snapshot WHILE the deployment is live:
        # the served model (+ its retrieval index) registers weakly, so
        # sampling after server.stop()/GC would read an empty ledger
        # and key.model_hbm_bytes would gate nothing (review finding)
        from predictionio_tpu.obs import memacct

        mem = memacct.report()
        detail["memacct"] = {"models": mem["models"],
                             "basis": mem["basis"]}
        detail["model_hbm_bytes"] = int(mem["total_model_bytes"])

        # saturating CONCURRENCY SWEEP (VERDICT r3 item 6 + r4 item 5):
        # 1/8/32/128 keep-alive connections hammering /queries.json —
        # per-request client latencies, the server-side serving time,
        # and its queue-wait vs model-dispatch SPLIT per point (where
        # does p50 cross 10 ms, and is it queueing or device work?).
        # The load generator runs in a SEPARATE process: in-process
        # client threads would share the server's GIL and bill the
        # clients' own CPU to the server's tail. The 32-conn point
        # keeps the r3/r4 gate (server-side p99 < 25 ms with real
        # batches forming) and runs min-of-2 — the single-vCPU bench
        # host has CPU-steal weather; other points run once.
        import tempfile as _tf

        with _tf.NamedTemporaryFile("w", suffix=".json", delete=False) as uf:
            json.dump(users, uf)
            users_file = uf.name

        def load_point(conns, per_thread):
            count_before = server.stats.request_count
            load = _run_loadgen(server.port, users_file, conns,
                                per_thread)
            n_timed = conns * per_thread
            assert server.stats.request_count - count_before >= n_timed
            srv_lat = sorted(server.stats.recent(n_timed))
            load["srv_p50_ms"] = round(_pct(srv_lat, 0.5) * 1e3, 2)
            load["srv_p99_ms"] = round(_pct(srv_lat, 0.99) * 1e3, 2)
            if server._batcher is not None:
                splits = server._batcher.recent_splits(n_timed)
                waits = sorted(s[0] for s in splits)
                disp = sorted(s[1] for s in splits)
                load["srv_queue_p50_ms"] = round(_pct(waits, 0.5) * 1e3, 2)
                load["srv_queue_p99_ms"] = round(_pct(waits, 0.99) * 1e3, 2)
                load["srv_dispatch_p50_ms"] = round(_pct(disp, 0.5) * 1e3, 2)
                load["srv_dispatch_p99_ms"] = round(_pct(disp, 0.99) * 1e3, 2)
            return load

        sweep = []
        runs = []
        stage_hist = {}
        try:
            for conns in (1, 8, 32, 128):
                per_thread = max(40, 4800 // conns)
                if conns == 32:
                    # gate point: snapshot the histogram around it so
                    # the batching evidence is this point's own
                    hist_before = (
                        server._batcher.histogram()["batchSizeHistogram"]
                        if server._batcher else {})
                    for _ in range(2):           # min-of-2 (gate)
                        runs.append(load_point(conns, per_thread))
                    hist_after = (
                        server._batcher.histogram()["batchSizeHistogram"]
                        if server._batcher else {})
                    stage_hist = {
                        k: hist_after.get(k, 0) - hist_before.get(k, 0)
                        for k in hist_after
                        if hist_after.get(k, 0) - hist_before.get(k, 0) > 0
                    }
                    point = min(runs, key=lambda r: r["srv_p99_ms"])
                else:
                    point = load_point(conns, per_thread)
                sweep.append({"conns": conns, **point})
        finally:
            os.unlink(users_file)
        detail["serve_sweep"] = sweep
        batched = sum(v for k, v in stage_hist.items() if int(k) > 1)
        best = min(runs, key=lambda r: r["srv_p99_ms"])
        # two latency views, both honest: the CLIENT-observed numbers
        # (include the load generator's own CPU on this single-core
        # bench host — client and server share the core, so client
        # parse/format time bills into the observed tail), and the
        # SERVER-side serving time (queue wait + dispatch, measured
        # inside the server) — the server's actual contribution, which
        # is what the gate holds to 25 ms. A multi-core serving host
        # would pull the client view toward the server view.
        detail["serve_qps_32conn"] = best["qps"]
        detail["serve_p50_ms_32conn"] = best["p50_ms"]
        detail["serve_p99_ms_32conn"] = best["p99_ms"]
        detail["serve_p50_ms_32conn_serverside"] = best["srv_p50_ms"]
        detail["serve_p99_ms_32conn_serverside"] = best["srv_p99_ms"]
        detail["serve_32conn_runs"] = runs
        detail["serve_32conn_note"] = (
            "min-of-2 runs (both reported in serve_32conn_runs): the "
            "single-vCPU bench host has CPU-steal weather; "
            "client-observed numbers include the loadgen's own CPU on "
            "the shared core; the gate holds the SERVER-side p99 "
            "(queue wait + dispatch) to 25 ms")
        detail["serve_batch_histogram"] = stage_hist
        detail["serve_32_gate_passed"] = bool(
            best["srv_p99_ms"] < 25.0 and batched > 0)
    finally:
        server.stop()


def stage_stream(base_dir, out_path):
    """Streaming freshness stage (ROADMAP item C / PR 9), run LAST in
    its own process: the stream bench APPENDS events, which advances
    the event-log fingerprint — run before the warm stage, those
    appends would evict the unchanged-data layout-cache fast path the
    warm stage exists to price. Reopening the store here also exercises
    the delta cursor's restart contract on the real bench log."""
    from predictionio_tpu.data.storage import set_storage
    from predictionio_tpu.serving.engine_server import EngineServer
    from predictionio_tpu.templates.recommendation import recommendation_engine

    storage = _storage(base_dir)
    detail = {}
    engine = recommendation_engine()
    server = EngineServer(
        engine, "bench_reco", host="127.0.0.1", port=0, storage=storage,
    ).start()
    try:
        item_ids = server.deployment.models[0].item_ids
        _stream_stage(storage, engine, server, item_ids, detail)
    finally:
        server.stop()
    storage.events().close()
    set_storage(None)
    with open(out_path, "w") as f:
        json.dump(detail, f)


def _stream_stage(storage, engine, server, item_ids, detail):
    """event_to_servable: append→changed-prediction latency through the
    streaming fold-in path (ROADMAP item C / PR 9) against a LIVE
    engine server serving the bench instance — plus fold-in throughput.

    The timed region is the full freshness loop a production stream
    daemon runs per cycle: raw event append into the native log, delta
    tail read (find_columnar_since), ALS fold-in solves, model patch
    over HTTP to the serving process, and a confirming /queries.json
    answer carrying the folded user's predictions. Jit buckets are
    warmed by the preceding folds (steady-state freshness is the
    metric, same stance as the serve warm-up)."""
    import datetime as dt
    import urllib.request

    from predictionio_tpu.data.event import Event
    from predictionio_tpu.workflow.stream import StreamUpdater

    updater = StreamUpdater(engine, "bench_reco", storage=storage,
                            patch_servers=[server])
    app = storage.apps().get_by_name("bench")
    events = storage.events()
    inv_items = item_ids.inverse()
    rng = np.random.default_rng(11)

    def rate(user, item, r):
        return Event(
            event="rate", entity_type="user", entity_id=user,
            target_entity_type="item", target_entity_id=item,
            properties={"rating": float(r)},
            event_time=dt.datetime.now(tz=dt.timezone.utc))

    def query(user):
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}/queries.json",
            data=json.dumps({"user": user, "num": 5}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as resp:
            return json.loads(resp.read())

    # warm the fold path's compiled buckets with one tiny fold
    events.insert_batch([rate("stream_warm_u", inv_items[0], 4.0)], app.id)
    updater.poll_once()

    # fold-in throughput: 1000 events from 100 new users over 8 distinct
    # existing items (bounds the per-item history scans)
    hot_items = [inv_items[int(i)]
                 for i in rng.integers(0, len(item_ids), size=8)]
    batch = [rate(f"stream_tp_u{k % 100}", hot_items[k % 8],
                  float(rng.integers(1, 11)) / 2.0)
             for k in range(1000)]
    events.insert_batch(batch, app.id)
    stats = updater.poll_once()
    assert stats["events"] == 1000 and stats["published"], stats
    detail["foldin_events_per_sec"] = round(
        stats["events"] / max(stats["seconds"], 1e-9), 1)

    # append -> servable changed prediction, measured end to end: the
    # fresh user answers empty before the fold and with scores after
    user = "stream_fresh_u"
    assert query(user)["itemScores"] == []
    t0 = time.perf_counter()
    events.insert_batch([rate(user, inv_items[1], 5.0)], app.id)
    stats = updater.poll_once()
    answer = query(user)
    elapsed_ms = (time.perf_counter() - t0) * 1e3
    assert stats["published"] and answer["itemScores"], (stats, answer)
    detail["event_to_servable_ms"] = round(elapsed_ms, 1)
    detail["stream_fold_stats"] = {
        k: stats[k] for k in ("events", "touched_users", "touched_items",
                              "seconds")}
    detail["event_to_servable_note"] = (
        "append -> delta tail -> ALS fold-in -> HTTP /model/patch -> "
        "confirmed changed /queries.json answer, steady-state (fold jit "
        "warmed); the batch warm path re-ships the world in "
        "warm_events_to_model_sec instead")


def stage_quality(base_dir, out_path):
    """Model-quality observability stage (ROADMAP item D): prices the
    continuous-evaluation plane on the bench's trained instance —

      quality_recall_vs_retrain  the shadow-drift probe's recall after
                                 a real fold cycle (the gate value the
                                 stream daemon exports continuously;
                                 benchcmp: "recall" = higher-better)
      quality_probe_ms           wall cost of one drift probe (the
                                 per-cycle tax of continuous eval)
      replay_mean_overlap        the replay harness end-to-end on
                                 captured live payloads (self-replay:
                                 must stay 1.0)
      replay_ms_per_query        replay throughput tax per query
      canary_verdict_ms          wall cost of rendering one canary
                                 promote/rollback verdict from paired
                                 stats + lane histograms (benchcmp:
                                 "_ms" = lower-better)
    """
    import urllib.request

    from predictionio_tpu.data.storage import set_storage
    from predictionio_tpu.obs import quality
    from predictionio_tpu.serving.engine_server import EngineServer
    from predictionio_tpu.templates.recommendation import recommendation_engine
    from predictionio_tpu.workflow import replay as replay_mod
    from predictionio_tpu.workflow.stream import StreamUpdater

    os.environ["PIO_FLIGHT_PAYLOADS"] = "128"
    storage = _storage(base_dir)
    detail = {}
    engine = recommendation_engine()
    server = EngineServer(
        engine, "bench_reco", host="127.0.0.1", port=0, storage=storage,
    ).start()
    try:
        import datetime as dt

        from predictionio_tpu.data.event import Event

        app = storage.apps().get_by_name("bench")
        item_ids = server.deployment.models[0].item_ids
        inv_items = item_ids.inverse()
        updater = StreamUpdater(engine, "bench_reco", storage=storage,
                                patch_servers=[server])
        # one real fold so the drift probe prices the live lane, not a
        # trivially-identical snapshot
        events = [Event(event="rate", entity_type="user",
                        entity_id=f"q_u{k % 16}",
                        target_entity_type="item",
                        target_entity_id=inv_items[k % 8],
                        properties={"rating": 4.0},
                        event_time=dt.datetime.now(tz=dt.timezone.utc))
                  for k in range(64)]
        storage.events().insert_batch(events, app.id)
        stats = updater.poll_once()
        assert stats["published"], stats
        t0 = time.perf_counter()
        report = updater.probe_quality()
        detail["quality_probe_ms"] = round(
            (time.perf_counter() - t0) * 1e3, 2)
        detail["quality_recall_vs_retrain"] = report["recall_vs_retrain"]
        detail["quality_rmse_drift"] = report["rmse_drift"]

        # replay: capture real payloads through the live HTTP lane,
        # then replay them (self-replay — overlap gates at 1.0)
        rng = np.random.default_rng(17)
        users = [f"q_u{int(u)}" for u in rng.integers(0, 16, size=32)]
        for user in users:
            req = urllib.request.Request(
                f"http://127.0.0.1:{server.port}/queries.json",
                data=json.dumps({"user": user, "num": 5}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=30) as resp:
                resp.read()
        from predictionio_tpu.obs import flight

        payloads = flight.RECORDER.payloads()
        assert payloads, "payload capture recorded nothing"
        target = replay_mod.server_target(server)
        t0 = time.perf_counter()
        rep = replay_mod.replay(payloads, target, target)
        replay_sec = time.perf_counter() - t0
        detail["replay_mean_overlap"] = rep["mean_overlap"]
        detail["replay_ms_per_query"] = round(
            replay_sec / max(1, rep["n"]) * 1e3, 3)

        # canary verdict: realistic paired stats + lane histograms,
        # then the verdict math end to end
        quality.STATE.canary_begin("bench_r1", "base_inst", "cand_inst")
        lat = rng.lognormal(-5.0, 0.4, size=512)
        for v in lat:
            quality.CANARY_SECONDS.labels("baseline").observe(float(v))
            quality.CANARY_SECONDS.labels("canary").observe(float(v * 1.1))
        for _ in range(256):
            quality.STATE.add_paired({"overlap": 0.9, "score_delta": 0.01})
        t0 = time.perf_counter()
        for _ in range(10):
            verdict = quality.STATE.canary_verdict()
        detail["canary_verdict_ms"] = round(
            (time.perf_counter() - t0) / 10 * 1e3, 3)
        detail["canary_verdict_note"] = (
            "verdict render over 256 paired samples + 2x512-observation "
            "lane histograms; verdict=" + verdict["verdict"])
        quality.STATE.canary_end("bench_done", None)
    finally:
        server.stop()
    storage.events().close()
    set_storage(None)
    with open(out_path, "w") as f:
        json.dump(detail, f)


def stage_retrieval(base_dir, out_path):
    """Candidate-generation stage (index subsystem): build the ANN
    indexes over the trained bench model's item factors, then sweep
    brute force vs the exact index (Pallas kernel where the backend
    supports it, XLA fallback otherwise) vs IVF at increasing nprobe —
    queries/s AT measured recall. The headline keys are
    ``retrieval_qps_recall95`` (best backend that clears recall >=
    0.95 vs brute force) and ``index_build_sec`` (that backend's build
    time): an index that answers fast but can't find the right items
    earns nothing."""
    from predictionio_tpu.data.storage import set_storage
    # backends constructed DIRECTLY: the stage sweeps both by design,
    # so the operator's PIO_INDEX_BACKEND (which overrides make_index's
    # argument) must not collapse the sweep onto one arm
    from predictionio_tpu.index.exact import ExactIndex
    from predictionio_tpu.index.ivf import IVFIndex
    from predictionio_tpu.index.recall import brute_force_topk, recall_at_k
    from predictionio_tpu.parallel.mesh import MeshContext
    from predictionio_tpu.templates.recommendation import recommendation_engine
    from predictionio_tpu.workflow.deploy import prepare_deploy

    storage = _storage(base_dir)
    detail = {}
    instance = storage.engine_instances().get_latest_completed(
        "bench_reco", "0", "default")
    deployment = prepare_deploy(recommendation_engine(), instance,
                                MeshContext(), storage)
    model = deployment.models[0]
    vectors = np.asarray(model.item_factors, np.float32)
    n_items = vectors.shape[0]
    rng = np.random.default_rng(23)
    n_q = int(os.environ.get("PIO_BENCH_RETRIEVAL_QUERIES", "256"))
    user_rows = rng.integers(0, len(model.user_ids), size=n_q)
    queries = np.asarray(model.user_factors, np.float32)[user_rows]
    k = 10
    batch = 32
    sweep = {}

    def timed_qps(search):
        """Steady-state queries/s at batch=32 (one warm call first —
        compile/build costs are priced separately)."""
        search(queries[:batch], k)
        t0 = time.perf_counter()
        n_done = 0
        while n_done < n_q or time.perf_counter() - t0 < 0.2:
            b = queries[n_done % n_q:(n_done % n_q) + batch]
            if len(b) == 0:
                b = queries[:batch]
            search(b, k)
            n_done += len(b)
        wall = time.perf_counter() - t0
        return round(n_done / wall, 1)

    # brute force is both the recall truth and the baseline arm
    sweep["brute"] = {
        "qps": timed_qps(lambda q, kk: brute_force_topk(vectors, q, kk)),
        "recall": 1.0,
    }

    t0 = time.perf_counter()
    exact = ExactIndex()
    exact.build(vectors)
    exact_build = time.perf_counter() - t0
    sweep["exact"] = {
        "qps": timed_qps(exact.search),
        "recall": round(recall_at_k(exact, queries[:64], k,
                                    vectors=vectors), 4),
        "build_sec": round(exact_build, 3),
        "kernel": exact.kernel_plan,
    }

    ivf_best = None
    t0 = time.perf_counter()
    ivf = IVFIndex()
    ivf.build(vectors)
    ivf_build = time.perf_counter() - t0
    for nprobe in sorted({1, 4, ivf.nprobe or 1,
                          min(2 * (ivf.nprobe or 1), ivf.stats()["nlist"])}):
        ivf.nprobe = nprobe
        arm = {
            "qps": timed_qps(ivf.search),
            "recall": round(recall_at_k(ivf, queries[:64], k,
                                        vectors=vectors), 4),
            "nprobe": nprobe,
        }
        sweep[f"ivf_nprobe{nprobe}"] = arm
        if arm["recall"] >= 0.95 and (
                ivf_best is None or arm["qps"] > ivf_best["qps"]):
            ivf_best = arm
    sweep["ivf_build_sec"] = round(ivf_build, 3)
    sweep["ivf_config"] = {kk: ivf.stats()[kk]
                           for kk in ("nlist", "quantize", "recall_floor")}

    # the gated headline pair: fastest arm at recall >= 0.95 (brute is
    # always eligible, so the key always lands) + its build cost
    arms = [("brute", sweep["brute"], 0.0),
            ("exact", sweep["exact"], exact_build)]
    if ivf_best is not None:
        arms.append((f"ivf_nprobe{ivf_best['nprobe']}", ivf_best, ivf_build))
    name, best, build = max(
        (a for a in arms if a[1]["recall"] >= 0.95), key=lambda a: a[1]["qps"])
    detail["retrieval"] = {**sweep, "n_items": n_items, "k": k,
                           "batch": batch, "best_backend": name}
    detail["retrieval_qps_recall95"] = best["qps"]
    detail["index_build_sec"] = round(build, 3)
    storage.events().close()
    set_storage(None)
    with open(out_path, "w") as f:
        json.dump(detail, f)


def _fleet_stage(storage, cfg, detail):
    """serve_128conn fleet sweep: the SAME trained instance behind
    1/2/4 threaded engine-server replicas and the health-routed query
    router (serving/fleet.py + serving/router.py), hammered by the
    out-of-process load generator at 128 keep-alive connections —
    qps + client p99 + the merged SERVER-side p99 per replica count.

    Honesty note: on a single-vCPU bench host threaded replicas share
    one core, so scaling here measures the router's overhead + the
    redundancy story, not multi-core speedup — per-process replicas on
    a serving host are where the qps curve moves. The gate metric is
    the 128-conn router-path server-side p99 at the best replica
    count (key.fleet_srv_p99_ms_128conn, lower-better in
    `pio bench-compare`)."""
    import tempfile as _tf

    from predictionio_tpu.serving.engine_server import EngineServer
    from predictionio_tpu.serving.fleet import (FleetSupervisor,
                                                threaded_fleet)
    from predictionio_tpu.serving.router import QueryRouter
    from predictionio_tpu.templates.recommendation import (
        recommendation_engine,
    )

    rng = np.random.default_rng(11)

    # the instance/model _serve_stage published; user ids re-derived
    # from the stored model blob so this stage stands alone
    import pickle as _pickle

    instance = storage.engine_instances().get_latest_completed(
        "bench_reco", "0", "default")
    assert instance is not None, "fleet stage needs the serve stage's instance"
    blob = storage.models().get(instance.id)
    model = _pickle.loads(blob.models)[0]
    inv = model.user_ids.inverse()
    users = [inv[int(u)]
             for u in rng.integers(0, len(model.user_ids), size=512)]
    with _tf.NamedTemporaryFile("w", suffix=".json", delete=False) as uf:
        json.dump(users, uf)
        users_file = uf.name

    # env-tunable for constrained hosts (defaults are the real sweep)
    replica_counts = [int(x) for x in os.environ.get(
        "PIO_BENCH_FLEET_REPLICAS", "1,2,4").split(",") if x.strip()]
    conns = int(os.environ.get("PIO_BENCH_FLEET_CONNS", "128"))
    sweep = []
    try:
        for n_replicas in replica_counts:
            engine = recommendation_engine()

            def factory(name, _engine=engine):
                return EngineServer(_engine, "bench_reco",
                                    host="127.0.0.1", port=0,
                                    storage=storage, chaos_tag=name)

            fleet = FleetSupervisor(
                threaded_fleet(n_replicas, factory),
                probe_interval=0.2).start()
            router = None
            try:
                assert fleet.wait_ready(timeout=120), "fleet not ready"
                router = QueryRouter(fleet, host="127.0.0.1",
                                     port=0).start()
                per_thread = max(20, 4800 // conns)
                warm_counts = {}

                def _snap_warmup(_fleet=fleet, _counts=warm_counts):
                    for r in _fleet.replicas:
                        _counts[r.name] = r.server.stats.request_count

                load = _run_loadgen(router.port, users_file, conns,
                                    per_thread, on_warmup=_snap_warmup)
                # merged server-side serving times across replicas,
                # warm-ups excluded exactly: the per-replica counter
                # snapshot at the loadgen's warm-up barrier bounds each
                # replica's timed-sample window (a warm-up burst of
                # conns simultaneous fresh connections would otherwise
                # outnumber the p99 cohort of the merged samples)
                srv = []
                for r in fleet.replicas:
                    timed = (r.server.stats.request_count
                             - warm_counts.get(r.name, 0))
                    if timed > 0:
                        srv.extend(r.server.stats.recent(timed))
                assert srv, "no post-warm-up server-side samples"
                srv.sort()
                point = {
                    "replicas": n_replicas,
                    "conns": conns,
                    "qps": load["qps"],
                    "p50_ms": load["p50_ms"],
                    "p99_ms": load["p99_ms"],
                    "srv_p50_ms": round(_pct(srv, 0.5) * 1e3, 2),
                    "srv_p99_ms": round(_pct(srv, 0.99) * 1e3, 2),
                }
                sweep.append(point)
                if n_replicas == max(replica_counts):
                    _federation_bench(router,
                                      {"user": users[0], "num": 10},
                                      detail)
            finally:
                if router is not None:
                    router.stop()
                fleet.stop()
    finally:
        os.unlink(users_file)
    detail["fleet_sweep"] = sweep
    if not sweep:  # PIO_BENCH_FLEET_REPLICAS= disables the sweep
        detail["fleet_note"] = "fleet sweep disabled via env"
        return
    best = min(sweep, key=lambda p: p["srv_p99_ms"])
    detail["fleet_best_replicas"] = best["replicas"]
    detail["fleet_qps_128conn"] = best["qps"]
    detail["fleet_p99_ms_128conn"] = best["p99_ms"]
    detail["fleet_srv_p99_ms_128conn"] = best["srv_p99_ms"]
    detail["fleet_note"] = (
        "threaded replicas share the bench host's core(s): the sweep "
        "prices the router hop + redundancy, not multi-core scaling; "
        "server-side percentiles merge all replicas' serving times")


def _federation_bench(router, payload, detail):
    """Price the observability federation plane (obs/collect.py) over
    the live bench fleet: one full member /metrics merge
    (``fleet_scrape_ms`` — the cost of a fleet-wide scrape pass) and
    one cross-process trace stitch (``trace_stitch_ms`` — query the
    router, then assemble the spans into the annotated tree). Both are
    benchcmp-gated lower-better (`_ms` suffix). Best-effort: a failed
    probe query leaves a note, never fails the fleet stage."""
    import urllib.request as _ur

    from predictionio_tpu.obs import collect, trace as trace_mod

    members = collect.default_members(router)
    t0 = time.perf_counter()
    fed = collect.federate_metrics(members)
    detail["fleet_scrape_ms"] = round((time.perf_counter() - t0) * 1e3, 2)
    detail["fleet_members_scraped"] = len(fed["merged_from"])
    req = _ur.Request(
        f"http://127.0.0.1:{router.port}/queries.json",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with _ur.urlopen(req, timeout=30) as resp:
            resp.read()
            trace_id = resp.headers.get(trace_mod.TRACE_HEADER)
    except Exception as e:  # noqa: BLE001 — the stitch number is
        # telemetry about telemetry; never fail the sweep over it
        detail["trace_stitch_note"] = f"stitch probe query failed: {e}"
        return
    if not trace_id:
        return
    # the edge spans seal as the handler threads unwind, AFTER the
    # response bytes: wait for the ring to carry the trace so the
    # stitch timing prices assembly, not an empty fan-out
    deadline = time.perf_counter() + 2.0
    while (not trace_mod.recent_spans(trace_id=trace_id)
           and time.perf_counter() < deadline):
        time.sleep(0.01)
    t0 = time.perf_counter()
    doc = collect.stitch_trace(trace_id, members)
    detail["trace_stitch_ms"] = round((time.perf_counter() - t0) * 1e3, 2)
    detail["trace_stitch_spans"] = doc["span_count"]


def stage_loadgen(config_json):
    """Out-of-process load generator for the saturation stage (its own
    GIL — client CPU must not masquerade as server latency). Drives
    ``threads`` keep-alive connections ``per_thread`` requests each
    against POST /queries.json; prints ONE JSON line with latencies.

    The client is a minimal raw-socket HTTP/1.1 driver, not
    http.client: on a single-core bench host the load generator shares
    the core with the server under test, so every cycle it burns in
    stdlib header parsing is a cycle STOLEN from the server — a light
    client is the closest stand-in for a second machine."""
    import socket
    import threading

    cfg = json.loads(config_json)
    with open(cfg["users_file"]) as f:
        users = json.load(f)
    port = int(cfg["port"])
    n_threads = int(cfg["threads"])
    per_thread = int(cfg["per_thread"])
    errs = []
    lat = [[] for _ in range(n_threads)]
    spans = [None] * n_threads
    barrier = threading.Barrier(n_threads)

    # pre-built request bytes per user: the timed loop only does
    # sendall + header-scan + body read
    def request_bytes(user):
        body = json.dumps({"user": user, "num": 10}).encode()
        return (b"POST /queries.json HTTP/1.1\r\n"
                b"Host: 127.0.0.1\r\n"
                b"Content-Type: application/json\r\n"
                b"Content-Length: " + str(len(body)).encode() +
                b"\r\n\r\n" + body)
    reqs = [request_bytes(u) for u in users]

    def one(sock, rfile, req):
        sock.sendall(req)
        # status line + headers
        status = rfile.readline()
        if not status.startswith(b"HTTP/1.1 200"):
            raise AssertionError(f"bad status {status[:80]!r}")
        length = None
        while True:
            line = rfile.readline()
            if line in (b"\r\n", b"\n", b""):
                break
            if line.lower().startswith(b"content-length:"):
                length = int(line.split(b":", 1)[1])
        if length is None:
            raise AssertionError("no Content-Length (route changed?)")
        data = rfile.read(length)
        if b"itemScores" not in data:
            raise AssertionError(data[:120])

    def worker(tid):
        try:
            sock = socket.create_connection(("127.0.0.1", port), 60)
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            rfile = sock.makefile("rb")
            # per-connection warm-up OUTSIDE the timed region (TCP
            # setup + server thread spawn are connection costs)
            for j in range(3):  # graftlint: disable=JT09 — except below records to errs[] and aborts the barrier; never silent
                one(sock, rfile, reqs[(tid + j) % len(reqs)])
            barrier.wait(timeout=120)  # a stuck peer aborts the barrier
            if tid == 0:
                # warm-up boundary marker: every connection's warm-ups
                # are done once the barrier releases, so the parent can
                # snapshot server-side counters HERE to exclude them
                print("WARMUP_DONE", flush=True)
            t_start = time.perf_counter()
        except Exception as e:  # noqa: BLE001
            errs.append(repr(e))
            barrier.abort()  # fail fast, never hang the stage
            return
        try:
            for j in range(per_thread):  # graftlint: disable=JT09 — except below records to errs[]; the stage reports them in its output
                t0 = time.perf_counter()
                one(sock, rfile, reqs[(tid * per_thread + j) % len(reqs)])
                lat[tid].append(time.perf_counter() - t0)
            spans[tid] = (t_start, time.perf_counter())
            rfile.close()
            sock.close()
        except Exception as e:  # noqa: BLE001
            errs.append(repr(e))

    # daemon: after a timed-out join prints the error JSON, the process
    # must still be able to exit (interpreter shutdown joins non-daemon
    # threads, which would hang until the parent's subprocess timeout
    # killed us and discarded the diagnostics)
    threads = [threading.Thread(target=worker, args=(t,), daemon=True)
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        # bounded join (JT12): the orchestrator's 600s subprocess
        # timeout would otherwise be the only thing ending a hung run
        t.join(timeout=540)
        if t.is_alive():
            errs.append("loadgen worker wedged past 540s")
            break
    if errs:
        print(json.dumps({"errors": len(errs), "first": errs[0]}))
        return
    wall = max(s[1] for s in spans) - min(s[0] for s in spans)
    flat = sorted(x for ls in lat for x in ls)
    print(json.dumps({
        "errors": 0,
        "qps": round(n_threads * per_thread / wall, 1),
        "p50_ms": round(flat[len(flat) // 2] * 1e3, 2),
        "p99_ms": round(flat[int(len(flat) * 0.99)] * 1e3, 2),
    }))


def stage_cold(base_dir, out_path):
    from predictionio_tpu.data.storage import EventColumns, set_storage
    from predictionio_tpu.ops.als import predict_rmse
    from predictionio_tpu.parallel.compile_cache import enable_persistent_cache

    enable_persistent_cache()
    n_users, n_items, n_ratings, rank, iterations = knobs()
    detail = {"n_users": n_users, "n_items": n_items, "n_ratings": n_ratings,
              "rank": rank, "iterations": iterations}
    rng = np.random.default_rng(0)

    t0 = time.perf_counter()
    uu, ii, vals = synthesize(n_users, n_items, n_ratings, rng)
    cols = EventColumns(
        entity_codes=uu.astype(np.int32),
        target_codes=ii.astype(np.int32),
        name_codes=np.zeros(n_ratings, np.int32),
        values=vals,
        times_us=np.arange(n_ratings, dtype=np.int64) * 1_000_000,
        entity_vocab=[f"u{i}" for i in range(n_users)],
        target_vocab=[f"i{i}" for i in range(n_items)],
        names=["rate"],
    )
    detail["synth_sec"] = round(time.perf_counter() - t0, 2)

    storage = _storage(base_dir)
    app = storage.apps().insert("bench")
    storage.events().init(app.id)

    t0 = time.perf_counter()
    storage.events().insert_columnar(
        cols, app.id, entity_type="user", target_entity_type="item",
        value_property="rating",
    )
    ingest_sec = time.perf_counter() - t0
    detail["ingest_sec"] = round(ingest_sec, 2)
    detail["ingest_events_per_sec"] = round(n_ratings / ingest_sec, 1)

    # row-path write rate, sampled — the lane the event server pays for
    # live traffic. Since r4 that lane is the NATIVE JSON encoder
    # (EventLogEventStore.insert_json_batch, wired into POST
    # /batch/events.json): the raw API-format JSON array bytes go
    # straight to C++ — parse + EventValidation + wire packing + append
    # in one GIL-released call, no per-row Python objects. The timed
    # region is exactly the server's post-HTTP work (auth/stats
    # excluded); building the JSON bytes is the CLIENT's cost and is
    # reported separately. The legacy Event-object path (the DAO
    # fallback every non-native backend still uses) is kept as a
    # secondary metric.
    sample = min(100_000, n_ratings)
    import datetime as dt

    from predictionio_tpu.data.event import Event

    uu_py, ii_py = uu[:sample].tolist(), ii[:sample].tolist()
    vals_py = vals[:sample].tolist()
    epoch = dt.datetime(2026, 1, 1, tzinfo=dt.timezone.utc)
    second = dt.timedelta(seconds=1)
    # the FIRST row append after a bulk columnar ingest absorbs the
    # ingest's amortized one-time costs (the pending index-snapshot
    # flush once kSnapshotInterval bytes accumulated — ~2s after 20M
    # rows; NOT the lazy by_id debt, which fresh-id live appends never
    # pay by design, eventlog.cpp append_packed). Pay and report it
    # separately so the timed sample measures the steady-state row
    # lane. The event name is NOT a training event, so the row stays
    # out of read_training.
    t0 = time.perf_counter()
    storage.events().insert_batch(
        [Event(event="bench-warmup", entity_type="user", entity_id="warmup",
               target_entity_type="item", target_entity_id="w0",
               properties={}, event_time=epoch)],
        app.id,
    )
    detail["post_bulk_append_debt_sec"] = round(time.perf_counter() - t0, 2)

    # client-side JSON build (the SDK's cost, not the server's)
    t0 = time.perf_counter()
    # event name is NOT the training event ("rate"), so the sampled
    # lanes stay out of read_training and the RMSE gates see exactly
    # the synthesized ratings
    raw = json.dumps([
        {"event": "bench-row", "entityType": "user", "entityId": f"u{uu_py[k]}",
         "targetEntityType": "item", "targetEntityId": f"i{ii_py[k]}",
         "properties": {"rating": vals_py[k]},
         "eventTime": f"2026-01-01T{(k // 3600) % 24:02d}:"
                      f"{(k // 60) % 60:02d}:{k % 60:02d}.000Z"}
        for k in range(sample)
    ]).encode()
    t1 = time.perf_counter()
    ids, codes, _, _ = storage.events().insert_json_batch(raw, app.id)
    t2 = time.perf_counter()
    assert all(c == 0 for c in codes) and len(ids) == sample
    detail["json_build_events_per_sec"] = round(sample / (t1 - t0), 1)
    detail["row_lane_events_per_sec"] = round(sample / (t2 - t1), 1)
    detail["row_lane_gate_passed"] = bool(
        detail["row_lane_events_per_sec"] >= 50_000.0)

    # FSYNC=1 lane (the HBase SYNC_WAL durability contract): same
    # batch, group-committed — one fdatasync per call
    from predictionio_tpu.data.backends.eventlog import EventLogEventStore

    fsync_store = EventLogEventStore(
        os.path.join(base_dir, "bench_fsync_lane"), fsync=True)
    fsync_store.init(1)
    t0 = time.perf_counter()
    fsync_store.insert_json_batch(raw, 1)
    t1 = time.perf_counter()
    fsync_store.close()
    detail["row_lane_fsync_events_per_sec"] = round(sample / (t1 - t0), 1)

    # legacy Event-object path (the non-native DAO fallback), two
    # phases: object build + Python-packed append
    t0 = time.perf_counter()
    events = [
        Event(event="bench-row", entity_type="user", entity_id=f"u{uu_py[k]}",
              target_entity_type="item", target_entity_id=f"i{ii_py[k]}",
              properties={"rating": vals_py[k]},
              event_time=epoch + k * second)
        for k in range(sample)
    ]
    t1 = time.perf_counter()
    storage.events().insert_batch(events, app.id)
    t2 = time.perf_counter()
    detail["event_build_events_per_sec"] = round(sample / (t1 - t0), 1)
    detail["insert_batch_events_per_sec"] = round(sample / (t2 - t1), 1)
    detail["python_row_lane_events_per_sec"] = round(sample / (t2 - t0), 1)

    trainer, pd, ho, train_stats, cfg, train_sec = _read_prepare_bin_train(
        detail, n_ratings
    )
    factors = trainer.factors()

    # quality gates (baseline: the global-mean predictor fit on train)
    rmse = predict_rmse(factors, ho)
    base_rmse = float(
        np.sqrt(np.mean((ho[2] - train_stats["train_mean"]) ** 2)))
    detail["rmse_heldout"] = round(rmse, 4)
    detail["rmse_global_mean_baseline"] = round(base_rmse, 4)
    detail["rmse_gate_passed"] = bool(rmse <= 0.85 * base_rmse)
    at_default = knobs() == DEFAULT_KNOBS
    detail["rmse_band"] = list(RMSE_BAND) if at_default else None
    detail["rmse_band_passed"] = (
        bool(RMSE_BAND[0] <= rmse <= RMSE_BAND[1]) if at_default else True
    )

    effective = (trainer.kept_user_entries + trainer.kept_item_entries) / 2
    assert int(effective) == train_stats["n_train"], (
        effective, train_stats["n_train"])
    detail["updates_per_sec"] = round(effective * iterations / train_sec, 1)
    detail["roofline"] = _roofline(trainer, train_sec, iterations)

    # MEASURED roofline (VERDICT r3 item 4): profile ONE alternation
    # under the JAX profiler (the PIO_PROFILE_DIR hook's machinery),
    # parse the xplane trace in a subprocess (per-category device time,
    # XLA cost-model flops + HBM-space bytes), and measure the
    # governing resource empirically — the claim is gather-ISSUE-bound
    # (ops/als.py), so the roof is a pure gather+mask kernel at the
    # real shapes, and the fraction is train slots/s over roof slots/s.
    import jax

    prof_dir = os.environ.get("PIO_PROFILE_DIR",
                              os.path.join(base_dir, "train_profile"))
    t0 = time.perf_counter()
    with jax.profiler.trace(prof_dir):
        trainer.step_n(1)
    profiled_step_sec = time.perf_counter() - t0
    roof = trainer.measure_gather_roof()
    trace = {}
    try:
        proc = subprocess.run(
            [sys.executable, sys.argv[0] if sys.argv[0].endswith(".py")
             else os.path.abspath(__file__),
             "--stage", "parse_profile", "--base", prof_dir],
            capture_output=True, text=True, timeout=600,
        )
        lines = [l for l in proc.stdout.splitlines() if l.startswith("{")]
        trace = json.loads(lines[-1]) if lines else {
            "error": f"parse rc={proc.returncode}: {proc.stderr[-300:]}"}
    except Exception as e:  # noqa: BLE001 — measurement must not fail bench
        trace = {"error": str(e)}
    train_slots_per_sec = roof["slots_per_iteration"] / profiled_step_sec
    governing_fraction = train_slots_per_sec / roof["roof_slots_per_sec"]
    measured = {
        "measured": True,
        "governing": "gather-issue",
        "profiled_step_sec": round(profiled_step_sec, 3),
        "train_slots_per_sec": round(train_slots_per_sec / 1e9, 3),
        "gather_roof_slots_per_sec": round(
            roof["roof_slots_per_sec"] / 1e9, 3),
        "slots_unit": "Gslots/s (one slot = one gathered K-vector row)",
        "governing_fraction": round(governing_fraction, 3),
        "trace": trace,
    }
    if trace.get("hbm_bytes_total"):
        measured["achieved_hbm_gb_per_sec_traced"] = round(
            trace["hbm_bytes_total"] / trace["device_time_sec"] / 1e9, 1)
        measured["hbm_fraction_traced"] = round(
            trace["hbm_bytes_total"] / trace["device_time_sec"]
            / V5E_PEAK_HBM_BYTES, 3)
    # the profiled region was exactly ONE alternation
    breakdown = _step_device_breakdown(trace, 1)
    if breakdown is not None:
        measured["step_device_breakdown"] = breakdown
    detail["roofline"]["measured"] = measured
    # release the trainer's HBM before the serving deployment compiles
    del trainer

    _serve_stage(storage, factors, pd, cfg, detail)
    _fleet_stage(storage, cfg, detail)

    # train high-water (obs/memacct.py): the trainer's peak estimate
    # survives the trainer (a plain dict, not an owner-scoped ledger
    # entry) — the serving-residency half (detail.memacct /
    # key.model_hbm_bytes) was sampled inside _serve_stage while the
    # deployment was live. benchcmp gates both (the _bytes suffix =
    # lower-better: resident growth IS the regression)
    from predictionio_tpu.obs import memacct

    detail.setdefault("memacct", {})["train_peaks"] = (
        memacct.train_peaks())
    als_peak = memacct.train_peaks().get("als")
    if als_peak:
        detail["train_peak_bytes"] = int(als_peak["bytes"])

    # clean close persists the eventlog index snapshot, so the warm
    # stage's open skips the full-log replay (production parity: servers
    # close their stores on shutdown)
    storage.events().close()
    set_storage(None)
    with open(out_path, "w") as f:
        json.dump(detail, f)


def stage_twotower(base_dir, out_path):
    """The MFU stage (VERDICT r4 item 5): train the stretch two-tower
    config (BASELINE.json configs[4]) on the real chip and measure
    achieved matmul-FLOP/s against the chip's public bf16 peak.

    Structured synthetic positives (64 user/item clusters, 80% of a
    user's positives inside their cluster) give the loss a real signal
    to learn, so the loss gate measures optimization, not luck: random
    in-batch softmax sits at ~ln(B); the clustered structure must pull
    well below it. Steady-state step time comes from post-compile
    epochs (one jitted lax.scan dispatch per epoch — host cannot gap
    the device); the MFU numerator is the ANALYTIC matmul FLOPs of the
    step (logits + its two backward products + MLP; matmul only — the
    optimizer's elementwise work deliberately doesn't count), and the
    denominator uses the xplane-traced device time for the same epoch,
    with the trace's own XLA-cost-model count reported alongside as a
    cross-check."""
    import jax

    from predictionio_tpu.ops.twotower import TwoTowerConfig, TwoTowerTrainer
    from predictionio_tpu.parallel.compile_cache import enable_persistent_cache

    enable_persistent_cache()
    at_default = knobs() == DEFAULT_KNOBS
    tt_ids = int(os.environ.get("PIO_BENCH_TT_IDS",
                                1_000_000 if at_default else 50_000))
    tt_pos = int(os.environ.get("PIO_BENCH_TT_POS",
                                4_000_000 if at_default else 200_000))
    tt_dim = int(os.environ.get("PIO_BENCH_TT_DIM",
                                128 if at_default else 32))
    tt_batch = int(os.environ.get("PIO_BENCH_TT_BATCH",
                                  8192 if at_default else 1024))
    epochs = 3
    detail = {"config": {"users": tt_ids, "items": tt_ids, "positives": tt_pos,
                         "dim": tt_dim, "batch": tt_batch, "epochs": epochs}}

    rng = np.random.default_rng(1)
    t0 = time.perf_counter()
    n_clusters = 64
    user_cluster = rng.integers(0, n_clusters, size=tt_ids)
    uu = rng.integers(0, tt_ids, size=tt_pos)
    in_cluster = rng.random(tt_pos) < 0.8
    per_cluster = tt_ids // n_clusters
    ii = np.where(
        in_cluster,
        user_cluster[uu] + n_clusters * rng.integers(0, per_cluster, tt_pos),
        rng.integers(0, tt_ids, size=tt_pos),
    ).astype(np.int64)
    detail["synth_sec"] = round(time.perf_counter() - t0, 2)

    cfg = TwoTowerConfig(dim=tt_dim, batch_size=tt_batch, epochs=epochs,
                         learning_rate=3e-3, seed=11)
    t0 = time.perf_counter()
    trainer = TwoTowerTrainer((uu, ii, None), tt_ids, tt_ids, cfg)
    detail["init_sec"] = round(time.perf_counter() - t0, 2)
    # which loss/update paths produced these numbers (ops/pallas vs
    # XLA): a step-time comparison across rounds is meaningless
    # without it — PIO_TT_FLASH_CE / PIO_TT_EMBED_UPDATE A/B from env
    detail["kernels"] = trainer.kernel_plan
    steps = trainer.steps_per_epoch
    detail["steps_per_epoch"] = steps

    epoch_secs = []
    losses = []
    for e in range(epochs):
        t0 = time.perf_counter()
        losses = trainer.run(epochs=e + 1)
        epoch_secs.append(time.perf_counter() - t0)   # raw; round at report
    detail["epoch_secs"] = [round(t, 2) for t in epoch_secs]  # [0]=compile
    detail["losses"] = [round(l, 3) for l in losses]
    steady = min(epoch_secs[1:]) if len(epoch_secs) > 1 else epoch_secs[0]
    detail["step_ms"] = round(steady / steps * 1e3, 3)
    detail["steps_per_sec"] = round(steps / steady, 1)
    detail["examples_per_sec"] = round(steps * trainer.batch / steady, 1)

    # loss gate: must LEARN (decrease) and, at the full stretch config,
    # land well below the ~ln(B) random-softmax floor
    random_floor = float(np.log(trainer.batch))
    detail["random_loss_floor"] = round(random_floor, 2)
    gate = losses[-1] < losses[0]
    tt_overridden = any(f"PIO_BENCH_TT_{k}" in os.environ
                        for k in ("IDS", "POS", "DIM", "BATCH"))
    if at_default and not tt_overridden:
        # absolute bar only at the exact stretch config it was
        # calibrated on; ANY override keeps the relative-only gate
        gate = gate and losses[-1] < 0.75 * random_floor
    detail["loss_gate_passed"] = bool(gate)

    # measured MFU: trace ONE steady-state epoch, parse the xplane
    prof_dir = os.path.join(base_dir, "tt_profile")
    t0 = time.perf_counter()
    with jax.profiler.trace(prof_dir):
        trainer.run(epochs=epochs + 1)
    profiled_epoch_sec = time.perf_counter() - t0
    trace = {}
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--stage", "parse_profile", "--base", prof_dir],
            capture_output=True, text=True, timeout=600,
        )
        lines = [l for l in proc.stdout.splitlines() if l.startswith("{")]
        trace = json.loads(lines[-1]) if lines else {
            "error": f"parse rc={proc.returncode}: {proc.stderr[-300:]}"}
    except Exception as e:  # noqa: BLE001 — measurement must not fail bench
        trace = {"error": str(e)}
    detail["profiled_epoch_sec"] = round(profiled_epoch_sec, 2)
    detail["trace"] = trace
    # per-step device-time breakdown (the traced epoch ran `steps`
    # steps): lands in detail.twotower.step_device_breakdown
    breakdown = _step_device_breakdown(trace, steps)
    if breakdown is not None:
        detail["step_device_breakdown"] = breakdown
    # matmul_flops_per_step delegates to the ONE shared formula
    # (obs/perfacct.twotower_matmul_flops — the same count the live
    # pio_train_mfu gauge uses), and the peak is the shared imported
    # constant: the driver-captured twotower_mfu and the production
    # gauge cannot drift apart.
    matmul_flops = trainer.matmul_flops_per_step() * steps
    detail["matmul_flops_per_step"] = trainer.matmul_flops_per_step()
    device_sec = trace.get("device_time_sec") or steady
    detail["mfu_basis"] = (
        "analytic matmul FLOPs (logits fwd+bwd + MLP, "
        "obs/perfacct.twotower_matmul_flops) over "
        f"{'TRACED device time' if trace.get('device_time_sec') else 'steady epoch wall'}"
        " vs 197 TFLOP/s public TPU v5e bf16 peak")
    achieved = matmul_flops / device_sec
    detail["achieved_matmul_tflops"] = round(achieved / 1e12, 2)
    detail["mfu"] = round(achieved / V5E_PEAK_BF16_FLOPS, 4)
    if trace.get("flops_total") and trace.get("device_time_sec"):
        detail["xla_costmodel_tflops"] = round(
            trace["flops_total"] / trace["device_time_sec"] / 1e12, 2)
    # the second honest number: utilization DURING the matmul window
    # (the conv-fusion category's own flops over its own device time) —
    # whole-step MFU divides the same matmuls over everything else the
    # step does (CE elementwise, embedding gathers/scatters)
    conv = (trace.get("by_category") or {}).get("convolution fusion")
    if conv and conv.get("time_frac") and trace.get("device_time_sec"):
        conv_sec = conv["time_frac"] * trace["device_time_sec"]
        detail["matmul_window_tflops"] = round(
            conv["flops"] / conv_sec / 1e12, 1)
        detail["matmul_window_fraction_of_peak"] = round(
            conv["flops"] / conv_sec / V5E_PEAK_BF16_FLOPS, 3)
    with open(out_path, "w") as f:
        json.dump(detail, f)


def _chunk_sweep(full_key, cfg):
    """The H2D chunk-size sweep (detail.datapath.chunk_sweep): re-put
    the CACHED layout at several PIO_BIN_CHUNK_MB settings — mmap load
    + chunked device_put, timed put-dispatch -> confirmed-resident.
    After the first point the file is page-cache-warm, so the sweep
    isolates the transfer pipeline itself (chunking/overlap), not disk;
    chunk 0 = double-buffering off (the old single-shot put per array),
    giving the in-round A/B for the pipeline."""
    from predictionio_tpu.ops import bincache
    from predictionio_tpu.ops.als import ALSTrainer, SideLayout

    points = []
    saved_chunk = os.environ.get("PIO_BIN_CHUNK_MB")
    saved_db = os.environ.get("PIO_TRANSFER_DOUBLE_BUFFER")
    try:
        for mb in (16, 64, 256, 0):
            cached = bincache.load(full_key)
            if cached is None:
                break
            arrays, meta = cached
            if mb > 0:
                os.environ["PIO_BIN_CHUNK_MB"] = str(mb)
                os.environ.pop("PIO_TRANSFER_DOUBLE_BUFFER", None)
            else:
                os.environ["PIO_TRANSFER_DOUBLE_BUFFER"] = "0"
            user_side = SideLayout.from_arrays(arrays, "u_", meta)
            item_side = SideLayout.from_arrays(arrays, "i_", meta)
            trainer = ALSTrainer.from_sides(
                user_side, item_side, int(meta["n_users"]),
                int(meta["n_items"]), int(meta["total_entries"]), cfg)
            dones = trainer.wait_device_timed()
            sec = max(dones[-1] - trainer.put_start, 1e-9)
            points.append({
                "chunk_mb": mb,
                "transfer_sec": round(sec, 3),
                "mb_per_sec": round(trainer.transfer_bytes / sec / 1e6, 1),
            })
            del trainer
    finally:
        for k, v in (("PIO_BIN_CHUNK_MB", saved_chunk),
                     ("PIO_TRANSFER_DOUBLE_BUFFER", saved_db)):
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return points


def stage_warm(base_dir, out_path):
    """Fresh process, same store + same compilation + layout caches:
    the repeat events->model path every retrain / deploy / reload pays.

    The retrain-on-unchanged-data fast path (VERDICT r3 item 2): the
    event log's O(1) fingerprint keys the binned-layout cache the cold
    stage populated, so read/prepare/bin are all SKIPPED — no 20M-row
    re-scan, no re-binning. The device transfer IS re-paid: device
    memory does not survive the process, so the compressed layout's
    bytes must cross the wire again (reported with bytes + MB/s so
    wire variance is distinguishable from a pipeline regression)."""
    from predictionio_tpu.data.storage import set_storage
    from predictionio_tpu.ops.als import ALSTrainer, LayoutCacheMiss
    from predictionio_tpu.parallel.compile_cache import enable_persistent_cache
    from predictionio_tpu.templates.recommendation import (
        RecoDataSource,
        RecoDataSourceParams,
    )

    enable_persistent_cache()
    n_users, n_items, n_ratings, _, iterations = knobs()
    _storage(base_dir)
    detail = {}
    fp = RecoDataSource(
        RecoDataSourceParams(app_name="bench")).data_fingerprint()
    trainer = None
    if fp is not None:
        try:
            t0 = time.perf_counter()
            trainer = ALSTrainer(None, None, None, _bench_cfg(),
                                 cache_key=fp + _HOLD_TAG)
            detail["bin_sec"] = round(time.perf_counter() - t0, 2)
            detail["read_sec"] = 0.0    # skipped: layout cache hit on
            detail["prepare_sec"] = 0.0  # the unchanged-data fingerprint
            detail["bin_cache_hit"] = True
            detail["transfer_note"] = (
                "re-paid: device memory does not survive the process; "
                "the compressed layout's bytes cross the wire again")
        except LayoutCacheMiss:
            trainer = None
    if trainer is not None:
        n_read = n_ratings  # what the skipped read would have returned
        _transfer_and_compile(detail, trainer, iterations, n_read)
        if os.environ.get("PIO_BENCH_CHUNK_SWEEP", "1") != "0":
            from predictionio_tpu.ops.als import layout_cache_key

            detail["datapath"] = {
                "chunk_sweep": _chunk_sweep(
                    layout_cache_key(fp + _HOLD_TAG, _bench_cfg(), 1),
                    _bench_cfg()),
                "note": ("warm re-puts of the cached layout per "
                         "PIO_BIN_CHUNK_MB (page-cache-warm after the "
                         "first point); chunk_mb 0 = double-buffered "
                         "pipeline OFF (single-shot put per array)"),
            }
    else:
        detail["bin_cache_hit"] = False
        _read_prepare_bin_train(detail, n_ratings)
    set_storage(None)
    with open(out_path, "w") as f:
        json.dump(detail, f)


def stage_lint(base_dir, out_path):
    """Project-mode graftlint over the installed package: every per-file
    rule plus the whole-program concurrency pass (JT18-JT21), timed end
    to end — parse, cross-module model build, rule evaluation. The wall
    clock is the gated number (key.lint_project_ms, lower-better in
    bench-compare): the same pass runs in tier-1 and bin/lint, so a
    super-linear regression in the analysis taxes every commit. The
    stage also FAILS on any unsuppressed finding — the bench must not
    bless a tree the lint gate rejects."""
    import predictionio_tpu
    from predictionio_tpu.tools.lint import lint_project

    pkg_dir = os.path.dirname(os.path.abspath(predictionio_tpu.__file__))
    t0 = time.perf_counter()
    findings, files = lint_project([pkg_dir])
    elapsed_ms = (time.perf_counter() - t0) * 1e3
    if findings:
        raise RuntimeError(
            f"graftlint --project: {len(findings)} unsuppressed "
            f"finding(s) — the bench refuses a tree the lint gate rejects")
    detail = {
        "lint_project_ms": round(elapsed_ms, 1),
        "lint_project_files": files,
    }
    with open(out_path, "w") as f:
        json.dump(detail, f)


def stage_prof(base_dir, out_path):
    """Continuous-profiler cost + the first measured serve-path
    interpreter breakdown: an in-process EventServer (memory storage —
    no chip, no JAX) under a few seconds of threaded HTTP load, with
    the always-on sampler retained by ``start()``. Exports
    ``key.prof_overhead_pct`` (lower-better in bench-compare: the
    sampler rides EVERY serving process, so its cost taxes every
    request) and the parse/json/socket/dispatch shares of
    handler-thread samples — the host-side answer to "where does a
    request's interpreter time actually go"."""
    import threading
    import urllib.request

    from predictionio_tpu.data.metadata import AccessKey
    from predictionio_tpu.data.storage import Storage
    from predictionio_tpu.obs import contprof
    from predictionio_tpu.serving.event_server import EventServer

    storage = Storage.from_env({
        "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
        **{f"PIO_STORAGE_REPOSITORIES_{r}_{k}": v
           for r in ("METADATA", "EVENTDATA", "MODELDATA")
           for k, v in (("NAME", r.lower()), ("SOURCE", "MEM"))},
    })
    app = storage.apps().insert("bench-prof")
    storage.events().init(app.id)
    access = AccessKey.generate(app.id)
    storage.access_keys().insert(access)

    contprof.PROFILER.reset()
    server = EventServer(storage=storage, host="127.0.0.1", port=0).start()
    try:
        base = f"http://127.0.0.1:{server.port}"
        post_url = f"{base}/events.json?accessKey={access.key}"
        body = json.dumps({"event": "view", "entityType": "user",
                           "entityId": "u1"}).encode()
        errs = []
        duration = float(os.environ.get("PIO_BENCH_PROF_SEC", "3.0"))
        deadline = time.perf_counter() + duration

        def worker():
            try:
                while time.perf_counter() < deadline:  # graftlint: disable=JT09 — except below hands the error to errs[]; the stage fails loudly on it
                    req = urllib.request.Request(
                        post_url, data=body,
                        headers={"Content-Type": "application/json"})
                    with urllib.request.urlopen(req, timeout=10) as r:
                        r.read()
                    with urllib.request.urlopen(f"{base}/healthz",
                                                timeout=10) as r:
                        r.read()
            except Exception as e:  # pragma: no cover - fails the stage
                errs.append(e)

        threads = [threading.Thread(target=worker, daemon=True)
                   for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(duration + 30.0)
        if errs:
            raise RuntimeError(f"prof stage load failed: {errs[0]!r}")
        snap = contprof.snapshot()
    finally:
        server.stop()
    total = snap["total_samples"]
    if not total:
        raise RuntimeError("prof stage: sampler collected zero samples "
                           "under load — the always-on profiler is dead")
    detail = {
        "prof_overhead_pct": round(
            contprof.PROFILER.overhead_ratio() * 100.0, 3),
        "prof_effective_hz": round(snap["effective_hz"], 2),
        "prof_samples": total,
        "prof_serve_breakdown": contprof.serve_path_breakdown(snap),
    }
    with open(out_path, "w") as f:
        json.dump(detail, f)


def stage_sentinel(base_dir, out_path):
    """Ops-journal + regression-sentinel cost: pure host, no chip, no
    storage. Times (a) the journal's fire-and-forget emit path — the
    cost a breaker flip or canary verdict adds to SERVING code
    (``key.journal_append_us``, lower-better; the acceptance bar is
    single-digit microseconds) and (b) one full sentinel change-point
    scan over a saturated timeline set — 360 samples in every series
    slot, the worst case the snapshot cadence ever pays
    (``key.anomaly_scan_ms``, lower-better)."""
    import collections

    from predictionio_tpu.obs import anomaly, journal, timeline

    # -- journal emit cost (ring only: the serving-path configuration;
    # PIO_JOURNAL_PATH adds one queue append, measured separately in
    # the detail)
    journal.JOURNAL.reset()
    os.environ.pop("PIO_JOURNAL_PATH", None)
    n = int(os.environ.get("PIO_BENCH_JOURNAL_EMITS", "20000"))
    for _ in range(200):  # warm the emit path (metrics labels, ring)
        journal.emit("breaker", target="warm", state="closed")
    t0 = time.perf_counter()
    for i in range(n):
        journal.emit("breaker", target="bench", state="open",
                     failures=i)
    ring_us = (time.perf_counter() - t0) / n * 1e6

    sink = os.path.join(base_dir, "journal_bench.jsonl")
    os.environ["PIO_JOURNAL_PATH"] = sink
    try:
        t0 = time.perf_counter()
        for i in range(n):
            journal.emit("breaker", target="bench", state="open",
                         failures=i)
        queued_us = (time.perf_counter() - t0) / n * 1e6
        if not journal.JOURNAL.flush(timeout=30.0):
            raise RuntimeError("journal writer never drained the "
                               "bench batch")
    finally:
        os.environ.pop("PIO_JOURNAL_PATH", None)
    events, corrupt = journal.read_back(sink)
    if corrupt or len(events) < n:
        raise RuntimeError(
            f"journal durability hole: {len(events)}/{n} lines back, "
            f"{corrupt} corrupt")
    journal.JOURNAL.reset()

    # -- sentinel scan cost over a SATURATED timeline: every series
    # slot full (obs/timeline MAX_SERIES x 360 samples)
    saved = timeline.TIMELINE
    bench_tl = timeline.Timeline()
    cap = 360
    series_n = timeline.MAX_SERIES
    base_ts = 1_000_000.0
    interval = 15.0
    try:
        timeline.TIMELINE = bench_tl
        anomaly.SENTINEL.reset()
        for si in range(series_n):
            name = f"serve_p99_ms.bench{si}"
            pts = bench_tl._series.setdefault(
                name, collections.deque(maxlen=cap))
            for k in range(cap):
                # flat series + one step halfway on even series: the
                # scan pays detection AND attribution work
                v = 10.0 + (5.0 if (si % 2 == 0 and k > cap // 2)
                            else 0.0)
                pts.append((base_ts + k * interval, v))
        journal.emit("reload", instance="bench-instance")
        scans = []
        for _ in range(5):
            t0 = time.perf_counter()
            anomaly.SENTINEL.scan(now=base_ts + cap * interval)
            scans.append((time.perf_counter() - t0) * 1e3)
        scan_ms = min(scans)  # best-of: the cost, not the scheduler
    finally:
        timeline.TIMELINE = saved
        anomaly.SENTINEL.reset()
        journal.JOURNAL.reset()

    detail = {
        "journal_append_us": round(ring_us, 3),
        "journal_append_queued_us": round(queued_us, 3),
        "anomaly_scan_ms": round(scan_ms, 3),
        "anomaly_scan_series": series_n,
        "anomaly_scan_samples": series_n * cap,
    }
    with open(out_path, "w") as f:
        json.dump(detail, f)


def stage_dataobs(base_dir, out_path):
    """Data & ingest observability cost (obs/dataobs.py): pure host,
    no chip, no shared store. Prices (a) the worker-side sketch update
    — count-min + space-saving + HLL + quantile work per event through
    the async queue, enqueue-to-drained (``key.dataobs_update_us``,
    lower-better) and (b) the hook's tax on the eventlog insert_batch
    bulk lane: same batch appended with the hook live vs
    PIO_DATAOBS_DISABLE=1, min-of-N walls
    (``key.dataobs_overhead_pct``, lower-better; the acceptance bar is
    <= 3%, gated)."""
    import datetime as dt

    from predictionio_tpu.data.backends.eventlog import EventLogEventStore
    from predictionio_tpu.data.event import Event
    from predictionio_tpu.obs import dataobs

    rng = np.random.default_rng(7)
    n = int(os.environ.get("PIO_BENCH_DATAOBS_EVENTS", "100000"))
    # Zipf ids: the skewed key stream the sketches exist for
    ents = rng.zipf(1.3, size=n) % 200_000
    names = [f"ev{k % 5}".encode() for k in range(n)]
    ids = [f"u{e}".encode() for e in ents]
    lens = rng.integers(80, 400, size=n).astype(np.int64)

    # -- (a) sketch update cost: enqueue + worker apply, measured
    # enqueue-to-drained so the number prices the FULL sketching work,
    # not just the hot-lane deque append
    dataobs.DATAOBS.reset()
    chunk = 2048
    dataobs.DATAOBS.observe_batch(1, names[:chunk], entity_ids=ids[:chunk],
                                  payload_lens=lens[:chunk])  # warm
    dataobs.DATAOBS.flush(timeout=10.0)
    t0 = time.perf_counter()
    for lo in range(0, n, chunk):
        dataobs.DATAOBS.observe_batch(
            1, names[lo:lo + chunk], entity_ids=ids[lo:lo + chunk],
            payload_lens=lens[lo:lo + chunk])
    if not dataobs.DATAOBS.flush(timeout=60.0):
        raise RuntimeError("dataobs worker never drained the bench batch")
    update_us = (time.perf_counter() - t0) / n * 1e6
    rep = dataobs.DATAOBS.report(top_n=1)
    if rep["events_total"] < n:
        raise RuntimeError(
            f"dataobs dropped events: {rep['events_total']}/{n}")

    # -- (b) ingest-lane overhead: what the guarded hook block in
    # eventlog.insert_batch costs per event, over the lane's own
    # per-event wall. An A/B wall diff on a ~0.3s lane run is
    # dominated by scheduler jitter (±10% — far above the 3% bar), so
    # the GATED number is the direct ratio: the hook's measured cost
    # (enabled() + np.diff over the extent offsets + one observe_batch
    # enqueue per batch) / the lane's measured per-event cost. The A/B
    # walls still run and land in the detail as a sanity record.
    sample = min(50_000, n)
    epoch = dt.datetime(2026, 1, 1, tzinfo=dt.timezone.utc)
    second = dt.timedelta(seconds=1)
    events = [
        Event(event=f"ev{k % 5}", entity_type="user",
              entity_id=f"u{ents[k]}", target_entity_type="item",
              target_entity_id=f"i{k % 1000}",
              properties={"rating": float(k % 5)},
              event_time=epoch + k * second)
        for k in range(sample)
    ]
    walls = {"on": [], "off": []}
    try:
        for rep_i in range(3):
            for mode in ("on", "off"):
                if mode == "off":
                    os.environ["PIO_DATAOBS_DISABLE"] = "1"
                else:
                    os.environ.pop("PIO_DATAOBS_DISABLE", None)
                    dataobs.DATAOBS.reset()
                store = EventLogEventStore(
                    os.path.join(base_dir, f"dataobs_lane_{mode}_{rep_i}"))
                store.init(1)
                t0 = time.perf_counter()
                store.insert_batch(events, 1)
                walls[mode].append(time.perf_counter() - t0)
                store.close()
    finally:
        os.environ.pop("PIO_DATAOBS_DISABLE", None)
    on_s, off_s = min(walls["on"]), min(walls["off"])
    lane_us = on_s / sample * 1e6

    # the hook block, exactly as the lane pays it: one enabled() check,
    # one np.diff over the packed-extent offsets, one enqueue carrying
    # the whole batch's field sequences
    dataobs.DATAOBS.reset()
    b_names = names[:sample]
    b_ids = ids[:sample]
    offs = np.concatenate(([0], np.cumsum(lens[:sample]))).astype(np.uint64)
    reps = 20
    t0 = time.perf_counter()
    for _ in range(reps):
        if dataobs.DATAOBS.enabled():
            dataobs.DATAOBS.observe_batch(
                1, b_names, entity_ids=b_ids,
                payload_lens=np.diff(offs.astype(np.int64)))
    hook_us = (time.perf_counter() - t0) / (reps * sample) * 1e6
    if not dataobs.DATAOBS.flush(timeout=60.0):
        raise RuntimeError("dataobs worker never drained the hook batch")
    dataobs.DATAOBS.reset()
    overhead_pct = hook_us / lane_us * 100.0

    detail = {
        "dataobs_update_us": round(update_us, 4),
        "dataobs_hook_us_per_event": round(hook_us, 5),
        "dataobs_overhead_pct": round(overhead_pct, 3),
        "dataobs_lane_on_events_per_sec": round(sample / on_s, 1),
        "dataobs_lane_off_events_per_sec": round(sample / off_s, 1),
        "dataobs_lane_ab_delta_pct": round((on_s - off_s) / off_s * 100.0,
                                           2),
        "dataobs_gate_passed": bool(overhead_pct <= 3.0),
    }
    with open(out_path, "w") as f:
        json.dump(detail, f)


#: hard ceiling for the final stdout line. The driver records only a
#: ~2 KB tail of bench stdout; round 4's single fat line outgrew it and
#: the whole round's headline landed as ``"parsed": null`` in
#: BENCH_r04.json (VERDICT r4 weak #1). The compact line carries the
#: metric, the gate booleans, and the ~dozen key numbers; EVERYTHING
#: else goes to BENCH_DETAIL.json next to this file, committed, and is
#: referenced by path from the line.
MAX_HEADLINE_BYTES = 1536

DETAIL_FILE = "BENCH_DETAIL.json"

#: the one assumed Spark-MLlib-ALS CPU-node throughput proxy —
#: vs_baseline and the detail's baseline_proxy block must agree
BASELINE_PROXY = 1e6


def emit_headline(detail, detail_path=None):
    """Build the compact final-line dict from the merged stage detail,
    write the full detail to ``BENCH_DETAIL.json`` (repo root, beside
    this file), and return the line dict. If the line would exceed
    ``MAX_HEADLINE_BYTES``, optional ``key`` entries are pruned (worst
    first) until it fits — a multi-hour run must ALWAYS end in a
    parseable headline (raising here would reproduce the exact
    BENCH_r04 parsed:null failure this split exists to prevent); the
    pruning is recorded in the detail file."""
    gates = {
        "rmse": bool(detail["rmse_gate_passed"]),
        "rmse_band": bool(detail["rmse_band_passed"]),
        "serve_p50": bool(detail["serve_gate_passed"]),
        "serve_32conn": bool(detail["serve_32_gate_passed"]),
        "row_lane": bool(detail["row_lane_gate_passed"]),
    }
    value = detail["updates_per_sec"] if all(gates.values()) else 0.0
    detail["baseline_proxy"] = {
        "value": BASELINE_PROXY,
        "unit": "ratings*iters/sec",
        "basis": ("ASSUMED Spark-MLlib-ALS CPU-node throughput; the "
                  "reference publishes no numbers (BASELINE.json "
                  "published={}) — this proxy is our own stated "
                  "assumption, not a citation"),
    }
    key = {
        "train_sec": detail.get("train_sec"),
        "events_to_model_sec": detail.get("events_to_model_sec"),
        # the zero-copy data path's own gates: cold host binning and
        # the H2D wire window (benchcmp: _sec suffix = lower-better)
        "bin_sec": detail.get("bin_sec"),
        "transfer_sec": detail.get("transfer_sec"),
        "warm_events_to_model_sec": detail.get("warm", {})
        .get("events_to_model_sec"),
        "warm_transfer_mb_per_sec": detail.get("warm", {})
        .get("transfer_mb_per_sec"),
        "row_lane_events_per_sec": detail.get("row_lane_events_per_sec"),
        "rmse_heldout": detail.get("rmse_heldout"),
        "serve_p50_ms": detail.get("serve_p50_ms"),
        "serve_p99_ms": detail.get("serve_p99_ms"),
        "serve_32_srv_p50_ms": detail.get("serve_p50_ms_32conn_serverside"),
        "serve_32_srv_p99_ms": detail.get("serve_p99_ms_32conn_serverside"),
        "serve_32_qps": detail.get("serve_qps_32conn"),
        # the fleet sweep's 128-conn router-path numbers (best replica
        # count; bench-compare gates the p99 lower-better, qps higher)
        "fleet_qps_128conn": detail.get("fleet_qps_128conn"),
        "fleet_srv_p99_ms_128conn": detail.get("fleet_srv_p99_ms_128conn"),
        # observability federation (obs/collect.py): one full member
        # /metrics merge and one cross-process trace stitch over the
        # bench fleet (benchcmp: _ms suffix = lower-better)
        "fleet_scrape_ms": detail.get("fleet_scrape_ms"),
        "trace_stitch_ms": detail.get("trace_stitch_ms"),
        # streaming freshness (PR 9): append->servable-changed-prediction
        # through the fold-in path (benchcmp: _ms suffix = lower-better)
        # and fold-in throughput (per_sec = higher-better)
        "event_to_servable_ms": detail.get("event_to_servable_ms"),
        "foldin_events_per_sec": detail.get("foldin_events_per_sec"),
        # candidate generation (index subsystem): fastest backend at
        # recall >= 0.95 vs brute force (qps = higher-better in
        # benchcmp) + its build cost (_sec = lower-better)
        "retrieval_qps_recall95": detail.get("retrieval_qps_recall95"),
        "index_build_sec": detail.get("index_build_sec"),
        # model-quality plane (ROADMAP item D): the drift probe's
        # recall-vs-retrain (benchcmp: "recall" = higher-better) and
        # the canary verdict's render cost ("_ms" = lower-better)
        "quality_recall_vs_retrain": detail.get(
            "quality_recall_vs_retrain"),
        "canary_verdict_ms": detail.get("canary_verdict_ms"),
        # device-memory accounting (obs/memacct.py): serving residency
        # of the trained model (+index) and the train high-water mark
        # (benchcmp: _bytes suffix = lower-better — growth is the regression)
        "model_hbm_bytes": detail.get("model_hbm_bytes"),
        "train_peak_bytes": detail.get("train_peak_bytes"),
        # correctness tooling (tools/lint): project-mode graftlint wall
        # clock over the package (benchcmp: _ms suffix = lower-better —
        # the pass runs in tier-1 + bin/lint, so analysis cost taxes
        # every commit)
        "lint_project_ms": detail.get("lint_project_ms"),
        # continuous profiling plane (obs/contprof.py): sampler cost
        # under serve load (benchcmp: "overhead" = lower-better — the
        # sampler rides every serving process)
        "prof_overhead_pct": detail.get("prof_overhead_pct"),
        # ops journal + regression sentinel (obs/journal.py,
        # obs/anomaly.py): the emit cost a breaker flip adds to serving
        # code (benchcmp: _us suffix = lower-better) and one full
        # change-point scan over a saturated 360-sample timeline set
        # (_ms = lower-better)
        "journal_append_us": detail.get("journal_append_us"),
        "anomaly_scan_ms": detail.get("anomaly_scan_ms"),
        # data & ingest observability (obs/dataobs.py): per-event
        # sketch update through the async queue (benchcmp: _us suffix =
        # lower-better) and the hook's tax on the insert_batch bulk
        # lane ("overhead" = lower-better; gated <= 3%)
        "dataobs_update_us": detail.get("dataobs_update_us"),
        "dataobs_overhead_pct": detail.get("dataobs_overhead_pct"),
    }
    if "twotower" in detail:
        tt = detail["twotower"]
        gates["twotower_loss"] = bool(tt.get("loss_gate_passed", False))
        key["twotower_mfu"] = tt.get("mfu")
        key["twotower_step_ms"] = tt.get("step_ms")
        if not gates["twotower_loss"]:
            value = 0.0
    if "dataobs_overhead_pct" in detail:
        gates["dataobs_overhead"] = bool(
            detail.get("dataobs_gate_passed", False))
        if not gates["dataobs_overhead"]:
            value = 0.0
    line = {
        "metric": "als_ml20m_rating_updates_per_sec_per_chip",
        "value": round(value, 1),
        "unit": "ratings*iters/sec",
        "vs_baseline": round(value / BASELINE_PROXY, 2),
        "gates": gates,
        "key": {k: v for k, v in key.items() if v is not None},
        "detail_file": DETAIL_FILE,
    }
    pruned = []
    while (len(json.dumps(line).encode()) > MAX_HEADLINE_BYTES
           and line["key"]):
        pruned.append(line["key"].popitem()[0])  # last = least essential
    if pruned:
        detail["headline_pruned_keys"] = pruned
    if detail_path is None:
        detail_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), DETAIL_FILE)
    try:
        with open(detail_path, "w") as f:
            json.dump(detail, f, indent=1, sort_keys=True)
    except OSError as e:
        # a failed detail write must never cost the headline (the whole
        # point of the split is that the line ALWAYS lands)
        line["detail_file"] = None
        line["detail_error"] = str(e)[:120]
    return line


def orchestrate():
    """Parent: never touches JAX (the chip is exclusive per process);
    runs the two stages as children sharing one store; the compile
    cache stays where parallel/compile_cache.py puts it."""
    base_dir = tempfile.mkdtemp(prefix="pio_bench_")
    env = dict(os.environ)
    env["PIO_BIN_CACHE_DIR"] = os.path.join(base_dir, "bin_cache")
    try:
        stages = {}
        # lint FIRST (pure AST, no store/JAX — fails fast on a dirty
        # tree before the expensive stages spend chip time); stream
        # stays LAST (it appends events — see stage_stream); retrieval
        # only READS the cold stage's trained instance; quality appends
        # a small fold batch, so it runs after warm (whose
        # unchanged-data fast path the appends would evict)
        # prof rides second: pure host HTTP load (no chip), and its
        # overhead number should reflect a quiet machine, before the
        # heavy stages contend for cores
        # sentinel rides beside prof: pure host math (journal ring +
        # change-point scan), cheapest on a quiet machine
        # dataobs likewise: sketch math + a private eventlog store, and
        # its <=3% overhead gate wants an uncontended box
        for stage in ("lint", "prof", "sentinel", "dataobs", "cold",
                      "warm", "twotower", "retrieval", "quality",
                      "stream"):
            out = os.path.join(base_dir, f"{stage}.json")
            # child stdout -> our stderr: the stdout contract is ONE line
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 "--stage", stage, "--base", base_dir, "--out", out],
                env=env, stdout=sys.stderr, stderr=sys.stderr,
            )
            if proc.returncode != 0:
                raise RuntimeError(f"bench {stage} stage failed "
                                   f"(rc {proc.returncode})")
            with open(out) as f:
                stages[stage] = json.load(f)

        detail = stages["cold"]
        detail["warm"] = stages["warm"]
        detail["twotower"] = stages["twotower"]
        # stream/retrieval/quality keys land at top level: emit_headline
        # reads detail["event_to_servable_ms"] /
        # ["retrieval_qps_recall95"] / ["index_build_sec"] /
        # ["foldin_events_per_sec"] / ["quality_recall_vs_retrain"] /
        # ["canary_verdict_ms"]
        detail.update(stages["lint"])
        detail.update(stages["prof"])
        detail.update(stages["sentinel"])
        detail.update(stages["dataobs"])
        detail.update(stages["retrieval"])
        detail.update(stages["quality"])
        detail.update(stages["stream"])
        print(json.dumps(emit_headline(detail)))
    finally:
        shutil.rmtree(base_dir, ignore_errors=True)


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--stage",
                        choices=["lint", "prof", "sentinel", "dataobs",
                                 "cold", "warm", "twotower", "retrieval",
                                 "quality", "stream", "parse_profile",
                                 "loadgen"])
    parser.add_argument("--base")
    parser.add_argument("--out")
    args = parser.parse_args()
    if args.stage == "lint":
        stage_lint(args.base, args.out)
    elif args.stage == "prof":
        stage_prof(args.base, args.out)
    elif args.stage == "sentinel":
        stage_sentinel(args.base, args.out)
    elif args.stage == "dataobs":
        stage_dataobs(args.base, args.out)
    elif args.stage == "cold":
        stage_cold(args.base, args.out)
    elif args.stage == "warm":
        stage_warm(args.base, args.out)
    elif args.stage == "twotower":
        stage_twotower(args.base, args.out)
    elif args.stage == "retrieval":
        stage_retrieval(args.base, args.out)
    elif args.stage == "quality":
        stage_quality(args.base, args.out)
    elif args.stage == "stream":
        stage_stream(args.base, args.out)
    elif args.stage == "parse_profile":
        _parse_train_profile(args.base)
    elif args.stage == "loadgen":
        stage_loadgen(args.base)
    else:
        orchestrate()


if __name__ == "__main__":
    main()
