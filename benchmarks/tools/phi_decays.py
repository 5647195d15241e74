"""How the decays are seeded decides what ``correct`` can see of the carried
state (a builder's tool; no run of the benchmark runs it). For each candidate
seeding of ``phi-4-mini-flash-reasoning``'s Mamba-1 decays (``seeded_decays``
in the configuration's file: the log-uniform range of the step and the
divisor of ``A[:, n] = n + 1``) it makes the cell's own seeded weights and
puts the REFERENCE, broken as ``benchmarks/control.py`` breaks it, in the
program's place on the control's histories (the mix's shortest sessions AND
its longest): one line a seeding and variant, a reading a history, beside the
configuration's limits. The sound reference's logits are computed once a
history and seeding.

    python3 benchmarks/tools/phi_decays.py --seed 5300000081 \\
        --seedings family,a4,a16 --variants state_bfloat16,window_less_one
    python3 benchmarks/tools/phi_decays.py --rehearse-cpu \\
        --bench-root tests/benchmarks/tiny_phi --workload phi-tiny.longlived-c2
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCHMARKS = os.path.dirname(HERE)
sys.path.insert(0, BENCHMARKS)
sys.path.insert(0, os.path.dirname(BENCHMARKS))

CELL = "phi-4-mini-flash-reasoning.longlived-c8"
#: ``family``: the family's own initialisation (a memory of 1,000 positions
#: at most); the others divide ``A`` and leave the steps
SEEDINGS = {"family": {"step": [1e-3, 0.1], "a_over": 1},
            "a4": {"step": [1e-3, 0.1], "a_over": 4},
            "a16": {"step": [1e-3, 0.1], "a_over": 16},
            "dt10": {"step": [1e-4, 1e-2], "a_over": 1}}


def main() -> int:
    import run as harness

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default=CELL)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seedings", default="configured")
    ap.add_argument("--variants", default="state_bfloat16,window_less_one")
    ap.add_argument("--bench-root", default=harness.CHECKOUT)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    if args.rehearse_cpu:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np

    root = os.path.abspath(args.bench_root)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cell = next(w for w in spec["workloads"] if w["name"] == args.workload)
    bench = harness.Bench(root, spec, cell, argparse.Namespace(
        seed=args.seed, seconds=0, trace=0))
    reference = bench.load_module("reference", bench.config["reference"])
    builder = bench.load_module("models", bench.config["engine"])
    dm, k = reference.dims_of(bench.config), int(bench.traffic["num"])
    histories = builder.control_histories(bench)
    reach = max(len(ids) for ids in histories)
    for seeding in args.seedings.split(","):
        if seeding != "configured":
            bench.config["seeded_decays"] = SEEDINGS[seeding]
        weights = builder.make_weights(bench)
        t = time.perf_counter()
        sound = [reference.forward(weights, ids, dm, reach=reach)
                 for ids in histories]
        sound_s = time.perf_counter() - t
        for name in args.variants.split(","):
            how = dict(reference.VARIANTS[name])
            if how.get("window") == -1:
                how["window"] = dm["window"] - 1
            readings = [reference.measure(logits, reference.top_k_answer(
                reference.forward(weights, ids, dm, reach=reach, **how), k), k)
                for ids, logits in zip(histories, sound)]
            print(json.dumps({
                "seeding": seeding, "decays": bench.config["seeded_decays"],
                "seed": args.seed, "variant": name,
                "limits": bench.config.get("limits"),
                "histories": [len(ids) for ids in histories],
                "score_err": [r[0] for r in readings],
                "rank_gap": [r[1] for r in readings],
                "sound_forwards_s": sound_s}), flush=True)
        del weights, sound
    return 0


if __name__ == "__main__":
    sys.exit(main())
