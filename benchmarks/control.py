"""The control of ``correct``: the reference in the program's place, computed
in a lower precision than the configuration states, must come out as NOT
correct. The benchmark's own runs never run this; a builder runs it on the
chip's machine at the cell's own size when a limit is set or checked:

    python3 benchmarks/control.py --workload <cell> --seeds 1,2,3

For each seed it prints the numbers the cell's checks compare, as the control
gives them, beside the limits of the configuration's file. The work is the
reference module's ``control(bench)``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))


def main() -> int:
    import run as harness

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--bench-root", default=harness.CHECKOUT)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    if args.rehearse_cpu:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    root = os.path.abspath(args.bench_root)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cell = next(w for w in spec["workloads"] if w["name"] == args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        ns = argparse.Namespace(seed=seed, seconds=0, trace=0)
        bench = harness.Bench(root, spec, cell, ns)
        reference = bench.load_module("reference", bench.config["reference"])
        readings = reference.control(bench)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "limits": bench.config.get("limits"),
                          "control": readings}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
