"""What the comparison of ``mimo-v2.5.mixed-c8`` must catch, shown on the chip
at the cell's own size (a builder's tool; no run of the benchmark runs it).
The cell's own seeded weights go through a program whose window layers are
broken underneath (``broken``; ``models/mimorec.stack_spec(ablate=)``):

* ``no_sink``: the learned sink left out of the window layers' normaliser;
* ``wide_window``: the window layers attending 2,048 positions in the 128's
  place: full attention for every history up to there (half of the mix's;
  spans for five more layers of full attention would take 16 GB beside the
  weights: the ring is what lets the cell exist);
* ``full_theta``: the window layers turned at the full layers' RoPE base
  (1e7 in 1e4's place).

    python3 benchmarks/tools/mimo_ablation.py --window no_sink --seed 7
        a WHOLE WINDOW of the cell (``benchmarks/run.py``'s own ``main``, in
        this process) with that program in the sound one's place: the
        result line must read ``"correct": false``
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCHMARKS = os.path.dirname(HERE)
sys.path.insert(0, BENCHMARKS)
sys.path.insert(0, os.path.dirname(BENCHMARKS))

CELL = "mimo-v2.5.mixed-c8"
VARIANTS = ("no_sink", "wide_window", "full_theta")


@contextlib.contextmanager
def broken(what: str, builder):
    """The program's window layers broken as ``what`` says, underneath
    ``builder`` (the module whose ``stack_spec`` the run deploys), until the
    block ends."""
    if what not in VARIANTS:
        raise ValueError(f"unknown ablation {what!r}")
    sound = builder.stack_spec
    builder.stack_spec = lambda cfg, **kw: sound(cfg, ablate=what)
    try:
        yield
    finally:
        builder.stack_spec = sound


def main() -> int:
    import run as harness

    ap = argparse.ArgumentParser()
    ap.add_argument("--window", choices=VARIANTS, required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--bench-root", default=harness.CHECKOUT)
    ap.add_argument("--workload", default=CELL)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    root = os.path.abspath(args.bench_root)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cell = next(w for w in spec["workloads"] if w["name"] == args.workload)
    cfg_file = next(c["file"] for c in spec["configs"]
                    if c["name"] == cell["config"])
    with open(os.path.join(root, cfg_file)) as f:
        engine = json.load(f)["engine"]
    # the module instance the run itself will load
    builder = harness.load_file(os.path.join(BENCHMARKS, "models",
                                             engine + ".py"))
    print(f"# ablation: a window of {args.workload} with {args.window} "
          "underneath", flush=True)
    with broken(args.window, builder):
        return harness.main(
            ["--workload", args.workload, "--seed", str(args.seed),
             "--seconds", str(args.seconds), "--trace", "0",
             "--bench-root", root]
            + (["--rehearse-cpu"] if args.rehearse_cpu else []))


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
