"""What the comparison of ``phi-4-mini-flash-reasoning.longlived-c8`` must
catch in the PROGRAM, shown on the chip at the cell's own size (a builder's
tool; no run of the benchmark runs it; ``benchmarks/control.py`` breaks the
REFERENCE in the program's place instead). The cell's own seeded weights go
through a program broken underneath (``models/phirec.stack_spec(ablate=)``):
``window_less_one``, the window layers attending 511 positions.

    python3 benchmarks/tools/phi_ablation.py --window window_less_one --seed 7
        a WHOLE WINDOW of the cell (``benchmarks/run.py``'s own ``main``, in
        this process) with that program in the sound one's place: the
        result line must read ``"correct": false``
"""

from __future__ import annotations

import argparse
import contextlib
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCHMARKS = os.path.dirname(HERE)
sys.path.insert(0, BENCHMARKS)
sys.path.insert(0, os.path.dirname(BENCHMARKS))

CELL = "phi-4-mini-flash-reasoning.longlived-c8"
VARIANTS = ("window_less_one",)


@contextlib.contextmanager
def broken(what: str, builder):
    """The program broken as ``what`` says, underneath ``builder`` (the
    module whose ``stack_spec`` the run deploys), until the block ends."""
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
    # the module instance the run itself will load
    builder = harness.load_file(os.path.join(BENCHMARKS, "models",
                                             "phirec.py"))
    print(f"# ablation: a window of {args.workload} with {args.window} "
          "underneath", flush=True)
    with broken(args.window, builder):
        return harness.main(
            ["--workload", args.workload, "--seed", str(args.seed),
             "--seconds", str(args.seconds), "--trace", "0",
             "--bench-root", os.path.abspath(args.bench_root)]
            + (["--rehearse-cpu"] if args.rehearse_cpu else []))


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
