"""Record the small trace that tests/benchmarks/test_axk_cell.py checks
A.X-K1's per-layer readers against (run once on the chip; committed as
tests/benchmarks/fixtures/axk_small.xplane.pb, with axk_small.scopes.json —
the program's instruction -> scope maps — and axk_small.ctx.json — the
engine's counters at the stretch's two ends — beside it):

    python3 benchmarks/tools/record_axk_trace_fixture.py <out_dir>

``record_hyb_trace_fixture.py`` over another rehearsal tree
(tests/benchmarks/tiny_axk): one process deploys the tiny configuration as
the benchmark's builder does, and under one ``bench:window`` two connections
play one session each (a history of several chunks, then three extensions),
so that a step holds an extension batch and a prefill chunk.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))


def main(out_dir: str) -> None:
    sys.path.insert(0, HERE)
    import record_hyb_trace_fixture as hyb_tool

    hyb_tool.TINY = os.path.join(CHECKOUT, "tests", "benchmarks", "tiny_axk")
    hyb_tool.CELL = "axk-tiny.lifelong-c2"
    hyb_tool.main(out_dir)
    for ext in ("xplane.pb", "scopes.json", "ctx.json"):
        os.replace(os.path.join(out_dir, "hyb_small." + ext),
                   os.path.join(out_dir, "axk_small." + ext))


if __name__ == "__main__":
    os.makedirs(sys.argv[1], exist_ok=True)
    main(sys.argv[1])
    sys.stdout.flush()
    os._exit(0)     # the server's threads are daemons
