"""How many of the program's ``pio:`` spans the traced stretch of a cell's
LAST traced run in this checkout holds, by name (a builder's tool: a traffic
file's ``trace_after_go_s`` is held to at least 10 ``pio:seq.extend`` and 20
``pio:seq.prefill_chunk`` spans a stretch, ``GLM_SPANS.md``).

    python3 benchmarks/tools/stretch_spans.py glm-5.lifelong32k-c4
"""

from __future__ import annotations

import collections
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCHMARKS = os.path.dirname(HERE)
sys.path.insert(0, BENCHMARKS)


def counts(trace_dir: str) -> dict:
    import program_spans
    import trace_reduce

    trace = program_spans.load(trace_reduce.find_xplane(trace_dir), {})
    if trace is None:
        return {}
    return dict(collections.Counter(s.name for s in trace.spans))


def main() -> int:
    cell = sys.argv[1]
    trace_dir = os.path.join(os.path.dirname(BENCHMARKS), ".pio_run", "bench",
                             cell, "trace")
    found = counts(trace_dir)
    print(f"# traced stretch of {cell}: "
          + ", ".join(f"{n} {name}" for name, n in sorted(
              found.items(), key=lambda kv: -kv[1])), flush=True)
    return 0 if found else 1


if __name__ == "__main__":
    sys.exit(main())
