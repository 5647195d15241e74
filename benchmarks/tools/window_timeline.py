"""Where in an UNTRACED window of a ``session_queries`` cell the extensions
and the first queries lie: what a traffic file's ``trace_after_go_s`` is
chosen from (a builder's tool; no run of the benchmark runs it).

    python3 benchmarks/tools/window_timeline.py --workload glm-5.lifelong32k-c4 --seed 7

It runs the cell through ``benchmarks/run.py``'s own ``main`` in this
process, untraced, and keeps what the load generator said at the window's
end: every request's latency in the order its connection sent it, whether it
was a first query and the history it carried. A connection sends its next
request the moment the last is answered, so a request STARTED when the ones
before it on its connection had taken their time, after the wave's delay of
the mix's ``start``; the connections' shares of the list are told apart by
the first histories each plays (``closed_loop_model.session_orders``). Printed
after the run's own lines: the first queries (start, latency, history), the
extensions that started in each second, and for every stretch of
``trace_seconds`` that could be traced (a start every half second) how many
extensions and how many first queries' prefills it holds. A stretch is worth
tracing where it holds both in every seed (a seed a call of this tool: a
process deploys the engine once).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
BENCHMARKS = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, BENCHMARKS)
sys.path.insert(0, os.path.dirname(BENCHMARKS))
from closed_loop_model import session_orders, wave_offsets  # noqa: E402

CELL = "glm-5.lifelong32k-c4"


def by_connection(load: dict, mix: dict, orders: list) -> list:
    """The load generator's flat lists cut into the connections' own:
    ``[[(latency_s, first_query, history), ...] per connection]``. The list
    is by connection, in order, and connection ``c`` begins with the first
    session after its warm-up ones."""
    flat = list(zip(load["latencies_s"], load["first_query"],
                    load["history_lengths"]))
    first_session = int(mix["warmup_sessions_per_connection"])
    per_session = int(mix["queries_per_session"])
    out, at = [], 0
    for c, order in enumerate(orders):
        mine, session = [], first_session
        while at < len(flat):
            _, first, history = flat[at]
            if first:
                opens_mine = history == order[session % len(order)] and (
                    len(mine) % per_session == 0)
                if not opens_mine:
                    break            # the next connection's first session
                session += 1
            mine.append(flat[at])
            at += 1
        out.append(mine)
    if at != len(flat):
        raise ValueError(f"{len(flat) - at} requests belong to no connection")
    return out


def starts(load: dict, mix: dict, orders: list) -> list:
    """``[(start_s after GO, latency_s, first_query, history)]`` of every
    request of the window, by start."""
    out = []
    for offset, mine in zip(wave_offsets(mix),
                            by_connection(load, mix, orders)):
        t = offset
        for latency, first, history in mine:
            out.append((t, latency, first, history))
            t += latency
    return sorted(out)


def stretches(requests: list, seconds: float, length: float) -> list:
    """``[(start, extensions wholly inside, first queries in prefill through
    all of it or part)]`` for a stretch of ``length`` starting every half
    second."""
    out, a = [], 0.0
    while a + length <= seconds:
        inside = sum(1 for t, d, f, _ in requests
                     if not f and a <= t and t + d <= a + length)
        prefills = sum(1 for t, d, f, _ in requests
                       if f and t < a + length and t + d > a)
        out.append((a, inside, prefills))
        a += 0.5
    return out


def report(requests: list, seconds: float, length: float) -> list:
    lines = [f"first query: start {t:.2f} s, {d:.3f} s, history {h}"
             for t, d, f, h in requests if f]
    per = [0] * (int(seconds) + 1)
    for t, _, f, _ in requests:
        if not f:
            per[min(int(t), len(per) - 1)] += 1
    lines.append("extensions started by the second after GO: "
                 + " ".join(f"{s}:{n}" for s, n in enumerate(per)))
    lines.append(f"stretches of {length:g} s (start: extensions wholly "
                 "inside / first queries in prefill): "
                 + " ".join(f"{a:g}:{n}/{p}" for a, n, p in
                            stretches(requests, seconds, length)))
    return lines


def main() -> int:
    import run as harness

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default=CELL)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--bench-root", default=harness.CHECKOUT)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    root = os.path.abspath(args.bench_root)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cell = next(w for w in spec["workloads"] if w["name"] == args.workload)

    said = []

    class Kept(subprocess.Popen):
        """The load generator's child, its last words kept."""

        def communicate(self, *a, **kw):
            out, err = super().communicate(*a, **kw)
            said.append(out)
            return out, err

    bench_args = ["--workload", args.workload, "--seed", str(args.seed),
                  "--seconds", str(args.seconds), "--trace", "0",
                  "--bench-root", root] + (
        ["--rehearse-cpu"] if args.rehearse_cpu else [])
    # the module instance the run itself will load
    probe = harness.Bench(root, spec, cell, argparse.Namespace(
        seed=args.seed, seconds=args.seconds, trace=0))
    mix = probe.traffic
    driver = probe.load_module("drivers", mix["driver"])
    driver.subprocess = types.SimpleNamespace(Popen=Kept,
                                              PIPE=subprocess.PIPE)
    try:
        code = harness.main(bench_args)
    finally:
        driver.subprocess = subprocess
    load = json.loads(said[-1].strip().splitlines()[-1])
    requests = starts(load, mix, session_orders(mix))
    print(f"# timeline of {args.workload}, seed {args.seed}, untraced:")
    for line in report(requests, args.seconds,
                       float(mix.get("trace_seconds", 3.0))):
        print(f"# {line}", flush=True)
    return code


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
