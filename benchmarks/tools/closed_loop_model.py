"""A closed-loop ``session_queries`` cell reckoned on paper: what its window
would COUNT and how its latencies would lie, from the traffic file and four
costs, before anybody asks for the cell (a builder's tool; standard library
and ``session_traffic.Sessions`` only, nothing of the chip, and no number it
prints is a measurement).

    python3 benchmarks/tools/closed_loop_model.py benchmarks/traffic/lifelong32k-c4.json \
        --chunk0-ms 28 --chunk-far-ms 80 --extend-ms 13 --host-ms 5 --seconds 20

The model is the sequence engine's order of work and nothing else
(``models/sessionrec.plan_step``): the connections begin their first session
after the warm-up ones in the waves of the mix's ``start``; a query whose
history is new is prefilled a chunk a step, the OLDEST such query first (a
FIFO of whole prefills), a chunk costing ``chunk0`` at offset 0 and
``chunk_far`` at the mix's ``history_max``, linear between; every query with
at most ``extend_len`` positions left joins the step's one extension batch
(``extend_ms`` a batch); a step costs ``host_ms`` besides, and its answers go
out at its end, when each connection sends its next query at once. Requests
that START inside the window are the window's, as the load generator has it.

What it is for (ISSUE 54): a tail read over ALL requests of such a cell is
the k-th slowest FIRST query, and k moves with the window's count of
extensions, so a change that makes extensions faster moves the tail either
way; ``sweep`` shows it, ``tests/benchmarks/test_closed_loop_model.py`` pins
it on ``lifelong32k-c4.json``. Reckon a closed-loop cell's tail with it
before asking for the cell (``benchmarks/README.md``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from session_traffic import Sessions  # noqa: E402

#: the serve shape of the long-history cells (``configs/*.json`` ``serve``)
CHUNK, EXTEND_LEN, EXTEND_BATCH = 512, 4, 4


def percentile(sorted_vals, q: float) -> float:
    """Nearest-rank percentile of an ascending list, as
    ``drivers/closed_loop_queries.percentile``."""
    n = len(sorted_vals)
    return sorted_vals[min(n - 1, max(0, int(-(-q * n // 1)) - 1))]


def session_orders(mix: dict) -> list:
    """``[connection]`` = the histories of the sessions it plays, in its own
    order (``Sessions.order``: the orders need no items)."""
    sessions = Sessions(mix, int(mix["topics"]))
    return [sessions.order(c) for c in range(int(mix["connections"]))]


def wave_offsets(mix: dict) -> list:
    """``[connection]`` = seconds after GO at which the mix's ``start`` lets
    it begin (all at once where the mix has no waves)."""
    out, at = [], 0.0
    for wave in mix.get("start") or [{"connections": mix["connections"]}]:
        at += float(wave.get("delay_s", 0.0))
        out += [at] * int(wave["connections"])
    return out


def simulate(mix: dict, chunk0_ms: float, chunk_far_ms: float,
             extend_ms: float, host_ms: float, seconds: float) -> dict:
    """One window of the mix under those costs: every request that started
    in it as ``(start_s, latency_s, first_query, connection)``, in the
    order answered, and every step as ``(start_s, end_s, extension rows,
    chunk offset or None)``."""
    n_conn = int(mix["connections"])
    per_session = int(mix["queries_per_session"])
    orders = session_orders(mix)
    far = float(mix["history_max"])

    def chunk_s(offset: int) -> float:
        return (chunk0_ms + (chunk_far_ms - chunk0_ms) * offset / far) / 1e3

    # [connection] = requests sent so far, counted from the first session
    # after the warm-up ones
    sent = [0] * n_conn
    first_session = int(mix["warmup_sessions_per_connection"])
    arrivals = [(at, c) for c, at in enumerate(wave_offsets(mix))]  # sorted
    pending, requests, steps, t = [], [], [], 0.0
    while arrivals or pending:
        while arrivals and arrivals[0][0] <= t:
            start, c = arrivals.pop(0)
            if start >= seconds:
                continue             # the window is over for this connection
            session, query = divmod(sent[c], per_session)
            sent[c] += 1
            history = orders[c][(first_session + session) % len(orders[c])]
            # a later query carries the cached history and 1..grow_max more
            pending.append({"c": c, "start": start, "first": query == 0,
                            "left": history if query == 0 else 1, "done": 0})
        if not pending:
            if arrivals:
                t = arrivals[0][0]
            continue
        short = [q for q in pending if q["left"] <= EXTEND_LEN][:EXTEND_BATCH]
        pre = next((q for q in pending if q["left"] > EXTEND_LEN), None)
        t0 = t
        t += host_ms / 1e3 + (extend_ms / 1e3 if short else 0.0) \
            + (chunk_s(pre["done"]) if pre else 0.0)
        steps.append((t0, t, len(short), pre["done"] if pre else None))
        answered = list(short)
        if pre:
            n = min(pre["left"], CHUNK)
            pre["done"] += n
            pre["left"] -= n
            if pre["left"] == 0:
                answered.append(pre)
        for q in answered:
            pending.remove(q)
            requests.append((q["start"], t - q["start"], q["first"], q["c"]))
            arrivals.append((t, q["c"]))
        arrivals.sort()
    return {"requests": requests, "steps": steps}


def summary(run: dict, seconds: float) -> dict:
    """What the driver would print of that window: the count, the median and
    the 95th percentile over ALL requests, and the first queries' own."""
    lat = sorted(d for _, d, _, _ in run["requests"])
    firsts = sorted(d for _, d, f, _ in run["requests"] if f)
    out = {"answered": len(lat),
           "query_rate": len(lat) / seconds,
           "query_p50_ms": percentile(lat, 0.5) * 1e3,
           "query_p95_ms": percentile(lat, 0.95) * 1e3,
           "first_queries": len(firsts)}
    if firsts:
        out["first_query_p50_ms"] = percentile(firsts, 0.5) * 1e3
    return out


def by_second(run: dict, seconds: float) -> list:
    """``[(second, extension batches, chunks)]`` by the second a step began
    in: where in the window a traced stretch would hold both."""
    out = [[s, 0, 0] for s in range(int(seconds))]
    for t0, _, rows, offset in run["steps"]:
        if t0 < len(out):
            out[int(t0)][1] += 1 if rows else 0
            out[int(t0)][2] += 1 if offset is not None else 0
    return [tuple(x) for x in out]


def sweep(mix: dict, extend_costs, **costs) -> list:
    """``summary`` at each extension cost, everything else fixed."""
    seconds = costs["seconds"]
    return [dict(summary(simulate(mix, extend_ms=e, **costs), seconds),
                 extend_ms=e) for e in extend_costs]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("traffic", help="a session_queries traffic file")
    ap.add_argument("--chunk0-ms", type=float, required=True,
                    help="a chunk's cost at offset 0")
    ap.add_argument("--chunk-far-ms", type=float, required=True,
                    help="a chunk's cost at the mix's longest history")
    ap.add_argument("--extend-ms", type=float, required=True,
                    help="an extension batch's cost")
    ap.add_argument("--host-ms", type=float, required=True,
                    help="host time a step")
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    with open(args.traffic) as f:
        mix = json.load(f)
    run = simulate(mix, args.chunk0_ms, args.chunk_far_ms, args.extend_ms,
                   args.host_ms, args.seconds)
    print("# a model, not a measurement: FIFO of whole prefills, one chunk "
          "a step")
    print(json.dumps(summary(run, args.seconds)))
    print("# by the second a step began in: second, extension batches, "
          "chunks")
    print(" ".join(f"{s}:{e}/{c}" for s, e, c in by_second(run,
                                                           args.seconds)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
