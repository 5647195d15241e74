"""The load generator of ``slate_queries``: ``session_loadgen.py`` for queries
that ask for a slate (``{"items", "generate"}``). A process of its own,
standard library only (no JAX), raw keep-alive sockets as ``loadgen.py``.

Every connection plays whole sessions back to back (``slate_traffic
.Sessions``) in ONE closed loop from its first request to its last: send, wait
for the slate, send again at once. Request bodies are built before the first
request goes out, so that the loop holds nothing but the socket. Protocol with
the parent as ``loadgen.py``: ``STARTED`` when the connections are let go,
``WARMUP_DONE`` when each has played its warm-up sessions, then ``GO`` on
standard input; the requests that START in the ``seconds`` after ``GO`` are
the window's. The last line is one JSON object with every latency (tagged
first query / follow-up) and a seeded sample of the window's answers: half
first queries, half follow-ups (which read reused blocks), the longest history
served among them, each with the item rows its query carried. An answer that
is no JSON object with ``generate`` ``itemScores`` is malformed.
"""

from __future__ import annotations

import json
import os
import random
import socket
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from loadgen import one  # noqa: E402 — the same minimal HTTP/1.1 client
from slate_traffic import Sessions  # noqa: E402


def request_bytes(rows, generate: int) -> bytes:
    body = json.dumps({"items": ["i%d" % r for r in rows],
                       "generate": generate}).encode()
    return (b"POST /queries.json HTTP/1.1\r\nHost: 127.0.0.1\r\n"
            b"Content-Type: application/json\r\nContent-Length: "
            + str(len(body)).encode() + b"\r\n\r\n" + body)


def main() -> int:
    cfg = json.loads(sys.argv[1])
    mix = cfg["mix"]
    port, seed = int(cfg["port"]), int(cfg["seed"])
    n_conn, num = int(mix["connections"]), int(mix["generate"])
    seconds = float(cfg["seconds"])
    warm_sessions = int(mix["warmup_sessions_per_connection"])
    prepared = int(mix["prepared_sessions_per_connection"])
    sample_n = int(mix["check_sample"])
    sessions = Sessions(mix, int(cfg["n_items"]))
    per_session = int(mix["queries_per_session"])

    # every body of every connection, before anything is sent
    plan = []                   # [connection][k] = (session, query, rows)
    bodies = []
    for c in range(n_conn):
        plan.append([(s, q, rows) for s in range(prepared)
                     for q, rows in enumerate(sessions.session(c, s))])
        bodies.append([request_bytes(rows, num) for _, _, rows in plan[c]])

    waves = mix.get("start") or [{"connections": n_conn}]
    wave_of = [k for k, w in enumerate(waves)
               for _ in range(int(w["connections"]))]
    if len(wave_of) != n_conn:
        print(json.dumps({"fatal": ["start: the waves' connections do not "
                                    f"add up to {n_conn}"]}), flush=True)
        return 1
    begin = threading.Event()
    released = [threading.Event() for _ in waves]
    connected = [threading.Event() for _ in range(n_conn)]
    at_boundary = [threading.Event() for _ in range(n_conn)]
    warm_requests = warm_sessions * per_session
    lat = [[] for _ in range(n_conn)]       # (t_done, seconds, k)
    answers = [[] for _ in range(n_conn)]   # (k, body)
    errors = [[] for _ in range(n_conn)]
    sent = [0] * n_conn
    played = [0] * n_conn                   # requests answered so far
    window = [float("inf"), float("inf")]

    def connect():
        sock = socket.create_connection(("127.0.0.1", port), 120)
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        return sock, sock.makefile("rb")

    def worker(c: int) -> None:
        try:
            sock, rfile = connect()
        except OSError as e:
            errors[c].append(f"connect: {e!r}")
            return
        finally:
            connected[c].set()
        begin.wait()
        k = 0
        while True:
            if k == warm_requests:
                at_boundary[c].set()
                released[wave_of[c]].wait()
            t0 = time.perf_counter()
            if t0 >= window[1]:
                break
            timed = t0 >= window[0]
            at = k % len(bodies[c])
            if timed:
                sent[c] += 1
            try:
                body = one(sock, rfile, bodies[c][at])
            except Exception as e:  # noqa: BLE001 — counted as failed
                errors[c].append(("window: " if timed else "warm-up: ")
                                 + repr(e))
                try:
                    sock.close()
                    sock, rfile = connect()
                except OSError:
                    return
                k += 1
                continue
            if timed:
                t1 = time.perf_counter()
                lat[c].append((t1, t1 - t0, at))
                answers[c].append((at, body))
            k += 1
            played[c] = k
        sock.close()

    threads = [threading.Thread(target=worker, args=(c,), daemon=True)
               for c in range(n_conn)]
    for t in threads:
        t.start()
    for e in connected:
        e.wait()
    print("STARTED", flush=True)
    begin.set()
    t_limit = time.perf_counter() + 900
    while not all(e.is_set() for e in at_boundary):
        dead = [c for c, t in enumerate(threads) if not t.is_alive()]
        if dead or time.perf_counter() > t_limit:
            print(json.dumps({"fatal": [e for es in errors for e in es][:3]
                              or ["warm-up did not finish"]}), flush=True)
            return 1
        time.sleep(0.01)
    print("WARMUP_DONE", flush=True)
    if sys.stdin.readline().strip() != "GO":
        return 1
    t_go = time.perf_counter()
    window[1] = t_go + seconds
    window[0] = t_go
    for w, go in zip(waves, released):
        time.sleep(float(w.get("delay_s", 0.0)))
        go.set()
    for t in threads:
        t.join(timeout=seconds + 600)

    done = [x for ls in lat for x in ls]         # by connection, in order
    t_end = max((t for t, _, _ in done), default=t_go)
    every = [(c, j) for c in range(n_conn) for j in range(len(answers[c]))]

    def rows_of(c, j):
        return plan[c][answers[c][j][0]][2]

    def is_first(c, j):
        return plan[c][answers[c][j][0]][1] == 0

    firsts = [cj for cj in every if is_first(*cj)]
    later = [cj for cj in every if not is_first(*cj)]
    rng = random.Random(seed)
    half = sample_n // 2
    pick = (rng.sample(firsts, min(half, len(firsts)))
            + rng.sample(later, min(sample_n - half, len(later))))
    if every:
        longest = max(every, key=lambda cj: len(rows_of(*cj)))
        if longest not in pick:
            pick[0 if longest in firsts or not later else -1] = longest
        # ... and the longest later query (the same answer unless a session
        # of the longest history had only begun): what ``check_floor`` names
        longest_later = max(later, key=lambda cj: len(rows_of(*cj)),
                            default=longest)
        if longest_later not in pick:
            pick[-1] = longest_later
    sample = [{"rows": rows_of(c, j),
               "first": is_first(c, j),
               "body": answers[c][j][1].decode("utf-8", "replace")}
              for c, j in sorted(set(pick))]
    def well_formed(body: bytes) -> bool:
        try:
            return len(json.loads(body)["itemScores"]) == num
        except (ValueError, KeyError, TypeError):
            return False

    malformed = sum(1 for c in range(n_conn) for _, b in answers[c]
                    if not well_formed(b))
    print(json.dumps({
        "sent": sum(sent), "answered": len(done),
        "errors": [e for es in errors for e in es][:5],
        "n_errors": sum(1 for es in errors for e in es
                        if e.startswith("window: ")),
        "malformed": malformed,
        "window_s": t_end - t_go,
        "latencies_s": [d for _, d, _ in done],
        # per request, in the order of latencies_s: first query of its
        # session or not, and the length of the history it carried
        "first_query": [plan[c][at][1] == 0 for c in range(n_conn)
                        for _, _, at in lat[c]],
        "history_lengths": [len(plan[c][at][2]) for c in range(n_conn)
                            for _, _, at in lat[c]],
        "sessions_played": [k // per_session for k in played],
        "sample": sample,
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
