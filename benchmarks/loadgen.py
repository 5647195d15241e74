"""The load generator: a process of its own that imports no JAX.

One general generator that reads a traffic mix (a data file under
``traffic/``) and drives ``POST /queries.json`` over raw keep-alive sockets:
a minimal HTTP/1.1 client, so that the cycles it burns are not taken from the
server it shares the machine with (the design of the pre-chip benchmark
script's load stage; that script left the tree at PR 29).

Protocol with the parent (which holds the chip): every connection runs ONE
closed loop from its first request to its last — send, wait for the answer,
send again at once — and is never stopped and restarted in between, so the
window sees the system in the state its own dynamics settled into. Every
socket is connected before the first request goes out; the mix's ``start``
then says in which waves the connections begin (all at once where it says
nothing), so that every run begins the same way. The generator prints
``STARTED`` as it lets the first wave go, ``WARMUP_DONE`` when every
connection has sent its warm-up queries, and then waits for ``GO`` on its
standard input; the requests that START in the ``seconds`` after ``GO`` are
the window's. One JSON line with every latency
and a seeded sample of the answers is printed last.

Standard library only.
"""

from __future__ import annotations

import gc
import json
import random
import socket
import sys
import threading
import time


def request_bytes(user: str, num: int) -> bytes:
    body = json.dumps({"user": user, "num": num}).encode()
    return (b"POST /queries.json HTTP/1.1\r\nHost: 127.0.0.1\r\n"
            b"Content-Type: application/json\r\nContent-Length: "
            + str(len(body)).encode() + b"\r\n\r\n" + body)


def one(sock, rfile, req: bytes) -> bytes:
    sock.sendall(req)
    status = rfile.readline()
    length = None
    while True:
        line = rfile.readline()
        if line in (b"\r\n", b"\n", b""):
            break
        if line.lower().startswith(b"content-length:"):
            length = int(line.split(b":", 1)[1])
    body = rfile.read(length) if length else b""
    if not status.startswith(b"HTTP/1.1 200"):
        raise RuntimeError(f"status {status[:60]!r} body {body[:120]!r}")
    return body


def main() -> int:
    cfg = json.loads(sys.argv[1])
    port, seed = int(cfg["port"]), int(cfg["seed"])
    n_conn, num = int(cfg["connections"]), int(cfg["num"])
    n_users, seconds = int(cfg["n_users"]), float(cfg["seconds"])
    warmup = int(cfg["warmup_per_connection"])
    sample_n = int(cfg["check_sample"])
    prefix = cfg.get("user_prefix", "u")
    # the waves in which the connections begin their loops, in order:
    # {"connections": n, "delay_s": seconds after the wave before it}
    waves = cfg.get("start") or [{"connections": n_conn}]
    if sum(int(w["connections"]) for w in waves) != n_conn:
        print(json.dumps({"fatal": ["start: the waves' connections do not "
                                    f"add up to {n_conn}"]}), flush=True)
        return 1
    wave_of = [k for k, w in enumerate(waves)
               for _ in range(int(w["connections"]))]
    released = [threading.Event() for _ in waves]
    connected = [threading.Event() for _ in range(n_conn)]

    lat = [[] for _ in range(n_conn)]       # (t_done, seconds)
    answers = [[] for _ in range(n_conn)]   # (user_row, body)
    errors = [[] for _ in range(n_conn)]
    sent = [0] * n_conn
    warmed = [0] * n_conn
    window = [float("inf"), float("inf")]   # [t_go, deadline]

    def connect():
        sock = socket.create_connection(("127.0.0.1", port), 120)
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        return sock, sock.makefile("rb")

    def worker(c: int) -> None:
        # every connection draws its users from (seed, connection): the
        # same seed sends the same users, whatever the timing
        rng = random.Random(seed * 1000003 + c)
        try:
            sock, rfile = connect()
        except OSError as e:
            errors[c].append(f"connect: {e!r}")
            return
        finally:
            connected[c].set()
        released[wave_of[c]].wait()
        while True:
            t0 = time.perf_counter()
            if t0 >= window[1]:
                break
            timed = t0 >= window[0]
            row = rng.randrange(n_users)
            req = request_bytes(prefix + str(row), num)
            if timed:
                sent[c] += 1
            try:
                body = one(sock, rfile, req)
            except Exception as e:  # noqa: BLE001 — counted as failed
                errors[c].append(("window: " if timed else "warm-up: ")
                                 + repr(e))
                try:
                    sock.close()
                    sock, rfile = connect()
                except OSError:
                    return
                continue
            if timed:
                t1 = time.perf_counter()
                lat[c].append((t1, t1 - t0))
                answers[c].append((row, body))
            else:
                warmed[c] += 1
        sock.close()

    # this process's own collector: every pause of it holds all the
    # connections at once ([collections, seconds in all, longest] by
    # generation; printed by the driver, read by no metric)
    pauses = {g: [0, 0.0, 0.0] for g in (0, 1, 2)}
    began = [0.0]

    def on_gc(phase, info):
        if phase == "start":
            began[0] = time.perf_counter()
        else:
            p, d = pauses[info["generation"]], time.perf_counter() - began[0]
            p[0], p[1], p[2] = p[0] + 1, p[1] + d, max(p[2], d)

    gc.callbacks.append(on_gc)
    threads = [threading.Thread(target=worker, args=(c,), daemon=True)
               for c in range(n_conn)]
    for t in threads:
        t.start()
    for e in connected:
        e.wait()
    print("STARTED", flush=True)
    for w, go in zip(waves, released):
        time.sleep(float(w.get("delay_s", 0.0)))
        go.set()
    t_limit = time.perf_counter() + 600
    while min(warmed) < warmup:
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
    for t in threads:
        t.join(timeout=seconds + 300)

    done = sorted(x for ls in lat for x in ls)
    t_end = done[-1][0] if done else t_go
    every = [(c, j) for c in range(n_conn) for j in range(len(answers[c]))]
    pick = random.Random(seed).sample(every, min(sample_n, len(every)))
    sample = [(answers[c][j][0], answers[c][j][1].decode("utf-8", "replace"))
              for c, j in sorted(pick)]
    # cheap shape check of EVERY answer; the sample is compared in full
    malformed = sum(1 for c in range(n_conn) for _, b in answers[c]
                    if b.count(b'"item"') != num)
    print(json.dumps({
        "sent": sum(sent), "answered": len(done),
        "errors": [e for es in errors for e in es][:5],
        "n_errors": sum(1 for es in errors for e in es
                        if e.startswith("window: ")),
        "malformed": malformed,
        "window_s": t_end - t_go,
        "latencies_s": [d for _, d in done],
        "done_s": [t - t_go for t, _ in done],
        "gc_pauses": pauses,
        "sample": sample,
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
