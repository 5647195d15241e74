"""What a sequence program's launch is made of (PERF.md section 7, PR 40): ONE
compiled program of the LongCat stack (``benchmarks/configs/longcat-flash-chat
.json``: its widths, its four double-layers, its serve shapes; seeded weights)
called alone, no server, in three forms:

* ``apart``: the program's function with what the host knows of a call as
  separate host arguments (numpy arrays and ``np.int32`` scalars: a call
  until PR 40), one host-to-device buffer each;
* ``packed``: ``StackPrograms``' own compiled program, the same in ONE int32
  host array;
* ``on_device``: the packed program with that array put on the device
  before the clock starts, so the call itself moves nothing.

Each reading is the host's clock around the call alone (ms until it RETURNS;
the device is idle when it starts and is waited for after the clock stops),
median and 90th percentile over ``ROUNDS`` calls, for the extension program
and the chunk program; then the same while 8 threads stand blocked in
``recv`` on keep-alive sockets, as a server's idle handlers do, and while 8
threads each take the interpreter for a request's worth of work (~0.3 ms)
every 5 ms, as handlers do that an answer woke. Before them
``back_to_back_ms``: what a call takes when fifty follow each other unwaited,
which is the program's time on the device, without and with the totals it
sums there.

One JSON line a reading, the log in ``chiprun_out/seq_launch_probe.log``:

    python3 tools/seq_launch_probe.py        (``--tiny``: on the CPU)
"""

import dataclasses
import json
import os
import socket
import sys
import threading
import time

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHECKOUT)
sys.path.insert(0, os.path.join(CHECKOUT, "benchmarks", "models"))

ROUNDS = 200


def idle_handlers(n, stop):
    """``n`` threads blocked in ``recv``; they end when ``stop`` is set and
    their peers are closed."""
    pairs = [socket.socketpair() for _ in range(n)]

    def handler(sock):
        while not stop.is_set() and sock.recv(64):
            pass

    threads = [threading.Thread(target=handler, args=(a,), daemon=True)
               for a, _ in pairs]
    for t in threads:
        t.start()
    return threads, [b for _, b in pairs]


def busy_handlers(n, stop):
    """``n`` threads that each serialise and parse an answer's JSON until
    ~0.3 ms have gone, then sleep 5 ms: a handler that an answer woke."""
    answer = {"itemScores": [{"item": f"i{i}", "score": 0.5 + i}
                             for i in range(10)]}

    def handler():
        while not stop.is_set():
            until = time.perf_counter() + 3e-4
            while time.perf_counter() < until:
                json.loads(json.dumps(answer))
            time.sleep(5e-3)

    threads = [threading.Thread(target=handler, daemon=True)
               for _ in range(n)]
    for t in threads:
        t.start()
    return threads, []


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import seqrec
    from predictionio_tpu.ops.sessionrec import (
        ServeShape, StackPrograms, init_stack)

    tiny = "--tiny" in sys.argv[1:]
    with open(os.path.join(CHECKOUT, "benchmarks", "configs",
                           "longcat-flash-chat.json")) as f:
        cfg = json.load(f)
    spec, shape = seqrec.stack_spec(cfg), ServeShape(**cfg["serve"])
    n_items = int(cfg["vocab_size"])
    if tiny:
        spec = dataclasses.replace(
            spec, dim=64, ffn_dim=128, blocks=spec.blocks[:1],
            mla=dataclasses.replace(spec.mla, dim=64, heads=4, d_nope=16,
                                    d_rope=8, d_v=16, q_rank=32, kv_rank=24),
            moe=dataclasses.replace(spec.moe, dim=64, expert_dim=32))
        shape, n_items = ServeShape(n_slots=3, capacity=64, chunk=16,
                                    extend_len=4, extend_batch=2), 256
    rounds = 5 if tiny else ROUNDS
    out_dir = os.path.join(CHECKOUT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    log = open(os.path.join(out_dir, "seq_launch_probe.log"), "w")

    def say(**line):
        text = json.dumps(line)
        print(text, flush=True)
        log.write(text + "\n")
        log.flush()

    device = jax.devices()[0]
    t0 = time.perf_counter()
    params = init_stack(spec, jax.random.PRNGKey(40), n_items, jnp.bfloat16)
    jax.block_until_ready(params)
    programs = StackPrograms(spec, params, shape)
    say(device=device.device_kind, platform=device.platform, tiny=tiny,
        rounds=rounds, parameter_leaves=len(jax.tree.leaves(params)),
        built_s=round(time.perf_counter() - t0, 1))

    # what the host knows of one call, as a step would assemble it
    rng = np.random.default_rng(40)
    n = shape.chunk - 7
    chunk_ids = np.zeros(shape.chunk, np.int32)
    chunk_ids[:n] = rng.integers(0, n_items, n)
    B, S = shape.extend_batch, shape.extend_len
    ids = np.zeros((B, S), np.int32)
    ids[:2, :2] = rng.integers(0, n_items, (2, 2))
    n_new = np.array([2, 2] + [0] * (B - 2), np.int32)
    slots = np.array([1, 2] + [shape.n_slots] * (B - 2), np.int32)
    pos0 = np.array([n, 3] + [0] * (B - 2), np.int32)
    apart_args = {
        "prefill": (chunk_ids, np.int32(n), np.int32(1), np.int32(0)),
        "extend": (ids, n_new, slots, pos0, programs.n_blocks(n + S))}
    fns = {"prefill": programs._prefill_fn, "extend": programs._extend_fn}

    for kind, apart in apart_args.items():
        packed = np.concatenate([np.ravel(a) for a in apart])
        t0 = time.perf_counter()
        compiled = jax.jit(fns[kind], donate_argnums=1).lower(
            params, programs.cache, *apart).compile()
        say(program=kind, compiled_apart_s=round(time.perf_counter() - t0, 1),
            host_arguments=len(apart), packed_int32s=int(packed.size))

        def call_apart():
            programs.cache, h, _ = compiled(params, programs.cache, *apart)
            return h

        def call_packed(args=packed):
            return programs._call(kind, args)[0]

        def timed(call, before=lambda: ()):
            took, whole = [], []
            for _ in range(rounds + 3):
                extra = before()
                t = time.perf_counter()
                h = call(*extra)
                took.append(time.perf_counter() - t)
                jax.block_until_ready(h)
                whole.append(time.perf_counter() - t)
            took, whole = sorted(took[3:]), sorted(whole[3:])
            return {"return_ms_p50": round(1e3 * took[len(took) // 2], 4),
                    "return_ms_p90": round(1e3 * took[len(took) * 9 // 10],
                                           4),
                    "ready_ms_p50": round(1e3 * whole[len(whole) // 2], 4)}

        def on_device():
            return (jax.block_until_ready(jax.device_put(packed)),)

        def back_to_back(call, calls=50):
            """ms a call when the next is launched while the last runs: the
            program's time on the device (each takes the last one's
            cache), the launch hidden behind it."""
            jax.block_until_ready(call())
            t = time.perf_counter()
            for _ in range(calls):
                h = call()
            jax.block_until_ready(h)
            return round(1e3 * (time.perf_counter() - t) / calls, 4)

        say(program=kind, back_to_back_ms={
            "apart": back_to_back(call_apart),
            "packed": back_to_back(call_packed)})

        for threads_kind, start in (("none", None), ("8_idle", idle_handlers),
                                    ("8_busy", busy_handlers)):
            stop = threading.Event()
            threads, peers = start(8, stop) if start else ([], [])
            try:
                for form, call, before in (
                        ("apart", call_apart, lambda: ()),
                        ("packed", call_packed, lambda: ()),
                        ("on_device", call_packed, on_device)):
                    say(program=kind, threads=threads_kind, form=form,
                        **timed(call, before))
            finally:
                stop.set()
                for peer in peers:
                    peer.close()
                for t in threads:
                    t.join(timeout=5)
    say(done=True, totals=np.asarray(programs.totals).tolist())


if __name__ == "__main__":
    main()
