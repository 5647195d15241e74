"""How a lone search's results come back, four forms over the same kernel
call on the chip: two ``np.asarray`` one after the other (what the program
did before ISSUE 30), ``jax.device_get`` of the pair (what
``ops/topk._fetch`` does), scores and ids packed into one int32 array with
the kernel's ``merged`` count still an output, and the same pack with
``merged`` left out of the compiled function's outputs. The table has the
shape of ``configs/als-amazon14.json`` (``n_items`` x ``rank``, float32,
seeded on the device in the kernel's layout); a search is the program's own
``_prepare_score_inputs`` on host vectors, the compiled call, the fetch.
Nothing else runs beside it. Run through the chip tool; one JSON line a
batch size, the log in ``chiprun_out/``:

    python3 benchmarks/tools/fetch_probe.py

ISSUE 30 asked the builder to say which fetch a ``serve-c1`` run prefers;
the readings are in PERF.md (Findings, PR 30).
"""

import json
import os
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, CHECKOUT)

ROUNDS, SEARCHES = 4, 150


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from predictionio_tpu.ops.pallas import topk_dot as tkd
    from predictionio_tpu.ops.topk import _prepare_score_inputs

    with open(os.path.join(CHECKOUT, "benchmarks", "configs",
                           "als-amazon14.json")) as f:
        cfg = json.load(f)
    n, D = int(cfg["n_items"]), int(cfg["rank"])
    table = jax.block_until_ready(jax.random.normal(
        jax.random.PRNGKey(30), tkd.table_shape(D, n), jnp.float32))
    device = jax.devices()[0]
    out_dir = os.path.join(CHECKOUT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    log = open(os.path.join(out_dir, "fetch_probe.log"), "w")

    def say(**line):
        text = json.dumps(line)
        print(text, flush=True)
        log.write(text + "\n")
        log.flush()

    say(device=device.device_kind, platform=device.platform, n_items=n,
        rank=D, rounds=ROUNDS, searches=SEARCHES)
    rng = np.random.default_rng(30)
    for B in (1, 16):
        vecs = rng.standard_normal((B, D)).astype(np.float32)
        q, excl, k, k_bucket, _ = _prepare_score_inputs(vecs, 10, None, n, 64)
        fn = tkd.make_topk_dot(n, D, q.shape[0], k_bucket, excl.shape[1])

        def pack(s, i):
            return jnp.concatenate(
                [jax.lax.bitcast_convert_type(s, jnp.int32), i], axis=1)

        @jax.jit
        def packed_merged(q, items, excl):
            s, i, merged = fn(q, items, excl)
            return pack(s, i), merged

        @jax.jit
        def packed_alone(q, items, excl):
            s, i, _ = fn(q, items, excl)
            return pack(s, i)

        def unpack(both):
            return both[:, :k_bucket].view(np.float32), both[:, k_bucket:]

        def two_asarray(q, excl):
            s, i, _ = fn(q, table, excl)
            t = time.perf_counter()
            return t, (np.asarray(s), np.asarray(i))

        def get_pair(q, excl):
            s, i, _ = fn(q, table, excl)
            t = time.perf_counter()
            return t, jax.device_get((s, i))

        def get_packed_merged(q, excl):
            both, _ = packed_merged(q, table, excl)
            t = time.perf_counter()
            return t, unpack(jax.device_get(both))

        def get_packed_alone(q, excl):
            both = packed_alone(q, table, excl)
            t = time.perf_counter()
            return t, unpack(jax.device_get(both))

        forms = {"two_asarray": two_asarray, "device_get_pair": get_pair,
                 "packed_merged_kept": get_packed_merged,
                 "packed_merged_dropped": get_packed_alone}
        want = None
        for form in forms.values():             # compile, warm, agree
            for _ in range(3):
                _, (s, i) = form(q, excl)
            want = want or (s.tobytes(), i.tobytes())
            assert (s.tobytes(), i.tobytes()) == want
        search_ms = {name: [] for name in forms}
        call_ms = {name: [] for name in forms}
        for _ in range(ROUNDS):                 # the forms take turns
            for name, form in forms.items():
                calls = 0.0
                t0 = time.perf_counter()
                for _ in range(SEARCHES):
                    t = time.perf_counter()
                    q, excl, *_ = _prepare_score_inputs(
                        vecs, 10, None, n, 64)
                    launched, _ = form(q, excl)
                    calls += launched - t
                search_ms[name].append(
                    (time.perf_counter() - t0) / SEARCHES * 1e3)
                call_ms[name].append(calls / SEARCHES * 1e3)
        line = {"B": B, "k_bucket": k_bucket}
        for name in forms:
            line[name + "_ms"] = round(statistics.median(search_ms[name]), 4)
            line[name + "_rounds_ms"] = [round(v, 4)
                                         for v in search_ms[name]]
            # prepare + the compiled call returning, before the fetch
            line[name + "_call_ms"] = round(
                statistics.median(call_ms[name]), 4)
        say(**line)
    log.close()


if __name__ == "__main__":
    main()
