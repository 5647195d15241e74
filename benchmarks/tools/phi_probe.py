"""One Mamba-1 layer's scan ALONE on the chip at the cell's shapes (a
builder's tool; no run of the benchmark runs it): ``ops/mamba1.scan`` over a
chunk of 512 positions (d_inner 5,120, d_state 16) by the ``selective_scan``
kernel and by XLA's ``lax.scan`` over ``scan_steps``' step at several
``unroll``s (``scan_steps`` itself holds the fastest of them), the
same recurrence as ``jax.lax.associative_scan`` over materialised ``[512, 16,
5120]`` decays, each as 16 scans chained in ONE program (a call's launch is
0.4 ms, more than a scan), and the whole mixer's chunk and extension paths,
each timed over ``--runs`` calls ended by ``block_until_ready``.

    python3 benchmarks/tools/phi_probe.py            # on the chip
    python3 benchmarks/tools/phi_probe.py --tiny     # control flow, on the CPU
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def timed(fn, *args, runs):
    import jax

    jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(runs):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / runs * 1e3


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--runs", type=int, default=20)
    args = ap.parse_args()
    if args.tiny:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops import mamba1 as m1

    T, D, inner, N, R = (16, 32, 64, 4, 2) if args.tiny else (
        512, 2560, 5120, 16, 160)
    dims = m1.Mamba1Dims(dim=D, d_inner=inner, d_state=N, dt_rank=R)
    dtype = jnp.float32 if args.tiny else jnp.bfloat16
    p = m1.init(jax.random.PRNGKey(0), dims, dtype)
    key = jax.random.split(jax.random.PRNGKey(1), 6)
    x = jax.random.normal(key[0], (T, inner), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(key[1], (T, inner)) - 4.0)
    B, C = (jax.random.normal(k, (T, N), jnp.float32) for k in key[2:4])
    a_t = m1._a(p)
    s0 = jnp.zeros((N, inner), jnp.float32)
    d0 = jax.devices()[0]
    print(json.dumps({"platform": d0.platform, "kind": d0.device_kind,
                      "T": T, "d_inner": inner, "d_state": N}), flush=True)
    want = m1.scan_steps(x, dt, a_t, B, C, s0)[0]
    reps = 2 if args.tiny else 16

    def chained(form):
        """``reps`` scans in ONE program, each from the state the last one
        left and each one's ``y`` summed: device time, not a call's launch
        (0.4 ms a call here: every fast form read alike when timed a call)."""
        def run(x, dt, a_t, B, C, s0):
            def one(_, carry):
                s, acc = carry
                y, s = form(x, dt, a_t, B, C, s)
                return s, acc + y.sum()
            return jax.lax.fori_loop(0, reps, one, (s0, jnp.float32(0)))
        return jax.jit(run)

    def report(name, form, **more):
        ms = timed(chained(form), x, dt, a_t, B, C, s0, runs=args.runs) / reps
        err = float(jnp.abs(jax.jit(form)(x, dt, a_t, B, C, s0)[0]
                            - want).max())
        print(json.dumps({"form": name, **more, "ms": ms, "err": err}),
              flush=True)

    if not args.tiny:
        from predictionio_tpu.ops.pallas import selective_scan as kernel

        report("selective_scan kernel", kernel.selective_scan)
    def xla_loop(unroll):
        def form(x, dt, a_t, B, C, s0):
            s, y = jax.lax.scan(lambda s, args: m1._step(s, a_t, *args), s0,
                                (x, dt, B, C), unroll=unroll)
            return y, s
        return form

    for unroll in (1, 2, 4, 8, 16, 32):
        report("lax.scan", xla_loop(min(unroll, T)), unroll=unroll)

    def associative(x, dt, a_t, B, C, s0):
        decay = jnp.exp(dt[:, None, :] * a_t)                 # [T, N, inner]
        add = B[:, :, None] * (dt * x)[:, None, :]
        add = add.at[0].add(decay[0] * s0)

        def combine(left, right):
            return left[0] * right[0], right[0] * left[1] + right[1]

        _, s = jax.lax.associative_scan(combine, (decay, add))
        return (s * C[:, :, None]).sum(axis=1), s[-1]

    report("associative_scan", associative)

    a = jax.random.normal(key[4], (T, D), jnp.float32)
    state = m1.init_state(dims, 3, dtype)
    chunk = jax.jit(lambda p, a, state: m1.prefill_chunk(
        p, dims, a, jnp.int32(T), jnp.int32(T), state, jnp.int32(1)))
    print(json.dumps({"form": "mixer chunk", "ms": timed(
        chunk, p, a, state, runs=args.runs)}), flush=True)
    rows = jax.random.normal(key[5], (8, 4, D), jnp.float32)
    extend = jax.jit(lambda p, rows, state: m1.extend(
        p, dims, rows, jnp.full((8,), 3), jnp.full((8,), T), state,
        jnp.asarray([0, 1, 2, 2, 2, 2, 2, 2])))
    print(json.dumps({"form": "mixer extension", "ms": timed(
        extend, p, rows, state, runs=args.runs)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
