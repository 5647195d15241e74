"""Two readings on the chip at granite-4.0-h-small's widths, taken before its
cell was run (PERF.md, Findings, PR 34):

* the expert layer of the extension program (64 token rows, 36 experts of
  4096 x 768 held of 72, top-10) in the forms of ``moe_small_probe.py``: the
  sorted-tile loop and the streaming kernel, whose whole-expert step is 37.7
  of its 40 MB of VMEM here, with 8, 24 and 36 touched experts;
* one Mamba-2 mixer (``ops/ssm.py``): a chunk of 512 positions and an
  extension of 16 rows of 4 (4 and 16 of them real), with the scan's own
  products at the highest precision (as the program runs them) and at the
  default.

Seeded bfloat16 weights; each runs ``LAYERS`` times a call. One JSON line a
case, the log in ``chiprun_out/hyb_probe.log``:

    python3 benchmarks/tools/hyb_probe.py        (``--tiny``: on the CPU)
"""

import dataclasses
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, CHECKOUT)
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "models"))

LAYERS = 9


def main() -> None:
    import jax
    import jax.numpy as jnp

    import hybrec
    import moe_small_probe as small
    from predictionio_tpu.ops import moe as moe_ops
    from predictionio_tpu.ops import ssm as ssm_ops
    from predictionio_tpu.ops.pallas import expert_stream

    tiny = "--tiny" in sys.argv[1:]
    with open(os.path.join(CHECKOUT, "benchmarks", "configs",
                           "granite-4.0-h-small.json")) as f:
        spec = hybrec.stack_spec(json.load(f))
    moe, ssm = dataclasses.replace(spec.moe, shared_dim=0), spec.ssm
    if tiny:
        moe = dataclasses.replace(moe, dim=128, expert_dim=256)
        ssm = dataclasses.replace(ssm, dim=128, heads=4, head_dim=16,
                                  d_state=16, chunk=32)
    out_dir = os.path.join(CHECKOUT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    log = open(os.path.join(out_dir, "hyb_probe.log"), "w")

    def say(**line):
        text = json.dumps(line)
        print(text, flush=True)
        log.write(text + "\n")
        log.flush()

    device = jax.devices()[0]
    say(device=device.device_kind, platform=device.platform, layers=LAYERS,
        tiny=tiny)
    rounds = 1 if tiny else 20

    def timed(fn, *args):
        fn = jax.jit(fn)
        jax.block_until_ready(fn(*args))
        t = time.perf_counter()
        for _ in range(rounds):
            last = fn(*args)
        jax.block_until_ready(last)
        return (time.perf_counter() - t) / rounds / LAYERS * 1e6

    # -- the expert layer ------------------------------------------------
    p = moe_ops.init(jax.random.PRNGKey(34), moe, jnp.bfloat16)
    x = jax.random.normal(jax.random.PRNGKey(1), (64, moe.dim), jnp.float32)
    say(case="experts", dims=str(moe),
        chunk=expert_stream.chunk_of(moe.dim, moe.expert_dim, 2))
    for touched in (8, 24, 36):
        idx, gates, valid = small.picks(moe, 64, touched, 16)
        args = (p, x, jnp.asarray(idx), jnp.asarray(gates),
                jnp.asarray(valid))
        line = {"case": "experts", "touched": touched}
        for name, form in (("sorted_tiles", moe_ops.experts_sorted),
                           ("kernel", moe_ops.experts_streamed)):
            def layers(p, x, idx, gates, valid, _form=form):
                y = jnp.zeros_like(x)
                for _ in range(LAYERS):
                    y = y + _form(p, moe, x + 1e-3 * y, idx, gates, valid)[0]
                return y

            us = timed(layers, *args)
            line[name + "_us_per_expert"] = round(us / touched, 2)
        say(**line)

    # -- one Mamba-2 mixer -----------------------------------------------
    p = ssm_ops.init(jax.random.PRNGKey(35), ssm, jnp.bfloat16)
    state = ssm_ops.init_state(ssm, 33, jnp.bfloat16)
    chunk = jax.random.normal(jax.random.PRNGKey(2), (512, ssm.dim))
    rows = jax.random.normal(jax.random.PRNGKey(3), (16, 4, ssm.dim))
    highest = ssm_ops._HIGHEST
    for name, precision in (("highest", highest), ("default", None)):
        ssm_ops._HIGHEST = precision

        def prefill(p, a, state):
            for i in range(LAYERS):
                out, state = ssm_ops.prefill_chunk(
                    p, ssm, a, 500, 512 * i, state, 3)
                a = a + 1e-3 * out
            return a, state

        def extend(p, a, n_new, state):
            for _ in range(LAYERS):
                out, state = ssm_ops.extend(
                    p, ssm, a, n_new, jnp.full((16,), 700), state,
                    jnp.arange(16))
                a = a + 1e-3 * out
            return a, state

        line = {"case": "ssm", "scan_precision": name,
                "prefill_chunk_us": round(timed(prefill, p, chunk, state), 1)}
        for real in (4, 16):
            n_new = jnp.where(jnp.arange(16) < real, 3, 0)
            line[f"extend_{real}_rows_us"] = round(
                timed(extend, p, rows, n_new, state), 1)
        say(**line)
    ssm_ops._HIGHEST = highest
    log.close()


if __name__ == "__main__":
    main()
