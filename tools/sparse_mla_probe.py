"""What a chunk's attention costs under a learned index, by form and by offset
(PERF.md section 5): ONE latent-
attention mixer with an index at the widths of ``benchmarks/configs/glm-5.json``
(seeded bfloat16 weights, a cache of two slots of 33,792 positions filled with
noise), called alone, no server, no stack around it:

* ``dense``: the mixer WITHOUT its index (every cached block expanded and
  attended: what the selection is held against, not a path of the stack);
* ``masked``: :func:`ops.mla.prefill_chunk_indexed`: scored, each row's set
  by bisection, the block walk under each row's mask (a third form, every
  scored chunk's rows over ``top_k``-gathered latents, read 44.5 ms at every
  offset where this one reads 3.0-19.5 and went: PERF.md section 5, PR 43);

each at the chunk offsets given (``--offsets``, default 2048,8192,32256), the
device's time a call (fifty calls follow each other unwaited). Beside them the
two selections alone on ``[512, W]`` float32 scores (``topk_mask``'s bisection
against ``jax.lax.top_k``) and the extension batch of 4 x 4 rows, absorbed
walk (no index) against scored and gathered, at the same reaches.

One JSON line a reading, the log in ``chiprun_out/sparse_mla_probe.log``:

    python3 tools/sparse_mla_probe.py        (``--tiny``: on the CPU)
"""

import argparse
import dataclasses
import json
import os
import sys
import time

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHECKOUT)

ROUNDS = 50


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--offsets", default="")
    args = ap.parse_args()
    if args.tiny:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops import mla

    if args.tiny:
        dims = mla.MLADims(
            dim=64, heads=4, d_nope=24, d_rope=16, d_v=32, q_rank=32,
            kv_rank=24, rope_theta=1e6, eps=1e-5, scale_q=False,
            scale_kv=False, index_heads=4, index_dim=32, index_topk=16)
        chunk, capacity, dtype, rounds = 16, 192, jnp.float32, 3
        offsets = [16, 64, 176]
    else:
        with open(os.path.join(CHECKOUT, "benchmarks", "configs",
                               "glm-5.json")) as f:
            cfg = json.load(f)
        sys.path.insert(0, os.path.join(CHECKOUT, "benchmarks", "models"))
        import glmrec

        dims = glmrec.stack_spec(cfg).mla
        chunk, capacity = (int(cfg["serve"][k]) for k in ("chunk",
                                                           "capacity"))
        dtype, rounds = jnp.bfloat16, ROUNDS
        offsets = [2048, 8192, 32256]
    if args.offsets:
        offsets = [int(o) for o in args.offsets.split(",")]
    P = capacity + chunk
    os.makedirs(os.path.join(CHECKOUT, "chiprun_out"), exist_ok=True)
    log = open(os.path.join(CHECKOUT, "chiprun_out", "sparse_mla_probe.log"),
               "a")

    def say(**reading):
        line = json.dumps({"device": jax.devices()[0].device_kind,
                           **reading})
        print(line, flush=True)
        log.write(line + "\n")
        log.flush()

    key = jax.random.PRNGKey(0)
    p = mla.init(key, dims, dtype)
    noise = jax.jit(lambda k, shape: jax.random.normal(
        k, shape, jnp.float32).astype(dtype), static_argnums=1)
    cache = {"latent": noise(jax.random.fold_in(key, 1),
                             (2, P, mla.cache_width(dims))),
             "index_k": noise(jax.random.fold_in(key, 2),
                              (2, P, dims.index_dim))}
    x = noise(jax.random.fold_in(key, 3), (chunk, dims.dim)).astype(
        jnp.float32)

    def timed(fn, *args):
        """ms a call of the device's time: ``rounds`` calls unwaited."""
        out = fn(*args)
        jax.block_until_ready(out)
        t = time.perf_counter()
        for _ in range(rounds):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t) * 1e3 / rounds

    forms = {
        "dense": dataclasses.replace(dims, index_heads=0, index_topk=0),
        "masked": dims}
    for name, d in forms.items():
        if d.has_index:
            fn = jax.jit(lambda x, at, c, d=d: mla.prefill_chunk_indexed(
                p, d, x, at, c, 1, chunk)[0])
            held = cache
        else:
            fn = jax.jit(lambda x, at, c, d=d: mla.prefill_chunk(
                p, d, x, at, c, 1, chunk)[0])
            held = cache["latent"]
        for at in offsets:
            say(what="prefill_chunk", form=name, offset=at,
                ms=timed(fn, x, jnp.int32(at), held))

    select = {"topk_mask": jax.jit(
        lambda s: mla.topk_mask(s, dims.index_topk)),
              "lax.top_k": jax.jit(
        lambda s: jax.lax.top_k(s, dims.index_topk)[1])}
    for W in sorted({min(P, -(-P // f // chunk) * chunk)
                     for f in (8, 4, 2, 1)}):
        scores = jax.random.normal(jax.random.fold_in(key, W), (chunk, W),
                                   jnp.float32)
        for name, fn in select.items():
            say(what="select", form=name, width=W, ms=timed(fn, scores))

    B, S = 4, 4
    xs = noise(jax.random.fold_in(key, 4), (B, S, dims.dim)).astype(
        jnp.float32)
    slots = jnp.array([0, 1, 0, 1], jnp.int32)
    walk = jax.jit(lambda x, pos, nb: mla.extend(
        p, forms["dense"], x, pos, cache["latent"], slots, nb, chunk)[0])
    pick = jax.jit(lambda x, pos, nb: mla.extend_indexed(
        p, dims, x, pos, cache, slots, nb, chunk)[0])
    for at in offsets:
        pos = at + jnp.arange(S, dtype=jnp.int32)[None] + jnp.zeros(
            (B, 1), jnp.int32)
        nb = jnp.int32(-(-(at + S) // chunk))
        say(what="extend", form="walk", reach=at, ms=timed(walk, xs, pos, nb))
        say(what="extend", form="indexed", reach=at,
            ms=timed(pick, xs, pos, nb))
    return 0


if __name__ == "__main__":
    sys.exit(main())
