"""What a prefill chunk's latent attention costs on the chip, by form and by
offset (PERF.md section 5): ONE latent-attention mixer at the widths of
``benchmarks/configs/{glm-5,ax-k1,longcat-flash-chat}.json`` (seeded bfloat16
weights, a cache of two slots of the configuration's capacity filled with
noise), ``ops/mla.prefill_chunk`` called alone, no server and no stack around
it, in two forms:

* ``kernel``: as the stack runs it, the walk over the slot's blocks in ONE
  kernel (``ops/pallas/chunk_attend.py``);
* ``xla``: the same function with ``ops/mla.attend_blocks`` in the kernel's
  place: the ``fori_loop`` of XLA's own fusions that the kernel replaced (the
  tests' reference; what the parent ran);

each at the chunk offsets given (default :data:`OFFSETS`: 0, 2,048, 8,192
and the last chunk of the cell's longest history: 32,256, 24,064 for
``ax-k1``, 5,632 for ``longcat-flash-chat``), GLM-5's with its index (scored and masked past 2,048
positions) and without (``dense``: the ablation's stack, every cached position
attended). A reading is ``ROUNDS`` calls unwaited under the profiler: the
host's ms a call, and the device's ms a call by instruction name (the kernel's
own, and the largest of the rest).

One JSON line a reading, the log in ``chiprun_out/chunk_attend_probe.log``:

    python3 tools/chunk_attend_probe.py     (``--tiny``: on the CPU, where the
                                             kernel runs under the interpreter
                                             and no device time is read)
"""

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import tempfile
import time

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHECKOUT)

ROUNDS = 20

#: configuration -> the benchmark's builder that reads its widths
BUILDERS = {"glm-5": "glmrec", "ax-k1": "axkrec",
            "longcat-flash-chat": "seqrec"}

#: configuration -> chunk offsets: none, two in between, its cell's last
OFFSETS = {"glm-5": (0, 2048, 8192, 32256), "ax-k1": (0, 2048, 8192, 24064),
           "longcat-flash-chat": (0, 2048, 5632)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--configs", default=",".join(BUILDERS))
    ap.add_argument("--offsets", default="",
                    help="comma-separated, in place of each configuration's")
    ap.add_argument("--forms", default="kernel,xla")
    args = ap.parse_args()
    if args.tiny:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.obs import profiler
    from predictionio_tpu.ops import mla

    os.makedirs(os.path.join(CHECKOUT, "chiprun_out"), exist_ok=True)
    log = open(os.path.join(CHECKOUT, "chiprun_out",
                            "chunk_attend_probe.log"), "a")

    def say(**reading):
        line = json.dumps({"device": jax.devices()[0].device_kind,
                           **reading})
        print(line, flush=True)
        log.write(line + "\n")
        log.flush()

    def sized(name):
        """``(dims, chunk, positions a slot holds, dtype, rounds)``."""
        if args.tiny:
            index = {"index_heads": 4, "index_dim": 32, "index_topk": 32} \
                if name == "glm-5" else {}
            wide = name != "glm-5"
            return mla.MLADims(
                dim=64, heads=4, d_nope=16 if wide else 24, d_rope=8,
                d_v=16 if wide else 32, q_rank=32, kv_rank=32,
                scale_q=False, scale_kv=False, **index), 16, 192, \
                jnp.float32, 2
        with open(os.path.join(CHECKOUT, "benchmarks", "configs",
                               name + ".json")) as f:
            cfg = json.load(f)
        sys.path.insert(0, os.path.join(CHECKOUT, "benchmarks", "models"))
        dims = __import__(BUILDERS[name]).stack_spec(cfg).mla
        chunk, capacity = (int(cfg["serve"][k]) for k in ("chunk",
                                                           "capacity"))
        return dims, chunk, capacity + chunk, jnp.bfloat16, ROUNDS

    @contextlib.contextmanager
    def walking(form):
        """``ops/mla.prefill_chunk``'s walk in ``form``, while it is traced."""
        held = mla.attend_kernel
        if form == "xla":
            mla.attend_kernel = mla.attend_blocks
        try:
            yield
        finally:
            mla.attend_kernel = held

    def readings(name, variant, d, chunk, P, dtype, rounds, forms, offsets):
        key = jax.random.PRNGKey(0)
        p = mla.init(key, d, dtype)
        noise = jax.jit(lambda k, shape: jax.random.normal(
            k, shape, jnp.float32).astype(dtype), static_argnums=1)
        cache = noise(jax.random.fold_in(key, 1),
                      (2, P, mla.cache_width(d)))
        if d.has_index:
            cache = {"latent": cache,
                     "index_k": noise(jax.random.fold_in(key, 2),
                                      (2, P, d.index_dim))}
        x = noise(jax.random.fold_in(key, 3), (chunk, d.dim)).astype(
            jnp.float32)
        for form in forms:
            # as the stack's chunk program holds it: ONE program for every
            # offset, the cache donated and handed back
            with walking(form):
                fn = jax.jit(lambda p, x, at, c: mla.prefill_chunk(
                    p, d, x, at, c, 1, chunk, "probe.mla_a")[:2],
                    donate_argnums=3).lower(
                        p, x, jnp.int32(0), cache).compile()
            for at in offsets:
                cache = reading(fn, p, x, jnp.int32(at), cache, rounds, dict(
                    config=name, variant=variant, form=form, offset=at,
                    blocks=-(-(at + chunk) // chunk)))

    def reading(fn, p, x, at, cache, rounds, about):
        """``rounds`` calls unwaited under the profiler; the cache as the
        last call left it."""
        out, cache = fn(p, x, at, cache)
        jax.block_until_ready(out)
        with tempfile.TemporaryDirectory() as tmp:
            with profiler.trace_capture(tmp):
                t = time.perf_counter()
                for _ in range(rounds):
                    out, cache = fn(p, x, at, cache)
                jax.block_until_ready(out)
                call_ms = (time.perf_counter() - t) * 1e3 / rounds
            groups = profiler.parse_xplane(tmp).get("by_category", {})
        by_name = sorted(((g["time_sec"] * 1e3 / rounds, k)
                          for k, g in groups.items()), reverse=True)
        kernel = sum(ms for ms, k in by_name if "chunk_attend" in k)
        say(what="prefill_chunk", **about, call_ms=call_ms,
            device_ms=sum(ms for ms, _ in by_name) or None,
            chunk_attend_ms=kernel or None,
            chunk_attend_ms_a_block=(kernel / about["blocks"] if kernel
                                     else None),
            largest={k: round(ms, 4) for ms, k in by_name[:6]} or None)
        return cache

    for name in args.configs.split(","):
        dims, chunk, P, dtype, rounds = sized(name)
        offsets = ([int(o) for o in args.offsets.split(",")] if args.offsets
                   else [0, 64, P - 2 * chunk] if args.tiny
                   else OFFSETS[name])
        variants = {"indexed" if dims.has_index else "dense": dims}
        if dims.has_index:
            variants["dense"] = dataclasses.replace(
                dims, index_heads=0, index_topk=0)
        for variant, d in variants.items():
            readings(name, variant, d, chunk, P, dtype, rounds,
                     args.forms.split(","), offsets)
    return 0


if __name__ == "__main__":
    sys.exit(main())
