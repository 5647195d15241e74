"""One window mixer, one full mixer and one expert layer of ``mimo-v2.5``
ALONE on the chip, at the published widths and the cell's serve shapes (25
slots, spans of 25,600, rings of 640, chunks of 512, extension batches of 8
x 4): what ISSUE 49 asked the builder to time BEFORE the cell's first run, to
write down what the cell should read (``PERF.md`` section 6). Each mixer
through its cached path (``ops/gqa.py``: ``window_prefill_chunk`` /
``window_extend`` over a ring, ``prefill_chunk`` / ``extend`` over a span) at
a chunk's offset or an extension's reach of 0 / 128, 4,096 and 24,064 /
24,600, and the window layer's walk ALSO over a span from block 0 (the
parent's only form: ``prefill_chunk`` with the window as a mask would have
to walk it) for what the ring saves; the expert layer (16 held of 4096 x
2048, top-8 of 256) at a chunk's 512 tokens and an extension batch's 32.
Seeded bfloat16 weights; each runs ``REPEATS`` times a call. Run through the
chip tool; one JSON line a case:

    python3 benchmarks/tools/mimo_probe.py

(``--tiny``: the same cases at small widths, to rehearse the control flow on
the CPU; never a device number.)
"""

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, CHECKOUT)

REPEATS = 10


def timed(fn, *args):
    """Median milliseconds of one application of ``fn`` (a jitted function
    that applies its body ``REPEATS`` times), over five calls."""
    import jax

    jax.block_until_ready(fn(*args))
    took = []
    for _ in range(5):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        took.append((time.perf_counter() - t) * 1e3 / REPEATS)
    return sorted(took)[len(took) // 2]


def timed_over(caches, name, fn, arg):
    """:func:`timed` of a function that is handed ``caches[name]`` donated
    and hands it back first: the cache is threaded, never copied."""
    def call(arg):
        caches[name], acc = fn(caches[name], arg)
        return acc

    return timed(call, arg)


def main() -> int:
    tiny = "--tiny" in sys.argv
    if tiny:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops import gqa, moe

    d0 = jax.devices()[0]
    print(json.dumps({"device": d0.platform, "kind": d0.device_kind}),
          flush=True)
    if tiny:
        D, H, dk, dv, rot, W, C, cap, E, held = 64, 8, 24, 16, 8, 8, 16, 96, 32, 2
        slots, offsets, n_routed = 3, (0, 32, 80), 32
    else:
        D, H, dk, dv, rot, W, C, cap, E, held = (4096, 64, 192, 128, 64, 128,
                                                 512, 25088, 2048, 16)
        slots, offsets, n_routed = 25, (0, 4096, 24064), 256
    same = dict(dim=D, heads=H, head_dim=dk, block_len=1, eps=1e-5,
                qk_norm=False, v_head_dim=dv, rope_dims=rot,
                value_scale=0.707)
    full = gqa.GQADims(kv_heads=H // 16 if not tiny else 2, rope_theta=1e7,
                       **same)
    win = gqa.GQADims(kv_heads=H // 8 if not tiny else 4, rope_theta=1e4,
                      window=W, sink=True, **same)
    masked = gqa.GQADims(kv_heads=win.kv_heads, rope_theta=1e4, **same)
    dtype = jnp.float32 if tiny else jnp.bfloat16
    key = jax.random.PRNGKey(0)
    p_full, p_win = gqa.init(key, full, dtype), gqa.init(key, win, dtype)
    R = gqa.ring_len(W, C)
    caches = {"span": jnp.zeros((slots, cap + C, full.cache_width), dtype),
              "wide": jnp.zeros((slots, cap + C, win.cache_width), dtype),
              "ring": jnp.zeros((slots, R, win.cache_width), dtype)}
    x = jax.random.normal(key, (C, D), jnp.float32)
    xe = jax.random.normal(key, (8 if not tiny else 2, 4, D), jnp.float32)
    B = xe.shape[0]

    def repeat(step):
        def run(cache, *args):
            def body(_, carry):
                cache, acc = carry
                out, cache = step(cache, *args)[:2]
                return cache, acc + out.sum()
            return jax.lax.fori_loop(0, REPEATS, body, (cache, 0.0))
        return jax.jit(run, donate_argnums=0)

    pre_full = repeat(lambda c, off: gqa.prefill_chunk(
        p_full, full, x, off, c, 1, C))
    pre_win = repeat(lambda c, off: gqa.window_prefill_chunk(
        p_win, win, x, jnp.int32(C), off, c, 1))
    # the window layer's projections over a SPAN walked from block 0 (its
    # keys' width, no window in the walk): what one cache shape would cost
    pre_span = repeat(lambda c, off: gqa.prefill_chunk(
        p_win, masked, x, off, c, 1, C))
    slots_b = jnp.arange(B, dtype=jnp.int32)
    n_new = jnp.full((B,), 3, jnp.int32)

    def positions(reach):
        return (reach + jnp.arange(4, dtype=jnp.int32))[None].repeat(B, 0)

    ext_full = repeat(lambda c, reach: gqa.extend(
        p_full, full, xe, positions(reach), c, slots_b,
        (reach + 4 + C - 1) // C, C))
    ext_win = repeat(lambda c, reach: gqa.window_extend(
        p_win, win, xe, n_new, positions(reach), c, slots_b))
    ext_span = repeat(lambda c, reach: gqa.extend(
        p_win, masked, xe, positions(reach), c, slots_b,
        (reach + 4 + C - 1) // C, C))
    for off in offsets:
        at = jnp.int32(off)
        for name, fn, cache in (("full", pre_full, "span"),
                                ("window_ring", pre_win, "ring"),
                                ("window_over_a_span", pre_span, "wide")):
            ms = timed_over(caches, cache, fn, at)
            print(json.dumps({"case": "prefill_chunk", "mixer": name,
                              "offset": off, "ms": ms}), flush=True)
        reach = jnp.int32(max(off, W) if off else W)
        for name, fn, cache in (("full", ext_full, "span"),
                                ("window_ring", ext_win, "ring"),
                                ("window_over_a_span", ext_span, "wide")):
            ms = timed_over(caches, cache, fn, reach)
            print(json.dumps({"case": "extend", "mixer": name,
                              "reach": int(reach), "ms": ms}), flush=True)
    dims = moe.MoEDims(dim=D, expert_dim=E, n_routed=n_routed, n_zero=0,
                       top_k=8 if not tiny else 4, scale=1.0, held=(0, held),
                       norm_topk=True, scoring="sigmoid")
    p_moe = moe.init(key, dims, dtype, bias_std=1e-3)
    for T in (C, B * 4):
        xt = jax.random.normal(key, (T, D), jnp.float32)
        valid = jnp.ones(T, bool)

        @jax.jit
        def layer(xt):
            def body(_, acc):
                y, counted = moe.moe(p_moe, dims, xt + acc * 1e-6, valid)
                return acc + y.mean()
            return jax.lax.fori_loop(0, REPEATS, body, 0.0)

        print(json.dumps({"case": "expert_layer", "tokens": T,
                          "ms": timed(layer, xt)}), flush=True)
    dense = jax.jit(lambda w, xt: jax.lax.fori_loop(
        0, REPEATS, lambda _, a: a + moe.swiglu(
            xt + a * 1e-6, w["w_g"], w["w_u"], w["w_d"]).mean(), 0.0))
    F = 16384 if not tiny else 128
    w = {k: (jax.random.normal(key, s, jnp.float32) / 64).astype(dtype)
         for k, s in (("w_g", (D, F)), ("w_u", (D, F)), ("w_d", (F, D)))}
    for T in (C, B * 4):
        xt = jax.random.normal(key, (T, D), jnp.float32)
        print(json.dumps({"case": "dense_ffn", "tokens": T,
                          "ms": timed(dense, w, xt)}), flush=True)
    stats = d0.memory_stats() or {}
    print(json.dumps({"peak_bytes": stats.get("peak_bytes_in_use")}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
