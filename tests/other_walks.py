"""The attention paths that ``ops/pallas/chunk_attend.py`` does NOT serve, each
traced at a small size: ``ops/mla.extend`` (absorbed form, with and without
an index), ``ops/gqa._attend`` (a chunk's and a block-diffusion forward's walk
over keys and values) and the window walk (a chunk's and an extension's over a
ring). ``jaxprs()`` gives each one's jaxpr as text;
``tests/test_pallas_kernels.py`` holds them to the text they had at the commit
before the kernel came (PR 50's parent, ``1340c6b``; the block-diffusion
forward's at PR 56's, ``1c1a441``, when ``ops/pallas/span_walk.py`` took a
CAUSAL stack's extension out of that walk), by its SHA-256: their compiled
programs do not change.

To print a tree's own: ``PYTHONPATH=<checkout> python3 tests/other_walks.py``.
"""

import hashlib

import jax
import jax.numpy as jnp


def jaxprs() -> dict:
    from predictionio_tpu.ops import gqa, mla

    out = {}
    pos = jnp.array([[40, 41, 42, 43], [4, 5, 6, 7]], jnp.int32)
    slots, x = jnp.array([1, 2]), jnp.zeros((2, 4, 64), jnp.float32)
    for name, index in (("mla.extend", {}), ("mla.extend.index", dict(
            index_heads=4, index_dim=32, index_topk=24))):
        d = mla.MLADims(dim=64, heads=4, d_nope=24, d_rope=8, d_v=32,
                        q_rank=32, kv_rank=32, **index)
        p = mla.init(jax.random.PRNGKey(0), d, jnp.float32)
        cache = mla.init_cache(d, 3, 64, jnp.float32)
        out[name] = jax.make_jaxpr(
            lambda p, x, pos, cache, slots, nb, d=d: mla.extend(
                p, d, x, pos, cache, slots, nb, 16))(
                    p, x, pos, cache, slots, jnp.int32(3))
    same = dict(dim=64, heads=8, head_dim=24, block_len=1, eps=1e-5,
                qk_norm=False, v_head_dim=16, rope_dims=8, value_scale=0.707)
    full = gqa.GQADims(kv_heads=2, rope_theta=1e7, **same)
    p = gqa.init(jax.random.PRNGKey(0), full)
    cache = jnp.zeros((3, 64, full.cache_width), jnp.float32)
    out["gqa.prefill_chunk"] = jax.make_jaxpr(
        lambda p, x, at, cache: gqa.prefill_chunk(
            p, full, x, at, cache, 1, 16))(
                p, x[0].repeat(4, axis=0), jnp.int32(24), cache)
    # the block-diffusion forward: rows of a whole block that see each other
    blocks = gqa.GQADims(dim=64, heads=8, kv_heads=2, head_dim=16,
                         block_len=4, rope_theta=1e6, eps=1e-6)
    out["gqa.block_step"] = jax.make_jaxpr(
        lambda p, x, pos, cache, slots, nb: gqa.block_step(
            p, blocks, x, pos, cache, slots, nb, 16))(
                gqa.init(jax.random.PRNGKey(0), blocks), x, pos,
                jnp.zeros((3, 64, blocks.cache_width), jnp.float32), slots,
                jnp.int32(3))
    win = gqa.GQADims(kv_heads=4, rope_theta=1e4, window=8, sink=True, **same)
    p = gqa.init(jax.random.PRNGKey(0), win)
    ring = jnp.zeros((3, gqa.ring_len(8, 16), win.cache_width), jnp.float32)
    out["gqa.window_prefill_chunk"] = jax.make_jaxpr(
        lambda p, x, n, at, ring: gqa.window_prefill_chunk(
            p, win, x, n, at, ring, 1))(
                p, x[0].repeat(4, axis=0), jnp.int32(11), jnp.int32(24), ring)
    out["gqa.window_extend"] = jax.make_jaxpr(
        lambda p, x, n, pos, ring, slots: gqa.window_extend(
            p, win, x, n, pos, ring, slots))(
                p, x, jnp.array([4, 2]), pos, ring, slots)
    return {name: str(j) for name, j in out.items()}


def digests() -> dict:
    return {name: hashlib.sha256(text.encode()).hexdigest()
            for name, text in jaxprs().items()}


if __name__ == "__main__":
    for name, digest in digests().items():
        print(f'    "{name}": "{digest}",')
