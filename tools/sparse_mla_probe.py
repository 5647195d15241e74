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
against ``jax.lax.top_k``) and the extension batch of 4 x 4 rows at the same
reaches: ``walk`` (:func:`ops.mla.extend`, no index: every cached latent of
the slot), ``indexed`` (:func:`ops.mla.extend_indexed`: scored, each row's set
by bisection, the same walk under each row's mask), ``gathered`` (the form
``indexed`` replaced at PR 44, kept HERE as its baseline: ``jax.lax.top_k``
and a gather of each row's 2,048 latents), and ``indexed`` with its loops'
step set by hand (``--steps``, in blocks of the chunk: what
``ops.mla._walk_block`` was chosen from) beside ``sets`` (the scorer and the
bisection alone, ``ops.mla.extension_sets``).

One JSON line a reading, the log in ``chiprun_out/sparse_mla_probe.log``:

    python3 tools/sparse_mla_probe.py        (``--tiny``: on the CPU)
"""

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHECKOUT)

ROUNDS = 50


def extend_gathered(mla, p, d, x, pos, cache, slots, n_blocks, block):
    """``ops.mla.extend_indexed`` as PR 43 had it, this probe's baseline
    only: scores block by block, ``jax.lax.top_k``, each row's selected
    latents GATHERED, one softmax over them in the absorbed form (no cache
    write: the probe times reads)."""
    import jax
    import jax.numpy as jnp

    B, S, _ = x.shape
    P = cache["latent"].shape[1]
    cq = mla.compress_q(p, d, x)
    qi, _, w = mla.project_index(p, d, x, cq, pos)
    qn, qr, _ = mla.project(p, d, x, pos, cq)
    lat_c, idx_c = cache["latent"], cache["index_k"]
    qi_c = qi.astype(idx_c.dtype)

    def score_block(j, scores):
        kb = jax.vmap(lambda s: jax.lax.dynamic_slice(
            idx_c, (s, j * block, 0), (1, block, idx_c.shape[-1]))[0])(slots)
        k_pos = j * block + jnp.arange(block)
        s = jnp.where(pos[..., None] >= k_pos,
                      mla.index_scores(qi_c, w, kb), -jnp.inf)
        return jax.lax.dynamic_update_slice(scores, s, (0, 0, j * block))

    scores = jax.lax.fori_loop(0, n_blocks, score_block,
                               jnp.full((B, S, P), -jnp.inf, jnp.float32))
    vals, idx = jax.lax.top_k(scores, min(d.index_topk, P))
    w_ukv = p["w_ukv"].reshape(d.kv_rank, d.heads, d.d_nope + d.d_v)
    q_abs = jnp.einsum("bshd,chd->bshc", qn.astype(w_ukv.dtype),
                       w_ukv[..., :d.d_nope],
                       preferred_element_type=jnp.float32)
    q = jnp.concatenate([q_abs, qr], axis=-1).astype(lat_c.dtype)
    lat = lat_c[slots[:, None, None], idx]                  # [B, S, K, width]
    s = jnp.einsum("bshc,bskc->bshk", q, lat[..., :d.latent],
                   preferred_element_type=jnp.float32) * d.softmax_scale
    prob = jax.nn.softmax(
        jnp.where((vals > -jnp.inf)[:, :, None], s, -1e30), axis=-1)
    o = jnp.einsum("bshk,bskc->bshc", prob.astype(lat.dtype),
                   lat[..., :d.kv_rank], preferred_element_type=jnp.float32)
    o = jnp.einsum("bshc,chd->bshd", o.astype(w_ukv.dtype),
                   w_ukv[..., d.d_nope:], preferred_element_type=jnp.float32)
    return mla._out(p, d, o)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--offsets", default="")
    ap.add_argument("--what", default="prefill_chunk,select,extend",
                    help="the parts to time (all three take nine minutes "
                         "on the chip, the extension's alone three)")
    ap.add_argument("--steps", default="",
                    help="blocks a step of the indexed extension's loops, "
                         "comma-separated: each timed beside the default")
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
    parts = set(args.what.split(","))
    for name, d in forms.items() if "prefill_chunk" in parts else ():
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
                     for f in (8, 4, 2, 1)} if "select" in parts else ()):
        scores = jax.random.normal(jax.random.fold_in(key, W), (chunk, W),
                                   jnp.float32)
        for name, fn in select.items():
            say(what="select", form=name, width=W, ms=timed(fn, scores))

    if "extend" not in parts:
        return 0
    B, S = 4, 4
    xs = noise(jax.random.fold_in(key, 4), (B, S, dims.dim)).astype(
        jnp.float32)
    slots = jnp.array([0, 1, 0, 1], jnp.int32)
    rows = B * S * dims.heads

    @contextlib.contextmanager
    def allowed(budget):
        """``budget`` bytes of scores a step, while a form is traced."""
        held, mla._WALK_SCORE_BYTES = mla._WALK_SCORE_BYTES, budget
        try:
            yield
        finally:
            mla._WALK_SCORE_BYTES = held

    def stepping(budget):
        """The step ``budget`` allows, the indexed extension under it and
        its sets alone (the scorer and the bisection)."""
        with allowed(budget):
            wide = mla._walk_block(P, chunk, rows)

        def indexed(x, pos, nb):
            with allowed(budget):
                return mla.extend_indexed(
                    p, dims, x, pos, cache, slots, nb, chunk)[0]

        def sets(x, pos, nb):
            cq = mla.compress_q(p, dims, x)
            qi, _, w = mla.project_index(p, dims, x, cq, pos)
            return mla.extension_sets(
                dims, qi.astype(dtype), w, cache["index_k"], slots, pos,
                (nb * chunk + wide - 1) // wide, wide)

        return wide, jax.jit(indexed), jax.jit(sets)

    wide, indexed, sets = stepping(mla._WALK_SCORE_BYTES)
    extensions = {
        "walk": (chunk, jax.jit(lambda x, pos, nb: mla.extend(
            p, forms["dense"], x, pos, cache["latent"], slots, nb,
            chunk)[0])),
        "gathered": (chunk, jax.jit(lambda x, pos, nb: extend_gathered(
            mla, p, dims, x, pos, cache, slots, nb, chunk))),
        "indexed": (wide, indexed), "sets": (wide, sets)}
    for m in (int(m) for m in args.steps.split(",") if m):
        wide, indexed, sets = stepping(4 * rows * chunk * m)
        extensions.update({f"indexed@{wide}": (wide, indexed),
                           f"sets@{wide}": (wide, sets)})
    for at in offsets:
        pos = at + jnp.arange(S, dtype=jnp.int32)[None] + jnp.zeros(
            (B, 1), jnp.int32)
        nb = jnp.int32(-(-(at + S) // chunk))
        for name, (step, fn) in extensions.items():
            say(what="extend", form=name, reach=at, step=step,
                ms=timed(fn, xs, pos, nb))
    return 0


if __name__ == "__main__":
    sys.exit(main())
