"""The grouped expert product on the chip, two forms over the same sorted
(token, pick) pairs: the program's loop over expert tiles
(``ops/moe.experts_sorted``; also at tiles of 128 and 256 rows) against XLA's
``jax.lax.ragged_dot``. One double-layer's expert layer at the published
widths of ``configs/longcat-flash-chat.json`` (16 experts held of 512 routed
+ 256 zero-compute, top-12), seeded bfloat16 weights. Run through the chip
tool; one JSON line a case, the log in ``chiprun_out/``:

    python3 benchmarks/tools/moe_grouped_probe.py

ISSUE 27 asked the builder to measure both and keep one; the readings are in
PERF.md (Findings, PR 27).
"""

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, CHECKOUT)


def ragged_form(p, dims, x, idx, gates, valid):
    """``experts_sorted``'s answer through ``ragged_dot``: every (token,
    pick) pair sorted by expert — the pairs of absent experts last, outside
    every group — three grouped products, a scatter-add back."""
    import jax
    import jax.numpy as jnp

    e0, n = dims.held
    T, k = idx.shape
    local = idx - e0
    here = (local >= 0) & (local < n) & valid[:, None]
    flat = jnp.where(here, local, n).reshape(-1)
    order = jnp.argsort(flat, stable=True)
    token = (order // k).astype(jnp.int32)
    gate = jnp.where(flat[order] < n, gates.reshape(-1)[order], 0.0)
    counts = jnp.zeros((n + 1,), jnp.int32).at[flat].add(1)[:n]
    rows = x[token].astype(p["w_g"].dtype)

    def grouped(a, w):
        return jax.lax.ragged_dot(a, w, counts,
                                  preferred_element_type=jnp.float32)

    h = jax.nn.silu(grouped(rows, p["w_g"])) * grouped(rows, p["w_u"])
    out = grouped(h.astype(p["w_d"].dtype), p["w_d"])
    y = jnp.zeros((T, dims.dim), jnp.float32).at[token].add(
        out * gate[:, None])
    return y, counts


def main() -> None:
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops import moe as moe_ops

    with open(os.path.join(CHECKOUT, "benchmarks", "configs",
                           "longcat-flash-chat.json")) as f:
        cfg = json.load(f)
    dims = moe_ops.MoEDims(
        dim=int(cfg["hidden_size"]),
        expert_dim=int(cfg["expert_ffn_hidden_size"]),
        n_routed=int(cfg["n_routed_experts_published"]),
        n_zero=int(cfg["zero_expert_num"]), top_k=int(cfg["moe_topk"]),
        scale=float(cfg["routed_scaling_factor"]),
        held=tuple(int(v) for v in cfg["experts_held"]))
    p = moe_ops.init(jax.random.PRNGKey(27), dims, jnp.bfloat16,
                     bias_std=1e-3)
    device = jax.devices()[0]
    out_dir = os.path.join(CHECKOUT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    log = open(os.path.join(out_dir, "moe_grouped_probe.log"), "w")

    def say(**line):
        text = json.dumps(line)
        print(text, flush=True)
        log.write(text + "\n")
        log.flush()

    say(device=device.device_kind, platform=device.platform, dims=str(dims))

    def timed(fn, *args, rounds=30):
        jax.block_until_ready(fn(*args))            # compile, warm
        jax.block_until_ready(fn(*args))
        t = time.perf_counter()
        for _ in range(rounds):
            got = fn(*args)
        jax.block_until_ready(got)
        return (time.perf_counter() - t) / rounds * 1e3, got

    for tokens in (512, 64):
        x = jax.random.normal(jax.random.PRNGKey(tokens),
                              (tokens, dims.dim), jnp.float32)
        valid = jnp.ones(tokens, bool)
        for routing in ("router", "one_expert"):
            q = dict(p)
            if routing == "one_expert":     # every token picks held expert 5
                q["bias"] = p["bias"].at[5].set(10.0)
            idx, gates = jax.jit(
                lambda q, x: moe_ops.route(q, dims, x))(q, x)
            held = int(((idx >= dims.held[0])
                        & (idx < dims.held[0] + dims.held[1])).sum())
            line = {"tokens": tokens, "routing": routing,
                    "held_picks": held}
            ms, (want, _) = timed(jax.jit(
                lambda q, x, i, g, v: ragged_form(q, dims, x, i, g, v)),
                q, x, idx, gates, valid)
            line["ragged_dot_ms"] = round(ms, 4)
            for tile in (64, 128, 256):
                moe_ops.TILE = tile         # read when the loop is traced
                ms, (got, _) = timed(jax.jit(
                    lambda q, x, i, g, v: moe_ops.experts_sorted(
                        q, dims, x, i, g, v)), q, x, idx, gates, valid)
                line[f"tile_loop_{tile}_ms"] = round(ms, 4)
                line[f"tile_loop_{tile}_gap"] = float(
                    jnp.abs(got - want).max()
                    / jnp.maximum(jnp.abs(want).max(), 1e-9))
            moe_ops.TILE = 64
            say(**line)
    log.close()


if __name__ == "__main__":
    main()
