"""The expert layer of a SMALL forward on the chip, three forms over the same
picks: the sorted-tile loop (``ops/moe.experts_sorted``), a plain-XLA loop
over the touched experts with every row through each (``xla_dense_form``
below: no sort, gather or scatter, but each round's products wait for their
own weights), and the streaming kernel (``ops/pallas/expert_stream.py``, at
several chunk widths); each also against the same sum in float32
(``float32_form``). One layer at the published widths of
``configs/sdar-30b-a3b-chat.json`` (32 tokens = 8 rows x a block of 4, 128
experts held, top-8) with 8, 66 and 128 touched experts (a traced window of
``slates-c8`` shows about 66), and of ``configs/longcat-flash-chat.json`` (64
token rows of which 4 are real, 16 experts held) with 0, 1 and 16 touched.
Seeded bfloat16 weights; the layer runs ``LAYERS`` times a call so that the
device's time is what the host's clock reads. Run through the chip tool; one
JSON line a case, the log in ``chiprun_out/``:

    python3 benchmarks/tools/moe_small_probe.py

(``--tiny``: the same cases at a hundredth of the widths, to rehearse the
control flow on the CPU; never a device number.)

ISSUE 33 asked the builder to measure the XLA loop before writing a kernel
and to keep one form; the readings are in PERF.md (Findings, PR 33).
"""

import dataclasses
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, CHECKOUT)

#: applications of the layer in one compiled call
LAYERS = 7


def xla_dense_form(p, dims, x, idx, gates, valid):
    """``experts_streamed``'s answer in plain XLA: a loop over the touched
    held experts, all rows through each, the rows that did not pick it
    selected out."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops import moe as moe_ops

    n = dims.held[1]
    gate, counts = moe_ops.held_gates(dims, idx, gates, valid)
    touched = (gate >= 0).any(axis=0)
    running = jnp.cumsum(touched.astype(jnp.int32))
    ids = (running[None, :] <= jnp.arange(n)[:, None]).sum(axis=1)
    xb = x.astype(p["w_g"].dtype)

    def one_expert(j, y):
        e = jnp.minimum(ids[j], n - 1)
        out = moe_ops.swiglu(xb, p["w_g"][e], p["w_u"][e], p["w_d"][e])
        g = jax.lax.dynamic_slice_in_dim(gate, e, 1, axis=1)
        return y + jnp.where(g >= 0, g * out, 0.0)

    y = jax.lax.fori_loop(0, touched.sum(), one_expert,
                          jnp.zeros((x.shape[0], dims.dim), jnp.float32))
    return y, counts


def float32_form(p, dims, x, idx, gates, valid):
    """The same sum in float32 at the highest matmul precision, every held
    expert over every row: what each form's ``_ref_gap`` is taken against."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops import moe as moe_ops

    gate, counts = moe_ops.held_gates(dims, idx, gates, valid)
    w_g, w_u, w_d = (p[k].astype(jnp.float32) for k in ("w_g", "w_u", "w_d"))
    hi = jax.lax.Precision.HIGHEST
    h = (jax.nn.silu(jnp.einsum("td,edf->etf", x, w_g, precision=hi))
         * jnp.einsum("td,edf->etf", x, w_u, precision=hi))
    out = jnp.einsum("etf,efd->etd", h, w_d, precision=hi)
    return jnp.einsum("te,etd->td", jnp.maximum(gate, 0.0), out,
                      precision=hi), counts


def picks(dims, tokens, touched, real):
    """``idx`` [tokens, top_k] whose real tokens' picks land on exactly
    ``touched`` held experts, in turn, and otherwise on absent ones; seeded
    gates that add up to 1 a token; ``valid``."""
    import numpy as np

    e0, n = dims.held
    absent = [e for e in range(dims.n_router) if not e0 <= e < e0 + n]
    on_held = min(dims.top_k, touched)
    if real * on_held < touched:
        raise ValueError("too few picks to touch that many experts")
    idx = np.empty((tokens, dims.top_k), np.int32)
    for t in range(tokens):
        for j in range(dims.top_k):
            idx[t, j] = (e0 + (t * on_held + j) % touched if j < on_held
                         else absent[(t * dims.top_k + j) % len(absent)])
    gates = np.random.default_rng(touched).random(
        (tokens, dims.top_k)).astype(np.float32) + 0.1
    gates /= gates.sum(axis=1, keepdims=True)
    return idx, gates, np.arange(tokens) < real


def main() -> None:
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops import moe as moe_ops
    from predictionio_tpu.ops.pallas import expert_stream

    def config(name):
        with open(os.path.join(CHECKOUT, "benchmarks", "configs",
                               name + ".json")) as f:
            return json.load(f)

    sdar, longcat = config("sdar-30b-a3b-chat"), config("longcat-flash-chat")
    cases = [
        ("sdar", moe_ops.MoEDims(
            dim=int(sdar["hidden_size"]),
            expert_dim=int(sdar["moe_intermediate_size"]),
            n_routed=int(sdar["num_experts"]), n_zero=0,
            top_k=int(sdar["num_experts_per_tok"]), scale=1.0,
            held=(0, int(sdar["num_experts"])), norm_topk=True),
         32, 32, (8, 66, 128), (256, 384, 768)),
        ("longcat", moe_ops.MoEDims(
            dim=int(longcat["hidden_size"]),
            expert_dim=int(longcat["expert_ffn_hidden_size"]),
            n_routed=int(longcat["n_routed_experts_published"]),
            n_zero=int(longcat["zero_expert_num"]),
            top_k=int(longcat["moe_topk"]),
            scale=float(longcat["routed_scaling_factor"]),
            held=tuple(int(v) for v in longcat["experts_held"])),
         64, 4, (0, 1, 16), (256, 512, 1024)),
    ]
    tiny = "--tiny" in sys.argv[1:]
    if tiny:
        cases = [(name, dataclasses.replace(dims, dim=128, expert_dim=256),
                  *rest[:-1], (128, 256))
                 for name, dims, *rest in cases]
    device = jax.devices()[0]
    out_dir = os.path.join(CHECKOUT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    log = open(os.path.join(out_dir, "moe_small_probe.log"), "w")

    def say(**line):
        text = json.dumps(line)
        print(text, flush=True)
        log.write(text + "\n")
        log.flush()

    say(device=device.device_kind, platform=device.platform, layers=LAYERS,
        tiny=tiny)

    def timed(form, *args, rounds=1 if tiny else 20):
        def layers(p, x, idx, gates, valid):
            y = jnp.zeros_like(x)
            for _ in range(LAYERS):         # each reads the last one's sum
                out, counts = form(p, x + 1e-3 * y, idx, gates, valid)
                y = y + out
            return y, counts

        fn = jax.jit(layers)
        jax.block_until_ready(fn(*args))            # compile, warm
        got = jax.block_until_ready(fn(*args))
        t = time.perf_counter()
        for _ in range(rounds):
            last = fn(*args)
        jax.block_until_ready(last)
        return (time.perf_counter() - t) / rounds / LAYERS * 1e6, got

    for name, dims, tokens, real, touched_cases, chunks in cases:
        p = moe_ops.init(jax.random.PRNGKey(33), dims, jnp.bfloat16)
        x = jax.random.normal(jax.random.PRNGKey(tokens),
                              (tokens, dims.dim), jnp.float32)
        expert_mb = 3 * dims.dim * dims.expert_dim * 2 / 1e6
        say(case=name, dims=str(dims), tokens=tokens, real=real,
            expert_mb=expert_mb)
        for touched in touched_cases:
            idx, gates, valid = picks(dims, tokens, touched, real)
            args = (p, x, jnp.asarray(idx), jnp.asarray(gates),
                    jnp.asarray(valid))
            line = {"case": name, "touched": touched}

            def put(form_name, us, got, want):
                line[form_name + "_layer_us"] = round(us, 2)
                if touched:
                    line[form_name + "_us_per_expert"] = round(
                        us / touched, 2)
                for against, name in ((want, "_gap"), (ref, "_ref_gap")):
                    if against is not None:
                        line[form_name + name] = float(
                            jnp.abs(got[0] - against[0]).max()
                            / jnp.maximum(jnp.abs(against[0]).max(), 1e-9))
                        assert bool((got[1] == against[1]).all()), form_name

            _, ref = timed(
                lambda *a: float32_form(a[0], dims, *a[1:]), *args, rounds=1)
            line["out_abs_max"] = float(jnp.abs(ref[0]).max())
            us, want = timed(
                lambda *a: moe_ops.experts_sorted(a[0], dims, *a[1:]), *args)
            put("sorted_tiles", us, want, None)
            us, got = timed(
                lambda *a: xla_dense_form(a[0], dims, *a[1:]), *args)
            put("xla_dense", us, got, want)
            default = expert_stream.chunk_of
            for chunk in chunks:
                expert_stream.chunk_of = lambda *_, _c=chunk: _c
                us, got = timed(
                    lambda *a: moe_ops.experts_streamed(a[0], dims, *a[1:]),
                    *args)
                put(f"kernel_chunk{chunk}", us, got, want)
            expert_stream.chunk_of = default
            say(**line)
    log.close()


if __name__ == "__main__":
    main()
