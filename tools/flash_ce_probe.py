"""What the two-tower step's cross-entropy costs on the chip, by kernel and by
form (PERF.md section 5, ``train``): ``ops/pallas/flash_ce.py`` alone at the
stretch cell's shape (B = 8,192, D = 128, bfloat16; seeded unit-norm towers,
ids drawn from UserBehavior's ranges with in-batch duplicates), no trainer
around it. A reading is one ``value_and_grad`` of the loss under the
profiler, ``ROUNDS`` calls unwaited, and gives each kernel's device time a
call by its instruction's name, us a tile, and the whole call's ms:

* ``one_pass``: ``flash_ce_fwd`` + ``flash_ce_bwd`` (the tile's softmax
  rebuilt once, both gradient products: what the trainer runs where dv fits
  the VMEM budget, ``flash_ce.backward_form``);
* ``two_pass``: ``flash_ce_fwd`` + ``flash_ce_bwd_du`` + ``flash_ce_bwd_dv``
  (the split that stands past the budget), with ``equal``: whether its du and
  dv are the one-pass form's bit for bit;
* for the record only, what ROADMAP.md S3's next step starts from: both at
  tiles of 256, and the one-pass backward with the tile's two exponentials
  FOLDED into one (``exp(L)`` times a row factor and a column factor: the
  same identity under another rounding, ``grad_gap`` against the unfolded
  form; not a path of the program);
* ``--shapes``: both forms at larger batches and widths (default
  32768x128, 65536x128, 65536x256, 81920x256), the one-pass form forced
  where the rule would not take it: what the rule's share of VMEM was
  chosen from.

One JSON line a reading, the log in ``chiprun_out/flash_ce_probe.log``:

    python3 tools/flash_ce_probe.py        (``--tiny``: on the CPU, where the
                                            kernels run under the interpreter
                                            and no device time is read)
"""

import argparse
import contextlib
import json
import os
import sys
import tempfile
import time

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHECKOUT)

ROUNDS = 20


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--shapes",
                    default="32768x128,65536x128,65536x256,81920x256",
                    help="BxD beside the cell's, comma-separated ('' for "
                         "none)")
    args = ap.parse_args()
    if args.tiny:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp
    import numpy as np

    from predictionio_tpu.obs import profiler
    from predictionio_tpu.ops.pallas import flash_ce

    if args.tiny:
        cell, blocks, others, rounds = (256, 16), (64, 32), [(512, 16)], 2
        cdt = jnp.float32
    else:
        cell, blocks, rounds, cdt = (8192, 128), (512, 256), ROUNDS, \
            jnp.bfloat16
        others = [tuple(int(n) for n in s.split("x"))
                  for s in args.shapes.split(",") if s]
    interpret = jax.default_backend() != "tpu"
    os.makedirs(os.path.join(CHECKOUT, "chiprun_out"), exist_ok=True)
    log = open(os.path.join(CHECKOUT, "chiprun_out", "flash_ce_probe.log"),
               "a")

    def say(**reading):
        line = json.dumps({"device": jax.devices()[0].device_kind,
                           **reading})
        print(line, flush=True)
        log.write(line + "\n")
        log.flush()

    def batch(B, D):
        rng = np.random.default_rng(B + D)
        u, v = (rng.normal(size=(B, D)).astype(np.float32) for _ in "uv")
        u /= np.linalg.norm(u, axis=1, keepdims=True)
        v /= np.linalg.norm(v, axis=1, keepdims=True)
        # the cell's id ranges over a Zipf-like draw: duplicates in a batch
        u_idx = (987_994 * rng.random(B) ** 3).astype(np.int32)
        i_idx = (4_162_024 * rng.random(B) ** 3).astype(np.int32)
        w = np.ones(B, np.float32)
        w[-B // 64:] = 0.0              # a last step's padding
        return tuple(jnp.asarray(a) for a in (u, v, u_idx, i_idx, w))

    @contextlib.contextmanager
    def steered(vmem, coef=None):
        """The shape rule's VMEM and the coefficient's form, while a
        reading's program is traced."""
        held = flash_ce._vmem_bytes, flash_ce._bwd_coef
        flash_ce._vmem_bytes = lambda: vmem
        flash_ce._bwd_coef = coef or held[1]
        try:
            yield
        finally:
            flash_ce._vmem_bytes, flash_ce._bwd_coef = held

    def folded_coef(i, j, br, bc, L, lse_ui, lse_iu, uir, uic, iir, iic, wr,
                    wc, scale):
        """``flash_ce._bwd_coef`` with ONE exponential a tile entry."""
        not_diag, ban_ui, ban_iu = flash_ce._tile_masks(
            i, j, br, bc, uir, uic, iir, iic, wr, wc)
        e = jnp.exp(L)
        p_ui = jnp.where(ban_ui, 0.0, e * jnp.exp(-lse_ui))
        p_iu = jnp.where(ban_iu, 0.0, e * jnp.exp(-lse_iu))
        isdiag = jnp.where(not_diag, 0.0, 1.0)
        return (wr * (p_ui - isdiag) + wc * (p_iu - isdiag)) * scale

    def reading(B, D, block, form, coef=None):
        """One form's kernels timed; its (loss, (du, dv)) as numpy."""
        data = batch(B, D)

        def fwd_bwd(u, v, u_idx, i_idx, w):
            with jax.named_scope("probe.flash_ce"):
                ce = flash_ce.make_flash_ce(u_idx, i_idx, w, 0.07, cdt, B,
                                            interpret=interpret, block=block)
                return jax.value_and_grad(ce, argnums=(0, 1))(u, v)

        # a VMEM of which the rule's share holds any dv, or none
        with steered((1 << 40) if form == "one_pass" else 0, coef):
            fn = jax.jit(fwd_bwd).lower(*data).compile()
        out = jax.block_until_ready(fn(*data))
        with tempfile.TemporaryDirectory() as d:
            with profiler.trace_capture(d):
                t = time.perf_counter()
                for _ in range(rounds):
                    last = fn(*data)
                jax.block_until_ready(last)
                call_ms = (time.perf_counter() - t) * 1e3 / rounds
            # self time by instruction name; none without a TPU's plane
            groups = profiler.parse_xplane(d).get("by_category", {})
        kernels = {k: g["time_sec"] * 1e3 / rounds
                   for k, g in groups.items() if "flash_ce" in k}
        tiles = (-(-B // block)) ** 2
        bwd = sum(ms for k, ms in kernels.items() if "bwd" in k)
        say(what="flash_ce", B=B, D=D, block=block, form=form,
            exponentials=1 if coef else 2, call_ms=call_ms,
            kernel_ms=kernels or None,
            backward_ms=bwd or None,
            backward_us_a_tile=bwd * 1e3 / tiles if bwd else None,
            vmem_counted=flash_ce.one_pass_vmem_bytes(B, D, block),
            rule=flash_ce.backward_form(B, D, block))
        return jax.tree.map(np.asarray, out)

    def gap(a, b):
        return float(max(np.abs(x - y).max() / np.abs(y).max()
                         for x, y in zip(a[1], b[1])))

    for B, D in [cell] + others:
        for block in blocks if (B, D) == cell else blocks[:1]:
            one = reading(B, D, block, "one_pass")
            two = reading(B, D, block, "two_pass")
            say(what="equal", B=B, D=D, block=block,
                equal=all(np.array_equal(x, y)
                          for x, y in zip(one[1], two[1]))
                and float(one[0]) == float(two[0]), grad_gap=gap(one, two))
            if (B, D) == cell:
                fold = reading(B, D, block, "one_pass", coef=folded_coef)
                say(what="folded", B=B, D=D, block=block,
                    grad_gap=gap(fold, one))
    return 0


if __name__ == "__main__":
    sys.exit(main())
