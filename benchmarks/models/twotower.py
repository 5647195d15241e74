"""Model builder ``twotower``: the program's ``TwoTowerTrainer`` on seeded
clustered positives, with the benchmark's own seeded tables put in.

The timed object is ONE trainer: set-up builds it, compiles its epoch
program, and drives it through its first call (one whole epoch — the
program's unit of dispatch is an epoch scan, so that is the smallest thing
the window's own call runs); the window goes on calling the same object.
"""

from __future__ import annotations

import functools
import time

import numpy as np

POSITIVES_STREAM = 3


def program_seed(seed: int) -> int:
    # the program feeds its seed to jax.random.PRNGKey (32 bits)
    return int(seed) % 2147483647


def make_positives(bench):
    """Clustered positives as the pre-chip benchmark script's two-tower stage
    made them (the script left the tree at PR 29): every user has a
    cluster, a share ``in_cluster`` of a user's items fall into it, so the
    loss has something to learn. Returns (u [n_pos], i [n_pos]) int32.

    The program closes its epoch program over the positives, so they are
    constants of the compiled program and part of its cache key: where the
    configuration gives ``positives_seed`` every ``--seed`` trains the same
    examples (in the seed's own order, on the seed's own tables) and finds
    the program in the compile cache; without it they follow ``--seed``."""
    cfg = bench.config
    n_users, n_items = int(cfg["n_users"]), int(cfg["n_items"])
    n_pos, C = int(cfg["n_positives"]), int(cfg["n_clusters"])
    rng = bench.lib("seeded").rng(cfg.get("positives_seed", bench.seed),
                                  POSITIVES_STREAM)
    user_cluster = rng.integers(0, C, size=n_users)
    uu = rng.integers(0, n_users, size=n_pos)
    inside = rng.random(n_pos) < float(cfg["in_cluster"])
    per_cluster = n_items // C
    ii = np.where(inside,
                  user_cluster[uu] + C * rng.integers(0, per_cluster, n_pos),
                  rng.integers(0, n_items, size=n_pos))
    return uu.astype(np.int32), ii.astype(np.int32)


def table_key(seed: int):
    import jax

    return jax.random.PRNGKey(program_seed(seed) ^ 0x5EED)


@functools.lru_cache(maxsize=None)
def _table_fns(n_users: int, n_items: int, width: int):
    import jax
    import jax.numpy as jnp

    scale = 1.0 / np.sqrt(width)

    def gen(key):
        k0, k1 = jax.random.split(key)
        return {
            "user": jax.random.normal(k0, (n_users, width),
                                      jnp.float32) * scale,
            "item": jax.random.normal(k1, (n_items, width),
                                      jnp.float32) * scale,
        }

    def change_norms(tables, key):
        init = gen(key)
        return {k: jnp.sqrt(jnp.sum((tables[k] - init[k]) ** 2))
                for k in ("user", "item")}

    return jax.jit(gen), jax.jit(change_norms)


def make_tables(bench):
    """The seeded id tables, N(0, 1/width), made on the device in one
    jitted call — the benchmark's weights, for the program and for the
    reference alike."""
    cfg = bench.config
    gen, _ = _table_fns(int(cfg["n_users"]), int(cfg["n_items"]),
                        int(cfg["dim"]))
    return gen(table_key(bench.seed))


def state_readings(bench, tables, acc) -> dict:
    """What the comparison reads from a state after the first call: per
    leaf, the norm of the parameters' change from the seeded tables, and
    the gradient norm as row-wise Adagrad accumulated it."""
    import jax.numpy as jnp

    cfg = bench.config
    _, change_norms = _table_fns(int(cfg["n_users"]), int(cfg["n_items"]),
                                 int(cfg["dim"]))
    dn = change_norms(tables, table_key(bench.seed))
    out = {}
    for k in ("user", "item"):
        out[f"change_norm.{k}"] = float(dn[k])
        out[f"grad_norm.{k}"] = float(jnp.sqrt(jnp.sum(acc[k])))
    return out


class Built:
    def __init__(self, bench, trainer, timings):
        self.bench, self.trainer, self.timings = bench, trainer, timings
        self.calls = 0
        self.examples_per_call = trainer.n_pos
        self.steps_per_call = trainer.steps_per_epoch

    def call(self) -> float:
        """One more call of the window's kind: one epoch, ended by the
        program's own ``block_until_ready``. Returns its mean loss."""
        self.calls += 1
        return self.trainer.run(epochs=self.calls)[-1]

    def first_call(self) -> dict:
        from predictionio_tpu.obs import jaxmon

        mean = self.call()
        report = dict(jaxmon.TRAINER_REPORTS.get("twotower", {}))
        tables, acc, _, _ = self.trainer._state
        got = state_readings(self.bench, tables, acc)
        got.update({"loss_mean": float(mean),
                    "loss_first": float(report["first_step_loss"]),
                    "loss_last": float(report["last_step_loss"])})
        self.timings["compile_s"] = float(self.trainer.compile_sec)
        return got

    def notes(self):
        return [f"kernel plan: {self.trainer.kernel_plan}"]

    def free(self) -> None:
        self.trainer._state = None
        self.trainer._compiled = None
        self.trainer = None


def build(bench) -> Built:
    from predictionio_tpu.ops.twotower import TwoTowerConfig, TwoTowerTrainer

    cfg = bench.config
    timings = {}
    t = time.perf_counter()
    uu, ii = make_positives(bench)
    timings["positives_s"] = time.perf_counter() - t

    t = time.perf_counter()
    tcfg = TwoTowerConfig(
        dim=int(cfg["dim"]), batch_size=int(cfg["batch_size"]),
        learning_rate=float(cfg["learning_rate"]),
        temperature=float(cfg["temperature"]),
        compute_dtype=cfg["compute_dtype"],
        flash_ce_kernel=cfg["flash_ce_kernel"],
        embed_update_kernel=cfg["embed_update_kernel"],
        seed=program_seed(bench.seed), epochs=1 << 30)
    trainer = TwoTowerTrainer((uu, ii, None), int(cfg["n_users"]),
                              int(cfg["n_items"]), tcfg)
    timings["trainer_init_s"] = time.perf_counter() - t

    # the benchmark's own seeded tables take the place of the trainer's
    # (no public seam for this: PERF.md, Open questions)
    t = time.perf_counter()
    _, acc, dense, opt_state = trainer._state
    trainer._state = None
    if any(len(v) for v in dense.values()):
        raise ValueError("this builder covers id-embedding towers only "
                         "(no hidden layers): the reference has no MLP")
    tables = make_tables(bench)
    trainer._state = (tables, acc, dense, opt_state)
    del tables
    timings["tables_s"] = time.perf_counter() - t
    return Built(bench, trainer, timings)
