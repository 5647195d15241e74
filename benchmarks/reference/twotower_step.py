"""Plain reference for two-tower training: float32, ``highest`` matmul
precision, no kernel, no chunking, nothing of the program.

Towers: id embedding -> L2 normalisation (this configuration has no hidden
layers, so there is no dense part and no AdamW). Loss: symmetric in-batch
softmax at ``temperature``; in the user->item direction another row's item
equal to this row's item is not a negative, in the item->user direction
another row's user equal to this row's user is not, and zero-weight padding
rows are nobody's negatives. Optimizer: row-wise Adagrad on the touched rows
(one accumulator per row: the mean of the squared gradient of the row is
added, duplicates accumulate, the rate is read after the add), at ten times
``learning_rate``.

The program's unit of dispatch is a whole epoch, so the reference follows
the whole FIRST epoch: the same seeded tables, the same seeded positives,
in the order the program documents for epoch 0 (``TwoTowerTrainer.run``: "the
shuffle key derives from (seed, epoch index)" — a permutation of
``jax.random`` under ``fold_in(PRNGKey(seed + 1), 0)``).
"""

from __future__ import annotations

import functools

import numpy as np

EPS = 1e-8


def epoch_order(seed32: int, n: int, steps: int, batch: int):
    import jax
    import jax.numpy as jnp

    key = jax.random.fold_in(jax.random.PRNGKey(seed32 + 1), 0)
    perm = jax.random.permutation(key, n).astype(jnp.int32)
    pad = jnp.full((steps * batch - n,), n, jnp.int32)
    return jnp.concatenate([perm, pad]).reshape(steps, batch)


#: (exponent bits, mantissa bits) of the precisions a control may hold in
FORMATS = {"bfloat16": (8, 7), "float8_e4m3fn": (4, 3)}


def _held(x, exponent_bits, mantissa_bits):
    """``x`` rounded to a narrower float format, with the gradient passed
    straight through. ``lax.reduce_precision`` and not a pair of casts: XLA
    may drop a cast down and up again as excess precision."""
    import jax

    @jax.custom_jvp
    def held(x):
        return jax.lax.reduce_precision(x, exponent_bits, mantissa_bits)

    @held.defjvp
    def _(primals, tangents):
        return held(primals[0]), tangents[0]

    return held(x)


def _loss(ue, ve, u_idx, i_idx, w, temp, round_to):
    import jax
    import jax.numpy as jnp

    u = ue / jnp.maximum(jnp.linalg.norm(ue, axis=-1, keepdims=True), EPS)
    v = ve / jnp.maximum(jnp.linalg.norm(ve, axis=-1, keepdims=True), EPS)
    def held_in(x):
        # the control: what the program computes in its compute dtype (the
        # towers' outputs that enter the logits product, the product, and
        # its scaling by 1/temperature) is computed one precision lower,
        # rounded going forward, the gradient passed straight through
        return x if round_to is None else _held(x, *round_to)

    raw = jnp.matmul(held_in(u), held_in(v).T, precision="highest")
    if round_to is None:
        logits = raw / temp
    else:
        inv_temp = jax.lax.reduce_precision(jnp.float32(1.0 / temp),
                                            *round_to)
        logits = held_in(held_in(raw) * inv_temp)
    B = logits.shape[0]
    eye = jnp.eye(B, dtype=bool)
    pad_col = (w <= 0.0)[None, :]
    same_i = ((i_idx[None, :] == i_idx[:, None]) | pad_col) & ~eye
    same_u = ((u_idx[None, :] == u_idx[:, None]) | pad_col) & ~eye
    diag = jnp.arange(B)
    l_ui = -jax.nn.log_softmax(jnp.where(same_i, -1e9, logits),
                               axis=1)[diag, diag]
    l_iu = -jax.nn.log_softmax(jnp.where(same_u, -1e9, logits.T),
                               axis=1)[diag, diag]
    return jnp.sum(0.5 * (l_ui + l_iu) * w) / jnp.maximum(w.sum(), EPS)


def _adagrad(table, acc, idx, grad, lr):
    import jax.numpy as jnp

    acc = acc.at[idx].add(jnp.mean(grad * grad, axis=-1))
    scale = lr / jnp.sqrt(acc[idx] + EPS)
    return table.at[idx].add(-scale[:, None] * grad), acc


@functools.lru_cache(maxsize=None)
def _epoch_fn(temp: float, table_lr: float, round_name):
    import jax
    import jax.numpy as jnp

    round_to = None if round_name is None else FORMATS[round_name]

    def epoch(tables, acc, u_all, i_all, w_all, order):
        def step(carry, idx):
            tables, acc = carry
            u_idx, i_idx, w = u_all[idx], i_all[idx], w_all[idx]
            ue, ve = tables["user"][u_idx], tables["item"][i_idx]
            loss, (gu, gv) = jax.value_and_grad(_loss, argnums=(0, 1))(
                ue, ve, u_idx, i_idx, w, temp, round_to)
            tu, au = _adagrad(tables["user"], acc["user"], u_idx, gu,
                              table_lr)
            ti, ai = _adagrad(tables["item"], acc["item"], i_idx, gv,
                              table_lr)
            return ({"user": tu, "item": ti}, {"user": au, "item": ai}), loss

        (tables, acc), losses = jax.lax.scan(step, (tables, acc), order)
        return tables, acc, losses

    return jax.jit(epoch, donate_argnums=(0, 1))


def first_epoch(bench, round_name=None) -> dict:
    """The reference's readings after epoch 0 (or the control's, with the
    logits and their inputs held in ``round_name``)."""
    import jax
    import jax.numpy as jnp

    cfg = bench.config
    builder = bench.load_module("models", cfg["engine"])
    uu, ii = builder.make_positives(bench)
    n, batch = len(uu), int(cfg["batch_size"])
    steps = -(-n // batch)
    u_all = jnp.asarray(np.concatenate([uu, np.zeros(1, np.int32)]))
    i_all = jnp.asarray(np.concatenate([ii, np.zeros(1, np.int32)]))
    w_all = jnp.asarray(np.concatenate([np.ones(n, np.float32),
                                        np.zeros(1, np.float32)]))
    order = epoch_order(builder.program_seed(bench.seed), n, steps, batch)
    tables = builder.make_tables(bench)
    acc = {"user": jnp.zeros((int(cfg["n_users"]),), jnp.float32),
           "item": jnp.zeros((int(cfg["n_items"]),), jnp.float32)}
    fn = _epoch_fn(float(cfg["temperature"]),
                   10.0 * float(cfg["learning_rate"]), round_name)
    tables, acc, losses = fn(tables, acc, u_all, i_all, w_all, order)
    losses = np.asarray(jax.block_until_ready(losses))
    got = builder.state_readings(bench, tables, acc)
    got.update({"loss_mean": float(losses.mean()),
                "loss_first": float(losses[0]),
                "loss_last": float(losses[-1])})
    del tables, acc
    return got


def gaps(program: dict, reference: dict) -> dict:
    """The numbers compared: each loss's relative gap, and per kind of norm
    the worst leaf's gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger."""
    out = {}
    for k in ("loss_first", "loss_mean", "loss_last"):
        out[k + "_gap"] = abs(program[k] - reference[k]) / abs(reference[k])
    for kind in ("grad_norm", "change_norm"):
        leaves = [k for k in reference if k.startswith(kind + ".")]
        median = float(np.median([reference[k] for k in leaves]))
        out[kind + "_gap"] = max(
            abs(program[k] - reference[k]) / max(reference[k], median)
            for k in leaves)
    return out


def compare(bench, program_readings: dict) -> dict:
    return gaps(program_readings, first_epoch(bench))


def control(bench) -> dict:
    """The control at the cell's own size: the reference with what the
    program holds in its compute dtype (the towers' outputs that enter the
    logits product, and the logits) held in the nearest precision below the
    configuration's bfloat16."""
    ref = first_epoch(bench)
    return {"reference": ref,
            "float8_e4m3fn": gaps(first_epoch(bench, "float8_e4m3fn"), ref),
            # not a control: the reference at the configuration's own
            # precision, to see how much of the program's gap that explains
            "bfloat16": gaps(first_epoch(bench, "bfloat16"), ref)}
