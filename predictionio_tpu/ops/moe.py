"""A mixture-of-experts layer that is told which experts it holds.

The router scores EVERY expert of the model — routed ones and zero-compute
(identity) ones — and picks ``top_k`` per token; this process computes only
what the routed experts it holds (``held = (e0, n)``) add for the tokens sent
to them, plus the zero-compute experts' part (``g_i * x``: no weights, no
matrix product). What the absent experts would add is left out: on one chip
of a deployment that divides a layer's experts over many, that is the part
the exchange would bring, and nothing here stands in for it.

Routing: ``p = softmax(x W_r)`` in float32 at the highest matmul precision,
``S = top_k(p + bias)``, gate ``g_i = scale * p_i`` (the selected expert's own
probability), or, where the model says so (``norm_topk``), renormalised over
the picks: ``g_i = scale * p_i / sum_{j in S} p_j``.

The grouped product (:func:`experts_sorted`) runs over the (token, pick)
pairs SORTED by expert. Each held expert's group is cut into tiles of
``TILE`` rows; a loop with as many rounds as there are tiles takes one tile,
gathers its tokens' rows, runs them through that expert's three matrices and
adds the gated result back to the tokens. No capacity factor exists and no
token is dropped: under any skew the loop just runs more tiles for the
crowded expert (all of them, if every token picks it). The loop was measured
on the chip against XLA's ``ragged_dot`` over the same sorted rows, and its
tile against 128 and 256 rows (``benchmarks/tools/moe_grouped_probe.py``; the
readings are in PERF.md, Findings, PR 27); the loop is the one form kept.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import jax
import jax.numpy as jnp

from predictionio_tpu.ops.mla import mm

#: rows of one expert tile (the measured best of 64, 128 and 256 at the
#: widths this layer has been run at: PERF.md, Findings, PR 27)
TILE = 64


@dataclasses.dataclass(frozen=True)
class MoEDims:
    dim: int
    expert_dim: int
    n_routed: int                    # routed experts of the MODEL
    n_zero: int                      # zero-compute (identity) experts
    top_k: int
    scale: float                     # routed_scaling_factor
    held: Tuple[int, int]            # (first routed expert held, how many)
    norm_topk: bool = False          # gates renormalised over the picks

    @property
    def n_router(self) -> int:
        return self.n_routed + self.n_zero


def init(key, dims: MoEDims, dtype=jnp.float32, bias_std: float = 0.0) -> dict:
    d, n = dims, dims.held[1]
    kr, kb, kg, ku, kd = jax.random.split(key, 5)

    def normal(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                / math.sqrt(fan_in)).astype(dtype)

    return {"w_r": normal(kr, (d.dim, d.n_router), d.dim),
            "bias": bias_std * jax.random.normal(kb, (d.n_router,),
                                                 jnp.float32),
            "w_g": normal(kg, (n, d.dim, d.expert_dim), d.dim),
            "w_u": normal(ku, (n, d.dim, d.expert_dim), d.dim),
            "w_d": normal(kd, (n, d.expert_dim, d.dim), d.expert_dim)}


def route(p, dims: MoEDims, x):
    """``(idx [T, top_k] int32, gates [T, top_k] float32)``."""
    logits = jnp.dot(x.astype(jnp.float32), p["w_r"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    prob = jax.nn.softmax(logits, axis=-1)
    _, idx = jax.lax.top_k(prob + p["bias"].astype(jnp.float32), dims.top_k)
    gates = jnp.take_along_axis(prob, idx, axis=-1)
    if dims.norm_topk:
        gates = gates / gates.sum(axis=-1, keepdims=True)
    return idx.astype(jnp.int32), dims.scale * gates


def swiglu(x, w_g, w_u, w_d):
    return mm(jax.nn.silu(mm(x, w_g)) * mm(x, w_u), w_d)


def experts_sorted(p, dims: MoEDims, x, idx, gates, valid):
    """What the held routed experts add, ``[T, dim]`` float32, and the tokens
    each of them got, ``[n]`` int32. ``valid`` [T] masks padding tokens out:
    they reach no expert."""
    e0, n = dims.held
    T, k = idx.shape
    local = idx - e0
    here = (local >= 0) & (local < n) & valid[:, None]
    flat = jnp.where(here, local, n).reshape(-1)             # n = "not here"
    order = jnp.argsort(flat, stable=True)
    token = (order // k).astype(jnp.int32)
    gate = gates.reshape(-1)[order]
    counts = jnp.zeros((n + 1,), jnp.int32).at[flat].add(1)[:n]
    tiles = (counts + TILE - 1) // TILE
    tile_end = jnp.cumsum(tiles)                             # [n]
    group_start = jnp.cumsum(counts) - counts
    rows = jnp.arange(TILE, dtype=jnp.int32)

    def one_tile(t, y):
        e = jnp.searchsorted(tile_end, t, side="right").astype(jnp.int32)
        first = group_start[e] + (t - (tile_end[e] - tiles[e])) * TILE
        at = first + rows
        ok = at < group_start[e] + counts[e]
        at = jnp.minimum(at, T * k - 1)
        tok = token[at]
        out = swiglu(x[tok], p["w_g"][e], p["w_u"][e], p["w_d"][e])
        g = jnp.where(ok, gate[at], 0.0)
        return y.at[tok].add(out * g[:, None])

    y = jax.lax.fori_loop(0, tile_end[-1], one_tile,
                          jnp.zeros((T, dims.dim), jnp.float32))
    return y, counts


def moe(p, dims: MoEDims, x, valid, scope: str = "moe"):
    """The layer's output here for tokens ``x`` [T, dim]: ``(y [T, dim]
    float32, counters)``; ``counters`` = tokens per held expert ``[n]`` and
    the zero-compute picks of the valid tokens (a scalar), int32."""
    with jax.named_scope(scope + ".route"):
        idx, gates = route(p, dims, x)
    with jax.named_scope(scope + ".experts"):
        routed, load = experts_sorted(p, dims, x, idx, gates, valid)
    if not dims.n_zero:
        return routed, {"expert_load": load, "zero_picks": jnp.int32(0)}
    with jax.named_scope(scope + ".zero"):
        is_zero = idx >= dims.n_routed
        zero_gate = jnp.where(is_zero, gates, 0.0).sum(axis=-1)
        zero = zero_gate[:, None] * x.astype(jnp.float32)
        zero_picks = (is_zero & valid[:, None]).sum().astype(jnp.int32)
    return routed + zero, {"expert_load": load, "zero_picks": zero_picks}
