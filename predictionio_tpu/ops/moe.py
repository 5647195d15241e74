"""A mixture-of-experts layer that is told which experts it holds.

The router scores EVERY expert of the model — routed ones and zero-compute
(identity) ones — and picks ``top_k`` per token; this process computes only
what the routed experts it holds (``held = (e0, n)``) add for the tokens sent
to them, plus the zero-compute experts' part (``g_i * x``: no weights, no
matrix product). What the absent experts would add is left out: on one chip
of a deployment that divides a layer's experts over many, that is the part
the exchange would bring, and nothing here stands in for it. A model may put
a SHARED expert beside the routed ones (``shared_dim``): every token runs
through it, ungated, and every chip of the deployment holds it.

Routing: ``p = softmax(x W_r)`` in float32 at the highest matmul precision,
``S = top_k(p + bias)``, gate ``g_i = scale * p_i`` (the selected expert's own
probability), or, where the model says so (``norm_topk``), renormalised over
the picks: ``g_i = scale * p_i / sum_{j in S} p_j``. A model may score its
experts one by one instead (``scoring="sigmoid"``: ``p = sigmoid(x W_r)``),
and may pick GROUPS before experts (``n_group``, ``topk_group``): the routed
experts lie in ``n_group`` equal runs, a group's score is the sum of its two
largest ``p + bias``, and ``S`` is the ``top_k`` of the ``topk_group`` best
groups' experts alone. One group (the default) is no selection by groups.

What the held experts add is computed in one of two forms, chosen by the
number of tokens ``T`` of the forward, a static shape (:func:`small_forward`;
no option, model name or environment variable is asked). Both are ONE Pallas
kernel a layer whose grid walks the TOUCHED experts, reading expert ``e + 1``
while ``e``'s products run; an expert no token picked is not read, and each
touched expert's matrices cross HBM once a forward:

* ``T <= TILE`` (a block forward's 8 x 4 tokens, an extension batch's 8 x 8):
  :func:`experts_streamed`. All ``T`` rows stay in place and run through each
  touched expert, the gate of a row that did not pick it selecting its
  product out (``ops/pallas/expert_stream.py``, ``expert_stream``). No sort,
  gather or scatter.
* ``T > TILE`` (a prefill chunk's 512): :func:`experts_grouped`. The (token,
  pick) pairs are SORTED by held expert (:func:`sorted_pairs`); inside an
  expert's grid step a loop takes its group ``expert_stream.GROUP_ROWS`` rows
  a product, gathering each row from ``x`` in VMEM and adding the gated
  product back to the float32 sum in VMEM (``expert_groups`` in the same
  file). The dense form would waste products here (at 512 tokens 16 times the
  FLOPs: a layer bound by reading its weights would be bound by arithmetic);
  the grouped one's work grows with the pairs that are here and nothing else.

The same picks and precision in both (product inputs in the weights' type,
float32 accumulation, gates and sum), summed in expert order. No capacity
factor exists and no token is dropped in either: under any skew the crowded
expert's group just runs more products under its one read (all 512 rows, if
every token picks it), and the small forward's rows all run through it.

:func:`experts_sorted` is the form both replaced: a ``fori_loop`` with one
round a tile of :data:`TILE` sorted rows. :func:`moe` calls it no more; the
tests hold both kernels to it and the benchmark's probes time it as their
baseline.

Chip readings (one v5e; PERF.md, Findings). The tile loop beat XLA's
``ragged_dot`` over the same sorted rows at 512 tokens, 2.34 to 6.35 ms
(``benchmarks/tools/moe_grouped_probe.py``; PRs 24-31). At 32 tokens (SDAR's
widths: 128 experts of 2048 x 768, 9.44 MB and 11.5 us of reading each; 66
touched) a tile of the loop costs 31.9 us whether it holds two tokens or
sixty, a plain-XLA loop over the touched experts 16.5 us (its products wait
for their own weights), the streaming kernel 12.8 us
(``benchmarks/tools/moe_small_probe.py``; PR 33). At 512 tokens, us a touched
expert with the sort, tile loop | grouped kernel (to read one): SDAR 34.9 |
13.4 (11.5); granite (36 held of 4096 x 768, ~71 rows each: two tiles of the
loop, one product of the kernel) 90.1 | 29.4 (23.0), the kernel alone 25.9
inside the cell's chunk program; LongCat (16 held of 6144 x 2048 in four
column chunks, ~8 rows each) 144.3 | 111.1 (92.2); every token on one
expert: 36.8 | 13.7, 91.6 | 30.7, 202.4 | 123.7. 64 and 128 rows a product
read alike (PR 36).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import jax
import jax.numpy as jnp

from predictionio_tpu.ops import pallas as pallas_ops
from predictionio_tpu.ops.mla import mm
from predictionio_tpu.ops.pallas import expert_stream

#: the tokens up to which a forward takes the streamed form
#: (:func:`small_forward`), and the rows of one tile of the sorted-tile loop
#: (:func:`experts_sorted`; its measured best of 64, 128 and 256 at 512
#: tokens: PERF.md, Findings, PRs 24-31)
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
    shared_dim: int = 0              # the shared expert's width; 0: none
    scoring: str = "softmax"         # "softmax" over all | "sigmoid" of each
    n_group: int = 1                 # equal runs of routed experts, of which
    topk_group: int = 1              # this many are kept before the top_k

    def __post_init__(self):
        if self.scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown expert scoring {self.scoring!r}")
        if self.n_group > 1 and (self.n_zero or self.n_routed % self.n_group
                                 or not 0 < self.topk_group <= self.n_group):
            raise ValueError(
                "groups divide the routed experts evenly, keep 1..n_group "
                "of them, and know no zero-compute experts")

    @property
    def n_router(self) -> int:
        return self.n_routed + self.n_zero


def init(key, dims: MoEDims, dtype=jnp.float32, bias_std: float = 0.0) -> dict:
    d, n = dims, dims.held[1]
    kr, kb, kg, ku, kd = jax.random.split(key, 5)

    def normal(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                / math.sqrt(fan_in)).astype(dtype)

    out = {"w_r": normal(kr, (d.dim, d.n_router), d.dim),
           "bias": bias_std * jax.random.normal(kb, (d.n_router,),
                                                jnp.float32),
           "w_g": normal(kg, (n, d.dim, d.expert_dim), d.dim),
           "w_u": normal(ku, (n, d.dim, d.expert_dim), d.dim),
           "w_d": normal(kd, (n, d.expert_dim, d.dim), d.expert_dim)}
    if d.shared_dim:
        kg, ku, kd = jax.random.split(jax.random.fold_in(key, 5), 3)
        out["shared"] = {
            "w_g": normal(kg, (d.dim, d.shared_dim), d.dim),
            "w_u": normal(ku, (d.dim, d.shared_dim), d.dim),
            "w_d": normal(kd, (d.shared_dim, d.dim), d.shared_dim)}
    return out


def kept_groups(dims: MoEDims, choice):
    """Which groups a token's pick is made from, ``[T, n_group]`` bool: the
    ``topk_group`` whose two largest ``choice`` [T, n_routed] add up
    highest (ties: the earlier group)."""
    per = choice.reshape(choice.shape[0], dims.n_group, -1)
    score = jax.lax.top_k(per, 2)[0].sum(axis=-1)
    _, best = jax.lax.top_k(score, dims.topk_group)
    return (best[:, :, None] == jnp.arange(dims.n_group)).any(axis=1)


def route_kept(p, dims: MoEDims, x):
    """:func:`route`'s answer and the groups it picked from (None where the
    model selects by no groups)."""
    logits = jnp.dot(x.astype(jnp.float32), p["w_r"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    prob = (jax.nn.softmax(logits, axis=-1) if dims.scoring == "softmax"
            else jax.nn.sigmoid(logits))
    choice, kept = prob + p["bias"].astype(jnp.float32), None
    if dims.n_group > 1:
        kept = kept_groups(dims, choice)
        choice = jnp.where(
            jnp.repeat(kept, dims.n_routed // dims.n_group, axis=1),
            choice, -jnp.inf)
    _, idx = jax.lax.top_k(choice, dims.top_k)
    gates = jnp.take_along_axis(prob, idx, axis=-1)
    if dims.norm_topk:
        gates = gates / gates.sum(axis=-1, keepdims=True)
    return idx.astype(jnp.int32), dims.scale * gates, kept


def route(p, dims: MoEDims, x):
    """``(idx [T, top_k] int32, gates [T, top_k] float32)``."""
    return route_kept(p, dims, x)[:2]


def held_groups(dims: MoEDims) -> slice:
    """The groups that hold any expert held here."""
    e0, n = dims.held
    per = dims.n_routed // dims.n_group
    return slice(e0 // per, (e0 + n - 1) // per + 1)


def swiglu(x, w_g, w_u, w_d):
    return mm(jax.nn.silu(mm(x, w_g)) * mm(x, w_u), w_d)


def sorted_pairs(dims: MoEDims, idx, gates, valid):
    """The (token, pick) pairs sorted by held expert, stably: ``token`` and
    ``gate`` [T * top_k], each pair's row and gate; ``counts`` and ``start``
    [n] int32, held expert ``e``'s group being pairs ``start[e] : start[e] +
    counts[e]``. The pairs of absent experts and of padding tokens
    (``valid`` [T] false) come last, outside every group."""
    e0, n = dims.held
    k = idx.shape[1]
    local = idx - e0
    here = (local >= 0) & (local < n) & valid[:, None]
    flat = jnp.where(here, local, n).reshape(-1)             # n = "not here"
    order = jnp.argsort(flat, stable=True)
    token = (order // k).astype(jnp.int32)
    gate = gates.reshape(-1)[order]
    counts = jnp.zeros((n + 1,), jnp.int32).at[flat].add(1)[:n]
    return token, gate, counts, jnp.cumsum(counts) - counts


def experts_sorted(p, dims: MoEDims, x, idx, gates, valid):
    """What the held routed experts add, ``[T, dim]`` float32, and the tokens
    each of them got, ``[n]`` int32, by a loop with one round a tile of
    :data:`TILE` sorted rows: gather the tile's rows, three products, add
    the gated result back. ``valid`` [T] masks padding tokens out: they
    reach no expert. :func:`moe` calls it no more (an expert with two tiles
    was read twice, and no round's weights could load before it began): it
    is the form the tests hold both kernels to and the baseline that the
    benchmark's probes time (``benchmarks/tools/moe_small_probe.py``,
    ``hyb_probe.py``, ``moe_grouped_probe.py``)."""
    T, k = idx.shape
    token, gate, counts, group_start = sorted_pairs(dims, idx, gates, valid)
    tiles = (counts + TILE - 1) // TILE
    tile_end = jnp.cumsum(tiles)                             # [n]
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


def experts_grouped(p, dims: MoEDims, x, idx, gates, valid):
    """:func:`experts_sorted`'s answer for a forward of more than one tile
    of tokens, summed in the same order: the same sorted pairs through ONE
    kernel a layer that walks the touched experts, each expert's matrices
    read once whatever its rows (``ops/pallas/expert_stream.py``,
    ``expert_groups``)."""
    token, gate, counts, start = sorted_pairs(dims, idx, gates, valid)
    y = expert_stream.expert_groups(
        x, token, gate, start, counts, p["w_g"], p["w_u"], p["w_d"],
        interpret=pallas_ops.interpret_mode())
    return y, counts


def row_tiles(load):
    """Products of sorted rows the grouped form runs for tokens-per-expert
    ``load`` (any shape, numpy or jax): one for every
    ``expert_stream.GROUP_ROWS`` rows of a group, begun."""
    return -(-load // expert_stream.GROUP_ROWS)


def small_forward(T: int) -> bool:
    """Whether a forward of ``T`` tokens takes :func:`experts_streamed`: its
    tokens fit one tile. A shape, known when the program is traced."""
    return T <= TILE


def held_gates(dims: MoEDims, idx, gates, valid):
    """The picks as a matrix: ``gate [T, n]`` float32, the gate of token ``t``
    on held expert ``e``, ``expert_stream.NOT_PICKED`` where it did not pick
    it or is padding; and the tokens each held expert got, ``[n]`` int32."""
    e0, n = dims.held
    local = idx - e0
    here = (local >= 0) & (local < n) & valid[:, None]
    hit = here[:, :, None] & (local[:, :, None]
                              == jnp.arange(n, dtype=jnp.int32))   # [T, k, n]
    gate = jnp.where(hit.any(axis=1),
                     jnp.where(hit, gates[:, :, None], 0.0).sum(axis=1),
                     expert_stream.NOT_PICKED)
    return gate, hit.sum(axis=(0, 1)).astype(jnp.int32)


def experts_streamed(p, dims: MoEDims, x, idx, gates, valid):
    """:func:`experts_sorted`'s answer for a forward whose tokens fit one
    tile, summed in expert order: all ``T`` rows stay in place and run
    through each TOUCHED held expert, the gate of a row that did not pick it
    (or is padding) selecting its product out
    (``ops/pallas/expert_stream.py``). No sort, gather or scatter."""
    gate, counts = held_gates(dims, idx, gates, valid)
    y = expert_stream.expert_stream(
        x, gate, p["w_g"], p["w_u"], p["w_d"],
        interpret=pallas_ops.interpret_mode())
    return y, counts


def moe(p, dims: MoEDims, x, valid, scope: str = "moe"):
    """The layer's output here for tokens ``x`` [T, dim]: ``(y [T, dim]
    float32, counters)``; ``counters`` = tokens per held expert ``[n]`` and
    the zero-compute picks of the valid tokens (a scalar), int32; where the
    model picks groups first, also ``group_hits``: the valid tokens that
    kept a group with experts held here."""
    with jax.named_scope(scope + ".route"):
        idx, gates, kept = route_kept(p, dims, x)
    with jax.named_scope(scope + ".experts"):
        experts = (experts_streamed if small_forward(x.shape[0])
                   else experts_grouped)
        routed, load = experts(p, dims, x, idx, gates, valid)
    if dims.shared_dim:
        with jax.named_scope(scope + ".shared"):
            routed = routed + swiglu(x, **p["shared"])
    counters = {"expert_load": load, "zero_picks": jnp.int32(0)}
    if kept is not None:
        hits = kept[:, held_groups(dims)].any(axis=1) & valid
        counters["group_hits"] = hits.sum().astype(jnp.int32)
    if not dims.n_zero:
        return routed, counters
    with jax.named_scope(scope + ".zero"):
        is_zero = idx >= dims.n_routed
        zero_gate = jnp.where(is_zero, gates, 0.0).sum(axis=-1)
        zero = zero_gate[:, None] * x.astype(jnp.float32)
        zero_picks = (is_zero & valid[:, None]).sum().astype(jnp.int32)
    return routed + zero, {**counters, "zero_picks": zero_picks}
