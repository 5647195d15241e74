"""Fused dot + top-k: exact retrieval's hot path as one Pallas kernel.

What it replaces: the XLA brute-force scorer (``ops.topk._topk_scores``)
computes the FULL ``[B, I]`` logits matrix — at millions of items that
is the one array the whole retrieval design cannot afford to
materialize in HBM (the JAMPI lesson from PAPERS.md restated for
tall-skinny retrieval matmuls: the matmul is cheap, the intermediate is
not). Here the item table is read ONCE, in the layout it is stored in:
``[D, Ip]`` with the items on the lanes (:func:`to_kernel_layout`, built
once per table), streamed through VMEM in ``[D, bi]`` tiles of thousands
of items (:func:`tile_items`). Each grid step computes its tile's
``[B, bi]`` scores and compares the row maxima with the running k-th
best score; only a tile that can change the top-k is merged — the only
HBM traffic is the item table read and the final ``[B, k]`` pair.

Merge strategy: the running top-k lives UNSORTED in a ``[B, 128]`` VMEM
scratch. A merging tile gives up its candidates best first (row max,
lowest position among equal maxima, retire the slot) for as long as some
row's next candidate beats that row's threshold — a loop with a dynamic
trip count, at most ``k`` + (excluded ids in the tile) rounds. A
candidate replaces the running minimum; the threshold is the new
minimum. The last grid step sorts the scratch into the outputs. Only
max / min / where / iota / reductions — no sort primitive, nothing
Mosaic can't lower. A candidate enters only if it BEATS the threshold,
so among equal scores the earlier tile (lower global id) stays, which
matches ``jax.lax.top_k``'s lowest-index preference across tiles but
not necessarily when a tied pair straddles the k-th place — the
equivalence contract is therefore "identical scores, identical indices
modulo exact score ties" (tests/test_index.py pins it).

Exclusions arrive as GLOBAL item ids (``[B, E]``, -1 padding, the
``ops.topk`` wire format) and are checked against each CANDIDATE's
global id as it is taken from the tile, never against the whole tile;
the zero-padded tail (table padded up to the tile multiple) is masked in
the last tile alone. The skip test therefore runs on raw scores: an
upper bound that can cost a needless merge, never an answer.

Selection contract (ops/pallas/__init__.py): the XLA scorer REMAINS
the reference; ``index/exact.py`` engages this kernel per-index via
:func:`predictionio_tpu.ops.pallas.decide` (``index_kernel``):
compiled on a TPU under ``auto``, interpret-mode on the CPU under
``on`` for tier-1.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from predictionio_tpu.ops.topk import NEG_INF

#: VMEM the double-buffered item tile may take: 2 x D x bi x 4 bytes.
#: 16,384 items at D = 64, 8,192 at D = 128 (:func:`tile_items`). Swept
#: once on a v5e at B = 1, k = 16 (PERF.md, PR 26): 9.4 M x 64 took
#: 3.52 / 3.32 / 3.31 / 3.32 ms at 4,096 / 8,192 / 16,384 / 32,768 items
#: (one pass of the table at the chip's peak rate is 2.94 ms), 4.16 M x
#: 128 took 2.93 / 2.93 / 2.96 ms at 4,096 / 8,192 / 16,384 (2.60 ms):
#: flat from a 2 MiB tile up, so the budget is a ceiling, not a tuning
TILE_VMEM_BYTES = 8 << 20

#: VMEM the tile's ``[B, bi]`` float32 scores may take: full tiles up to
#: B = 16, halved for every doubling of the batch beyond
SCORE_VMEM_BYTES = 1 << 20

#: lanes of the running top-k scratch: one vreg row, so ``k <= MAX_K``
RUN_LANES = 128

#: eligibility caps — beyond these the XLA fallback answers. Inside
#: them the kernel took less device time than ``ops.topk._topk_scores``
#: at every shape timed at 9.4 M x 64 on a v5e (PERF.md, PR 26): B = 1
#: 3.3 against 5.1 ms, B = 32 4.1 against 22.5, B = 128 9.0 against 84.8,
#: B = 128 with k = 128 and E = 64 14.5 against 537
MAX_K = RUN_LANES
MAX_EXCLUDE = 64
MAX_BATCH = 128

#: below every score the merge can meet, NEG_INF included: a retired slot
_RETIRED = -3.0e38


def tile_items(D, n_items, B=1):
    """Items per tile, a power of two: the largest whose double-buffered
    ``[D, bi]`` float32 tile fits :data:`TILE_VMEM_BYTES` and whose
    ``[B, bi]`` scores fit :data:`SCORE_VMEM_BYTES`, at least one
    128-lane row, and no more than the table rounded up to a power of
    two. Every batch's tile divides the ``B = 1`` tile, which is what
    :func:`to_kernel_layout` pads the table to."""
    bi = 128
    while (bi < n_items
           and 2 * (2 * bi) * _sublanes(D) * 4 <= TILE_VMEM_BYTES
           and (2 * bi) * B * 4 <= SCORE_VMEM_BYTES):
        bi *= 2
    return bi


def table_shape(D, n_items, block_items=None):
    """``(Dp, Ip)`` of the table in the kernel's layout."""
    pad_to = int(block_items or tile_items(D, n_items))
    return _sublanes(D), -(-int(n_items) // pad_to) * pad_to


def _sublanes(D):
    """``D`` rounded up to whole float32 sublane groups of 8."""
    return -(-int(D) // 8) * 8


def _lane_chunk(bi):
    """Lanes scored per inner step: 512 keeps a ``[D, chunk]`` operand
    within the vector registers at D = 64."""
    return 512 if bi % 512 == 0 else 128


def _topk_dot_kernel(q_ref, it_ref, excl_ref, s_ref, i_ref, n_ref,
                     sc_ref, rs_ref, ri_ref, thr_ref,
                     *, bi, k, n_valid):
    j = pl.program_id(0)
    last = pl.num_programs(0) - 1
    B = sc_ref.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (B, RUN_LANES), 1)

    @pl.when(j == 0)
    def _():
        rs_ref[...] = jnp.full_like(rs_ref, NEG_INF)
        ri_ref[...] = jnp.full_like(ri_ref, -1)
        thr_ref[...] = jnp.full_like(thr_ref, NEG_INF)
        n_ref[0, 0] = 0

    # the tile's [B, bi] scores, a lane chunk at a time: MXU partial
    # dots with f32 accumulation, kept in VMEM for a merge that mostly
    # never comes; the row maxima ride along
    ch = _lane_chunk(bi)
    q = q_ref[...]

    def score(c, mx):
        at = pl.ds(pl.multiple_of(c * ch, ch), ch)
        s = jnp.dot(q, it_ref[:, at], preferred_element_type=jnp.float32)
        sc_ref[:, at] = s
        return jnp.maximum(mx, s)

    mx = jax.lax.fori_loop(0, bi // ch, score,
                           jnp.full((B, ch), _RETIRED, jnp.float32))
    m0 = jnp.max(mx, axis=1, keepdims=True)

    def beats(m):
        return jnp.max(jnp.where(m > thr_ref[...], 1, 0))

    def kth_best(R):
        return jnp.min(jnp.where(lane < k, R, -_RETIRED), axis=1,
                       keepdims=True)

    @pl.when(beats(m0) > 0)
    def _():
        n_ref[0, 0] += 1
        pos = jax.lax.broadcasted_iota(jnp.int32, (B, bi), 1)
        if n_valid < pl.num_programs(0) * bi:
            # the zero-padded tail can never win a slot
            @pl.when((j + 1) * bi > n_valid)
            def _():
                sc_ref[...] = jnp.where(pos < n_valid - j * bi,
                                        sc_ref[...], _RETIRED)
        ex = excl_ref[...]

        def take(_):
            # every row's best remaining candidate leaves the tile ...
            S = sc_ref[...]
            m = jnp.max(S, axis=1, keepdims=True)
            first = jnp.min(jnp.where(S == m, pos, bi), axis=1,
                            keepdims=True)
            S = jnp.where(pos == first, _RETIRED, S)
            sc_ref[...] = S
            gid = j * bi + first
            # ... and enters where it beats the row's threshold and is
            # not excluded (-1 pads never match a gid >= 0)
            banned = jnp.max(jnp.where(ex == gid, 1, 0), axis=1,
                             keepdims=True) > 0
            enters = (m > thr_ref[...]) & jnp.logical_not(banned)
            R = rs_ref[...]
            out = jnp.min(jnp.where((R == thr_ref[...]) & (lane < k), lane,
                                    RUN_LANES), axis=1, keepdims=True)
            put = (lane == out) & enters
            R = jnp.where(put, m, R)
            rs_ref[...] = R
            ri_ref[...] = jnp.where(put, gid, ri_ref[...])
            thr_ref[...] = kth_best(R)
            return beats(jnp.max(S, axis=1, keepdims=True))

        jax.lax.while_loop(lambda go: go > 0, take, jnp.int32(1))

    @pl.when(j == last)
    def _():
        # sort the running set: k rounds of (row max, lowest id among
        # equal maxima); unfilled slots stay NEG_INF / -1
        big = jnp.iinfo(jnp.int32).max

        def rank(r, carry):
            R, out_s, out_i = carry
            RI = ri_ref[...]
            m = jnp.max(R, axis=1, keepdims=True)
            won = jnp.min(jnp.where(R == m, RI, big), axis=1, keepdims=True)
            R = jnp.where((R == m) & (RI == won), _RETIRED, R)
            real = m > _RETIRED
            out_s = jnp.where(lane == r, jnp.where(real, m, NEG_INF), out_s)
            out_i = jnp.where(lane == r, jnp.where(real, won, -1), out_i)
            return R, out_s, out_i

        R0 = jnp.where(lane < k, rs_ref[...], _RETIRED)
        _, out_s, out_i = jax.lax.fori_loop(
            0, k, rank, (R0, jnp.full((B, RUN_LANES), NEG_INF, jnp.float32),
                         jnp.full((B, RUN_LANES), -1, jnp.int32)))
        s_ref[...] = out_s[:, :k]
        i_ref[...] = out_i[:, :k]


def make_topk_dot(n_items, D, B, k, n_excl, *, block_items=None,
                  interpret=False):
    """Build ``fn(q [B, D], items [Dp, Ip], excl [B, E]) -> (scores
    [B, k], idx [B, k], merged [1, 1])`` for one set of static shapes.

    ``items`` is the table in the kernel's layout
    (:func:`to_kernel_layout` with the same ``block_items``); padded
    and excluded entries never enter, a slot nothing filled comes back
    as ``NEG_INF`` score / -1 index like the XLA scorer's masked
    entries. ``merged`` counts the tiles (of ``fn.tiles``) that were
    merged. ``k`` must be <= ``n_items`` (the caller buckets). ``q``
    and ``excl`` may be host (numpy) arrays: their transfer is then part
    of this one call, and the ``D -> Dp`` pad runs inside it.
    ``block_items`` overrides :func:`tile_items` for the tests."""
    bi = int(block_items or tile_items(D, n_items, B))
    Dp, Ip = table_shape(D, n_items, block_items)
    kernel = functools.partial(
        _topk_dot_kernel, bi=bi, k=int(k), n_valid=int(n_items))
    vm = pltpu.VMEM
    call = pl.pallas_call(
        kernel,
        grid=(Ip // bi,),
        in_specs=[
            pl.BlockSpec((B, Dp), lambda j: (0, 0), memory_space=vm),
            pl.BlockSpec((Dp, bi), lambda j: (0, j), memory_space=vm),
            pl.BlockSpec((B, n_excl), lambda j: (0, 0), memory_space=vm),
        ],
        out_specs=[
            pl.BlockSpec((B, k), lambda j: (0, 0), memory_space=vm),
            pl.BlockSpec((B, k), lambda j: (0, 0), memory_space=vm),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, k), jnp.float32),
            jax.ShapeDtypeStruct((B, k), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((B, bi), jnp.float32),          # the tile's scores
            pltpu.VMEM((B, RUN_LANES), jnp.float32),   # running top-k
            pltpu.VMEM((B, RUN_LANES), jnp.int32),     # ... its ids
            pltpu.VMEM((B, 1), jnp.float32),           # running k-th best
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=2 * Dp * bi * 4 + 8 * B * bi * 4 + (8 << 20)),
        interpret=interpret,
        # the compiled instruction's name (%topk_dot.N): how a device
        # trace's reader finds this kernel's events
        name="topk_dot",
    )

    def fn(q, items, excl):
        if Dp != D:
            q = jnp.pad(q, ((0, 0), (0, Dp - D)))
        return call(q, items, excl)

    fn = jax.jit(fn)
    fn.tiles = Ip // bi
    return fn


@functools.partial(jax.jit, static_argnames=("Dp", "Ip"))
def _empty_table(Dp, Ip):
    return jnp.zeros((Dp, Ip), jnp.float32)


@functools.partial(jax.jit, donate_argnums=0)
def _put_rows(table, rows, at):
    """``table[:D, at:at + len(rows)] = rows.T`` in place."""
    return jax.lax.dynamic_update_slice(table, rows.T, (0, at))


#: bytes per transfer of :func:`to_kernel_layout`: what the build holds
#: on the device beside the table itself (1 << 18 rows at D = 64; at D =
#: 2,560 a slab of 1 << 18 rows was the whole 2 GB table a second time)
_BUILD_BYTES = 64 << 20


def to_kernel_layout(items, block_items=None):
    """The ``[n, D]`` host table as the kernel reads it: ``[Dp, Ip]``
    float32 on the device, items on the lanes, zero-padded to whole
    sublane groups and whole tiles (the kernel masks the tail by
    ``n_valid``). Made ON the device, a slab of rows at a time written
    into the one buffer in place: no second table-sized array exists on
    either side."""
    n, D = items.shape
    table = _empty_table(*table_shape(D, n, block_items))
    # whole lanes' worth of rows: a slab lands at an aligned column
    slab = max(128, _BUILD_BYTES // (4 * D) // 128 * 128)
    for lo in range(0, n, slab):
        rows = items[lo:lo + slab]
        if len(rows) < slab and lo:
            # a ragged last slab would compile a second program
            lo = n - slab
            rows = items[lo:]
        table = _put_rows(table, jnp.asarray(rows, jnp.float32), lo)
        # one slab in flight: the host would otherwise queue every
        # slab's transfer at once, a second table's worth of buffers
        table.block_until_ready()
    return table


def topk_dot(q, items, exclude_idx, k, *, block_items=None,
             interpret=False):
    """One-call form for tests: (scores [B, k], idx [B, k], merged
    tiles) over the ``[n, D]`` ``items`` table."""
    q = jnp.asarray(q, jnp.float32)
    excl = jnp.asarray(exclude_idx, jnp.int32)
    n, D = items.shape
    fn = make_topk_dot(n, D, q.shape[0], k, excl.shape[1],
                       block_items=block_items, interpret=interpret)
    s, i, merged = fn(q, to_kernel_layout(items, block_items), excl)
    return s, i, int(merged[0, 0])
