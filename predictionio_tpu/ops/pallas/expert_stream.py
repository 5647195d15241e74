"""The expert layer's two kernels: the experts' weights streamed once a
forward, over the TOUCHED experts only.

Both replace the sorted-tile loop (``ops/moe.experts_sorted``): one round a
tile of 64 sorted rows — gather 64 rows, three products whose weights cannot
start loading before the round begins, scatter-add 64 rows — and an expert
with two tiles read twice. Both grids walk the list of touched experts
(scalar prefetch: ``ids``, ``n_touched``; :func:`touched_list`) x the column
chunks of ``expert_dim`` (:func:`chunk_of`: two buffers of three matrices fit
:data:`WEIGHT_VMEM_BYTES`); the weights' ``index_map`` picks ``w_g[e]``,
``w_u[e]``, ``w_d[e]`` straight from the stored ``[n, dim, expert_dim]`` /
``[n, expert_dim, dim]`` arrays (:func:`weight_specs`), and Pallas' pipeline
reads expert ``e + 1``'s matrices while ``e``'s products run. An expert no row
picked is not read: grid steps past the last touched expert repeat the last
block index (no new read) and skip the products, and a call with no touched
expert at all does not reach the kernel (``lax.cond``). The body,
:func:`swiglu_rows`, is one expert's SwiGLU over rows in VMEM and knows
nothing of the grid.

``expert_stream`` (a forward whose tokens fit one tile, ``ops/moe.TILE``: at
32 tokens a call the loop ran one round an expert with two or three real rows
in it). The rows stay where they are: ``x`` ``[T, dim]``, the gates ``[T, n]``
and the float32 sum ``[T, dim]`` stay in VMEM for the whole call and EVERY
row runs through every touched expert. A row that did not pick expert ``e``
carries the gate -1 (gates are probabilities times a positive scale, never
negative) and its product is selected out with ``where``, never multiplied
by 0: a huge value in such a row cannot reach the sum.

``expert_groups`` (more tokens: a prefill chunk's 512). The (token, pick)
pairs arrive SORTED by held expert (``ops/moe.sorted_pairs``): a token list
and a gate list in scalar memory, each held expert's group by its start and
count. ``x`` is copied into VMEM once a call, in float32 (a row is gathered
one 32-bit row at a time); inside a grid step a loop takes that expert's
group :data:`GROUP_ROWS` rows a product: gather the rows, :func:`swiglu_rows`,
add each gated row of the product to its token's row of the float32 sum in
VMEM, which is copied out once at the end. Work grows with the pairs that
are here; a pair inside no group (an absent expert's, a padding token's) is
never read, so a huge value in a padding row cannot reach the sum. Under
column chunks an expert's rows pass under each chunk. No capacity: all 512
tokens on one expert are four products under one read.

Selection (ops/pallas/__init__.py's contract, without a flag): ``ops/moe.moe``
takes ``expert_stream`` for ``T <= TILE`` and ``expert_groups`` above it, a
static shape; compiled on a TPU, under the interpreter elsewhere
(:func:`predictionio_tpu.ops.pallas.interpret_mode`) so that tier-1 runs the
same code. The chip readings are in ``ops/moe.py``'s docstring and PERF.md
(Findings, PRs 33 and 36): 64 and 128 rows a product read alike at all three
configurations' widths (256 read 3-12% slower in PR 35's builder's probe);
128 halves the products under a skew.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: VMEM the double-buffered chunks of the three matrices may take: 2 x 3 x
#: dim x chunk x itemsize
WEIGHT_VMEM_BYTES = 40 << 20

#: the gate of a row that did not pick the expert
NOT_PICKED = -1.0

#: sorted rows one product of the grouped kernel takes
GROUP_ROWS = 128


def chunk_of(dim: int, expert_dim: int, itemsize: int) -> int:
    """Columns of ``expert_dim`` a grid step reads: the largest divisor that
    is a whole number of 128-lane rows and whose two buffers of three
    matrices fit :data:`WEIGHT_VMEM_BYTES`; all of ``expert_dim`` where it is
    not a multiple of 128 (the tests' small sizes)."""
    if expert_dim % 128:
        return expert_dim
    fits = [c for c in range(128, expert_dim + 1, 128)
            if expert_dim % c == 0
            and 2 * 3 * dim * c * itemsize <= WEIGHT_VMEM_BYTES]
    return max(fits) if fits else 128


def swiglu_rows(x, w_g, w_u, w_d):
    """``(silu(x w_g) * (x w_u)) w_d`` for rows ``x`` [R, dim] held in VMEM
    and one expert's matrices (or the same columns of ``w_g`` and ``w_u`` and
    rows of ``w_d``: the chunks' results add up): product inputs in the
    weights' type, float32 accumulation, ``[R, dim]`` float32."""
    h = (jax.nn.silu(jnp.dot(x, w_g, preferred_element_type=jnp.float32))
         * jnp.dot(x, w_u, preferred_element_type=jnp.float32))
    return jnp.dot(h.astype(w_d.dtype), w_d,
                   preferred_element_type=jnp.float32)


def _expert_stream_kernel(ids_ref, n_ref, x_ref, gate_ref, wg_ref, wu_ref,
                          wd_ref, y_ref):
    i, c = pl.program_id(0), pl.program_id(1)

    @pl.when((i == 0) & (c == 0))
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(i < n_ref[0])
    def _():
        lane = jax.lax.broadcasted_iota(jnp.int32, gate_ref.shape, 1)
        g = jnp.max(jnp.where(lane == ids_ref[i], gate_ref[...], NOT_PICKED),
                    axis=1, keepdims=True)                       # [T, 1]
        out = swiglu_rows(x_ref[...], wg_ref[...], wu_ref[...], wd_ref[...])
        y_ref[...] += jnp.where(g >= 0, g * out, 0.0)


def weight_specs(dim, chunk, n_chunks):
    """The block specs of ``w_g``, ``w_u`` (``[n, dim, expert_dim]``) and
    ``w_d`` (``[n, expert_dim, dim]``) on a grid of (touched experts, column
    chunks) whose first two prefetched scalars are :func:`touched_list`'s:
    step ``(i, c)`` holds chunk ``c`` of expert ``ids[i]``; steps past the
    last touched expert repeat the block just read, so nothing is copied
    for them."""

    def columns(i, c, ids, n_touched, *_):
        return ids[i], 0, jnp.where(i < n_touched[0], c, n_chunks - 1)

    def rows(i, c, ids, n_touched, *_):
        return ids[i], jnp.where(i < n_touched[0], c, n_chunks - 1), 0

    vm = pltpu.VMEM
    return [pl.BlockSpec((None, dim, chunk), columns, memory_space=vm),
            pl.BlockSpec((None, dim, chunk), columns, memory_space=vm),
            pl.BlockSpec((None, chunk, dim), rows, memory_space=vm)]


def touched_list(touched):
    """``(ids [n] int32, n_touched [1] int32)`` of ``touched`` [n] bool: the
    j-th touched expert, in expert order, is the first whose running count
    of touched experts reaches j + 1; the list's tail repeats its last
    entry."""
    n = touched.shape[0]
    n_touched = touched.sum().astype(jnp.int32)
    running = jnp.cumsum(touched.astype(jnp.int32))
    j = jnp.minimum(jnp.arange(n, dtype=jnp.int32), n_touched - 1)
    ids = (running[None, :] <= j[:, None]).sum(axis=1).astype(jnp.int32)
    return ids, n_touched[None]


def _where_touched(n_touched, call, padded, T):
    """The first ``T`` rows of ``call()`` (``padded`` = its float32 shape)
    where any expert is touched; zeros, without reaching the kernel, where
    none is."""
    y = jax.lax.cond(n_touched[0] > 0, call,
                     lambda: jnp.zeros(padded, jnp.float32))
    return y[:T]


def _stream_call(T, dim, expert_dim, n, dtype, chunk, interpret):
    n_chunks = expert_dim // chunk
    itemsize = jnp.dtype(dtype).itemsize

    def fixed(i, c, ids, n_touched):
        return 0, 0

    vm = pltpu.VMEM
    return pl.pallas_call(
        _expert_stream_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n, n_chunks),
            in_specs=[
                pl.BlockSpec((T, dim), fixed, memory_space=vm),
                pl.BlockSpec((T, n), fixed, memory_space=vm),
                *weight_specs(dim, chunk, n_chunks),
            ],
            out_specs=pl.BlockSpec((T, dim), fixed, memory_space=vm),
        ),
        out_shape=jax.ShapeDtypeStruct((T, dim), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # two buffers of three chunks, of x, the gates and the sum; the
            # products' float32 intermediates; room for the compiler's own
            vmem_limit_bytes=(2 * 3 * dim * chunk * itemsize
                              + 2 * T * dim * (itemsize + 4)
                              + 2 * T * max(n, 128) * 4
                              + 3 * T * dim * 4 + 4 * T * chunk * 4
                              + (8 << 20))),
        interpret=interpret,
        # the compiled instruction's name (%expert_stream.N): how a device
        # trace's reader finds this kernel's events
        name="expert_stream",
    )


def expert_stream(x, gate, w_g, w_u, w_d, *, interpret=False):
    """What the held experts add for rows ``x`` [T, dim]: ``[T, dim]``
    float32. ``gate`` [T, n] float32: the gate of row ``t`` on held expert
    ``e``, :data:`NOT_PICKED` where it did not pick it (or is padding)."""
    T, dim = x.shape
    n, _, expert_dim = w_g.shape
    chunk = chunk_of(dim, expert_dim, w_g.dtype.itemsize)
    Tp = -(-T // 16) * 16           # whole sublane groups of either type
    x = x.astype(w_g.dtype)
    if Tp != T:
        x = jnp.pad(x, ((0, Tp - T), (0, 0)))
        gate = jnp.pad(gate, ((0, Tp - T), (0, 0)),
                       constant_values=NOT_PICKED)
    ids, n_touched = touched_list((gate >= 0).any(axis=0))
    call = _stream_call(Tp, dim, expert_dim, n, w_g.dtype, chunk, interpret)
    return _where_touched(
        n_touched, lambda: call(ids, n_touched, x, gate, w_g, w_u, w_d),
        (Tp, dim), T)


def _expert_groups_kernel(ids_ref, n_ref, start_ref, count_ref, token_ref,
                          gate_ref, x_hbm, wg_ref, wu_ref, wd_ref, y_hbm,
                          x_ref, y_ref, rows_ref, out_ref, sem):
    R = rows_ref.shape[0]           # sorted rows a product
    i, c = pl.program_id(0), pl.program_id(1)
    last = (i == pl.num_programs(0) - 1) & (c == pl.num_programs(1) - 1)

    @pl.when((i == 0) & (c == 0))
    def _():
        copy = pltpu.make_async_copy(x_hbm, x_ref, sem)
        copy.start()
        y_ref[...] = jnp.zeros_like(y_ref)
        copy.wait()  # graftlint: disable=JT12 — a DMA's semaphore inside the kernel, no thread

    @pl.when(i < n_ref[0])
    def _():
        e = ids_ref[i]
        count = count_ref[e]

        def product(t, _):
            first = start_ref[e] + t * R
            here = jnp.minimum(count - t * R, R)

            def gather(j, _):
                rows_ref[pl.ds(j, 1), :] = x_ref[
                    pl.ds(token_ref[first + j], 1), :]

            jax.lax.fori_loop(0, here, gather, None)
            # rows past ``here`` hold what an earlier product left: each
            # row's product is its own, and no list sends theirs anywhere
            out_ref[...] = swiglu_rows(rows_ref[...].astype(wg_ref.dtype),
                                       wg_ref[...], wu_ref[...], wd_ref[...])

            def add(j, _):
                at = pl.ds(token_ref[first + j], 1)
                y_ref[at, :] = (y_ref[at, :]
                                + gate_ref[first + j] * out_ref[pl.ds(j, 1), :])

            jax.lax.fori_loop(0, here, add, None)

        jax.lax.fori_loop(0, pl.cdiv(count, R), product, None)

    @pl.when(last)
    def _():
        copy = pltpu.make_async_copy(y_ref, y_hbm, sem)
        copy.start()
        copy.wait()  # graftlint: disable=JT12 — a DMA's semaphore inside the kernel, no thread


def _groups_call(T, dim, expert_dim, n, dtype, chunk, interpret):
    n_chunks = expert_dim // chunk
    itemsize = jnp.dtype(dtype).itemsize
    f32 = jnp.float32
    return pl.pallas_call(
        _expert_groups_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n, n_chunks),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pl.ANY),
                *weight_specs(dim, chunk, n_chunks),
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((T, dim), f32), pltpu.VMEM((T, dim), f32),
                pltpu.VMEM((GROUP_ROWS, dim), f32),
                pltpu.VMEM((GROUP_ROWS, dim), f32),
                pltpu.SemaphoreType.DMA(()),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((T, dim), f32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # two buffers of three chunks; x and the sum once each; a
            # product's rows in float32 and in the weights' type, its
            # result and its float32 intermediates; room for the compiler's
            vmem_limit_bytes=(2 * 3 * dim * chunk * itemsize
                              + 2 * T * dim * 4
                              + GROUP_ROWS * dim * (3 * 4 + itemsize)
                              + 4 * GROUP_ROWS * chunk * 4
                              + (8 << 20))),
        interpret=interpret,
        # %expert_groups.N in a device trace
        name="expert_groups",
    )


def expert_groups(x, token, gate, start, count, w_g, w_u, w_d, *,
                  interpret=False):
    """What the held experts add for rows ``x`` [T, dim], ``[T, dim]``
    float32, from the (token, pick) pairs SORTED by held expert: ``token``
    [P] int32 and ``gate`` [P] float32, the row and the gate of each pair;
    held expert ``e``'s group is pairs ``start[e] : start[e] + count[e]``
    (``[n]`` int32 each). A pair inside no group (an absent expert's, a
    padding token's) is never read. No capacity: a group of any length runs,
    :data:`GROUP_ROWS` rows a product, under ONE read of its expert."""
    T, dim = x.shape
    n, _, expert_dim = w_g.shape
    chunk = chunk_of(dim, expert_dim, w_g.dtype.itemsize)
    Tp = -(-T // 8) * 8             # whole float32 sublane groups
    x = x.astype(jnp.float32)       # rows are gathered one 32-bit row a time
    if Tp != T:
        x = jnp.pad(x, ((0, Tp - T), (0, 0)))
    ids, n_touched = touched_list(count > 0)
    call = _groups_call(Tp, dim, expert_dim, n, w_g.dtype, chunk, interpret)
    return _where_touched(
        n_touched, lambda: call(ids, n_touched, start, count, token, gate, x,
                                w_g, w_u, w_d), (Tp, dim), T)
