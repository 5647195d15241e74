"""The expert layer of a small forward: every row through every TOUCHED
expert, the experts' weights streamed, as one Pallas kernel.

What it replaces, for a forward whose tokens fit one tile
(``ops/moe.TILE``): the sorted-tile loop (``ops/moe.experts_sorted``), which
at 32 tokens a call sorts 256 (token, pick) pairs, then runs one round an
expert with two or three real rows in it: gather 64 rows, three products
whose weights cannot start loading before the round begins, scatter-add 64
rows. Here the rows stay where they are. The grid walks the list of touched
experts (scalar prefetch: ``ids``, ``n_touched``), the weights' ``index_map``
picks ``w_g[e]``, ``w_u[e]``, ``w_d[e]`` straight from the stored ``[n, dim,
expert_dim]`` / ``[n, expert_dim, dim]`` arrays, and Pallas' pipeline reads
expert ``e + 1``'s matrices while ``e``'s products run. ``x`` ``[T, dim]``,
the gates ``[T, n]`` and the float32 sum ``[T, dim]`` stay in VMEM for the
whole call. ``expert_dim`` is cut into chunks (:func:`chunk_of`) so that two
buffers of three matrices fit :data:`WEIGHT_VMEM_BYTES`. An expert no row
picked is not read: grid steps past the last touched expert repeat the last
block index (no new read) and skip the products, and a call with no touched
expert at all does not reach the kernel (``lax.cond`` in
:func:`expert_stream`).

A row that did not pick expert ``e`` carries the gate -1 (gates are
probabilities times a positive scale, never negative) and its product is
selected out with ``where``, never multiplied by 0: a huge value in such a
row cannot reach the sum.

The body, :func:`swiglu_rows`, is one expert's SwiGLU over rows in VMEM and
knows nothing of the grid: a grouped form over sorted rows (a chunk's tiles)
can reuse it.

Selection (ops/pallas/__init__.py's contract, without a flag): ``ops/moe.moe``
takes this path for ``T <= TILE``, a static shape; compiled on a TPU, under
the interpreter elsewhere (:func:`predictionio_tpu.ops.pallas.interpret_mode`)
so that tier-1 runs the same code. The chip readings are in
``ops/moe.py``'s docstring and PERF.md (Findings, PR 33).
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


def _stream_call(T, dim, expert_dim, n, dtype, chunk, interpret):
    n_chunks = expert_dim // chunk
    itemsize = jnp.dtype(dtype).itemsize

    def fixed(i, c, ids, n_touched):
        return 0, 0

    def columns(i, c, ids, n_touched):
        # past the last touched expert: the block just read, no new copy
        return ids[i], 0, jnp.where(i < n_touched[0], c, n_chunks - 1)

    def rows(i, c, ids, n_touched):
        return ids[i], jnp.where(i < n_touched[0], c, n_chunks - 1), 0

    vm = pltpu.VMEM
    return pl.pallas_call(
        _expert_stream_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n, n_chunks),
            in_specs=[
                pl.BlockSpec((T, dim), fixed, memory_space=vm),
                pl.BlockSpec((T, n), fixed, memory_space=vm),
                pl.BlockSpec((None, dim, chunk), columns, memory_space=vm),
                pl.BlockSpec((None, dim, chunk), columns, memory_space=vm),
                pl.BlockSpec((None, chunk, dim), rows, memory_space=vm),
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
    touched = (gate >= 0).any(axis=0)                            # [n]
    n_touched = touched.sum().astype(jnp.int32)
    # the j-th touched expert, in expert order, is the first whose running
    # count of touched experts reaches j + 1; the list's tail repeats its
    # last entry
    running = jnp.cumsum(touched.astype(jnp.int32))
    j = jnp.minimum(jnp.arange(n, dtype=jnp.int32), n_touched - 1)
    ids = (running[None, :] <= j[:, None]).sum(axis=1).astype(jnp.int32)
    call = _stream_call(Tp, dim, expert_dim, n, w_g.dtype, chunk, interpret)
    y = jax.lax.cond(
        n_touched > 0,
        lambda: call(ids, n_touched[None], x, gate, w_g, w_u, w_d),
        lambda: jnp.zeros((Tp, dim), jnp.float32))
    return y[:T] if Tp != T else y
