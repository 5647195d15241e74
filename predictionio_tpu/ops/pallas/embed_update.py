"""Fused embedding-update: the rowwise-adagrad table scatter as one
Pallas gather→combine→write pass.

What it replaces: the table half of ``ops.twotower._rowwise_adagrad``
— ``table.at[idx].add(-scale[:, None] * grad)`` — measured at
~0.62 ms/table/step at the stretch config (B=8192 rows into
[1M, 128]), the largest non-matmul term of the two-tower step. The
scalar-thin accumulator ops were measured nearly free there and STAY
in XLA; this kernel fuses the coefficient multiply, the
duplicate-index combine, and the read-modify-write of the touched rows
into one VMEM-resident pass over ``tile`` rows at a time, with the
table aliased in place (``input_output_aliases``).

Mechanics per grid step (tile of T batch rows):

  1. wait the PREVIOUS tile's write DMAs (a later tile may touch the
     same row — the wait is the cross-tile duplicate ordering);
  2. start + wait T concurrent row-read DMAs ``table[idx[k]] → VMEM``;
  3. in-tile duplicates: ``adj = (idx == idx^T)`` routes every
     duplicate's delta to EVERY holder of that row
     (``rows += adj @ (-scale * grad)``), so duplicate holders carry
     byte-identical contents and their concurrent write-backs are
     benign regardless of DMA completion order;
  4. start T row-write DMAs back to the aliased output.

Semantics match the XLA reference at <=1e-5 in f32 (scale is computed
from the fully-updated accumulator BEFORE the kernel, read-after-add,
exactly like the reference; only floating-point summation order
differs for duplicates).

DEFAULT OFF (``TwoTowerConfig.embed_update_kernel = "off"``), the
repo's measured-rejection discipline applied prospectively: the XLA
scatter's floor is its ~75 ns/row ISSUE RATE (measured before this
repo's chip runs; optimization_barrier, sorted-indices and
fused-accumulator-column forms all tried and rejected with numbers,
``_rowwise_adagrad`` docstring), and this kernel's per-row DMA
round-trips amortize only ``tile``-wide, so the analytic projection at
B=8192 is AT BEST parity (2 x 8192 row-DMAs/step vs 2 x 8192 scatter
row-issues) — it must WIN on-chip before becoming default: set
``embed_update_kernel: "on"`` in the algorithm's params for the
comparison and record the numbers either way.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: batch rows per grid step = concurrent row DMAs in flight
DEFAULT_TILE = 8


def _apply_kernel(idx_sref, idxr_ref, idxc_ref, grad_ref, scale_ref,
                  table_ref, out_ref, rows, rsem, wsem, *, T):
    t = pl.program_id(0)
    nt = pl.num_programs(0)

    def write_copy(tile, k):
        r = idx_sref[tile * T + k]
        return pltpu.make_async_copy(rows.at[pl.ds(k, 1)],
                                     out_ref.at[pl.ds(r, 1)], wsem.at[k])

    @pl.when(t > 0)
    def _():
        for k in range(T):
            write_copy(t - 1, k).wait()

    # reads go through OUT_REF, not table_ref: they are the same buffer
    # on TPU (input_output_aliases), but the interpreter emulates the
    # alias as a copy — a table_ref read there would miss earlier
    # tiles' writes and silently drop cross-tile duplicate updates
    for k in range(T):
        r = idx_sref[t * T + k]
        pltpu.make_async_copy(out_ref.at[pl.ds(r, 1)],
                              rows.at[pl.ds(k, 1)], rsem.at[k]).start()
    for k in range(T):
        r = idx_sref[t * T + k]
        pltpu.make_async_copy(out_ref.at[pl.ds(r, 1)],
                              rows.at[pl.ds(k, 1)], rsem.at[k]).wait()

    # route every in-tile duplicate's delta to every holder of the row:
    # holders end up byte-identical, so their concurrent write-backs
    # commute (see module docstring, step 3)
    adj = (idxr_ref[...] == idxc_ref[...]).astype(jnp.float32)   # [T, T]
    delta = -(scale_ref[...] * grad_ref[...])                    # [T, E] f32
    # HIGHEST: the TPU's default matmul precision rounds delta to bf16,
    # which the f32 XLA scatter this kernel stands in for does not
    rows[...] += jnp.dot(adj, delta, preferred_element_type=jnp.float32,
                         precision=jax.lax.Precision.HIGHEST)

    for k in range(T):
        write_copy(t, k).start()

    @pl.when(t == nt - 1)
    def _():
        for k in range(T):
            write_copy(t, k).wait()


def _scatter_apply(table, idx, grad, scale, *, tile, interpret):
    """``table[idx[b]] += -scale[b] * grad[b]`` (duplicate-safe) via
    the DMA kernel; pads the batch up to the tile multiple with
    zero-delta rows aimed at row 0 (a += 0 no-op)."""
    B, E = grad.shape
    T = int(tile)
    Bp = -(-B // T) * T
    pad = Bp - B
    idx32 = idx.astype(jnp.int32)
    if pad:
        idx32 = jnp.pad(idx32, (0, pad))
        grad = jnp.pad(grad, ((0, pad), (0, 0)))
        scale = jnp.pad(scale, (0, pad))
    grad = grad.astype(jnp.float32)
    idxr = idx32.reshape(Bp, 1)
    # one [1, T] row per grid step, the step axis squeezed: a (1, T)
    # block of a [1, Bp] array is not a legal TPU tile
    idxc = idx32.reshape(Bp // T, 1, T)
    scale2 = scale.astype(jnp.float32).reshape(Bp, 1)
    vm = pltpu.VMEM
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Bp // T,),
        in_specs=[
            pl.BlockSpec((T, 1), lambda t, idx_s: (t, 0), memory_space=vm),
            pl.BlockSpec((None, 1, T), lambda t, idx_s: (t, 0, 0),
                         memory_space=vm),
            pl.BlockSpec((T, E), lambda t, idx_s: (t, 0), memory_space=vm),
            pl.BlockSpec((T, 1), lambda t, idx_s: (t, 0), memory_space=vm),
            pl.BlockSpec(memory_space=pl.ANY),     # table: DMA'd by row
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((T, E), jnp.float32),
            pltpu.SemaphoreType.DMA((T,)),
            pltpu.SemaphoreType.DMA((T,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_apply_kernel, T=T),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(table.shape, table.dtype),
        # operand 5 counting the scalar-prefetch idx: the table updates
        # in place — no [N, E] copy per step
        input_output_aliases={5: 0},
        interpret=interpret,
    )(idx32, idxr, idxc, grad, scale2, table)


def pallas_rowwise_adagrad(table, acc, idx, grad, lr, eps=1e-8,
                           *, interpret=False, tile=DEFAULT_TILE):
    """Drop-in for ``ops.twotower._rowwise_adagrad`` with the table
    scatter fused into :func:`_scatter_apply`; the accumulator
    scatter-add and the read-after-add scale stay XLA (measured nearly
    free — scalar-thin rows)."""
    g2 = jnp.mean(grad * grad, axis=-1)              # [B]
    acc = acc.at[idx].add(g2)
    scale = lr / jnp.sqrt(acc[idx] + eps)            # read after add
    table = _scatter_apply(table, idx, grad, scale,
                           tile=tile, interpret=interpret)
    return table, acc
