"""An extension's walk over a span of cached keys and values in one kernel:
each REAL row of the batch reads the blocks of its OWN reach, where the cache
holds them, and a padding row reads nothing.

What it replaces for ``ops/gqa.extend`` and ``ops/gqa.cross_rows`` is the
``fori_loop`` of ``ops/attention.attend_over_blocks`` over a ``vmap`` of
``dynamic_slice``: one ``n_blocks`` for the whole padded batch (the longest
row's), and in each of its rounds a block of every row, padding rows among
them, sliced out of the span, COPIED in HBM (one fusion a row: 2.6 MB of
``bf16[1, 1, 512, 2560]`` at Phi-4-mini-flash's widths) and then multiplied.
At 1.5 real rows of 8 that walk moved a tenth of what it read for a real row's
own reach.

Here the grid walks the batch's ROWS; a row's slot and its round count are
prefetched scalars, and inside a step a loop walks the row's own blocks (a
padding row's count is 0: no copy, no product, a result of zeros): the block
arrives by a double-buffered copy from where the cache holds it, and each
GROUP of key heads (``groups``: the lanes of a block that hold the group's
keys, and the lanes that hold the values behind them) is folded into the
group's running softmax by ``chunk_attend.fold``, which is
``ops/attention._accum_block`` for one head with its roundings: scores
accumulated in float32 and scaled, the causal mask from the positions, a
masked score ``_NEG``, ``p`` cast to the values' type before ``p . v``. ``m``,
``l`` and ``o`` of every group stay in VMEM over the row's blocks; ``o / l``
leaves once a row. A block of the span is read once for all of its heads.

The caller lays the queries out a group at a time (``ops/gqa._walk``): a group
of ONE key head takes that head's folded queries as they are (its keys' lanes
may start inside a 128-lane row: 192-wide keys); a differential PAIR, whose two
key heads lie side by side and share the pair's two values, takes its two
heads' queries block-diagonally, zeros under the other head's keys, so that
one product scores both and one gives both the values: a product's exact
zeros change no sum, and two 64-wide heads fill one 128-lane row.

A grid of rows x rounds with the block's index held past a row's count (the
form the issue sketched) was not built: its steps are static, 8 x 66 at the
cell's capacity, and 500 empty steps a reader cost more than the walk they
would carry. A loop inside the row's step takes the row's count as it is.

Selection (ops/pallas/__init__.py's contract, without a flag): every walk of
a causal stack's extension and of a cross mixer goes here, compiled on a TPU
and under the interpreter elsewhere; ``_accum_block`` under
``attend_over_blocks`` stays the tests' reference and the path of every chunk
walk, of the rings and of the block-diffusion forward.
"""

from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from predictionio_tpu.ops.attention import _NEG
from predictionio_tpu.ops.pallas.chunk_attend import LANES, fold

#: a group of key heads: (first lane of its keys, lanes of keys, first lane
#: of its values, lanes of values) in a cached row
Group = Tuple[int, int, int, int]


def _walk_kernel(slots_ref, rounds_ref, q_ref, pos_ref, span_hbm, o_ref, buf,
                 m_ref, l_ref, sem, *, groups, scale):
    row = pl.program_id(0)
    slot, rounds = slots_ref[row], rounds_ref[row]
    block = buf.shape[1]
    M = q_ref.shape[2]

    def copy(j, b):
        at = pl.ds(pl.multiple_of(j * block, block), block)
        return pltpu.make_async_copy(span_hbm.at[slot, at, :], buf.at[b],
                                     sem.at[b])

    @pl.when(rounds > 0)
    def _():
        copy(0, 0).start()

    m_ref[...] = jnp.full_like(m_ref, _NEG)
    l_ref[...] = jnp.zeros_like(l_ref)
    o_ref[...] = jnp.zeros_like(o_ref)
    q_pos = pos_ref[0]                                          # [M, 1]
    k_in_block = jax.lax.broadcasted_iota(jnp.int32, (M, block), 1)

    def one_block(j, _):
        b = j % 2

        @pl.when(j + 1 < rounds)
        def _():
            copy(j + 1, 1 - b).start()

        copy(j, b).wait()  # graftlint: disable=JT12 — a DMA's semaphore inside the kernel, no thread
        mask = q_pos >= j * block + k_in_block
        for g, (k_at, k_wide, v_at, v_wide) in enumerate(groups):
            m_ref[g], l_ref[g], o_ref[0, g] = fold(
                q_ref[0, g], buf[b, :, k_at:k_at + k_wide],
                buf[b, :, v_at:v_at + v_wide], m_ref[g], l_ref[g],
                o_ref[0, g], mask, scale)

    jax.lax.fori_loop(0, rounds, one_block, None)
    o_ref[0] = o_ref[0] / jnp.maximum(l_ref[...], 1e-30)


def span_walk(q, q_pos, span, slots, rounds, *, groups: Sequence[Group],
              block: int, scale: float, interpret=False):
    """Causal attention of each row's queries over ITS slot of ``span``
    [slots, P, width] (a position's keys and values side by side, in the
    type of ``q``), read where it lies: ``q`` [B, G, M, Dk] (a group's
    queries, laid out as the group's ``Dk`` key lanes are) at positions
    ``q_pos`` [B, M, 1] (int32: a query sees the keys at or before its
    own), ``slots`` [B] and ``rounds`` [B] int32: row ``b`` walks blocks ``0
    .. rounds[b] - 1`` of ``block`` positions of slot ``slots[b]``; ``groups``
    (static, one :data:`Group` a group, all of one ``Dk`` and one ``Dv``).
    ``[B, G, M, Dv]`` float32, normalised; zeros for a row of no rounds."""
    B, G, M, Dk = q.shape
    Dv = groups[0][3]
    width = span.shape[-1]
    item = jnp.dtype(span.dtype).itemsize
    if not interpret and width % LANES:
        raise ValueError(
            f"a cached row of {width} values is not whole rows of {LANES} "
            "lanes: the copy of a block cannot be compiled for a TPU")

    def row(b, slots, rounds):
        return b, 0, 0, 0

    vm, f32 = pltpu.VMEM, jnp.float32
    call = pl.pallas_call(
        functools.partial(_walk_kernel, groups=tuple(groups), scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, G, M, Dk), row, memory_space=vm),
                pl.BlockSpec((1, M, 1), lambda b, slots, rounds: (b, 0, 0),
                             memory_space=vm),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, G, M, Dv), row, memory_space=vm),
            scratch_shapes=[
                pltpu.VMEM((2, block, width), span.dtype),
                pltpu.VMEM((G, M, 1), f32), pltpu.VMEM((G, M, 1), f32),
                pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=jax.ShapeDtypeStruct((B, G, M, Dv), f32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # two buffers of a block, of a row's queries and of its result;
            # a group's keys and values cut from the block; its scores and
            # probabilities in float32 and in the values' type; room for
            # the compiler's own
            vmem_limit_bytes=(
                2 * block * width * item
                + 2 * G * M * (Dk * item + Dv * 4 + 8 * 128)
                + block * (Dk + Dv) * item + 4 * M * block * 4
                + (8 << 20))),
        interpret=interpret,
        # %span_walk.N in a device trace
        name="span_walk",
    )
    return call(jnp.asarray(slots, jnp.int32), jnp.asarray(rounds, jnp.int32),
                q, q_pos, span)
