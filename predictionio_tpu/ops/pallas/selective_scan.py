"""The selective scan of a Mamba-1 mixer over one chunk, ``S`` on the chip.

``S_t = exp(delta_t * A) * S_{t-1} + (delta_t x_t) (x) B_t``, ``y_t = S_t
C_t``: ``T`` sequential positions over ``d_inner`` channels of ``d_state``
states each, the decay of every (channel, state) its own, so nothing of it is
a matrix product (``ops/mamba1.py``). XLA's ``lax.scan`` runs a round of a few
fusions a position with ``S`` as the loop's carry in HBM; here a grid step
takes 1,024 channels (eight sublanes of 128 lanes: ONE vector register a
state), keeps their ``d_state`` states in registers across all ``T``
positions, and reads a position's ``x`` and ``delta`` as one register each
and its ``B`` and ``C`` as scalars: what moves is ``x`` and ``delta`` in, ``y``
out, and the state once either way.

Channel ``c`` lies at sublane ``c // (d_inner / 8)``, lane ``c % (d_inner /
8)``: a reshape of the rows as they are, the same for ``x``, ``delta``, ``A``,
``S`` and ``y``. The reference is ``ops/mamba1.scan_steps`` (``lax.scan``
over :func:`ops.mamba1._step`), which is also the form of every shape this
kernel does not take (:func:`takes`)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: channels of one grid step: eight sublanes of 128 lanes
CHANNELS = 1024
#: positions of one round of the kernel's loop
UNROLL = 8


def takes(T: int, d_inner: int) -> bool:
    """Whether the kernel runs this shape: whole groups of 1,024 channels
    and whole rounds of positions."""
    return d_inner % CHANNELS == 0 and T % UNROLL == 0


def _scan_kernel(b_ref, c_ref, x_ref, dt_ref, a_ref, s0_ref, y_ref, s_ref, *,
                 T: int, N: int):
    a = [a_ref[n] for n in range(N)]                        # [8, 128] each

    def step(t, s):
        x_t, dt_t = x_ref[t], dt_ref[t]
        u = dt_t * x_t
        y, out = jnp.zeros_like(x_t), []
        for n in range(N):
            s_n = jnp.exp(dt_t * a[n]) * s[n] + b_ref[t * N + n] * u
            y = y + c_ref[t * N + n] * s_n
            out.append(s_n)
        y_ref[t] = y
        return tuple(out)

    def one_round(r, s):        # the compiler's loops unroll by 1 or whole
        for i in range(UNROLL):
            s = step(r * UNROLL + i, s)
        return s

    s = jax.lax.fori_loop(0, T // UNROLL, one_round,
                          tuple(s0_ref[n] for n in range(N)))
    for n in range(N):
        s_ref[n] = s[n]


def selective_scan(x, dt, a_t, B, C, s0, *, interpret: bool = False):
    """``x``, ``dt`` [T, d_inner] (``dt`` 0 where a position is padding),
    ``a_t`` [d_state, d_inner] (negative), ``B``, ``C`` [T, d_state], ``s0``
    [d_state, d_inner], all float32. ``(y [T, d_inner] without the D term,
    S_T [d_state, d_inner])``."""
    T, inner = x.shape
    N = a_t.shape[0]
    if not takes(T, inner):
        raise ValueError(f"selective_scan takes whole groups of {CHANNELS} "
                         f"channels and {UNROLL} positions: got {T} x {inner}")
    lanes = inner // 8
    f32 = jnp.float32

    def rows(v):                # [.., d_inner] -> [.., 8, d_inner / 8]
        return v.astype(f32).reshape(v.shape[:-1] + (8, lanes))

    def block(lead):
        return pl.BlockSpec((lead, 8, 128), lambda g: (0, 0, g),
                            memory_space=pltpu.VMEM)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    y, s = pl.pallas_call(
        functools.partial(_scan_kernel, T=T, N=N),
        grid=(lanes // 128,),
        in_specs=[smem, smem, block(T), block(T), block(N), block(N)],
        out_specs=[block(T), block(N)],
        out_shape=[jax.ShapeDtypeStruct((T, 8, lanes), f32),
                   jax.ShapeDtypeStruct((N, 8, lanes), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            # two buffers each of a step's x, delta and y rows, A and the
            # state in and out; room for the compiler's own
            vmem_limit_bytes=2 * (3 * T + 3 * N) * CHANNELS * 4 + (8 << 20)),
        interpret=interpret,
        name="selective_scan",
    )(B.astype(f32).reshape(-1), C.astype(f32).reshape(-1), rows(x), rows(dt),
      rows(a_t), rows(s0))
    return y.reshape(T, inner), s.reshape(N, inner)
