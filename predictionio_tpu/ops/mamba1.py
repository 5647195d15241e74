"""A Mamba-1 mixer (the selective scan) over a per-session RECURRENT state:
two paths over one set of weights, under ``ops/ssm.py``'s contract.

``[x, z] = a W_in`` (``d_inner`` columns each, no bias); ``x =
silu(conv1d_causal(x))`` (depthwise, ``d_conv`` taps, with bias); ``[dt, B, C]
= x W_x`` (``dt_rank``, ``d_state``, ``d_state`` columns); ``delta =
softplus(dt W_dt + b_dt)`` (``d_inner`` wide: a step a CHANNEL); ``A =
-exp(A_log)`` ``[d_inner, d_state]``; per position ``t``: ``S_t = exp(delta_t
* A) * S_{t-1} + (delta_t x_t) (x) B_t`` (``S`` is ``[d_inner, d_state]``, the
decay differs in every channel AND state, so the recurrence is no masked
product as Mamba-2's is), ``y_t = S_t C_t + D * x_t``; out ``(y * silu(z))
W_out``. Every path also hands out ``y`` itself, BEFORE the gate: the memory
a stack's gated memory units read (:func:`gmu`).

What a session carries is a state of FIXED size: the last ``d_conv - 1`` rows
of ``x`` before the convolution (``conv`` [slots, d_conv - 1, d_inner], the
weights' type) and ``S`` (``ssm`` [slots, d_state, d_inner], float32: the
states lie on the sublanes and the channels on the lanes, as the chip's
vector unit wants them). It stands at ONE position and cannot be rewound
(``models/sessionrec.LatentCache``).

* :func:`prefill_chunk`: a chunk of ONE session from its slot's state, the
  recurrence itself over the chunk's positions (:func:`scan`: the
  ``selective_scan`` kernel, ``ops/pallas/selective_scan.py``, which keeps
  ``S`` in registers across the chunk, where the shape is whole groups of
  1,024 channels; else :func:`scan_steps`, a sequential ``lax.scan`` with
  ``S`` as the loop's carry; the ``[T, d_inner, d_state]`` decays are never
  written out in either). Padding positions take
  a step of ``delta = 0``: decay 1, nothing added;
* :func:`extend`: a few new positions of each of several sessions, all rows'
  states gathered from their slots, stepped together and written back (a
  padding row names the scratch slot and moves nothing).

A call whose first position is the session's position 0 starts from a ZERO
state (``ops/ssm.fresh``). Both give the numbers of :func:`mix_full` (one
position at a time from zeros, in the equations' own layout), the plain form
the tests hold them to.

Matrix products with the weights take their inputs in the weights' type and
accumulate in float32; ``delta``, ``A``, the decays, the scan and the carried
``S`` are float32.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from predictionio_tpu.ops import pallas as plk
from predictionio_tpu.ops.mla import mm
from predictionio_tpu.ops.pallas import selective_scan as scan_kernel
from predictionio_tpu.ops.ssm import fresh, steps_of


@dataclasses.dataclass(frozen=True)
class Mamba1Dims:
    dim: int
    d_inner: int
    d_state: int
    dt_rank: int
    d_conv: int = 4


def init(key, dims: Mamba1Dims, dtype=jnp.float32) -> dict:
    """The family's own initialisation: N(0, 1 / fan_in) matrices, ``A[:, n]
    = n + 1``, ``b_dt`` the inverse softplus of a log-uniform step in [1e-3,
    0.1], ``D`` = 1."""
    d = dims
    k_in, k_out, k_conv, k_x, k_dt, k_b = jax.random.split(key, 6)

    def normal(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                / math.sqrt(fan_in)).astype(dtype)

    step = jnp.exp(jax.random.uniform(k_b, (d.d_inner,), jnp.float32,
                                      math.log(1e-3), math.log(0.1)))
    return {"w_in": normal(k_in, (d.dim, 2 * d.d_inner), d.dim),
            "conv_w": normal(k_conv, (d.d_conv, d.d_inner), d.d_conv),
            "conv_b": jnp.zeros((d.d_inner,), dtype),
            "w_x": normal(k_x, (d.d_inner, d.dt_rank + 2 * d.d_state),
                          d.d_inner),
            "w_dt": normal(k_dt, (d.dt_rank, d.d_inner), d.dt_rank),
            "b_dt": step + jnp.log(-jnp.expm1(-step)),
            "a_log": jnp.log(jnp.broadcast_to(
                jnp.arange(1, d.d_state + 1, dtype=jnp.float32),
                (d.d_inner, d.d_state))),
            "d": jnp.ones((d.d_inner,), jnp.float32),
            "w_out": normal(k_out, (d.d_inner, d.dim), d.d_inner)}


def init_state(dims: Mamba1Dims, n_slots: int, dtype) -> dict:
    d = dims
    return {"conv": jnp.zeros((n_slots, d.d_conv - 1, d.d_inner), dtype),
            "ssm": jnp.zeros((n_slots, d.d_state, d.d_inner), jnp.float32)}


def project(p, dims: Mamba1Dims, a):
    """``(x [..., d_inner] before the convolution, z [..., d_inner])`` of
    the positions ``a`` [..., dim], float32."""
    xz = mm(a, p["w_in"])
    return xz[..., :dims.d_inner], xz[..., dims.d_inner:]


def conv(p, dims: Mamba1Dims, window):
    """``window`` [..., d_conv - 1 + T, d_inner] (the carried rows, then the
    new ones, float32): the ``T`` new positions after the causal depthwise
    convolution and the activation."""
    T = window.shape[-2] - (dims.d_conv - 1)
    w = p["conv_w"].astype(jnp.float32)
    out = p["conv_b"].astype(jnp.float32)
    for k in range(dims.d_conv):
        out = out + w[k] * window[..., k:k + T, :]
    return jax.nn.silu(out)


def select(p, dims: Mamba1Dims, x):
    """``(delta [..., d_inner], B [..., d_state], C [..., d_state])`` of the
    convolved ``x`` [..., d_inner]: what makes the scan selective."""
    d = dims
    dbc = mm(x, p["w_x"])
    delta = jax.nn.softplus(mm(dbc[..., :d.dt_rank], p["w_dt"])
                            + p["b_dt"].astype(jnp.float32))
    return (delta, dbc[..., d.dt_rank:d.dt_rank + d.d_state],
            dbc[..., d.dt_rank + d.d_state:])


def _a(p):
    """``A`` with the states on the sublanes: ``[d_state, d_inner]``."""
    return -jnp.exp(p["a_log"].astype(jnp.float32)).T


def _step(s, a_t, x_t, dt_t, B_t, C_t):
    """One position of the recurrence for states ``s`` [..., d_state,
    d_inner]: ``(S_t, S_t C_t)``."""
    s = (jnp.exp(dt_t[..., None, :] * a_t) * s
         + B_t[..., :, None] * (dt_t * x_t)[..., None, :])
    return s, (s * C_t[..., :, None]).sum(axis=-2)


def scan_steps(x, dt, a_t, B, C, s0):
    """The recurrence over ``x`` [T, d_inner] from ``s0`` [d_state, d_inner]:
    ``dt`` [T, d_inner] (0 where a position is padding), ``a_t`` [d_state,
    d_inner] (negative), ``B``, ``C`` [T, d_state], float32. ``(y [T,
    d_inner] without the ``D`` term, S_T)``. XLA's own loop: the kernel's
    reference, and the form of the shapes it does not take (four positions a
    round: the fastest of 1-32 at 512 x 5,120 on a v5e, ``PERF.md`` PR 53)."""
    def one(s, args):
        return _step(s, a_t, *args)

    s, y = jax.lax.scan(one, s0, (x, dt, B, C), unroll=4)
    return y, s


def scan(x, dt, a_t, B, C, s0):
    """:func:`scan_steps`'s numbers, by the ``selective_scan`` kernel where
    the shape is its own (compiled on a TPU, under the interpreter elsewhere:
    by shape alone, no option)."""
    if scan_kernel.takes(*x.shape):
        return scan_kernel.selective_scan(x, dt, a_t, B, C, s0,
                                          interpret=plk.interpret_mode())
    return scan_steps(x, dt, a_t, B, C, s0)


def _gate_out(p, y, x, z):
    """``(the mixer's output, y with its D term: the memory)``."""
    y = y + p["d"].astype(jnp.float32) * x
    return mm(y * jax.nn.silu(z), p["w_out"]), y


def _scoped(scope: str, part: str):
    return jax.named_scope(f"{scope}.ssm.{part}")


def mix_full(p, dims: Mamba1Dims, a):
    """Every position of ``a`` [T, dim] by the recurrence itself, one
    position at a time from a zero state, ``S`` as ``[d_inner, d_state]``:
    the plain form. ``(out [T, dim], y [T, d_inner] before the gate)``."""
    d = dims
    x, z = project(p, d, a)
    x = conv(p, d, jnp.concatenate(
        [jnp.zeros((d.d_conv - 1, d.d_inner), jnp.float32), x]))
    delta, B, C = select(p, d, x)
    A = -jnp.exp(p["a_log"].astype(jnp.float32))           # [d_inner, N]

    def one(s, args):
        x_t, dt_t, B_t, C_t = args
        s = (jnp.exp(dt_t[:, None] * A) * s
             + (dt_t * x_t)[:, None] * B_t[None, :])
        return s, (s * C_t[None, :]).sum(axis=-1)

    _, y = jax.lax.scan(one, jnp.zeros((d.d_inner, d.d_state), jnp.float32),
                        (x, delta, B, C))
    return _gate_out(p, y, x, z)


def prefill_chunk(p, dims: Mamba1Dims, a, n_valid, offset, state, slot,
                  scope: str = "mamba1"):
    """A chunk ``a`` [C, dim] of ONE session (``n_valid`` real positions, the
    first of them the session's position ``offset``) from its slot's state,
    zeros where ``offset`` is 0. ``(out [C, dim] float32, state, y [C,
    d_inner] before the gate)``, the slot's state now at position ``offset +
    n_valid``."""
    d = dims
    T = a.shape[0]
    with _scoped(scope, "in_proj"):
        x, z = project(p, d, a)
    with _scoped(scope, "conv"):
        held = fresh(jax.lax.dynamic_slice(
            state["conv"], (slot, 0, 0), (1, d.d_conv - 1, d.d_inner))[0],
            offset == 0)
        window = jnp.concatenate([held.astype(jnp.float32), x])
        x = conv(p, d, window)
        # the rows before position n_valid: of this chunk, and of what was
        # carried in where the chunk has fewer than d_conv - 1
        carried = jax.lax.dynamic_slice(
            window, (n_valid, 0), (d.d_conv - 1, d.d_inner))
        conv_state = jax.lax.dynamic_update_slice(
            state["conv"], carried.astype(state["conv"].dtype)[None],
            (slot, 0, 0))
        delta, B, C = select(p, d, x)
    with _scoped(scope, "scan"):
        s0 = fresh(jax.lax.dynamic_slice(
            state["ssm"], (slot, 0, 0), (1, d.d_state, d.d_inner))[0],
            offset == 0)
        delta = steps_of(delta, (jnp.arange(T) < n_valid)[:, None])
        y, s = scan(x, delta, _a(p), B, C, s0)
        ssm_state = jax.lax.dynamic_update_slice(
            state["ssm"], s[None], (slot, 0, 0))
    with _scoped(scope, "out_proj"):
        out, y = _gate_out(p, y, x, z)
    return out, {"conv": conv_state, "ssm": ssm_state}, y


def extend(p, dims: Mamba1Dims, a, n_new, pos0, state, slots,
           scope: str = "mamba1"):
    """A few new positions of several sessions: ``a`` [B, S, dim], the first
    ``n_new`` [B] of each row real, the row's first at its session's position
    ``pos0`` [B], states in the slots ``slots`` [B] (no two real rows name
    one slot; padding rows name the scratch slot and step by ``delta = 0``).
    ``(out [B, S, dim] float32, state, y [B, S, d_inner] before the
    gate)``."""
    d = dims
    S = a.shape[1]
    with _scoped(scope, "in_proj"):
        x, z = project(p, d, a)
    with _scoped(scope, "conv"):
        held = fresh(state["conv"][slots], (pos0 == 0)[:, None, None])
        window = jnp.concatenate([held.astype(jnp.float32), x], axis=1)
        x = conv(p, d, window)
        at = n_new[:, None] + jnp.arange(d.d_conv - 1)[None]     # [B, 3]
        carried = jnp.take_along_axis(window, at[:, :, None], axis=1)
        conv_state = state["conv"].at[slots].set(
            carried.astype(state["conv"].dtype))
        delta, B, C = select(p, d, x)
    with _scoped(scope, "scan"):
        delta = steps_of(
            delta, (jnp.arange(S)[None] < n_new[:, None])[:, :, None])
        s = fresh(state["ssm"][slots], (pos0 == 0)[:, None, None])
        a_t, ys = _a(p), []
        for t in range(S):
            s, y_t = _step(s, a_t, x[:, t], delta[:, t], B[:, t], C[:, t])
            ys.append(y_t)
        ssm_state = state["ssm"].at[slots].set(s)
    with _scoped(scope, "out_proj"):
        out, y = _gate_out(p, jnp.stack(ys, axis=1), x, z)
    return out, {"conv": conv_state, "ssm": ssm_state}, y


# -- the gated memory unit ------------------------------------------------------

def init_gmu(key, dim: int, d_mem: int, dtype=jnp.float32) -> dict:
    k1, k2 = jax.random.split(key)
    return {"w_1": (jax.random.normal(k1, (dim, d_mem), jnp.float32)
                    / math.sqrt(dim)).astype(dtype),
            "w_2": (jax.random.normal(k2, (d_mem, dim), jnp.float32)
                    / math.sqrt(d_mem)).astype(dtype)}


def gmu(p, a, memory):
    """``(silu(a W_1) * m) W_2``: ``a`` [..., dim] gates the SAME positions'
    memory ``m`` [..., d_mem] (another layer's scan output before its gate).
    A mixer with no state of its own."""
    return mm(jax.nn.silu(mm(a, p["w_1"])) * memory, p["w_2"])
