"""A Mamba-2 mixer (state-space duality) over a per-session RECURRENT state:
two paths over one set of weights, as ``ops/mla.py``'s and ``ops/gqa.py``'s.

``[z, xBC, dt] = a W_in`` (``d_inner + conv_dim + heads`` columns, no bias);
``xBC = silu(conv1d_causal(xBC))`` (depthwise, ``d_conv`` taps, with bias);
``[x, B, C] = xBC`` (``x`` as ``heads`` heads of ``head_dim``; ``B`` and ``C``
of ``d_state`` values, shared by all heads: one group); ``delta =
softplus(dt + dt_bias)``, ``A = -exp(A_log)``; per head ``h`` and position
``t``: ``S_t = exp(delta_t A_h) S_{t-1} + delta_t x_t (x) B_t`` (``S`` is
``[head_dim, d_state]``), ``y_t = S_t C_t + D_h x_t``; then ``y = RMS(y *
silu(z)) * w`` over all of ``d_inner`` (the gate first, then the norm) and
``y W_out``.

What a session carries from one call to the next is a state of FIXED size,
whatever its length: the last ``d_conv - 1`` rows of ``xBC`` before the
convolution (``conv`` [slots, d_conv - 1, conv_dim], the weights' type) and
``S`` (``ssm`` [slots, heads, head_dim, d_state], float32). It stands at ONE
position, the end of what the session has been given, and cannot be rewound:
which positions a slot's state has absorbed is its owner's business
(``models/sessionrec.LatentCache``).

* :func:`prefill_chunk`: a chunk of ONE session from its slot's state, by the
  chunked scan (:func:`scan_chunks`: inside a chunk of ``dims.chunk`` positions
  the recurrence is a masked product, between chunks the state is carried).
  Padding positions beyond ``n_valid`` take a step of ``delta = 0``: decay 1,
  nothing added, the state as it was;
* :func:`extend`: a few new positions of each of several sessions, states
  read from and written to their slots in place, one real row at a time (a
  padding row moves no state). The positions of a row are one chunk of the
  same scan, so a row's state is read twice and written once.

A call whose first position is the session's position 0 starts from a ZERO
state, whatever its slot held (:func:`fresh`). Both give the numbers of
:func:`mix_full` (the recurrence itself, position by position, from zeros),
which is the plain form the tests hold them to.

Matrix products with the weights take their inputs in the weights' type and
accumulate in float32; ``delta``, ``A``, the decays, the scan and the carried
``S`` are float32, the scan's own products at the highest precision.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from predictionio_tpu.ops.mla import mm, rms_norm

_HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class SSMDims:
    dim: int
    heads: int
    head_dim: int
    d_state: int
    d_conv: int = 4
    chunk: int = 256            # positions of one chunk of the scan
    eps: float = 1e-5

    @property
    def d_inner(self) -> int:
        return self.heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        """``x``, ``B`` and ``C`` side by side: what the convolution sees."""
        return self.d_inner + 2 * self.d_state

    @property
    def in_width(self) -> int:
        return self.d_inner + self.conv_dim + self.heads


def init(key, dims: SSMDims, dtype=jnp.float32) -> dict:
    """The family's own initialisation: N(0, 1 / fan_in) matrices, ``A`` in
    U(1, 16), ``dt_bias`` the inverse softplus of a log-uniform step in
    [1e-3, 0.1], ``D`` = 1, a unit norm."""
    d = dims
    k_in, k_out, k_conv, k_a, k_dt = jax.random.split(key, 5)

    def normal(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                / math.sqrt(fan_in)).astype(dtype)

    step = jnp.exp(jax.random.uniform(k_dt, (d.heads,), jnp.float32,
                                      math.log(1e-3), math.log(0.1)))
    return {"w_in": normal(k_in, (d.dim, d.in_width), d.dim),
            "conv_w": normal(k_conv, (d.d_conv, d.conv_dim), d.d_conv),
            "conv_b": jnp.zeros((d.conv_dim,), dtype),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "a_log": jnp.log(jax.random.uniform(k_a, (d.heads,), jnp.float32,
                                                1.0, 16.0)),
            "d": jnp.ones((d.heads,), jnp.float32),
            "norm": jnp.ones((d.d_inner,), dtype),
            "w_out": normal(k_out, (d.d_inner, d.dim), d.d_inner)}


def init_state(dims: SSMDims, n_slots: int, dtype) -> dict:
    d = dims
    return {"conv": jnp.zeros((n_slots, d.d_conv - 1, d.conv_dim), dtype),
            "ssm": jnp.zeros((n_slots, d.heads, d.head_dim, d.d_state),
                             jnp.float32)}


def fresh(state, is_start):
    """``state`` where the call continues a session, zeros where it starts
    one (``is_start``, traced): a miss never resumes from what its slot
    held."""
    return jnp.where(is_start, jnp.zeros_like(state), state)


def steps_of(dt, valid):
    """``delta`` with the padding positions' steps set to 0: decay 1 and
    nothing added, so the state passes them unchanged."""
    return jnp.where(valid, dt, 0.0)


def project(p, dims: SSMDims, a):
    """``(z [..., d_inner], xBC [..., conv_dim] before the convolution,
    delta [..., heads])`` of the positions ``a`` [..., dim], float32."""
    d = dims
    zxbcdt = mm(a, p["w_in"])
    dt = jax.nn.softplus(zxbcdt[..., d.d_inner + d.conv_dim:]
                         + p["dt_bias"].astype(jnp.float32))
    return (zxbcdt[..., :d.d_inner],
            zxbcdt[..., d.d_inner:d.d_inner + d.conv_dim], dt)


def conv(p, dims: SSMDims, window):
    """``window`` [..., d_conv - 1 + T, conv_dim] (the carried rows, then the
    new ones, float32): the ``T`` new positions after the causal depthwise
    convolution and the activation."""
    T = window.shape[-2] - (dims.d_conv - 1)
    w = p["conv_w"].astype(jnp.float32)
    out = p["conv_b"].astype(jnp.float32)
    for k in range(dims.d_conv):
        out = out + w[k] * window[..., k:k + T, :]
    return jax.nn.silu(out)


def split(dims: SSMDims, xbc):
    """``(x [..., heads, head_dim], B [..., d_state], C [..., d_state])``."""
    d = dims
    x = xbc[..., :d.d_inner].reshape(xbc.shape[:-1] + (d.heads, d.head_dim))
    return (x, xbc[..., d.d_inner:d.d_inner + d.d_state],
            xbc[..., d.d_inner + d.d_state:])


def scan_chunks(x, dt, a, B, C, s0, chunk: int):
    """The recurrence over ``T`` positions (``T`` a multiple of ``chunk``) in
    chunks: ``x`` [T, H, P], ``dt`` [T, H] (0 where a position is padding),
    ``a`` [H] (negative), ``B``, ``C`` [T, N], ``s0`` [H, P, N], all float32.
    Inside a chunk position ``i`` takes from position ``j <= i`` the weight
    ``(C_i . B_j) exp(sum_{j < l <= i} dt_l a)`` and from the chunk's incoming
    state ``exp(sum_{l <= i} dt_l a)``. ``(y [T, H, P] without the ``D`` term,
    s_T)``."""
    T, H, P = x.shape
    n = T // chunk

    def chunked(v):
        return v.reshape((n, chunk) + v.shape[1:])

    lower = jnp.tril(jnp.ones((chunk, chunk), bool))

    def one(s, args):
        x_c, dt_c, B_c, C_c = args
        cum = jnp.cumsum(dt_c * a, axis=0)                    # [Q, H]
        seg = cum.T[:, :, None] - cum.T[:, None, :]           # [H, i, j]
        decay = jnp.exp(jnp.where(lower, seg, -jnp.inf))
        cb = jnp.einsum("in,jn->ij", C_c, B_c, precision=_HIGHEST)
        u = x_c * dt_c[:, :, None]                            # [Q, H, P]
        y = jnp.einsum("hij,jhp->ihp", cb * decay, u, precision=_HIGHEST)
        y = y + (jnp.einsum("hpn,in->ihp", s, C_c, precision=_HIGHEST)
                 * jnp.exp(cum)[:, :, None])
        to_end = jnp.exp(cum[-1] - cum)                       # [Q, H]
        s = (s * jnp.exp(cum[-1])[:, None, None]
             + jnp.einsum("jhp,jn->hpn", u * to_end[:, :, None], B_c,
                          precision=_HIGHEST))
        return s, y

    s, y = jax.lax.scan(one, s0, (chunked(x), chunked(dt), chunked(B),
                                  chunked(C)))
    return y.reshape(T, H, P), s


def _gate_out(p, dims: SSMDims, y, x, z):
    """``y`` [..., H, P] (the scan's), the ``D`` term, the gate, the norm
    over all of ``d_inner``, ``W_out``."""
    y = y + p["d"].astype(jnp.float32)[:, None] * x
    y = y.reshape(y.shape[:-2] + (dims.d_inner,)) * jax.nn.silu(z)
    return mm(rms_norm(y, p["norm"], dims.eps), p["w_out"])


def _a(p):
    return -jnp.exp(p["a_log"].astype(jnp.float32))


def _scoped(scope: str, part: str):
    return jax.named_scope(f"{scope}.ssm.{part}")


def mix_full(p, dims: SSMDims, a):
    """Every position of ``a`` [T, dim] by the recurrence itself, one
    position at a time from a zero state: the plain form."""
    d = dims
    z, xbc, dt = project(p, d, a)
    window = jnp.concatenate(
        [jnp.zeros((d.d_conv - 1, d.conv_dim), jnp.float32), xbc])
    x, B, C = split(d, conv(p, d, window))
    A = _a(p)

    def one(s, args):
        x_t, dt_t, B_t, C_t = args
        s = (s * jnp.exp(dt_t * A)[:, None, None]
             + (dt_t[:, None] * x_t)[:, :, None] * B_t)
        return s, jnp.einsum("hpn,n->hp", s, C_t, precision=_HIGHEST)

    _, y = jax.lax.scan(
        one, jnp.zeros((d.heads, d.head_dim, d.d_state), jnp.float32),
        (x, dt, B, C))
    return _gate_out(p, d, y, x, z)


def prefill_chunk(p, dims: SSMDims, a, n_valid, offset, state, slot,
                  scope: str = "ssm"):
    """A chunk ``a`` [C, dim] of ONE session (``n_valid`` real positions, the
    first of them the session's position ``offset``) from its slot's state,
    zeros where ``offset`` is 0. ``C`` is a multiple of ``dims.chunk`` or
    shorter than it. ``(out [C, dim] float32, state)``, the slot's state now
    at position ``offset + n_valid``."""
    d = dims
    T = a.shape[0]
    with _scoped(scope, "in_proj"):
        z, xbc, dt = project(p, d, a)
    with _scoped(scope, "conv"):
        held = fresh(jax.lax.dynamic_slice(
            state["conv"], (slot, 0, 0), (1, d.d_conv - 1, d.conv_dim))[0],
            offset == 0)
        window = jnp.concatenate([held.astype(jnp.float32), xbc])
        x, B, C = split(d, conv(p, d, window))
        # the rows before position n_valid: of this chunk, and of what was
        # carried in where the chunk has fewer than d_conv - 1
        carried = jax.lax.dynamic_slice(
            window, (n_valid, 0), (d.d_conv - 1, d.conv_dim))
        conv_state = jax.lax.dynamic_update_slice(
            state["conv"], carried.astype(state["conv"].dtype)[None],
            (slot, 0, 0))
    with _scoped(scope, "scan"):
        s0 = fresh(jax.lax.dynamic_slice(
            state["ssm"], (slot, 0, 0, 0),
            (1, d.heads, d.head_dim, d.d_state))[0], offset == 0)
        dt = steps_of(dt, (jnp.arange(T) < n_valid)[:, None])
        y, s = scan_chunks(x, dt, _a(p), B, C, s0, min(d.chunk, T))
        ssm_state = jax.lax.dynamic_update_slice(
            state["ssm"], s[None], (slot, 0, 0, 0))
    with _scoped(scope, "out_proj"):
        out = _gate_out(p, d, y, x, z)
    return out, {"conv": conv_state, "ssm": ssm_state}


def extend(p, dims: SSMDims, a, n_new, pos0, state, slots,
           scope: str = "ssm"):
    """A few new positions of several sessions: ``a`` [B, S, dim], the
    first ``n_new`` [B] of each row real, the row's first at its session's
    position ``pos0`` [B], states in the slots ``slots`` [B]. The real rows
    come first (``n_new`` 0 marks padding, which moves no state). ``(out
    [B, S, dim] float32, state)``."""
    d = dims
    S = a.shape[1]
    with _scoped(scope, "in_proj"):
        z, xbc, dt = project(p, d, a)
    with _scoped(scope, "conv"):
        held = fresh(state["conv"][slots], (pos0 == 0)[:, None, None])
        window = jnp.concatenate([held.astype(jnp.float32), xbc], axis=1)
        x, B, C = split(d, conv(p, d, window))
        at = n_new[:, None] + jnp.arange(d.d_conv - 1)[None]     # [B, 3]
        carried = jnp.take_along_axis(window, at[:, :, None], axis=1)
        conv_state = state["conv"].at[slots].set(
            carried.astype(state["conv"].dtype))
    with _scoped(scope, "scan"):
        dt = steps_of(dt, (jnp.arange(S)[None] < n_new[:, None])[:, :, None])
        A = _a(p)

        def row(b, carry):
            ssm, y = carry
            at = (slots[b], 0, 0, 0)
            s0 = fresh(jax.lax.dynamic_slice(
                ssm, at, (1, d.heads, d.head_dim, d.d_state))[0],
                pos0[b] == 0)
            y_b, s = scan_chunks(x[b], dt[b], A, B[b], C[b], s0, S)
            return (jax.lax.dynamic_update_slice(ssm, s[None], at),
                    jax.lax.dynamic_update_slice(y, y_b[None], (b, 0, 0, 0)))

        ssm_state, y = jax.lax.fori_loop(
            0, (n_new > 0).sum(), row, (state["ssm"], jnp.zeros_like(x)))
    with _scoped(scope, "out_proj"):
        out = _gate_out(p, d, y, x, z)
    return out, {"conv": conv_state, "ssm": ssm_state}
