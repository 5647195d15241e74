"""Fused flash-CE: the two-tower symmetric in-batch softmax loss as
Pallas kernels (fwd + hand-written bwd under one ``custom_vjp``).

What it replaces: ``ops.twotower._make_blockwise_ce_vjp``'s
``lax.scan`` over column tiles. That XLA form already avoids the
[B, B] HBM materialization, but its per-tile elementwise (masks, exp,
where, reductions) lowers as a separate fusion per scan step — the
``while`` envelope measured at 56% of the stretch step's device time
(a profile taken before this repo's chip runs). Here each (row-tile, col-tile) grid step computes the
tile logits ON the MXU and does the masking/exp/reduction while the
next tile's operands stream in — the elementwise rides in the matmul's
shadow instead of owning the loop.

Semantics are pinned to the XLA reference (tests/test_pallas_kernels.py,
<=1e-5 in f32):

  fwd   per-tile bf16 (``compute_dtype``) logits; in-batch
        false-negative banning identical to ``_tile_masks``; one-pass
        direct-exp LSE (unit-sphere logits are bounded by 1/temp —
        ``_DIRECT_EXP_MAX_INV_TEMP`` — so exp cannot overflow f32 and
        no max-subtraction pass is needed; callers must not select
        this kernel outside that regime);
  bwd   softmax reconstruction from the two saved [B] LSE vectors,

            dLoss/dL[b,j] = [w_b (p_ui - d) + w_j (p_iu - d)] / (2*Sum_w)

        recomputing tile logits with the SAME cdt rounding as fwd
        (bf16 divide before the f32 cast — a different rounding here
        would reconstruct probabilities inconsistent with the saved
        LSEs, the r5-review grad-bias hazard). ONE grid pass, rows
        outer (``flash_ce_bwd``): a tile's logits, masks, two
        exponentials and cdt cast are made once and feed both products;
        du accumulates over column tiles in its (br, D) block, dv in an
        output that stays RESIDENT in VMEM for the whole grid
        ([Sc, bc, D], slice j) and is written back once. Where that
        array does not fit (``backward_form``: a rule of the shapes
        alone) the standard flash split stands in: two passes
        (``flash_ce_bwd_du``, ``flash_ce_bwd_dv``), each rebuilding the
        tile, dv's with columns outer. Both give the same du and dv
        bit for bit: a block receives the same tiles in the same order.

NON-DIFFERENTIABLE BY CONSTRUCTION: ``u_idx`` / ``i_idx`` / ``weight``
are closed over by the factory, not traced arguments of the returned
``ce(u, v)`` — exactly like the XLA reference. Differentiating the
surrounding loss w.r.t. ``weight`` raises ``UnexpectedTracerError``
(loud, never silent zero grads); weighted-loss tuning must thread the
weights differentiably through a different formulation first.

Ragged batches: inputs are zero-padded up to the tile multiple before
the grid and sliced after — pad rows carry weight 0, so they are
banned as columns, contribute nothing weighted as rows, and their
diagonal keeps every LSE finite (exp(0) = 1); the equivalence tests
cover a ragged last tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: below this batch the dense XLA loss is already cheap and tile
#: shapes degenerate — selection falls back
MIN_BATCH = 128

#: the share of a core's VMEM the one-pass backward may fill (the rest is
#: the compiler's: spills, semaphores, what it keeps of its own). On a v5e
#: the one-pass form took 0.63-0.67 of the two-pass form's time at every
#: shape tried up to this share (tools/flash_ce_probe.py; PERF.md section 6,
#: PR 45), so the rule asks only whether dv fits
_VMEM_SHARE = 0.75
#: on top of what the one-pass backward counts for itself, as the other
#: kernels of this package leave the compiler
_VMEM_HEADROOM = 8 << 20


def pick_block(B: int) -> int:
    """Largest square tile (rows == cols) that keeps a few grid steps:
    512 bounds the tile logits at 1 MB f32 in VMEM."""
    for t in (512, 256, 128, 64, 32):
        if B >= t:
            return t
    return 8


def _vmem_bytes() -> int:
    """One core's VMEM: the chip's own where this process has one, a
    v5e's elsewhere (the interpreter's stand-in, and what a compile for a
    described v5e is held to)."""
    if jax.default_backend() == "tpu":
        return pltpu.get_tpu_info().vmem_capacity_bytes
    return 128 << 20


def one_pass_vmem_bytes(B: int, D: int, block=None) -> int:
    """What the one-pass backward holds in VMEM at batch ``B`` and tower
    width ``D``: the resident dv ONCE (an output block under a constant
    index map is given one buffer), two buffers each of the u and v tiles,
    of du's block and of the four (b, 1) row operands (a whole lane tile
    wide in VMEM), and the tile's float32 intermediates (logits, two
    exponentials, coefficient, its cast, its transpose). The compiler's
    own count at 8192 x 128, without the intermediates: 7.64 MiB."""
    b = int(block or pick_block(B))
    Bp = -(-B // b) * b
    lanes = -(-D // 128) * 128
    return ((Bp + 2 * 3 * b) * lanes + 2 * 4 * b * 128 + 6 * b * b) * 4


def backward_form(B: int, D: int, block=None) -> str:
    """``"one_pass"`` where the whole dv can stay in VMEM beside the
    tile's working set, ``"two_pass"`` beyond: a rule of the shapes and
    the chip's VMEM, of which the kernel may fill ``_VMEM_SHARE``."""
    fits = one_pass_vmem_bytes(B, D, block) <= _VMEM_SHARE * _vmem_bytes()
    return "one_pass" if fits else "two_pass"


def _pad_rows(a, Bp: int):
    B = a.shape[0]
    if B == Bp:
        return a
    return jnp.pad(a, [(0, Bp - B)] + [(0, 0)] * (a.ndim - 1))


def _tile_logits(u_ref, v_ref, temp, cdt):
    """[br, bc] tile logits with the XLA reference's exact rounding:
    cdt matmul output (f32 MXU accumulation), cdt divide, THEN f32."""
    ut = u_ref[...].astype(cdt)
    vt = v_ref[...].astype(cdt)
    # the TPU matmul accumulates in 32 bits (Mosaic refuses any other
    # accumulator); rounding its result to cdt is the reference's output
    L = jax.lax.dot_general(ut, vt, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32).astype(cdt)
    return (L / temp).astype(jnp.float32)


def _tile_masks(i, j, br, bc, uir, uic, iir, iic, wr, wc):
    """Banning semantics of ``ops.twotower._tile_masks`` restated on
    global grid coordinates (the equivalence tests pin the two)."""
    row_g = i * br + jax.lax.broadcasted_iota(jnp.int32, (br, 1), 0)
    col_g = j * bc + jax.lax.broadcasted_iota(jnp.int32, (1, bc), 1)
    not_diag = row_g != col_g
    ban_ui = ((iic == iir) | (wc <= 0.0)) & not_diag
    ban_iu = ((uir == uic) | (wr <= 0.0)) & not_diag
    return not_diag, ban_ui, ban_iu


def _fwd_kernel(u_ref, v_ref, uir_ref, uic_ref, iir_ref, iic_ref,
                wr_ref, wc_ref, sum_ui_ref, diag_ref, iu_part_ref,
                *, temp, cdt, br, bc):
    i, j = pl.program_id(0), pl.program_id(1)
    L = _tile_logits(u_ref, v_ref, temp, cdt)
    not_diag, ban_ui, ban_iu = _tile_masks(
        i, j, br, bc, uir_ref[...], uic_ref[...], iir_ref[...],
        iic_ref[...], wr_ref[...], wc_ref[...])
    e = jnp.exp(L)

    @pl.when(j == 0)
    def _():
        sum_ui_ref[...] = jnp.zeros_like(sum_ui_ref)
        diag_ref[...] = jnp.zeros_like(diag_ref)

    sum_ui_ref[...] += jnp.sum(jnp.where(ban_ui, 0.0, e), axis=1,
                               keepdims=True)
    diag_ref[...] += jnp.sum(jnp.where(not_diag, 0.0, L), axis=1,
                             keepdims=True)
    # column exp-sums cannot accumulate in VMEM (their block revisits
    # non-consecutively under a row-major grid): write one [1, bc]
    # partial per row-tile; the wrapper reduces the [Sr, 1, Bp] partials
    iu_part_ref[...] = jnp.sum(jnp.where(ban_iu, 0.0, e), axis=0,
                               keepdims=True)


def _bwd_coef(i, j, br, bc, L, lse_ui, lse_iu, uir, uic, iir, iic, wr, wc,
              scale):
    """The shared softmax-reconstruction: one fused exp/where pass."""
    not_diag, ban_ui, ban_iu = _tile_masks(
        i, j, br, bc, uir, uic, iir, iic, wr, wc)
    p_ui = jnp.where(ban_ui, 0.0, jnp.exp(L - lse_ui))
    p_iu = jnp.where(ban_iu, 0.0, jnp.exp(L - lse_iu))
    isdiag = jnp.where(not_diag, 0.0, 1.0)
    return (wr * (p_ui - isdiag) + wc * (p_iu - isdiag)) * scale


def _tile_cc(i, j, scale_ref, u_ref, v_ref, masks, temp, cdt, br, bc):
    """Tile (i, j)'s coefficient as both gradient products take it:
    logits on the MXU, the reconstruction, the cdt cast. ``masks``: the
    eight refs after v in the backward calls' argument order."""
    uir, uic, iir, iic, wr, wc, lse_ui, lse_iu = (r[...] for r in masks)
    L = _tile_logits(u_ref, v_ref, temp, cdt)
    coef = _bwd_coef(i, j, br, bc, L, lse_ui, lse_iu, uir, uic, iir, iic,
                     wr, wc, scale_ref[0, 0])
    return coef.astype(cdt)


def _du_tile(cc, v_ref, cdt):
    return jax.lax.dot_general(
        cc, v_ref[...].astype(cdt), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _dv_tile(cc, u_ref, cdt):
    return jax.lax.dot_general(
        cc, u_ref[...].astype(cdt), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _bwd_kernel(scale_ref, u_ref, v_ref, *rest, temp, cdt, br, bc):
    """One pass, rows outer: the tile's coefficient once, both products.
    ``dv_ref`` is the WHOLE [Sc, bc, D] gradient, resident over the grid:
    slice j takes its row tiles in the order i = 0 .. Sr-1, which is the
    order ``_bwd_dv_kernel``'s inner axis gives them."""
    *masks, du_ref, dv_ref = rest
    i, j = pl.program_id(0), pl.program_id(1)
    cc = _tile_cc(i, j, scale_ref, u_ref, v_ref, masks, temp, cdt, br, bc)

    @pl.when(j == 0)
    def _():
        du_ref[...] = jnp.zeros_like(du_ref)

    du_ref[...] += _du_tile(cc, v_ref, cdt)

    @pl.when(i == 0)
    def _():
        dv_ref[j] = jnp.zeros(dv_ref.shape[1:], dv_ref.dtype)

    dv_ref[j] += _dv_tile(cc, u_ref, cdt)


def _bwd_du_kernel(scale_ref, u_ref, v_ref, *rest, temp, cdt, br, bc):
    *masks, du_ref = rest
    i, j = pl.program_id(0), pl.program_id(1)
    cc = _tile_cc(i, j, scale_ref, u_ref, v_ref, masks, temp, cdt, br, bc)

    @pl.when(j == 0)
    def _():
        du_ref[...] = jnp.zeros_like(du_ref)

    du_ref[...] += _du_tile(cc, v_ref, cdt)


def _bwd_dv_kernel(scale_ref, u_ref, v_ref, *rest, temp, cdt, br, bc):
    # transposed grid: columns outer, rows inner, so dv's block is
    # constant over the inner axis and accumulates in VMEM
    *masks, dv_ref = rest
    j, i = pl.program_id(0), pl.program_id(1)
    cc = _tile_cc(i, j, scale_ref, u_ref, v_ref, masks, temp, cdt, br, bc)

    @pl.when(i == 0)
    def _():
        dv_ref[...] = jnp.zeros_like(dv_ref)

    dv_ref[...] += _dv_tile(cc, u_ref, cdt)


def _row_spec(br, rowmajor=True):
    vm = pltpu.VMEM
    if rowmajor:
        return pl.BlockSpec((br, 1), lambda i, j: (i, 0), memory_space=vm)
    return pl.BlockSpec((br, 1), lambda j, i: (i, 0), memory_space=vm)


def _col_spec(bc, rowmajor=True):
    vm = pltpu.VMEM
    if rowmajor:
        return pl.BlockSpec((1, bc), lambda i, j: (0, j), memory_space=vm)
    return pl.BlockSpec((1, bc), lambda j, i: (0, j), memory_space=vm)


def make_flash_ce(u_idx, i_idx, weight, temp, cdt, B,
                  *, interpret=False, block=None):
    """Build ``ce(u, v) -> loss`` (custom_vjp) for one batch's
    index/weight vectors — the Pallas counterpart of
    ``ops.twotower._make_blockwise_ce_vjp`` (same closure shape, same
    nondiff contract: see module docstring)."""
    br = bc = int(block or pick_block(B))
    Bp = -(-B // br) * br
    Sr, Sc = Bp // br, Bp // bc
    f32 = jnp.float32
    cdt = jnp.dtype(cdt)
    temp = float(temp)

    wsum = jnp.maximum(weight.sum(), 1e-8)
    # both orientations of the mask operands, padded to the grid:
    # row-blocked [Bp, 1] and col-blocked [1, Bp]
    uir = _pad_rows(u_idx.astype(jnp.int32).reshape(B, 1), Bp)
    iir = _pad_rows(i_idx.astype(jnp.int32).reshape(B, 1), Bp)
    wr = _pad_rows(weight.astype(f32).reshape(B, 1), Bp)
    uic, iic, wc = uir.reshape(1, Bp), iir.reshape(1, Bp), wr.reshape(1, Bp)
    w_pad = wr[:, 0]

    def _mask_specs(rowmajor):
        return [_row_spec(br, rowmajor), _col_spec(bc, rowmajor),
                _row_spec(br, rowmajor), _col_spec(bc, rowmajor),
                _row_spec(br, rowmajor), _col_spec(bc, rowmajor)]

    def _fwd_parts(u, v):
        D = u.shape[1]
        up, vp = _pad_rows(u, Bp), _pad_rows(v, Bp)
        kernel = functools.partial(_fwd_kernel, temp=temp, cdt=cdt,
                                   br=br, bc=bc)
        vm = pltpu.VMEM
        sum_ui, diag, iu_parts = pl.pallas_call(
            kernel,
            grid=(Sr, Sc),
            in_specs=[
                pl.BlockSpec((br, D), lambda i, j: (i, 0), memory_space=vm),
                pl.BlockSpec((bc, D), lambda i, j: (j, 0), memory_space=vm),
                *_mask_specs(rowmajor=True),
            ],
            out_specs=[
                _row_spec(br), _row_spec(br),
                # [Sr, 1, Bp] with the row-tile axis squeezed: a (1, bc)
                # block of an [Sr, Bp] array is not a legal TPU tile
                pl.BlockSpec((None, 1, bc), lambda i, j: (i, 0, j),
                             memory_space=vm),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((Bp, 1), f32),
                jax.ShapeDtypeStruct((Bp, 1), f32),
                jax.ShapeDtypeStruct((Sr, 1, Bp), f32),
            ],
            interpret=interpret,
            name="flash_ce_fwd",
        )(up, vp, uir, uic, iir, iic, wr, wc)
        # direct-exp combine (selection guarantees |L| <= 1/temp <=
        # _DIRECT_EXP_MAX_INV_TEMP): log of the global exp-sums; the
        # never-banned diagonal keeps every sum >= exp(L[b,b]) > 0
        lse_ui = jnp.log(sum_ui[:, 0])
        lse_iu = jnp.log(jnp.sum(iu_parts, axis=(0, 1)))
        d = diag[:, 0]
        loss = 0.5 * (jnp.sum((lse_ui - d) * w_pad)
                      + jnp.sum((lse_iu - d) * w_pad)) / wsum
        return loss, lse_ui, lse_iu

    def _bwd_call(kernel_fn, name, rowmajor, outs, args, vmem_limit=None):
        """One backward kernel over the tile grid (rows outer where
        ``rowmajor``). ``outs``: (block, index map, array shape) an
        output."""
        D = args[1].shape[1]
        vm = pltpu.VMEM
        if rowmajor:
            u_map, v_map = (lambda i, j: (i, 0)), (lambda i, j: (j, 0))
            grid = (Sr, Sc)
        else:
            u_map, v_map = (lambda j, i: (i, 0)), (lambda j, i: (j, 0))
            grid = (Sc, Sr)
        return pl.pallas_call(
            functools.partial(kernel_fn, temp=temp, cdt=cdt, br=br, bc=bc),
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1), lambda *_: (0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((br, D), u_map, memory_space=vm),
                pl.BlockSpec((bc, D), v_map, memory_space=vm),
                *_mask_specs(rowmajor),
                _row_spec(br, rowmajor), _col_spec(bc, rowmajor),
            ],
            out_specs=[pl.BlockSpec(block, index_map, memory_space=vm)
                       for block, index_map, _ in outs],
            out_shape=[jax.ShapeDtypeStruct(shape, f32)
                       for _, _, shape in outs],
            compiler_params=(pltpu.CompilerParams(
                vmem_limit_bytes=vmem_limit) if vmem_limit else None),
            interpret=interpret,
            name=name,
        )(*args)

    @jax.custom_vjp
    def ce(u, v):
        return _fwd_parts(u, v)[0]

    def fwd(u, v):
        loss, lse_ui, lse_iu = _fwd_parts(u, v)
        return loss, (u, v, lse_ui, lse_iu)

    def bwd(res, ct):
        u, v, lse_ui, lse_iu = res
        D = u.shape[1]
        scale = (ct / (2.0 * wsum * temp)).astype(f32).reshape(1, 1)
        args = (scale, _pad_rows(u, Bp), _pad_rows(v, Bp), uir, uic, iir,
                iic, wr, wc, lse_ui.reshape(Bp, 1), lse_iu.reshape(1, Bp))
        du_out = ((br, D), lambda i, j: (i, 0), (Bp, D))
        if backward_form(B, D, br) == "one_pass":
            du, dv = _bwd_call(
                _bwd_kernel, "flash_ce_bwd", True,
                # dv whole under a constant index map: it stays in VMEM
                # for the grid and is written back once
                [du_out, ((Sc, bc, D), lambda i, j: (0, 0, 0), (Sc, bc, D))],
                args,
                vmem_limit=one_pass_vmem_bytes(B, D, br) + _VMEM_HEADROOM)
            dv = dv.reshape(Bp, D)
        else:
            du, = _bwd_call(_bwd_du_kernel, "flash_ce_bwd_du", True,
                            [du_out], args)
            dv, = _bwd_call(_bwd_dv_kernel, "flash_ce_bwd_dv", False,
                            [((bc, D), lambda j, i: (j, 0), (Bp, D))], args)
        return du[:B], dv[:B]

    ce.defvjp(fwd, bwd)
    return ce


def pallas_blockwise_ce(u, v, u_idx, i_idx, weight, temp, cdt,
                        *, interpret=False, block=None):
    """One-call form mirroring ``ops.twotower._blockwise_softmax_ce``."""
    fn = make_flash_ce(u_idx, i_idx, weight, temp, cdt, u.shape[0],
                       interpret=interpret, block=block)
    return fn(u, v)
