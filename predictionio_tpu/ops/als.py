"""Alternating least squares on the TPU mesh.

The TPU-native replacement for MLlib ALS (the reference's flagship
algorithm: examples/scala-parallel-recommendation templates call
``ALS.train`` with shuffle-based block exchange each iteration). Design
per SURVEY.md §2.9/§7.4:

  - ragged ratings are pre-binned into static padded blocks
    (predictionio_tpu.ops.ragged) — no recompilation across iterations
  - each half-step solves ALL users (or items) as one batched
    normal-equation problem: gather opposing factors [B, L, K], form
    A = Yg^T Yg (+reg), b = Yg^T r with masked einsums (MXU work), and
    solve the K x K systems with a batched LU — ``lax.map`` over fixed
    user blocks bounds HBM footprint
  - data parallelism: the group axis is sharded over the mesh ``data``
    axis with ``shard_map``; the opposing factor matrix is replicated,
    so the only cross-device traffic is the all-gather of the freshly
    solved factors at the end of each half-step (XLA inserts it when
    the sharded output is next consumed replicated) — ICI traffic
    instead of the reference's Spark shuffle
  - explicit feedback uses ALS-WR regularization (lambda * n_u * I,
    matching MLlib); implicit feedback implements Hu-Koren-Volinsky
    (c = 1 + alpha * r) with the Y^T Y Gramian trick

Solves run in float32 (K x K conditioning); the big gather+einsum work
is float32 too — scoring (ops.topk) may downcast to bfloat16.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import os
import threading
import time
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from predictionio_tpu.ops.ragged import SegmentedGroups, build_segmented_groups

log = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class ALSConfig:
    rank: int = 32
    iterations: int = 10
    reg: float = 0.1          # lambda
    implicit: bool = False
    alpha: float = 1.0        # implicit confidence scale, c = 1 + alpha*r
    block_size: int = 4096    # users solved per lax.map step
    seed: int = 7
    solver: str = "cg"        # "cg" (MXU-friendly, default) | "direct" (LU)
    cg_iters: int = 6         # CG steps. The solve WARM-STARTS from the
                              # previous iteration's factors, so far fewer
                              # steps than a cold solve needs. r5 on-chip
                              # sweep at ML-20M/K=64 under jacobi+unroll
                              # (below): held-out RMSE identical to the 4th
                              # decimal from 10 down to 6 (0.4276); first
                              # movement at 5 (0.4277), visible at 4
                              # (0.4281). Integrated step 1.477->1.410 s
                              # vs the r4 scan-none-10 default; 6 keeps a
                              # one-step margin above the visible cliff
    cg_dtype: str = "bfloat16"  # CG matvec storage dtype: the solve is
                                # HBM-bound on re-reading A each step, so
                                # bf16 halves it (f32 accumulate/recurrences)
    cg_unroll: bool = True    # unroll the CG recurrence into straight-line
                              # code instead of a lax.scan: the loop body is
                              # a handful of SMALL ops ([B,K] matvec + dots),
                              # so the while-loop's per-step sync/dispatch
                              # overhead dominates its actual HBM traffic
                              # (r5 measurement below)
    cg_precond: str = "jacobi"  # "jacobi" | "none": diagonal preconditioner
                                # — one [B,K] divide per solve, buys the same
                                # residual in fewer CG steps (ALS-WR adds
                                # reg*n_u to the diagonal, so group scales
                                # vary wildly and Jacobi normalizes them)
    compute_dtype: str = "bfloat16"  # gather/Gramian input dtype; accumulation
                                     # is always f32 (MXU native bf16xbf16->f32)
    map_batch: object = None  # lax.map batch_size for the row-partial and
                              # group-solve loops: N vmaps N blocks per
                              # while iteration. MEASURED REJECTION
                              # (r5, ML-20M/K=64 integrated): 2/4/8 ->
                              # 1.854/1.897/1.866 s vs 1.454 s at None —
                              # the vmapped blocks materialize N x the
                              # [B, L, K] intermediates and break the
                              # per-block fusion; the map loop itself is
                              # pipelined fine by XLA. Keep None.
    seg_len: object = "auto"  # virtual-row length (int), or "auto": sized
                              # from the group-size histogram to minimize
                              # padded slots — the gather is issue-bound,
                              # so padding costs like real entries
    # NOTE: a fused gather+Gramian Pallas kernel (VMEM-resident table,
    # aligned-tile one-hot gathers) was built, lowered through Mosaic and
    # measured on a real chip: 0.46-0.66x the XLA path at ML-20M shapes
    # (G=27k K=64 R=8192 L in {128,512}, f32 and bf16) — the stage is
    # gather-ISSUE-bound and the one-hot select costs ALIGNx more VMEM
    # loads per slot than the hardware gather XLA emits. Removed.


def als_row_cost_slots(rank: int) -> float:
    """Per-row overhead in equivalent slots for the auto seg-len sweep:
    the [rows, K, K] partial-Gramian HBM round trip relative to the
    per-slot gather cost. The ONE copy — this number shapes the
    PHYSICAL layout (it drives auto seg_len), and the binned-layout
    cache key covers it only through ``rank``, so every lane (trainer,
    binned fit lane) must derive it from rank the same way or
    a shared cache entry would carry a different geometry than the
    requesting lane would build."""
    return max(8.0, rank * rank / 300.0)


def _build_side(
    group_idx: np.ndarray,
    item_idx: np.ndarray,
    vals: np.ndarray,
    n_groups: int,
    cfg: ALSConfig,
    n_shards: int,
    max_len: Optional[int],
) -> SegmentedGroups:
    """Build one side's segmented layout (block planning lives in the
    builder; both axes come back padded to exact block multiples)."""
    return build_segmented_groups(
        group_idx, item_idx, vals, n_groups, seg_len=cfg.seg_len,
        max_len=max_len, n_shards=n_shards, block_size=cfg.block_size,
        row_cost_slots=als_row_cost_slots(cfg.rank),
    )


def _batched_cg(A, b, iters: int, x0=None, matvec_dtype=jnp.float32,
                unroll: bool = False, precond: str = "none",
                active_steps=None):
    """Batched conjugate gradient for SPD K x K systems.

    TPU-shaped replacement for ``jnp.linalg.solve``: batched LU/Cholesky
    lowers poorly on TPU (~20x slower than the einsum work feeding it),
    while CG is pure batched matvecs the MXU eats. 16 iterations reach
    ~1e-3 relative error at K=64 — far below ALS's own convergence
    tolerance. ``x0`` warm-starts from the previous outer iteration's
    factors (they drift slowly), buying the same residual in fewer steps.

    ``matvec_dtype=bfloat16`` stores A once in bf16 and runs the matvecs
    from it with f32 accumulation: CG is HBM-bound on re-reading A every
    iteration, so this halves solve time; the bf16 residual floor
    (~2e-3 relative at K=64) sits below ALS's tolerance. All scalar
    recurrences (alpha, beta, x, r) stay f32.

    MEASURED alternatives (r4 roofline follow-up; the trace put this
    solve at ~45% of step time), all REJECTED on integrated step time
    at ML-20M/K=64 even when their ISOLATED microbenchmarks won:
      - full-G f32 CG (no lax.map): isolated 113 ms vs 168 ms mapped —
        but the INTEGRATED step regressed 1.52 s -> 1.77 s (the blocked
        form fuses the regularize+cast into the per-block loop; the
        full-G form materializes extra [G, K, K] copies);
      - full-G bf16: integrated 1.67 s;
      - a Pallas kernel holding A VMEM-resident across all CG steps
        (lanes = groups, unrolled multi-accumulator FMA matvec):
        best 106 ms isolated, but it needs A in a [K, K, T]-transposed
        layout rebuilt EVERY outer iteration, which eats the win.
    The lesson is the same as the gather kernel note above: the fused
    XLA program beats locally-faster formulations with worse layouts
    or fusion boundaries.

    ``unroll=True`` replaces the ``lax.scan`` with straight-line code:
    the recurrence body is a few SMALL [B, K] ops whose while-loop
    dispatch/sync overhead exceeds their HBM traffic, so unrolling lets
    XLA fuse across iterations and schedule without per-step loop
    plumbing.

    ``precond="jacobi"`` runs preconditioned CG with M = diag(A): one
    [B, K] reciprocal per solve (A's diagonal is reg*n_u-shifted, so
    per-group scales vary by orders of magnitude and Jacobi equalizes
    them), reaching the same residual in fewer steps — the knob that
    lets cg_iters drop below the unpreconditioned cliff.

    ON-CHIP MEASUREMENTS (ML-20M, K=64, integrated 5-iteration train,
    min-of-2), taken before this repo's benchmark existed and not
    repeated since (no cell trains ALS, PERF.md §7):
      scan-none-10 (r4 default)  1.477 s  rmse 0.4276
      unroll-none-10             1.468 s  rmse 0.4276
      unroll-jacobi-10           1.434 s  rmse 0.4276
      unroll-jacobi-6  (DEFAULT) 1.410 s  rmse 0.4276
      unroll-jacobi-4            1.400 s  rmse 0.4281  <- quality moves
      scan-jacobi-6              1.508 s  <- REGRESSION: under the scan
        the extra precondition ops cost more than 4 saved iterations,
        confirming the loop is dispatch-bound, not HBM-bound
    The sweep also corrects the r4 narrative: cutting CG work 40% moved
    the step only ~4.5%, so the trace's ~47% "while" fraction is mostly
    the lax.map over row/group blocks (also while-lowered), not this
    recurrence; a block_size sweep (4096->32768) found 4096 already
    optimal (8192: 1.467 s).
    """
    Am = A.astype(matvec_dtype)

    def matvec(v):
        return jnp.einsum("bij,bj->bi", Am, v.astype(matvec_dtype),
                          preferred_element_type=jnp.float32)

    if precond == "jacobi":
        # f32 diagonal BEFORE the matvec cast: the reg*n_u shift spans
        # orders of magnitude and bf16 would quantize the equalization
        Minv = 1.0 / (jnp.diagonal(A, axis1=-2, axis2=-1) + 1e-20)
    elif precond == "none":
        Minv = None
    else:
        # a typo must not silently run unpreconditioned: the cg_iters=6
        # default is validated only WITH Jacobi
        raise ValueError(f"unknown cg_precond {precond!r} "
                         "(expected 'jacobi' or 'none')")

    def prec(r):
        return r if Minv is None else Minv * r

    if x0 is None:
        x = jnp.zeros_like(b)
        r = b
    else:
        x = x0
        r = b - matvec(x0)
    z = prec(r)
    p = z
    rs = jnp.einsum("bi,bi->b", r, z)

    def body(carry, k):
        x, r, p, rs = carry
        Ap = matvec(p)
        alpha = rs / (jnp.einsum("bi,bi->b", p, Ap) + 1e-20)
        x1 = x + alpha[:, None] * p
        r1 = r - alpha[:, None] * Ap
        z = prec(r1)
        rs1 = jnp.einsum("bi,bi->b", r1, z)
        p1 = z + (rs1 / (rs + 1e-20))[:, None] * p
        if active_steps is not None:
            # per-candidate step budget (the vmapped grid axis): steps
            # past a candidate's budget compute but FREEZE its state,
            # so a grid member with cg_iters=4 finishes bit-identical
            # to a sequential 4-step solve
            on = k < active_steps
            x1 = jnp.where(on, x1, x)
            r1 = jnp.where(on, r1, r)
            p1 = jnp.where(on, p1, p)
            rs1 = jnp.where(on, rs1, rs)
        return (x1, r1, p1, rs1), None

    carry = (x, r, p, rs)
    if unroll:
        for k in range(iters):
            carry, _ = body(carry, k)
    else:
        carry, _ = jax.lax.scan(body, carry, jnp.arange(iters))
    return carry[0]


#: uint8 value-code reserved for padded slots (compress_side); the
#: mask derives as ``code != 255``
PAD_CODE = 255


def _solve_shard(Y, X_prev, idx, val, mask, seg, counts, *, rank, reg, implicit,
                 alpha, row_block, group_block, groups_loc, solver, cg_iters,
                 cg_dtype, compute_dtype, cg_unroll=False, cg_precond="none",
                 cg_active=None, map_batch=None, val_affine=None):
    """Solve all groups of one shard from segmented virtual rows.

    Three stages, all static-shape:

      1. per-row partial Gramians A_r = Yg^T Yg, b_r = Yg^T r over
         fixed-length rows (``lax.map`` over row blocks bounds HBM).
         The gather + einsums run in ``compute_dtype`` (bf16 by
         default: native MXU input type, halves the HBM traffic of the
         materialized [B, L, K] gather); accumulation stays float32.
      2. segment-sum partials to groups (sorted local segment ids) —
         Gramians are additive, so a group split across rows recombines
         exactly; this is what removes the per-group length cap.
      3. batched regularized solve per group block (CG warm-started
         from the previous iteration's factors).

    With ``val_affine=(a, b)`` (the compressed layout, compress_side):
    ``val`` carries uint8 codes, ``mask`` is None — the slot value
    decodes as ``a + b*code`` (one VPU multiply-add; a table GATHER
    here would double the gather issue the stage is bound by) and the
    mask as ``code != PAD_CODE``, collapsing the val+mask HBM/transfer
    streams (8 bytes/slot) into one byte. Pad slots decode to a+255b,
    which is safe: every consumer multiplies by the mask (through the
    zeroed Yg rows or explicitly).
    """
    R_loc, L = idx.shape
    nrb = R_loc // row_block
    cdt = jnp.dtype(compute_dtype)
    f32 = jnp.float32
    Yc = Y.astype(cdt)

    def partial_block(args):
        if val_affine is None:
            idx_b, val_b, mask_b = args
        else:
            idx_b, code_b = args
            a, b = val_affine
            val_b = a + b * code_b.astype(jnp.float32)  # VPU, no gather
            mask_b = code_b != PAD_CODE
        maskc = mask_b.astype(cdt)
        Yg = Yc[idx_b] * maskc[..., None]  # [B, L, K] pad slots zeroed
        if implicit:
            # partials of: alpha * Yg^T diag(r) Yg  and  Yg^T (1 + alpha r)
            A_r = alpha * jnp.einsum(
                "blk,bl,blj->bkj", Yg, val_b.astype(cdt), Yg,
                preferred_element_type=f32,
            )
            b_r = jnp.einsum(
                "blk,bl->bk", Yg,
                ((1.0 + alpha * val_b) * maskc.astype(val_b.dtype)).astype(cdt),
                preferred_element_type=f32,
            )
        else:
            A_r = jnp.einsum("blk,blj->bkj", Yg, Yg, preferred_element_type=f32)
            b_r = jnp.einsum("blk,bl->bk", Yg, val_b.astype(cdt),
                             preferred_element_type=f32)
        # NOTE the f32 partial store is a MEASURED choice, not an
        # oversight (r5, ML-20M integrated): bf16-storing this stack —
        # the step's largest intermediate — ran 1.304 s vs 1.435 but
        # DIVERGED (RMSE 1e12: a Zipf-popular item sums thousands of
        # partials and bf16 adds round to no-ops once the running sum
        # exceeds ~256x the increment); with a correct f32-accumulating
        # segment-sum the conversion materializes the whole stack and
        # the win vanishes (1.475 s). f32 stays.
        return A_r, b_r

    if val_affine is None:
        operands = (idx.reshape(nrb, row_block, L),
                    val.reshape(nrb, row_block, L),
                    mask.reshape(nrb, row_block, L))
    else:
        operands = (idx.reshape(nrb, row_block, L),
                    val.reshape(nrb, row_block, L))
    Ar, br = jax.lax.map(partial_block, operands, batch_size=map_batch)
    Ar = Ar.reshape(R_loc, rank, rank)
    br = br.reshape(R_loc, rank)
    return _solve_groups(Ar, br, X_prev, seg, counts, Yc, rank=rank, reg=reg,
                         implicit=implicit, group_block=group_block,
                         groups_loc=groups_loc, solver=solver,
                         cg_iters=cg_iters, cg_dtype=cg_dtype,
                         cg_unroll=cg_unroll, cg_precond=cg_precond,
                         cg_active=cg_active, map_batch=map_batch)


def _solve_groups(Ar, br, X_prev, seg, counts, Yc, *, rank, reg, implicit,
                  group_block, groups_loc, solver, cg_iters, cg_dtype,
                  cg_unroll=False, cg_precond="none", cg_active=None,
                  map_batch=None):
    """Stages 2+3: segment-sum row partials to groups, regularize, solve."""
    f32 = jnp.float32
    A = jax.ops.segment_sum(Ar, seg, num_segments=groups_loc,
                            indices_are_sorted=True)
    b = jax.ops.segment_sum(br, seg, num_segments=groups_loc,
                            indices_are_sorted=True)

    eye = jnp.eye(rank, dtype=f32)
    YtY = (
        jnp.einsum("lk,lj->kj", Yc, Yc, preferred_element_type=f32)
        if implicit else None
    )
    ngb = groups_loc // group_block
    A = A.reshape(ngb, group_block, rank, rank)
    b = b.reshape(ngb, group_block, rank)
    cnt = counts.reshape(ngb, group_block)
    x0 = X_prev.reshape(ngb, group_block, rank)

    def solve_block(args):
        A_b, b_b, cnt_b, x0_b = args
        if implicit:
            A_b = A_b + YtY + reg * eye
        else:
            # ALS-WR: reg * n_u * I ; empty groups stay nonsingular
            n_u = jnp.maximum(cnt_b.astype(f32), 1.0)
            A_b = A_b + (reg * n_u)[:, None, None] * eye
        if solver == "cg":
            x = _batched_cg(A_b, b_b, cg_iters, x0=x0_b,
                            matvec_dtype=jnp.dtype(cg_dtype),
                            unroll=cg_unroll,
                            precond=cg_precond,
                            active_steps=cg_active)   # [B, K]
        else:
            x = jnp.linalg.solve(A_b, b_b[..., None])[..., 0]
        # groups with no ratings keep EXACT zero factors (the iterative
        # solve only drives the random x0 toward 0 to its residual
        # floor; the reference's unseen users have no factors at all)
        return x * (cnt_b > 0)[:, None]

    out = jax.lax.map(solve_block, (A, b, cnt, x0),
                      batch_size=map_batch)  # [ngb, B, K]
    return out.reshape(groups_loc, rank)


def make_half_step(mesh: Optional[Mesh], cfg: ALSConfig, row_block: int,
                   group_block: int, groups_loc: int,
                   val_affine=None):
    """Compile one ALS half-step, sharded over the mesh ``data`` axis.

    ``val_affine`` switches the step to the compressed layout: the
    positional args become (Y, X_prev, idx, codes, seg, counts) — no
    mask stream — with the affine decode constants baked in."""
    kwargs = dict(
        rank=cfg.rank, reg=cfg.reg, implicit=cfg.implicit, alpha=cfg.alpha,
        row_block=row_block, group_block=group_block, groups_loc=groups_loc,
        solver=cfg.solver, cg_iters=cfg.cg_iters, cg_dtype=cfg.cg_dtype,
        compute_dtype=cfg.compute_dtype, cg_unroll=cfg.cg_unroll,
        cg_precond=cfg.cg_precond, map_batch=cfg.map_batch,
    )
    if val_affine is None:
        fn = functools.partial(_solve_shard, **kwargs)
        in_specs = (P(), P("data", None), P("data", None), P("data", None),
                    P("data", None), P("data"), P("data"))
    else:
        ab = (float(val_affine[0]), float(val_affine[1]))

        def fn(Y, X_prev, idx, codes, seg, counts):
            return _solve_shard(Y, X_prev, idx, codes, None, seg, counts,
                                val_affine=ab, **kwargs)

        in_specs = (P(), P("data", None), P("data", None), P("data", None),
                    P("data"), P("data"))
    if mesh is not None and np.prod([mesh.shape[a] for a in mesh.axis_names]) > 1:
        fn = jax.shard_map(
            fn,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=P("data", None),
        )
    return jax.jit(fn)


def _init_factors(key, n_groups: int, n_real: int, rank: int,
                  grid: Optional[int] = None) -> jax.Array:
    """Scaled-normal factor init with padded rows zeroed (pad rows must
    never influence solves). With ``grid``, one shared draw broadcast
    over a leading [G] axis so grid points differ only by hyperparams."""
    scale = 1.0 / np.sqrt(rank)
    X = jax.random.normal(key, (1, n_groups, rank), jnp.float32) * scale
    if n_groups > n_real:
        X = X.at[:, n_real:].set(0.0)
    if grid is None:
        return X[0]
    return jnp.tile(X, (grid, 1, 1))


def _materialize(x: jax.Array) -> np.ndarray:
    """Device array -> host numpy, multi-host-safe (every host gets the
    full factors, as every Spark executor's ALS blocks collect to the
    driver in the reference)."""
    from predictionio_tpu.parallel.multihost import to_host

    return to_host(x)


@dataclasses.dataclass
class ALSFactors:
    user_factors: np.ndarray  # [n_users, K] float32
    item_factors: np.ndarray  # [n_items, K] float32


@dataclasses.dataclass
class SideLayout:
    """One side's device-bound arrays in transfer-compressed form.

    The host->device transfer is a one-time cost of every train, so
    the wire layout is shrunk before the put:

    - when the ratings form an exact affine ladder of <= 255 distinct
      values (explicit feedback: half-star steps) the val+mask float
      streams (8 B/slot) collapse into ONE uint8 code (a + b*code
      decodes on the VPU, code 255 = padded slot) — measured FASTER
      per step than the f32 streams (less HBM read);
    - the gather indexes cross the wire SPLIT as lo-uint16 (+ hi-uint8
      only when the opposing vocab exceeds 65535; vocabs are < 2^24 by
      assertion), recombined to int32 ONCE on device right after the
      put. The earlier, rejected int16 variant made the
      per-STEP gather pay an int16->s32 conversion (~12% step time);
      the one-time decode keeps the steady-state gather on int32 while
      the wire pays 2-3 B/slot instead of 4 — 9 -> 3-4 B/slot total
      at ML-20M shapes, ~1.45x less transfer."""

    idx_lo: np.ndarray            # [R, L] uint16 (low 16 index bits)
    idx_hi: Optional[np.ndarray]  # [R, L] uint8, None when vocab < 2^16
    val: np.ndarray               # [R, L] uint8 codes | float32
    mask: Optional[np.ndarray]    # [R, L] uint8, None when val is coded
    seg: np.ndarray               # [R] int32
    counts: np.ndarray            # [G] int32
    affine: Optional[tuple]       # (a, b): value = a + b*code, VPU decode
    row_block: int
    group_block: int
    groups_per_shard: int
    n_shards: int

    @property
    def kept_entries(self) -> int:
        return int(self.counts.sum())

    @property
    def slot_bytes(self) -> int:
        return (2 + (1 if self.idx_hi is not None else 0)
                + self.val.dtype.itemsize
                + (1 if self.mask is not None else 0))

    @property
    def transfer_bytes(self) -> int:
        n = (self.idx_lo.nbytes + self.val.nbytes + self.seg.nbytes
             + self.counts.nbytes)
        if self.idx_hi is not None:
            n += self.idx_hi.nbytes
        if self.mask is not None:
            n += self.mask.nbytes
        return n

    def to_arrays(self, prefix: str) -> dict:
        out = {f"{prefix}idx_lo": self.idx_lo, f"{prefix}val": self.val,
               f"{prefix}seg": self.seg, f"{prefix}counts": self.counts}
        if self.idx_hi is not None:
            out[f"{prefix}idx_hi"] = self.idx_hi
        if self.mask is not None:
            out[f"{prefix}mask"] = self.mask
        return out

    @classmethod
    def from_arrays(cls, arrays: dict, prefix: str, meta: dict) -> "SideLayout":
        affine = meta.get(f"{prefix}affine")
        return cls(
            idx_lo=arrays[f"{prefix}idx_lo"],
            idx_hi=arrays.get(f"{prefix}idx_hi"),
            val=arrays[f"{prefix}val"],
            mask=arrays.get(f"{prefix}mask"), seg=arrays[f"{prefix}seg"],
            counts=arrays[f"{prefix}counts"],
            affine=tuple(affine) if affine is not None else None,
            row_block=int(meta[f"{prefix}row_block"]),
            group_block=int(meta[f"{prefix}group_block"]),
            groups_per_shard=int(meta[f"{prefix}groups_per_shard"]),
            n_shards=int(meta["n_shards"]),
        )

    def meta(self, prefix: str) -> dict:
        return {f"{prefix}row_block": self.row_block,
                f"{prefix}group_block": self.group_block,
                f"{prefix}groups_per_shard": self.groups_per_shard,
                f"{prefix}affine": (list(self.affine)
                                    if self.affine is not None else None)}


def _split_idx(idx: np.ndarray) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    """int32 gather indexes -> wire streams (lo uint16, hi uint8|None)."""
    mx = int(idx.max(initial=0))
    if mx >= (1 << 24):
        # a real error, not an assert: under -O silent truncation would
        # gather wrong rows and train wrong factors without a symptom
        raise ValueError(f"vocab {mx} exceeds the 24-bit index wire "
                         "format (widen idx_hi before raising this cap)")
    lo = (idx & 0xFFFF).astype(np.uint16)
    if mx < (1 << 16):
        return lo, None
    return lo, (idx >> 16).astype(np.uint8)


@jax.jit
def _recombine_idx16(lo):
    return lo.astype(jnp.int32)


@jax.jit
def _recombine_idx24(lo, hi):
    return lo.astype(jnp.int32) | (hi.astype(jnp.int32) << 16)


def compress_side(sg: SegmentedGroups, n_opposing: int) -> SideLayout:
    """Shrink one side's arrays for the wire (see SideLayout).

    Value coding engages only when the distinct values form an exact
    AFFINE ladder (``uniq[k] == a + b*k`` — explicit-feedback half-star
    ratings do): the device then decodes with one multiply-add on the
    VPU instead of a 256-entry table GATHER. The stage is
    gather-issue-bound, so a table lookup would ADD a second gather per
    slot and give back the transfer win as train time (measured ~2x
    step regression with the table form). Non-affine value sets stay
    float32 + mask. ``n_opposing`` is unused (the index width derives
    from the actual index values in ``_split_idx``); kept for API
    stability."""
    idx_lo, idx_hi = _split_idx(sg.idx)
    # cheap distinct-count probe (first 256k ELEMENTS of the flattened
    # array) before committing to the full 20M-element unique
    probe = np.unique(sg.val.reshape(-1)[:1 << 18])
    if len(probe) <= PAD_CODE:
        # pads are coded 255 regardless, so their 0.0 filler must NOT
        # join the codebook (it would break the affine ladder for any
        # rating scale that does not start at 0)
        uniq = np.unique(sg.val[sg.mask != 0])
        n = len(uniq)
        affine = None
        if n == 1:
            affine = (float(uniq[0]), 0.0)
        elif 2 <= n <= PAD_CODE:
            a, b = float(uniq[0]), float(uniq[1] - uniq[0])
            if b != 0.0 and np.array_equal(
                    uniq, np.float32(a) + np.float32(b)
                    * np.arange(n, dtype=np.float32)):
                affine = (a, b)
        if affine is not None:
            codes = np.searchsorted(
                uniq, sg.val).clip(0, n - 1).astype(np.uint8)
            codes[sg.mask == 0] = PAD_CODE
            return SideLayout(
                idx_lo=idx_lo, idx_hi=idx_hi, val=codes, mask=None,
                seg=sg.seg, counts=sg.counts, affine=affine,
                row_block=sg.row_block, group_block=sg.group_block,
                groups_per_shard=sg.groups_per_shard, n_shards=sg.n_shards)
    return SideLayout(
        idx_lo=idx_lo, idx_hi=idx_hi, val=sg.val,
        mask=sg.mask.astype(np.uint8), seg=sg.seg,
        counts=sg.counts, affine=None,
        row_block=sg.row_block, group_block=sg.group_block,
        groups_per_shard=sg.groups_per_shard, n_shards=sg.n_shards)


def side_layout_from_binned(bs) -> "SideLayout":
    """``data.storage.BinnedSide`` (the native zero-copy builders'
    product) -> the trainer's SideLayout — same arrays, no copies."""
    return SideLayout(
        idx_lo=bs.idx_lo, idx_hi=bs.idx_hi, val=bs.val, mask=bs.mask,
        seg=bs.seg, counts=bs.counts,
        affine=tuple(bs.affine) if bs.affine is not None else None,
        row_block=bs.row_block, group_block=bs.group_block,
        groups_per_shard=bs.groups_per_shard, n_shards=bs.n_shards)


def build_compressed_side(
    group_idx: np.ndarray,
    item_idx: np.ndarray,
    vals: np.ndarray,
    n_groups: int,
    cfg: ALSConfig,
    n_shards: int,
    max_len: Optional[int],
) -> "SideLayout":
    """One side's compressed device layout from COO, in ONE native pass
    when available (ragged.build_compressed_segmented: plan + wire-
    stream fill with no [R, L] f32 val/mask intermediates), else the
    two-stage Python reference (build_segmented_groups +
    compress_side). Both produce bit-identical layouts — pinned by
    tests/test_bin_columnar.py."""
    from predictionio_tpu.ops import ragged as ragged_mod

    try:
        bs = ragged_mod.build_compressed_segmented(
            group_idx, item_idx, vals, n_groups, seg_len=cfg.seg_len,
            max_len=max_len, n_shards=n_shards, block_size=cfg.block_size,
            row_cost_slots=als_row_cost_slots(cfg.rank))
    except MemoryError as e:
        log.warning("native compressed binning failed (%s) — falling "
                    "back to the two-stage path", e)
        bs = None
    if bs is not None:
        return side_layout_from_binned(bs)
    sg = _build_side(group_idx, item_idx, vals, n_groups, cfg, n_shards,
                     max_len)
    return compress_side(sg, 0)


#: default H2D chunk for the double-buffered transfer pipeline (MB);
#: PIO_BIN_CHUNK_MB overrides, PIO_TRANSFER_DOUBLE_BUFFER=0 restores
#: the single-shot put per array
_DEFAULT_CHUNK_MB = 64.0


@functools.lru_cache(maxsize=32)
def _chunk_concat_fn(n_chunks: int):
    """Device-side concat of n row-chunks, compiled once per chunk
    count (then per shape set via the jit cache; the persistent compile
    cache absorbs it across processes). The chunk buffers are transfer
    temporaries nothing else reads, but concatenate cannot alias its
    inputs into the (larger) output, so donating them only produces
    XLA's donated-buffer-unusable warning — they are instead freed
    naturally right after the concat consumes them."""
    del n_chunks  # keying arg: one cached jit wrapper per chunk count
    return jax.jit(lambda *xs: jnp.concatenate(xs, axis=0))


def _chunked_device_put(a: np.ndarray, chunk_bytes: int):
    """Chunked, double-buffered host->device put: row-slices of the
    (C-contiguous) host array are dispatched as independent async
    device_puts and concatenated ON DEVICE. While chunk N's bytes cross
    the wire, chunk N+1 is being serialized/paged-in on the host — on
    the warm lane the source is an mmap'd cache file, so the OS read of
    chunk N+1 overlaps chunk N's transfer instead of serializing in
    front of it. Small arrays keep the one-shot put."""
    if a.ndim == 0 or a.shape[0] < 2 or a.nbytes <= chunk_bytes:
        return jnp.asarray(a)
    per_row = max(1, a.nbytes // a.shape[0])
    rows = max(1, chunk_bytes // per_row)
    chunks = [jax.device_put(a[s:s + rows])
              for s in range(0, a.shape[0], rows)]
    if len(chunks) == 1:
        return chunks[0]
    return _chunk_concat_fn(len(chunks))(*chunks)


def layout_cache_key(cache_key: str, cfg: ALSConfig, n_shards: int,
                     max_ratings_per_user: Optional[int] = None,
                     max_ratings_per_item: Optional[int] = None) -> str:
    """The ONE bincache key derivation for ALS segmented layouts —
    shared by ALSTrainer's internal COO-path cache and the zero-copy
    binned lane (models/als._train_binned), so an entry written by
    either lane serves the other (the layouts are
    bit-identical by construction)."""
    from predictionio_tpu.ops import bincache

    return bincache.layout_key(
        cache_key, "als-segmented",
        {"seg_len": cfg.seg_len, "block_size": cfg.block_size,
         "rank": cfg.rank, "n_shards": n_shards,
         "max_u": max_ratings_per_user, "max_i": max_ratings_per_item})


class LayoutCacheMiss(LookupError):
    """No cached layout for the key (caller falls back to the read path)."""


@dataclasses.dataclass(frozen=True)
class SideSpec:
    """Array-free descriptor of one side's device layout (what a step
    function needs to be rebuilt against already-placed arrays)."""

    row_block: int
    group_block: int
    groups_per_shard: int
    affine: Optional[tuple]


class ALSTrainer:
    """Prepared ALS run: data binned + placed on device, steps compiled.

    Separates the one-time costs (host binning, sharding, XLA compile)
    from the per-iteration device work so callers
    can alternate without paying them again. The full pipeline replaces
    the reference's `ALS.train` call (examples/.../ALSAlgorithm.scala:56).
    """

    def __init__(
        self,
        user_coo: Optional[Tuple[np.ndarray, np.ndarray, np.ndarray]],
        n_users: Optional[int],
        n_items: Optional[int],
        cfg: ALSConfig,
        mesh: Optional[Mesh] = None,
        max_ratings_per_user: Optional[int] = None,
        max_ratings_per_item: Optional[int] = None,
        cache_key: Optional[str] = None,
    ):
        """``cache_key`` enables the persistent binned-layout cache
        (ops.bincache): the compressed device layout
        is loaded by key when present — ``user_coo``/``n_users``/
        ``n_items`` may then be None, and retraining on unchanged
        events skips the whole read->bin pipeline — and saved after a
        build otherwise. The key must already identify the DATA (event
        fingerprint + derivation); layout-affecting config is appended
        here. With no cached layout and no COO, raises LayoutCacheMiss
        so the caller can fall back to reading events."""
        self.cfg = cfg
        self.mesh = mesh
        n_shards = mesh.shape["data"] if mesh is not None else 1
        self.cache_hit = False

        full_key = None
        if cache_key is not None:
            from predictionio_tpu.ops import bincache

            full_key = layout_cache_key(
                cache_key, cfg, n_shards, max_ratings_per_user,
                max_ratings_per_item)
            cached = bincache.load(full_key)
            if cached is not None:
                arrays, meta = cached
                self.n_users = int(meta["n_users"])
                self.n_items = int(meta["n_items"])
                self.total_entries = int(meta["total_entries"])
                # load + put one side at a time: side 2's disk read
                # overlaps side 1's bytes in flight
                user_side = SideLayout.from_arrays(arrays, "u_", meta)
                self._ud = self._put_side(user_side)
                item_side = SideLayout.from_arrays(arrays, "i_", meta)
                self._it = self._put_side(item_side)
                self.cache_hit = True
        if not self.cache_hit:
            if user_coo is None:
                raise LayoutCacheMiss(
                    f"no cached layout for key {cache_key!r} and no COO "
                    "data was provided")
            u_idx, i_idx, vals = user_coo
            self.n_users, self.n_items = n_users, n_items
            # build one side, START its (async) device transfer, then
            # build the other: this hides the second side's host
            # binning underneath the first side's bytes in flight
            t_bin = time.perf_counter()
            user_side = build_compressed_side(
                u_idx, i_idx, vals, n_users, cfg, n_shards,
                max_ratings_per_user)
            self._ud = self._put_side(user_side)
            item_side = build_compressed_side(
                i_idx, u_idx, vals, n_items, cfg, n_shards,
                max_ratings_per_item)
            self._it = self._put_side(item_side)
            self.total_entries = len(vals)
            # data-path ledger: the host binning sub-stage, beside the
            # read/prepare/compile/train stages (obs/perfacct.py)
            from predictionio_tpu.obs import perfacct

            perfacct.LEDGER.note_stage("bin", time.perf_counter() - t_bin)
            if full_key is not None:
                from predictionio_tpu.ops import bincache

                arrays = {**user_side.to_arrays("u_"),
                          **item_side.to_arrays("i_")}
                bincache.save(full_key, arrays, {
                    "n_users": n_users, "n_items": n_items,
                    "n_shards": n_shards, "total_entries": len(vals),
                    **user_side.meta("u_"), **item_side.meta("i_"),
                })
        self._finish_init(user_side, item_side)

    @classmethod
    def from_sides(
        cls,
        user_side: "SideLayout",
        item_side: "SideLayout",
        n_users: int,
        n_items: int,
        total_entries: int,
        cfg: ALSConfig,
        mesh: Optional[Mesh] = None,
    ) -> "ALSTrainer":
        """Prepared trainer from ALREADY-BUILT compressed layouts — the
        zero-copy lanes' entry point (native el_bin_columnar output, or
        a bincache mmap load): the sides go straight to the chunked
        device puts, no COO, no re-binning. The arrays may be zero-copy
        views over native buffers or mmap'd cache files; the trainer
        keeps them referenced until the transfer completes
        (``_note_transfer``)."""
        self = cls.__new__(cls)
        self.cfg = cfg
        self.mesh = mesh
        self.cache_hit = False
        self.n_users, self.n_items = n_users, n_items
        self.total_entries = total_entries
        self._ud = self._put_side(user_side)
        self._it = self._put_side(item_side)
        self._finish_init(user_side, item_side)
        return self

    def _finish_init(self, user_side: "SideLayout",
                     item_side: "SideLayout") -> None:
        cfg = self.cfg
        n_shards = user_side.n_shards
        # light layout descriptors only — the SideLayout objects pin
        # hundreds of MB of host arrays and must not outlive the puts
        # (experiment harnesses rebuild step fns against the same
        # device arrays without re-binning); _host_refs keeps them —
        # and through them any native/mmap buffers — alive EXACTLY
        # until the async transfers complete (_note_transfer)
        self._sides = tuple(
            SideSpec(s.row_block, s.group_block, s.groups_per_shard, s.affine)
            for s in (user_side, item_side))
        self._g_users = user_side.groups_per_shard * n_shards
        self._g_items = item_side.groups_per_shard * n_shards
        # entries actually processed per half-step (all of them unless an
        # explicit max_ratings_per_* cap is set)
        self.kept_user_entries = user_side.kept_entries
        self.kept_item_entries = item_side.kept_entries
        self.transfer_bytes = (user_side.transfer_bytes
                               + item_side.transfer_bytes)
        self._slot_bytes = (user_side.slot_bytes, item_side.slot_bytes)
        self._host_refs = (user_side, item_side)
        self._transfer_lock = threading.Lock()
        self._transfer_noted = False
        # device-memory ledger (obs/memacct.py): the chunked-put lane's
        # device-resident binned sides live as long as this trainer —
        # weakly referenced, so a dropped trainer's footprint sweeps
        from predictionio_tpu.obs import memacct

        memacct.LEDGER.register(self, "als", "train_data",
                                int(self.transfer_bytes))

        key = jax.random.PRNGKey(cfg.seed)
        ku, ki = jax.random.split(key)
        self._X = _init_factors(ku, self._g_users, self.n_users, cfg.rank)
        self._Y = _init_factors(ki, self._g_items, self.n_items, cfg.rank)

        self._user_step = make_half_step(
            self.mesh, cfg, user_side.row_block, user_side.group_block,
            user_side.groups_per_shard, val_affine=user_side.affine,
        )
        self._item_step = make_half_step(
            self.mesh, cfg, item_side.row_block, item_side.group_block,
            item_side.groups_per_shard, val_affine=item_side.affine,
        )
        self._run_cache = {}
        # MFU/roofline accounting (obs/perfacct.py), built on first step
        self._acct = None
        # transfer watcher: notes the wire window into the data-path
        # ledger (pio_datapath_stage_seconds{stage="transfer"}) and
        # releases the host buffers as soon as the puts complete — the
        # engine lane never calls wait_device itself. Multi-host runs
        # skip it: indexing a non-fully-addressable sharded array
        # raises, and the host arrays then stay referenced for the
        # trainer's lifetime exactly as they always did on that path
        if jax.process_count() == 1:
            threading.Thread(target=self._transfer_watch, daemon=True,
                             name="als-transfer-watch").start()

    def _transfer_watch(self) -> None:
        try:  # graftlint: disable=JT09 — logged below; accounting must not break training
            self.wait_device_timed()
        except Exception as e:  # noqa: BLE001
            log.debug("transfer watcher failed: %s", e)

    def _put_side(self, side: SideLayout):
        if not hasattr(self, "put_start"):
            #: when the FIRST wire byte could start moving — the honest
            #: start of the transfer window (puts are async and overlap
            #: the second side's binning and the layout-cache save);
            #: _put_log records (dispatch_time, bytes) per side so
            #: callers can separate wire time from overlapped host work
            self.put_start = time.perf_counter()
            self._put_log = []
        wire = [side.idx_lo] + ([side.idx_hi]
                                if side.idx_hi is not None else [])
        wire += [side.val]
        if side.mask is not None:
            wire.append(side.mask)
        wire += [side.seg, side.counts]
        if self.mesh is not None:
            arrs = [
                jax.device_put(a, NamedSharding(
                    self.mesh, P("data", None) if a.ndim == 2 else P("data")))
                for a in wire
            ]
        else:
            # chunked double-buffered H2D (PIO_BIN_CHUNK_MB /
            # PIO_TRANSFER_DOUBLE_BUFFER): row-chunks dispatch as
            # independent async puts + one device-side concat, so host
            # serialization/page-in of chunk N+1 overlaps chunk N's
            # bytes on the wire (the warm mmap lane's win; the mesh
            # path keeps whole-array puts — NamedSharding already
            # splits them)
            chunk_bytes = int(float(os.environ.get(
                "PIO_BIN_CHUNK_MB", str(_DEFAULT_CHUNK_MB))) * 1e6)
            if (chunk_bytes > 0
                    and os.environ.get("PIO_TRANSFER_DOUBLE_BUFFER",
                                       "1") != "0"):
                arrs = [_chunked_device_put(a, chunk_bytes) for a in wire]
            else:
                arrs = [jnp.asarray(a) for a in wire]
        # recombine the index wire streams to int32 ONCE on device (the
        # per-step gather must read int32 — an int16 gather paid ~12%
        # step time when measured in r3); the puts above are async and
        # the recombine kernels are module-level jits (compiled once
        # per process), so this enqueues without re-tracing
        if side.idx_hi is not None:
            idx = _recombine_idx24(arrs[0], arrs[1])
            rest = arrs[2:]
        else:
            idx = _recombine_idx16(arrs[0])
            rest = arrs[1:]
        self._put_log.append((time.perf_counter(), side.transfer_bytes))
        return tuple([idx] + rest)

    def _run_compiled(self, n: int):
        """One jitted program for n full alternations: `lax.scan` over
        (user solve; item solve) — a single dispatch instead of 2n, so
        per-call host latency never gaps the device."""
        fn = self._run_cache.get(n)
        if fn is None:
            user_step, item_step = self._user_step, self._item_step
            n_ud = len(self._ud)

            def run_n(X, Y, *data):
                ud, it = data[:n_ud], data[n_ud:]

                def body(carry, _):
                    X, Y = carry
                    X = user_step(Y, X, *ud)
                    Y = item_step(X, Y, *it)
                    return (X, Y), None

                (X, Y), _ = jax.lax.scan(body, (X, Y), None, length=n)
                return X, Y

            fn = jax.jit(run_n, donate_argnums=(0, 1))
            self._run_cache[n] = fn
        return fn

    def wait_device(self) -> "ALSTrainer":
        """Block until the binned arrays are resident on device.

        Device puts are async: the bulk transfer (~GBs at ML-20M
        scale) otherwise completes inside the FIRST execution, silently
        attributing transfer time to compile."""
        self.wait_device_timed()
        return self

    def wait_device_timed(self):
        """Like wait_device, but returns the per-side completion
        timestamps (perf_counter), in put order. Paired with _put_log
        this lets a caller compute a PURE-WIRE window: the last side's
        (dispatch_done -> completion) span contains no host work, so
        bytes/that-span reads as bandwidth even when earlier transfer
        overlaps binning or compile."""
        out = []
        for arrs in (self._ud, self._it):
            jax.block_until_ready(arrs)
            out.append(time.perf_counter())
        self._note_transfer(out[-1])
        return out

    def _note_transfer(self, done_ts: float) -> None:
        """Once, at first confirmed transfer completion: record the
        wire window in the data-path ledger (``transfer`` stage beside
        bin/read/compile/train) and drop the host-side layout refs —
        zero-copy native buffers and mmap'd cache pages are released
        the moment the device owns the bytes."""
        with self._transfer_lock:
            if self._transfer_noted:
                return
            self._transfer_noted = True
            self._host_refs = None
        from predictionio_tpu.obs import jaxmon, perfacct

        perfacct.LEDGER.note_stage("transfer", done_ts - self.put_start)
        # what each device holds now that the binned arrays are placed:
        # under a data mesh every device must hold a share, none all
        jaxmon.record_trainer_report("als", {
            "transfer_bytes": int(self.transfer_bytes),
            "placed_bytes_in_use": [
                (d.memory_stats() or {}).get("bytes_in_use")
                for d in jax.local_devices()],
        })

    def compile(self) -> "ALSTrainer":
        """Compile the default-iteration-count program ahead of time:
        ``.lower().compile()`` at the resident shapes,
        kept as the program ``step_n`` dispatches — no throwaway run."""
        n = self.cfg.iterations
        t0 = time.perf_counter()
        self._run_cache[n] = self._run_compiled(n).lower(
            self._X, self._Y, *self._ud, *self._it).compile()
        self.compile_host_sec = time.perf_counter() - t0
        # data-path ledger (obs/perfacct.py): the compile tax of this
        # run, beside the read/prepare/train stages the workflow notes
        from predictionio_tpu.obs import perfacct

        perfacct.LEDGER.note_stage("compile", self.compile_host_sec)
        return self

    def step_n(self, iterations: Optional[int] = None) -> None:
        """Run n alternations on device, synced by block_until_ready;
        factors stay device-resident (materialize with `factors()`)."""
        n = iterations if iterations is not None else self.cfg.iterations
        fn = self._run_compiled(n)
        t0 = time.perf_counter()
        self._X, self._Y = fn(self._X, self._Y, *self._ud, *self._it)
        jax.block_until_ready(self._X)
        # live MFU/roofline gauges (obs/perfacct.py): the analytic
        # work_model is the cost basis
        if self._acct is None:
            from predictionio_tpu.obs import memacct, perfacct

            wm = self.work_model()
            self._acct = perfacct.StepAccountant(
                "als", wm["flops_per_iter"], wm["hbm_bytes_per_iter"])
            # train high-water (obs/memacct.py): analytic like the FLOP
            # basis above — resident binned sides + both factor tables
            # twice (donated in/out under the scan)
            memacct.note_train_peak(
                "als",
                int(self.transfer_bytes) + 2 * int(self._X.nbytes
                                                   + self._Y.nbytes),
                source="analytic")
        step_sec = time.perf_counter() - t0
        self._acct.observe(step_sec, steps=n)
        from predictionio_tpu.obs import jaxmon

        # the window above ends in block_until_ready; the first call of
        # a process includes the jit compile unless compile() ran
        jaxmon.record_trainer_report(
            "als", {"iterations": n, "step_n_sec": step_sec})

    def run(self, iterations: Optional[int] = None) -> ALSFactors:
        self.step_n(iterations)
        return self.factors()

    def factors(self) -> ALSFactors:
        return ALSFactors(
            user_factors=_materialize(self._X)[: self.n_users],
            item_factors=_materialize(self._Y)[: self.n_items],
        )

    def work_model(self) -> dict:
        """Analytic FLOP/byte counts per full alternation (both half
        steps), from the ACTUAL padded array shapes on device — the
        basis for roofline accounting (achieved vs chip peak). Padded
        slots count: they cost real gather issue slots, MXU cycles and
        HBM beats.

        The byte model counts the dominant streams of `_solve_shard`:
        gather-read of the opposing factors, the materialized [B, L, K]
        block (one write + one einsum read), idx/val/mask input reads,
        per-row partial Gramians (f32 write + segment-sum read), and
        the CG solve re-reading A each iteration (cg_dtype). It is an
        UNDER-estimate of true traffic (ignores fusion-dependent
        intermediates), so achieved-bandwidth derived from it is a
        lower bound.
        """
        K = self.cfg.rank
        cs = jnp.dtype(self.cfg.compute_dtype).itemsize
        cg_b = jnp.dtype(self.cfg.cg_dtype).itemsize
        cg_iters = self.cfg.cg_iters if self.cfg.solver == "cg" else 0
        flops = 0.0
        bytes_ = 0.0
        for side, n_groups, slot_b in (
                (self._ud, self._g_users, self._slot_bytes[0]),
                (self._it, self._g_items, self._slot_bytes[1])):
            idx = side[0]
            S = float(idx.shape[0]) * float(idx.shape[1])  # slots incl. pad
            G = float(n_groups)
            flops += 2.0 * S * K * K          # partial Gramians (MXU)
            flops += 2.0 * S * K              # rhs
            flops += (cg_iters + 1) * 2.0 * G * K * K  # CG matvecs
            bytes_ += S * K * cs              # factor gather read
            bytes_ += 2.0 * S * K * cs        # materialized Yg write+read
            bytes_ += S * slot_b              # idx/val[/mask] input reads
            bytes_ += 2.0 * float(idx.shape[0]) * K * K * 4  # partials w+r
            bytes_ += (cg_iters + 1) * G * K * K * cg_b      # CG A re-reads
            bytes_ += G * K * 4               # solved factors write
        return {"flops_per_iter": flops, "hbm_bytes_per_iter": bytes_}


def als_train(
    user_coo: Tuple[np.ndarray, np.ndarray, np.ndarray],
    n_users: int,
    n_items: int,
    cfg: ALSConfig,
    mesh: Optional[Mesh] = None,
    max_ratings_per_user: Optional[int] = None,
    max_ratings_per_item: Optional[int] = None,
    cache_key: Optional[str] = None,
) -> ALSFactors:
    """One-call train from COO (user_idx, item_idx, rating) triples."""
    return ALSTrainer(
        user_coo, n_users, n_items, cfg, mesh=mesh,
        max_ratings_per_user=max_ratings_per_user,
        max_ratings_per_item=max_ratings_per_item,
        cache_key=cache_key,
    ).run()


def als_grid_train(
    user_coo: Tuple[np.ndarray, np.ndarray, np.ndarray],
    n_users: int,
    n_items: int,
    cfg: ALSConfig,
    regs: "np.ndarray | list",
    alphas: "np.ndarray | list | None" = None,
    iterations: "np.ndarray | list | None" = None,
    cg_iters: "np.ndarray | list | None" = None,
) -> List[ALSFactors]:
    """Train EVERY hyperparameter grid point simultaneously via vmap.

    The hyperparameter-tuning capability Spark never had (SURVEY.md
    §7.6): the segmented layout is built and placed once, the factor
    tensors grow a leading grid axis [G, n, K], and ONE compiled program
    alternates all G solves together. Measured on-chip (2M ratings,
    rank 32, G=6): warm sweep 1.6 s vs 1.8 s for six sequential warm
    runs — device work is comparable — and ONE XLA compile replaces six,
    which is where sequential grid search actually spends its time.
    Single-device (the grid axis occupies the batch dimension; shard the
    DATA instead when one model alone saturates a chip).

    Beyond ``regs``, candidates may differ in any SHAPE-STABLE scalar:
    ``alphas`` (implicit confidence) rides the
    vmap like reg; ``iterations`` and ``cg_iters`` are per-candidate
    step BUDGETS — the program runs to the max and freezes a
    candidate's state once its budget is spent, so each grid member
    finishes bit-identical to a sequential run at its own counts (the
    spent compute for frozen lanes is the usual vmap-padding trade).

    Returns one ALSFactors per candidate, in order.
    """
    regs = np.asarray(regs, np.float32)
    G = len(regs)
    alphas = (np.full(G, cfg.alpha, np.float32) if alphas is None
              else np.asarray(alphas, np.float32))
    iters_arr = (np.full(G, cfg.iterations, np.int32) if iterations is None
                 else np.asarray(iterations, np.int32))
    cg_arr = (np.full(G, cfg.cg_iters, np.int32) if cg_iters is None
              else np.asarray(cg_iters, np.int32))
    # a real error, not an assert (same rationale as _split_idx): under
    # python -O a silently shorter list would vmap over garbage scalars
    # and train wrong candidates without a symptom
    for name, arr in (("alphas", alphas), ("iterations", iters_arr),
                      ("cg_iters", cg_arr)):
        if len(arr) != G:
            raise ValueError(
                f"als_grid_train: `{name}` has {len(arr)} entries but "
                f"`regs` defines {G} grid candidates — every "
                "per-candidate list must match len(regs)")
    max_iters = int(iters_arr.max())
    max_cg = int(cg_arr.max())
    u_idx, i_idx, vals = user_coo
    by_user = _build_side(u_idx, i_idx, vals, n_users, cfg, 1, None)
    by_item = _build_side(i_idx, u_idx, vals, n_items, cfg, 1, None)
    g_users = by_user.groups_per_shard
    g_items = by_item.groups_per_shard

    def step_fn(side, groups_loc):
        kwargs = dict(
            rank=cfg.rank, implicit=cfg.implicit,
            row_block=side.row_block, group_block=side.group_block,
            groups_loc=groups_loc, solver=cfg.solver, cg_iters=max_cg,
            cg_dtype=cfg.cg_dtype, compute_dtype=cfg.compute_dtype,
            cg_unroll=cfg.cg_unroll, cg_precond=cfg.cg_precond,
            map_batch=cfg.map_batch,
        )

        def one(Y, X_prev, reg, alpha, cg_n, idx, val, mask, seg, counts):
            return _solve_shard(Y, X_prev, idx, val, mask, seg, counts,
                                reg=reg, alpha=alpha, cg_active=cg_n,
                                **kwargs)

        # grid axis on factors + scalars; the data layout is shared (None)
        return jax.vmap(one, in_axes=(0, 0, 0, 0, 0,
                                      None, None, None, None, None))

    user_step = step_fn(by_user, g_users)
    item_step = step_fn(by_item, g_items)

    key = jax.random.PRNGKey(cfg.seed)
    ku, ki = jax.random.split(key)
    X = _init_factors(ku, g_users, n_users, cfg.rank, grid=G)
    Y = _init_factors(ki, g_items, n_items, cfg.rank, grid=G)
    regs_dev = jnp.asarray(regs)
    alphas_dev = jnp.asarray(alphas)
    cg_dev = jnp.asarray(cg_arr)
    iters_dev = jnp.asarray(iters_arr)
    ud = tuple(jnp.asarray(a) for a in
               (by_user.idx, by_user.val, by_user.mask, by_user.seg, by_user.counts))
    it = tuple(jnp.asarray(a) for a in
               (by_item.idx, by_item.val, by_item.mask, by_item.seg, by_item.counts))

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def run(X, Y):
        def body(carry, t):
            X, Y = carry
            X1 = user_step(Y, X, regs_dev, alphas_dev, cg_dev, *ud)
            Y1 = item_step(X1, Y, regs_dev, alphas_dev, cg_dev, *it)
            # per-candidate iteration budget: past it, the candidate's
            # factors freeze (bit-identical to a sequential run at its
            # own iteration count)
            on = (t < iters_dev)[:, None, None]
            return (jnp.where(on, X1, X), jnp.where(on, Y1, Y)), None

        (X, Y), _ = jax.lax.scan(body, (X, Y), jnp.arange(max_iters))
        return X, Y

    X, Y = run(X, Y)
    jax.block_until_ready(X)
    Xh, Yh = np.asarray(X), np.asarray(Y)
    return [
        ALSFactors(user_factors=Xh[g, :n_users], item_factors=Yh[g, :n_items])
        for g in range(G)
    ]


# ---------------------------------------------------------------------------
# streaming fold-in (ROADMAP item C): solve a handful of touched groups
# against the FIXED opposing factors — the classic implicit/explicit ALS
# fold-in (one exact half-step for the touched rows), reusing the same
# Gramian + CG machinery as the full train but at delta scale.
# ---------------------------------------------------------------------------

#: fold-in CG floor: the full train warm-starts from last iteration's
#: factors so 6 steps suffice; a fold-in may solve COLD groups (new
#: users), where ~16 jacobi-CG steps reach ~1e-3 relative at K=64 —
#: far below the fold-in equivalence tolerance
FOLD_IN_CG_ITERS = 16


def _pow2_at_least(n: int, floor: int = 8) -> int:
    v = floor
    while v < n:
        v *= 2
    return v


@functools.lru_cache(maxsize=64)
def _build_fold_in(b_pad: int, l_pad: int, rank: int, implicit: bool,
                   solver: str, cg_iters: int):
    """One jitted fold-in solve per (padded batch, padded length, rank,
    flags) bucket — pow2 padding bounds the distinct compiles."""
    f32 = jnp.float32
    eye = np.eye(rank, dtype=np.float32)

    def solve(Y, idx, val, mask, counts, x0, reg, alpha):
        maskf = mask.astype(f32)
        Yg = Y[idx] * maskf[..., None]               # [B, L, K], pads zeroed
        if implicit:
            A = alpha * jnp.einsum("blk,bl,blj->bkj", Yg, val, Yg,
                                   preferred_element_type=f32)
            b = jnp.einsum("blk,bl->bk", Yg, (1.0 + alpha * val) * maskf,
                           preferred_element_type=f32)
            YtY = jnp.einsum("lk,lj->kj", Y, Y, preferred_element_type=f32)
            A = A + YtY + reg * eye
        else:
            A = jnp.einsum("blk,blj->bkj", Yg, Yg,
                           preferred_element_type=f32)
            b = jnp.einsum("blk,bl->bk", Yg, val,
                           preferred_element_type=f32)
            n_u = jnp.maximum(counts.astype(f32), 1.0)
            A = A + (reg * n_u)[:, None, None] * eye
        if solver == "cg":
            x = _batched_cg(A, b, cg_iters, x0=x0, matvec_dtype=f32,
                            unroll=False, precond="jacobi")
        else:
            x = jnp.linalg.solve(A, b[..., None])[..., 0]
        # empty (all-pad) groups keep their warm start untouched: a
        # zero-rating solve would drag an existing factor toward zero
        return jnp.where((counts > 0)[:, None], x, x0)

    return jax.jit(solve)


def fold_in_solve(
    Y: np.ndarray,
    rows: "List[Tuple[np.ndarray, np.ndarray]]",
    cfg: ALSConfig,
    x0: Optional[np.ndarray] = None,
) -> np.ndarray:
    """Solve ``len(rows)`` groups' factors against fixed opposing
    factors ``Y`` [n_opposing, K].

    ``rows[i] = (opp_idx, values)``: group i's COMPLETE rating set
    (opposing-side row indices + ratings) — for a new user this is
    exactly its delta events, and the solve is the exact conditional
    ALS optimum given Y; for an existing user the caller supplies the
    full history so the fold-in matches what a half-step of the full
    train would produce. ``x0`` [B, K] warm-starts the CG from the
    groups' current factors (zeros for new groups).

    Everything runs in float32 (deltas are small; fold-in precision is
    what the equivalence gate measures). Inputs are padded to pow2
    (batch, length) buckets so repeated folds hit a bounded set of
    compiled programs. Returns the solved [B, K] float32 factors.
    """
    B = len(rows)
    if B == 0:
        return np.zeros((0, cfg.rank), np.float32)
    L = max(1, max(len(idx) for idx, _ in rows))
    b_pad = _pow2_at_least(B)
    l_pad = _pow2_at_least(L)
    idx = np.zeros((b_pad, l_pad), np.int32)
    val = np.zeros((b_pad, l_pad), np.float32)
    mask = np.zeros((b_pad, l_pad), np.bool_)
    counts = np.zeros(b_pad, np.int32)
    for i, (gi, gv) in enumerate(rows):
        n = len(gi)
        idx[i, :n] = gi
        val[i, :n] = gv
        mask[i, :n] = True
        counts[i] = n
    x0_arr = np.zeros((b_pad, cfg.rank), np.float32)
    if x0 is not None:
        x0_arr[:B] = np.asarray(x0, np.float32)
    cg_iters = max(cfg.cg_iters, FOLD_IN_CG_ITERS)
    fn = _build_fold_in(b_pad, l_pad, cfg.rank, cfg.implicit,
                        cfg.solver, cg_iters)
    out = fn(jnp.asarray(Y, dtype=jnp.float32), idx, val, mask,
             counts, x0_arr, np.float32(cfg.reg), np.float32(cfg.alpha))
    return np.asarray(out)[:B]


def predict_rmse(factors: ALSFactors, coo) -> float:
    """Host-side RMSE over COO ratings (evaluation metric helper)."""
    u, i, r = coo
    pred = np.einsum(
        "nk,nk->n", factors.user_factors[np.asarray(u)], factors.item_factors[np.asarray(i)]
    )
    return float(np.sqrt(np.mean((pred - np.asarray(r)) ** 2)))
