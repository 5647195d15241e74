"""Two-tower neural retrieval trained with in-batch softmax on the mesh.

The stretch model proving DASE extends past factorization to deep
models (SURVEY.md §7.7): a user tower and item tower (id embedding ->
optional MLP -> L2-normalized vector) trained on positive (user, item)
events with a symmetric in-batch sampled-softmax loss — the standard
retrieval formulation. The reference has no neural models (Spark MLlib
only), so the behavior contract is the recommendation template's (same
query/result surface as ALS); the training loop is what a TPU-native
framework adds.

The loop is shaped by what actually binds at catalog scale (1M x 128
tables):

  - ROW-SPARSE table updates. A flax ``nn.Embed`` under
    ``value_and_grad`` materializes a DENSE [N, E] gradient and a dense
    optimizer pass per step — GBs of HBM traffic for a batch that
    touches 8k of 1M rows. Tables here are raw arrays, gathered rows
    enter the loss directly, and the update is rowwise ADAGRAD (the
    DLRM-standard embedding optimizer): one scalar accumulator per row,
    scatter-add (duplicate-index-safe), donated buffers so XLA updates
    in place. Dense MLP params (when ``hidden``/``embed_dim`` add any)
    keep AdamW.
  - WHOLE EPOCH under one jit: positives live on device; each epoch is
    a single ``lax.scan`` over a device-computed permutation — one
    dispatch per epoch instead of one per batch, so neither host Python
    nor per-batch transfers gap the device.
  - bf16 MATMULS, f32 everywhere it matters: tower compute and the
    [B, B] logits einsum run in ``compute_dtype`` (bf16 = native MXU
    input) with f32 accumulation; the L2 normalization, softmax/CE, and
    all optimizer state stay f32.

Kernel layer (r6): on single-device runs the blockwise-CE scan body
and (opt-in) the table update can be replaced by Pallas kernels from
``ops/pallas/`` — the fused flash-CE ``custom_vjp`` pair and the fused
embedding-update pass — selected per-trainer by ``_plan_kernels`` with
the XLA forms below remaining the reference and the fallback
(equivalence pinned by tests/test_pallas_kernels.py; flags in
``TwoTowerConfig``; interpret mode covers them on CPU tier-1).

Mesh mapping:
  - the scan's batch axis is sharding-constrained over ``data`` (DP):
    each device gathers and runs tower compute on its batch shard; the
    in-batch softmax needs every item vector, so the logits einsum
    induces an all-gather over ``data`` — the TPU analogue of the
    reference's Spark shuffle, riding ICI.
  - optionally the tables (and their accumulators) are row-sharded
    over ``model`` (TP) for catalogs too large to replicate
    (``shard_embeddings``); lookups then gather over ICI.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from predictionio_tpu.ops import pallas as _plk

from predictionio_tpu.ops.pallas import embed_update as _pl_embed
from predictionio_tpu.ops.pallas import flash_ce as _pl_flash


@dataclasses.dataclass(frozen=True)
class TwoTowerConfig:
    dim: int = 64                      # final embedding dimension
    hidden: Tuple[int, ...] = ()       # MLP widths on top of the id embedding
    embed_dim: Optional[int] = None    # id-embedding width (default: dim)
    temperature: float = 0.07
    learning_rate: float = 3e-3        # dense (AdamW) learning rate
    weight_decay: float = 1e-6         # dense AdamW weight decay
    table_learning_rate: Optional[float] = None  # rowwise-adagrad lr for
                                                 # the id tables (default:
                                                 # 10x learning_rate — the
                                                 # usual embedding/dense
                                                 # split; adagrad shrinks
                                                 # its own effective rate)
    epochs: int = 5
    batch_size: int = 1024
    seed: int = 11
    compute_dtype: str = "bfloat16"    # tower matmul input dtype (f32 accum)
    loss_chunk: Optional[int] = 2048   # blockwise in-batch CE: compute the
                                       # [B, B] logits in [B, chunk] column
                                       # tiles under jax.checkpoint so the
                                       # full matrix never hits HBM (the
                                       # flash-attention trick applied to
                                       # the softmax CE) — engages when
                                       # batch_size >= 2*chunk; None =
                                       # always dense. Measured r5: the
                                       # dense loss made the step HBM-bound
                                       # on B^2 mask/softmax passes (6.4 ms
                                       # at B=8192 D=128, 2.1% MFU)
    shard_embeddings: bool = False     # row-shard tables over the "model" axis
    checkpoint_dir: Optional[str] = None  # mid-training checkpoint/resume
    checkpoint_every: int = 1             # epochs between checkpoints
    flash_ce_kernel: str = "auto"      # Pallas fused flash-CE loss kernel:
                                       # "auto" (on for single-device TPU
                                       # runs, XLA elsewhere) | "on" | "off"
    embed_update_kernel: str = "off"   # Pallas fused table-update kernel:
                                       # default OFF pending an on-chip win
                                       # over the measured XLA scatter floor
                                       # (ops/pallas/embed_update.py
                                       # docstring)


@dataclasses.dataclass
class TwoTowerEmbeddings:
    user_vecs: np.ndarray    # [n_users, dim] float32, L2-normalized
    item_vecs: np.ndarray    # [n_items, dim] float32, L2-normalized
    losses: List[float]      # per-epoch mean loss


def _init_dense(key, widths, cfg: TwoTowerConfig):
    """He-init MLP params for one tower's tail ([] when the tail is
    pure normalization)."""
    layers = []
    for w_in, w_out in zip(widths[:-1], widths[1:]):
        key, k = jax.random.split(key)
        layers.append({
            "w": jax.random.normal(k, (w_in, w_out), jnp.float32)
            * np.sqrt(2.0 / w_in),
            "b": jnp.zeros((w_out,), jnp.float32),
        })
    return layers


def _tail_widths(cfg: TwoTowerConfig) -> List[int]:
    width = cfg.embed_dim or cfg.dim
    widths = [width, *cfg.hidden]
    if cfg.hidden or width != cfg.dim:
        widths.append(cfg.dim)
    return widths


def _apply_tail(dense, x, cfg: TwoTowerConfig):
    """Gathered embedding rows -> L2-normalized tower output.

    Matmuls run in ``compute_dtype`` with f32 accumulation (MXU native);
    the final normalization is f32 (a bf16 norm would quantize the unit
    sphere the dot-product scores live on)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    h = x
    for li, layer in enumerate(dense):
        h = jnp.einsum("be,eh->bh", h.astype(cdt), layer["w"].astype(cdt),
                       preferred_element_type=jnp.float32) + layer["b"]
        if li < len(dense) - 1:
            h = jax.nn.relu(h)
    h = h.astype(jnp.float32)
    return h / jnp.maximum(jnp.linalg.norm(h, axis=-1, keepdims=True), 1e-8)


def _dense_softmax_ce(u, v, u_idx, i_idx, weight, temp, cdt):
    """Reference dense form: materializes the [B, B] logits and masks.

    Kept for small batches (the blockwise form needs B >= 2*chunk) and
    as the numerical ground truth the blockwise path is tested against.
    Masks in-batch false negatives: the same item (user->item
    direction) or the same user (item->user) elsewhere in the batch,
    and zero-weight padding rows whose placeholders would otherwise act
    as real negatives."""
    logits = jnp.einsum("bd,cd->bc", u.astype(cdt), v.astype(cdt),
                        preferred_element_type=jnp.float32) / temp
    B = logits.shape[0]
    eye = jnp.eye(B, dtype=bool)
    pad_col = (weight <= 0.0)[None, :]
    dup_i = ((i_idx[None, :] == i_idx[:, None]) | pad_col) & ~eye
    dup_u = ((u_idx[None, :] == u_idx[:, None]) | pad_col) & ~eye
    labels = jnp.arange(B)
    l_ui = optax.softmax_cross_entropy_with_integer_labels(
        jnp.where(dup_i, -1e9, logits), labels)
    l_iu = optax.softmax_cross_entropy_with_integer_labels(
        jnp.where(dup_u, -1e9, logits.T), labels)
    wsum = jnp.maximum(weight.sum(), 1e-8)
    return jnp.sum(0.5 * (l_ui + l_iu) * weight) / wsum


def _blockwise_softmax_ce(u, v, u_idx, i_idx, weight, temp, chunk, cdt):
    """Dispatch: hand-written VJP by default (fewer backward passes —
    the saved LSEs make the softmax reconstruction one fused pass per
    tile, skipping autodiff's scan-reversal and logsumexp-grad
    plumbing); the checkpoint-autodiff form below remains as
    ``_blockwise_softmax_ce_autodiff`` and the equivalence tests pin
    the two to each other and to the dense reference."""
    fn = _make_blockwise_ce_vjp(u_idx, i_idx, weight, temp, chunk, cdt,
                                u.shape[0])
    return fn(u, v)


def _blockwise_softmax_ce_autodiff(u, v, u_idx, i_idx, weight, temp, chunk,
                                   cdt):
    """Blockwise symmetric in-batch softmax CE (the flash-attention
    trick applied to the retrieval loss): logits are computed in
    [B, chunk] column tiles inside ``jax.checkpoint``, so the full
    [B, B] matrix and its masks NEVER materialize in HBM — the step
    stays matmul-bound instead of elementwise-HBM-bound (the dense
    loss measured 6.4 ms a step at B=8192, D=128, before the chip).

    One pass over column tiles yields BOTH directions: each tile
    contributes a partial row-LSE for user->item (combined across tiles
    afterwards) and the COMPLETE column-LSE for its items' item->user
    terms. Same masking semantics as ``_dense_softmax_ce`` (tested
    equal). With the default temperature the direct-exp one-pass LSE
    runs (see _tile_stats — banned entries contribute exp=0, all-banned
    tile parts go -inf and the cross-tile combine absorbs them); the
    1/temp > _DIRECT_EXP_MAX_INV_TEMP fallback uses a -1e9 sentinel
    (not -inf) so all-banned tiles' grads stay finite under autodiff."""
    B, _ = u.shape
    S = B // chunk
    rows = jnp.arange(B)
    v_t = v.reshape(S, chunk, -1)
    i_t = i_idx.reshape(S, chunk)
    w_t = weight.reshape(S, chunk)
    col_t = rows.reshape(S, chunk)
    pad_row = (weight <= 0.0)[:, None]
    wsum = jnp.maximum(weight.sum(), 1e-8)
    direct_exp = (1.0 / temp) <= _DIRECT_EXP_MAX_INV_TEMP

    def tile(u, vc, ic, wc, colc):
        # the tile logits stay in compute_dtype (bf16): the matmul
        # output is the tile's dominant HBM stream and the CE reads it
        # several times; unit-sphere logits (|L| <= 1/temp ~ 14) lose
        # ~3 decimal digits to bf16, well inside the loss's tolerance.
        # The diag/LSE accumulations (inside _tile_stats) are f32.
        Lc = jnp.einsum("bd,cd->bc", u.astype(cdt), vc.astype(cdt)) / temp
        not_diag, ban_ui, ban_iu = _tile_masks(
            rows, u_idx, i_idx, pad_row, ic, wc, colc, u_idx[colc])
        lse_ui_c, diag_c, lse_iu_c, pos_c = _tile_stats(
            Lc, not_diag, ban_ui, ban_iu, direct_exp)
        iu_contrib = jnp.sum(wc * (lse_iu_c - pos_c))
        return lse_ui_c, diag_c, iu_contrib

    tile = jax.checkpoint(tile)

    # lax.scan over tiles (NOT a static unroll: measured on-chip at
    # B=8192/chunk=2048, the unrolled form was 10% slower per step and
    # ~2.5x slower to compile)
    def body(carry, xs):
        lse_ui_c, diag_c, iu_contrib = tile(u, *xs)
        return carry + iu_contrib, (lse_ui_c, diag_c)

    iu_total, (lse_parts, diag_parts) = jax.lax.scan(
        body, jnp.float32(0.0), (v_t, i_t, w_t, col_t))
    l_ui = jax.nn.logsumexp(lse_parts, axis=0) - diag_parts.sum(axis=0)
    return (0.5 * (jnp.sum(l_ui * weight) + iu_total)) / wsum


def _tile_masks(rows, u_idx, i_idx, pad_row, ic, wc, colc, uc):
    """The ONE place the in-batch false-negative banning semantics
    live for the blockwise forms (the dense reference states them
    independently and the equivalence tests pin all three): ban the
    same item elsewhere in the batch (user->item), the same user
    (item->user), and zero-weight padding rows/columns — never the
    diagonal."""
    not_diag = colc[None, :] != rows[:, None]
    ban_ui = ((ic[None, :] == i_idx[:, None])
              | (wc <= 0.0)[None, :]) & not_diag
    ban_iu = ((u_idx[:, None] == uc[None, :]) | pad_row) & not_diag
    return not_diag, ban_ui, ban_iu


#: direct exp-sum-log is safe while |logit| <= 1/temp stays under this.
#: f32 overflows at exp(~88.7) and the reduction sums up to B terms, so
#: the bound needs ln(B) headroom: 70 + ln(2^24) ~ 86.6 keeps the SUM
#: finite for any batch this module could run. Tower outputs are
#: L2-normalized, so the logit bound itself is STRUCTURAL.
_DIRECT_EXP_MAX_INV_TEMP = 70.0


def _tile_stats(Lc, not_diag, ban_ui, ban_iu, direct_exp):
    """Per-tile LSE/diag reductions shared by both blockwise forms.
    The f32 casts fuse into the reductions (registers, not HBM): only
    the matmul output's cdt stream touches memory.

    ``direct_exp`` (on whenever 1/temp <= _DIRECT_EXP_MAX_INV_TEMP):
    unit-sphere logits are bounded by 1/temp, so exp cannot overflow
    f32 and the LSEs compute as log(sum(exp(L))) in ONE pass — no
    max-subtraction reduction. Banned entries contribute exp=0; a tile
    whose row/column is fully banned yields -inf, which the cross-tile
    logsumexp combine absorbs (the diagonal is never banned, so every
    row/column has a finite part somewhere)."""
    f32 = jnp.float32
    if direct_exp:
        e = jnp.exp(Lc.astype(f32))
        lse_ui_c = jnp.log(jnp.sum(jnp.where(ban_ui, 0.0, e), axis=1))
        lse_iu_c = jnp.log(jnp.sum(jnp.where(ban_iu, 0.0, e), axis=0))
    else:
        lse_ui_c = jax.nn.logsumexp(
            jnp.where(ban_ui, -1e9, Lc).astype(f32), axis=1)  # [B]
        lse_iu_c = jax.nn.logsumexp(
            jnp.where(ban_iu, -1e9, Lc).astype(f32), axis=0)  # [C]
    diag_c = jnp.sum(jnp.where(~not_diag, Lc, 0.0).astype(f32), axis=1)
    pos_c = jnp.sum(jnp.where(~not_diag, Lc, 0.0).astype(f32), axis=0)
    return lse_ui_c, diag_c, lse_iu_c, pos_c


def _make_blockwise_ce_vjp(u_idx, i_idx, weight, temp, chunk, cdt, B):
    """Blockwise CE with a HAND-WRITTEN VJP.

    Forward matches ``_blockwise_softmax_ce_autodiff`` (tested equal);
    backward uses the saved row/column LSEs directly:

        dLoss/dL[b,j] = [w_b (p_ui - δ) + w_j (p_iu - δ)] / (2·Σw)
        p_ui[b,j] = exp(L[b,j] - lse_ui[b])   (0 where banned)
        p_iu[b,j] = exp(L[b,j] - lse_iu[j])   (0 where banned)

    so the softmax reconstruction is ONE fused exp/where pass per tile
    feeding two grad matmuls — no autodiff scan-reversal, no
    logsumexp-grad max-pass recompute. Only (u, v) residuals plus two
    [B] LSE vectors are saved.

    ``u_idx``/``i_idx``/``weight`` are NON-DIFFERENTIABLE BY
    CONSTRUCTION: they are closed over by this factory, not traced
    arguments of the returned ``ce(u, v)``, and the custom_vjp
    declares cotangents only for (u, v). Differentiating a surrounding
    loss w.r.t. ``weight`` (weighted-loss tuning) does NOT silently
    return zero grads — JAX raises ``UnexpectedTracerError`` on the
    closed-over tracer. To make weights tunable, thread them as a real
    argument with an explicit d(loss)/dw rule (the loss is linear in w:
    dLoss/dw_b = [0.5*(l_ui[b] + l_iu[b]) - loss] / Sum_w), or use the
    checkpoint-autodiff form, which differentiates anything. The same
    contract holds for the Pallas flash-CE kernel
    (ops/pallas/flash_ce.py), which mirrors this factory's closure."""
    S = B // chunk
    rows = jnp.arange(B)
    i_t = i_idx.reshape(S, chunk)
    w_t = weight.reshape(S, chunk)
    col_t = rows.reshape(S, chunk)
    uc_t = u_idx.reshape(S, chunk)
    pad_row = (weight <= 0.0)[:, None]
    wsum = jnp.maximum(weight.sum(), 1e-8)
    f32 = jnp.float32
    direct_exp = (1.0 / temp) <= _DIRECT_EXP_MAX_INV_TEMP

    def masks(ic, wc, colc, uc):
        return _tile_masks(rows, u_idx, i_idx, pad_row, ic, wc, colc, uc)

    def _fwd_parts(u, v):
        v_t = v.reshape(S, chunk, -1)

        def body(iu_acc, xs):
            vc, ic, wc, colc, uc = xs
            Lc = jnp.einsum("bd,cd->bc", u.astype(cdt),
                            vc.astype(cdt)) / temp
            not_diag, ban_ui, ban_iu = masks(ic, wc, colc, uc)
            lse_c, diag_c, lse_iu_c, pos_c = _tile_stats(
                Lc, not_diag, ban_ui, ban_iu, direct_exp)
            iu_acc = iu_acc + jnp.sum(wc * (lse_iu_c - pos_c))
            return iu_acc, (lse_c, diag_c, lse_iu_c)

        iu_total, (lse_parts, diag_parts, lse_iu_parts) = jax.lax.scan(
            body, jnp.float32(0.0), (v_t, i_t, w_t, col_t, uc_t))
        lse_ui = jax.nn.logsumexp(lse_parts, axis=0)          # [B]
        l_ui = lse_ui - diag_parts.sum(axis=0)
        loss = 0.5 * (jnp.sum(l_ui * weight) + iu_total) / wsum
        return loss, lse_ui, lse_iu_parts.reshape(B)

    @jax.custom_vjp
    def ce(u, v):
        return _fwd_parts(u, v)[0]

    def fwd(u, v):
        loss, lse_ui, lse_iu = _fwd_parts(u, v)
        return loss, (u, v, lse_ui, lse_iu)

    def bwd(res, ct):
        u, v, lse_ui, lse_iu = res
        v_t = v.reshape(S, chunk, -1)
        lse_iu_t = lse_iu.reshape(S, chunk)
        scale = ct / (2.0 * wsum * temp)

        def body(du, xs):
            vc, ic, wc, colc, uc, lse_iu_c = xs
            # recompute the tile logits EXACTLY as fwd did (cdt divide
            # BEFORE the f32 cast): under bf16 a different rounding
            # here would reconstruct probabilities inconsistent with
            # the saved LSEs — a systematic grad bias (r5 review)
            Lc = (jnp.einsum("bd,cd->bc", u.astype(cdt),
                             vc.astype(cdt)) / temp).astype(f32)
            not_diag, ban_ui, ban_iu = masks(ic, wc, colc, uc)
            p_ui = jnp.where(ban_ui, 0.0, jnp.exp(Lc - lse_ui[:, None]))
            p_iu = jnp.where(ban_iu, 0.0, jnp.exp(Lc - lse_iu_c[None, :]))
            isdiag = (~not_diag).astype(f32)
            coef = (weight[:, None] * (p_ui - isdiag)
                    + wc[None, :] * (p_iu - isdiag)) * scale
            cc = coef.astype(cdt)
            du = du + jnp.einsum("bc,cd->bd", cc, vc.astype(cdt),
                                 preferred_element_type=f32)
            dvc = jnp.einsum("bc,bd->cd", cc, u.astype(cdt),
                             preferred_element_type=f32)
            return du, dvc

        du, dv_t = jax.lax.scan(
            body, jnp.zeros_like(u),
            (v_t, i_t, w_t, col_t, uc_t, lse_iu_t))
        return du, dv_t.reshape(B, -1)

    ce.defvjp(fwd, bwd)
    return ce


def _rowwise_adagrad(table, acc, idx, grad, lr, eps=1e-8):
    """DLRM-style sparse embedding update: one accumulator scalar per
    row, scatter-add so duplicate in-batch indices accumulate correctly
    — per-step traffic is O(batch x dim), never O(vocab x dim).

    MEASURED (r5, B=8192 rows into [1M, 128], real chip): the scatter
    costs ~0.62 ms/table/step — the largest non-matmul term in the
    two-tower step (~30%). Two attempted fixes both REJECTED on the
    integrated step:
      - ``optimization_barrier`` pinning gather-before-scatter (the
        copy-insertion theory): no change — the cost is the scatter's
        own ~75 ns/row issue rate, not a table copy;
      - argsort + ``indices_are_sorted=True`` (the "sorted fast path"
        theory): step 4.16 -> 6.37 ms — the sorted lowering plus the
        [B, E] gather-reorder is 2.6x SLOWER than the plain unsorted
        scatter at these shapes;
      - FUSING the accumulator into the table as a 129th column (one
        [N, E+1] scatter per side instead of table-scatter +
        acc-scatter + acc-gather): step 2.90 -> 2.98 ms — the odd row
        width breaks (8,128) tile alignment so each scattered row
        spans two lane tiles (scatter fusions 0.62 -> 0.71 ms each),
        while the dropped acc ops were nearly free (their rows are
        scalar-thin; the scatter cost scales with aligned row tiles,
        not a fixed per-row issue rate).
    The unsorted duplicate-safe scatter-add on the [N, E] table
    stands."""
    g2 = jnp.mean(grad * grad, axis=-1)              # [B]
    acc = acc.at[idx].add(g2)
    scale = lr / jnp.sqrt(acc[idx] + eps)            # read after add
    table = table.at[idx].add(-scale[:, None] * grad)
    return table, acc


class TwoTowerTrainer:
    """Prepared training run over positive (user, item, weight) triples.

    Mirrors ALSTrainer's shape: one-time costs (param init, device
    placement, compile) in the constructor, `run()` drives compiled
    epochs, `embeddings()` materializes the serving tables.
    """

    def __init__(
        self,
        positives: Tuple[np.ndarray, np.ndarray, Optional[np.ndarray]],
        n_users: int,
        n_items: int,
        cfg: TwoTowerConfig,
        mesh: Optional[Mesh] = None,
    ):
        u_idx, i_idx, w = positives
        self.cfg = cfg
        self.mesh = mesh
        self.n_users, self.n_items = n_users, n_items
        u = np.asarray(u_idx, dtype=np.int32)
        i = np.asarray(i_idx, dtype=np.int32)
        w = (np.ones(len(u), np.float32) if w is None
             else np.asarray(w, dtype=np.float32))
        self.n_pos = len(u)

        n_data = mesh.shape.get("data", 1) if mesh is not None else 1
        # fixed step shape: full batches only, tails padded via a dummy
        # zero-weight row appended at index n_pos
        self.batch = max(cfg.batch_size - cfg.batch_size % max(n_data, 1),
                         n_data)
        self.steps_per_epoch = max(1, -(-self.n_pos // self.batch))

        # the dataset lives on device for the whole run (one transfer);
        # index n_pos is the padding row. Replicated explicitly under a
        # mesh so the epoch jit sees consistent placement.
        def _put_data(a):
            if mesh is not None:
                return jax.device_put(a, NamedSharding(mesh, P()))
            return jnp.asarray(a)

        self._u = _put_data(np.concatenate([u, np.zeros(1, np.int32)]))
        self._i = _put_data(np.concatenate([i, np.zeros(1, np.int32)]))
        self._w = _put_data(np.concatenate([w, np.zeros(1, np.float32)]))

        self.kernel_plan = self._plan_kernels()

        width = cfg.embed_dim or cfg.dim
        k0, k1, k2, k3 = jax.random.split(jax.random.PRNGKey(cfg.seed), 4)
        scale = 1.0 / np.sqrt(width)
        tables = {
            "user": jax.random.normal(k0, (n_users, width), jnp.float32) * scale,
            "item": jax.random.normal(k1, (n_items, width), jnp.float32) * scale,
        }
        acc = {
            "user": jnp.zeros((n_users,), jnp.float32),
            "item": jnp.zeros((n_items,), jnp.float32),
        }
        widths = _tail_widths(cfg)
        dense = {"user": _init_dense(k2, widths, cfg),
                 "item": _init_dense(k3, widths, cfg)}
        self._tx = optax.adamw(cfg.learning_rate,
                               weight_decay=cfg.weight_decay)
        opt_state = self._tx.init(dense)

        if mesh is not None:
            if cfg.shard_embeddings and mesh.shape.get("model", 1) > 1:
                tshard = NamedSharding(mesh, P("model", None))
                ashard = NamedSharding(mesh, P("model"))
            else:
                tshard = NamedSharding(mesh, P())
                ashard = NamedSharding(mesh, P())
            rep = NamedSharding(mesh, P())
            tables = {k: jax.device_put(v, tshard) for k, v in tables.items()}
            acc = {k: jax.device_put(v, ashard) for k, v in acc.items()}
            dense = jax.device_put(dense, rep)
            opt_state = jax.device_put(opt_state, rep)
        self._state = (tables, acc, dense, opt_state)
        self._epoch_fn = self._make_epoch()
        self._compiled = None        # AOT executable of _epoch_fn
        self.compile_sec = 0.0
        self._epochs_done = 0
        self._losses: List[float] = []
        # MFU accounting (obs/perfacct.py): built with the AOT compile
        self._acct = None
        # device-memory ledger (obs/memacct.py): the whole-run device
        # residents — embedding tables + tail MLPs as params, adagrad
        # accumulators + adamw state as opt_state, the on-device
        # dataset as train_data — priced once, swept when the trainer
        # is dropped
        from predictionio_tpu.obs import memacct

        def _tree_bytes(tree) -> int:
            return sum(int(getattr(leaf, "nbytes", 0))
                       for leaf in jax.tree_util.tree_leaves(tree))

        self._param_bytes = _tree_bytes((tables, dense))
        self._opt_bytes = _tree_bytes((acc, opt_state))
        data_bytes = _tree_bytes((self._u, self._i, self._w))
        memacct.LEDGER.register(self, "twotower", "params",
                                self._param_bytes)
        memacct.LEDGER.register(self, "twotower", "opt_state",
                                self._opt_bytes)
        memacct.LEDGER.register(self, "twotower", "train_data",
                                data_bytes)
        self._data_bytes = data_bytes

        # mid-training checkpoint/resume (core.checkpoint — beyond the
        # reference's train-to-completion-or-nothing, SURVEY.md §5.4)
        self._ckpt = None
        if cfg.checkpoint_dir:
            from predictionio_tpu.core.checkpoint import (
                TrainCheckpointer,
                train_fingerprint,
            )

            fp = train_fingerprint(
                cfg, n_users, n_items, self.n_pos,
                u[:4096], u[-4096:], i[:4096], w[:4096],
            )
            self._ckpt = TrainCheckpointer(cfg.checkpoint_dir,
                                           every=cfg.checkpoint_every,
                                           fingerprint=fp)
            restored = self._ckpt.restore()
            if restored is not None:
                epoch, state = restored
                tables, acc, dense, opt_state = (
                    state["tables"], state["acc"], state["dense"],
                    state["opt_state"])
                if mesh is not None:
                    tables = {k: jax.device_put(v, tshard)
                              for k, v in tables.items()}
                    acc = {k: jax.device_put(v, ashard)
                           for k, v in acc.items()}
                    dense = jax.device_put(dense, rep)
                    opt_state = jax.device_put(opt_state, rep)
                self._state = (tables, acc, dense, opt_state)
                self._epochs_done = epoch
                self._losses = list(state["losses"])

    # -- kernel selection ---------------------------------------------------

    def _plan_kernels(self) -> dict:
        """Decide, once per trainer, whether the Pallas kernels
        (ops/pallas/) replace their XLA forms for this run.

        Eligibility is per-kernel; both additionally require a
        single-device run (``pallas_call`` does not partition under a
        multi-device mesh). An engaged kernel is used as is: if the
        chip's compiler refuses it, the first epoch raises that error —
        there is no XLA fallback to hide it. The decision dict
        is exported (``pio train``'s report, ``kernel_plan`` and the
        ``pio_pallas_kernel_enabled`` metric) so a capture always says
        which path produced it."""
        from predictionio_tpu.obs import jaxmon

        cfg = self.cfg
        interp = _plk.interpret_mode()
        backend = jax.default_backend()
        on_tpu = backend == "tpu"
        single = self.mesh is None or self.mesh.size == 1
        direct = (1.0 / cfg.temperature) <= _DIRECT_EXP_MAX_INV_TEMP
        plan = {"interpret": interp, "backend": backend}

        elig_ce = single and direct and self.batch >= _pl_flash.MIN_BATCH
        why_ce = ("multi-device mesh" if not single
                  else "1/temp outside the direct-exp regime" if not direct
                  else f"batch {self.batch} < {_pl_flash.MIN_BATCH}")
        ce_on, ce_why = _plk.decide(
            cfg.flash_ce_kernel, eligible=elig_ce, ineligible_reason=why_ce,
            auto_default=on_tpu)

        emb_on, emb_why = _plk.decide(
            cfg.embed_update_kernel, eligible=single,
            ineligible_reason="multi-device mesh",
            auto_default=False)  # default-off: measured-rejection
        #                          discipline, ops/pallas/embed_update.py

        plan.update({"flash_ce": ce_on, "flash_ce_reason": ce_why,
                     # which backward the kernel's own shape rule takes
                     # ("one_pass" / "two_pass"); None where it is off
                     "flash_ce_backward": (
                         _pl_flash.backward_form(self.batch, cfg.dim)
                         if ce_on else None),
                     "embed_update": emb_on, "embed_update_reason": emb_why})
        jaxmon.record_kernel_plan(plan)
        return plan

    # -- loss ---------------------------------------------------------------

    def _loss_from_rows(self, ue, ve, dense, u_idx, i_idx, weight):
        cfg = self.cfg
        u = _apply_tail(dense["user"], ue, cfg)         # [B, D] f32 unit
        v = _apply_tail(dense["item"], ve, cfg)
        B = u.shape[0]
        if self.kernel_plan["flash_ce"]:
            with jax.named_scope("twotower.flash_ce"):
                return _pl_flash.pallas_blockwise_ce(
                    u, v, u_idx, i_idx, weight, cfg.temperature,
                    jnp.dtype(cfg.compute_dtype),
                    interpret=self.kernel_plan["interpret"])
        chunk = cfg.loss_chunk
        if chunk and B >= 2 * chunk and B % chunk == 0:
            return _blockwise_softmax_ce(
                u, v, u_idx, i_idx, weight, cfg.temperature, chunk,
                jnp.dtype(cfg.compute_dtype))
        return _dense_softmax_ce(
            u, v, u_idx, i_idx, weight, cfg.temperature,
            jnp.dtype(cfg.compute_dtype))

    # -- epoch program ------------------------------------------------------

    def _make_epoch(self):
        cfg = self.cfg
        tx = self._tx
        B = self.batch
        S = self.steps_per_epoch
        n = self.n_pos
        table_lr = (cfg.table_learning_rate
                    if cfg.table_learning_rate is not None
                    else 10.0 * cfg.learning_rate)
        mesh = self.mesh
        dp = mesh is not None and mesh.shape.get("data", 1) > 1
        loss_from_rows = self._loss_from_rows
        if self.kernel_plan["embed_update"]:
            row_update = functools.partial(
                _pl_embed.pallas_rowwise_adagrad,
                interpret=self.kernel_plan["interpret"])
        else:
            row_update = _rowwise_adagrad

        # the scopes name the step's parts for whoever reads a device
        # trace (obs/jaxmon.record_scope_map keeps instruction -> scope
        # of the compiled program); they add metadata, not operations
        @jax.named_scope("twotower.step")
        def step(carry, idx):
            tables, acc, dense, opt_state = carry
            with jax.named_scope("twotower.gather"):
                u_idx = self._u[idx]
                i_idx = self._i[idx]
                w = self._w[idx]
                ue = tables["user"][u_idx]              # [B, E] gather
                ve = tables["item"][i_idx]
            loss, (gu, gv, gd) = jax.value_and_grad(
                loss_from_rows, argnums=(0, 1, 2),
            )(ue, ve, dense, u_idx, i_idx, w)
            tables = dict(tables)
            acc = dict(acc)
            with jax.named_scope("twotower.adagrad_user"):
                tables["user"], acc["user"] = row_update(
                    tables["user"], acc["user"], u_idx, gu, table_lr)
            with jax.named_scope("twotower.adagrad_item"):
                tables["item"], acc["item"] = row_update(
                    tables["item"], acc["item"], i_idx, gv, table_lr)
            if any(len(v) for v in dense.values()):
                updates, opt_state = tx.update(gd, opt_state, dense)
                dense = optax.apply_updates(dense, updates)
            return (tables, acc, dense, opt_state), loss

        def epoch(tables, acc, dense, opt_state, key):
            perm = jax.random.permutation(key, n)
            order = jnp.concatenate(
                [perm.astype(jnp.int32),
                 jnp.full((S * B - n,), n, jnp.int32)]).reshape(S, B)
            if dp:
                order = jax.lax.with_sharding_constraint(
                    order, NamedSharding(mesh, P(None, "data")))
            (tables, acc, dense, opt_state), losses = jax.lax.scan(
                step, (tables, acc, dense, opt_state), order)
            # [mean, first step, last step]: the mean is the epoch's
            # loss; first vs last shows it fell within ONE epoch
            return tables, acc, dense, opt_state, jnp.stack(
                [losses.mean(), losses[0], losses[-1]])

        return jax.jit(epoch, donate_argnums=(0, 1, 2, 3))

    def run(self, epochs: Optional[int] = None) -> List[float]:
        """Train up to ``epochs`` TOTAL epochs (resume-aware: epochs
        already completed by a restored checkpoint are not repeated).
        One device dispatch per epoch; the shuffle key derives from
        (seed, epoch index) so a resumed run replays the same order."""
        import time as _time

        from predictionio_tpu.obs import jaxmon, trace

        target = epochs if epochs is not None else self.cfg.epochs
        base = jax.random.PRNGKey(self.cfg.seed + 1)
        while self._epochs_done < target:
            key = jax.random.fold_in(base, self._epochs_done)
            if self._compiled is None:
                self._compile_epoch(key)
            with trace.device_span("train.epoch",
                                   epoch=self._epochs_done):
                t_step = _time.perf_counter()
                *state, stats = self._compiled(*self._state, key)
                mean_loss, first, last = np.asarray(
                    jax.block_until_ready(stats))
                epoch_sec = _time.perf_counter() - t_step
            with trace.device_span("train.report"):
                self._state = tuple(state)
                self._losses.append(float(mean_loss))
                # per-dispatch wall time onto pio_train_step_seconds;
                # also beats the train-step stall watchdog
                # (obs/health.py)
                jaxmon.observe_train_step(epoch_sec)
                self._acct.observe(epoch_sec)
                jaxmon.record_trainer_report("twotower", {
                    "kernel_plan": self.kernel_plan,
                    "steps_per_epoch": self.steps_per_epoch,
                    "batch": self.batch,
                    "compile_sec": round(self.compile_sec, 3),
                    # host clock around one epoch dispatch, ended by
                    # block_until_ready — compile excluded (AOT above)
                    "epoch_sec": epoch_sec,
                    "step_ms": epoch_sec / self.steps_per_epoch * 1e3,
                    "first_step_loss": float(first),
                    "last_step_loss": float(last),
                    "epoch_losses": list(self._losses),
                })
                self._epochs_done += 1
                if self._ckpt is not None:
                    tables, acc, dense, opt_state = self._state
                    self._ckpt.maybe_save(self._epochs_done, {
                        "tables": tables, "acc": acc, "dense": dense,
                        "opt_state": opt_state,
                        "losses": list(self._losses),
                    })
        return list(self._losses)

    def _compile_epoch(self, key) -> None:
        """Compile the epoch program ahead of time, once (its shapes
        are stable across epochs): the SAME executable then gives the
        dispatches, the MFU cost basis and the train high-water mark,
        and the first epoch's timing carries no compile."""
        import time as _time

        from predictionio_tpu.obs import jaxmon, memacct, perfacct

        t0 = _time.perf_counter()
        self._compiled = self._epoch_fn.lower(*self._state, key).compile()
        self.compile_sec = _time.perf_counter() - t0
        jaxmon.record_scope_map(self._compiled)
        # one dispatch = one epoch (the jitted lax.scan), so the cost
        # basis is per-EPOCH: cost_analysis of the compiled epoch when
        # the backend reports one, else the analytic matmul count x
        # steps (obs/perfacct.twotower_matmul_flops)
        self._acct = perfacct.StepAccountant.from_compiled(
            "twotower", self._compiled,
            fallback_flops=(self.matmul_flops_per_step()
                            * self.steps_per_epoch))
        # train high-water (obs/memacct.py): memory_analysis of the
        # same executable, else the analytic floor — every whole-run
        # resident plus one gradient-sized temp set
        peak = memacct.peak_from_compiled(self._compiled)
        if peak is not None:
            memacct.note_train_peak("twotower", peak,
                                    source="memory_analysis")
        else:
            memacct.note_train_peak(
                "twotower",
                2 * self._param_bytes + self._opt_bytes + self._data_bytes,
                source="analytic")

    # -- serving tables -----------------------------------------------------

    def _all_vecs(self, side: str, n: int) -> np.ndarray:
        tables, _, dense, _ = self._state
        cfg = self.cfg

        @jax.jit
        def fwd(table_chunk, dense_side):
            return _apply_tail(dense_side, table_chunk, cfg)

        chunk = 8192
        out = np.empty((n, cfg.dim), np.float32)
        for s in range(0, n, chunk):
            e = min(s + chunk, n)
            out[s:e] = np.asarray(fwd(tables[side][s:e], dense[side]))
        return out

    def embeddings(self, losses: Optional[List[float]] = None) -> TwoTowerEmbeddings:
        return TwoTowerEmbeddings(
            user_vecs=self._all_vecs("user", self.n_users),
            item_vecs=self._all_vecs("item", self.n_items),
            losses=losses or [],
        )

    def matmul_flops_per_step(self) -> float:
        """Analytic matmul FLOPs per training step (fwd + bwd), by
        obs/perfacct.twotower_matmul_flops: the live ``pio_train_mfu``
        gauge's cost basis where the backend reports none."""
        from predictionio_tpu.obs import perfacct

        return perfacct.twotower_matmul_flops(
            self.batch, self.cfg.dim, _tail_widths(self.cfg))


def twotower_train(
    positives: Tuple[np.ndarray, np.ndarray, Optional[np.ndarray]],
    n_users: int,
    n_items: int,
    cfg: TwoTowerConfig,
    mesh: Optional[Mesh] = None,
) -> TwoTowerEmbeddings:
    """One-call train from positive (user_idx, item_idx, weight?) triples."""
    trainer = TwoTowerTrainer(positives, n_users, n_items, cfg, mesh=mesh)
    losses = trainer.run()
    return trainer.embeddings(losses)


# ---------------------------------------------------------------------------
# streaming online steps (ROADMAP item C): bounded mini-batch gradient
# steps on a delta buffer, applied to the SERVING embeddings — the
# two-tower counterpart of the ALS fold-in. The full trainer owns the
# tables + tail MLP; at serving time a TwoTowerModel carries only the
# final (L2-normalized) embedding vectors, so the online step treats the
# touched rows as free embeddings and descends the same in-batch
# softmax-CE the trainer optimizes, renormalizing after each step to
# stay on the serving manifold. Quality gates for this delta path are a
# ROADMAP follow-up (item C close-out); equivalence with a full retrain
# is NOT claimed — this keeps fresh interactions from serving stale.
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=32)
def _build_online_step(steps: int):
    def run(Uu, Vi, pos_u, pos_i, weight, lr, temp):
        def loss_fn(params):
            Uu_, Vi_ = params
            return _dense_softmax_ce(Uu_[pos_u], Vi_[pos_i], pos_u, pos_i,
                                     weight, temp, jnp.float32)

        def renorm(t):
            return t / jnp.maximum(
                jnp.linalg.norm(t, axis=-1, keepdims=True), 1e-8)

        def body(params, _):
            loss, (gU, gV) = jax.value_and_grad(loss_fn)(params)
            Uu_, Vi_ = params
            return (renorm(Uu_ - lr * gU), renorm(Vi_ - lr * gV)), loss

        (Uu, Vi), losses = jax.lax.scan(body, (Uu, Vi), None, length=steps)
        return Uu, Vi, losses

    return jax.jit(run)


def online_delta_step(
    user_vecs: np.ndarray,
    item_vecs: np.ndarray,
    u_rows: np.ndarray,
    i_rows: np.ndarray,
    weight: Optional[np.ndarray] = None,
    lr: float = 0.05,
    steps: int = 4,
    temp: float = 0.05,
):
    """``steps`` SGD steps of the in-batch softmax CE over the delta
    pairs ``(u_rows[p], i_rows[p])``, updating ONLY the touched rows of
    the serving embedding tables.

    Returns ``(touched_u_rows, new_u_vecs, touched_i_rows, new_i_vecs,
    losses)`` — the unique touched row indices and their updated
    (renormalized) vectors; untouched rows are never read back, so the
    result is directly a model patch. Inputs pad to pow2 buckets so
    repeated folds hit a bounded set of compiled programs.
    """
    u_rows = np.asarray(u_rows, np.int32)
    i_rows = np.asarray(i_rows, np.int32)
    P = len(u_rows)
    if P == 0:
        d = user_vecs.shape[1]
        return (np.zeros(0, np.int32), np.zeros((0, d), np.float32),
                np.zeros(0, np.int32), np.zeros((0, d), np.float32), [])
    from predictionio_tpu.ops.als import _pow2_at_least

    uu, pos_u = np.unique(u_rows, return_inverse=True)
    ii, pos_i = np.unique(i_rows, return_inverse=True)
    p_pad = _pow2_at_least(P)
    bu_pad = _pow2_at_least(len(uu))
    bi_pad = _pow2_at_least(len(ii))
    d = user_vecs.shape[1]
    Uu = np.zeros((bu_pad, d), np.float32)
    Uu[:len(uu)] = np.asarray(user_vecs, np.float32)[uu]
    Vi = np.zeros((bi_pad, d), np.float32)
    Vi[:len(ii)] = np.asarray(item_vecs, np.float32)[ii]
    posu = np.zeros(p_pad, np.int32)
    posu[:P] = pos_u
    posi = np.zeros(p_pad, np.int32)
    posi[:P] = pos_i
    w = np.zeros(p_pad, np.float32)
    w[:P] = (np.asarray(weight, np.float32)
             if weight is not None else np.ones(P, np.float32))
    fn = _build_online_step(int(steps))
    Uu2, Vi2, losses = fn(Uu, Vi, posu, posi, w,
                          np.float32(lr), np.float32(temp))
    return (uu.astype(np.int32), np.asarray(Uu2)[:len(uu)],
            ii.astype(np.int32), np.asarray(Vi2)[:len(ii)],
            [float(x) for x in np.asarray(losses)])
