"""Sequential (next-item) recommendation: causal transformer over event
histories.

The reference's recommendation templates are order-blind matrix models
(MLlib ALS); nothing in it models the event *sequence* (SURVEY.md §5.7).
This module is the long-context model family the TPU rebuild adds: a
SASRec-style causal self-attention encoder over each user's
chronological item history, trained to predict the next item, with the
sequence axis scalable past one device's HBM via the attention paths in
ops.attention:

  - ``attn_block > 0``: flash-style blockwise scan (single device, long
    sequences without the O(L^2) score matrix),
  - ``seq_axis``: ring attention — the sequence dimension sharded over a
    mesh axis, kv blocks rotating over ICI (sequence/context
    parallelism). FFN/LayerNorm are position-wise, so GSPMD shards them
    along with the activations; only attention needs the ring.

Fixed shapes throughout: histories truncated/padded to ``max_len``
(item id 0 reserved for padding), so one compiled step serves every
batch. Embeddings tied between input and output softmax.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from predictionio_tpu.ops import gqa as gqa_ops
from predictionio_tpu.ops import mamba1 as mamba1_ops
from predictionio_tpu.ops import mla as mla_ops
from predictionio_tpu.ops import moe as moe_ops
from predictionio_tpu.ops import ssm as ssm_ops
from predictionio_tpu.ops.attention import (
    blockwise_attention,
    mha_reference,
    ring_attention_sharded,
)
from predictionio_tpu.ops.gqa import GQADims
from predictionio_tpu.ops.mamba1 import Mamba1Dims
from predictionio_tpu.ops.mla import MLADims
from predictionio_tpu.ops.moe import MoEDims
from predictionio_tpu.ops.ssm import SSMDims


@dataclasses.dataclass(frozen=True)
class SessionRecConfig:
    dim: int = 64
    heads: int = 2
    layers: int = 2
    ffn_mult: int = 4
    max_len: int = 64              # fixed sequence length (pad id = 0)
    dropout: float = 0.1
    learning_rate: float = 1e-3
    weight_decay: float = 1e-6
    epochs: int = 5
    batch_size: int = 256
    seed: int = 13
    attn_block: int = 0            # >0: blockwise attention block size
    seq_axis: Optional[str] = None  # mesh axis for ring attention (SP)
    checkpoint_dir: Optional[str] = None  # mid-training checkpoint/resume
    checkpoint_every: int = 1             # epochs between checkpoints

    def stack(self) -> "StackSpec":
        """This model as a configuration of the block stack."""
        return StackSpec(
            dim=self.dim, ffn_dim=self.dim * self.ffn_mult,
            blocks=(BlockSpec(),) * self.layers, heads=self.heads,
            positions="learned", max_len=self.max_len,
            embed_scale=self.dim ** 0.5)


# ---------------------------------------------------------------------------
# The block stack, built from a configuration
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BlockSpec:
    """One block of the stack, by the kinds of its parts."""

    #: "mha" (q/k/v heads) | "mla" (ops/mla.py) | "gqa" (ops/gqa.py) |
    #: "gqa_window" (ops/gqa.py under the stack's second dims, a sliding
    #: window's) | "mamba2" (ops/ssm.py) | "mamba1" (ops/mamba1.py) | "gmu"
    #: (ops/mamba1.gmu: it gates the stack's ``memory_block``'s scan output)
    #: | "gqa_cross" (ops/gqa.cross_rows over the span of the stack's LAST
    #: "gqa" block); the blocks of one stack may differ in it, and the last
    #: two keep nothing per session
    mixer: str = "mha"
    #: "gelu_mlp" | "swiglu" | "moe" (the expert layer, ops/moe.py, as the
    #: block's whole FFN: "pre_ln" only)
    ffn: str = "gelu_mlp"
    norm: str = "layernorm"     # "layernorm" | "rmsnorm"
    #: "pre_ln": x + mix(norm x), then + ffn(norm .);
    #: "scmoe": the shortcut-connected double-layer — two mixers, two dense
    #: FFNs and ONE expert layer computed from the first half's input and
    #: added at the double-layer's end (so nothing after it in the
    #: double-layer waits for it)
    topology: str = "pre_ln"


@dataclasses.dataclass(frozen=True)
class Generation:
    """Block-diffusion generation (``StackPrograms``' block program, ``models/
    sessionrec.SeqStackModel``): a block of ``block_len`` positions starts as
    mask rows; each denoise forward takes, at every position still masked,
    the best item and its probability, and unmasks by ``rule``; the forward
    over the finished block commits its keys and values.

    ``low_confidence_static``: the ``n_s`` most confident masked positions
    of the block's ``s``-th denoise forward, ``n_s`` from ``block_len /
    denoising_steps`` (the remainder goes to the first forwards), all of
    them where fewer are left. ``low_confidence_dynamic``: every masked
    position whose confidence exceeds ``threshold``, and at least ``n_s``."""

    mask_row: int                        # the item row that stands for a mask
    block_len: int = 4
    denoising_steps: int = 4
    rule: str = "low_confidence_dynamic"
    threshold: float = 0.9

    def __post_init__(self):
        if self.rule not in ("low_confidence_static",
                             "low_confidence_dynamic"):
            raise ValueError(f"unknown unmasking rule {self.rule!r}")

    def unmask_at_least(self, s: int) -> int:
        """``n_s`` of a block's ``s``-th denoise forward (from 0)."""
        base, more = divmod(self.block_len, self.denoising_steps)
        s = min(s, self.denoising_steps - 1)
        return base + (1 if s < more else 0)

    @property
    def over(self) -> float:
        """The confidence above which a position is unmasked whatever
        its rank: the static rule knows none."""
        return (self.threshold if self.rule == "low_confidence_dynamic"
                else float("inf"))


def unmask_by_rule(gen: Generation, masked, confidence, n_unmask):
    """The positions of ``masked`` [B, block_len] one denoise forward
    unmasks: the ``n_unmask`` [B] most confident of each block (ties: the
    earlier position; all of them where fewer are left), and whatever lies
    over the rule's threshold."""
    c = jnp.where(masked, confidence, -jnp.inf)
    rank = jnp.argsort(jnp.argsort(-c, axis=1, stable=True), axis=1)
    return masked & ((rank < n_unmask[:, None]) | (c > gen.over))


@dataclasses.dataclass(frozen=True)
class StackSpec:
    """A stack of blocks over item embeddings. The toy next-item model is
    one such configuration (:meth:`SessionRecConfig.stack`), a latent-
    attention expert model another, a block-diffusion expert model with
    grouped-query attention a third, a stack of state-space mixers with an
    attention layer among every few a fourth, window and full grouped-query
    attention layers mixed (two ``GQADims``: heads, RoPE base and mask
    differ) a fifth; all run through :func:`apply_block`."""

    dim: int
    ffn_dim: int
    blocks: Tuple[BlockSpec, ...]
    heads: int = 0                       # "mha" mixers
    positions: str = "learned"           # "learned" (added) | "rope" (mixer's)
    max_len: int = 0                     # learned positions
    embed_scale: float = 1.0
    #: what a mixer's and an FFN's output is multiplied by before it joins
    #: the residual stream, and the final hidden state before the head
    residual_scale: float = 1.0
    logits_scale: float = 1.0
    eps: float = 1e-6
    tied_head: bool = True               # scores against the item embedding
    mla: Optional[MLADims] = None
    gqa: Optional[GQADims] = None
    #: the dims of the "gqa_window" blocks (``window`` > 0), beside ``gqa``'s
    gqa_window: Optional[GQADims] = None
    ssm: Optional[SSMDims] = None
    moe: Optional[MoEDims] = None
    #: how the stack generates, where it does (a "gqa" stack under the
    #: block-causal mask); None: a query is answered once, from the head
    generation: Optional[Generation] = None
    mamba1: Optional[Mamba1Dims] = None
    #: the dims of the "gqa_cross" blocks (``cross``), which read the span of
    #: the stack's last "gqa" block
    gqa_cross: Optional[GQADims] = None
    #: the "mamba1" block whose scan output (before its gate) the "gmu"
    #: blocks gate: the stack's memory
    memory_block: Optional[int] = None

    @property
    def cross_from(self) -> Optional[int]:
        """The first block of the CROSS-DECODER: the blocks behind the last
        one that keeps something per session, all "gmu" or "gqa_cross" (row
        ``t`` of them reads row ``t`` of the block before, row ``t`` of the
        memory and another block's keys and values up to ``t``, so they run
        for the rows that are answered and no other). None: the stack has no
        such blocks."""
        stateless = [b.mixer in ("gmu", "gqa_cross") for b in self.blocks]
        if not any(stateless):
            return None
        first = len(stateless) - stateless[::-1].index(False)
        if any(stateless[:first]):
            raise ValueError("'gmu' and 'gqa_cross' blocks come last")
        if "gqa" not in [b.mixer for b in self.blocks[:first]] \
                or self.memory_block is None \
                or self.blocks[self.memory_block].mixer != "mamba1":
            raise ValueError("a cross-decoder needs a 'gqa' block's span "
                             "and a 'mamba1' memory_block before it")
        return first


def _layernorm(p, x, eps):
    x = x.astype(jnp.float32)
    mean = x.mean(axis=-1, keepdims=True)
    var = jnp.square(x - mean).mean(axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _gelu_mlp(p, x):
    h = jax.nn.gelu(x @ p["w1"] + p["b1"], approximate=True)
    return h @ p["w2"] + p["b2"]


NORMS = {"layernorm": _layernorm,
         "rmsnorm": lambda p, x, eps: mla_ops.rms_norm(x, p, eps)}
FFNS = {"gelu_mlp": _gelu_mlp,
        "swiglu": lambda p, x: moe_ops.swiglu(x, p["w_g"], p["w_u"],
                                              p["w_d"])}


def apply_block(spec: StackSpec, block: BlockSpec, p, x, mix, *,
                moe=None, drop=lambda h: h, scope: str = "seq.layer0"):
    """One block. ``mix(which, params, h)`` is the caller's mixer ("a" and,
    in a double-layer, "b"): it carries what differs between training,
    chunked prefill and extension (positions, the cache). ``moe(params, h)``
    likewise, where the topology has an expert layer."""
    norm = functools.partial(NORMS[block.norm], eps=spec.eps)
    r = spec.residual_scale

    def scaled(out):
        return out if r == 1.0 else r * out

    def scoped(name, fn, *args):
        with jax.named_scope(f"{scope}.{name}"):
            return scaled(fn(*args))

    mixer = block.mixer
    h1 = x + drop(scoped(f"{mixer}_a", mix, "a", p["mixer_a"],
                         norm(p["norm_a"], x)))
    u = norm(p["norm_ffn_a"], h1)
    if block.topology == "pre_ln" and block.ffn == "moe":
        return h1 + scaled(moe(p["moe"], u))
    ffn = FFNS[block.ffn]
    if block.topology == "pre_ln":
        return h1 + drop(scoped("ffn_a", ffn, p["ffn_a"], u))
    if r != 1.0:
        raise ValueError("a residual scale is a 'pre_ln' block's")
    if block.topology != "scmoe":
        raise ValueError(f"unknown block topology {block.topology!r}")
    m = moe(p["moe"], u)
    h2 = h1 + scoped("ffn_a", ffn, p["ffn_a"], u)
    h3 = h2 + scoped(f"{mixer}_b", mix, "b", p["mixer_b"],
                     norm(p["norm_b"], h2))
    return h3 + scoped("ffn_b", ffn, p["ffn_b"],
                       norm(p["norm_ffn_b"], h3)) + m


def _init_norm(kind: str, width: int, dtype):
    if kind == "layernorm":
        return {"scale": jnp.ones((width,), dtype),
                "bias": jnp.zeros((width,), dtype)}
    return jnp.ones((width,), dtype)


def _init_mixer(spec: StackSpec, block: BlockSpec, key, dtype):
    if block.mixer == "mla":
        return mla_ops.init(key, spec.mla, dtype)
    if block.mixer == "gqa":
        return gqa_ops.init(key, spec.gqa, dtype)
    if block.mixer == "gqa_window":
        return gqa_ops.init(key, spec.gqa_window, dtype)
    if block.mixer == "mamba2":
        return ssm_ops.init(key, spec.ssm, dtype)
    if block.mixer == "mamba1":
        return mamba1_ops.init(key, spec.mamba1, dtype)
    if block.mixer == "gmu":
        return mamba1_ops.init_gmu(key, spec.dim, spec.mamba1.d_inner, dtype)
    if block.mixer == "gqa_cross":
        return gqa_ops.init(key, spec.gqa_cross, dtype)
    k1, k2 = jax.random.split(key)
    head_dim = spec.dim // spec.heads
    lecun = jax.nn.initializers.lecun_normal
    return {
        "wqkv": lecun(in_axis=0, out_axis=(1, 2, 3))(
            k1, (spec.dim, 3, spec.heads, head_dim), dtype),
        "bqkv": jnp.zeros((3, spec.heads, head_dim), dtype),
        "wo": lecun(in_axis=(0, 1), out_axis=2)(
            k2, (spec.heads, head_dim, spec.dim), dtype),
        "bo": jnp.zeros((spec.dim,), dtype),
    }


def _init_ffn(spec: StackSpec, block: BlockSpec, key, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    lecun = jax.nn.initializers.lecun_normal()
    if block.ffn == "gelu_mlp":
        return {"w1": lecun(k1, (spec.dim, spec.ffn_dim), dtype),
                "b1": jnp.zeros((spec.ffn_dim,), dtype),
                "w2": lecun(k2, (spec.ffn_dim, spec.dim), dtype),
                "b2": jnp.zeros((spec.dim,), dtype)}
    return {"w_g": lecun(k1, (spec.dim, spec.ffn_dim), dtype),
            "w_u": lecun(k2, (spec.dim, spec.ffn_dim), dtype),
            "w_d": lecun(k3, (spec.ffn_dim, spec.dim), dtype)}


def init_stack(spec: StackSpec, key, n_rows: int, dtype=jnp.float32) -> Dict:
    """Parameters of a stack over ``n_rows`` embedding rows."""
    keys = iter(jax.random.split(key, 3 + 5 * len(spec.blocks)))
    embed = jax.nn.initializers.variance_scaling(
        1.0, "fan_in", "normal", out_axis=0)
    params: Dict = {"item_embed": {
        "embedding": embed(next(keys), (n_rows, spec.dim), dtype)}}
    if spec.positions == "learned":
        params["pos_embed"] = 0.02 * jax.random.normal(
            next(keys), (spec.max_len, spec.dim), dtype)
    if not spec.tied_head:
        params["head"] = embed(next(keys), (n_rows, spec.dim), dtype)
    blocks = []
    for block in spec.blocks:
        p = {"norm_a": _init_norm(block.norm, spec.dim, dtype),
             "mixer_a": _init_mixer(spec, block, next(keys), dtype),
             "norm_ffn_a": _init_norm(block.norm, spec.dim, dtype)}
        if block.ffn == "moe":
            p["moe"] = moe_ops.init(next(keys), spec.moe, dtype)
        else:
            p["ffn_a"] = _init_ffn(spec, block, next(keys), dtype)
        if block.topology == "scmoe":
            p.update(
                norm_b=_init_norm(block.norm, spec.dim, dtype),
                mixer_b=_init_mixer(spec, block, next(keys), dtype),
                norm_ffn_b=_init_norm(block.norm, spec.dim, dtype),
                ffn_b=_init_ffn(spec, block, next(keys), dtype),
                moe=moe_ops.init(next(keys), spec.moe, dtype))
        blocks.append(p)
    params["blocks"] = blocks
    params["final_norm"] = _init_norm(spec.blocks[-1].norm, spec.dim, dtype)
    return params


def stack_tree_from_flax(tree):
    """A parameter tree of the flax module that the toy configuration was
    before it ran through the block stack (``block_<i>/LayerNorm_0,
    DenseGeneral_0, DenseGeneral_1, LayerNorm_1, Dense_0, Dense_1``), in the
    stack's layout (``blocks[i]/norm_a, mixer_a, norm_ffn_a, ffn_a``): the
    same arrays under their new names. Stored models and mid-training
    checkpoints from before carry the old tree; any other tree is returned
    as it is."""
    if not isinstance(tree, dict) or "block_0" not in tree:
        return tree

    def block(b):
        qkv, out = b["DenseGeneral_0"], b["DenseGeneral_1"]
        up, down = b["Dense_0"], b["Dense_1"]
        return {"norm_a": b["LayerNorm_0"],
                "mixer_a": {"wqkv": qkv["kernel"], "bqkv": qkv["bias"],
                            "wo": out["kernel"], "bo": out["bias"]},
                "norm_ffn_a": b["LayerNorm_1"],
                "ffn_a": {"w1": up["kernel"], "b1": up["bias"],
                          "w2": down["kernel"], "b2": down["bias"]}}

    new = {k: v for k, v in tree.items() if not k.startswith("block_")}
    new["blocks"] = [block(tree[f"block_{i}"])
                     for i in range(len(tree) - len(new))]
    return new


def stack_trees_from_flax(state):
    """:func:`stack_tree_from_flax` over every such tree inside ``state``:
    ``{"params": tree}``, or an optimiser state whose moments mirror it."""
    return jax.tree_util.tree_map(
        stack_tree_from_flax, state,
        is_leaf=lambda x: isinstance(x, dict) and "block_0" in x)


class SessionEncoder:
    """Item+position embedding -> causal blocks -> hidden states: the toy
    configuration of the block stack, with the ``init`` / ``apply`` surface
    of the module it once was (``params`` is ``{"params": tree}``).

    Vocabulary is n_items + 1: index 0 is the padding token; real items
    are 1-shifted by the caller.
    """

    def __init__(self, n_items: int, cfg: SessionRecConfig,
                 mesh: Optional[Mesh] = None):
        self.n_items, self.cfg, self.mesh = n_items, cfg, mesh
        self.spec = cfg.stack()

    def init(self, key, seq=None, *, deterministic: bool = True) -> Dict:
        return {"params": init_stack(self.spec, key, self.n_items + 1)}

    def _mix(self, which, p, h):
        cfg = self.cfg
        qkv = jnp.einsum("bld,dthe->blthe", h, p["wqkv"]) + p["bqkv"]
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]   # [B, L, H, Dh]
        if cfg.seq_axis is not None and self.mesh is not None:
            attn = ring_attention_sharded(
                q, k, v, self.mesh, axis=cfg.seq_axis, causal=True
            )
        elif cfg.attn_block:
            attn = blockwise_attention(q, k, v, block_size=cfg.attn_block)
        else:
            attn = mha_reference(q, k, v, causal=True)
        return jnp.einsum("blhe,hed->bld", attn, p["wo"]) + p["bo"]

    def apply(self, params, seq: jax.Array, *, deterministic: bool = True,
              rngs: Optional[Dict] = None) -> jax.Array:
        cfg, spec, p = self.cfg, self.spec, params["params"]
        rate = 0.0 if deterministic else cfg.dropout
        sites = iter(range(1 << 30))

        def drop(h):
            if rate == 0.0:
                return h
            key = jax.random.fold_in(rngs["dropout"], next(sites))
            keep = jax.random.bernoulli(key, 1.0 - rate, h.shape)
            return jnp.where(keep, h / (1.0 - rate), 0.0)

        x = p["item_embed"]["embedding"][seq] * spec.embed_scale
        x = drop(x + p["pos_embed"][None, : seq.shape[1]])
        for i, block in enumerate(spec.blocks):
            x = apply_block(spec, block, p["blocks"][i], x, self._mix,
                            drop=drop, scope=f"seq.layer{i}")
        x = NORMS[spec.blocks[-1].norm](p["final_norm"], x, spec.eps)
        # padding positions carry no signal downstream
        return x * (seq > 0)[..., None]


def build_sequences(
    user_idx: np.ndarray,
    item_idx: np.ndarray,
    times: np.ndarray,
    n_users: int,
    max_len: int,
) -> np.ndarray:
    """Per-user chronological histories -> [n_users, max_len + 1] int32
    of 1-shifted item ids, LEFT-aligned (trailing 0-pad); the +1 column
    keeps the final target of each history. Left alignment means every
    training prefix doubles as a short session starting at position 0 —
    so serve-time sessions shorter than max_len are in-distribution.
    Fully vectorized host pass: O(n log n) sort + O(n) scatter — no
    per-user Python loop (the ops.ragged discipline applied to
    sequence building)."""
    order = np.lexsort((times, user_idx))
    u, it = user_idx[order], item_idx[order] + 1
    out = np.zeros((n_users, max_len + 1), np.int32)
    if len(u) == 0:
        return out
    starts = np.searchsorted(u, np.arange(n_users))
    ends = np.searchsorted(u, np.arange(n_users), side="right")
    lengths = ends - starts
    # each event's position within its user's history; keep only the
    # last max_len+1 per user, left-aligned after the drop
    pos = np.arange(len(u)) - starts[u]
    drop = np.maximum(lengths - (max_len + 1), 0)[u]
    kept = pos >= drop
    out[u[kept], pos[kept] - drop[kept]] = it[kept]
    return out


@dataclasses.dataclass
class SessionRecModelState:
    """Serializable training product: params pytree (numpy leaves) +
    per-user padded histories for serve-time encoding."""

    params: Dict
    sequences: np.ndarray          # [n_users, max_len] inputs (1-shifted)
    n_items: int
    cfg: SessionRecConfig
    losses: List[float]

    def __setstate__(self, d):
        self.__dict__.update(d)
        self.params = stack_trees_from_flax(self.params)


class SessionRecTrainer:
    """Mirrors ALSTrainer/TwoTowerTrainer: one-time costs (sequence
    build, param init, compile) up front, `run()` drives jitted steps."""

    def __init__(
        self,
        events: Tuple[np.ndarray, np.ndarray, np.ndarray],
        n_users: int,
        n_items: int,
        cfg: SessionRecConfig,
        mesh: Optional[Mesh] = None,
    ):
        u_idx, i_idx, times = events
        self.cfg, self.mesh, self.n_items = cfg, mesh, n_items
        seqs = build_sequences(
            np.asarray(u_idx, np.int64), np.asarray(i_idx, np.int64),
            np.asarray(times), n_users, cfg.max_len,
        )
        self.inputs = seqs[:, :-1]                     # [U, max_len]
        self.targets = seqs[:, 1:]                     # next-item labels
        keep = (self.targets > 0).any(axis=1)
        self._train_rows = np.flatnonzero(keep)

        self.encoder = SessionEncoder(n_items, cfg, mesh=mesh)
        probe = jnp.zeros((1, cfg.max_len), jnp.int32)
        self._params = self.encoder.init(
            jax.random.PRNGKey(cfg.seed), probe, deterministic=True
        )
        self._tx = optax.adamw(cfg.learning_rate, weight_decay=cfg.weight_decay)
        self._opt_state = self._tx.init(self._params)

        n_data = mesh.shape.get("data", 1) if mesh is not None else 1
        self.batch = max(cfg.batch_size - cfg.batch_size % max(n_data, 1), n_data)
        if mesh is not None:
            rep = NamedSharding(mesh, P())
            self._params = jax.device_put(self._params, rep)
            self._opt_state = jax.device_put(self._opt_state, rep)
            data_ax = "data" if "data" in mesh.shape else None
            self._batch_sharding = NamedSharding(mesh, P(data_ax))
        else:
            self._batch_sharding = None
        self._step = jax.jit(self._make_step(), donate_argnums=(0, 1))
        self._shuffle = np.random.default_rng(cfg.seed)
        self._rng = jax.random.PRNGKey(cfg.seed + 1)
        self._epochs_done = 0
        self._losses: List[float] = []

        # mid-training checkpoint/resume (core.checkpoint — beyond the
        # reference's train-to-completion-or-nothing, SURVEY.md §5.4)
        self._ckpt = None
        if cfg.checkpoint_dir:
            from predictionio_tpu.core.checkpoint import (
                TrainCheckpointer,
                train_fingerprint,
            )

            fp = train_fingerprint(
                cfg, n_users, n_items, self.inputs.shape,
                self.inputs[:512], self.inputs[-512:],
            )
            self._ckpt = TrainCheckpointer(cfg.checkpoint_dir,
                                           every=cfg.checkpoint_every,
                                           fingerprint=fp)
            restored = self._ckpt.restore()
            if restored is not None:
                epoch, state = restored
                params, opt_state = stack_trees_from_flax(
                    (state["params"], state["opt_state"]))
                if mesh is not None:
                    rep = NamedSharding(mesh, P())
                    params = jax.device_put(params, rep)
                    opt_state = jax.device_put(opt_state, rep)
                self._params, self._opt_state = params, opt_state
                self._shuffle.bit_generator.state = state["shuffle_state"]
                self._rng = jnp.asarray(state["rng_key"])
                self._epochs_done = epoch
                self._losses = list(state["losses"])

    def _make_step(self):
        apply, tx, n_items = self.encoder.apply, self._tx, self.n_items

        def loss_fn(params, seq, tgt, rng):
            h = apply(
                params, seq, deterministic=False, rngs={"dropout": rng}
            )                                           # [B, L, D]
            emb = params["params"]["item_embed"]["embedding"]   # tied softmax
            logits = jnp.einsum("bld,vd->blv", h, emb)          # [B, L, V]
            mask = (tgt > 0).astype(jnp.float32)
            ll = optax.softmax_cross_entropy_with_integer_labels(logits, tgt)
            return jnp.sum(ll * mask) / jnp.maximum(mask.sum(), 1e-8)

        def step(params, opt_state, seq, tgt, rng):
            loss, grads = jax.value_and_grad(loss_fn)(params, seq, tgt, rng)
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return params, opt_state, loss

        return step

    def run(self, epochs: Optional[int] = None) -> List[float]:
        """Train up to ``epochs`` TOTAL epochs (resume-aware: epochs
        already completed by a restored checkpoint are not repeated)."""
        target = epochs if epochs is not None else self.cfg.epochs
        rng = self._rng
        from predictionio_tpu.obs import jaxmon

        while self._epochs_done < target:
            order = self._shuffle.permutation(self._train_rows)
            total, batches = 0.0, 0
            for s in range(0, len(order), self.batch):
                t_step = time.perf_counter()
                sel = order[s:s + self.batch]
                if len(sel) < self.batch:   # fixed shape: wrap the tail
                    sel = np.concatenate(
                        [sel, order[: self.batch - len(sel)]]
                    ) if len(order) >= self.batch else np.resize(sel, self.batch)
                seq = jnp.asarray(self.inputs[sel])
                tgt = jnp.asarray(self.targets[sel])
                jaxmon.record_transfer(seq.nbytes + tgt.nbytes, "h2d")
                if self._batch_sharding is not None:
                    seq = jax.device_put(seq, self._batch_sharding)
                    tgt = jax.device_put(tgt, self._batch_sharding)
                rng, sub = jax.random.split(rng)
                self._params, self._opt_state, loss = self._step(
                    self._params, self._opt_state, seq, tgt, sub
                )
                total += float(loss)
                batches += 1
                # float(loss) above synced the device, so this is the
                # true step wall time (h2d + dispatch + compute)
                jaxmon.observe_train_step(time.perf_counter() - t_step)
            self._losses.append(total / max(batches, 1))
            self._epochs_done += 1
            self._rng = rng
            if self._ckpt is not None:
                self._ckpt.maybe_save(self._epochs_done, {
                    "params": self._params,
                    "opt_state": self._opt_state,
                    "shuffle_state": self._shuffle.bit_generator.state,
                    "rng_key": self._rng,
                    "losses": list(self._losses),
                })
        return list(self._losses)

    def state(self, losses: Optional[List[float]] = None) -> SessionRecModelState:
        # serve-time input: the last max_len REAL items (drop the held
        # -out target column, then re-truncate)
        full = np.concatenate(
            [self.inputs, self.targets[:, -1:]], axis=1
        )                                          # [U, max_len+1] left-aligned
        L = self.cfg.max_len
        counts = (full > 0).sum(axis=1)
        drop = np.maximum(counts - L, 0)           # at most 1 (full has L+1 cols)
        # vectorized shift-left-by-drop + truncate to L columns
        gather = np.minimum(drop[:, None] + np.arange(L)[None, :], full.shape[1] - 1)
        serve = np.take_along_axis(full, gather, axis=1)
        serve[np.arange(L)[None, :] >= counts[:, None] - drop[:, None]] = 0
        params_np = jax.tree_util.tree_map(np.asarray, self._params)
        return SessionRecModelState(
            params=params_np, sequences=serve, n_items=self.n_items,
            cfg=self.cfg, losses=losses or [],
        )


class SessionScorer:
    """Serve path: encode a batch of histories, score the catalog from
    the last hidden state, fixed-shape top-k with seen-item exclusion.
    One compiled fn reused across requests (fixed [1, max_len] shape) —
    the framework's <10 ms serving discipline applied to the deep model."""

    def __init__(self, state: SessionRecModelState, mesh: Optional[Mesh] = None):
        self.state = state
        attn_block = state.cfg.attn_block
        if state.cfg.seq_axis is not None and not attn_block:
            # the model was trained with ring attention precisely because
            # max_len's O(L^2) score matrix is too big for one device;
            # serving single-device must not materialize it — fall back
            # to blockwise attention with the largest power-of-two block
            # <= 512 that divides max_len
            attn_block = 512
            while state.cfg.max_len % attn_block:
                attn_block //= 2
        cfg = dataclasses.replace(
            state.cfg, dropout=0.0, seq_axis=None, attn_block=attn_block
        )
        self._cfg = cfg
        encoder = SessionEncoder(state.n_items, cfg, mesh=None)
        params = jax.tree_util.tree_map(jnp.asarray, state.params)

        def score(seq, exclude_seen):                    # [B, max_len]
            h = encoder.apply(params, seq, deterministic=True)
            # last non-pad position per row
            idx = jnp.maximum(
                (seq > 0).astype(jnp.int32).sum(axis=1) - 1, 0
            )
            last = jnp.take_along_axis(h, idx[:, None, None], axis=1)[:, 0]
            emb = params["params"]["item_embed"]["embedding"]
            logits = last @ emb.T                        # [B, V]
            logits = logits.at[:, 0].set(-jnp.inf)       # never the pad token
            if exclude_seen:                             # repeat items are a
                B = seq.shape[0]                         # legitimate next-item
                logits = logits.at[                      # answer, so opt-in
                    jnp.arange(B)[:, None], seq
                ].set(-jnp.inf)
            return logits

        self._score = jax.jit(score, static_argnums=1)

    def top_k(
        self, seq_rows: np.ndarray, k: int, *, exclude_seen: bool = False
    ) -> Tuple[np.ndarray, np.ndarray]:
        """(scores, 0-based item indices) of the k best next items; k is
        clamped to the catalog size (num > catalog returns the full
        ranking, not an error — TopKScorer's contract). The batch is
        bucketed to powers of two so micro-batched serving's arbitrary
        batch sizes reuse a handful of compiled programs."""
        seq_rows = np.atleast_2d(np.asarray(seq_rows, np.int32))
        B = seq_rows.shape[0]
        b_bucket = 1
        while b_bucket < B:
            b_bucket *= 2
        if B < b_bucket:   # pad rows are all-padding sequences
            seq_rows = np.concatenate(
                [seq_rows, np.zeros((b_bucket - B, seq_rows.shape[1]), np.int32)]
            )
        logits = self._score(jnp.asarray(seq_rows), exclude_seen)
        # clamp to the true catalog size: column 0 is the pad token and
        # is always -inf, so it must never count toward (or appear in) k
        scores, idx = jax.lax.top_k(logits, min(k, logits.shape[1] - 1))
        return np.asarray(scores)[:B], np.asarray(idx)[:B] - 1  # unshift pad


# ---------------------------------------------------------------------------
# Serving a stack in steps, over a per-session cache
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ServeShape:
    """The fixed shapes of the serve programs (everything a window can ask
    for runs in these, compiled once)."""

    n_slots: int = 32            # sessions whose cached positions are kept
    capacity: int = 8192         # positions a slot holds
    #: positions of one prefill chunk, and the cached positions attention
    #: takes per round of its block loop
    chunk: int = 512
    extend_len: int = 8          # new positions of one extension, at most
    extend_batch: int = 8        # extensions of one step, at most
    gen_batch: int = 8           # blocks of one block forward, at most


def _cut(args, shapes):
    """Flat ``args`` as the arrays of ``shapes``, one after the other: views
    of a numpy array (the host writes a call's arguments through them),
    static slices of a traced one (the program reads them)."""
    parts, at = [], 0
    for shape in shapes:
        n = math.prod(shape)
        parts.append(args[at:at + n].reshape(shape))
        at += n
    return parts


class StackPrograms:
    """The compiled serve path of a stack whose mixers each keep something
    per session: a cache PER MIXER, of that mixer's kind, in one list.

    * latent attention (``"mla"``): ``[n_slots + 1, capacity + chunk,
      latent]``, a position's latent; with a learned index
      (``MLADims.index_heads``) ``{latent: that, index_k [n_slots + 1,
      capacity + chunk, index_dim]}`` (``ops/mla.init_cache`` says which);
    * grouped-query attention (``"gqa"``): ``[n_slots + 1, capacity + chunk,
      kv_heads * (head_dim + v_dim)]``, a position's keys, then its values:
      a SPAN that grows with the session;
    * grouped-query attention under a sliding window (``"gqa_window"``,
      ``StackSpec.gqa_window``): ``[n_slots + 1, ring, kv_heads * (head_dim +
      v_dim)]``, a RING of ``ops/gqa.ring_len(window, chunk)`` rows (the
      window and one chunk, in whole blocks of ``window`` positions):
      position ``t`` lies at row ``t mod ring``, only real positions are
      written, and a query walks the blocks that hold its window and no
      other, whatever its reach. A slot's rings hold the last ``ring``
      positions written to it, which is what its owner may resume from
      (``models/sessionrec.LatentCache``: the ring's rule);
    * a state-space mixer (``"mamba2"``): ``{conv [n_slots + 1, d_conv - 1,
      conv_dim], ssm [n_slots + 1, heads, head_dim, d_state]}``, the
      session's recurrent state at ONE position (the caller may then
      resume a slot only from the end of what it holds); ``"mamba1"``: ``{conv
      [n_slots + 1, d_conv - 1, d_inner], ssm [n_slots + 1, d_state,
      d_inner]}``, alike;
    * a gated memory unit (``"gmu"``) and a cross-attention mixer
      (``"gqa_cross"``): NOTHING (``None`` in the list). They read what other
      blocks made: the memory block's scan output at the same position, and
      the span of the stack's last ``"gqa"`` block. A stack that ends in such
      blocks (``StackSpec.cross_from``: a CROSS-DECODER) runs them for the
      rows that are answered and no other: ``prefill`` then runs the blocks
      before them over its chunk and returns nothing, ``prefill_last`` (the
      chunk that ends a history) also carries the chunk's last real row
      through them, and ``extend`` carries each session's last real row.

    The blocks of a stack may differ in their mixer. Every stack has
    ``prefill`` (one chunk of one session against its slot); beside it a
    stack that answers once has ``extend`` (a few new positions of several
    sessions: absorbed latent attention, causal grouped-query attention, the
    recurrence from each slot's state), and a stack that generates
    (``StackSpec.generation``: grouped-query attention under the block-causal
    mask in every block) has ``block`` (one block of each of several
    sessions, denoised or committed, with the head and the unmasking rule
    inside).

    The extra slot is scratch for the padding rows of a batch; the extra
    chunk of positions lets the last chunk of a full slot be written whole.

    The programs take the parameters as arguments (nothing is baked in),
    donate the cache, and return ``(cache, result, counters, totals)``:
    ``prefill`` and ``extend`` the final-normed hidden state of each
    session's last real position, ``block`` what the rule decided; and what
    the expert layers counted. Which slot holds which session is the
    caller's business (``models/sessionrec.LatentCache``).

    A call crosses to the device ONCE: what the host knows of it (ids,
    lengths, slots, positions) goes in as one int32 array, cut up again by
    static slices inside the program (:func:`_cut`, the same on both
    sides). And nothing of a call need come back but its result: what the
    expert layers counted is also summed ON THE DEVICE, into ``totals``
    (``[TOTAL_KINDS, TOTAL_FIELDS]`` int32: an argument in, the sum out,
    never donated, so whoever keeps an older one keeps a live buffer
    whatever runs meanwhile). The caller fetches ``totals`` when somebody
    asks, and takes them (:meth:`take_totals`) before ``drain_every`` runs
    could wrap an int32."""

    #: the rows of ``totals``: a program's kind
    TOTAL_KINDS = ("extend", "prefill", "block")
    #: its columns, per kind: program runs, real tokens, (token, pick) pairs
    #: that reached a held expert, held experts that got any token (per
    #: layer, summed), zero-compute picks, runs whose expert layers took the
    #: small forward's form (all of a kind's or none: the program's shape
    #: decides, ``ops/moe.small_forward``) and, for the others, the products
    #: of sorted rows their grouped kernels ran (over ``experts_touched``:
    #: how many products shared one read of an expert), the fullest held
    #: expert's tokens (per layer, summed), (token, expert layer) pairs that
    #: kept a group with experts held here; under a learned index the BLOCKS
    #: of index keys scanned (per layer, summed: a chunk's for its rows, an
    #: extension batch's once a real session) and the real query rows whose
    #: reach exceeded ``index_topk`` (once a run), both in units an int32
    #: holds for long (the positions in reach of one chunk at 32k over five
    #: layers are 84 M and would wrap in 25 runs); in a stack with window
    #: layers the blocks of ``window`` cached positions those layers WALKED
    #: (per layer, summed: a chunk's rounds, an extension batch's once a real
    #: session), the blocks a walk from 0 to the same reach would have taken,
    #: and the blocks of ``chunk`` positions its full layers walked (a
    #: chunk's as far as it reaches, an extension batch's real rows each as
    #: far as its own reach). A stack without expert layers, whose router picks no groups,
    #: without an index or without window layers leaves those columns at 0:
    #: the keys of a call's ``counters`` decide, when the program is traced
    TOTAL_FIELDS = ("runs", "tokens", "held_picks", "experts_touched",
                    "zero_picks", "dense_expert_runs", "expert_row_tiles",
                    "load_max_sum", "group_hit_tokens", "index_blocks",
                    "index_sparse_rows", "window_blocks",
                    "window_blocks_from0", "full_blocks",
                    # a stack with a cross-decoder: the rows carried through
                    # it, and the blocks of ``chunk`` positions of the ONE
                    # span its readers (the block that writes it and every
                    # cross mixer) walked for an extension batch's rows,
                    # beside what each row's own reach takes (the same,
                    # since the rows walk in ``span_walk``)
                    "cross_rows", "span_blocks_walked", "span_blocks_own")

    def __init__(self, spec: StackSpec, params: Dict, shape: ServeShape):
        #: the mixers' kinds, in the order of their caches
        self.kinds = [b.mixer for b in spec.blocks
                      for _ in range(2 if b.topology == "scmoe" else 1)]
        #: the kinds that keep nothing per session: they read other blocks'
        stateless = {"gmu", "gqa_cross"}
        if not set(self.kinds) <= {"mla", "gqa", "gqa_window", "mamba2",
                                   "mamba1"} | stateless:
            raise ValueError(
                "stepwise serving needs mixers that keep a per-session "
                "cache ('mla', 'gqa', 'gqa_window', 'mamba2' or 'mamba1') "
                "or read another block's ('gmu', 'gqa_cross'): got "
                f"{sorted(set(self.kinds))}")
        #: the first block of the cross-decoder (None: the stack has none),
        #: and the mixer whose span its cross mixers read
        self.cross_from = spec.cross_from
        if self.cross_from is not None:
            self._span = max(i for i in range(self.cross_from)
                             if self.kinds[i] == "gqa")
        from predictionio_tpu.obs import jaxmon

        self.spec, self.shape, self.params = spec, shape, params
        gen = spec.generation
        if "gqa" in self.kinds and spec.gqa.block_len != (
                gen.block_len if gen else 1):
            raise ValueError(
                "a 'gqa' stack under a block-causal mask is served by block "
                "diffusion: its StackSpec.generation must be set, with the "
                "mask's block length")
        if gen is not None and set(self.kinds) != {"gqa"}:
            raise ValueError("a stack that generates has 'gqa' mixers only")
        #: whether some mixers attend a sliding window, over rings
        self.windowed = "gqa_window" in self.kinds
        if self.windowed and not spec.gqa_window.window:
            raise ValueError("a 'gqa_window' block's dims give its window")
        dtype = params["item_embed"]["embedding"].dtype
        positions = (shape.n_slots + 1, shape.capacity + shape.chunk)
        #: whether the latent mixers select their positions by a learned index
        self.indexed = "mla" in self.kinds and spec.mla.has_index
        #: whether a chunk's attention runs in the ``chunk_attend`` kernel
        #: (``ops/mla.prefill_chunk``'s walk: every latent mixer's, no other)
        self.attend_kernel = "mla" in self.kinds
        #: whether an extension's rows walk a span of keys and values in the
        #: ``span_walk`` kernel, each as far as its own reach
        #: (``ops/gqa.extend`` and ``cross_rows``: a causal stack's)
        self.walk_kernel = "gqa" in self.kinds and gen is None

        held = {
            "mla": lambda: mla_ops.init_cache(spec.mla, *positions, dtype),
            "gqa": lambda: jnp.zeros(
                positions + (spec.gqa.cache_width,), dtype),
            "gqa_window": lambda: jnp.zeros(
                (positions[0],
                 gqa_ops.ring_len(spec.gqa_window.window, shape.chunk),
                 spec.gqa_window.cache_width), dtype),
            "mamba2": lambda: ssm_ops.init_state(
                spec.ssm, shape.n_slots + 1, dtype),
            "mamba1": lambda: mamba1_ops.init_state(
                spec.mamba1, shape.n_slots + 1, dtype)}
        self.cache = [None if kind in stateless else held[kind]()
                      for kind in self.kinds]
        #: tokens of a call of each program that this stack compiles: the
        #: shape by which ``ops/moe.moe`` chooses its form
        self.tokens = {"prefill": shape.chunk}
        #: the shapes of what the host knows of a call, in the order the
        #: program's function takes them: the layout of its one array
        self._shapes = {"prefill": ((shape.chunk,), (), (), ())}
        self._zeros = self.totals = jnp.zeros(
            (len(self.TOTAL_KINDS), len(self.TOTAL_FIELDS)), jnp.int32)
        # a program's name: (the row of ``totals`` it counts in, its function)
        fns = {"prefill": ("prefill", self._prefill_fn)}
        if self.cross_from is not None:
            self.tokens["prefill_last"] = shape.chunk
            self._shapes["prefill_last"] = self._shapes["prefill"]
            fns["prefill_last"] = ("prefill", self._prefill_last_fn)
        if gen is None:
            B, S = shape.extend_batch, shape.extend_len
            self.tokens["extend"] = B * S
            self._shapes["extend"] = ((B, S), (B,), (B,), (B,), ())
            fns["extend"] = ("extend", self._extend_fn)
        else:
            if shape.chunk % gen.block_len or shape.capacity % gen.block_len:
                raise ValueError("chunk and capacity must be multiples of "
                                 "the block length")
            B, S = shape.gen_batch, gen.block_len
            self.tokens["block"] = B * S
            self._shapes["block"] = ((B, S), (B,), (B,), (B,), (B,), ())
            fns["block"] = ("block", self._block_fn)
        self._compiled = {
            kind: jax.jit(self._packed(kind, fn, counted),
                          donate_argnums=1).lower(
                params, self.cache, self.totals,
                self._args(kind)[0]).compile()
            for kind, (counted, fn) in fns.items()}
        for program in self._compiled.values():
            jaxmon.record_scope_map(program)
        # in one run no column grows by more than every token's every pick
        # and every held expert, in every expert layer (fewer than the
        # mixers), or than every block of a slot's index keys for every row
        # of an extension batch, in every mixer: the widest column says how
        # many runs an int32 holds
        moe = spec.moe
        widest = (max(self.tokens.values()) * (moe.top_k if moe else 1)
                  + (moe.held[1] if moe else 0))
        if self.indexed:
            widest = max(widest, -(-positions[1] // shape.chunk)
                         * shape.extend_batch)
        if self.windowed:
            widest = max(widest, -(-positions[1] // spec.gqa_window.window)
                         * shape.extend_batch)
        #: runs after which the caller takes the totals, at the latest
        self.drain_every = (2 ** 31 - 1) // (len(self.kinds) * widest)

    # -- the programs ---------------------------------------------------------
    def _run(self, params, x, valid, mix_with, blocks=None):
        """The blocks over tokens ``x`` [T, dim] (float32 residual stream);
        ``mix_with(i_mixer, params, h, scope)`` gives block code its mixer.
        ``blocks``: the (start, stop) of the blocks to run, where not all (a
        stack with a cross-decoder, whose topology is "pre_ln" throughout: a
        block's mixer has the block's own index)."""
        spec = self.spec
        start, stop = blocks or (0, len(spec.blocks))
        loads, zeros, hits, i_mixer = [], [], [], start
        for i, block in enumerate(spec.blocks[start:stop], start):
            mixers = {"a": i_mixer, "b": i_mixer + 1}

            def mix(which, p, h, _m=mixers, _i=i, _kind=block.mixer):
                return mix_with(_m[which], p, h,
                                f"seq.layer{_i}.{_kind}_{which}")

            def moe(p, h, _i=i):
                y, counted = moe_ops.moe(p, spec.moe, h, valid,
                                         scope=f"seq.layer{_i}.moe")
                loads.append(counted["expert_load"])
                zeros.append(counted["zero_picks"])
                if "group_hits" in counted:
                    hits.append(counted["group_hits"])
                return y

            x = apply_block(spec, block, params["blocks"][i], x, mix,
                            moe=moe, scope=f"seq.layer{i}")
            i_mixer += 2 if block.topology == "scmoe" else 1
        counters = {"tokens": valid.sum().astype(jnp.int32)}
        if loads:
            counters.update(expert_load=jnp.stack(loads),
                            zero_picks=jnp.stack(zeros))
        if hits:
            counters["group_hits"] = jnp.stack(hits)
        return x, counters

    def _final(self, params, h):
        h = NORMS[self.spec.blocks[-1].norm](
            params["final_norm"], h, self.spec.eps)
        scale = self.spec.logits_scale
        return h if scale == 1.0 else scale * h

    def _embed(self, params, ids):
        return (params["item_embed"]["embedding"][ids].astype(jnp.float32)
                * self.spec.embed_scale)

    def _packed(self, kind, fn, counted):
        """Program ``kind`` as it is compiled: ``fn`` with what the host
        knows of a call cut from its one array, and the call's counters
        added to row ``counted`` of ``totals`` (one of ``TOTAL_KINDS``), in
        ``TOTAL_FIELDS``."""
        shapes = self._shapes[kind]
        row_of = self.TOTAL_KINDS.index(counted)

        def program(params, cache, totals, args):
            cache, result, counters = fn(params, cache, *_cut(args, shapes))
            add = {"runs": 1, "tokens": counters["tokens"]}
            if "expert_load" in counters:
                load = counters["expert_load"]          # [layers, held]
                add.update(held_picks=load.sum(),
                           experts_touched=(load > 0).sum(),
                           zero_picks=counters["zero_picks"].sum(),
                           load_max_sum=load.max(axis=1).sum())
                if moe_ops.small_forward(self.tokens[kind]):
                    add["dense_expert_runs"] = 1
                else:
                    add["expert_row_tiles"] = moe_ops.row_tiles(load).sum()
            if "group_hits" in counters:
                add["group_hit_tokens"] = counters["group_hits"].sum()
            if "index_blocks" in counters:
                add.update(index_blocks=counters["index_blocks"],
                           index_sparse_rows=counters["index_sparse_rows"])
            if "window_blocks" in counters:
                add.update((f, counters[f]) for f in (
                    "window_blocks", "window_blocks_from0", "full_blocks"))
            add.update((f, counters[f]) for f in (
                "cross_rows", "span_blocks_walked", "span_blocks_own")
                if f in counters)
            row = jnp.stack([jnp.asarray(add.get(f, 0), jnp.int32)
                             for f in self.TOTAL_FIELDS])
            return cache, result, counters, totals.at[row_of].add(row)

        # the compiled module's name, by which a trace's readers know it
        program.__name__ = fn.__name__
        return program

    def _prefill_fn(self, params, cache, ids, n_valid, slot, offset):
        """A chunk through every block and its last real row's final-normed
        hidden state; in a stack with a cross-decoder, a chunk that does NOT
        end its history: the blocks before the cross-decoder, and no
        result (zeros)."""
        cache, x, counters, _ = self._chunk(params, cache, ids, n_valid,
                                            slot, offset)
        if self.cross_from is not None:
            return cache, jnp.zeros((1, self.spec.dim), jnp.float32), counters
        return cache, self._final(params, x[n_valid - 1])[None], counters

    def _prefill_last_fn(self, params, cache, ids, n_valid, slot, offset):
        """The chunk that ENDS a history, in a stack with a cross-decoder:
        as :meth:`_prefill_fn`, and the chunk's last real row carried through
        the cross-decoder to the head."""
        cache, x, counters, memory = self._chunk(params, cache, ids, n_valid,
                                                 slot, offset)
        last = n_valid - 1
        n_blocks = (offset + n_valid + self.shape.chunk - 1) // self.shape.chunk
        row = self._cross(params, cache, x[last][None], memory[last][None],
                          jnp.reshape(offset + last, (1,)),
                          jnp.reshape(slot, (1,)),
                          jnp.reshape(n_blocks, (1,)), jnp.ones((1,), bool))
        counters["cross_rows"] = jnp.int32(1)
        return cache, self._final(params, row), counters

    def _chunk(self, params, cache, ids, n_valid, slot, offset):
        """One chunk of one session through the blocks that keep something
        per session (all of them, or those before the cross-decoder):
        ``(cache, x [chunk, dim], counters, the memory block's scan output
        [chunk, d_mem] or None)``."""
        cache = list(cache)
        spec, chunk = self.spec, self.shape.chunk
        valid = jnp.arange(ids.shape[0]) < n_valid
        scanned, walked, memory = [], [], [None]

        def mix_with(m, p, h, scope):
            kind = self.kinds[m]
            if kind == "mamba2":
                out, cache[m] = ssm_ops.prefill_chunk(
                    p, spec.ssm, h, n_valid, offset, cache[m], slot, scope)
            elif kind == "mamba1":
                out, cache[m], y = mamba1_ops.prefill_chunk(
                    p, spec.mamba1, h, n_valid, offset, cache[m], slot,
                    scope)
                if m == spec.memory_block:
                    memory[0] = y
            elif kind == "mla":
                out, cache[m], blocks = mla_ops.prefill_chunk(
                    p, spec.mla, h, offset, cache[m], slot, chunk, scope)
                scanned.append(blocks)
            elif kind == "gqa_window":
                out, cache[m], rounds = gqa_ops.window_prefill_chunk(
                    p, spec.gqa_window, h, n_valid, offset, cache[m], slot,
                    scope, m)
                walked.append(rounds)
            else:
                out, cache[m] = gqa_ops.prefill_chunk(
                    p, spec.gqa, h, offset, cache[m], slot, chunk, scope, m)
            return out

        x, counters = self._run(
            params, self._embed(params, ids), valid, mix_with,
            self.cross_from and (0, self.cross_from))
        if self.indexed:
            pos = offset + jnp.arange(ids.shape[0], dtype=jnp.int32)
            counters.update(self._index_counts(sum(scanned), valid, pos))
        if self.windowed:
            counters.update(self._walk_counts(
                sum(walked), (offset + n_valid - 1)[None],
                (offset + ids.shape[0] + chunk - 1) // chunk, 1))
        return cache, x, counters, memory[0]

    def _cross(self, params, cache, x, memory, pos, slots, n_blocks, valid):
        """The cross-decoder over ONE row a session: ``x`` [B, dim] (the
        blocks before it at positions ``pos`` [B] of the slots ``slots``),
        ``memory`` [B, d_mem] the same positions' memory; each cross mixer
        walks the one span, row ``b`` as far as ``n_blocks[b]`` (traced).
        ``[B, dim]``."""
        spec, chunk = self.spec, self.shape.chunk

        def mix_with(m, p, h, scope):
            if self.kinds[m] == "gmu":
                return mamba1_ops.gmu(p, h, memory)
            return gqa_ops.cross_rows(
                p, spec.gqa_cross, h, pos, cache[self._span], slots,
                n_blocks, chunk, scope, m)

        return self._run(params, x, valid, mix_with,
                         (self.cross_from, len(spec.blocks)))[0]

    def _walk_counts(self, rounds, last, n_blocks, rows):
        """A run's three counts in a stack with window layers: the blocks of
        ``window`` positions those layers walked (``rounds``: summed over the
        window layers; every one of the run's ``rows`` real sessions walks
        them), what walks from 0 to each session's last position
        ``last`` [rows, or more with padding at -1] would have taken, and
        the blocks of ``chunk`` positions the full layers walked
        (``n_blocks``: what a full layer's rows walked, summed over them)."""
        window = self.spec.gqa_window.window
        n_window, n_full = (self.kinds.count(k)
                            for k in ("gqa_window", "gqa"))
        return {"window_blocks": (rounds * rows).astype(jnp.int32),
                "window_blocks_from0": (
                    n_window * ((last + window) // window).sum()).astype(
                        jnp.int32),
                "full_blocks": jnp.asarray(n_full * n_blocks, jnp.int32)}

    def _index_counts(self, blocks, valid, pos):
        """A run's two counts under a learned index: blocks of index keys
        scanned, and real query rows that reach past ``index_topk``
        positions (those the index selects for)."""
        return {"index_blocks": blocks.astype(jnp.int32),
                "index_sparse_rows": (
                    valid & (pos >= self.spec.mla.index_topk)).sum().astype(
                        jnp.int32)}

    def _extend_fn(self, params, cache, ids, n_new, slots, pos0, n_blocks):
        """``n_blocks``: the batch's longest reach, in blocks (what a latent
        mixer's rows all walk). A span of keys and values is walked by each
        real row as far as ITS OWN reach (``own`` [B]: the blocks that hold
        the row's ``extend_len`` positions, 0 for a padding row), by the
        block that writes it and by every cross mixer."""
        cache = list(cache)
        spec, chunk = self.spec, self.shape.chunk
        B, S = ids.shape
        pos = pos0[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
        valid = (jnp.arange(S)[None] < n_new[:, None]).reshape(-1)
        real = n_new > 0
        own = jnp.where(real, (pos0 + S + chunk - 1) // chunk, 0)
        scanned, walked, memory = [], [], [None]

        def mix_with(m, p, h, scope):
            kind, h = self.kinds[m], h.reshape(B, S, -1)
            if kind == "mamba2":
                out, cache[m] = ssm_ops.extend(
                    p, spec.ssm, h, n_new, pos0, cache[m], slots, scope)
            elif kind == "mamba1":
                out, cache[m], y = mamba1_ops.extend(
                    p, spec.mamba1, h, n_new, pos0, cache[m], slots, scope)
                if m == spec.memory_block:
                    memory[0] = y
            elif kind == "mla":
                out, cache[m], blocks = mla_ops.extend(
                    p, spec.mla, h, pos, cache[m], slots, n_blocks, chunk,
                    scope)
                scanned.append(blocks)
            elif kind == "gqa_window":
                out, cache[m], rounds = gqa_ops.window_extend(
                    p, spec.gqa_window, h, n_new, pos, cache[m], slots,
                    scope, m)
                walked.append(rounds)
            else:
                out, cache[m] = gqa_ops.extend(
                    p, spec.gqa, h, pos, cache[m], slots, own, chunk, scope,
                    m)
            return out.reshape(B * S, -1)

        x, counters = self._run(
            params, self._embed(params, ids).reshape(B * S, -1), valid,
            mix_with, self.cross_from and (0, self.cross_from))
        if self.indexed:    # every real session's rows scan them
            counters.update(self._index_counts(
                sum(scanned) * real.sum(), valid, pos.reshape(-1)))
        if self.windowed:   # a padding row's last position is -1: no block
            counters.update(self._walk_counts(
                sum(walked), jnp.where(real, pos0 + n_new - 1, -1),
                own.sum(), real.sum()))
        at = jnp.maximum(n_new - 1, 0)
        last = x.reshape(B, S, -1)[jnp.arange(B), at]
        if self.cross_from is not None:
            last = self._cross(params, cache, last,
                               memory[0][jnp.arange(B), at], pos0 + at, slots,
                               own, real)
            # the span's readers: the block that writes it and the cross
            # mixers; each walks every real row's own blocks and no other
            walked_own = ((1 + self.kinds.count("gqa_cross"))
                          * own.sum()).astype(jnp.int32)
            counters.update(cross_rows=real.sum().astype(jnp.int32),
                            span_blocks_walked=walked_own,
                            span_blocks_own=walked_own)
        return cache, self._final(params, last), counters

    def _block_fn(self, params, cache, ids, slots, pos0, denoise, n_unmask,
                  n_blocks):
        """``ids`` [B, block_len]: one block a row, mask rows where masked.
        Every row is a whole forward that writes its keys and values;
        ``denoise`` rows are then unmasked by the rule (the head and the
        rule run here because the decision is the next forward's input: a
        round trip of [B * block_len, items] logits, or of a top-k that
        lacks the softmax's normaliser, would stand between two forwards)."""
        cache = list(cache)
        gen = self.spec.generation
        B, S = ids.shape
        pos = pos0[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
        valid = jnp.repeat(slots != self.shape.n_slots, S)

        def mix_with(m, p, h, scope):
            out, cache[m] = gqa_ops.block_step(
                p, self.spec.gqa, h.reshape(B, S, -1), pos, cache[m], slots,
                n_blocks, self.shape.chunk, scope, m)
            return out.reshape(B * S, -1)

        x, counters = self._run(
            params, self._embed(params, ids).reshape(B * S, -1), valid,
            mix_with)
        with jax.named_scope("seq.head"):
            table = (params["item_embed"]["embedding"] if self.spec.tied_head
                     else params["head"])
            logits = mla_ops.mm(self._final(params, x), table.T)
            # a mask is never an answer
            logits = logits.at[:, gen.mask_row].set(-jnp.inf)
            best = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            top = logits.max(axis=-1)
            conf = jnp.exp(top - jax.nn.logsumexp(logits, axis=-1))
            best, top, conf = (a.reshape(B, S) for a in (best, top, conf))
        picked = unmask_by_rule(
            gen, (ids == gen.mask_row) & (denoise != 0)[:, None], conf,
            n_unmask)
        return cache, {"ids": jnp.where(picked, best, ids), "picked": picked,
                       "score": top, "confidence": conf}, counters

    # -- calls ----------------------------------------------------------------
    def _args(self, kind: str):
        """A call's one array, zeroed, and its parts to fill in."""
        shapes = self._shapes[kind]
        args = np.zeros(sum(map(math.prod, shapes)), np.int32)
        return args, _cut(args, shapes)

    def _call(self, kind: str, args: np.ndarray):
        """One crossing to the device: the compiled call with the host's one
        array. The result and the call's own counters, still there."""
        self.cache, result, counters, self.totals = self._compiled[kind](
            self.params, self.cache, self.totals, args)
        return result, counters

    def take_totals(self):
        """What the programs counted since this was last called (the array,
        still on the device); they count on from zero."""
        taken, self.totals = self.totals, self._zeros
        return taken

    def prefill(self, ids: np.ndarray, slot: int, offset: int,
                last: bool = True):
        """One chunk (``len(ids) <= chunk`` real positions) of the session in
        ``slot``, from position ``offset`` on. ``(h_last [1, dim],
        counters)``, still on the device. ``last``: whether the chunk ends
        its history; in a stack with a cross-decoder a chunk that does not
        runs the shorter program, and its ``h_last`` is zeros."""
        args, (padded, n_valid, at_slot, at) = self._args("prefill")
        padded[:len(ids)] = ids
        n_valid[()], at_slot[()], at[()] = len(ids), slot, offset
        return self._call(self.prefill_program(last), args)

    def prefill_program(self, last: bool) -> str:
        """The chunk program a chunk runs, by whether it ends its history."""
        return ("prefill_last" if last and self.cross_from is not None
                else "prefill")

    def n_blocks(self, reach: int) -> np.int32:
        """The cached blocks attention walks to reach position ``reach``."""
        return np.int32(-(-reach // self.shape.chunk))

    def extend(self, rows):
        """``rows``: [(ids, slot, position of ids[0])], at most
        ``extend_batch`` of at most ``extend_len`` ids each; in a stack with
        recurrent state ``position of ids[0]`` is where the slot's state
        stands.
        ``(h_last [extend_batch, dim], counters)``, still on the device."""
        sh = self.shape
        args, (ids, n_new, slots, pos0, n_blocks) = self._args("extend")
        slots[:] = sh.n_slots                                    # scratch
        for b, (new, slot, at) in enumerate(rows):
            ids[b, :len(new)] = new
            n_new[b], slots[b], pos0[b] = len(new), slot, at
        n_blocks[()] = self.n_blocks(int((pos0 + sh.extend_len).max()))
        return self._call("extend", args)

    def block(self, rows):
        """``rows``: [(ids of one block, slot, position of ids[0], denoise,
        n_unmask)], at most ``gen_batch``; a row that is not ``denoise``
        commits its (finished or known) block. ``(decided, counters)``,
        still on the device: ``decided["ids"]`` the blocks after the rule,
        ``["picked"]`` the positions it unmasked, ``["score"]`` and
        ``["confidence"]`` the best item's logit and probability at every
        position, each ``[gen_batch, block_len]``."""
        sh, S = self.shape, self.spec.generation.block_len
        args, (ids, slots, pos0, denoise, n_unmask, n_blocks) = self._args(
            "block")
        slots[:] = sh.n_slots                                    # scratch
        for b, (block, slot, at, den, n) in enumerate(rows):
            ids[b], slots[b], pos0[b] = block, slot, at
            denoise[b], n_unmask[b] = den, n
        n_blocks[()] = self.n_blocks(int(pos0.max()) + S)
        return self._call("block", args)
