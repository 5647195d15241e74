"""Phi-4-mini-flash-reasoning through the sequence engine (ISSUE 53), at a
small size on the CPU with seeded weights: Mamba-1 mixers and differential
attention under a window by turns, one full-attention layer whose span the
cross-attention layers share, gated memory units over the memory layer's scan
output, and a stack of which a chunk runs only a PART (``StackSpec
.cross_from``); the program against ``mix_full`` / ``attend_full``,
hand-written einsums and the benchmark's plain reference
(``benchmarks/reference/phi4flash_forward.py``).

Tolerances: everything here is float32 on the CPU, so program and reference
differ by the order of their sums alone: 2e-4 of the compared array's largest
value (as ``tests/test_seqmimo.py``), 1e-4 relative where two evaluations of
the reference are held together."""

import dataclasses
import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.models.sessionrec import (LatentCache, SeqStackModel,
                                                SeqTicket, plan_step)
from predictionio_tpu.ops import gqa as gqa_ops
from predictionio_tpu.ops import mamba1 as m1
from predictionio_tpu.ops.sessionrec import (BlockSpec, ServeShape,
                                             StackPrograms, StackSpec,
                                             init_stack)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def ref():
    path = os.path.join(REPO, "benchmarks", "reference",
                        "phi4flash_forward.py")
    spec = importlib.util.spec_from_file_location("phi4flash_ref", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


D, H, KV, HD, WINDOW, L, N_ITEMS = 32, 8, 4, 4, 8, 8, 50
SAME = dict(dim=D, heads=H, kv_heads=KV, head_dim=HD, block_len=1, eps=1e-5,
            rope=False, qk_norm=False, bias=True, diff=True)
FULL = gqa_ops.GQADims(**SAME)
WIN = gqa_ops.GQADims(window=WINDOW, **SAME)
CROSS = gqa_ops.GQADims(cross=True, **SAME)
M1 = m1.Mamba1Dims(dim=D, d_inner=64, d_state=4, dt_rank=2, d_conv=4)
#: layers 0-3 Mamba-1 and window attention by turns, 4 the memory layer, 5
#: the full layer, 6 a gated memory unit, 7 cross attention
KINDS = ("mamba1", "gqa_window", "mamba1", "gqa_window", "mamba1", "gqa",
         "gmu", "gqa_cross")
#: chunks of 16 over rings of 24 (three blocks of 8), spans of 96 + 16
SHAPE = ServeShape(n_slots=3, capacity=96, chunk=16, extend_len=4,
                   extend_batch=2)
DM = {"D": D, "F": 64, "eps": 1e-5, "H": H, "Hkv": KV, "d": HD,
      "window": WINDOW, "L": L, "inner": 64, "N": 4, "R": 2, "K": 4}


def small_spec(window=WIN):
    blocks = tuple(BlockSpec(mixer=k, ffn="swiglu", norm="layernorm",
                             topology="pre_ln") for k in KINDS)
    return StackSpec(dim=D, ffn_dim=64, blocks=blocks, positions="rope",
                     eps=1e-5, tied_head=True, gqa=FULL, gqa_window=window,
                     gqa_cross=CROSS, mamba1=M1, memory_block=4)


def seeded_params(spec, seed=0):
    """init_stack's weights with every norm and bias made non-trivial and a
    small item embedding (a tied head's)."""
    params = init_stack(spec, jax.random.PRNGKey(seed), N_ITEMS)
    rng = np.random.default_rng(seed)

    def jitter(tree, name=""):
        if isinstance(tree, dict):
            return {k: jitter(v, k) for k, v in tree.items()}
        if isinstance(tree, list):
            return [jitter(v) for v in tree]
        if name in ("scale", "bias", "subln", "conv_b") or name[:2] == "b_":
            return jnp.asarray(tree + 0.2 * rng.standard_normal(tree.shape),
                               jnp.float32)
        return tree

    params = jitter(params)
    params["item_embed"]["embedding"] = jnp.asarray(
        0.5 * rng.standard_normal((N_ITEMS, D)), jnp.float32)
    return params


def as_reference(params):
    """The same arrays under the reference's names."""
    return {"embed": params["item_embed"]["embedding"],
            "final_norm": params["final_norm"], "layers": params["blocks"]}


def close(a, b, tol=2e-4):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    scale = max(float(np.abs(b).max()), 1e-6)
    assert float(np.abs(a - b).max()) <= tol * scale, (
        float(np.abs(a - b).max()), scale)


def normal(seed, *shape):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape),
                       jnp.float32)


def history(seed, n):
    return np.random.default_rng(seed).integers(1, N_ITEMS, n).astype(
        np.int32)


def logits_of(params, h):
    return np.asarray(h @ params["item_embed"]["embedding"].T)


# -- Mamba-1 ------------------------------------------------------------------

def mamba_by_hand(p, dims, a):
    """The issue's equations in float64 numpy, one position at a time."""
    w = {k: np.asarray(v, np.float64) for k, v in p.items()}
    a = np.asarray(a, np.float64)
    T, d = a.shape[0], dims
    xz = a @ w["w_in"]
    x, z = xz[:, :d.d_inner], xz[:, d.d_inner:]
    padded = np.concatenate([np.zeros((d.d_conv - 1, d.d_inner)), x])
    x = sum(w["conv_w"][k] * padded[k:k + T] for k in range(d.d_conv)) \
        + w["conv_b"]
    x = x / (1 + np.exp(-x))
    dbc = x @ w["w_x"]
    delta = np.log1p(np.exp(dbc[:, :d.dt_rank] @ w["w_dt"] + w["b_dt"]))
    B = dbc[:, d.dt_rank:d.dt_rank + d.d_state]
    C = dbc[:, d.dt_rank + d.d_state:]
    A = -np.exp(w["a_log"])
    S, y = np.zeros((d.d_inner, d.d_state)), np.zeros((T, d.d_inner))
    for t in range(T):
        S = np.exp(delta[t][:, None] * A) * S \
            + (delta[t] * x[t])[:, None] * B[t][None]
        y[t] = S @ C[t] + w["d"] * x[t]
    return (y * (z / (1 + np.exp(-z)))) @ w["w_out"], y


@pytest.fixture(scope="module")
def mamba():
    p = m1.init(jax.random.PRNGKey(5), M1)
    p["conv_b"] = normal(6, 64) * 0.2
    return p


def test_the_mamba1_plain_form_is_the_hand_written_recurrence(mamba):
    a = normal(7, 37, D)
    out, y = m1.mix_full(mamba, M1, a)
    want_out, want_y = mamba_by_hand(mamba, M1, a)
    close(out, want_out)
    close(y, want_y)
    assert mamba["a_log"].shape == (64, 4) and mamba["w_x"].shape == (64, 10)
    assert mamba["w_dt"].shape == (2, 64) and mamba["w_in"].shape == (D, 128)


@pytest.mark.parametrize("cuts", [(16, 16, 5), (16, 3), (7,), (16, 16)])
def test_mamba1_chunks_give_the_plain_form_across_chunk_boundaries(mamba,
                                                                   cuts):
    """Chunks of up to 16 positions (the last ones padded) from a FRESH slot
    whose state held something else: a call at position 0 starts from zeros."""
    T = sum(cuts)
    a = normal(8, T, D)
    want_out, want_y = m1.mix_full(mamba, M1, a)
    state = jax.tree_util.tree_map(lambda s: s + 3.0,
                                   m1.init_state(M1, 3, jnp.float32))
    outs, ys, at = [], [], 0
    for n in cuts:
        padded = jnp.zeros((16, D)).at[:n].set(a[at:at + n])
        out, state, y = m1.prefill_chunk(mamba, M1, padded, jnp.int32(n),
                                         jnp.int32(at), state, jnp.int32(1))
        outs.append(out[:n])
        ys.append(y[:n])
        at += n
    close(jnp.concatenate(outs), want_out)
    close(jnp.concatenate(ys), want_y)
    # the other slots' states are as they were
    assert float(jnp.abs(state["ssm"][0] - 3.0).max()) == 0.0
    assert float(jnp.abs(state["conv"][2] - 3.0).max()) == 0.0


def test_a_mamba1_extension_steps_each_rows_state_in_its_slot(mamba):
    """Two sessions prefilled into slots 2 and 0, then extended in one batch
    by 3 and 1 positions beside a padding row on the scratch slot."""
    a, b = normal(9, 23, D), normal(10, 18, D)
    state = m1.init_state(M1, 4, jnp.float32)
    for rows, n, slot in ((a, 20, 2), (b, 17, 0)):
        for at in range(0, n, 16):
            k = min(16, n - at)
            padded = jnp.zeros((16, D)).at[:k].set(rows[at:at + k])
            _, state, _ = m1.prefill_chunk(mamba, M1, padded, jnp.int32(k),
                                           jnp.int32(at), state,
                                           jnp.int32(slot))
    new = jnp.zeros((3, 4, D)).at[0, :3].set(a[20:23]).at[1, :1].set(b[17:18])
    out, state2, y = m1.extend(
        mamba, M1, new, jnp.asarray([3, 1, 0]), jnp.asarray([20, 17, 0]),
        state, jnp.asarray([2, 0, 3]))
    want_a, want_ya = m1.mix_full(mamba, M1, a)
    want_b, _ = m1.mix_full(mamba, M1, b)
    close(out[0, :3], want_a[20:23])
    close(y[0, :3], want_ya[20:23])
    close(out[1, :1], want_b[17:18])
    # slot 1 was never touched, and a further extension continues the state
    assert float(jnp.abs(state2["ssm"][1]).max()) == 0.0
    more = normal(11, 2, D)
    out3, _, _ = m1.extend(
        mamba, M1, jnp.zeros((3, 4, D)).at[0, :2].set(more),
        jnp.asarray([2, 0, 0]), jnp.asarray([23, 0, 0]), state2,
        jnp.asarray([2, 3, 3]))
    close(out3[0, :2],
          m1.mix_full(mamba, M1, jnp.concatenate([a, more]))[0][23:25])


@pytest.mark.parametrize("T, inner, N, real", [
    (16, 1024, 4, 16), (24, 2048, 16, 19), (8, 1024, 2, 1)])
def test_the_selective_scan_kernel_is_the_sequential_scan(T, inner, N, real):
    """The kernel (under the interpreter here) against XLA's loop: from a
    state that is not zero, with padding positions (``delta`` 0) behind the
    real ones; and ``scan`` takes it by shape alone."""
    from predictionio_tpu.ops.pallas import selective_scan as kernel

    x, B, C = normal(60, T, inner), normal(61, T, N), normal(62, T, N)
    dt = jax.nn.softplus(normal(63, T, inner) - 2.0)
    dt = jnp.where((jnp.arange(T) < real)[:, None], dt, 0.0)
    a_t, s0 = -jnp.exp(normal(64, N, inner)), normal(65, N, inner)
    want_y, want_s = m1.scan_steps(x, dt, a_t, B, C, s0)
    y, s = kernel.selective_scan(x, dt, a_t, B, C, s0, interpret=True)
    close(y, want_y, tol=1e-5)
    close(s, want_s, tol=1e-5)
    assert kernel.takes(T, inner) and not kernel.takes(T, 64)
    assert not kernel.takes(T + 1, inner)
    got_y, got_s = m1.scan(x, dt, a_t, B, C, s0)
    close(got_y, want_y, tol=1e-5)
    # the state passes the padding positions unchanged
    if real < T:
        close(s, m1.scan_steps(x[:real], dt[:real], a_t, B[:real], C[:real],
                               s0)[1], tol=1e-5)
    with pytest.raises(ValueError, match="whole groups"):
        kernel.selective_scan(x[:, :64], dt[:, :64], a_t[:, :64], B, C,
                              s0[:, :64])


def test_a_gated_memory_unit_gates_the_same_positions_memory():
    p = m1.init_gmu(jax.random.PRNGKey(12), D, 64)
    a, m = normal(13, 5, D), normal(14, 5, 64)
    want = (np.asarray(jax.nn.silu(a @ p["w_1"])) * np.asarray(m)) \
        @ np.asarray(p["w_2"])
    close(m1.gmu(p, a, m), want)
    assert set(p) == {"w_1", "w_2"}


# -- differential attention -----------------------------------------------------

def diff_by_hand(p, dims, x, depth, kv_from=None):
    """The issue's equations for one layer in float64, every score
    materialised: heads pair up ``(2i, 2i + 1)``; a query pair reads key/value
    pair ``i // group``."""
    d, T = dims, x.shape[0]
    w = {k: np.asarray(v, np.float64) for k, v in p.items()}
    x = np.asarray(x, np.float64)
    q = (x @ w["w_q"] + w["b_q"]).reshape(T, d.heads, d.head_dim)
    if kv_from is None:
        k = (x @ w["w_k"] + w["b_k"]).reshape(T, d.kv_heads, d.head_dim)
        v = (x @ w["w_v"] + w["b_v"]).reshape(T, d.kv_heads, d.head_dim)
    else:
        k, v = (np.asarray(a, np.float64) for a in kv_from)
    pos = np.arange(T)
    gap = pos[:, None] - pos[None, :]
    sees = (gap >= 0) & ((gap < d.window) if d.window else True)
    l0 = 0.8 - 0.6 * math.exp(-0.3 * depth)
    lam = (np.exp(w["lambda_q1"] @ w["lambda_k1"])
           - np.exp(w["lambda_q2"] @ w["lambda_k2"]) + l0)
    out = np.zeros((T, d.heads // 2, 2 * d.head_dim))
    for j in range(d.heads // 2):           # a double head
        pair = j // d.group
        v12 = np.concatenate([v[:, 2 * pair], v[:, 2 * pair + 1]], axis=-1)
        o = []
        for a in (0, 1):
            s = q[:, 2 * j + a] @ k[:, 2 * pair + a].T / math.sqrt(d.head_dim)
            s = np.where(sees, s, -np.inf)
            e = np.exp(s - s.max(axis=-1, keepdims=True))
            o.append((e / e.sum(axis=-1, keepdims=True)) @ v12)
        mixed = o[0] - lam * o[1]
        out[:, j] = (mixed / np.sqrt((mixed ** 2).mean(-1, keepdims=True)
                                     + d.eps) * w["subln"] * (1 - l0))
    return out.reshape(T, -1) @ w["w_o"] + w["b_o"]


def attention_params(dims, seed=3):
    p = gqa_ops.init(jax.random.PRNGKey(seed), dims)
    for i, name in enumerate(sorted(n for n in p if n[:2] == "b_")):
        p[name] = 0.2 * normal(40 + i, *p[name].shape)
    p["subln"] = 1.0 + 0.2 * normal(50, 2 * dims.head_dim)
    return p


VARIANTS = {"full_layer": FULL, "window_layer": WIN,
            "one_pair_a_group": dataclasses.replace(FULL, kv_heads=8),
            "no_bias": dataclasses.replace(WIN, bias=False)}


@pytest.mark.parametrize("depth", [0, 5])
@pytest.mark.parametrize("name", sorted(VARIANTS))
def test_the_plain_differential_form_is_the_hand_written_einsum(name, depth):
    dims = VARIANTS[name]
    p = attention_params(dims)
    assert ("b_q" in p) == dims.bias and p["subln"].shape == (2 * HD,)
    assert all(p[n].shape == (HD,) for n in gqa_ops.LAMBDAS)
    x = normal(4, 29, D)
    if not dims.bias:
        p = {**p, **{b: jnp.zeros_like(p["w_" + b[2:]][0])
                     for b in ("b_q", "b_k", "b_v", "b_o")}}
    want = diff_by_hand(p, dims, x, depth)
    close(gqa_ops.attend_full(p, dims, x, jnp.arange(29, dtype=jnp.int32),
                              depth), want)


def test_lambda_init_follows_the_depth():
    assert gqa_ops.lambda_init(0) == pytest.approx(0.2)
    assert gqa_ops.lambda_init(17) == pytest.approx(
        0.8 - 0.6 * math.exp(-5.1))
    p = attention_params(FULL)
    want = (math.exp(float(p["lambda_q1"] @ p["lambda_k1"]))
            - math.exp(float(p["lambda_q2"] @ p["lambda_k2"])) + 0.2)
    assert float(gqa_ops.diff_lambda(p, 0)) == pytest.approx(want, rel=1e-5)


def test_each_part_of_differential_attention_moves_the_answer():
    """Leaving out ``lambda o_2``, a bias, the sub-norm's weight or one
    position of the window is visible at these sizes."""
    p = attention_params(WIN)
    x, pos = normal(4, 29, D), jnp.arange(29, dtype=jnp.int32)
    base = np.asarray(gqa_ops.attend_full(p, WIN, x, pos, 3))
    others = {
        "depth": gqa_ops.attend_full(p, WIN, x, pos, 4),
        "window": gqa_ops.attend_full(
            p, dataclasses.replace(WIN, window=WINDOW - 1), x, pos, 3),
        # (a key's bias moves every score of a query alike: the softmax
        # does not see it; a query's does)
        "bias": gqa_ops.attend_full(
            {**p, "b_q": jnp.zeros_like(p["b_q"])}, WIN, x, pos, 3),
        "subln": gqa_ops.attend_full(
            {**p, "subln": jnp.ones_like(p["subln"])}, WIN, x, pos, 3),
        "plain": gqa_ops.attend_full(
            {k: v for k, v in p.items()},
            dataclasses.replace(WIN, diff=False), x, pos)}
    for name, got in others.items():
        assert float(np.abs(np.asarray(got) - base).max()) > 1e-2, name


@pytest.mark.parametrize("depth", [1, 17])
def test_a_chunk_and_an_extension_of_a_full_layer_give_the_plain_form(depth):
    p = attention_params(FULL)
    x = normal(15, 39, D)
    want = gqa_ops.attend_full(p, FULL, x, jnp.arange(39, dtype=jnp.int32),
                               depth)
    cache = jnp.zeros((3, 64, FULL.cache_width), jnp.float32)
    outs = []
    for at in (0, 16):
        out, cache = gqa_ops.prefill_chunk(p, FULL, x[at:at + 16],
                                           jnp.int32(at), cache,
                                           jnp.int32(1), 16, depth=depth)
        outs.append(out)
    for at in (32, 36):
        n = min(4, 39 - at)
        new = jnp.zeros((2, 4, D)).at[0, :n].set(x[at:at + n])
        pos = jnp.asarray([[at, at + 1, at + 2, at + 3], [0, 1, 2, 3]])
        out, cache = gqa_ops.extend(p, FULL, new, pos, cache,
                                    jnp.asarray([1, 2]), jnp.int32(3), 16,
                                    depth=depth)
        outs.append(out[0, :n])
    close(jnp.concatenate(outs), want)


@pytest.mark.parametrize("depth", [1, 15])
def test_a_window_layers_ring_gives_the_plain_form(depth):
    p = attention_params(WIN)
    x = normal(16, 53, D)
    want = gqa_ops.attend_full(p, WIN, x, jnp.arange(53, dtype=jnp.int32),
                               depth)
    ring = jnp.zeros((3, gqa_ops.ring_len(WINDOW, 16), WIN.cache_width))
    outs = []
    for at, n in ((0, 16), (16, 16), (32, 14)):
        padded = jnp.zeros((16, D)).at[:n].set(x[at:at + n])
        out, ring, _ = gqa_ops.window_prefill_chunk(
            p, WIN, padded, jnp.int32(n), jnp.int32(at), ring, jnp.int32(2),
            "w", depth)
        outs.append(out[:n])
    for at, n in ((46, 4), (50, 3)):
        new = jnp.zeros((2, 4, D)).at[1, :n].set(x[at:at + n])
        pos = jnp.asarray([[0, 1, 2, 3], [at, at + 1, at + 2, at + 3]])
        out, ring, _ = gqa_ops.window_extend(
            p, WIN, new, jnp.asarray([0, n]), pos, ring, jnp.asarray([0, 2]),
            "w", depth)
        outs.append(out[1, :n])
    close(jnp.concatenate(outs), want)


def test_a_cross_mixer_walks_another_mixers_span_and_writes_nothing():
    """A cross mixer holds ``W_q`` and ``W_o`` alone; its rows attend the
    span a full layer wrote, each up to its own position."""
    full, cross = attention_params(FULL, 3), attention_params(CROSS, 4)
    assert "w_k" not in cross and "b_v" not in cross and "subln" in cross
    x, u = normal(17, 30, D), normal(18, 30, D)
    pos = jnp.arange(30, dtype=jnp.int32)
    _, k, v = gqa_ops.project(full, FULL, x, pos)
    want = gqa_ops.attend_full(cross, CROSS, u, pos, 7, kv=(k, v))
    close(want, diff_by_hand(cross, CROSS, u, 7, kv_from=(k, v)))
    cache = jnp.zeros((3, 48, FULL.cache_width), jnp.float32)
    for at, n in ((0, 16), (16, 14)):
        padded = jnp.zeros((16, D)).at[:n].set(x[at:at + n])
        _, cache = gqa_ops.prefill_chunk(full, FULL, padded, jnp.int32(at),
                                         cache, jnp.int32(1), 16, depth=5)
    rows = jnp.asarray([29, 11, 0])
    got = gqa_ops.cross_rows(cross, CROSS, u[rows], rows, cache,
                             jnp.asarray([1, 1, 1]), jnp.int32(2), 16,
                             depth=7)
    close(got, np.asarray(want)[np.asarray(rows)])


# -- the stack ----------------------------------------------------------------

@pytest.fixture(scope="module")
def stack():
    spec = small_spec()
    params = seeded_params(spec)
    return spec, params, StackPrograms(spec, params, SHAPE)


def prefill(programs, rows, slot):
    h = None
    for at in range(0, len(rows), 16):
        h, _ = programs.prefill(rows[at:at + 16], slot, at,
                                last=at + 16 >= len(rows))
    return h


def test_fourteen_of_the_mixers_hold_nothing(stack):
    spec, _, programs = stack
    assert spec.cross_from == 6 and programs.cross_from == 6
    held = [type(c).__name__ for c in programs.cache]
    assert held[6:] == ["NoneType", "NoneType"] and "NoneType" not in held[:6]
    assert programs.cache[0]["ssm"].shape == (4, 4, 64)
    assert programs.cache[1].shape == (4, 24, FULL.cache_width)
    assert programs.cache[5].shape == (4, 112, FULL.cache_width)
    assert set(programs._compiled) == {"prefill", "prefill_last", "extend"}
    assert StackPrograms.TOTAL_FIELDS[-3:] == (
        "cross_rows", "span_blocks_walked", "span_blocks_own")


def test_a_cross_decoder_comes_last_and_needs_its_span_and_memory():
    blocks = small_spec().blocks
    with pytest.raises(ValueError, match="come last"):
        dataclasses.replace(small_spec(),
                            blocks=blocks[:6] + blocks[7:] + blocks[6:7]
                            + blocks[:1]).cross_from
    with pytest.raises(ValueError, match="memory_block"):
        dataclasses.replace(small_spec(), memory_block=None).cross_from
    with pytest.raises(ValueError, match="memory_block"):
        dataclasses.replace(small_spec(), memory_block=1).cross_from
    assert dataclasses.replace(small_spec(), blocks=blocks[:6]).cross_from \
        is None


@pytest.mark.parametrize("n", [45, 32, 7, 17])
def test_chunked_prefill_then_extensions_give_the_full_forwards_logits(
        stack, ref, n):
    """A history prefilled in chunks of 16 (its last chunk carries ONE row
    through layers 6-7), then extended three times through the cache, against
    the reference's full forward over each history: logits, not ranks."""
    _, params, programs = stack
    weights = as_reference(params)
    rows = history(n, n + 9)
    close(logits_of(params, prefill(programs, rows[:n], 1))[0],
          ref.forward(weights, rows[:n], DM))
    at = n
    for step in (3, 2, 4):
        hs, _ = programs.extend([(rows[at:at + step], 1, at)])
        at += step
        close(logits_of(params, hs)[0], ref.forward(weights, rows[:at], DM))


def test_a_chunk_that_does_not_end_a_history_returns_nothing(stack):
    _, _, programs = stack
    rows = history(21, 40)
    h, _ = programs.prefill(rows[:16], 2, 0, last=False)
    assert float(jnp.abs(h).max()) == 0.0 and h.shape == (1, D)
    assert programs.prefill_program(False) == "prefill"
    assert programs.prefill_program(True) == "prefill_last"


def test_two_sessions_extend_in_one_batch_each_from_its_own_slot(stack, ref):
    _, params, programs = stack
    weights = as_reference(params)
    a, b = history(22, 40), history(23, 21)
    prefill(programs, a[:37], 0)
    prefill(programs, b[:19], 2)
    hs, _ = programs.extend([(a[37:40], 0, 37), (b[19:21], 2, 19)])
    got = logits_of(params, hs)
    close(got[0], ref.forward(weights, a, DM))
    close(got[1], ref.forward(weights, b, DM))


def test_an_extension_batch_in_the_kernel_answers_as_the_parents_walk(
        stack, monkeypatch):
    """The span's two readers (layer 5's ``extend``, layer 7's
    ``cross_rows``) with each row over its OWN blocks in ``span_walk``,
    against the walk until PR 56: every row as far as the batch's longest
    through ``attend_over_blocks``; and the last chunk's one row through the
    cross-decoder alike (``prefill_last``)."""
    spec, params, programs = stack

    def answers(programs):
        a, b = history(27, 40), history(28, 21)
        first = [prefill(programs, a[:37], 0), prefill(programs, b[:19], 2)]
        hs, _ = programs.extend([(a[37:40], 0, 37), (b[19:21], 2, 19)])
        return [np.asarray(h) for h in first] + [np.asarray(hs)]

    mine = answers(programs)
    monkeypatch.setattr(
        gqa_ops, "_walk", lambda d, q, pos, cache, slots, n, block:
        gqa_ops._attend(d, q, pos, cache, slots, jnp.max(n), block))
    for got, want in zip(mine, answers(StackPrograms(spec, params, SHAPE))):
        close(got, want, 1e-5)


def test_the_programs_count_the_rows_they_carry_and_the_blocks_they_walk(
        stack):
    _, _, programs = stack
    programs.take_totals()
    a, b = history(24, 40), history(25, 20)
    prefill(programs, a[:37], 0)            # three chunks, the last carries
    prefill(programs, b[:18], 1)            # two chunks
    programs.extend([(a[37:40], 0, 37), (b[18:20], 1, 18)])
    totals = np.asarray(programs.take_totals())
    got = {kind: dict(zip(StackPrograms.TOTAL_FIELDS, row))
           for kind, row in zip(StackPrograms.TOTAL_KINDS, totals)}
    assert got["prefill"]["runs"] == 5 and got["prefill"]["tokens"] == 55
    assert got["prefill"]["cross_rows"] == 2
    assert got["extend"]["cross_rows"] == 2
    # two readers (layer 5 and the one cross layer); each row walks the
    # blocks of its own reach, 3 and 2, and not the 3 of the longer
    assert got["extend"]["span_blocks_walked"] == 2 * (3 + 2)
    assert got["extend"]["span_blocks_own"] == 2 * (3 + 2)
    assert got["prefill"]["span_blocks_walked"] == 0


def test_the_reference_at_the_last_row_is_its_all_rows_evaluation(stack,
                                                                  ref):
    """Layers 6-7 evaluated for the compared row alone, and for every row:
    the same numbers to float32 rounding, at every history length."""
    _, params, _ = stack
    weights = as_reference(params)
    rows = history(26, 41)
    every = ref.forward(weights, rows, DM, rows="all")
    assert every.shape == (41, N_ITEMS)
    for n in (41, 30, 16, 1):
        close(ref.forward(weights, rows[:n], DM), every[n - 1], tol=1e-4)


def test_the_references_layer_kinds_are_the_stacks(ref):
    assert ref.layer_kinds(8) == ("mamba", "window", "mamba", "window",
                                  "memory", "full", "gmu", "cross")
    kinds = ref.layer_kinds(32)
    assert [k for k in kinds[:16:2]] == ["mamba"] * 8
    assert [k for k in kinds[1:16:2]] == ["window"] * 8
    assert kinds[16:18] == ("memory", "full")
    assert kinds[18::2] == ("gmu",) * 7 and kinds[19::2] == ("cross",) * 7


@pytest.mark.parametrize("what", ["no_lambda", "window", "state"])
def test_the_references_controls_move_its_answer(stack, ref, what):
    _, params, _ = stack
    weights = as_reference(params)
    rows = history(27, 40)
    how = {"no_lambda": {"no_lambda": True}, "window": {"window": 7},
           "state": {"state_hold": (4, 3)}}[what]
    base = ref.forward(weights, rows, DM)
    assert float(np.abs(ref.forward(weights, rows, DM, **how) - base).max()) \
        > 1e-3 * float(np.abs(base).max())


# -- the model: slots, plans, counters ------------------------------------------

def small_model(n_slots=3):
    spec = small_spec()
    params = seeded_params(spec)
    items = BiMap.from_vocab([f"i{r}" for r in range(N_ITEMS)])
    shape = dataclasses.replace(SHAPE, n_slots=n_slots)
    return SeqStackModel(spec, params, items, shape), params


def query(rows, num=5):
    return {"items": [f"i{int(r)}" for r in rows], "num": num}


def test_the_slot_holds_state_rings_and_a_span_under_the_whole_prefix_rule():
    model, _ = small_model()
    assert model.cache.recurrent and model.cache.ring == 24
    assert model.cache.window == WINDOW


def test_a_session_that_grows_resumes_and_one_that_diverges_misses(ref):
    model, params = small_model()
    weights = as_reference(params)
    rows = history(28, 60)
    first = model.answer(query(rows[:40]))
    assert first.extension is False
    grown = model.answer(query(rows[:43]))
    assert grown.extension is True and grown.slot == first.slot
    stats = model.stats()
    assert stats["state_resumes"] == 1 and stats["hit_tokens"] == 40
    # diverged at position 41: a per-position cache would resume there; the
    # states cannot, so every layer starts over in the slot the session had
    other = np.concatenate([rows[:41], history(29, 6)])
    missed = model.answer(query(other))
    stats = model.stats()
    assert missed.slot == first.slot and missed.extension is False
    assert stats["rewind_misses"] == 1 and stats["ring_misses"] == 0
    assert stats["rewind_miss_tokens"] == 41
    want = ref.forward(weights, other, DM)
    served = {item: score for item, score in missed.result}
    top = np.argsort(-want)[:5]
    assert set(served) == {f"i{int(i)}" for i in top}
    close([served[f"i{int(i)}"] for i in top], want[top])
    # a repeated query is a miss too: its last position is left to compute
    model.answer(query(other))
    assert model.stats()["rewind_misses"] == 2


def test_a_ring_never_fires_under_the_whole_prefix_rule():
    """Went back far past the rings' floor: with a state in the slot it is a
    rewind miss, never a ring miss."""
    cache = LatentCache(2, recurrent=True, ring=24, window=8)
    rows = np.arange(1, 81, dtype=np.int32)
    slot, cached = cache.acquire(rows)
    cache.release(slot, rows)
    assert cache.floor[slot] == 80 - 24
    again = cache.acquire(rows[:50])        # 50 of 80 shared: a would-be hit
    assert again == (slot, 0)
    assert cache.rewind_misses == 1 and cache.ring_misses == 0
    cache.release(slot, rows[:50])
    assert cache.acquire(rows[:53]) == (slot, 50)       # grew: a resume
    assert cache.state_resumes == 1


def tickets_of(shape, *remaining):
    out = []
    for i, n in enumerate(remaining):
        t = SeqTicket(np.arange(1, n + 1, dtype=np.int32), 5, i, 0)
        out.append(t)
    return out


@pytest.mark.parametrize("remaining, tokens, last", [
    (45, 16, False), (17, 16, False), (16, 16, True), (9, 9, True),
    (33, 16, False)])
def test_plan_step_says_whether_the_chunk_ends_its_history(remaining, tokens,
                                                           last):
    (t,) = tickets_of(SHAPE, remaining)
    plan = plan_step([t], SHAPE)
    assert plan.prefill is t and plan.prefill_tokens == tokens
    assert plan.prefill_last is last


def test_plan_step_without_a_prefill_names_no_last_chunk():
    plan = plan_step(tickets_of(SHAPE, 3, 2), SHAPE)
    assert plan.prefill is None and plan.prefill_last is False
    assert len(plan.extend) == 2


def test_every_chunk_but_a_historys_last_runs_the_short_program():
    model, _ = small_model()
    ran = []
    programs = model.programs()
    prefill_of = programs.prefill

    def spy(ids, slot, offset, last=True):
        ran.append((offset, len(ids), programs.prefill_program(last)))
        return prefill_of(ids, slot, offset, last)

    programs.prefill = spy
    model.answer(query(history(30, 45)))
    assert ran == [(0, 16, "prefill"), (16, 16, "prefill"),
                   (32, 13, "prefill_last")]
    # a history whose tail behind its whole chunks fits an extension ends in
    # one: no chunk of it is its last, and the extension carries its row
    del ran[:]
    model.answer(query(history(31, 35)))
    assert ran == [(0, 16, "prefill"), (16, 16, "prefill")]
    stats = model.stats()
    assert stats["prefill_cross_rows"] == 1
    assert stats["extend_cross_rows"] == 1 and stats["extend_runs"] == 1


def test_every_answer_went_through_the_cross_decoder_exactly_once():
    """No part of the mathematics is left out: a chunk skips layers 6-7 only
    because no answer reads its rows; each answered query carried one row
    through them, a first query in its last chunk (or, where its tail fits
    one, in an extension), a later one in its extension."""
    model, _ = small_model(n_slots=4)
    rng = np.random.default_rng(32)
    answered = firsts_by_chunk = 0
    for s, n in enumerate((40, 9, 33, 50, 16)):
        rows = history(100 + s, n + 12)
        model.answer(query(rows[:n]))
        answered += 1
        firsts_by_chunk += (n % 16 == 0 or n % 16 > SHAPE.extend_len)
        at = n
        for _ in range(3):
            at += int(rng.integers(1, 4))
            model.answer(query(rows[:at]))
            answered += 1
    stats = model.stats()
    assert stats["prefill_cross_rows"] == firsts_by_chunk == 3
    assert stats["prefill_cross_rows"] + stats["extend_cross_rows"] \
        == answered == 20
    assert stats["extend_state_rows"] == stats["extend_cross_rows"]
    assert stats["state_resumes"] == 15 and stats["rewind_misses"] == 0
    assert stats["prefill_tokens"] + stats["extend_tokens"] \
        == stats["miss_tokens"]
