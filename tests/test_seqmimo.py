"""MiMo-V2.5 through the sequence engine (ISSUE 49), at a small size on the
CPU with seeded weights: window and full grouped-query layers in one stack
(two ``GQADims``: 8 and 4 key/value heads, two RoPE bases on the head's
leading dimensions, values of their own width, a learned sink in the window
layers' normaliser), a RING a window layer beside a SPAN a full one in each
slot, the ring's resume rule, and the expert layer's sixteen shares; the
program against ``attend_full``, hand-written einsums and the benchmark's
plain reference (``benchmarks/reference/mimo_v2_forward.py``). The two stacks
that ran ``ops/gqa.py`` before trace to what they traced to
(``tests/parent_gqa.py``)."""

import dataclasses
import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.models.sessionrec import LatentCache, SeqStackModel
from predictionio_tpu.ops import attention as attention_ops
from predictionio_tpu.ops import gqa as gqa_ops
from predictionio_tpu.ops import moe as moe_ops
from predictionio_tpu.ops import sessionrec as stack_ops
from predictionio_tpu.ops.sessionrec import (
    BlockSpec, Generation, ServeShape, StackPrograms, StackSpec, init_stack)
from tests import parent_gqa

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def ref():
    path = os.path.join(REPO, "benchmarks", "reference", "mimo_v2_forward.py")
    spec = importlib.util.spec_from_file_location("mimo_v2_forward_ref", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


WINDOW = 8
SAME = dict(dim=64, heads=8, head_dim=24, block_len=1, eps=1e-5,
            qk_norm=False, v_head_dim=16, rope_dims=8, value_scale=0.707)
FULL = gqa_ops.GQADims(kv_heads=2, rope_theta=1e7, **SAME)
WIN = gqa_ops.GQADims(kv_heads=4, rope_theta=1e4, window=WINDOW, sink=True,
                      **SAME)
MOE = moe_ops.MoEDims(
    dim=64, expert_dim=32, n_routed=32, n_zero=0, top_k=4, scale=1.0,
    held=(0, 2), norm_topk=True, scoring="sigmoid")
N_ITEMS = 50
PATTERN = (0, 1, 1, 1, 1, 1, 0)
#: chunks of 16 over rings of 24 (three blocks of 8), spans of 96 + 16
SHAPE = ServeShape(n_slots=3, capacity=96, chunk=16, extend_len=4,
                   extend_batch=2)


def small_spec(moe=MOE, window=WIN):
    blocks = tuple(BlockSpec(mixer="gqa_window" if w else "gqa",
                             ffn="moe" if i else "swiglu", norm="rmsnorm",
                             topology="pre_ln")
                   for i, w in enumerate(PATTERN))
    return StackSpec(dim=64, ffn_dim=128, positions="rope", eps=1e-5,
                     tied_head=False, gqa=FULL, gqa_window=window, moe=moe,
                     blocks=blocks)


def seeded_params(spec, seed=0):
    """init_stack's weights with every norm made non-trivial, a small
    selection bias in the router and a unit-scale item embedding."""
    params = init_stack(spec, jax.random.PRNGKey(seed), N_ITEMS)
    rng = np.random.default_rng(seed)

    def jitter(tree, name=""):
        if isinstance(tree, dict):
            return {k: jitter(v, k if "norm" in k else name)
                    for k, v in tree.items()}
        if isinstance(tree, list):
            return [jitter(v) for v in tree]
        if "norm" in name:
            return jnp.asarray(tree + 0.2 * rng.standard_normal(tree.shape),
                               jnp.float32)
        return tree

    params = jitter(params)
    for block in params["blocks"]:
        if "moe" in block:
            block["moe"]["bias"] = jnp.asarray(
                2e-3 * rng.standard_normal(spec.moe.n_router), jnp.float32)
    params["item_embed"]["embedding"] = jnp.asarray(
        rng.standard_normal((N_ITEMS, spec.dim)), jnp.float32)
    return params


def as_reference(params):
    """The same arrays under the reference's names."""
    return {"embed": params["item_embed"]["embedding"],
            "head": params["head"], "final_norm": params["final_norm"],
            "layers": params["blocks"]}


def ref_dims(spec, held=None):
    f, w, e = spec.gqa, spec.gqa_window, spec.moe
    return {"D": spec.dim, "H": f.heads, "dk": f.head_dim, "dv": f.v_dim,
            "rot": f.rope_dims, "K_full": f.kv_heads, "K_window": w.kv_heads,
            "theta_full": f.rope_theta, "theta_window": w.rope_theta,
            "window": w.window, "value_scale": f.value_scale,
            "eps": spec.eps, "n_routed": e.n_routed, "top_k": e.top_k,
            "scale": e.scale, "norm_topk": e.norm_topk,
            "held": held or e.held, "pattern": PATTERN,
            "moe": tuple(int(b.ffn == "moe") for b in spec.blocks)}


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


# -- one mixer against hand-written einsums -----------------------------------

def by_hand(p, dims, x):
    """The issue's equations for one layer, every score materialised: q of
    ``heads`` heads, k and v of ``kv_heads``, RoPE on the leading
    ``rope_dims`` as pairs ``(i, i + rope_dims / 2)``, ``v`` scaled, the
    window's mask, the sink in the normaliser."""
    d, T = dims, x.shape[0]
    pos = np.arange(T)
    x = np.asarray(x, np.float64)
    w = {k: np.asarray(v, np.float64) for k, v in p.items()}

    def turned(a):
        rot = d.head_dim if d.rope_dims is None else d.rope_dims
        half = rot // 2
        ang = pos[:, None, None] * d.rope_theta ** (
            -np.arange(half) / half)[None, None]
        a1, a2 = a[..., :half], a[..., half:rot]
        return np.concatenate([a1 * np.cos(ang) - a2 * np.sin(ang),
                               a2 * np.cos(ang) + a1 * np.sin(ang),
                               a[..., rot:]], axis=-1)

    q = turned((x @ w["w_q"]).reshape(T, d.heads, d.head_dim))
    k = turned((x @ w["w_k"]).reshape(T, d.kv_heads, d.head_dim))
    v = d.value_scale * (x @ w["w_v"]).reshape(T, d.kv_heads, d.v_dim)
    k, v = (np.repeat(a, d.group, axis=1) for a in (k, v))
    s = np.einsum("thd,uhd->htu", q, k) / math.sqrt(d.head_dim)
    gap = pos[:, None] - pos[None, :]
    sees = (gap >= 0) & ((gap < d.window) if d.window else True)
    s = np.where(sees[None], s, -np.inf)
    m = s.max(axis=-1, keepdims=True)
    if d.sink:
        m = np.maximum(m, w["sink"][:, None, None])
    e = np.exp(s - m)
    norm = e.sum(axis=-1, keepdims=True)
    if d.sink:
        norm = norm + np.exp(w["sink"][:, None, None] - m)
    o = np.einsum("htu,uhd->thd", e / norm, v)
    return o.reshape(T, -1) @ w["w_o"]


VARIANTS = {
    "window_layer": WIN,
    "full_layer": FULL,
    "no_sink": dataclasses.replace(WIN, sink=False),
    "every_dim_turned": dataclasses.replace(WIN, rope_dims=None),
    "no_value_scale": dataclasses.replace(WIN, value_scale=1.0),
    "one_head_a_group": dataclasses.replace(WIN, kv_heads=8),
    "values_as_wide_as_keys": dataclasses.replace(FULL, v_head_dim=None),
}


@pytest.mark.parametrize("name", sorted(VARIANTS))
def test_the_plain_form_is_the_hand_written_einsum(name):
    dims = VARIANTS[name]
    p = gqa_ops.init(jax.random.PRNGKey(3), dims)
    assert ("sink" in p) == dims.sink
    assert p["w_k"].shape == (64, dims.kv_heads * 24)
    assert p["w_v"].shape == (64, dims.kv_heads * dims.v_dim)
    assert dims.cache_width == dims.kv_heads * (24 + dims.v_dim)
    x = normal(4, 37, 64)
    close(gqa_ops.attend_full(p, dims, x, jnp.arange(37, dtype=jnp.int32)),
          by_hand(p, dims, x))


def test_each_part_moves_the_answer():
    """Leaving out the sink, the partial RoPE, the value scale or the window
    is visible at these sizes (or the tests above would pass a program that
    ignored it)."""
    p = gqa_ops.init(jax.random.PRNGKey(3), WIN)
    x, pos = normal(4, 37, 64), jnp.arange(37, dtype=jnp.int32)
    base = np.asarray(gqa_ops.attend_full(p, WIN, x, pos))
    for change in ({"sink": False}, {"rope_dims": None},
                   {"value_scale": 1.0}, {"window": 0},
                   {"rope_theta": 1e7}):
        other = np.asarray(gqa_ops.attend_full(
            p, dataclasses.replace(WIN, **change), x, pos))
        assert np.abs(other - base).max() > 1e-2 * np.abs(base).max(), change


def test_the_reference_attention_is_the_plain_form(ref):
    spec = small_spec()
    dm = ref_dims(spec)
    x, pos = normal(5, 61, 64), jnp.arange(61, dtype=jnp.int32)
    for dims, window in ((FULL, False), (WIN, True)):
        p = gqa_ops.init(jax.random.PRNGKey(6), dims)
        with jax.default_matmul_precision("highest"):
            want = ref.attention(p, x, pos, dm, window)
        close(gqa_ops.attend_full(p, dims, x, pos), want)
        close(by_hand(p, dims, x), want)


# -- ring and span against the plain form -------------------------------------

def test_a_ring_is_the_window_and_a_chunk_in_whole_blocks():
    assert gqa_ops.ring_len(128, 512) == 640
    assert gqa_ops.ring_len(8, 16) == 24
    assert gqa_ops.ring_len(6, 8) == 18


def run_window(p, dims, x, sizes, ring, slot, C):
    """Chunks of ``sizes`` real positions through ``slot`` of ``ring``:
    ``(outputs of the real rows, ring, rounds a chunk)``."""
    outs, rounds, at = [], [], 0
    for n in sizes:
        chunk = jnp.zeros((C, 64), jnp.float32).at[:n].set(x[at:at + n])
        out, ring, walked = gqa_ops.window_prefill_chunk(
            p, dims, chunk, jnp.int32(n), jnp.int32(at), ring, slot)
        outs.append(out[:n])
        rounds.append(int(walked))
        at += n
    return jnp.concatenate(outs), ring, rounds


def test_window_chunks_and_extensions_through_a_ring_equal_the_plain_form():
    """Chunks of 16 into a ring of 24 rows that starts FULL OF NOISE: whole
    chunks, short ones (so that later chunks start at any row and wrap), a
    first chunk under the window's reach, then extensions of 3, 4 and 1
    positions across the ring's end beside a short session in the batch;
    each against ``attend_full``, and the walk never takes more than the
    bound whatever the reach."""
    p = gqa_ops.init(jax.random.PRNGKey(0), WIN)
    T, C = 150, 16
    x, pos = normal(1, T, 64), jnp.arange(T, dtype=jnp.int32)
    want = gqa_ops.attend_full(p, WIN, x, pos)
    R = gqa_ops.ring_len(WINDOW, C)
    ring = normal(2, 4, R, WIN.cache_width)
    sizes = [5, 16, 11, 16, 16, 3, 16, 16, 16, 16, 9]
    got, ring, rounds = run_window(p, WIN, x, sizes, ring, 1, C)
    at = sum(sizes)
    close(got, want[:at])
    bound = -(-(C + WINDOW - 1) // WINDOW) + 1
    assert max(rounds) <= bound == 4
    assert rounds[0] == 1               # a reach of 5: one block, not three
    # what a walk from 0 would take grows with the reach; the ring's does not
    assert -(-at // WINDOW) == 18
    # the slot holds the last R real positions, where they belong
    k, v = (np.asarray(a) for a in gqa_ops.project(p, WIN, x, pos)[1:])
    held = np.asarray(ring[1])
    for t in range(at - R, at):
        close(held[t % R], np.concatenate([k[t].ravel(), v[t].ravel()]),
              1e-6)
    short = normal(3, 9, 64)
    want_short = gqa_ops.attend_full(p, WIN, short, jnp.arange(9))
    _, ring, _ = run_window(p, WIN, short, [6], ring, 0, C)
    for n, m in ((3, 2), (4, 1), (1, 0)):
        rows = jnp.zeros((2, 4, 64), jnp.float32).at[0, :n].set(
            x[at:at + n])
        rows = rows.at[1, :m].set(short[6:6 + m] if n == 3 else short[8:9])
        at_short = 6 if n == 3 else 8
        ext_pos = jnp.array([[at + i for i in range(4)],
                             [at_short + i for i in range(4)]], jnp.int32)
        out, ring, walked = gqa_ops.window_extend(
            p, WIN, rows, jnp.array([n, m]), ext_pos, ring,
            jnp.array([1, 0 if m else 3]))
        close(out[0, :n], want[at:at + n])
        if m:
            close(out[1, :m], want_short[at_short:at_short + m])
        assert int(walked) <= -(-(4 + WINDOW - 1) // WINDOW) + 1 == 3
        at += n
    assert at == T - 2


def test_a_padded_row_writes_nothing_into_a_ring():
    """Only real positions are written: a chunk's and an extension's padding
    rows leave every row of the ring as it was, so a slot always holds its
    last ``ring`` real positions (the resume rule counts on it)."""
    p = gqa_ops.init(jax.random.PRNGKey(0), WIN)
    x = normal(1, 40, 64)
    R = gqa_ops.ring_len(WINDOW, 16)
    ring = normal(2, 3, R, WIN.cache_width)
    before = np.asarray(ring)
    chunk = jnp.zeros((16, 64), jnp.float32).at[:5].set(x[:5])
    _, ring, _ = gqa_ops.window_prefill_chunk(
        p, WIN, chunk, jnp.int32(5), jnp.int32(20), ring, 1)
    after = np.asarray(ring)
    rows = np.arange(20, 25) % R
    assert (after[1, rows] != before[1, rows]).all(axis=-1).all()
    untouched = np.setdiff1d(np.arange(R), rows)
    assert (after[1, untouched] == before[1, untouched]).all()
    assert (after[[0, 2]] == before[[0, 2]]).all()
    ext = jnp.zeros((2, 4, 64), jnp.float32).at[0, :2].set(x[5:7])
    pos = jnp.array([[22, 23, 24, 25], [0, 1, 2, 3]], jnp.int32)
    _, ring2, _ = gqa_ops.window_extend(
        p, WIN, ext, jnp.array([2, 0]), pos, ring, jnp.array([1, 2]))
    again = np.array(ring2)
    assert (again[1, [22, 23]] != after[1, [22, 23]]).all(axis=-1).all()
    again[1, [22, 23]] = after[1, [22, 23]]
    assert (again == after).all()


def test_full_chunks_and_extensions_through_a_span_equal_the_plain_form():
    """The full layers' path (the one the two accepted stacks run) at this
    model's shapes: keys of 24 beside values of 16, RoPE on 8 of the 24, the
    value scale, four query heads a key/value head."""
    p = gqa_ops.init(jax.random.PRNGKey(0), FULL)
    T = 50
    x, pos = normal(1, T, 64), jnp.arange(T, dtype=jnp.int32)
    want = gqa_ops.attend_full(p, FULL, x, pos)
    cache = normal(2, 3, 64, FULL.cache_width)
    outs, at = [], 0
    for n in (16, 16, 9):
        chunk = jnp.zeros((16, 64), jnp.float32).at[:n].set(x[at:at + n])
        out, cache = gqa_ops.prefill_chunk(p, FULL, chunk, at, cache, 1, 8)
        outs.append(out[:n])
        at += n
    close(jnp.concatenate(outs), want[:at])
    rows = jnp.zeros((2, 4, 64), jnp.float32).at[0].set(x[at:at + 4])
    ext_pos = jnp.array([[at + i for i in range(4)], [0, 1, 2, 3]],
                        jnp.int32)
    out, cache = gqa_ops.extend(p, FULL, rows, ext_pos, cache,
                                jnp.array([1, 2]), jnp.int32(6), 8)
    close(out[0], want[at:at + 4])


# -- the stack against the reference ------------------------------------------

@pytest.fixture(scope="module")
def served():
    spec = small_spec()
    params = seeded_params(spec)
    return spec, params, StackPrograms(spec, params, SHAPE)


def test_a_slot_holds_a_span_a_full_layer_and_a_ring_a_window_layer(served):
    spec, params, programs = served
    assert programs.kinds == ["gqa"] + ["gqa_window"] * 5 + ["gqa"]
    assert programs.windowed and not programs.indexed
    shapes = [c.shape for c in programs.cache]
    assert shapes == [(4, 112, FULL.cache_width)] + [
        (4, 24, WIN.cache_width)] * 5 + [(4, 112, FULL.cache_width)]
    # the window and the full layers lie under device scopes of their own
    text = programs._compiled["prefill"].as_text()
    assert "seq.layer1.gqa_window_a" in text and "seq.layer0.gqa_a" in text
    assert "seq.layer6.gqa_a" in text and "seq.layer6.gqa_window" not in text
    assert {"sink"} == set(params["blocks"][1]["mixer_a"]) - set(
        params["blocks"][0]["mixer_a"])


def logits_of(params, h):
    return np.asarray(h, np.float32) @ np.asarray(params["head"]).T


def test_prefill_then_extensions_equal_the_references_full_forward(
        served, ref):
    """A history of 75 through five chunks (the rings wrap three times),
    then extensions of 3, 1 and 4 positions, each answer's LOGITS against
    the reference's full forward over the history so far; and what the
    programs counted of their walks."""
    spec, params, programs = served
    programs.take_totals()
    weights, dm = as_reference(params), ref_dims(spec)
    rows = history(7, 83)
    at = 0
    while at < 75:
        n = min(16, 75 - at)
        h, _ = programs.prefill(rows[at:at + n], 1, at)
        at += n
    close(logits_of(params, h)[0], ref.forward(weights, rows[:75], dm)[0],
          5e-4)
    for n in (3, 1, 4):
        h, _ = programs.extend([(rows[at:at + n], 1, at)])
        at += n
        close(logits_of(params, h)[0],
              ref.forward(weights, rows[:at], dm)[0], 5e-4)
    totals = dict(zip(StackPrograms.TOTAL_KINDS,
                      np.asarray(programs.take_totals()).tolist()))
    f = StackPrograms.TOTAL_FIELDS.index
    pre, ext = totals["prefill"], totals["extend"]
    # chunks at 0, 16, 32, 48, 64 (11 real): blocks of 8 holding [first - 7,
    # last real]: 2, 3, 3, 3, 3 a window layer; from 0: 2, 4, 6, 8, 10
    assert pre[f("window_blocks")] == 5 * (2 + 3 + 3 + 3 + 3)
    assert pre[f("window_blocks_from0")] == 5 * (2 + 4 + 6 + 8 + 10)
    # the full layers walk chunks of 16 from 0: 1, 2, 3, 4, 5 each
    assert pre[f("full_blocks")] == 2 * (1 + 2 + 3 + 4 + 5)
    # extensions at 75 (3), 78 (1), 79 (4): [68, 77], [71, 78], [72, 82]
    assert ext[f("window_blocks")] == 5 * (2 + 2 + 2)
    assert ext[f("window_blocks_from0")] == 5 * (10 + 10 + 11)
    assert ext[f("full_blocks")] == 2 * (5 + 6 + 6)
    assert ext[f("window_blocks")] < ext[f("window_blocks_from0")]


def test_a_batch_of_extensions_equals_each_alone_and_counts_each(served, ref):
    spec, params, programs = served
    weights, dm = as_reference(params), ref_dims(spec)
    a, b = history(8, 40), history(9, 13)
    for slot, rows, n in ((0, a, 38), (2, b, 10)):
        for at in range(0, n, 16):
            programs.prefill(rows[at:min(at + 16, n)], slot, at)
    programs.take_totals()
    h, _ = programs.extend([(a[38:], 0, 38), (b[10:], 2, 10)])
    close(logits_of(params, h)[0], ref.forward(weights, a, dm)[0], 5e-4)
    close(logits_of(params, h)[1], ref.forward(weights, b, dm)[0], 5e-4)
    ext = np.asarray(programs.take_totals())[0]
    f = StackPrograms.TOTAL_FIELDS.index
    # [31, 39] and [3, 12]: 2 rounds each; both rows walk the longer one
    assert ext[f("window_blocks")] == 5 * 2 * 2
    assert ext[f("window_blocks_from0")] == 5 * (5 + 2)
    # a full layer's rows walk their own reach: 3 blocks of 16 and 1
    assert ext[f("full_blocks")] == 2 * (3 + 1)


def test_the_sixteen_shares_add_up_to_the_uncut_layer(ref):
    """Every chip of the deployment routes over all 32 experts and computes
    its two; the sixteen partial results add up to the reference's layer
    with all 32 held (there is no shared expert to count once)."""
    whole = dataclasses.replace(MOE, held=(0, 32))
    p = moe_ops.init(jax.random.PRNGKey(5), whole, bias_std=2e-3)
    spec = small_spec()
    for T in (23, 80):          # the streamed kernel, the grouped one
        x, valid = normal(T, T, 64), jnp.ones(T, bool)
        with jax.default_matmul_precision("highest"):
            want, _ = ref.moe_parts(p, x, ref_dims(spec, (0, 32)), (0, 32))
        total = 0.0
        for share in range(16):
            held = (2 * share, 2)
            dims = dataclasses.replace(MOE, held=held)
            mine = dict(p, **{k: p[k][held[0]:held[0] + 2]
                              for k in ("w_g", "w_u", "w_d")})
            y, counted = moe_ops.moe(mine, dims, x, valid)
            with jax.default_matmul_precision("highest"):
                part, _ = ref.moe_parts(mine, x, ref_dims(spec, held), held)
            close(y, part)
            total = total + y
        close(total, want)
    idx, gates = moe_ops.route(p, whole, x)
    np.testing.assert_allclose(np.asarray(gates).sum(axis=1), 1.0, rtol=1e-5)


# -- the ring's resume rule ----------------------------------------------------

def rows_of(n, seed=0):
    return np.random.default_rng(seed).integers(0, 1000, n).astype(np.int32)


def test_the_rings_rule_a_hit_inside_ring_minus_window_a_miss_past_it():
    ring, window = 24, 8
    cache = LatentCache(2, ring=ring, window=window)
    rows = rows_of(100)
    slot, cached = cache.acquire(rows)
    assert cached == 0
    cache.release(slot, rows)
    assert cache.floor[slot] == 100 - ring
    # grew: always a hit
    grown = np.concatenate([rows, rows_of(3, 1)])
    assert cache.acquire(grown) == (slot, 100)
    cache.release(slot, grown)
    assert cache.floor[slot] == 103 - ring == 79
    # went back a little: position p needs p - 7 .. p - 1 in the rings
    back = grown[:90]                          # resumes at 89: 82 >= 79
    assert cache.acquire(back) == (slot, 89)
    cache.release(slot, back)
    assert cache.floor[slot] == 79             # what was overwritten stays so
    assert cache.ring_misses == 0
    # past ring - window: the prefix rule would hit, the rings cannot
    far = grown[:86]                           # resumes at 85: 78 < 79
    hits, misses = cache.hit_tokens, cache.miss_tokens
    assert cache.acquire(far) == (slot, 0)     # the slot it had, from 0
    assert cache.ring_misses == 1 and cache.ring_miss_tokens == 85
    assert cache.hit_tokens == hits and cache.miss_tokens == misses + 86
    assert cache.evictions == 1
    cache.release(slot, far)
    assert cache.floor[slot] == 86 - ring      # written anew from 0
    # the edge: resuming at floor + window - 1 is the last hit
    edge = far[:86 - ring + window]            # resumes at 69: 62 >= 62
    assert cache.acquire(edge) == (slot, 86 - ring + window - 1)
    cache.release(slot, edge)
    # a history under the ring's length never pushed anything out
    other = rows_of(20, 2)
    s2, _ = cache.acquire(other)
    cache.release(s2, other)
    assert cache.floor[s2] == 0
    assert cache.acquire(other[:12]) == (s2, 11)


def test_a_cache_without_rings_keeps_the_prefix_rule():
    cache = LatentCache(1)
    rows = rows_of(100)
    slot, _ = cache.acquire(rows)
    cache.release(slot, rows)
    assert cache.acquire(rows[:60]) == (slot, 59)
    assert cache.ring_misses == 0 and cache.floor == [0]


def small_model(shape=SHAPE):
    spec = small_spec()
    items = BiMap.from_vocab(list(map("i%d".__mod__, range(N_ITEMS))))
    return SeqStackModel(spec, seeded_params(spec), items, shape), spec


def query(rows, num=5):
    return {"items": ["i%d" % r for r in rows], "num": num}


def test_sessions_through_the_model_resume_miss_and_share_slots(ref):
    """Through ``SeqStackModel`` (``plan_step``, ``LatentCache``, both
    programs): a session that grows, goes back a little (a hit, answered
    from the rings), goes back past the rings (a ring miss: every layer from
    position 0, in its slot), and a slot evicted and reused by another
    session, rings and spans together; every answer's scores against the
    reference's full forward."""
    model, spec = small_model(ServeShape(n_slots=2, capacity=96, chunk=16,
                                         extend_len=4, extend_batch=2))
    assert model.cache.ring == 24 and model.cache.window == WINDOW
    weights, dm = as_reference(model.params), ref_dims(spec)

    def check(rows):
        ticket = model.answer(query(rows))
        logits = ref.forward(weights, rows, dm)[0]
        got = ref.measure(logits, [(int(i[1:]), s) for i, s in ticket.result],
                          5)
        assert got is not None and max(got) < 1e-3, got
        return ticket

    a = history(11, 70)
    check(a[:60])
    stats = model.stats()
    assert (stats["hit_tokens"], stats["ring_misses"]) == (0, 0)
    t = check(a[:63])                          # grew: an extension
    assert t.extension and model.stats()["hit_tokens"] == 60
    assert model.stats()["extend_window_positions"] == 3 + WINDOW - 1
    t = check(a[:55])                          # back 8: 54 - 7 >= 63 - 24
    assert model.stats()["hit_tokens"] == 60 + 54
    check(a[:70])                              # on from the slot's 55
    assert model.stats()["hit_tokens"] == 60 + 54 + 55
    assert model.stats()["ring_misses"] == 0
    check(a[:50])                              # back 20: 49 - 7 < 70 - 24
    stats = model.stats()
    assert stats["ring_misses"] == 1 and stats["ring_miss_tokens"] == 49
    assert stats["evictions"] == 1
    # two more sessions: the second evicts the first's slot, rings and all
    b, c = history(12, 40), history(13, 45)
    check(b)
    check(c)
    assert model.stats()["evictions"] == 2
    check(np.concatenate([b, history(14, 2)]))   # b's slot survived: a hit
    assert model.stats()["hit_tokens"] == 60 + 54 + 55 + 40
    check(a[:50])                              # a's did not: from 0 again
    assert model.stats()["ring_misses"] == 1


# -- the two stacks that ran ops/gqa.py before ---------------------------------

SDAR = dict(dim=64, heads=8, kv_heads=2, head_dim=16, block_len=4,
            rope_theta=1e6, eps=1e-6)
GRANITE = dict(dim=64, heads=8, kv_heads=2, head_dim=8, block_len=1,
               rope=False, qk_norm=False, scale=0.0625)


@pytest.mark.parametrize("fields", [SDAR, GRANITE], ids=["sdar", "granite"])
def test_the_accepted_stacks_cached_paths_trace_to_the_parents_jaxpr(fields):
    """``prefill_chunk`` and ``block_step`` / ``extend`` under the dims of
    the block-diffusion stack and of the causal layer among recurrent ones,
    against their copies from the commit before (tests/parent_gqa.py): the
    same primitives in the same order, letter for letter, and the same
    bits."""
    new, old = gqa_ops.GQADims(**fields), parent_gqa.GQADims(**fields)
    p = gqa_ops.init(jax.random.PRNGKey(5), new)
    assert set(p) == set(parent_gqa.init(jax.random.PRNGKey(5), old))
    assert new.cache_width == old.cache_width
    S = new.block_len if new.block_len > 1 else 4
    x, cache = normal(5, 16, 64), normal(6, 3, 48, new.cache_width)

    def chunk(fn, dims):
        return lambda x, at, c: fn(p, dims, x, at, c, 1, 8)

    args = (x, jnp.int32(24), cache)
    assert str(jax.make_jaxpr(chunk(gqa_ops.prefill_chunk, new))(*args)) == \
        str(jax.make_jaxpr(chunk(parent_gqa.prefill_chunk, old))(*args))
    got, want = (jax.jit(chunk(f, d))(*args) for f, d in (
        (gqa_ops.prefill_chunk, new), (parent_gqa.prefill_chunk, old)))
    assert all((np.asarray(a) == np.asarray(b)).all()
               for a, b in zip(got, want))
    rows = normal(7, 2, S, 64)
    pos = jnp.array([[40 + i for i in range(S)], [4 + i for i in range(S)]],
                    jnp.int32)

    def step(fn, dims):
        return lambda x, pos, c: fn(p, dims, x, pos, c, jnp.array([1, 2]),
                                    jnp.int32(6), 8)

    args = (rows, pos, cache)
    assert str(jax.make_jaxpr(step(gqa_ops.block_step, new))(*args)) == str(
        jax.make_jaxpr(step(parent_gqa.block_step, old))(*args))
    got, want = (jax.jit(step(f, d))(*args) for f, d in (
        (gqa_ops.block_step, new), (parent_gqa.block_step, old)))
    assert all((np.asarray(a) == np.asarray(b)).all()
               for a, b in zip(got, want))
    if new.block_len == 1:
        # an extension is a program of its own (each row walks its own reach
        # in ``span_walk``), with the block step's numbers
        for a, b in zip(jax.jit(step(gqa_ops.extend, new))(*args), got):
            close(a, b, 1e-6)


def accepted_stack(name):
    moe = moe_ops.MoEDims(dim=64, expert_dim=32, n_routed=8, n_zero=0,
                          top_k=2, scale=1.0, held=(0, 4), norm_topk=True)
    if name == "sdar":
        block = BlockSpec(mixer="gqa", ffn="moe", norm="rmsnorm",
                          topology="pre_ln")
        return StackSpec(
            dim=64, ffn_dim=128, positions="rope", eps=1e-6, tied_head=False,
            gqa=gqa_ops.GQADims(**SDAR), moe=moe, blocks=(block,) * 2,
            generation=Generation(mask_row=N_ITEMS - 1, block_len=4))
    from predictionio_tpu.ops.ssm import SSMDims

    ssm = SSMDims(dim=64, heads=4, head_dim=16, d_state=8, d_conv=4,
                  chunk=8)
    blocks = tuple(BlockSpec(mixer=m, ffn="moe", norm="rmsnorm",
                             topology="pre_ln")
                   for m in ("mamba2", "gqa", "mamba2"))
    return StackSpec(dim=64, ffn_dim=128, positions="rope", eps=1e-5,
                     tied_head=True, gqa=gqa_ops.GQADims(**GRANITE), ssm=ssm,
                     moe=moe, blocks=blocks)


@pytest.mark.parametrize("name", ["sdar", "granite"])
def test_the_accepted_stacks_serve_programs_trace_to_the_parents_jaxpr(
        name, monkeypatch):
    """Every serve program of a block-diffusion stack and of a stack of
    recurrent mixers around a causal attention layer, traced whole with the
    tree's ``ops/gqa.py`` and ``attend_over_blocks`` and again with the
    parent's underneath: the same jaxpr, letter for letter. (The programs'
    own frame differs from the parent's by the three columns ``totals``
    gained, on both sides here.)"""
    spec = accepted_stack(name)
    params = init_stack(spec, jax.random.PRNGKey(1), N_ITEMS)
    shape = ServeShape(n_slots=2, capacity=32, chunk=8, extend_len=4,
                       extend_batch=2, gen_batch=2)

    def traced():
        programs = StackPrograms.__new__(StackPrograms)
        made = {}

        class Lowered:
            def __init__(self, fn):
                self.fn = fn

            def lower(self, *args):
                made[self.fn.__name__] = str(jax.make_jaxpr(self.fn)(*args))
                return self

            def compile(self):
                return None

        monkeypatch.setattr(stack_ops.jax, "jit",
                            lambda fn, **kw: Lowered(fn))
        from predictionio_tpu.obs import jaxmon
        monkeypatch.setattr(jaxmon, "record_scope_map", lambda program: None)
        StackPrograms.__init__(programs, spec, params, shape)
        monkeypatch.undo()
        return made

    def answers():
        """Two sessions prefilled, then extended in one batch."""
        programs = StackPrograms(spec, params, shape)
        programs.prefill(np.arange(1, 9), 0, 0)
        programs.prefill(np.arange(9, 15), 0, 8)
        programs.prefill(np.arange(20, 23), 1, 0)
        h, _ = programs.extend([(np.arange(30, 33), 0, 14),
                                (np.arange(40, 44), 1, 3)])
        return programs, np.asarray(h)

    tree = traced()
    assert set(tree) == ({"_prefill_fn", "_block_fn"} if name == "sdar"
                         else {"_prefill_fn", "_extend_fn"})
    mine = None if name == "sdar" else answers()[1]
    # (PR 53: the tree's take the block's place in the stack and its scope
    # behind the parent's arguments: 7 and 8 of them)
    for fn, n in (("prefill_chunk", 7), ("block_step", 8)):
        monkeypatch.setattr(gqa_ops, fn, lambda *a, _f=getattr(
            parent_gqa, fn), _n=n: _f(*a[:_n]))
    # the parent's extension is its block step: one count for the batch,
    # every row as far as the longest
    monkeypatch.setattr(gqa_ops, "extend", lambda *a: parent_gqa.extend(
        *a[:6], jnp.max(a[6]), a[7]))
    parents = traced()
    # an extension's walk is the ``span_walk`` kernel (PR 56); every other
    # program is the parent's, letter for letter
    walk = tree.pop("_extend_fn", ""), parents.pop("_extend_fn", "")
    assert tree == parents
    assert not any("gqa_window" in text for text in tree.values())
    if name == "granite":
        assert "span_walk" in walk[0] and "span_walk" not in walk[1]
        # and an extension batch's answers are the parent walk's
        close(mine, answers()[1], 1e-5)
    # no column of the walks moves in a stack without window layers
    monkeypatch.undo()
    programs = StackPrograms(spec, params, shape)
    assert not programs.windowed
    programs.prefill(np.arange(1, 9), 0, 0)
    f = StackPrograms.TOTAL_FIELDS.index
    assert not np.asarray(programs.totals)[:, [
        f("window_blocks"), f("window_blocks_from0"), f("full_blocks")]].any()


def test_attend_over_blocks_defaults_are_the_parents():
    q, k = normal(1, 2, 6, 2, 8), normal(2, 2, 40, 2, 8)
    v = normal(3, 2, 40, 2, 4)
    pos = jnp.array([[30, 31, 32, 33, 34, 35]] * 2, jnp.int32)

    def walk(fn):
        return lambda q, k, v: fn(
            q, pos, lambda j: (jax.lax.dynamic_slice_in_dim(k, j * 8, 8, 1),
                               jax.lax.dynamic_slice_in_dim(v, j * 8, 8, 1)),
            jnp.int32(5), 8, 4)

    assert str(jax.make_jaxpr(walk(attention_ops.attend_over_blocks))(
        q, k, v)) == str(jax.make_jaxpr(walk(
            parent_gqa.attend_over_blocks))(q, k, v))
