"""A latent-attention stack whose every mixer carries a LEARNED INDEX (an
index key cached beside every latent, each query row attending only its
``index_topk`` best cached positions), one dense block then expert blocks
under a sigmoid router with a selection bias, against the plain reference
(benchmarks/reference/glm5_forward.py, which imports nothing of the program),
at a small size on the CPU: hidden 64, 4 heads of 24 + 16 / 32, 4 index heads
of 32, 12 positions a row, histories of 140-150, seeded float32 weights."""

import dataclasses
import importlib.util
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.models.sessionrec import SeqStackModel
from predictionio_tpu.ops import mla as mla_ops
from predictionio_tpu.ops import moe as moe_ops
from predictionio_tpu.ops.sessionrec import (
    BlockSpec, ServeShape, StackPrograms, StackSpec, init_stack)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def ref():
    path = os.path.join(REPO, "benchmarks", "reference", "glm5_forward.py")
    spec = importlib.util.spec_from_file_location("glm5_forward_ref", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def ablation():
    path = os.path.join(REPO, "benchmarks", "tools", "glm_ablation.py")
    spec = importlib.util.spec_from_file_location("glm_ablation_tool", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


TOPK = 12
MLA = mla_ops.MLADims(
    dim=64, heads=4, d_nope=24, d_rope=16, d_v=32, q_rank=32, kv_rank=24,
    rope_theta=1e6, eps=1e-5, scale_q=False, scale_kv=False, index_heads=4,
    index_dim=32, index_topk=TOPK)
PLAIN = dataclasses.replace(MLA, index_heads=0, index_dim=0, index_topk=0)
MOE = moe_ops.MoEDims(
    dim=64, expert_dim=32, n_routed=16, n_zero=0, top_k=4, scale=2.5,
    held=(0, 2), norm_topk=True, shared_dim=32, scoring="sigmoid")
N_ITEMS = 50


def small_spec(mla=MLA, moe=MOE):
    def block(ffn):
        return BlockSpec(mixer="mla", ffn=ffn, norm="rmsnorm",
                         topology="pre_ln")

    return StackSpec(dim=64, ffn_dim=128, positions="rope", eps=1e-5,
                     tied_head=False, mla=mla, moe=moe,
                     blocks=(block("swiglu"), block("moe"), block("moe")))


def seeded_params(spec, seed=0):
    """init_stack's weights with every norm (the index's LayerNorm and its
    bias among them) made non-trivial, so that a part that skipped one would
    show, and a small selection bias in the router."""
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
                2e-3 * rng.standard_normal(MOE.n_router), jnp.float32)
    params["item_embed"]["embedding"] = jnp.asarray(
        rng.standard_normal((N_ITEMS, spec.dim)), jnp.float32)
    return params


def as_reference(params):
    """The same arrays under the reference's names."""
    return {"embed": params["item_embed"]["embedding"],
            "head": params["head"], "final_norm": params["final_norm"],
            "layers": params["blocks"]}


def ref_dims(spec):
    m, e = spec.mla, spec.moe
    return {"D": spec.dim, "H": m.heads, "dn": m.d_nope, "dr": m.d_rope,
            "dv": m.d_v, "rq": m.q_rank, "rkv": m.kv_rank,
            "theta": m.rope_theta, "eps": spec.eps, "Hi": m.index_heads,
            "di": m.index_dim, "topk": m.index_topk, "eps_i": m.index_eps,
            "n_routed": e.n_routed, "top_k": e.top_k, "scale": e.scale,
            "held": e.held, "first_dense": 1}


def close(a, b, tol=2e-4):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    scale = max(float(np.abs(b).max()), 1e-6)
    assert float(np.abs(a - b).max()) <= tol * scale, (
        float(np.abs(a - b).max()), scale)


def normal(seed, *shape):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape),
                       jnp.float32)


def mixer(seed=0, mla=MLA):
    return seeded_params(small_spec(mla), seed)["blocks"][0]["mixer_a"]


def empty_cache(slots, positions):
    return mla_ops.init_cache(MLA, slots, positions, jnp.float32)


# -- the selection ------------------------------------------------------------

@pytest.mark.parametrize("k", [1, 5, 12, 29, 35])
def test_topk_mask_selects_what_lax_top_k_selects_ties_included(k):
    """Scores on a coarse grid (ties everywhere, some straddling the cut),
    rows whose reach is shorter than ``k`` (the rest ``-inf``), a row of
    equal scores, signed zeros."""
    rng = np.random.default_rng(k)
    scores = np.round(rng.standard_normal((9, 40)) * 2) / 2
    scores[:, 30:] = -np.inf
    scores[1, 3:] = -np.inf
    scores[2, :30] = 0.25
    scores[3, :10] = -0.0
    scores = jnp.asarray(scores, jnp.float32)
    idx = np.asarray(jax.lax.top_k(scores, k)[1])
    want = np.zeros(scores.shape, bool)
    want[np.arange(9)[:, None], idx] = True
    assert (np.asarray(mla_ops.topk_mask(scores, k)) == want).all()


def test_the_programs_sets_are_the_references_position_for_position(ref):
    """The plain form's sets, and the sets the cached path finds from the
    index keys it wrote (scores block by block, the bisection), against the
    reference's ``jax.lax.top_k`` over the whole row."""
    p = mixer()
    T = 150
    x, pos = normal(1, T, 64), jnp.arange(T, dtype=jnp.int32)
    dm = ref_dims(small_spec())
    want_out, want_sets = ref.mla(p, x, pos, dm, with_sets=True)
    want_sets = np.asarray(want_sets)
    assert (want_sets.sum(axis=1) == np.minimum(np.arange(T) + 1,
                                                 TOPK)).all()
    # the index binds: it is not the last 12, and it reaches far back
    recent = np.tril(np.ones((T, T), bool)) & ~np.tril(
        np.ones((T, T), bool), -TOPK)
    assert (want_sets != recent).any(axis=1).sum() > 100
    assert want_sets[-1, :T // 2].any()
    cq = mla_ops.compress_q(p, MLA, x)
    qi, ki, w = mla_ops.project_index(p, MLA, x, cq, pos)
    plain = mla_ops.selected_full(MLA, mla_ops.index_scores(qi, w, ki), pos)
    assert (np.asarray(plain) == want_sets).all()
    # through the cache: chunks of 16 into a slot, then each chunk's rows
    # scored against the slot's keys up to the chunk's end
    cache = empty_cache(2, 160)
    for at in range(0, T, 16):
        n = min(16, T - at)
        chunk = jnp.zeros((16, 64), jnp.float32).at[:n].set(x[at:at + n])
        _, cache, _ = mla_ops.prefill_chunk(
            p, MLA, chunk, at, cache, 1, 8)
    keys = cache["index_k"][1, :T]
    close(keys, ki, 1e-6)
    for at in (0, 64, 144):
        rows = slice(at, min(at + 16, T))
        scores = jnp.where(
            pos[rows, None] >= pos[None, :],
            mla_ops.index_scores(qi[rows], w[rows], keys), -jnp.inf)
        got = np.asarray(mla_ops.topk_mask(scores, TOPK)) & np.asarray(
            pos[rows, None] >= pos[None, :])
        assert (got == want_sets[rows]).all(), at


def test_chunks_and_extensions_through_the_cache_equal_the_plain_form(ref):
    """Prefill across nine chunk boundaries (blocks of 8, the first chunk
    starting mid-block) under each row's mask, then extensions of 4, 4 and 2
    positions walking the slot's latents under each row's mask, beside a
    short session in the same batch: ``attend_full`` with the index and the
    reference, row for row."""
    dims = MLA
    p = mixer()
    T = 150
    x, pos = normal(1, T, 64), jnp.arange(T, dtype=jnp.int32)
    want = ref.mla(p, x, pos, ref_dims(small_spec()))
    close(mla_ops.attend_full(p, dims, x[None], pos[None])[0], want)
    cache = empty_cache(3, 176)
    outs, scanned, at = [], [], 0
    while at < 140:
        n = min(12 if at == 0 else 16, 140 - at)
        chunk = jnp.zeros((16, 64), jnp.float32).at[:n].set(x[at:at + n])
        out, cache, blocks = mla_ops.prefill_chunk(
            p, dims, chunk, at, cache, 1, 8)
        outs.append(out[:n])
        scanned.append(int(blocks))
        at += n
    close(jnp.concatenate(outs), want[:140])
    # every chunk reaches past 12 positions, so every one is scored, as far
    # as its own end
    assert scanned == [2, 4, 6, 8, 10, 12, 14, 16, 18]
    short = normal(2, 9, 64)
    want_short = ref.mla(p, short, jnp.arange(9), ref_dims(small_spec()))
    chunk = jnp.zeros((16, 64), jnp.float32).at[:7].set(short[:7])
    _, cache, blocks = mla_ops.prefill_chunk(
        p, dims, chunk, 0, cache, 0, 8)
    assert int(blocks) == 2                  # 16 rows reach past 12
    for n in (4, 4, 2):
        rows = jnp.zeros((2, 4, 64), jnp.float32).at[0, :n].set(
            x[at:at + n])
        both = at == 140
        if both:
            rows = rows.at[1, :2].set(short[7:])
        ext_pos = jnp.array([[at + i for i in range(4)],
                             [7 + i for i in range(4)]], jnp.int32)
        out, cache, blocks = mla_ops.extend(
            p, dims, rows, ext_pos, cache,
            jnp.array([1, 0 if both else 2]), jnp.int32(-(-(at + 4) // 8)),
            8)
        close(out[0, :n], want[at:at + n])
        if both:
            close(out[1, :2], want_short[7:])
        assert int(blocks) == -(-(at + 4) // 8)
        at += n


SCRATCH = 3        # the slot a batch's padding sessions write to

#: an extension batch through a cache of four slots of 176 positions (22
#: blocks of 8): ``(each session's (slot, first new position, new
#: positions), positions of the slot that hold ONE index key)``
EXTENSIONS = {
    "reach_within_the_set": ([(1, 5, 4), (2, 3, 4)], None),
    "reach_one_past_the_set": ([(1, 9, 4), (2, 8, 4)], None),
    "reach_over_many_blocks": ([(1, 150, 4), (2, 97, 4)], None),
    "a_tie_straddling_the_cut": ([(1, 100, 4), (2, 60, 4)], (3, 100)),
    "one_real_session_of_four": ([(2, 120, 4)] + [(SCRATCH, 0, 0)] * 3, None),
    "one_new_position_of_four": ([(1, 77, 1), (2, 130, 1)], None),
    "reaches_far_apart": ([(1, 6, 4), (2, 160, 4)], None),
}


def plain_extension(p, x, pos, cache, slots):
    """Each row alone, the plain form: its scores over its WHOLE reach from
    the slot's index keys, ``jax.lax.top_k``, and one softmax over the keys
    and values expanded from the latents of that set. ``(out [B, S, dim],
    sets [B, S, P])``."""
    B, S, _ = x.shape
    cq = mla_ops.compress_q(p, MLA, x)
    qi, _, w = mla_ops.project_index(p, MLA, x, cq, pos)
    qn, qr, _ = mla_ops.project(p, MLA, x, pos, cq)
    q = jnp.concatenate([qn, qr], axis=-1)
    out = np.zeros(x.shape, np.float32)
    sets = np.zeros((B, S, cache["latent"].shape[1]), bool)
    for b in range(B):
        for i in range(S):
            reach = int(pos[b, i]) + 1
            row = mla_ops.index_scores(
                qi[b, i][None], w[b, i][None],
                cache["index_k"][slots[b], :reach])[0]
            idx = jax.lax.top_k(row, min(TOPK, reach))[1]
            sets[b, i, np.asarray(idx)] = True
            k, v = mla_ops.expand(p, MLA, cache["latent"][slots[b], idx])
            prob = jax.nn.softmax(jnp.einsum(
                "hd,khd->hk", q[b, i], k) * MLA.softmax_scale, axis=-1)
            out[b, i] = mla_ops._out(
                p, MLA, jnp.einsum("hk,khd->hd", prob, v))
    return out, sets


@pytest.mark.parametrize("steps", ["one_step_a_slot", "steps_of_two_blocks"])
@pytest.mark.parametrize("case", sorted(EXTENSIONS))
def test_an_extensions_sets_and_outputs_are_the_plain_forms(
        case, steps, monkeypatch):
    """What the hazards name: a reach within the set (all in reach, whatever
    fills the set up is past it), one position past it, many steps of the
    walk, ties across the cut (the earlier position), padding sessions and
    padding positions (every row must keep some key), and two reaches far
    apart in one batch (each row's mask inside ITS slot and ITS reach)."""
    sessions, tied = EXTENSIONS[case]
    B = len(sessions)
    rows = B * 4 * MLA.heads
    if steps == "steps_of_two_blocks":  # float32 scores of 16 positions
        monkeypatch.setattr(mla_ops, "_WALK_SCORE_BYTES", 4 * rows * 16)
    wide = mla_ops._walk_block(176, 8, rows)
    assert wide == (176 if steps == "one_step_a_slot" else 16)
    p = mixer()
    cache = {"latent": normal(21, 4, 176, MLA.latent),
             "index_k": normal(22, 4, 176, MLA.index_dim)}
    if tied:        # all of a slot's keys but a few are one key
        cache["index_k"] = cache["index_k"].at[1:3, tied[0]:tied[1]].set(
            cache["index_k"][1, tied[0]])
    slots = jnp.array([s for s, _, _ in sessions], jnp.int32)
    pos = jnp.array([[at + i for i in range(4)] for _, at, _ in sessions],
                    jnp.int32)
    x = normal(23, B, 4, 64)
    longest = max(at + 4 for _, at, _ in sessions)
    out, after, blocks = mla_ops.extend(
        p, MLA, x, pos, cache, slots, jnp.int32(-(-longest // 8)), 8)
    assert int(blocks) == -(-longest // 8)
    # the new positions' latents and keys are written where they belong
    cq = mla_ops.compress_q(p, MLA, x)
    qi, ki, w = mla_ops.project_index(p, MLA, x, cq, pos)
    latent = mla_ops.project(p, MLA, x, pos, cq)[2]
    real = [b for b, (slot, _, _) in enumerate(sessions) if slot != SCRATCH]
    for b in real:
        at = sessions[b][1]
        close(after["index_k"][slots[b], at:at + 4], ki[b], 1e-6)
        close(after["latent"][slots[b], at:at + 4], latent[b], 1e-6)
    want, want_sets = plain_extension(p, x, pos, after, slots)
    got_sets = np.asarray(mla_ops.extension_sets(
        MLA, qi, w, after["index_k"], slots, pos, -(-longest // wide), wide))
    in_reach = np.asarray(pos)[..., None] >= np.arange(176)
    for b in real:
        assert (got_sets[b] & in_reach[b] == want_sets[b]).all(), b
        assert (want_sets[b].sum(axis=1)
                == np.minimum(np.asarray(pos[b]) + 1, TOPK)).all()
        close(out[b], want[b])
    if tied:        # the cut falls among the tied positions of every row
        tied_kept = want_sets[0, :, tied[0]:tied[1]].sum(axis=1)
        assert ((tied_kept > 0) & (tied_kept < tied[1] - tied[0])).all()
        first = want_sets[0, 0, tied[0]:tied[1]]
        assert first[:int(first.sum())].all()       # the earliest of them


def test_an_extensions_loops_step_as_wide_as_the_slot_divides():
    """The cell's 66 blocks of 512 against 16 rows x 64 heads: three blocks
    a step; a slot of a prime number of blocks, or rows whose scores against
    one block already pass the budget: a block a step."""
    assert mla_ops._walk_block(33792, 512, 16 * 64) == 1536
    assert mla_ops._walk_block(13 * 16, 16, 32) == 13 * 16
    assert mla_ops._walk_block(13 * 512, 512, 16 * 64) == 512
    assert mla_ops._walk_block(33792, 512, 1 << 20) == 512


def test_a_chunk_whose_reach_is_within_the_set_scores_nothing_and_still_writes_its_keys(ref):
    p = mixer()
    x = normal(3, 8, 64)
    cache = empty_cache(2, 32)
    out, cache, blocks = mla_ops.prefill_chunk(
        p, MLA, x, 0, cache, 1, 8)
    assert int(blocks) == 0
    want = ref.mla(p, x, jnp.arange(8), ref_dims(small_spec()))
    close(out, want)
    cq = mla_ops.compress_q(p, MLA, x)
    ki = mla_ops.project_index(p, MLA, x, cq, jnp.arange(8))[1]
    close(cache["index_k"][1, :8], ki, 1e-6)
    assert not np.asarray(cache["index_k"][0]).any()


def test_an_index_needs_its_sizes_and_whole_blocks():
    with pytest.raises(ValueError):
        dataclasses.replace(MLA, index_topk=0)
    with pytest.raises(ValueError):
        dataclasses.replace(MLA, index_dim=8)
    with pytest.raises(ValueError):
        mla_ops.prefill_chunk(
            mixer(), MLA, normal(4, 16, 64), 0, empty_cache(2, 20), 0, 8)


# -- one cached path of each kind, the index inside ----------------------------

@pytest.mark.parametrize("path", ["chunk", "extension"])
@pytest.mark.parametrize("dims", [PLAIN, MLA], ids=["no_index", "index"])
def test_a_cached_path_gives_the_plain_forms_numbers_and_writes_its_rows(
        dims, path):
    """``ops/mla.prefill_chunk`` and ``extend`` over what ``init_cache``
    gives their mixer (a bare array, or two under an index): 40 positions in
    chunks of 16, or 36 and then an extension of 4 beside a padding session,
    against ``attend_full``; every latent (and index key) where it belongs,
    the next slot untouched, no block of index keys scanned without one."""
    p = mixer(mla=dims)
    T = 40
    x, pos = normal(5, T, 64), jnp.arange(T, dtype=jnp.int32)
    want = mla_ops.attend_full(p, dims, x[None], pos[None])[0]
    cache = mla_ops.init_cache(dims, 4, 48, jnp.float32)
    assert isinstance(cache, dict if dims.has_index else jax.Array)
    chunked = T if path == "chunk" else T - 4
    outs, scanned = [], []
    for at in range(0, chunked, 16):
        n = min(16, chunked - at)
        chunk = jnp.zeros((16, 64), jnp.float32).at[:n].set(x[at:at + n])
        out, cache, blocks = mla_ops.prefill_chunk(
            p, dims, chunk, at, cache, 1, 8)
        outs.append(out[:n])
        scanned.append(int(blocks))
    if path == "extension":
        rows = jnp.zeros((2, 4, 64), jnp.float32).at[0].set(x[chunked:])
        ext_pos = jnp.array([[chunked + i for i in range(4)], [0, 1, 2, 3]],
                            jnp.int32)
        out, cache, blocks = mla_ops.extend(
            p, dims, rows, ext_pos, cache, jnp.array([1, SCRATCH]),
            jnp.int32(5), 8)
        outs.append(out[0])
        scanned.append(int(blocks))
    close(jnp.concatenate(outs), want)
    # every chunk reaches past 12 positions: scored as far as its own end
    assert scanned == ([2, 4, 6, 5][:len(scanned)] if dims.has_index
                       else [0] * len(scanned))
    cq = mla_ops.compress_q(p, dims, x)
    latents = cache["latent"] if dims.has_index else cache
    assert latents.shape == (4, 48, 128)        # 40 values, whole lane rows
    close(latents[1, :T, :dims.latent],
          mla_ops.project(p, dims, x, pos, cq)[2], 1e-6)
    assert not np.asarray(latents[1, :T, dims.latent:]).any()
    assert not np.asarray(latents[0]).any()
    if dims.has_index:
        close(cache["index_k"][1, :T],
              mla_ops.project_index(p, dims, x, cq, pos)[1], 1e-6)
        assert not np.asarray(cache["index_k"][0]).any()


def test_a_stack_without_an_index_compiles_the_programs_it_had():
    """One plain array a mixer, no index column moves, no index scope in
    either program, and the run's counters as they were."""
    spec = small_spec(mla=PLAIN)
    programs = StackPrograms(spec, seeded_params(spec), SHAPE)
    assert not programs.indexed
    assert all(isinstance(c, jax.Array) for c in programs.cache)
    for program in programs._compiled.values():
        text = program.as_text()
        assert ".index" not in text and ".select" not in text
    h, counted = programs.prefill(np.arange(1, 17), 0, 0)
    assert set(counted) == {"tokens", "expert_load", "zero_picks"}
    programs.extend([(np.array([3, 4]), 0, 16)])
    totals = np.asarray(programs.totals)
    fields = StackPrograms.TOTAL_FIELDS
    assert not totals[:, [fields.index("index_blocks"),
                          fields.index("index_sparse_rows")]].any()
    # the parent's rule for the runs an int32 holds
    assert programs.drain_every == (2 ** 31 - 1) // (3 * (16 * 4 + 2))


# -- the router and the shares ------------------------------------------------

def test_the_expert_layer_matches_the_reference_and_the_bias_only_chooses(ref):
    dims = dataclasses.replace(MOE, held=(4, 4))
    p = moe_ops.init(jax.random.PRNGKey(5), dims, bias_std=0.05)
    for T in (29, 80):          # the streamed kernel, the grouped one
        x, valid = normal(T, T, 64), jnp.ones(T, bool)
        routed, shared, _ = ref.moe_parts(p, x, ref_dims(small_spec()),
                                          (4, 4))
        y, _ = moe_ops.moe(p, dims, x, valid)
        close(y, routed + shared)
    idx, gates = moe_ops.route(p, dims, x)
    np.testing.assert_allclose(np.asarray(gates).sum(axis=1), 2.5, rtol=1e-5)
    unbiased = moe_ops.route(dict(p, bias=jnp.zeros_like(p["bias"])), dims,
                             x)[0]
    assert (np.sort(np.asarray(idx), axis=1)
            != np.sort(np.asarray(unbiased), axis=1)).any()


def test_the_sixteen_ways_a_layer_is_shared_add_up_to_the_uncut_layer(ref):
    """Sixteen shares of one expert each (the deployment's 16 shares of 16),
    the shared expert counted once: the whole layer, in the program and in
    the reference alike."""
    whole = dataclasses.replace(MOE, held=(0, 16))
    p = moe_ops.init(jax.random.PRNGKey(6), whole, bias_std=2e-3)
    x, valid = normal(6, 29, 64), jnp.ones(29, bool)
    dm = ref_dims(small_spec())
    uncut_routed, uncut_shared, _ = ref.moe_parts(p, x, dm, (0, 16))
    idx, gates = moe_ops.route(p, whole, x)
    total, picks = 0.0, 0
    for e0 in range(16):
        share = dataclasses.replace(MOE, held=(e0, 1))
        ps = dict(p, **{k: p[k][e0:e0 + 1] for k in ("w_g", "w_u", "w_d")})
        routed, _ = moe_ops.experts_streamed(ps, share, x, idx, gates, valid)
        ref_routed, ref_shared, _ = ref.moe_parts(ps, x, dm, (e0, 1))
        close(routed, ref_routed)
        close(ref_shared, uncut_shared)
        y, counted = moe_ops.moe(ps, share, x, valid)
        close(y, ref_routed + uncut_shared)
        total = total + routed
        picks += int(counted["expert_load"].sum())
    close(total, uncut_routed)
    close(moe_ops.moe(p, whole, x, valid)[0], uncut_routed + uncut_shared)
    assert picks == 29 * 4          # every pick lands in exactly one share


def test_an_answer_from_the_other_side_of_an_open_cut_is_held_to_that_side(
        ref, monkeypatch):
    """The comparison's both sides: where the router's cut at an answer's
    last position is a near tie with a held expert at it, an answer computed
    on the OTHER side is compared with that side; a cut that is no near tie
    is not crossed. (Every cut is made 'near' here.)"""
    spec = small_spec()
    weights, dm = as_reference(seeded_params(spec)), ref_dims(spec)
    monkeypatch.setattr(ref, "CUT_TOL", 0.05)
    rng = np.random.default_rng(11)
    for _ in range(20):
        hist = rng.integers(0, N_ITEMS, size=40).tolist()
        own, _, cuts = ref._forward(weights, hist, dm)
        other = {i: ref.sides(*cut, 4, dm["held"])
                 for i, cut in cuts.items()}
        if any(other.values()):
            break
    assert set(cuts) == {1, 2}
    layer = max(i for i, found in other.items() if found)
    picks = other[layer][0]
    ranked, order = cuts[layer]
    # another side: four distinct picks, the sure ones among them, and
    # another set of HELD experts (0 and 1 here) than the reference's own
    assert len(set(picks.tolist())) == 4
    assert ({e for e in picks.tolist() if e < 2}
            != {e for e in order[:4].tolist() if e < 2})
    there = ref._forward(weights, hist, dm, None, {layer: picks})[0]
    answer = ref.top_k_answer(there, 5)
    apart = ref.measure(own, answer, 5)
    assert apart[0] > 1e-3                  # a whole gated expert's output
    got = ref.compare(weights, [(hist, answer)], 5, dm)
    assert got["open_cuts"] == got["crossed"] == 1
    assert got["score_err"] < 1e-6 and got["rank_gap"] == 0.0
    # the reference's own answer stays on its own side
    got = ref.compare(weights, [(hist, ref.top_k_answer(own, 5))], 5, dm)
    assert (got["open_cuts"], got["crossed"]) == (1, 0)
    assert got["score_err"] < 1e-6
    # no near tie: nothing open, and the answer reads as far off as it is
    monkeypatch.setattr(ref, "CUT_TOL", 1e-9)
    got = ref.compare(weights, [(hist, answer)], 5, dm)
    assert (got["open_cuts"], got["crossed"]) == (0, 0)
    assert got["score_err"] == pytest.approx(apart[0])


def test_the_sides_of_a_cut_are_counted_by_the_held_experts_they_keep(
        ref, monkeypatch):
    """``sides`` on hand-made rankings (top-4 of experts 0-15, experts 0 and
    1 held, a tolerance of 1%): picks in ``order``'s places 0-3, the cut
    after them."""
    monkeypatch.setattr(ref, "CUT_TOL", 0.01)
    ranked = np.array([0.9, 0.8, 0.7, 0.6, 0.5, 0.4, 0.3])

    def sides(ranked, order):
        return [sorted(p.tolist()) for p in ref.sides(
            np.asarray(ranked), np.asarray(order), 4, (0, 2))]

    # no near tie: nothing, whoever sits at the cut
    assert sides(ranked, [5, 6, 7, 0, 1, 8, 9]) == []
    tie = ranked.copy()
    tie[4] = 0.5995                         # the 5th within 1% of the 4th
    # a near tie between two absent experts moves nothing held
    assert sides(tie, [5, 6, 7, 8, 9, 0, 1]) == []
    # the held expert just inside may drop out, the one just outside come in
    assert sides(tie, [5, 6, 7, 0, 9, 8, 1]) == [[5, 6, 7, 9]]
    assert sides(tie, [5, 6, 7, 9, 0, 8, 1]) == [[0, 5, 6, 7]]
    # both held at the cut: each alone (the reference's own side keeps 0)
    assert sides(tie, [5, 6, 7, 0, 1, 8, 9]) == [[1, 5, 6, 7]]
    # three within the tolerance: two picks may drop, one expert come in
    three = tie.copy()
    three[2] = 0.6005
    assert sides(three, [5, 6, 0, 7, 8, 9, 10]) == [[5, 6, 7, 8]]
    assert sides(three, [5, 6, 0, 1, 8, 9, 10]) == [
        [0, 5, 6, 8], [1, 5, 6, 8]]
    assert sides(three, [5, 6, 7, 8, 0, 9, 10]) == [[0, 5, 6, 7]]


# -- the stack ----------------------------------------------------------------

SHAPE = ServeShape(n_slots=3, capacity=192, chunk=16, extend_len=4,
                   extend_batch=2)


def scores(params, h_last):
    return np.asarray(h_last) @ np.asarray(params["head"]).T


def test_an_indexed_stack_gives_the_reference_scores_and_counts_its_scans(
        ref):
    """Scores, not ranks: a history of 140 prefilled in chunks and grown by
    extensions through the two arrays a mixer keeps, against the full
    forward over it."""
    spec = small_spec()
    params = seeded_params(spec)
    programs = StackPrograms(spec, params, SHAPE)
    assert programs.indexed
    assert [set(c) for c in programs.cache] == [{"latent", "index_k"}] * 3
    assert programs.cache[0]["index_k"].shape == (4, 208, 32)
    weights, dm = as_reference(params), ref_dims(spec)
    rng = np.random.default_rng(7)
    hist = rng.integers(0, N_ITEMS, size=140).tolist()
    at = 0
    while at < 133:
        n = min(16, 133 - at)
        h, counted = programs.prefill(np.array(hist[at:at + n]), 1, at)
        at += n
    close(scores(params, h[0]), ref.forward(weights, hist[:133], dm)[0], 5e-4)
    # the last chunk: 5 real rows at 128-132, every one past 12 positions;
    # three mixers each scanned the 9 blocks up to the chunk's end
    assert int(counted["tokens"]) == 5
    assert int(counted["index_sparse_rows"]) == 5
    assert int(counted["index_blocks"]) == 3 * 9
    other = rng.integers(0, N_ITEMS, size=9).tolist()
    _, counted = programs.prefill(np.array(other[:7]), 0, 0)
    # 7 real rows, none past 12 positions; the chunk's 16 rows reach 16
    assert int(counted["index_sparse_rows"]) == 0
    assert int(counted["index_blocks"]) == 3 * 1
    h, counted = programs.extend([(hist[133:136], 1, 133),
                                  (other[7:9], 0, 7)])
    close(scores(params, h[0]), ref.forward(weights, hist[:136], dm)[0], 5e-4)
    close(scores(params, h[1]), ref.forward(weights, other, dm)[0], 5e-4)
    # two real sessions, each scanning as far as the batch's longest reach
    assert int(counted["index_blocks"]) == 3 * 2 * 9
    assert int(counted["index_sparse_rows"]) == 3
    h, _ = programs.extend([(hist[136:140], 1, 136)])
    close(scores(params, h[0]), ref.forward(weights, hist, dm)[0], 5e-4)
    totals = dict(zip(StackPrograms.TOTAL_FIELDS,
                      np.asarray(programs.totals)[0]))
    assert totals["index_blocks"] == 3 * 2 * 9 + 3 * 9
    assert totals["index_sparse_rows"] == 3 + 4
    # the widest column is the experts' (16 tokens x 4 picks + 2 held), not
    # the 13 blocks x 2 rows an extension can scan
    assert programs.drain_every == (2 ** 31 - 1) // (3 * (16 * 4 + 2))


@pytest.mark.parametrize("what", ["no_index", "last_positions", "no_rope"])
def test_a_program_that_selects_otherwise_misses_the_reference(
        ref, ablation, what):
    """What the cell's comparison must catch on the chip: dense attention,
    the last 12 positions, or an index without its RoPE."""
    spec = small_spec()
    params = seeded_params(spec)
    hist = np.random.default_rng(8).integers(0, N_ITEMS, size=150)
    want = ref.forward(as_reference(params), hist.tolist(), ref_dims(spec))[0]
    span = float(want.max() - want.min())

    def err(spec):
        programs = StackPrograms(spec, params, SHAPE)
        for at in range(0, 150, 16):
            h, _ = programs.prefill(hist[at:at + 16], 0, at)
        return float(np.abs(scores(params, h[0]) - want).max()) / span

    sound = err(spec)
    if what == "no_index":
        wrong = err(small_spec(mla=PLAIN))
    else:       # the builder's own way of breaking it
        with ablation.broken(what, types.SimpleNamespace(stack_spec=None)):
            wrong = err(spec)
    assert sound < 5e-4 and wrong > 20 * sound, (sound, wrong)


def test_the_model_counts_what_the_index_scanned_and_gathered():
    """``extend_latents_gathered`` keeps its name from the form that
    gathered: the latents each new position SELECTED, a layer."""
    spec = small_spec()
    items = BiMap.from_vocab([f"i{r}" for r in range(N_ITEMS)])
    model = SeqStackModel(spec, seeded_params(spec), items, SHAPE)
    rng = np.random.default_rng(9)
    long = [f"i{r}" for r in rng.integers(0, N_ITEMS, size=100)]
    short = [f"i{r}" for r in rng.integers(0, N_ITEMS, size=10)]
    for history in (long, short):
        assert len(model.recommend({"items": history, "num": 5})) == 5
    before = model.stats()
    # the long history: six chunks of 16, then its last 4 positions as an
    # extension; rows at positions 12..95 reach past 12, none of the short
    # history's ten do
    assert before["prefill_index_sparse_rows"] == 96 - 12
    # chunks ending at 16, 32 .. 96 scan 1 + 2 + .. + 6 blocks a mixer; the
    # short history's one chunk (its 16 rows reach 16) scans 1
    assert before["prefill_index_blocks"] == 3 * (21 + 1)
    assert before["extend_index_blocks"] == 3 * 7
    assert before["extend_index_sparse_rows"] == 4
    assert before["extend_latents_gathered"] == 4 * 12
    # every chunk's attention ran in the kernel, under a mask or not
    assert before["prefill_attend_kernel_chunks"] == before["prefill_runs"] \
        == 7
    # both sessions grow by two items and are extended in ONE batch
    tickets = [model.begin({"items": h + ["i1", "i2"], "num": 5})
               for h in (long, short)]
    model.step(tickets)
    after = model.stats()
    new = {k: after[k] - before[k] for k in after if k.startswith("extend_")}
    assert new["extend_runs"] == 1 and new["extend_tokens"] == 4
    # reach 100 + 4 positions in blocks of 16: 7, for each of two sessions
    assert new["extend_index_blocks"] == 3 * 2 * 7
    # positions 100, 101 (12 each) and 10, 11 (11 and 12 in reach)
    assert new["extend_index_sparse_rows"] == 2
    assert new["extend_latents_gathered"] == 12 + 12 + 11 + 12
    # the blocks of latents the walk read are the blocks of index keys the
    # scorer scanned (above): an indexed stack counts them once, there
    # (tests/benchmarks/test_glm_cell.py holds this one to 0)
    assert new["extend_latent_blocks_attended"] == 0
    assert new["extend_latent_blocks_own"] == 0
    assert after["block_index_blocks"] == 0
