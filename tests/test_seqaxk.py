"""A latent-attention stack under YaRN whose first block is a dense FFN and
whose others are expert layers with a sigmoid router that picks groups
first, against the plain reference (benchmarks/reference/axk1_forward.py,
which imports nothing of the program), at a small size on the CPU: hidden
64, 4 heads, 16 RoPE dims stretched 4 times over an original length of 64,
one dense + two expert blocks, 16 routed experts in 4 groups of which 2 are
kept, top-4, a shared expert, seeded float32 weights."""

import dataclasses
import importlib.util
import os

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
    path = os.path.join(REPO, "benchmarks", "reference", "axk1_forward.py")
    spec = importlib.util.spec_from_file_location("axk1_forward_ref", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


MLA = mla_ops.MLADims(
    dim=64, heads=4, d_nope=16, d_rope=16, d_v=16, q_rank=32, kv_rank=24,
    rope_theta=1e4, eps=1e-6, scale_q=False, scale_kv=False, rope_factor=4.0,
    rope_original_max=64, rope_beta_fast=32.0, rope_beta_slow=1.0,
    rope_mscale=1.0, rope_mscale_all_dim=1.0)
MOE = moe_ops.MoEDims(
    dim=64, expert_dim=32, n_routed=16, n_zero=0, top_k=4, scale=2.5,
    held=(0, 2), norm_topk=True, shared_dim=32, scoring="sigmoid", n_group=4,
    topk_group=2)
N_ITEMS = 50


def small_spec(mla=MLA, moe=MOE):
    def block(ffn):
        return BlockSpec(mixer="mla", ffn=ffn, norm="rmsnorm",
                         topology="pre_ln")

    return StackSpec(dim=64, ffn_dim=128, positions="rope", eps=1e-6,
                     tied_head=False, mla=mla, moe=moe,
                     blocks=(block("swiglu"), block("moe"), block("moe")))


def seeded_params(spec, seed=0):
    """init_stack's weights with the norms made non-trivial, so that a part
    that skipped them would show; no selection bias, as the model has
    none."""
    params = init_stack(spec, jax.random.PRNGKey(seed), N_ITEMS)
    rng = np.random.default_rng(seed)

    def jitter(tree):
        if isinstance(tree, dict):
            return {k: (jnp.asarray(1 + 0.2 * rng.standard_normal(v.shape),
                                    jnp.float32)
                        if "norm" in k and not isinstance(v, dict)
                        else jitter(v)) for k, v in tree.items()}
        if isinstance(tree, list):
            return [jitter(v) for v in tree]
        return tree

    params = jitter(params)
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
            "theta": m.rope_theta, "eps": spec.eps, "factor": m.rope_factor,
            "L0": m.rope_original_max, "beta_fast": m.rope_beta_fast,
            "beta_slow": m.rope_beta_slow, "mscale": m.rope_mscale,
            "mscale_all_dim": m.rope_mscale_all_dim, "n_routed": e.n_routed,
            "n_group": e.n_group, "topk_group": e.topk_group,
            "top_k": e.top_k, "scale": e.scale, "held": e.held,
            "first_dense": 1}


def close(a, b, tol=2e-4):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    scale = max(float(np.abs(b).max()), 1e-6)
    assert float(np.abs(a - b).max()) <= tol * scale, (
        float(np.abs(a - b).max()), scale)


def normal(seed, *shape):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape),
                       jnp.float32)


# -- YaRN ---------------------------------------------------------------------

def test_the_ramp_is_crossed_and_the_reference_has_the_same_angles(ref):
    freqs, amplitude, scale = ref.yarn(ref_dims(small_spec()))
    plain = 1e4 ** (-np.arange(0, 16, 2) / 16)
    ratio = freqs / plain
    # a fast pair keeps its frequency, a slow one turns 4 times slower, and
    # some pair lies on the ramp between them
    assert ratio[0] == pytest.approx(1.0) and ratio[-1] == pytest.approx(.25)
    assert ((ratio > 0.26) & (ratio < 0.99)).any()
    np.testing.assert_allclose(np.asarray(MLA.rope_freqs()), freqs, rtol=1e-6)
    m = 0.1 * np.log(4.0) + 1
    assert scale == pytest.approx(m * m / np.sqrt(32))
    assert MLA.softmax_scale == pytest.approx(scale)
    assert MLA.rope_amplitude == pytest.approx(amplitude) == 1.0


def test_without_a_stretch_the_angles_and_the_scale_are_the_plain_ones():
    plain = dataclasses.replace(MLA, rope_factor=1.0)
    want = 1e4 ** (-jnp.arange(0, 16, 2, dtype=jnp.float32) / 16)
    assert (np.asarray(plain.rope_freqs()) == np.asarray(want)).all()
    assert plain.softmax_scale == 32 ** -0.5 and plain.rope_amplitude == 1.0


def test_mla_full_and_chunked_prefill_match_the_reference_past_the_original_length(ref):
    p = seeded_params(small_spec())["blocks"][0]["mixer_a"]
    x, pos = normal(1, 150, 64), jnp.arange(150, dtype=jnp.int32)
    want = ref.mla(p, x, pos, ref_dims(small_spec()))
    close(mla_ops.attend_full(p, MLA, x[None], pos[None])[0], want)
    # chunks of 16 against a slot's cache, blocks of 8, starting mid-block
    cache = jnp.zeros((2, 176, MLA.latent), jnp.float32)
    outs, at = [], 0
    while at < 150:
        n = min(12 if at == 0 else 16, 150 - at)
        chunk = jnp.zeros((16, 64), jnp.float32).at[:n].set(x[at:at + n])
        out, cache, _ = mla_ops.prefill_chunk(p, MLA, chunk, at, cache, 1, 8)
        outs.append(out[:n])
        at += n
    close(jnp.concatenate(outs), want)


def test_an_extension_batch_of_a_short_and_a_long_session_equals_each_alone(ref):
    """One batch attends as many blocks as its LONGEST row needs: the short
    row's answer is the one it gets alone, and both are the reference's."""
    p = seeded_params(small_spec())["blocks"][1]["mixer_a"]
    rng = np.random.default_rng(2)
    xs = [jnp.asarray(rng.standard_normal((n, 64)), jnp.float32)
          for n in (140, 9)]
    dm = ref_dims(small_spec())
    want = [ref.mla(p, x, jnp.arange(len(x)), dm) for x in xs]
    cache = jnp.zeros((3, 160, MLA.latent), jnp.float32)
    new = (3, 2)
    for slot, (x, n) in enumerate(zip(xs, new)):
        head = len(x) - n
        chunk = jnp.zeros((144, 64), jnp.float32).at[:head].set(x[:head])
        _, cache, _ = mla_ops.prefill_chunk(p, MLA, chunk, 0, cache, slot, 8)
    rows = [jnp.zeros((4, 64)).at[:n].set(x[len(x) - n:])
            for x, n in zip(xs, new)]
    pos0 = [len(x) - n for x, n in zip(xs, new)]

    def extend(which, n_blocks):
        pos = jnp.array([pos0[b] for b in which], jnp.int32)[:, None] \
            + jnp.arange(4)[None]
        return mla_ops.extend(
            p, MLA, jnp.stack([rows[b] for b in which]), pos, cache,
            jnp.array(which), jnp.int32(n_blocks), 8)[0]

    both = extend([0, 1], 18)
    for b, (w, n) in enumerate(zip(want, new)):
        close(both[b, :n], w[len(w) - n:])
    close(both[0, :3], extend([0], 18)[0, :3], 1e-6)
    close(both[1, :2], extend([1], 2)[0, :2], 1e-6)


# -- the router ---------------------------------------------------------------

def brute_force_route(w_r, x, dims):
    """Group-limited selection written out position by position."""
    s = 1 / (1 + np.exp(-(np.asarray(x, np.float64)
                          @ np.asarray(w_r, np.float64))))
    per = dims.n_routed // dims.n_group
    idx, gates = [], []
    for row in s:
        score = [np.sort(row[g * per:(g + 1) * per])[-2:].sum()
                 for g in range(dims.n_group)]
        kept = sorted(range(dims.n_group), key=lambda g: (-score[g], g))[
            :dims.topk_group]
        inside = [e for e in range(dims.n_routed) if e // per in kept]
        picks = sorted(inside, key=lambda e: (-row[e], e))[:dims.top_k]
        idx.append(picks)
        gates.append(dims.scale * row[picks] / row[picks].sum())
    return np.array(idx), np.array(gates), s


def test_route_is_the_brute_force_group_limited_selection():
    p = moe_ops.init(jax.random.PRNGKey(3), MOE)
    x = normal(3, 200, 64) * 3
    idx, gates = moe_ops.route(p, MOE, x)
    want_idx, want_gates, s = brute_force_route(p["w_r"], x, MOE)
    assert (np.asarray(idx) == want_idx).all()
    np.testing.assert_allclose(np.asarray(gates), want_gates, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gates).sum(axis=1), 2.5, rtol=1e-5)
    # the rule binds: plain top-4 over all 16 picks otherwise somewhere
    plain = np.argsort(-s, axis=1, kind="stable")[:, :4]
    assert (np.sort(plain, axis=1) != np.sort(want_idx, axis=1)).any()
    _, _, kept = moe_ops.route_kept(p, MOE, x)
    assert (np.asarray(kept).sum(axis=1) == 2).all()
    assert (np.asarray(kept)[np.arange(200)[:, None], want_idx // 4]).all()


def test_one_group_of_softmax_scores_is_the_rule_as_it_was_bit_for_bit():
    dims = moe_ops.MoEDims(dim=64, expert_dim=32, n_routed=16, n_zero=8,
                           top_k=4, scale=3.0, held=(4, 4))
    p = moe_ops.init(jax.random.PRNGKey(4), dims, bias_std=2e-3)
    x = normal(4, 64, 64)
    logits = jnp.dot(x, p["w_r"], precision=jax.lax.Precision.HIGHEST)
    prob = jax.nn.softmax(logits, axis=-1)
    _, want_idx = jax.lax.top_k(prob + p["bias"], 4)
    picked = jnp.take_along_axis(prob, want_idx, axis=-1)
    want_gates = 3.0 * picked
    idx, gates, kept = moe_ops.route_kept(p, dims, x)
    assert kept is None
    assert (np.asarray(idx) == np.asarray(want_idx)).all()
    assert (np.asarray(gates) == np.asarray(want_gates)).all()
    renorm = dataclasses.replace(dims, norm_topk=True)
    want = 3.0 * (picked / picked.sum(axis=-1, keepdims=True))
    assert (np.asarray(moe_ops.route(p, renorm, x)[1])
            == np.asarray(want)).all()


@pytest.mark.parametrize("change", [
    {"scoring": "tanh"}, {"n_group": 3}, {"topk_group": 5},
    {"topk_group": 0}, {"n_zero": 4}])
def test_a_router_the_layer_cannot_compute_is_refused(change):
    with pytest.raises(ValueError):
        dataclasses.replace(MOE, **change)


@pytest.mark.parametrize("T", [29, 80])
def test_the_expert_layer_matches_the_reference(ref, T):
    """A small forward (the streamed kernel) and a chunk (the grouped one):
    held experts 4-7 (group 1 whole) and the shared expert."""
    dims = dataclasses.replace(MOE, held=(4, 4))
    p = moe_ops.init(jax.random.PRNGKey(5), dims)
    x, valid = normal(5, T, 64), jnp.ones(T, bool)
    routed, shared, _ = ref.moe_parts(p, x, ref_dims(small_spec()), (4, 4))
    y, counted = moe_ops.moe(p, dims, x, valid)
    close(y, routed + shared)
    idx, _, kept = moe_ops.route_kept(p, dims, x)
    assert int(counted["group_hits"]) == int(np.asarray(kept)[:, 1].sum())
    assert (np.asarray(counted["expert_load"])
            == [(np.asarray(idx) == e).sum() for e in range(4, 8)]).all()


def test_the_sixteen_ways_a_layer_is_shared_add_up_to_the_uncut_layer(ref):
    """Eight shares of two experts (each half of a routing group, as the
    deployment's 16 shares of 12), the shared expert counted once: the whole
    layer, in the program and in the reference alike."""
    whole = dataclasses.replace(MOE, held=(0, 16))
    p = moe_ops.init(jax.random.PRNGKey(6), whole)
    x, valid = normal(6, 29, 64), jnp.ones(29, bool)
    dm = ref_dims(small_spec())
    uncut_routed, uncut_shared, _ = ref.moe_parts(p, x, dm, (0, 16))
    idx, gates = moe_ops.route(p, whole, x)
    total, hits = 0.0, 0
    for e0 in range(0, 16, 2):
        share = dataclasses.replace(MOE, held=(e0, 2))
        ps = dict(p, **{k: p[k][e0:e0 + 2] for k in ("w_g", "w_u", "w_d")})
        routed, _ = moe_ops.experts_streamed(ps, share, x, idx, gates, valid)
        ref_routed, ref_shared, _ = ref.moe_parts(ps, x, dm, (e0, 2))
        close(routed, ref_routed)
        close(ref_shared, uncut_shared)
        y, counted = moe_ops.moe(ps, share, x, valid)
        close(y, ref_routed + uncut_shared)
        total = total + routed
        hits += int(counted["group_hits"])
    close(total, uncut_routed)
    close(moe_ops.moe(p, whole, x, valid)[0], uncut_routed + uncut_shared)
    # every token keeps 2 of 4 groups, and each group has two shares
    assert hits == 29 * 2 * 2


# -- the stack ----------------------------------------------------------------

SHAPE = ServeShape(n_slots=3, capacity=192, chunk=16, extend_len=4,
                   extend_batch=2)


def scores(params, h_last):
    return np.asarray(h_last) @ np.asarray(params["head"]).T


def test_a_dense_block_then_expert_blocks_give_the_reference_scores(ref):
    """Scores, not ranks: a history past the original length prefilled in
    chunks and grown by extensions through the cache, against the full
    forward over it; the dense block counts no expert."""
    spec = small_spec()
    params = seeded_params(spec)
    programs = StackPrograms(spec, params, SHAPE)
    weights, dm = as_reference(params), ref_dims(spec)
    rng = np.random.default_rng(7)
    hist = rng.integers(0, N_ITEMS, size=140).tolist()
    at = 0
    while at < 133:
        n = min(16, 133 - at)
        h, counted = programs.prefill(np.array(hist[at:at + n]), 1, at)
        at += n
    close(scores(params, h[0]), ref.forward(weights, hist[:133], dm)[0], 5e-4)
    assert int(counted["tokens"]) == 5
    assert counted["expert_load"].shape == (2, 2)      # two expert blocks
    assert counted["group_hits"].shape == (2,)
    other = rng.integers(0, N_ITEMS, size=9).tolist()
    programs.prefill(np.array(other[:7]), 0, 0)
    # a long and a short session extended in one step
    h, counted = programs.extend([(hist[133:136], 1, 133),
                                  (other[7:9], 0, 7)])
    close(scores(params, h[0]), ref.forward(weights, hist[:136], dm)[0], 5e-4)
    close(scores(params, h[1]), ref.forward(weights, other, dm)[0], 5e-4)
    assert int(counted["tokens"]) == 5
    assert (np.asarray(counted["group_hits"]) <= 5).all()
    h, _ = programs.extend([(hist[136:140], 1, 136)])
    close(scores(params, h[0]), ref.forward(weights, hist, dm)[0], 5e-4)


@pytest.mark.parametrize("what", ["plain_rope", "plain_top_k"])
def test_a_program_without_the_stretch_or_the_groups_misses_the_reference(
        ref, what):
    """What the cell's comparison must catch on the chip: plain RoPE in
    YaRN's place, or plain top-k in the group rule's, past the original
    length."""
    spec = small_spec()
    wrong = small_spec(
        mla=dataclasses.replace(MLA, rope_factor=1.0)
        if what == "plain_rope" else MLA,
        moe=dataclasses.replace(MOE, n_group=1, topk_group=1)
        if what == "plain_top_k" else MOE)
    params = seeded_params(spec)
    hist = np.random.default_rng(8).integers(0, N_ITEMS, size=150)
    want = ref.forward(as_reference(params), hist.tolist(), ref_dims(spec))[0]
    span = float(want.max() - want.min())
    errs = {}
    for name, s in (("sound", spec), ("wrong", wrong)):
        programs = StackPrograms(s, params, SHAPE)
        for at in range(0, 150, 16):
            h, _ = programs.prefill(hist[at:at + 16], 0, at)
        errs[name] = float(np.abs(scores(params, h[0]) - want).max()) / span
    assert errs["sound"] < 5e-4 and errs["wrong"] > 20 * errs["sound"], errs


def test_the_model_counts_group_hits_and_the_blocks_an_extension_walks():
    spec = small_spec()
    items = BiMap.from_vocab([f"i{r}" for r in range(N_ITEMS)])
    model = SeqStackModel(spec, seeded_params(spec), items, SHAPE)
    rng = np.random.default_rng(9)
    long = [f"i{r}" for r in rng.integers(0, N_ITEMS, size=100)]
    short = [f"i{r}" for r in rng.integers(0, N_ITEMS, size=10)]
    for history in (long, short):
        assert len(model.recommend({"items": history, "num": 5})) == 5
    before = model.stats()
    assert 0 < before["prefill_group_hit_tokens"] <= 2 * 110
    # both sessions grow by two items and are extended in ONE batch
    tickets = [model.begin({"items": h + ["i1", "i2"], "num": 5})
               for h in (long, short)]
    model.step(tickets)
    after = model.stats()
    new = {k: after[k] - before[k] for k in after if k.startswith("extend_")}
    assert new["extend_runs"] == 1 and new["extend_tokens"] == 4
    assert 0 <= new["extend_group_hit_tokens"] <= 2 * 4
    # reach 100 + 4 and 10 + 4 positions in blocks of 16: 7 and 1 alone,
    # 7 each when batched
    assert new["extend_latent_blocks_own"] == 8
    assert new["extend_latent_blocks_attended"] == 14
    assert after["block_group_hit_tokens"] == 0
