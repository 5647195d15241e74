"""The hybrid stack (state-space mixers with a causal attention layer among
them, an expert layer with a shared expert as every block's FFN, the model's
four multipliers) against the plain reference (benchmarks/reference/
granite_forward.py, which imports nothing of the program), at a small size on
the CPU: hidden 64, 4 state-space heads of 16 with a state of 8, 8 query heads
on 2 key/value heads of 8, 8 experts of 32 (top-3) and a shared one of 48,
4 layers (mamba, mamba, attention, mamba), 50 items, seeded float32 weights."""

import dataclasses
import importlib.util
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.models.sessionrec import LatentCache, SeqStackModel
from predictionio_tpu.ops import gqa as gqa_ops
from predictionio_tpu.ops import moe as moe_ops
from predictionio_tpu.ops import ssm as ssm_ops
from predictionio_tpu.ops.sessionrec import (
    BlockSpec, ServeShape, StackPrograms, StackSpec, init_stack)
from tests.test_seqstack import close, deploy_small, post

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_ITEMS = 50
SSM = ssm_ops.SSMDims(dim=64, heads=4, head_dim=16, d_state=8, d_conv=4,
                      chunk=8, eps=1e-5)
GQA = gqa_ops.GQADims(dim=64, heads=8, kv_heads=2, head_dim=8, block_len=1,
                      eps=1e-5, rope=False, qk_norm=False, scale=0.125)
MOE = moe_ops.MoEDims(dim=64, expert_dim=32, n_routed=8, n_zero=0, top_k=3,
                      scale=1.0, held=(0, 4), norm_topk=True, shared_dim=48)
KINDS = ("mamba", "mamba", "attention", "mamba")
SHAPE = ServeShape(n_slots=3, capacity=96, chunk=16, extend_len=4,
                   extend_batch=3)


@pytest.fixture(scope="module")
def ref():
    path = os.path.join(REPO, "benchmarks", "reference", "granite_forward.py")
    spec = importlib.util.spec_from_file_location("granite_forward_ref", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def small_spec(moe=MOE):
    return StackSpec(
        dim=64, ffn_dim=0, positions="rope", embed_scale=12.0,
        residual_scale=0.22, logits_scale=1 / 16, eps=1e-5, tied_head=True,
        gqa=GQA, ssm=SSM, moe=moe,
        blocks=tuple(BlockSpec(
            mixer="gqa" if kind == "attention" else "mamba2", ffn="moe",
            norm="rmsnorm", topology="pre_ln") for kind in KINDS))


def ref_dims(moe=MOE):
    return {"D": 64, "eps": 1e-5, "layer_types": KINDS, "embed_mult": 12.0,
            "attn_mult": 0.125, "res_mult": 0.22, "logits_scaling": 16.0,
            "H": 8, "Hkv": 2, "d": 8, "m_heads": 4, "m_head": 16,
            "m_state": 8, "m_conv": 4, "n_routed": moe.n_routed,
            "top_k": moe.top_k, "held": moe.held}


def seeded_params(spec, seed=0):
    """init_stack's weights with the norms, the convolution's bias and ``D``
    made non-trivial, so that a part that skipped them would show."""
    params = init_stack(spec, jax.random.PRNGKey(seed), N_ITEMS)
    rng = np.random.default_rng(seed)

    def jitter(tree):
        if isinstance(tree, dict):
            return {k: (jnp.asarray(1 + 0.2 * rng.standard_normal(v.shape),
                                    jnp.float32)
                        if ("norm" in k or k == "d")
                        and not isinstance(v, dict)
                        else jnp.asarray(0.3 * rng.standard_normal(v.shape),
                                         jnp.float32) if k == "conv_b"
                        else jitter(v)) for k, v in tree.items()}
        if isinstance(tree, list):
            return [jitter(v) for v in tree]
        return tree

    params = jitter(params)
    params["item_embed"]["embedding"] = jnp.asarray(
        rng.standard_normal((N_ITEMS, spec.dim)) / 8, jnp.float32)
    return params


def as_reference(params):
    return {"embed": params["item_embed"]["embedding"],
            "final_norm": params["final_norm"], "layers": params["blocks"]}


def history(seed, n):
    return np.random.default_rng(seed).integers(0, N_ITEMS, size=n).tolist()


def query(rows, num=5):
    return {"items": [f"i{r}" for r in rows], "num": num}


def small_model(n_slots=3, **shape):
    spec = small_spec()
    params = seeded_params(spec)
    items = BiMap.from_vocab([f"i{r}" for r in range(N_ITEMS)])
    model = SeqStackModel(spec, params, items, dataclasses.replace(
        SHAPE, n_slots=n_slots, **shape))
    return model, as_reference(params)


def check(ref, weights, hist, result, tol=1e-3):
    want = ref.top_k_answer(ref.forward(weights, hist, ref_dims())[0],
                            len(result))
    assert [int(item[1:]) for item, _ in result] == [i for i, _ in want]
    close([s for _, s in result], [v for _, v in want], tol)


# -- the mixers ---------------------------------------------------------------

def test_ssm_chunks_then_extensions_through_the_state_match_the_recurrence(
        ref):
    p = seeded_params(small_spec())["blocks"][0]["mixer_a"]
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((47, 64)), jnp.float32)
    want = ref.mamba2(p, x, ref_dims())
    close(ssm_ops.mix_full(p, SSM, x), want)
    # chunks of 16 positions (two chunks of 8 of the scan each) into slot 1,
    # which held another session: 16 whole, 11 real of 16, then extensions
    # of 1-4 positions together with a second session that is shorter than
    # the convolution (2 positions, from position 0 of slot 2)
    state = jax.tree_util.tree_map(
        lambda a: jnp.asarray(rng.standard_normal(a.shape), a.dtype),
        ssm_ops.init_state(SSM, 4, jnp.float32))
    outs, at = [], 0
    for n in (16, 11):
        chunk = jnp.asarray(rng.standard_normal((16, 64)),
                            jnp.float32).at[:n].set(x[at:at + n])
        out, state = ssm_ops.prefill_chunk(p, SSM, chunk, n, at, state, 1)
        outs.append(out[:n])
        at += n
    close(jnp.concatenate(outs), want[:27])
    other = jnp.asarray(rng.standard_normal((5, 64)), jnp.float32)
    want_other = ref.mamba2(p, other, ref_dims())
    at_other = 0
    for n, n_other in ((4, 2), (1, 3), (3, 0), (4, 0), (4, 0), (4, 0)):
        batch = jnp.asarray(rng.standard_normal((3, 4, 64)), jnp.float32)
        batch = batch.at[0, :n].set(x[at:at + n])
        batch = batch.at[1, :n_other].set(other[at_other:at_other + n_other])
        out, state = ssm_ops.extend(
            p, SSM, batch, jnp.array([n, n_other, 0]),
            jnp.array([at, at_other, 0]), state, jnp.array([1, 2, 3]))
        close(out[0, :n], want[at:at + n])
        if n_other:
            close(out[1, :n_other],
                  want_other[at_other:at_other + n_other])
        at, at_other = at + n, at_other + n_other
    assert at == 47 and at_other == 5


def test_a_padded_position_leaves_the_state_untouched():
    p = seeded_params(small_spec())["blocks"][1]["mixer_a"]
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((16, 64)), jnp.float32)
    empty = ssm_ops.init_state(SSM, 2, jnp.float32)
    _, short = ssm_ops.prefill_chunk(p, SSM, x[:8], 6, 0, empty, 0)
    _, padded = ssm_ops.prefill_chunk(p, SSM, x.at[6:].set(7.0), 6, 0,
                                      empty, 0)
    for name in ("conv", "ssm"):
        close(padded[name][0], short[name][0], 1e-6)
        assert not np.asarray(padded[name][1]).any()


def test_causal_attention_without_positions_matches_the_reference(ref):
    p = seeded_params(small_spec())["blocks"][2]["mixer_a"]
    assert set(p) == {"w_q", "w_k", "w_v", "w_o"}
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((37, 64)), jnp.float32)
    pos = jnp.arange(37, dtype=jnp.int32)
    want = ref.attention(p, x, ref_dims())
    close(gqa_ops.attend_full(p, GQA, x, pos), want)
    # no position encoding: the same rows at other positions, same output
    close(gqa_ops.attend_full(p, GQA, x, pos + 11), want)
    cache = jnp.zeros((2, 64, GQA.cache_width), jnp.float32)
    outs, at = [], 0
    for n in (16, 14):
        chunk = jnp.zeros((16, 64), jnp.float32).at[:n].set(x[at:at + n])
        out, cache = gqa_ops.prefill_chunk(p, GQA, chunk, at, cache, 1, 8)
        outs.append(out[:n])
        at += n
    # then extensions of 4 and 3 positions of the one row
    for n in (4, 3):
        rows = jnp.zeros((1, 4, 64), jnp.float32).at[0, :n].set(x[at:at + n])
        out, cache = gqa_ops.extend(
            p, GQA, rows, at + jnp.arange(4)[None], cache, jnp.array([1]),
            jnp.int32(-(-(at + 4) // 8)), 8)
        outs.append(out[0, :n])
        at += n
    close(jnp.concatenate(outs), want)


def test_the_router_takes_the_top_of_the_logits_and_a_softmax_over_them(ref):
    p = seeded_params(small_spec())["blocks"][0]["moe"]
    x = jnp.asarray(np.random.default_rng(4).standard_normal((9, 64)),
                    jnp.float32)
    idx, gates = moe_ops.route(p, MOE, x)
    logits = np.asarray(x) @ np.asarray(p["w_r"])
    for t in range(9):
        top = np.argsort(-logits[t])[:3]
        assert sorted(idx[t].tolist()) == sorted(top.tolist())
        e = np.exp(logits[t, top] - logits[t, top].max())
        close(gates[t][np.argsort(np.asarray(idx[t]))],
              (e / e.sum())[np.argsort(top)], 1e-5)
    dense, _ = ref.route(p, x, ref_dims())
    close(np.asarray(dense).sum(axis=1), np.ones(9), 1e-5)


@pytest.mark.parametrize("tokens", [9, 80])
def test_two_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(
        ref, tokens):
    """The routed parts of held = (0, 4) and (4, 4) plus the shared expert
    counted once equal the reference's layer with all 8 experts held."""
    whole = dataclasses.replace(MOE, held=(0, 8))
    p = seeded_params(small_spec(whole))["blocks"][0]["moe"]
    x = jnp.asarray(np.random.default_rng(5).standard_normal((tokens, 64)),
                    jnp.float32)
    valid = jnp.ones(tokens, bool)
    routed, shared, _ = ref.moe_parts(p, x, ref_dims(whole), (0, 8))
    total = jnp.zeros_like(x)
    for e0 in (0, 4):
        dims = dataclasses.replace(MOE, held=(e0, 4), shared_dim=0)
        part = {k: (v[e0:e0 + 4] if k in ("w_g", "w_u", "w_d") else v)
                for k, v in p.items()}
        y, counted = moe_ops.moe(part, dims, x, valid)
        close(y, ref.moe_parts(part, x, ref_dims(whole), (e0, 4))[0], 5e-4)
        total = total + y
    assert not np.allclose(np.asarray(total), 0)
    close(total + moe_ops.swiglu(x, **p["shared"]), routed + shared, 5e-4)
    # and the program's own layer with a share and the shared expert
    half = {k: (v[:4] if k in ("w_g", "w_u", "w_d") else v)
            for k, v in p.items()}
    y, _ = moe_ops.moe(half, MOE, x, valid)
    close(y, ref.moe_parts(half, x, ref_dims(), (0, 4))[0] + shared, 5e-4)


@pytest.mark.parametrize("case", ["uniform", "padding", "one_expert"])
@pytest.mark.parametrize("tokens", [65, 200])
def test_a_chunks_grouped_experts_beside_the_shared_one(ref, tokens, case):
    """A forward of more than one tile through the layer with a share of the
    routed experts and the shared expert: the grouped kernel adds what the
    tile loop adds, the shared expert runs beside it over every row, and
    the sum is the reference's; the load counts the products."""
    p = dict(seeded_params(small_spec())["blocks"][0]["moe"])
    if case == "one_expert":        # every token picks held expert 2
        p["bias"] = p["bias"].at[2].set(10.0)
    x = jnp.asarray(np.random.default_rng(tokens).standard_normal(
        (tokens, 64)), jnp.float32)
    valid = (jnp.arange(tokens) < tokens - 9 if case == "padding"
             else jnp.ones(tokens, bool))
    idx, gates = moe_ops.route(p, MOE, x)
    grouped, counts = moe_ops.experts_grouped(p, MOE, x, idx, gates, valid)
    tiles, counts_tiles = moe_ops.experts_sorted(p, MOE, x, idx, gates, valid)
    close(grouped, tiles, 1e-6)
    assert counts.tolist() == counts_tiles.tolist()
    y, counted = moe_ops.moe(p, MOE, x, valid)
    shared = moe_ops.swiglu(x, **p["shared"])
    close(y, grouped + shared, 1e-6)
    real = np.asarray(valid)
    if case != "one_expert":        # the reference's router has no bias
        routed, ref_shared, _ = ref.moe_parts(p, x, ref_dims(), MOE.held)
        close(np.asarray(y)[real], np.asarray(routed + ref_shared)[real],
              5e-4)
    # a padding row gets the shared expert's part alone
    assert np.array_equal(np.asarray(y)[~real], np.asarray(shared)[~real])
    assert counted["expert_load"].tolist() == counts.tolist()
    if case == "one_expert":
        assert int(counts[2]) == int(real.sum())
        assert moe_ops.row_tiles(counts)[2] == -(-int(real.sum()) // 128)


# -- the programs -------------------------------------------------------------

def test_chunked_prefill_then_extensions_give_the_reference_logits(ref):
    spec = small_spec()
    params = seeded_params(spec)
    programs = StackPrograms(spec, params, SHAPE)
    assert programs.kinds == ["mamba2", "mamba2", "gqa", "mamba2"]
    assert set(programs.cache[0]) == {"conv", "ssm"}
    assert programs.cache[2].shape == (4, 112, GQA.cache_width)
    weights, dm = as_reference(params), ref_dims()
    table = np.asarray(params["item_embed"]["embedding"])
    hist = history(6, 45)

    def logits(h_last):
        return np.asarray(h_last) @ table.T

    at = 0
    for n in (16, 16, 5):                 # 37 positions in three chunks
        h, counted = programs.prefill(np.array(hist[at:at + n]), 1, at)
        at += n
    close(logits(h[0]), ref.forward(weights, hist[:37], dm)[0], 5e-4)
    assert int(counted["tokens"]) == 5
    other = history(7, 9)
    programs.prefill(np.array(other[:7]), 0, 0)
    # two sessions extended in one step, and each alone gives the same
    h, counted = programs.extend([(hist[37:40], 1, 37), (other[7:9], 0, 7)])
    close(logits(h[0]), ref.forward(weights, hist[:40], dm)[0], 5e-4)
    close(logits(h[1]), ref.forward(weights, other, dm)[0], 5e-4)
    assert int(counted["tokens"]) == 5
    assert counted["expert_load"].shape == (4, 4)
    alone = StackPrograms(spec, params, SHAPE)
    at = 0
    for n in (16, 16, 5):
        alone.prefill(np.array(hist[at:at + n]), 2, at)
        at += n
    h_alone, _ = alone.extend([(hist[37:40], 2, 37)])
    close(h_alone[0], h[0], 1e-5)
    h, _ = programs.extend([(hist[40:44], 1, 40)])
    close(logits(h[0]), ref.forward(weights, hist[:44], dm)[0], 5e-4)
    # a short session served by the extension program from position 0 of a
    # slot that held another: it starts from zeros
    h, _ = programs.extend([(hist[:3], 0, 0)])
    close(logits(h[0]), ref.forward(weights, hist[:3], dm)[0], 5e-4)


def test_stack_programs_refuse_a_mixer_without_a_cache_and_a_gqa_stack_that_cannot_extend():
    spec = small_spec()
    params = seeded_params(spec)
    uncached = dataclasses.replace(spec, blocks=spec.blocks[:1] + (
        dataclasses.replace(spec.blocks[1], mixer="mha"),))
    with pytest.raises(ValueError, match="per-session"):
        StackPrograms(uncached, params, SHAPE)
    blocked = dataclasses.replace(
        spec, gqa=dataclasses.replace(GQA, block_len=4))
    with pytest.raises(ValueError, match="generation"):
        StackPrograms(blocked, params, SHAPE)


# -- the cache's rule and the model -------------------------------------------

def test_a_recurrent_slot_is_resumed_from_its_end_or_not_at_all():
    a = np.arange(10, dtype=np.int32)
    cache = LatentCache(2, recurrent=True)
    assert cache.acquire(a) == (0, 0)
    cache.release(0, a)
    grown = np.concatenate([a, [3, 4]]).astype(np.int32)
    assert cache.acquire(grown) == (0, 10)        # the session grew
    cache.release(0, grown)
    assert cache.state_resumes == 1
    # repeated, gone back a little, diverged late: a per-position cache
    # would find 11, 9 and 10 positions; the state finds none, and the
    # session starts over in the slot it had
    for rows, would in ((grown, 11), (grown[:9], 8),
                        (np.concatenate([grown[:10], [9, 9, 9]]), 10)):
        evictions = cache.evictions
        assert cache.acquire(rows.astype(np.int32)) == (0, 0)
        assert cache.evictions == evictions + 1
        cache.release(0, grown)
        assert cache.rewind_miss_tokens == would
        cache.rewind_miss_tokens = 0
    assert cache.rewind_misses == 3 and cache.state_resumes == 1
    # an unrelated query takes the least recently used slot, no marker
    assert cache.acquire(np.arange(20, 30, dtype=np.int32)) == (1, 0)
    assert cache.rewind_misses == 3
    # a per-position cache keeps the longest-common-prefix rule
    plain = LatentCache(2)
    plain.acquire(a)
    plain.release(0, grown)
    assert plain.acquire(grown[:9]) == (0, 8)
    assert plain.rewind_misses == plain.state_resumes == 0


def test_a_session_in_a_slot_that_held_another_equals_it_from_an_empty_cache(
        ref):
    model, weights = small_model(n_slots=1)
    first, second = history(8, 40), history(9, 21)
    check(ref, weights, first, model.answer(query(first)).result)
    got = model.answer(query(second))
    assert got.slot == 0 and model.cache.evictions == 1
    cold_model, _ = small_model(n_slots=1)
    cold = cold_model.answer(query(second))
    assert got.result == cold.result
    check(ref, weights, second, got.result)


def test_a_diverged_and_a_repeated_query_are_misses_and_answer_right(ref):
    model, weights = small_model()
    hist = history(10, 30)
    check(ref, weights, hist, model.answer(query(hist)).result)
    grown = hist + [3, 4]
    check(ref, weights, grown, model.answer(query(grown)).result)
    stats = model.stats()
    assert (stats["hit_tokens"], stats["state_resumes"]) == (30, 1)
    assert stats["extend_state_rows"] == 1
    assert stats["extend_kv_positions"] == 32
    assert stats["extend_latent_positions"] == 0
    for rows in (grown, grown[:25] + [7, 8, 9]):       # repeated; diverged
        check(ref, weights, rows, model.answer(query(rows)).result)
    stats = model.stats()
    assert stats["hit_tokens"] == 30 and stats["rewind_misses"] == 2
    assert stats["rewind_miss_tokens"] == 31 + 25
    assert stats["miss_tokens"] == 30 + 2 + 32 + 28


def test_cancel_in_mid_prefill_releases_at_the_states_position(ref):
    model, weights = small_model()
    hist = history(11, 70)                            # 5 chunks of 16
    ticket = model.begin(query(hist))
    model.step([ticket])
    model.step([ticket])
    assert ticket.done == 32 and ticket.result is None
    model.cancel(ticket)
    assert model.cache.rows[ticket.slot].tolist() == hist[:32]
    assert not model.cache.busy[ticket.slot]
    # the same session again resumes from position 32, and answers right
    again = model.answer(query(hist))
    assert again.slot == ticket.slot and model.cache.hit_tokens == 32
    check(ref, weights, hist, again.result)


def test_two_sessions_extend_while_a_third_prefills_through_the_worker(ref):
    spec = small_spec()
    params = seeded_params(spec)
    server, _, _ = deploy_small(n_slots=3, capacity=96, stack=(spec, params),
                                n_items=N_ITEMS, extend_len=4, extend_batch=3)
    try:
        model = server.deployment.models[0]
        assert server._batcher.histogram()["stepwise"] is True
        weights = as_reference(params)
        short = [history(21, 10), history(22, 7)]
        for hist in short:
            post(server, hist)                        # their slots are warm
        hists = [short[0] + [1, 2], short[1] + [3], history(23, 70)]
        answers = [None] * 3

        def ask(i):
            answers[i] = post(server, hists[i])

        threads = [threading.Thread(target=ask, args=(i,)) for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        for hist, got in zip(hists, answers):
            check(ref, weights, hist,
                  [(s["item"], s["score"]) for s in got])
        stats = model.stats()
        assert stats["state_resumes"] == 2 and stats["extend_rows"] == 2
        assert stats["prefill_runs"] == 2 + 5
        assert server._batcher.histogram()["answered"] == 5
    finally:
        server.stop()
