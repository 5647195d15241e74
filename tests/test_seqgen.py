"""The block-diffusion expert stack (grouped-query attention under the
block-causal mask, an expert layer as the whole FFN, generation by blocks)
against the plain reference (benchmarks/reference/sdar_forward.py, which
imports nothing of the program), at a small size on the CPU: hidden 64, 8
query heads on 2 key/value heads of 16, 16 experts of 32, top-4, 2 layers,
60 items, block length 4, seeded float32 weights."""

import dataclasses
import importlib.util
import json
import os
import threading
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.models.sessionrec import SeqStackModel
from predictionio_tpu.ops import gqa as gqa_ops
from predictionio_tpu.ops import moe as moe_ops
from predictionio_tpu.ops.sessionrec import (
    BlockSpec, Generation, ServeShape, StackPrograms, StackSpec, init_stack)
from tests.test_seqstack import close, deploy_small

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_ITEMS, MASK = 60, 37
GQA = gqa_ops.GQADims(dim=64, heads=8, kv_heads=2, head_dim=16, block_len=4,
                      rope_theta=1e4, eps=1e-6)
MOE = moe_ops.MoEDims(dim=64, expert_dim=32, n_routed=16, n_zero=0, top_k=4,
                      scale=1.0, held=(0, 16), norm_topk=True)
DM = {"D": 64, "H": 8, "KV": 2, "hd": 16, "theta": 1e4, "eps": 1e-6,
      "top_k": 4, "bl": 4}
STATIC = Generation(mask_row=MASK, block_len=4, denoising_steps=2,
                    rule="low_confidence_static")
#: with 60 items and these weights a best item's probability is 0.07-0.11:
#: a threshold of 0.09 is passed by some positions and not by others
DYNAMIC = Generation(mask_row=MASK, block_len=4, denoising_steps=4,
                     rule="low_confidence_dynamic", threshold=0.09)
SHAPE = ServeShape(n_slots=3, capacity=96, chunk=16, extend_len=8,
                   gen_batch=4)


@pytest.fixture(scope="module")
def ref():
    path = os.path.join(REPO, "benchmarks", "reference", "sdar_forward.py")
    spec = importlib.util.spec_from_file_location("sdar_forward_ref", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def small_spec(gen=STATIC, layers=2):
    return StackSpec(
        dim=64, ffn_dim=0, positions="rope", eps=1e-6, tied_head=False,
        gqa=GQA, moe=MOE, generation=gen,
        blocks=(BlockSpec(mixer="gqa", ffn="moe", norm="rmsnorm",
                          topology="pre_ln"),) * layers)


def seeded_params(spec, seed=0):
    """init_stack's weights with every norm weight made non-trivial, so
    that a part that skipped one would show."""
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
    return {"embed": params["item_embed"]["embedding"],
            "head": params["head"], "final_norm": params["final_norm"],
            "layers": params["blocks"]}


def as_dict(gen):
    return dataclasses.asdict(gen)


def history(seed, n):
    rows = np.random.default_rng(seed).integers(0, N_ITEMS - 1, size=n)
    return [int(r) + (r >= MASK) for r in rows]          # never the mask


def test_gqa_chunks_then_blocks_through_the_cache_match_the_full_forward(ref):
    """A history of 40 prefilled in chunks of 16 (a chunk boundary inside
    it, the last chunk half padding), then two blocks through the block
    program, rounds of 8 cached positions: the reference's attention over
    all 48 positions under the block-causal mask."""
    p = seeded_params(small_spec())["blocks"][0]["mixer_a"]
    x = jnp.asarray(np.random.default_rng(1).standard_normal((48, 64)),
                    jnp.float32)
    pos = jnp.arange(48, dtype=jnp.int32)
    want = ref.attention(p, x, pos, DM)
    close(gqa_ops.attend_full(p, GQA, x, pos), want)
    cache = jnp.zeros((3, 64, GQA.cache_width), jnp.float32)
    outs = []
    for at, n in ((0, 16), (16, 16), (32, 8)):
        chunk = jnp.zeros((16, 64), jnp.float32).at[:n].set(x[at:at + n])
        out, cache = gqa_ops.prefill_chunk(p, GQA, chunk, at, cache, 1, 8)
        outs.append(out[:n])
    # both blocks in ONE call, as two rows of the same slot; a padding row
    # of another slot beside them
    blocks = jnp.stack([x[40:44], x[44:48], jnp.zeros((4, 64))])
    bpos = jnp.array([40, 44, 0])[:, None] + jnp.arange(4)[None]
    out, cache = gqa_ops.block_step(p, GQA, blocks, bpos, cache,
                                    jnp.array([1, 1, 2]), jnp.int32(6), 8)
    close(jnp.concatenate(outs + [out[0], out[1]]), want)


def test_a_position_sees_its_whole_block_and_nothing_after_it(ref):
    p = seeded_params(small_spec())["blocks"][0]["mixer_a"]
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((12, 64)), jnp.float32)
    pos = jnp.arange(12, dtype=jnp.int32)
    base = gqa_ops.attend_full(p, GQA, x, pos)
    later = gqa_ops.attend_full(p, GQA, x.at[9].add(1.0), pos)
    assert np.allclose(base[:8], later[:8], atol=1e-6)       # blocks before
    assert float(jnp.abs(base[8] - later[8]).max()) > 1e-4   # its own block


def test_renormalised_gates_match_the_reference(ref):
    p = seeded_params(small_spec())["blocks"][0]["moe"]
    x = jnp.asarray(np.random.default_rng(3).standard_normal((70, 64)),
                    jnp.float32)
    idx, gates = moe_ops.route(p, MOE, x)
    assert np.allclose(np.asarray(gates).sum(axis=-1), 1.0, atol=1e-6)
    dense = np.zeros((70, 16), np.float32)
    np.put_along_axis(dense, np.asarray(idx), np.asarray(gates), axis=1)
    close(dense, ref.route(p, x, DM))
    y, counted = moe_ops.moe(p, MOE, x, jnp.ones(70, bool))
    close(y, ref.experts(p, x, DM))
    assert int(counted["expert_load"].sum()) == 70 * 4
    assert int(counted["zero_picks"]) == 0
    # LongCat's gates are its picks' own probabilities, as they were
    plain = dataclasses.replace(MOE, norm_topk=False, scale=6.0)
    _, g = moe_ops.route(p, plain, x)
    prob = jax.nn.softmax(x @ p["w_r"], axis=-1)
    close(g, 6.0 * jnp.take_along_axis(prob, idx, axis=-1))


@pytest.mark.parametrize("T", [1, 32, 64])
def test_a_small_forward_with_renormalised_gates_matches_the_reference(
        ref, T):
    """Every expert held, no zero-compute ones, gates renormalised over the
    picks: a block forward's expert layer (tokens that fit one tile, every
    row through each touched expert) against the tile loop and the float32
    reference."""
    p = seeded_params(small_spec())["blocks"][0]["moe"]
    x = jnp.asarray(np.random.default_rng(30 + T).standard_normal((T, 64)),
                    jnp.float32)
    valid = jnp.ones(T, bool)
    idx, gates = moe_ops.route(p, MOE, x)
    y, counts = moe_ops.experts_streamed(p, MOE, x, idx, gates, valid)
    y_tiles, counts_tiles = moe_ops.experts_sorted(p, MOE, x, idx, gates,
                                                   valid)
    close(y, y_tiles, 1e-6)
    close(y, ref.experts(p, x, DM))
    assert counts.tolist() == counts_tiles.tolist()
    assert int(counts.sum()) == T * MOE.top_k
    whole, counted = moe_ops.moe(p, MOE, x, valid)
    close(whole, y, 1e-6)
    assert int(counted["zero_picks"]) == 0


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("T", [65, 128, 512])
def test_a_chunk_with_renormalised_gates_matches_the_reference(ref, T, dtype):
    """Every expert held, gates renormalised over the picks, a forward of
    more than one tile: the grouped kernel over the sorted rows against the
    tile loop and the float32 reference; every pair is here, so a chunk of
    512 tokens sends 2,048 rows through the 16 experts."""
    p = dict(seeded_params(small_spec())["blocks"][0]["moe"])
    p.update({k: p[k].astype(dtype) for k in ("w_g", "w_u", "w_d")})
    x = jnp.asarray(np.random.default_rng(40 + T).standard_normal((T, 64)),
                    jnp.float32)
    valid = jnp.ones(T, bool)
    idx, gates = moe_ops.route(p, MOE, x)
    assert np.allclose(np.asarray(gates).sum(axis=-1), 1.0, atol=1e-6)
    y, counts = moe_ops.experts_grouped(p, MOE, x, idx, gates, valid)
    y_tiles, counts_tiles = moe_ops.experts_sorted(p, MOE, x, idx, gates,
                                                   valid)
    close(y, y_tiles, 1e-6)
    as_f32 = {k: v.astype(jnp.float32) for k, v in p.items()}
    close(y, ref.experts(as_f32, x, DM),
          2e-4 if dtype == jnp.float32 else 2e-2)
    assert counts.tolist() == counts_tiles.tolist()
    assert int(counts.sum()) == T * MOE.top_k
    whole, counted = moe_ops.moe(p, MOE, x, valid)
    close(whole, y, 1e-6)
    assert int(counted["zero_picks"]) == 0
    assert int(moe_ops.row_tiles(counts).sum()) >= 16


@pytest.mark.parametrize("chunk", [16, 128])
def test_every_block_forward_takes_the_small_forwards_form(chunk):
    """``block_dense_expert_runs`` equals ``block_runs`` (4 rows x a block of
    4 fit one tile); a chunk of 128 positions takes the tile loop, one of 16
    does not."""
    model, _ = small_model(STATIC, chunk=chunk, capacity=128)
    model.answer(query(history(2, 22), 8))
    stats = model.stats()
    assert stats["block_runs"] > 0 and stats["prefill_runs"] > 0
    assert stats["block_dense_expert_runs"] == stats["block_runs"]
    assert stats["prefill_dense_expert_runs"] == (
        stats["prefill_runs"] if chunk <= moe_ops.TILE else 0)
    assert stats["extend_dense_expert_runs"] == stats["extend_runs"] == 0
    # a chunk above a tile counts its products of sorted rows: one a touched
    # expert here (22 tokens x 4 picks over 16 experts); a block forward none
    assert stats["block_expert_row_tiles"] == 0
    assert stats["prefill_expert_row_tiles"] == (
        0 if chunk <= moe_ops.TILE else stats["prefill_experts_touched"])
    assert (stats["prefill_experts_touched"] > 0)


def test_programs_prefill_and_blocks_give_the_reference_logits(ref):
    """Scores, through the program's own head: a history of 22 (five whole
    blocks, two items left over) prefilled in two chunks, then the first
    block denoised: the best item, its logit and its probability at every
    position are the full forward's."""
    spec = small_spec()
    params = seeded_params(spec)
    programs = StackPrograms(spec, params, SHAPE)
    weights = as_reference(params)
    hist = history(4, 22)
    programs.prefill(np.array(hist[:16]), 1, 0)
    _, counted = programs.prefill(np.array(hist[16:20]), 1, 16)
    assert int(counted["tokens"]) == 4
    block = hist[20:] + [MASK, MASK]
    decided, counted = jax.device_get(
        programs.block([(np.array(block), 1, 20, True, 1)]))
    logits = ref.forward(weights, hist[:20] + block, DM)
    one_a_forward = dict(as_dict(STATIC), denoising_steps=4)
    best, score, conf, chosen = ref.decide(logits, [2, 3], one_a_forward, 0)
    assert len(chosen) == 1
    assert int(counted["tokens"]) == 4
    assert counted["expert_load"].shape == (2, 16)
    close(decided["score"][0], score, 5e-4)
    close(decided["confidence"][0], conf, 2e-3)
    assert decided["picked"][0].tolist() == [i in chosen for i in range(4)]
    assert decided["ids"][0].tolist() == [
        int(best[i]) if i in chosen else block[i] for i in range(4)]
    # a commit row decides nothing; a padding row reaches no expert
    decided, counted = jax.device_get(
        programs.block([(np.array(block), 1, 20, False, 0)]))
    assert not decided["picked"].any()
    assert decided["ids"][0].tolist() == block


def small_model(gen, **shape):
    spec = small_spec(gen)
    params = seeded_params(spec)
    items = BiMap.from_vocab([f"i{r}" for r in range(N_ITEMS)])
    model = SeqStackModel(spec, params, items,
                          dataclasses.replace(SHAPE, **shape))
    return model, as_reference(params)


def query(rows, generate):
    return {"items": [f"i{r}" for r in rows], "generate": generate}


def same_slate(ticket, want, tol=2e-3):
    got = ticket.result + ticket.tail
    assert [int(i[1:]) for i, *_ in got] == [w[0] for w in want]
    assert [s for *_, s in got] == [w[3] for w in want]          # the steps
    close([g[1] for g in got], [w[1] for w in want], tol)        # logits
    close([g[2] for g in got], [w[2] for w in want], 5 * tol)    # confidence


@pytest.mark.parametrize("gen", [STATIC, DYNAMIC], ids=lambda g: g.rule)
@pytest.mark.parametrize("n_history", [24, 21, 18, 31])
def test_a_whole_slate_follows_the_reference_generation(ref, gen, n_history):
    """The engine's steps (prefill chunks, known blocks, denoise and commit
    forwards through the cache) against the plain loop, every forward of
    which is a full forward: same items, same forward for each, close
    logits and confidences; the left-over items of the history open the
    first block."""
    model, weights = small_model(gen)
    hist = history(5 + n_history, n_history)
    ticket = model.answer(query(hist, 10))
    want, forwards = ref.block_diffusion_generate(
        weights, hist, 10, as_dict(gen), DM)
    assert len(ticket.result) == 10
    assert len(ticket.tail) == len(want) - 10 == (-(n_history + 10)) % 4
    same_slate(ticket, want)
    assert ticket.forwards == len(forwards)
    assert MASK not in [w[0] for w in want]
    assert ref.rebuild(n_history, 10, want, as_dict(gen)) is not None
    stats = model.stats()
    assert stats["slates_done"] == 1
    assert stats["positions_unmasked"] == len(want)
    assert stats["denoise_rows"] == sum(f["kind"] == "denoise"
                                        for f in forwards)
    # the slot holds the history and the slate, every block committed
    assert model.cache.rows[ticket.slot].tolist() == hist + [
        w[0] for w in want]


def test_the_dynamic_rule_unmasks_by_threshold_and_the_static_one_by_count(
        ref):
    """The two rules differ on the same weights and history: the dynamic
    one takes every position over its threshold at once."""
    hist = history(9, 24)
    slates = {}
    for gen in (STATIC, DYNAMIC):
        model, _ = small_model(gen)
        ticket = model.answer(query(hist, 12))
        slates[gen.rule] = ticket
        per_forward = {}
        for *_, step in ticket.result:
            per_forward[step] = per_forward.get(step, 0) + 1
        if gen is STATIC:
            assert set(per_forward.values()) == {2}
            assert ticket.forwards == 9            # 3 blocks x (2 + commit)
        else:
            assert max(per_forward.values()) > 1   # one forward, several
            assert min(per_forward.values()) == 1  # and the minimum of one
    assert (slates[STATIC.rule].forwards
            != slates[DYNAMIC.rule].forwards)


def test_a_follow_up_over_reused_blocks_equals_the_same_query_cold(ref):
    """The second query of a session carries the history and what the user
    took since (not the slate): it reuses the history's whole blocks, writes
    over the slate the slot still holds, and answers as from an empty
    cache. Reuse rounds DOWN to a block boundary."""
    model, weights = small_model(STATIC)
    hist = history(11, 22)
    first = model.answer(query(hist, 8))
    assert (model.cache.hit_tokens, model.cache.miss_tokens) == (0, 22)
    grown = hist + history(12, 5)
    warm = model.answer(query(grown, 8))
    assert warm.slot == first.slot
    # 22 shared positions, 20 of them in whole blocks
    assert model.cache.hit_tokens == 20
    cold_model, _ = small_model(STATIC)
    cold = cold_model.answer(query(grown, 8))
    assert warm.result == cold.result and warm.tail == cold.tail
    want, _ = ref.block_diffusion_generate(weights, grown, 8,
                                           as_dict(STATIC), DM)
    same_slate(warm, want)
    # the same query again: everything it carries is held, but for the last
    # position (the rule of every cached engine), rounded down to a block
    again = model.answer(query(grown, 8))
    assert again.result == warm.result
    assert model.cache.hit_tokens == 20 + 24


def test_cancel_in_mid_slate_releases_committed_blocks_only():
    model, _ = small_model(STATIC)
    hist = history(13, 18)
    ticket = model.begin(query(hist, 8))
    # 16 known positions: one prefill chunk. Block 4 (two items of the
    # history, two masks) takes one denoise forward and its commit; the
    # third forward is block 5's first
    while ticket.forwards < 3:
        model.step([ticket])
    assert ticket.done == 20 and ticket.result is None
    assert (ticket.block != MASK).sum() == 2
    model.cancel(ticket)
    held = model.cache.rows[ticket.slot]
    assert len(held) == 20 and held[:18].tolist() == hist
    assert held[18:].tolist() == [ticket.found[p][0] for p in (18, 19)]
    assert not model.cache.busy[ticket.slot]


def test_a_stack_that_generates_refuses_a_query_that_asks_for_none():
    model, _ = small_model(STATIC)
    with pytest.raises(ValueError, match="generate"):
        model.begin({"items": ["i1", "i2"], "num": 5})
    with pytest.raises(ValueError, match="generate"):
        model.begin(query(history(1, 8), 96))    # no room in a slot
    assert model.begin(query([10 ** 6], 4)).result == []
    # the mask row is never part of a history
    assert MASK not in model.resolve(query([1, MASK, 2], 4)).tolist()


def test_stack_programs_refuse_mixed_or_uncached_mixers():
    spec = small_spec()
    params = seeded_params(spec)
    mixed = dataclasses.replace(spec, blocks=(
        spec.blocks[0], dataclasses.replace(spec.blocks[0], mixer="mha")))
    with pytest.raises(ValueError, match="per-session"):
        StackPrograms(mixed, params, SHAPE)
    with pytest.raises(ValueError, match="generation"):
        StackPrograms(dataclasses.replace(spec, generation=None), params,
                      SHAPE)


# -- through the engine server ------------------------------------------------

def post(server, rows, generate):
    req = urllib.request.Request(
        f"http://127.0.0.1:{server.port}/queries.json",
        data=json.dumps(query(rows, generate)).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as resp:
        return json.loads(resp.read())


def test_two_tickets_generate_while_a_third_prefills_through_the_worker(ref):
    """Three queries at once through ``POST /queries.json`` and the step
    worker: two short histories generate in the same block forwards while a
    long one is prefilled chunk by chunk; each answer is the reference's."""
    spec = small_spec()
    params = seeded_params(spec)
    server, _, _ = deploy_small(n_slots=3, capacity=96, stack=(spec, params),
                                n_items=N_ITEMS, extend_len=8, gen_batch=4)
    try:
        model = server.deployment.models[0]
        assert server._batcher.histogram()["stepwise"] is True
        weights = as_reference(params)
        hists = [history(21, 10), history(22, 7), history(23, 70)]
        answers = [None] * 3

        def ask(i):
            answers[i] = post(server, hists[i], 6)

        threads = [threading.Thread(target=ask, args=(i,)) for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        for hist, got in zip(hists, answers):
            want, _ = ref.block_diffusion_generate(
                weights, hist, 6, as_dict(STATIC), DM)
            entries = got["itemScores"] + got.get("blockTail", [])
            assert len(got["itemScores"]) == 6
            assert set(entries[0]) == {"item", "score", "confidence", "step"}
            assert [int(e["item"][1:]) for e in entries] == [
                w[0] for w in want]
            assert [e["step"] for e in entries] == [w[3] for w in want]
            close([e["score"] for e in entries], [w[1] for w in want], 2e-3)
        stats = model.stats()
        assert stats["slates_done"] == 3
        # 68 known positions: four chunks of 16, the last block as a known
        # row of a block forward
        assert stats["prefill_runs"] == 4
        # rows shared forwards: fewer block forwards than rows
        assert stats["block_runs"] < (stats["denoise_rows"]
                                      + stats["commit_rows"])
        hist = server._batcher.histogram()
        assert hist["answered"] == 3
        # most steps finished nobody
        assert hist["batchSizeHistogram"].get("0", 0) > 3
        # a query without "generate" is refused, the server lives on
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(urllib.request.Request(
                f"http://127.0.0.1:{server.port}/queries.json",
                data=json.dumps({"items": ["i1"], "num": 3}).encode(),
                headers={"Content-Type": "application/json"}), timeout=60)
        assert len(post(server, hists[0], 2)["itemScores"]) == 2
    finally:
        server.stop()
