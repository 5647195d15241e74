"""The latent-attention expert stack against the plain reference
(benchmarks/reference/longcat_forward.py, which imports nothing of the
program), at a small size on the CPU: hidden 64, 4 heads, 2 double-layers,
16 routed + 8 zero-compute experts, top-4, seeded float32 weights."""

import dataclasses
import importlib.util
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.ops import mla as mla_ops
from predictionio_tpu.ops import moe as moe_ops
from predictionio_tpu.ops.pallas import expert_stream
from predictionio_tpu.ops.sessionrec import (
    BlockSpec, ServeShape, StackPrograms, StackSpec, apply_block, init_stack)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def ref():
    path = os.path.join(REPO, "benchmarks", "reference", "longcat_forward.py")
    spec = importlib.util.spec_from_file_location("longcat_forward_ref", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


MLA = mla_ops.MLADims(dim=64, heads=4, d_nope=16, d_rope=8, d_v=16,
                      q_rank=32, kv_rank=24, rope_theta=1e4)
MOE = moe_ops.MoEDims(dim=64, expert_dim=32, n_routed=16, n_zero=8, top_k=4,
                      scale=3.0, held=(4, 4))
N_ITEMS = 50


def small_spec(moe=MOE, layers=2):
    return StackSpec(
        dim=64, ffn_dim=128, heads=4, positions="rope", eps=1e-5,
        tied_head=False, mla=MLA, moe=moe,
        blocks=(BlockSpec(mixer="mla", ffn="swiglu", norm="rmsnorm",
                          topology="scmoe"),) * layers)


def seeded_params(spec, seed=0):
    """init_stack's weights with the norms and the selection bias made
    non-trivial, so that a part that skipped them would show."""
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
    for block in params["blocks"]:
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


def ref_dims(spec):
    m, e = spec.mla, spec.moe
    return {"D": spec.dim, "H": m.heads, "dn": m.d_nope, "dr": m.d_rope,
            "dv": m.d_v, "rq": m.q_rank, "rkv": m.kv_rank,
            "theta": m.rope_theta, "eps": spec.eps, "n_routed": e.n_routed,
            "n_zero": e.n_zero, "top_k": e.top_k, "scale": e.scale,
            "held": e.held, "scale_q": m.scale_q, "scale_kv": m.scale_kv}


def close(a, b, tol=2e-4):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    scale = max(float(np.abs(b).max()), 1e-6)
    assert float(np.abs(a - b).max()) <= tol * scale, (
        float(np.abs(a - b).max()), scale)


def test_mla_full_and_chunked_prefill_match_the_reference(ref):
    p = seeded_params(small_spec())["blocks"][0]["mixer_a"]
    x = jnp.asarray(np.random.default_rng(1).standard_normal((40, 64)),
                    jnp.float32)
    pos = jnp.arange(40, dtype=jnp.int32)
    want = ref.mla(p, x, pos, ref_dims(small_spec()))
    close(mla_ops.attend_full(p, MLA, x[None], pos[None])[0], want)
    # chunks of 16 against a slot's cache, blocks of 8, starting mid-block
    cache = jnp.zeros((2, 64, MLA.latent), jnp.float32)
    outs, at = [], 0
    for n in (12, 16, 12):
        chunk = jnp.zeros((16, 64), jnp.float32).at[:n].set(x[at:at + n])
        out, cache, _ = mla_ops.prefill_chunk(p, MLA, chunk, at, cache, 1, 8)
        outs.append(out[:n])
        at += n
    close(jnp.concatenate(outs), want)


def test_mla_extension_over_cached_latents_matches_the_reference(ref):
    p = seeded_params(small_spec())["blocks"][0]["mixer_b"]
    rng = np.random.default_rng(2)
    xs = [jnp.asarray(rng.standard_normal((n, 64)), jnp.float32)
          for n in (21, 9)]
    dm = ref_dims(small_spec())
    want = [ref.mla(p, x, jnp.arange(len(x)), dm) for x in xs]
    cache = jnp.zeros((3, 40, MLA.latent), jnp.float32)
    # all but the last 3 / 2 positions prefilled, the rest extended together
    new = (3, 2)
    for slot, (x, n) in enumerate(zip(xs, new)):
        head = len(x) - n
        chunk = jnp.zeros((24, 64), jnp.float32).at[:head].set(x[:head])
        _, cache, _ = mla_ops.prefill_chunk(p, MLA, chunk, 0, cache, slot, 8)
    batch = jnp.stack([jnp.zeros((4, 64)).at[:n].set(x[len(x) - n:])
                       for x, n in zip(xs, new)])
    pos0 = jnp.array([len(x) - n for x, n in zip(xs, new)], jnp.int32)
    pos = pos0[:, None] + jnp.arange(4)[None]
    out, _, _ = mla_ops.extend(p, MLA, batch, pos, cache,
                               jnp.array([0, 1]), jnp.int32(4), 8)
    for b, (w, n) in enumerate(zip(want, new)):
        close(out[b, :n], w[len(w) - n:])


@pytest.mark.parametrize("skew", ["uniform", "one_expert"])
def test_expert_layer_matches_the_reference_and_drops_no_token(ref, skew):
    spec = small_spec()
    p = dict(seeded_params(spec)["blocks"][0]["moe"])
    if skew == "one_expert":        # every token picks held expert 5
        p["bias"] = p["bias"].at[5].set(10.0)
    # 150 tokens: a crowded expert's group runs over several tiles
    x = jnp.asarray(np.random.default_rng(3).standard_normal((150, 64)),
                    jnp.float32)
    valid = jnp.ones(150, bool)
    y, counted = moe_ops.moe(p, MOE, x, valid)
    routed, zero, _ = ref.moe_parts(p, x, ref_dims(spec), MOE.held)
    close(y, routed + zero)
    if skew == "one_expert":
        assert int(counted["expert_load"][1]) == 150 > 2 * moe_ops.TILE
    # a padding token reaches no expert and counts nowhere
    y2, counted2 = moe_ops.moe(p, MOE, x, valid.at[-5:].set(False))
    close(y2[:-5], (routed + zero)[:-5])
    assert int(counted2["expert_load"].sum()) < int(
        counted["expert_load"].sum()) or skew == "uniform"


@pytest.mark.parametrize("form", ["experts_sorted", "experts_streamed"])
def test_the_shares_of_the_experts_add_up_to_the_uncut_layer(ref, form):
    """Four shares of the 16 routed experts, the zero-compute part counted
    once: the whole layer, in the program and in the reference alike, by
    the tile loop and by the small forward's form (29 tokens fit a tile)."""
    whole = dataclasses.replace(MOE, held=(0, 16))
    p = moe_ops.init(jax.random.PRNGKey(4), whole, bias_std=2e-3)
    x = jnp.asarray(np.random.default_rng(4).standard_normal((29, 64)),
                    jnp.float32)
    valid = jnp.ones(29, bool)
    dm = ref_dims(small_spec())
    uncut_r, uncut_z, _ = ref.moe_parts(p, x, dm, (0, 16))
    idx, gates = moe_ops.route(p, whole, x)
    total = 0.0
    for e0 in range(0, 16, 4):
        share = dataclasses.replace(MOE, held=(e0, 4))
        ps = dict(p, **{k: p[k][e0:e0 + 4] for k in ("w_g", "w_u", "w_d")})
        routed, _ = getattr(moe_ops, form)(ps, share, x, idx, gates, valid)
        ref_routed, ref_zero, _ = ref.moe_parts(ps, x, dm, (e0, 4))
        close(routed, ref_routed)
        close(ref_zero, uncut_z)
        total = total + routed
    close(total, uncut_r)
    y, _ = moe_ops.moe(p, whole, x, valid)
    close(y, uncut_r + uncut_z)


def small_forward_case(case, T, seed=6):
    """One expert layer's weights, ``T`` tokens and their ``valid`` for a
    named routing: ``uniform``; ``padding`` (the second half of the rows, all
    of one row); ``one_expert`` (every token picks held expert 5);
    ``none_held`` (no token picks a held expert: zero rounds)."""
    p = dict(seeded_params(small_spec())["blocks"][0]["moe"])
    if case == "one_expert":
        p["bias"] = p["bias"].at[5].set(10.0)
    if case == "none_held":
        p["bias"] = p["bias"].at[4:8].set(-10.0)
    x = jnp.asarray(np.random.default_rng(seed + T).standard_normal((T, 64)),
                    jnp.float32)
    valid = jnp.ones(T, bool)
    if case == "padding":
        valid = valid.at[T // 2:].set(False)
    return p, x, valid


@pytest.mark.parametrize("T", [1, 32, 64])
@pytest.mark.parametrize("case", ["uniform", "padding", "one_expert",
                                  "none_held"])
def test_a_small_forward_runs_every_row_through_the_touched_experts(
        ref, case, T):
    """A forward whose tokens fit one tile (the kernel under the interpreter
    here) against the tile loop and against the float32 reference, with
    zero-compute experts and a strict share of the routed ones held."""
    p, x, valid = small_forward_case(case, T)
    assert moe_ops.small_forward(T)
    idx, gates = moe_ops.route(p, MOE, x)
    y, counts = moe_ops.experts_streamed(p, MOE, x, idx, gates, valid)
    y_tiles, counts_tiles = moe_ops.experts_sorted(p, MOE, x, idx, gates,
                                                   valid)
    close(y, y_tiles, 1e-6)
    assert counts.tolist() == counts_tiles.tolist()
    routed, zero, _ = ref.moe_parts(p, x, ref_dims(small_spec()), MOE.held)
    real = np.asarray(valid)[:, None]
    # a padding row reaches no expert and counts nowhere
    close(y, np.where(real, routed, 0.0))
    assert int(counts.sum()) <= int(real.sum()) * MOE.top_k
    whole, counted = moe_ops.moe(p, MOE, x, valid)
    close(np.where(real, whole, 0.0), np.where(real, routed + zero, 0.0))
    assert counted["expert_load"].tolist() == counts.tolist()
    if case == "one_expert":
        assert int(counts[1]) == int(real.sum())
    if case == "none_held":
        assert not counts.any() and not np.asarray(y).any()


@pytest.mark.parametrize("T", [32, 64])
def test_a_huge_row_that_picked_no_expert_reaches_no_sum(T):
    """The rows that did not pick an expert are selected out, not multiplied
    by 0: a padding row of 1e30 (its products overflow) reads 0 and leaves
    every other row's output as it was."""
    p, x, valid = small_forward_case("uniform", T)
    idx, gates = moe_ops.route(p, MOE, x)
    want, _ = moe_ops.experts_streamed(p, MOE, x, idx, gates, valid)
    x = x.at[3].set(1e30)
    valid = valid.at[3].set(False)
    y, counts = moe_ops.experts_streamed(p, MOE, x, idx, gates, valid)
    assert np.isfinite(np.asarray(y)).all()
    assert not np.asarray(y)[3].any()
    keep = np.arange(T) != 3
    close(np.asarray(y)[keep], np.asarray(want)[keep], 1e-6)
    e0, n = MOE.held
    assert int(counts.sum()) == int(
        ((idx[keep] >= e0) & (idx[keep] < e0 + n)).sum())


def plain_experts(p, dims, x, idx, gates, valid):
    """What the held experts add, the plain way: for each held expert the
    rows that picked it, through its three matrices in float32 numpy, times
    their gates, added to their tokens. No sort, no tile, no kernel."""
    e0, n = dims.held
    x, gates = np.asarray(x, np.float32), np.asarray(gates, np.float32)
    idx = np.asarray(idx)
    w_g, w_u, w_d = (np.asarray(p[k], np.float32)
                     for k in ("w_g", "w_u", "w_d"))
    y = np.zeros((x.shape[0], dims.dim), np.float32)
    counts = np.zeros(n, np.int64)
    for t, k in zip(*np.nonzero((idx >= e0) & (idx < e0 + n)
                                & np.asarray(valid)[:, None])):
        e = idx[t, k] - e0
        a = x[t] @ w_g[e]
        y[t] += gates[t, k] * ((a / (1 + np.exp(-a)) * (x[t] @ w_u[e]))
                               @ w_d[e])
        counts[e] += 1
    return y, counts


def chunk_case(case, T, dtype, seed=9):
    """:func:`small_forward_case` for a forward of more than one tile, its
    expert weights in ``dtype``; ``padding`` pads the tail."""
    p, x, valid = small_forward_case(case, T, seed)
    p = dict(p, **{k: p[k].astype(dtype) for k in ("w_g", "w_u", "w_d")})
    if case == "padding":
        valid = jnp.arange(T) < T - T // 3
    return p, x, valid


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("T", [65, 128, 512])
@pytest.mark.parametrize("case", ["uniform", "padding", "one_expert",
                                  "none_held"])
def test_a_chunk_runs_its_sorted_rows_through_the_grouped_kernel(
        case, T, dtype):
    """A forward of more than one tile (the kernel under the interpreter
    here) against the tile loop, the same products summed in the same order,
    and against the plain per-expert sum in float32; no capacity: every
    token on one expert is more products under one read."""
    p, x, valid = chunk_case(case, T, dtype)
    assert not moe_ops.small_forward(T)
    idx, gates = moe_ops.route(p, MOE, x)
    y, counts = moe_ops.experts_grouped(p, MOE, x, idx, gates, valid)
    y_tiles, counts_tiles = moe_ops.experts_sorted(p, MOE, x, idx, gates,
                                                   valid)
    close(y, y_tiles, 1e-6)
    assert counts.tolist() == counts_tiles.tolist()
    want, n_rows = plain_experts(p, MOE, x, idx, gates, valid)
    close(y, want, 2e-4 if dtype == jnp.float32 else 2e-2)
    assert counts.tolist() == n_rows.tolist()
    real = np.asarray(valid)
    assert not np.asarray(y)[~real].any()
    whole, counted = moe_ops.moe(p, MOE, x, valid)
    assert counted["expert_load"].tolist() == counts.tolist()
    if case == "one_expert":
        assert int(counts[1]) == int(real.sum())
        assert int(moe_ops.row_tiles(counts)[1]) == -(-int(real.sum()) // 128)
    if case == "none_held":
        assert not counts.any() and not np.asarray(y).any()
        assert not moe_ops.row_tiles(counts).any()


#: rows of each of 12 held experts' groups: exactly 1-4 products of 128 rows,
#: one row over and one row under, one row, none
GROUP_LENGTHS = [128, 256, 384, 512, 129, 127, 1, 0, 257, 255, 0, 383]


def grouped_case(lengths, dtype=jnp.float32, seed=12):
    """One pick a token, dealt so that held expert ``e`` gets ``lengths[e]``
    tokens, the tokens in a shuffled order; two absent experts get some
    too."""
    n = len(lengths)
    dims = dataclasses.replace(MOE, n_routed=n + 2, n_zero=0, top_k=1,
                               held=(1, n), scale=1.0)
    rng = np.random.default_rng(seed)
    picks = np.concatenate([np.full(c, e + 1) for e, c in enumerate(lengths)]
                           + [np.full(5, 0), np.full(7, n + 1)])
    rng.shuffle(picks)
    T = len(picks)
    p = moe_ops.init(jax.random.PRNGKey(seed), dims, dtype)
    x = jnp.asarray(rng.standard_normal((T, 64)), jnp.float32)
    idx = jnp.asarray(picks[:, None], jnp.int32)
    gates = jnp.asarray(rng.uniform(0.1, 1.0, (T, 1)), jnp.float32)
    return dims, p, x, idx, gates, jnp.ones(T, bool)


def test_groups_of_whole_products_and_of_a_row_more_or_less():
    """Group lengths on both sides of a product's 128 rows, a group of one
    row and groups of none, among pairs of absent experts: every row of
    every group is summed once, by the products counted."""
    assert expert_stream.GROUP_ROWS == 128
    dims, p, x, idx, gates, valid = grouped_case(GROUP_LENGTHS)
    y, counts = moe_ops.experts_grouped(p, dims, x, idx, gates, valid)
    assert counts.tolist() == GROUP_LENGTHS
    assert moe_ops.row_tiles(counts).tolist() == [
        1, 2, 3, 4, 2, 1, 1, 0, 3, 2, 0, 3]
    want, _ = plain_experts(p, dims, x, idx, gates, valid)
    close(y, want)
    y_tiles, _ = moe_ops.experts_sorted(p, dims, x, idx, gates, valid)
    close(y, y_tiles, 1e-6)
    # a token of an absent expert gets nothing here
    absent = (np.asarray(idx)[:, 0] == 0) | (np.asarray(idx)[:, 0] == 13)
    assert absent.sum() == 12 and not np.asarray(y)[absent].any()


@pytest.mark.parametrize("rows", [8, 16])
def test_the_rows_of_a_product_change_no_sum(monkeypatch, rows):
    """The same forward with fewer rows a product (more products under one
    read of an expert): the same sum."""
    p, x, valid = chunk_case("uniform", 128, jnp.float32)
    idx, gates = moe_ops.route(p, MOE, x)
    want, counts = moe_ops.experts_grouped(p, MOE, x, idx, gates, valid)
    monkeypatch.setattr(expert_stream, "GROUP_ROWS", rows)
    y, _ = moe_ops.experts_grouped(p, MOE, x, idx, gates, valid)
    close(y, want, 1e-6)
    assert int(moe_ops.row_tiles(counts).sum()) == int(
        (-(-np.asarray(counts) // rows)).sum()) > int((counts > 0).sum())


@pytest.mark.parametrize("T", [65, 512])
def test_an_experts_rows_pass_under_each_of_its_column_chunks(monkeypatch, T):
    """``expert_dim`` cut into two column chunks (LongCat's is cut into
    four): an expert's rows run under each chunk and the chunks' products
    add up, in another order than the uncut one's."""
    p, x, valid = chunk_case("padding", T, jnp.float32)
    idx, gates = moe_ops.route(p, MOE, x)
    want, _ = moe_ops.experts_sorted(p, MOE, x, idx, gates, valid)
    monkeypatch.setattr(expert_stream, "chunk_of",
                        lambda dim, expert_dim, itemsize: expert_dim // 2)
    text = str(jax.make_jaxpr(lambda *a: moe_ops.experts_grouped(
        p, MOE, *a))(x, idx, gates, valid))
    assert "grid=(4, 2)" in text          # touched experts x column chunks
    y, _ = moe_ops.experts_grouped(p, MOE, x, idx, gates, valid)
    close(y, want, 1e-6)


@pytest.mark.parametrize("T", [65, 512])
def test_a_huge_padding_row_of_a_chunk_reaches_no_sum(T):
    """Padding rows are in no group: a row of 1e30 among them (its products
    overflow) is never gathered, reads 0 and leaves every other row's
    output as it was."""
    p, x, valid = chunk_case("padding", T, jnp.float32)
    idx, gates = moe_ops.route(p, MOE, x)
    want, _ = moe_ops.experts_grouped(p, MOE, x, idx, gates, valid)
    x = x.at[T - 2].set(1e30)
    assert not bool(valid[T - 2])
    y, counts = moe_ops.experts_grouped(p, MOE, x, idx, gates, valid)
    assert np.isfinite(np.asarray(y)).all()
    assert np.array_equal(np.asarray(y), np.asarray(want))
    e0, n = MOE.held
    here = (np.asarray(idx) >= e0) & (np.asarray(idx) < e0 + n)
    assert int(counts.sum()) == int(here[np.asarray(valid)].sum())


@pytest.mark.parametrize("T", [1, 32, 64, 65, 512])
def test_the_forwards_shape_chooses_the_expert_layers_form(T):
    """Up to one tile of tokens the streaming kernel and no sort; beyond it
    the sort and the grouped kernel over the sorted rows. ONE kernel either
    way, no loop over tiles and no scatter-add left in the program, and
    nothing but the shape is asked."""
    p, x, valid = small_forward_case("uniform", T)
    text = str(jax.make_jaxpr(
        lambda p, x, valid: moe_ops.moe(p, MOE, x, valid))(p, x, valid))
    small = T <= moe_ops.TILE
    assert moe_ops.small_forward(T) == small
    assert text.count("pallas_call") == 1
    assert ("name=expert_stream" in text) == small
    assert ("name=expert_groups" in text) == (not small)
    assert (" sort[" in text) == (not small)
    # the kernel's own loops are inside the pallas_call's jaxpr: the text
    # before it holds no while; the one scatter-add left counts the pairs
    # of each expert ([n + 1] integers), no row of ``dim`` values
    assert "while[" not in text[:text.index("pallas_call")]
    assert text.count("scatter-add") == (0 if small else 1)
    assert not re.search(r"f32\[\d+,64\] = scatter-add", text)


@pytest.mark.parametrize("chunk", [16, 128])
def test_the_model_counts_the_runs_that_took_the_small_forwards_form(chunk):
    """``<kind>_dense_expert_runs``: every run of a program whose tokens fit
    one tile (the extension program's 2 x 4; a chunk of 16), none of a
    program whose tokens do not (a chunk of 128)."""
    from predictionio_tpu.data.bimap import BiMap
    from predictionio_tpu.models.sessionrec import SeqStackModel

    spec = small_spec()
    model = SeqStackModel(
        spec, seeded_params(spec),
        BiMap.from_vocab([f"i{r}" for r in range(N_ITEMS)]),
        dataclasses.replace(SHAPE, capacity=128, chunk=chunk))
    rows = np.random.default_rng(8).integers(0, N_ITEMS, size=23).tolist()
    for upto in (20, 23):
        model.answer({"items": [f"i{r}" for r in rows[:upto]], "num": 5})
    stats = model.stats()
    assert stats["prefill_runs"] > 0 and stats["extend_runs"] > 0
    assert stats["extend_dense_expert_runs"] == stats["extend_runs"]
    assert stats["prefill_dense_expert_runs"] == (
        stats["prefill_runs"] if chunk <= moe_ops.TILE else 0)
    assert stats["block_dense_expert_runs"] == stats["block_runs"] == 0
    # products of sorted rows: none in a small forward; at these sizes no
    # group passes a product's 128 rows, so one a touched expert
    assert stats["extend_expert_row_tiles"] == 0
    assert stats["prefill_expert_row_tiles"] == (
        0 if chunk <= moe_ops.TILE else stats["prefill_experts_touched"])


def recording(model, monkeypatch):
    """Every program call's own counters, as the programs return them:
    ``[(kind, counters)]``, filled in as the model runs."""
    calls, programs = [], model.programs()
    for kind in programs.tokens:
        def call(*args, _kind=kind, _call=getattr(programs, kind)):
            result, counted = _call(*args)
            calls.append((_kind, counted))
            return result, counted
        monkeypatch.setattr(programs, kind, call)
    return calls


def test_the_model_counts_the_products_of_sorted_rows(monkeypatch):
    """``<kind>_expert_row_tiles`` against the loads that ran: with 8 rows a
    product (the programs are traced with it) a chunk of 128 positions runs
    more products than it touches experts, one for every 8 rows of a group,
    begun."""
    from predictionio_tpu.data.bimap import BiMap
    from predictionio_tpu.models.sessionrec import SeqStackModel
    monkeypatch.setattr(expert_stream, "GROUP_ROWS", 8)
    spec = small_spec()
    model = SeqStackModel(
        spec, seeded_params(spec),
        BiMap.from_vocab([f"i{r}" for r in range(N_ITEMS)]),
        dataclasses.replace(SHAPE, capacity=256, chunk=128))
    calls = recording(model, monkeypatch)
    rows = np.random.default_rng(8).integers(0, N_ITEMS, size=203).tolist()
    for upto in (200, 203):
        model.answer({"items": [f"i{r}" for r in rows[:upto]], "num": 5})
    stats = model.stats()
    chunks = [np.asarray(counted["expert_load"]) for kind, counted in calls
              if kind == "prefill"]
    assert len(chunks) == stats["prefill_runs"] == 2
    want = sum(int((-(-load // 8)).sum()) for load in chunks)
    assert stats["prefill_expert_row_tiles"] == want
    assert want > stats["prefill_experts_touched"] == sum(
        int((load > 0).sum()) for load in chunks)
    assert stats["extend_runs"] > 0 and stats["extend_expert_row_tiles"] == 0


def test_double_layer_topology_matches_the_reference(ref):
    spec = small_spec()
    p = seeded_params(spec)["blocks"][1]
    x = jnp.asarray(np.random.default_rng(5).standard_normal((23, 64)),
                    jnp.float32)
    pos = jnp.arange(23, dtype=jnp.int32)
    want, _ = ref.double_layer(p, x, pos, ref_dims(spec), MOE.held)

    def mix(which, mp, h):
        return mla_ops.attend_full(mp, MLA, h[None], pos[None])[0]

    def moe(mp, h):
        return moe_ops.moe(mp, MOE, h, jnp.ones(23, bool))[0]

    got = apply_block(spec, spec.blocks[1], p, x, mix, moe=moe)
    close(got, want)


SHAPE = ServeShape(n_slots=3, capacity=64, chunk=16, extend_len=4,
                   extend_batch=2)


def test_chunked_prefill_then_extensions_give_the_reference_scores(ref):
    """Scores, not ranks: a history prefilled in chunks and grown by
    extensions through the cache, against the full forward over it."""
    spec = small_spec()
    params = seeded_params(spec)
    programs = StackPrograms(spec, params, SHAPE)
    weights, dm = as_reference(params), ref_dims(spec)
    rng = np.random.default_rng(6)
    hist = rng.integers(0, N_ITEMS, size=45).tolist()

    def scores(h_last):
        return np.asarray(h_last) @ np.asarray(params["head"]).T

    at = 0
    for n in (16, 16, 5):                 # 37 positions in three chunks
        h, counted = programs.prefill(np.array(hist[at:at + n]), 1, at)
        at += n
    close(scores(h[0]), ref.forward(weights, hist[:37], dm)[0], 5e-4)
    assert int(counted["tokens"]) == 5
    other = rng.integers(0, N_ITEMS, size=9).tolist()
    programs.prefill(np.array(other[:7]), 0, 0)
    # two sessions extended in one step
    h, counted = programs.extend([(hist[37:40], 1, 37), (other[7:9], 0, 7)])
    close(scores(h[0]), ref.forward(weights, hist[:40], dm)[0], 5e-4)
    close(scores(h[1]), ref.forward(weights, other, dm)[0], 5e-4)
    assert int(counted["tokens"]) == 5
    assert counted["expert_load"].shape == (2, 4)
    h, _ = programs.extend([(hist[40:44], 1, 40)])
    close(scores(h[0]), ref.forward(weights, hist[:44], dm)[0], 5e-4)


# -- through the engine server ------------------------------------------------

from predictionio_tpu.core.persistent_model import (  # noqa: E402
    PersistentModel, PersistentModelManifest)

_HANDOVER = {}


class HandedOverStack(PersistentModel):
    """The model reaches the server as a deployment's own loader would hand
    it over: the stored blob is a manifest, ``load`` builds the model."""

    def save(self, instance_id, params, ctx):
        return True

    @classmethod
    def load(cls, instance_id, params, ctx):
        from predictionio_tpu.data.bimap import BiMap
        from predictionio_tpu.models.sessionrec import SeqStackModel

        spec, weights, n_items = _HANDOVER[instance_id]
        items = BiMap.from_vocab([f"i{r}" for r in range(n_items)])
        return SeqStackModel(spec, weights, items, params.shape())


def deploy_small(n_slots=2, capacity=128, stack=None, n_items=N_ITEMS,
                 **shape):
    """``stack``: another ``(spec, params)`` than this file's (tests/
    test_seqgen.py deploys a block-diffusion stack the same way)."""
    import datetime as dt
    import json
    import pickle
    import uuid

    from predictionio_tpu.core.params import EngineParams
    from predictionio_tpu.data.metadata import EngineInstance, Model
    from predictionio_tpu.data.storage import Storage
    from predictionio_tpu.models.sessionrec import SeqStackParams
    from predictionio_tpu.serving.engine_server import EngineServer
    from predictionio_tpu.templates.sessionrec import (
        SeqDataSourceParams, sessionrec_engine)

    spec, params = stack or (small_spec(), None)
    params = seeded_params(spec) if params is None else params
    storage = Storage.from_env({
        "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
        **{f"PIO_STORAGE_REPOSITORIES_{r}_{k}": v
           for r in ("METADATA", "EVENTDATA", "MODELDATA")
           for k, v in (("NAME", r.lower()), ("SOURCE", "MEM"))}})
    ep = EngineParams(
        data_source_params=("", SeqDataSourceParams(app_name="t")),
        preparator_params=("", None),
        algorithm_params_list=[("seqstack", SeqStackParams(**{
            "n_slots": n_slots, "capacity": capacity, "chunk": 16,
            "extend_len": 4, "extend_batch": 2, **shape}))],
        serving_params=("", None)).to_json_dict()
    now = dt.datetime.now(tz=dt.timezone.utc)
    instance = EngineInstance(
        id=uuid.uuid4().hex, status="COMPLETED", start_time=now,
        end_time=now, engine_id="seq_t", engine_version="0",
        engine_variant="default", engine_factory="t", batch="t",
        data_source_params=json.dumps(ep["dataSourceParams"]),
        preparator_params=json.dumps(ep["preparatorParams"]),
        algorithms_params=json.dumps(ep["algorithmParamsList"]),
        serving_params=json.dumps(ep["servingParams"]))
    storage.engine_instances().insert(instance)
    _HANDOVER[instance.id] = (spec, params, n_items)
    manifest = PersistentModelManifest(
        class_name="HandedOverStack", module_name=__name__)
    storage.models().insert(Model(id=instance.id,
                                  models=pickle.dumps([manifest])))
    server = EngineServer(sessionrec_engine(), "seq_t", host="127.0.0.1",
                          port=0, storage=storage,
                          slo_conf={"latency_ms": 60000.0}).start()
    return server, spec, params


def post(server, items, num=5):
    import json
    import urllib.request

    req = urllib.request.Request(
        f"http://127.0.0.1:{server.port}/queries.json",
        data=json.dumps({"items": [f"i{r}" for r in items],
                         "num": num}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as resp:
        return json.loads(resp.read())["itemScores"]


def test_cached_partly_cached_evicted_and_never_cached_answer_alike(ref):
    server, spec, params = deploy_small()
    try:
        weights, dm = as_reference(params), ref_dims(spec)
        model = server.deployment.models[0]
        assert server._batcher.histogram()["stepwise"] is True
        rng = np.random.default_rng(7)
        a = rng.integers(0, N_ITEMS, size=30).tolist()
        b = rng.integers(0, N_ITEMS, size=25).tolist()
        c = rng.integers(0, N_ITEMS, size=41).tolist()

        def check(hist, got):
            logits = ref.forward(weights, hist, dm)[0]
            want = ref.top_k_answer(logits, 5)
            assert [int(s["item"][1:]) for s in got] == [i for i, _ in want]
            close([s["score"] for s in got], [v for _, v in want], 1e-3)

        # no known item: answered at admission, in the algorithm's format
        assert post(server, [10 ** 6]) == []
        check(a, post(server, a))                     # never cached
        assert (model.cache.hit_tokens, model.cache.miss_tokens) == (0, 30)
        check(a, post(server, a))                     # wholly cached
        assert model.cache.hit_tokens == 29
        grown = a + [3, 4]
        check(grown, post(server, grown))             # an extension
        assert model.cache.hit_tokens == 29 + 30
        forked = a[:20] + [7, 8, 9, 10, 11, 12]
        check(forked, post(server, forked))           # partly cached
        assert model.cache.hit_tokens == 29 + 30 + 20
        check(b, post(server, b))
        check(c, post(server, c))                     # two slots: a's is gone
        assert model.cache.evictions >= 1
        hits = model.cache.hit_tokens
        check(grown, post(server, grown))             # prefilled again
        assert model.cache.hit_tokens == hits
    finally:
        server.stop()


def test_an_extension_is_served_within_one_step_of_a_long_prefill():
    server, spec, params = deploy_small(n_slots=3)
    try:
        model = server.deployment.models[0]
        rng = np.random.default_rng(8)
        short = rng.integers(0, N_ITEMS, size=10).tolist()
        post(server, short)                           # its slot is warm
        chunks0 = model.stats()["prefill_runs"]
        long = rng.integers(0, N_ITEMS, size=80).tolist()   # 5 chunks of 16
        t_long = model.begin({"items": [f"i{r}" for r in long], "num": 5})
        model.step([t_long])                          # chunk 1 of 5
        t_ext = model.begin({"items": [f"i{r}" for r in short + [1, 2]],
                             "num": 5})
        assert t_ext.extension and t_ext.remaining == 2
        queued0 = {k: model.counters[k] for k in ("extend_queue_ns",
                                                  "extend_tickets")}
        done = model.step([t_long, t_ext])            # its first step
        step_ended_ns = time.perf_counter_ns()
        assert done == [t_ext] and len(t_ext.result) == 5
        assert t_long.done == 32 and t_long.result is None
        assert model.stats()["prefill_runs"] - chunks0 == 2
        # it waited for no step: its program was launched inside the first
        # step after its admission, and the counters say how long after
        assert model.counters["extend_tickets"] - queued0["extend_tickets"] == 1
        waited = model.counters["extend_queue_ns"] - queued0["extend_queue_ns"]
        assert waited == t_ext.launched_ns - t_ext.admitted_ns
        assert 0 <= waited < step_ended_ns - t_ext.admitted_ns
        while t_long.result is None:
            model.step([t_long])
        assert model.stats()["prefill_runs"] - chunks0 == 5
    finally:
        server.stop()


def test_a_reload_onto_the_other_kind_of_worker_is_refused(monkeypatch):
    """The worker's kind is chosen at start; a reload whose deployment
    needs the other kind must not fall back to it silently."""
    from predictionio_tpu.models.sessionrec import (
        SessionRecAlgorithm, SessionRecParams)
    from predictionio_tpu.serving import engine_server

    server, _, _ = deploy_small()
    try:
        live = server.deployment
        assert live.stepwise
        other = dataclasses.replace(
            live, algorithms=[SessionRecAlgorithm(SessionRecParams())])
        assert not other.stepwise
        monkeypatch.setattr(engine_server, "prepare_deploy",
                            lambda *a, **k: other)
        with pytest.raises(RuntimeError, match="reload refused"):
            server.reload()
        assert server.deployment is live
        assert len(post(server, [1, 2, 3])) == 5
    finally:
        server.stop()
