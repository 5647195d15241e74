"""The references' row-wise cuts (ISSUE 48), on the CPU at the tiny sizes: the
index's selection by counting, a routed expert over its routed rows alone, an
open cut crossed as one row. A file of its own beside ``test_check_budget.py``
(whose helpers it takes) so that the two run side by side."""

import numpy as np
import pytest

from tests.benchmarks.test_check_budget import (  # noqa: F401
    histories, small_blocks, span, world)
from tests.benchmarks.test_seq_cell import harness  # noqa: F401


@pytest.mark.parametrize("k", [1, 7, 16, 64])
@pytest.mark.parametrize("kind", ["random", "ties", "few_above_minus_inf"])
def test_the_counted_selection_names_the_set_lax_top_k_names(world, k, kind):
    """``largest`` (32 counts over the row, no sort) against
    ``jax.lax.top_k``: the same positions, ties to the earlier one, rows
    that hold fewer than ``k`` finite scores among them."""
    import jax
    import jax.numpy as jnp

    ref = world("tiny_glm")[0]
    rng = np.random.default_rng(k)
    s = rng.standard_normal((9, 64)).astype(np.float32)
    if kind == "ties":
        s = np.round(s * 2) / 2          # a handful of values, -0.0 too
        s[0] = 0.0
        s[1, ::2] = -0.0
    elif kind == "few_above_minus_inf":
        for row in range(9):
            s[row, row * 7 + 1:] = -np.inf
    k = min(k, s.shape[1])
    idx = np.asarray(jax.lax.top_k(jnp.asarray(s), k)[1])
    want = np.zeros(s.shape, bool)
    want[np.arange(9)[:, None], idx] = True
    got = np.asarray(jax.jit(lambda v: ref.largest(v, k))(jnp.asarray(s)))
    assert (got == want).all()
    assert (got.sum(axis=1) == k).all()


@pytest.mark.parametrize("tiny", ["tiny_glm", "tiny_axk"])
@pytest.mark.parametrize("routed_rows", [1, 4, 128])
def test_an_expert_over_its_routed_rows_alone_gives_the_whole_products_rows(
        world, monkeypatch, tiny, routed_rows):
    """``gated``: each held expert over the rows whose gate is not 0, a few
    at a time, against every expert over every row times its gate; none
    routed, all routed."""
    import jax
    import jax.numpy as jnp

    ref, weights, dm, _ = world(tiny)
    monkeypatch.setattr(ref, "ROUTED_ROWS", routed_rows)
    moe = next(p["moe"] for p in weights["layers"] if "moe" in p)
    held = moe["w_g"].shape[0]
    rng = np.random.default_rng(routed_rows)
    x = jnp.asarray(rng.standard_normal((29, dm["D"])), jnp.float32)
    for share in (0.0, 0.3, 1.0):
        gates = jnp.asarray(rng.random((29, held))
                            * (rng.random((29, held)) < share), jnp.float32)
        with jax.default_matmul_precision("highest"):
            want = sum(gates[:, e, None] * ref.ffn(
                {k: moe[k][e] for k in ("w_g", "w_u", "w_d")}, x)
                for e in range(held))
            got = ref.gated(moe, x, gates)
        assert np.abs(np.asarray(got - want)).max() <= 1e-6 * max(
            1.0, float(np.abs(np.asarray(want)).max()))
        assert (np.asarray(got)[~np.asarray(gates).any(axis=1)] == 0).all()


# -- a crossed cut recomputed as one row --------------------------------------

@pytest.mark.parametrize("blocked", [False, True])
@pytest.mark.parametrize("which", ["first", "last", "both"])
def test_the_one_row_crossing_gives_the_whole_crossed_forwards_logits(
        world, small_blocks, which, blocked):
    """A cut crossed in the first expert layer, in the last, and in both: the
    last position's row recomputed from the kept keys against a whole forward
    with the same picks forced."""
    ref, weights, dm, vocab = world("tiny_glm")
    if blocked:
        small_blocks("tiny_glm")
    hist, = histories(vocab, 150, seed=9)
    kept = []
    own, _, cuts = ref._forward(weights, hist, dm, reach=203, kept=kept)
    assert len(kept) == len(weights["layers"]) and sorted(cuts) == [1, 2]
    layers = {"first": [1], "last": [2], "both": [1, 2]}[which]
    # the ranking's places 1..top_k: the best pick dropped, the first expert
    # left out taken: another set of held experts than the forward's own
    crossed = {i: np.asarray(cuts[i][1][1:dm["top_k"] + 1], np.int32)
               for i in layers}
    whole = ref._forward(weights, hist, dm, None, crossed, reach=203)[0]
    row = ref.crossed_row(weights, kept, len(hist), dm, crossed)
    assert np.abs(whole - own).max() / span(own) > 1e-3
    assert np.abs(row - whole).max() / span(whole) < 2e-6


def test_compare_crosses_an_open_cut_without_a_second_forward(
        world, monkeypatch):
    ref, weights, dm, vocab = world("tiny_glm")
    monkeypatch.setattr(ref, "CUT_TOL", 0.05)
    for seed in range(20):
        hist, = histories(vocab, 60, seed=seed)
        own, _, cuts = ref._forward(weights, hist, dm)
        other = {i: ref.sides(*cut, dm["top_k"], dm["held"])
                 for i, cut in cuts.items()}
        if any(other.values()):
            break
    layer = max(i for i, found in other.items() if found)
    there = ref._forward(weights, hist, dm, None, {layer: other[layer][0]})[0]
    calls = []
    forward = ref._forward
    monkeypatch.setattr(ref, "_forward", lambda *a, **k: (
        calls.append(a[1]), forward(*a, **k))[1])
    got = ref.compare(weights, [(hist, ref.top_k_answer(there, 5))], 5, dm)
    assert len(calls) == 1
    assert got["open_cuts"] == got["crossed"] == 1
    assert got["score_err"] < 1e-5 and got["rank_gap"] < 1e-5
