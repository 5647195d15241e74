"""ALS compute core + DASE template end-to-end
(ref: MLlib ALS behavior used by examples/scala-parallel-recommendation)."""

import datetime as dt

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from predictionio_tpu.data.event import Event
from predictionio_tpu.models.als import ALSAlgorithm, ALSModel, ALSParams
from predictionio_tpu.ops.als import ALSConfig, als_train, predict_rmse
from predictionio_tpu.ops.ragged import build_padded_groups
from predictionio_tpu.ops.topk import TopKScorer, cosine_normalize
from predictionio_tpu.parallel.mesh import MeshContext, create_mesh
from predictionio_tpu.templates.recommendation import (
    RecoDataSourceParams,
    recommendation_engine,
)
from predictionio_tpu.workflow.deploy import prepare_deploy
from predictionio_tpu.workflow.train import run_train

UTC = dt.timezone.utc


# ---------------------------------------------------------------------------
# ragged -> padded binning
# ---------------------------------------------------------------------------

def test_padded_groups_basic():
    g = np.array([0, 0, 2, 2, 2])
    i = np.array([10, 11, 20, 21, 22])
    v = np.array([1.0, 2.0, 3.0, 4.0, 5.0])
    pg = build_padded_groups(g, i, v, n_groups=3, len_multiple=4)
    assert pg.idx.shape == (3, 4)
    assert pg.counts.tolist() == [2, 0, 3]
    assert pg.idx[0, :2].tolist() == [10, 11]
    assert pg.mask[0].tolist() == [1, 1, 0, 0]
    assert pg.val[2, :3].tolist() == [3.0, 4.0, 5.0]
    assert pg.mask[1].sum() == 0


def test_segmented_groups_splits_long_groups():
    from predictionio_tpu.ops.ragged import build_segmented_groups

    # 3 groups: sizes 5, 0, 11; L=8 -> rows 1, 0, 2
    g = np.array([0] * 5 + [2] * 11)
    i = np.arange(16)
    v = np.arange(16, dtype=float)
    sg = build_segmented_groups(g, i, v, n_groups=3, seg_len=8)
    assert sg.counts.tolist() == [5, 0, 11] + [0] * (len(sg.counts) - 3)
    assert sg.idx[0, :5].tolist() == [0, 1, 2, 3, 4]
    assert sg.idx[1].tolist() == list(range(5, 13))     # group 2 part 1
    assert sg.idx[2, :3].tolist() == [13, 14, 15]       # group 2 part 2
    assert sg.seg[:3].tolist() == [0, 2, 2]
    # seg nondecreasing (sorted-scatter invariant), incl. padded rows
    assert all(a <= b for a, b in zip(sg.seg, sg.seg[1:]))
    assert sg.rows_per_shard % sg.row_block == 0
    assert sg.groups_per_shard % sg.group_block == 0


def test_segmented_groups_sharded_layout():
    from predictionio_tpu.ops.ragged import build_segmented_groups

    g = np.array([0] * 5 + [2] * 11)
    i = np.arange(16)
    v = np.ones(16, dtype=float)
    sg = build_segmented_groups(g, i, v, n_groups=3, seg_len=8, n_shards=2)
    # shard 0 owns groups [0, g_per_shard), shard 1 the rest; every
    # shard sees the same (padded) row count and local segment ids
    assert sg.idx.shape[0] == 2 * sg.rows_per_shard
    s1 = slice(sg.rows_per_shard, 2 * sg.rows_per_shard)
    for shard_seg in (sg.seg[: sg.rows_per_shard], sg.seg[s1]):
        assert all(a <= b for a, b in zip(shard_seg, shard_seg[1:]))
        assert shard_seg.max() < sg.groups_per_shard
    # all 16 entries present exactly once
    assert int(sg.mask.sum()) == 16


def test_segmented_groups_max_len_keeps_latest():
    from predictionio_tpu.ops.ragged import build_segmented_groups

    g = np.zeros(10, dtype=int)
    i = np.arange(10)
    v = np.arange(10, dtype=float)
    sg = build_segmented_groups(g, i, v, n_groups=1, seg_len=8, max_len=6)
    assert sg.counts[0] == 6
    assert sg.idx[0, :6].tolist() == [4, 5, 6, 7, 8, 9]


def test_padded_groups_truncation_keeps_latest():
    g = np.zeros(10, dtype=int)
    i = np.arange(10)
    v = np.arange(10, dtype=float)
    pg = build_padded_groups(g, i, v, n_groups=1, max_len=4, len_multiple=4)
    # keeps the LAST 4 entries (recency)
    assert pg.idx[0].tolist() == [6, 7, 8, 9]
    assert pg.counts[0] == 4


def test_padded_groups_group_axis_padding():
    pg = build_padded_groups(np.array([0]), np.array([1]), np.array([1.0]),
                             n_groups=3, group_multiple=8)
    assert pg.idx.shape[0] == 8
    assert pg.n_groups == 3
    assert pg.mask[3:].sum() == 0


# ---------------------------------------------------------------------------
# ALS solver
# ---------------------------------------------------------------------------

def _synthetic(n_u=200, n_i=80, k=4, density=0.3, seed=0):
    rng = np.random.default_rng(seed)
    U = rng.normal(size=(n_u, k))
    V = rng.normal(size=(n_i, k))
    R = U @ V.T
    mask = rng.random((n_u, n_i)) < density
    uu, ii = np.nonzero(mask)
    return (uu, ii, R[uu, ii].astype(np.float32)), R, mask


def test_als_recovers_low_rank_matrix():
    coo, R, mask = _synthetic()
    cfg = ALSConfig(rank=6, iterations=10, reg=0.01, block_size=64)
    f = als_train(coo, 200, 80, cfg)
    assert predict_rmse(f, coo) < 0.1
    # generalization to held-out entries of the low-rank matrix
    uu, ii = np.nonzero(~mask)
    heldout_rmse = float(
        np.sqrt(np.mean((np.einsum("nk,nk->n", f.user_factors[uu], f.item_factors[ii]) - R[uu, ii]) ** 2))
    )
    assert heldout_rmse < 0.5


def test_als_mesh_matches_single_device():
    coo, _, _ = _synthetic()
    cfg = ALSConfig(rank=6, iterations=5, reg=0.05, block_size=32)
    f1 = als_train(coo, 200, 80, cfg)
    mesh = create_mesh({"data": 8})
    f8 = als_train(coo, 200, 80, cfg, mesh=mesh)
    np.testing.assert_allclose(f1.user_factors, f8.user_factors, atol=1e-4)
    np.testing.assert_allclose(f1.item_factors, f8.item_factors, atol=1e-4)


def test_als_implicit_separates_positives():
    rng = np.random.default_rng(1)
    coo, R, mask = _synthetic(density=0.2, seed=1)
    uu, ii, vals = coo
    pos = vals > 0
    cfg = ALSConfig(rank=8, iterations=8, reg=0.1, implicit=True, alpha=40.0, block_size=64)
    f = als_train((uu[pos], ii[pos], np.ones(pos.sum(), np.float32)), 200, 80, cfg)
    pred_pos = np.einsum("nk,nk->n", f.user_factors[uu[pos]], f.item_factors[ii[pos]]).mean()
    nu, ni = np.nonzero(~mask)
    pred_un = np.einsum("nk,nk->n", f.user_factors[nu], f.item_factors[ni]).mean()
    assert pred_pos > pred_un + 0.2


def test_als_empty_users_get_zero_factors():
    # user 5 has no ratings; solver must stay nonsingular and return zeros
    coo = (np.array([0, 1]), np.array([0, 1]), np.array([1.0, 2.0], np.float32))
    cfg = ALSConfig(rank=4, iterations=2, reg=0.1, block_size=8)
    f = als_train(coo, 6, 2, cfg)
    assert np.all(np.isfinite(f.user_factors))
    np.testing.assert_allclose(f.user_factors[5], 0.0, atol=1e-6)


# ---------------------------------------------------------------------------
# top-k scoring
# ---------------------------------------------------------------------------

def test_topk_scorer_and_exclusion():
    Y = np.eye(4, dtype=np.float32)  # 4 items = unit axes
    scorer = TopKScorer(Y)
    u = np.array([[3.0, 2.0, 1.0, 0.5]], dtype=np.float32)
    scores, idx = scorer.score(u, 2)
    assert idx[0].tolist() == [0, 1]
    scores, idx = scorer.score(u, 2, exclude_idx=np.array([[0, -1]], dtype=np.int32))
    assert idx[0].tolist() == [1, 2]


def test_cosine_normalize():
    m = np.array([[3.0, 4.0], [0.0, 0.0]])
    n = cosine_normalize(m)
    np.testing.assert_allclose(n[0], [0.6, 0.8])
    assert np.all(np.isfinite(n))


# ---------------------------------------------------------------------------
# DASE template end-to-end
# ---------------------------------------------------------------------------

def _seed_events(storage, app_name="reco-app"):
    app = storage.apps().insert(app_name)
    storage.events().init(app.id)
    rng = np.random.default_rng(42)
    # 30 users x 12 items, block structure: users 0-14 like items 0-5,
    # users 15-29 like items 6-11
    t0 = dt.datetime(2026, 1, 1, tzinfo=UTC)
    n = 0
    for u in range(30):
        liked = range(6) if u < 15 else range(6, 12)
        disliked = range(6, 12) if u < 15 else range(6)
        for i in liked:
            if rng.random() < 0.8:
                storage.events().insert(
                    Event(event="rate", entity_type="user", entity_id=f"u{u}",
                          target_entity_type="item", target_entity_id=f"i{i}",
                          properties={"rating": 5.0},
                          event_time=t0 + dt.timedelta(minutes=n)), app.id)
                n += 1
        for i in disliked:
            if rng.random() < 0.5:
                storage.events().insert(
                    Event(event="rate", entity_type="user", entity_id=f"u{u}",
                          target_entity_type="item", target_entity_id=f"i{i}",
                          properties={"rating": 1.0},
                          event_time=t0 + dt.timedelta(minutes=n)), app.id)
                n += 1
        # a few buys (implicit 4.0)
        storage.events().insert(
            Event(event="buy", entity_type="user", entity_id=f"u{u}",
                  target_entity_type="item",
                  target_entity_id=f"i{list(liked)[0]}",
                  event_time=t0 + dt.timedelta(minutes=n)), app.id)
        n += 1
    return app


def test_recommendation_template_end_to_end(memory_storage):
    _seed_events(memory_storage)
    engine = recommendation_engine()
    ep = engine.engine_params_from_variant({
        "engineFactory": "predictionio_tpu.templates.recommendation.recommendation_engine",
        "datasource": {"name": "", "params": {"app_name": "reco-app"}},
        "algorithms": [{"name": "als", "params": {
            "rank": 8, "num_iterations": 8, "lambda_": 0.05, "block_size": 32}}],
    })
    ctx = MeshContext(mesh=create_mesh({"data": 8}))
    instance = run_train(engine, ep, engine_id="reco", storage=memory_storage, ctx=ctx)
    assert instance.status == "COMPLETED"

    deployment = prepare_deploy(engine, instance, ctx, memory_storage)
    result = deployment.query({"user": "u3", "num": 4})
    items = [r["item"] for r in result["itemScores"]]
    assert len(items) == 4
    # u3 is in the first block: recommendations should be block-0 items
    assert sum(1 for i in items if int(i[1:]) < 6) >= 3
    scores = [r["score"] for r in result["itemScores"]]
    assert scores == sorted(scores, reverse=True)
    # unknown user -> empty result, not an error
    assert deployment.query({"user": "nobody", "num": 3}) == {"itemScores": []}


def test_recommendation_read_eval_folds(memory_storage):
    _seed_events(memory_storage, "reco-eval")
    ds = RecoDataSource = None
    from predictionio_tpu.templates.recommendation import RecoDataSource

    ds = RecoDataSource(RecoDataSourceParams(app_name="reco-eval", eval_k=3))
    folds = ds.read_eval(MeshContext())
    assert len(folds) == 3
    total = sum(len(qa) for _, _, qa in folds)
    all_train = sum(len(td.ratings) for td, _, _ in folds)
    # each rating appears in exactly one test fold and k-1 train folds
    assert all_train == 2 * total
    q, a = folds[0][2][0]
    assert set(q) == {"user", "num"} and set(a) == {"item", "rating"}


def test_als_batch_predict_matches_predict(memory_storage):
    _seed_events(memory_storage, "reco-bp")
    engine = recommendation_engine()
    ep = engine.engine_params_from_variant({
        "engineFactory": "x",
        "datasource": {"name": "", "params": {"app_name": "reco-bp"}},
        "algorithms": [{"name": "als", "params": {"rank": 4, "num_iterations": 4,
                                                   "block_size": 32}}],
    })
    ctx = MeshContext()
    result = engine.train(ctx, ep)
    algo = engine.make_algorithms(ep)[0]
    model = result.models[0]
    queries = [(0, {"user": "u1", "num": 3}), (1, {"user": "nobody", "num": 3}),
               (2, {"user": "u20", "num": 2})]
    batch = dict(algo.batch_predict(model, queries))
    assert [r["item"] for r in batch[0]["itemScores"]] == \
        [r["item"] for r in algo.predict(model, {"user": "u1", "num": 3})["itemScores"]]
    assert batch[1] == {"itemScores": []}
    assert len(batch[2]["itemScores"]) == 2


def test_whitelist_respects_blacklist(memory_storage):
    _seed_events(memory_storage, "reco-wl")
    engine = recommendation_engine()
    ep = engine.engine_params_from_variant({
        "engineFactory": "x",
        "datasource": {"name": "", "params": {"app_name": "reco-wl"}},
        "algorithms": [{"name": "als", "params": {"rank": 4, "num_iterations": 4,
                                                   "block_size": 32}}],
    })
    result = engine.train(MeshContext(), ep)
    algo = engine.make_algorithms(ep)[0]
    model = result.models[0]
    out = algo.predict(model, {
        "user": "u1", "num": 5, "whitelist": ["i0", "i1", "i2"], "blacklist": ["i1"],
    })
    items = [r["item"] for r in out["itemScores"]]
    assert "i1" not in items
    assert set(items) <= {"i0", "i2"}


def test_topk_shape_bucketing():
    """Varying k / exclusion widths must reuse a few compiled shapes."""
    Y = np.arange(40, dtype=np.float32).reshape(20, 2)
    scorer = TopKScorer(Y, max_exclude=8)
    u = np.ones((1, 2), dtype=np.float32)
    for k in (1, 3, 5, 7):
        scores, idx = scorer.score(u, k, exclude_idx=np.arange(k, dtype=np.int32))
        assert scores.shape == (1, k)
        assert not set(idx[0].tolist()) & set(range(k))
    # overlong exclusion list is truncated to max_exclude, keeping the tail
    long_excl = np.arange(12, dtype=np.int32)
    _, idx = scorer.score(u, 5, exclude_idx=long_excl)
    assert not set(idx[0].tolist()) & set(range(4, 12))


def test_grid_train_vmapped_matches_sequential():
    """als_grid_train: all reg grid points in ONE vmapped program
    (SURVEY.md §7.6 — grid points vmapped, a capability Spark's
    sequential batchEval never had)."""
    from predictionio_tpu.ops.als import als_grid_train, predict_rmse

    rng = np.random.default_rng(9)
    nnz, n_users, n_items = 600, 40, 16
    coo = (rng.integers(0, n_users, nnz), rng.integers(0, n_items, nnz),
           (rng.random(nnz) * 4 + 1).astype(np.float32))
    cfg = ALSConfig(rank=8, iterations=4, block_size=16, seg_len=8,
                    compute_dtype="float32", cg_dtype="float32")

    out = als_grid_train(coo, n_users, n_items, cfg,
                         regs=[0.05, 0.05, 1.0, 10.0])
    assert len(out) == 4
    # identical regs (+ shared init) -> identical factors
    np.testing.assert_array_equal(out[0].user_factors, out[1].user_factors)
    # stronger regularization -> smaller factors, worse train fit
    n0 = np.linalg.norm(out[0].user_factors)
    n3 = np.linalg.norm(out[3].user_factors)
    assert n3 < n0
    assert predict_rmse(out[0], coo) < predict_rmse(out[3], coo)
    # each grid point trains as well as a dedicated sequential run
    for reg, factors in zip((0.05, 1.0), (out[0], out[2])):
        solo = als_train(coo, n_users, n_items,
                         ALSConfig(rank=8, iterations=4, reg=reg,
                                   block_size=16, seg_len=8,
                                   compute_dtype="float32",
                                   cg_dtype="float32"))
        grid_rmse = predict_rmse(factors, coo)
        solo_rmse = predict_rmse(solo, coo)
        assert abs(grid_rmse - solo_rmse) < 0.05, (reg, grid_rmse, solo_rmse)


def test_grid_train_multi_scalar_matches_sequential():
    """Candidates differing in reg AND iteration
    budget AND cg budget ride ONE vmapped dispatch — each candidate's
    factors match its own dedicated sequential run (the run-to-max +
    freeze masking must be numerically faithful, not approximate)."""
    import dataclasses

    from predictionio_tpu.ops.als import als_grid_train

    rng = np.random.default_rng(11)
    n, n_users, n_items = 8000, 120, 40
    coo = (rng.integers(0, n_users, n), rng.integers(0, n_items, n),
           (1.0 + rng.integers(0, 9, n) * 0.5).astype(np.float32))
    cfg = ALSConfig(rank=4, iterations=4, reg=0.1, block_size=32,
                    compute_dtype="float32", cg_dtype="float32")
    regs = [0.05, 0.1, 0.5]
    iters = [2, 4, 3]
    cgs = [6, 4, 6]
    out = als_grid_train(coo, n_users, n_items, cfg, regs=regs,
                        iterations=iters, cg_iters=cgs)
    assert len(out) == 3
    for g, (reg, it, cg) in enumerate(zip(regs, iters, cgs)):
        solo = als_train(coo, n_users, n_items, dataclasses.replace(
            cfg, reg=reg, iterations=it, cg_iters=cg))
        np.testing.assert_allclose(
            out[g].user_factors, solo.user_factors, rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(
            out[g].item_factors, solo.item_factors, rtol=2e-4, atol=2e-4)


def test_grid_train_implicit_alpha_axis():
    """The implicit-feedback confidence scale rides the grid too."""
    import dataclasses

    from predictionio_tpu.ops.als import als_grid_train

    rng = np.random.default_rng(3)
    n, n_users, n_items = 5000, 80, 30
    coo = (rng.integers(0, n_users, n), rng.integers(0, n_items, n),
           rng.integers(1, 6, n).astype(np.float32))
    cfg = ALSConfig(rank=4, iterations=3, reg=0.1, block_size=32,
                    implicit=True, compute_dtype="float32",
                    cg_dtype="float32")
    alphas = [0.5, 2.0, 8.0]
    out = als_grid_train(coo, n_users, n_items, cfg,
                        regs=[0.1] * 3, alphas=alphas)
    for g, alpha in enumerate(alphas):
        solo = als_train(coo, n_users, n_items,
                         dataclasses.replace(cfg, alpha=alpha))
        # vmapped YtY/einsum reduce order differs slightly from the
        # sequential program: tolerance, not exactness, is the contract
        np.testing.assert_allclose(
            out[g].user_factors, solo.user_factors, rtol=6e-4, atol=6e-4)


def test_map_batch_matches_default():
    """map_batch (lax.map batch_size) is a measured-rejected perf knob
    kept for re-measurement; its vmapped path must stay numerically
    equal to the default, including a batch that does not divide the
    block count."""
    import dataclasses

    coo = (np.array([0, 1, 2, 3, 1, 2]), np.array([0, 1, 0, 1, 0, 1]),
           np.array([1.0, 2.0, 3.0, 4.0, 5.0, 1.5], np.float32))
    cfg = ALSConfig(rank=4, iterations=2, reg=0.1, block_size=8,
                    compute_dtype="float32", cg_dtype="float32")
    base = als_train(coo, 5, 2, cfg)
    for mb in (2, 3):
        f = als_train(coo, 5, 2, dataclasses.replace(cfg, map_batch=mb))
        np.testing.assert_allclose(f.user_factors, base.user_factors,
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(f.item_factors, base.item_factors,
                                   rtol=1e-5, atol=1e-5)


def test_grid_train_validates_candidate_list_lengths():
    """Mismatched per-candidate lists must raise ValueError (a bare
    assert vanishes under `python -O` and would vmap over garbage
    scalars — advisor finding, r6), and must raise BEFORE any layout
    work touches the device."""
    from predictionio_tpu.ops.als import als_grid_train

    rng = np.random.default_rng(2)
    coo = (rng.integers(0, 12, 60), rng.integers(0, 8, 60),
           (rng.random(60) * 4 + 1).astype(np.float32))
    cfg = ALSConfig(rank=4, iterations=2, block_size=8, seg_len=8)
    for kw in ({"alphas": [1.0]}, {"iterations": [2, 3, 4]},
               {"cg_iters": [4]}):
        name = next(iter(kw))
        with pytest.raises(ValueError, match=f"`{name}`.*match len\\(regs\\)"):
            als_grid_train(coo, 12, 8, cfg, regs=[0.1, 0.2], **kw)
