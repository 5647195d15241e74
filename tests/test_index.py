"""Vector-retrieval index subsystem (predictionio_tpu/index).

The contract under test, per ISSUE 12's acceptance criteria:

  - the fused Pallas dot+top-k kernel (interpret mode on CPU) returns
    EXACTLY what the ``ops.topk`` brute-force reference returns —
    identical scores, identical indices modulo exact score ties —
    including ragged tails, tie groups, exclusion masks and item ids
    beyond 2^16;
  - the IVF CPU fallback clears recall@10 >= 0.95 against brute force
    on the fixture (and measures/records that recall at build);
  - a streamed ``POST /model/patch`` item is retrievable WITHOUT a
    ``/reload`` (the ``event_to_servable`` contract extended to
    retrieval), and the index survives a ``/reload`` hot-swap;
  - the streaming recall probe exports ``pio_stream_index_recall`` and
    counts floor breaches.
"""

import json
import pickle

import numpy as np
import pytest

from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.index import (
    QUERIES_TOTAL,
    SIZE_ITEMS,
    make_index,
    resolve_backend,
)
from predictionio_tpu.index.exact import ExactIndex
from predictionio_tpu.index.ivf import IVFIndex
from predictionio_tpu.index.recall import brute_force_topk, recall_at_k
from predictionio_tpu.models.als import ALSAlgorithm, ALSModel, ALSParams
from predictionio_tpu.ops.als import ALSFactors
from predictionio_tpu.ops.pallas.topk_dot import tile_items, topk_dot
from predictionio_tpu.ops.topk import NEG_INF, TopKScorer

RNG = np.random.default_rng(42)


def _clustered(n, d, n_clusters=12, seed=5, spread=0.15):
    """Gaussian-mixture vectors — the realistic (clusterable) shape IVF
    is built for; pure iid gaussians are its degenerate worst case."""
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(n_clusters, d)).astype(np.float32)
    assign = rng.integers(0, n_clusters, size=n)
    return (centers[assign]
            + spread * rng.normal(size=(n, d)).astype(np.float32)
            ).astype(np.float32)


def _brute_masked(vectors, q, k, exclude_rows=None):
    """lax.top_k over the FULL logits matrix — the reference the kernel
    must match. ``exclude_rows``: [B, E] global ids, -1 pads."""
    import jax

    scores = np.atleast_2d(q) @ vectors.T
    if exclude_rows is not None:
        excl = np.atleast_2d(np.asarray(exclude_rows, np.int64))
        for b in range(scores.shape[0]):
            drop = excl[b]
            drop = drop[(drop >= 0) & (drop < vectors.shape[0])]
            scores[b, drop] = float(NEG_INF)
    s, i = jax.lax.top_k(scores, k)
    return np.asarray(s), np.asarray(i)


# ---------------------------------------------------------------------------
# Pallas kernel equivalence (interpret mode)
# ---------------------------------------------------------------------------

class TestTopkDotKernel:
    @pytest.mark.parametrize("I,D,B,k,E", [
        (1024, 16, 4, 8, 1),      # exact tile multiple
        (1300, 16, 4, 8, 4),      # ragged last tile
        (700, 8, 1, 16, 2),       # k bigger than one would guess vs I
        (513, 32, 8, 8, 8),       # one full tile + a 1-row tail
    ])
    def test_matches_brute_force(self, I, D, B, k, E):
        rng = np.random.default_rng(I + D)
        q = rng.normal(size=(B, D)).astype(np.float32)
        items = rng.normal(size=(I, D)).astype(np.float32)
        excl = np.full((B, E), -1, np.int32)
        # valid + out-of-tile + -1 pads
        excl[:, 0] = rng.integers(0, I, size=B)
        s, i, _ = topk_dot(q, items, excl, k, block_items=512,
                           interpret=True)
        bs, bi = _brute_masked(items, q, k, excl)
        np.testing.assert_allclose(np.asarray(s), bs, rtol=1e-5, atol=1e-5)
        assert np.array_equal(np.asarray(i), bi)

    @pytest.mark.parametrize("n,D", [(1000, 40), (640, 40), (90, 8)])
    def test_the_table_built_in_slabs_is_the_transposed_table(
            self, monkeypatch, n, D):
        """``to_kernel_layout`` writes slabs of whole lanes' worth of rows
        (a multiple of 128: 6,528 rows at D = 2,560, not 6,553) into the
        one buffer; the ragged last slab overlaps the one before it."""
        from predictionio_tpu.ops.pallas import topk_dot as tkd

        monkeypatch.setattr(tkd, "_BUILD_BYTES", 4 * D * 300)
        puts = []
        put = tkd._put_rows
        monkeypatch.setattr(tkd, "_put_rows", lambda table, rows, at: (
            puts.append((len(rows), at)), put(table, rows, at))[1])
        items = np.random.default_rng(n).normal(size=(n, D)).astype(
            np.float32)
        table = np.asarray(tkd.to_kernel_layout(items, 512))
        assert np.array_equal(table[:D, :n], items.T)
        assert not table[:, n:].any() and not table[D:].any()
        # 300 rows' worth of bytes: slabs of 256 rows
        if n > 256:
            assert {rows for rows, _ in puts} == {256}
            assert [at for _, at in puts][:-1] == list(
                range(0, n - 256, 256)) and puts[-1][1] == n - 256
        else:
            assert puts == [(n, 0)]
        assert (64 << 20) // (4 * 2560) // 128 * 128 == 6528

    def test_item_ids_beyond_uint16(self):
        """>2^16 items: the winning global id must survive the int32
        iota/merge path (a uint16 anywhere would alias it)."""
        I, D = 66_000, 8
        rng = np.random.default_rng(0)
        items = 0.01 * rng.normal(size=(I, D)).astype(np.float32)
        q = rng.normal(size=(2, D)).astype(np.float32)
        winner = 65_777   # > 2^16, inside the ragged tail region
        items[winner] = 100.0 * q[0] / np.linalg.norm(q[0])
        s, i, _ = topk_dot(q, items, np.full((2, 1), -1, np.int32), 8,
                           interpret=True)
        assert int(np.asarray(i)[0, 0]) == winner

    def test_ties_identical_scores_valid_indices(self):
        """Duplicate item rows tie exactly; the pinned contract is
        identical SCORES and indices drawn from the tied equivalence
        class (lax.top_k's intra-tile order is not promised)."""
        rng = np.random.default_rng(1)
        D = 8
        base = rng.normal(size=(600, D)).astype(np.float32)
        items = np.vstack([base, base[:200]])   # 200 exact-tie pairs
        q = rng.normal(size=(3, D)).astype(np.float32)
        k = 16
        s, i, _ = topk_dot(q, items, np.full((3, 1), -1, np.int32), k,
                           block_items=256, interpret=True)
        bs, _ = _brute_masked(items, q, k)
        np.testing.assert_allclose(np.asarray(s), bs, rtol=1e-5, atol=1e-5)
        # every returned index's true score matches the returned score
        s_np, i_np = np.asarray(s), np.asarray(i)
        for b in range(3):
            true = items[i_np[b]] @ q[b]
            np.testing.assert_allclose(true, s_np[b], rtol=1e-5, atol=1e-5)
            assert len(set(i_np[b].tolist())) == k   # no duplicates

    def test_whole_tile_excluded(self):
        """Excluding every top candidate in one tile forces the merge
        to fill from other tiles — the NEG_INF routing under stress."""
        rng = np.random.default_rng(2)
        items = rng.normal(size=(1024, 8)).astype(np.float32)
        q = rng.normal(size=(1, 8)).astype(np.float32)
        _, top = _brute_masked(items, q, 16)
        excl = top[:, :16].astype(np.int32)       # ban the true top-16
        s, i, _ = topk_dot(q, items, excl, 8, block_items=512,
                           interpret=True)
        bs, bi = _brute_masked(items, q, 8, excl)
        np.testing.assert_allclose(np.asarray(s), bs, rtol=1e-5, atol=1e-5)
        assert np.array_equal(np.asarray(i), bi)

    # -- the tiling: big tiles, a merge only where the top-k can change ------
    @pytest.mark.parametrize("B", [1, 8, 32])
    @pytest.mark.parametrize("D", [64, 128])
    @pytest.mark.parametrize("E,k", [(1, 8), (64, 16), (1, 128)])
    def test_tiled_matches_brute_force(self, B, D, E, k):
        """The module's own tile rule (no ``block_items``) over a table
        of several tiles and a ragged tail; every row excludes the item
        that is its best tile's maximum."""
        bi = tile_items(D, 1 << 30, B)
        I = 2 * bi + 777
        rng = np.random.default_rng(B * 1000 + D + k)
        q = rng.normal(size=(B, D)).astype(np.float32)
        items = rng.normal(size=(I, D)).astype(np.float32)
        excl = np.full((B, E), -1, np.int32)
        excl[:, 0] = np.argmax(q @ items.T, axis=1)
        if E > 1:
            excl[:, 1:E // 2] = rng.integers(0, I, size=(B, E // 2 - 1))
        s, i, merged = topk_dot(q, items, excl, k, interpret=True)
        bs, bidx = _brute_masked(items, q, k, excl)
        np.testing.assert_allclose(np.asarray(s), bs, rtol=1e-5, atol=1e-5)
        assert np.array_equal(np.asarray(i), bidx)
        assert 1 <= merged <= 3
        assert not (np.asarray(i) == excl[:, :1]).any()

    @pytest.mark.parametrize("B", [1, 8])
    def test_below_one_tile(self, B):
        """Fewer items than one tile of the rule: the tile shrinks to
        the table (a power of two of lanes), one grid step."""
        I, D, k = 300, 64, 16
        assert tile_items(D, I, B) == 512
        rng = np.random.default_rng(B)
        q = rng.normal(size=(B, D)).astype(np.float32)
        items = rng.normal(size=(I, D)).astype(np.float32)
        s, i, merged = topk_dot(q, items, np.full((B, 1), -1, np.int32), k,
                                interpret=True)
        bs, bidx = _brute_masked(items, q, k)
        np.testing.assert_allclose(np.asarray(s), bs, rtol=1e-5, atol=1e-5)
        assert np.array_equal(np.asarray(i), bidx)
        assert merged == 1

    @pytest.mark.parametrize("B", [1, 8, 32])
    def test_padded_tail_never_wins_against_negative_scores(self, B):
        """Every real score is negative, the zero-padded tail scores 0:
        nothing of the tail may enter, in any tile that holds padding."""
        I, D, k = 2 * 512 + 3, 16, 8
        rng = np.random.default_rng(3)
        items = np.abs(rng.normal(size=(I, D))).astype(np.float32)
        q = -np.abs(rng.normal(size=(B, D))).astype(np.float32)
        s, i, _ = topk_dot(q, items, np.full((B, 1), -1, np.int32), k,
                           block_items=512, interpret=True)
        bs, bidx = _brute_masked(items, q, k)
        assert (bs < 0).all()
        np.testing.assert_allclose(np.asarray(s), bs, rtol=1e-5, atol=1e-5)
        assert np.array_equal(np.asarray(i), bidx)

    @pytest.mark.parametrize("order,merged_tiles", [("ascending", 8),
                                                    ("descending", 1)])
    @pytest.mark.parametrize("B", [1, 8])
    def test_merged_tiles_counts_the_tiles_that_could_change_the_topk(
            self, order, merged_tiles, B):
        """Scores ascending along the table: every tile beats the
        running k-th best and merges. Descending: none after the first."""
        bi, D, k = 256, 8, 8
        I = 8 * bi
        ramp = np.linspace(1.0, 2.0, I, dtype=np.float32)
        if order == "descending":
            ramp = ramp[::-1]
        items = np.zeros((I, D), np.float32)
        items[:, 0] = ramp
        q = np.zeros((B, D), np.float32)
        q[:, 0] = np.arange(1, B + 1)
        s, i, merged = topk_dot(q, items, np.full((B, 1), -1, np.int32), k,
                                block_items=bi, interpret=True)
        bs, bidx = _brute_masked(items, q, k)
        np.testing.assert_allclose(np.asarray(s), bs, rtol=1e-6)
        assert np.array_equal(np.asarray(i), bidx)
        assert merged == merged_tiles

    def test_fewer_candidates_than_k_leaves_unfilled_slots_masked(self):
        """Exclusions leave fewer than k candidates: the slots nothing
        filled read NEG_INF / -1, as the index contract has it."""
        items = np.eye(8, dtype=np.float32) + 1.0
        q = np.ones((1, 8), np.float32)
        excl = np.arange(5, dtype=np.int32)[None, :]
        s, i, _ = topk_dot(q, items, np.concatenate(
            [excl, np.full((1, 3), -1, np.int32)], axis=1), 8,
            interpret=True)
        s, i = np.asarray(s), np.asarray(i)
        assert sorted(i[0, :3].tolist()) == [5, 6, 7]
        assert (s[0, 3:] <= float(NEG_INF)).all() and (i[0, 3:] == -1).all()


# ---------------------------------------------------------------------------
# ExactIndex
# ---------------------------------------------------------------------------

class TestExactIndex:
    VECS = RNG.normal(size=(900, 12)).astype(np.float32)

    def test_fallback_equals_reference_scorer(self):
        index = make_index(self.VECS, backend="exact")   # auto: XLA on CPU
        assert isinstance(index, ExactIndex)
        assert not index.kernel_plan["engaged"]
        q = RNG.normal(size=(5, 12)).astype(np.float32)
        excl = np.array([3, 7], np.int32)
        s, i = index.search(q, 10, excl)
        rs, ri = TopKScorer(self.VECS).score(q, 10, excl)
        np.testing.assert_array_equal(i, ri)
        np.testing.assert_allclose(s, rs, rtol=1e-6)

    def test_kernel_on_equals_reference(self):
        index = make_index(self.VECS, backend="exact", kernel="on")
        assert index.kernel_plan == {"engaged": True, "reason": "forced on",
                                     "interpret": True}
        q = RNG.normal(size=(3, 12)).astype(np.float32)
        s, i = index.search(q, 10)
        rs, ri = TopKScorer(self.VECS).score(q, 10)
        np.testing.assert_allclose(s, rs, rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(i, ri)   # no ties in random data

    @pytest.mark.parametrize("kernel", ["auto", "on"])
    def test_upsert_overwrite_and_append(self, kernel):
        index = make_index(self.VECS.copy(), backend="exact", kernel=kernel)
        q = RNG.normal(size=(12,)).astype(np.float32)
        probe = (q / np.linalg.norm(q)).astype(np.float32)
        # overwrite row 5 to dominate, append a new row that dominates more
        index.upsert(np.array([5]), 50.0 * probe)
        s, i = index.search(probe, 2)
        assert int(i[0, 0]) == 5
        index.upsert(np.array([len(index)]), 99.0 * probe)
        assert len(index) == 901
        s, i = index.search(probe, 2)
        assert int(i[0, 0]) == 900 and int(i[0, 1]) == 5

    def test_upsert_rebuilds_the_device_table_in_the_kernels_layout(self):
        """The device copy is ``[D, Ip]``, items on the lanes; an
        overwrite rebuilds it at the same shape, an append past the last
        tile grows it by a tile (and drops the compiled searches)."""
        vecs = RNG.normal(size=(1024, 12)).astype(np.float32)
        index = ExactIndex(kernel="on", block_items=256)
        index.build(vecs)
        probe = np.zeros(12, np.float32)
        probe[3] = 1.0
        index.search(probe, 4)
        assert index._device_table.shape == (16, 1024)
        np.testing.assert_array_equal(
            np.asarray(index._device_table)[:12, :1024], vecs.T)
        index.upsert(np.array([1023]), 70.0 * probe)
        assert index._device_table is None      # dropped, not patched
        s, i = index.search(probe, 4)
        assert int(i[0, 0]) == 1023 and index._device_table.shape == (16, 1024)
        index.upsert(np.array([1024]), 90.0 * probe)
        assert not index._fns
        s, i = index.search(probe, 4)
        assert i[0, :2].tolist() == [1024, 1023]
        assert index._device_table.shape == (16, 1280)
        assert index.stats()["kernel"]["tiles"] == 5

    def test_stats_fetches_the_merged_tile_count_and_search_does_not(self):
        import jax

        index = ExactIndex(kernel="on", block_items=256)
        index.build(self.VECS)
        assert "tiles" not in index.stats()["kernel"]
        index.search(RNG.normal(size=(2, 12)).astype(np.float32), 10)
        tiles, merged = index._last_merge
        # still on the device: a query never waits for it
        assert isinstance(merged, jax.Array)
        kernel = index.stats()["kernel"]
        assert kernel["tiles"] == tiles == 4
        assert 1 <= kernel["merged_tiles"] <= 4
        assert isinstance(kernel["merged_tiles"], int)
        # a fallback search leaves the last kernel search's count alone
        index.search(RNG.normal(size=(1, 12)).astype(np.float32), 5000)
        assert index.stats()["kernel"]["tiles"] == 4

    def test_empty_index_search(self):
        index = ExactIndex()
        s, i = index.search(np.zeros((2, 4), np.float32), 5)
        assert s.shape == (2, 0) and i.shape == (2, 0)

    def test_k_beyond_catalog_falls_back(self):
        """k above the kernel's bucket eligibility (or the catalog)
        degrades to the XLA fallback, never fails."""
        index = make_index(self.VECS, backend="exact", kernel="on")
        s, i = index.search(RNG.normal(size=(1, 12)).astype(np.float32),
                            5000)
        assert s.shape == (1, 900)
        assert sorted(i[0].tolist()) == list(range(900))


# ---------------------------------------------------------------------------
# IVF
# ---------------------------------------------------------------------------

class TestIVFIndex:
    def test_recall_at_10_clears_floor(self):
        vecs = _clustered(4000, 24)
        index = make_index(vecs, backend="ivf")
        assert isinstance(index, IVFIndex)
        # the build-time autotune already measured >= floor
        assert index.measured_recall >= 0.95
        # independent check with held-out queries
        q = _clustered(48, 24, seed=99)
        assert recall_at_k(index, q, 10) >= 0.95
        stats = index.stats()
        assert stats["nlist"] >= 1 and stats["nprobe"] >= 1
        assert stats["measured_recall"] >= 0.95

    def test_int8_quantization_still_clears_floor(self):
        vecs = _clustered(4000, 24)
        index = make_index(vecs, backend="ivf", quantize="int8")
        assert index.stats()["quantize"] == "int8"
        assert index.measured_recall >= 0.95
        q = _clustered(48, 24, seed=98)
        assert recall_at_k(index, q, 10) >= 0.95

    def test_upsert_new_item_retrievable(self):
        vecs = _clustered(1500, 16)
        index = make_index(vecs, backend="ivf")
        probe = _clustered(1, 16, seed=7)[0]
        probe /= np.linalg.norm(probe)
        index.upsert(np.array([1500]), 30.0 * probe)
        assert len(index) == 1501
        s, i = index.search(probe, 5)
        assert int(i[0, 0]) == 1500
        # overwrite moves the row's list membership too
        index.upsert(np.array([3]), 60.0 * probe)
        s, i = index.search(probe, 5)
        assert int(i[0, 0]) == 3

    def test_exclusions(self):
        vecs = _clustered(800, 16)
        index = make_index(vecs, backend="ivf")
        q = vecs[17]
        _, base = index.search(q, 3)
        top = int(base[0, 0])
        _, excluded = index.search(q, 3, np.array([top], np.int64))
        assert top not in excluded[0].tolist()


# ---------------------------------------------------------------------------
# factory / env selection / metrics
# ---------------------------------------------------------------------------

class TestSelection:
    def test_resolve_backend(self, monkeypatch):
        assert resolve_backend(None) == "exact"
        assert resolve_backend("auto") == "exact"
        assert resolve_backend("ivf") == "ivf"
        monkeypatch.setenv("PIO_INDEX_BACKEND", "ivf")
        assert resolve_backend("exact") == "ivf"   # env beats the arg
        monkeypatch.setenv("PIO_INDEX_BACKEND", "bogus")
        with pytest.raises(ValueError):
            resolve_backend("exact")

    def test_env_selects_ivf_for_models(self, monkeypatch):
        monkeypatch.setenv("PIO_INDEX_BACKEND", "ivf")
        vecs = _clustered(600, 8)
        index = make_index(vecs, backend="auto")
        assert isinstance(index, IVFIndex)

    def test_metrics_exported(self):
        vecs = RNG.normal(size=(50, 8)).astype(np.float32)
        index = make_index(vecs, backend="exact")
        before = QUERIES_TOTAL.labels("exact").value
        index.search(vecs[0], 5)
        assert QUERIES_TOTAL.labels("exact").value == before + 1
        assert SIZE_ITEMS.labels("exact").value == 50.0


# ---------------------------------------------------------------------------
# model wiring (ALSModel container — ALS and two-tower share it)
# ---------------------------------------------------------------------------

def _model(n_users=20, n_items=120, rank=8, seed=11):
    rng = np.random.default_rng(seed)
    model = ALSModel(
        ALSFactors(
            user_factors=rng.normal(size=(n_users, rank)).astype(np.float32),
            item_factors=rng.normal(size=(n_items, rank)).astype(np.float32)),
        BiMap.string_int([f"u{j}" for j in range(n_users)]),
        BiMap.string_int([f"i{j}" for j in range(n_items)]))
    return model


class TestModelWiring:
    def test_recommend_routes_through_index_with_scorer_parity(self):
        model = _model()
        recs = model.recommend("u1", 5, exclude_items=["i3", "i9"])
        assert model._index is not None   # recommend built/used the index
        row = model.user_ids["u1"]
        s, i = TopKScorer(model.item_factors).score(
            model.user_factors[row], 5, np.array([3, 9], np.int32))
        inv = model.item_ids.inverse()
        assert [r[0] for r in recs] == [inv[int(j)] for j in i[0]]

    def test_similar_items_excludes_self(self):
        model = _model()
        sims = model.similar_items("i0", 10)
        names = [n for n, _ in sims]
        assert "i0" not in names and len(names) == 10
        sims2 = model.similar_items("i0", 10, exclude_items=[names[0]])
        assert names[0] not in [n for n, _ in sims2]

    def test_similar_items_self_exclusion_survives_blacklist_overflow(self):
        """A blacklist past the exact backend's max_exclude cap drops
        oldest-first — it must drop ITSELF before the self-exclusion
        (which rides last), and the result filter backstops the query
        item regardless (the code-review finding)."""
        model = _model(n_items=200)
        # make i0 its own best match by a wide margin
        model.item_factors[0] *= 50.0
        blacklist = [f"i{j}" for j in range(100, 180)]   # 80 > cap of 64
        sims = model.similar_items("i0", 10, exclude_items=blacklist)
        assert sims and all(n != "i0" for n, _ in sims)

    def test_predict_item_query(self):
        model = _model()
        algo = ALSAlgorithm(ALSParams(rank=8))
        out = algo.predict(model, {"item": "i4", "num": 3})
        assert len(out["itemScores"]) == 3
        assert all(e["item"] != "i4" for e in out["itemScores"])
        # user queries keep their shape
        out_u = algo.predict(model, {"user": "u2", "num": 3})
        assert len(out_u["itemScores"]) == 3

    def test_patch_upserts_into_live_index_without_rebuild(self):
        model = _model()
        model.retrieval_index()
        index_obj = model._index
        q = np.asarray(model.item_factors[4], np.float32)
        newvec = 40.0 * q / np.linalg.norm(q)
        model.upsert_rows(item_rows=[("brand_new", newvec)])
        assert model._index is index_obj          # upsert, not rebuild
        assert len(index_obj) == 121
        sims = model.similar_items("i4", 3)
        assert sims[0][0] == "brand_new"

    def test_pickle_drops_index_and_rebuilds(self):
        model = _model()
        model.retrieval_index()
        clone = pickle.loads(pickle.dumps(model))
        assert clone._index is None
        assert clone.index_backend == "auto"
        assert [n for n, _ in clone.similar_items("i0", 3)] \
            == [n for n, _ in model.similar_items("i0", 3)]

    def test_warmup_builds_index(self):
        from predictionio_tpu.parallel.mesh import MeshContext

        model = _model()
        ALSAlgorithm(ALSParams(rank=8)).warmup(model, MeshContext())
        assert model._index is not None
        assert model.retrieval_stats()["backend"] == "exact"


# ---------------------------------------------------------------------------
# serving end-to-end: patch -> retrievable without /reload; /reload survival
# ---------------------------------------------------------------------------

@pytest.fixture()
def served_world(tmp_path, request):
    """A trained recommendation engine behind a live server; a test
    may pass extra ALS params (``indirect=True``)."""
    from predictionio_tpu.data.storage import set_storage
    from predictionio_tpu.serving.engine_server import EngineServer

    from tests.test_stream import _seed_world, _train_reco
    from tests.test_storage import make_storage

    storage = make_storage("eventlog", tmp_path)
    set_storage(storage)
    app = storage.apps().insert("stream")
    storage.events().init(app.id)
    _seed_world(storage, app.id, n_users=30, n_items=20, n_events=600)
    engine, instance = _train_reco(storage, engine_id="idx_e2e",
                                   iterations=6,
                                   **getattr(request, "param", {}))
    server = EngineServer(engine, "idx_e2e", host="127.0.0.1", port=0,
                          storage=storage, micro_batch=False).start()
    try:
        yield storage, engine, server
    finally:
        server.stop()
        set_storage(None)


class TestServingEndToEnd:
    def _query(self, server, payload):
        import urllib.request

        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}/queries.json",
            data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as resp:
            return json.loads(resp.read())

    def test_patched_item_retrievable_without_reload(self, served_world):
        storage, engine, server = served_world
        model = server.deployment.models[0]
        # warm-up (run by the server at load) built the index
        assert server.status()["retrieval"][0] is not None
        base = self._query(server, {"item": "i3", "num": 5})
        assert base["itemScores"]
        # streamed patch: a brand-new item whose factor shadows i3's
        vec = np.asarray(model.item_factors[model.item_ids["i3"]])
        vec = (1.0001 * vec).tolist()
        server.apply_patch({
            "instanceId": server.deployment.instance.id,
            "algorithms": [{"index": 0, "itemRows":
                            [["patched_item", vec]]}],
        })
        after = self._query(server, {"item": "i3", "num": 5})
        names = [e["item"] for e in after["itemScores"]]
        assert names[0] == "patched_item"   # retrieval, no /reload
        # user -> top-k retrieval sees the full (grown) catalog too
        user_q = self._query(server, {"user": "u1", "num": 21})
        assert len(user_q["itemScores"]) == 21   # 20 trained + patched

    @pytest.mark.parametrize(
        "served_world", [{"index_kernel": "on"}],   # interpret on CPU
        indirect=True, ids=["index_kernel_on"])
    def test_status_page_shows_the_kernels_merged_tile_count(
            self, served_world):
        """``GET /`` -> ``retrieval[].kernel``: how many tiles the last
        kernel search had and how many it merged."""
        import urllib.request

        _, _, server = served_world
        self._query(server, {"user": "u1", "num": 5})
        with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/", timeout=30) as resp:
            kernel = json.loads(resp.read())["retrieval"][0]["kernel"]
        assert kernel["engaged"] and kernel["tiles"] == 1
        assert kernel["merged_tiles"] == 1

    def test_index_survives_reload_hot_swap(self, served_world):
        storage, engine, server = served_world
        server.apply_patch({
            "instanceId": server.deployment.instance.id,
            "algorithms": [{"index": 0, "itemRows":
                            [["ephemeral", [0.0] * 8]]}],
        })
        server.reload()
        status = server.status()
        # the swapped-in deployment rebuilt its own index at warm-up...
        assert status["retrieval"][0] is not None
        answer = self._query(server, {"item": "i3", "num": 5})
        names = [e["item"] for e in answer["itemScores"]]
        # ...from the TRAINED factors: the unreloadable patch row is
        # gone (full retrains own reconciliation — the cursor contract)
        assert "ephemeral" not in names and names


# ---------------------------------------------------------------------------
# streaming recall probe
# ---------------------------------------------------------------------------

class TestStreamRecallProbe:
    def test_probe_exports_gauge_and_counts_breaches(self, tmp_path,
                                                     monkeypatch):
        import datetime as dt

        from predictionio_tpu.data.event import Event
        from predictionio_tpu.data.storage import set_storage
        from predictionio_tpu.obs import metrics as obs_metrics
        from predictionio_tpu.workflow.stream import StreamUpdater

        from tests.test_stream import _seed_world, _train_reco
        from tests.test_storage import make_storage

        monkeypatch.setenv("PIO_STREAM_RECALL_EVERY", "1")
        storage = make_storage("eventlog", tmp_path)
        set_storage(storage)
        try:
            app = storage.apps().insert("stream")
            storage.events().init(app.id)
            _seed_world(storage, app.id, n_users=30, n_items=20,
                        n_events=600)
            engine, instance = _train_reco(storage, engine_id="idx_probe",
                                           iterations=6)
            updater = StreamUpdater(engine, "idx_probe", storage=storage,
                                    instance=instance)
            storage.events().insert_batch(
                [Event(event="rate", entity_type="user", entity_id="u1",
                       target_entity_type="item", target_entity_id="i1",
                       properties={"rating": 4.5},
                       event_time=dt.datetime.now(tz=dt.timezone.utc))],
                app.id)
            stats = updater.poll_once()
            assert stats["published"]
            # the probe ran (EVERY=1): a healthy patched index reads ~1
            assert stats["index_recall"] >= 0.99
            gauge = obs_metrics.REGISTRY.get("pio_stream_index_recall")
            assert gauge.value >= 0.99

            # corrupt the patched index directly (bypassing the model)
            # -> drift becomes visible and the breach counter moves
            model = updater._folders[0].model
            index = model.retrieval_index()
            rng = np.random.default_rng(0)
            index.upsert(
                np.arange(len(index)),
                rng.normal(size=(len(index),
                                 model.item_factors.shape[1])
                           ).astype(np.float32))
            breaches = obs_metrics.REGISTRY.get(
                "pio_stream_recall_breaches_total")
            before = breaches.value
            recall = updater.probe_recall()
            assert recall < 0.95
            assert breaches.value == before + 1
        finally:
            set_storage(None)
