"""Zero-copy data path: the native columnar->binned builders must be
BIT-IDENTICAL to the Python reference binning
(compress_side(build_segmented_groups(...))), and the chunked H2D
pipeline must place exactly the bytes a single-shot device_put would.

Covers the ISSUE-pinned fixtures: tombstones, compacted logs, empty
groups, >idx16 vocab sizes, ragged-shape fuzz, chunked-pipeline
equivalence, and the mmap'd warm load surviving a concurrent prune.
"""

from __future__ import annotations

import datetime as dt
import os

import numpy as np
import pytest

from predictionio_tpu.data.backends.eventlog import EventLogEventStore
from predictionio_tpu.data.event import Event
from predictionio_tpu.data.storage import EventColumns
from predictionio_tpu.ops import ragged
from predictionio_tpu.ops.als import ALSConfig, ALSTrainer, compress_side

pytestmark = pytest.mark.skipif(
    not __import__("predictionio_tpu.native",
                   fromlist=["native_available"]).native_available("eventlog"),
    reason="C++ toolchain unavailable",
)

UTC = dt.timezone.utc


def _store(tmp_path) -> EventLogEventStore:
    st = EventLogEventStore(str(tmp_path / "events"))
    st.init(1)
    return st


def _fill(st, n=60_000, users=800, items=300, seed=0, buy_frac=0.2):
    rng = np.random.default_rng(seed)
    names = np.where(rng.random(n) < buy_frac, 1, 0).astype(np.int32)
    vals = (0.5 + 0.5 * rng.integers(0, 10, n)).astype(np.float64)
    vals[names == 1] = np.nan  # buy rows carry no rating property
    cols = EventColumns(
        entity_codes=rng.integers(0, users, n).astype(np.int32),
        target_codes=rng.integers(0, items, n).astype(np.int32),
        name_codes=names,
        values=vals,
        times_us=np.arange(n, dtype=np.int64) * 1000,
        entity_vocab=[f"u{i}" for i in range(users)],
        target_vocab=[f"i{i}" for i in range(items)],
        names=["rate", "buy"],
    )
    st.insert_columnar(cols, 1, entity_type="user",
                       target_entity_type="item", value_property="rating")


def _reference(st, skip_mod=0, skip_rem=0, buy_rating=4.0, **knobs):
    """The Python reference pipeline the native builder must match:
    columnar scan -> target-drop -> value resolution -> holdout ->
    build_segmented_groups -> compress_side, per side."""
    cs = st.find_columnar(1, value_property="rating", time_ordered=False,
                          entity_type="user", event_names=["rate", "buy"],
                          target_entity_type="item")
    keep = cs.target_codes >= 0
    u = cs.entity_codes[keep].astype(np.int64)
    i = cs.target_codes[keep].astype(np.int64)
    v = np.nan_to_num(cs.values[keep], nan=0.0).astype(np.float32)
    if "buy" in cs.names:
        buy = cs.names.index("buy")
        v = np.where(cs.name_codes[keep] == buy, np.float32(buy_rating), v)
    hold = (np.arange(len(u)) % skip_mod == skip_rem) if skip_mod else (
        np.zeros(len(u), bool))
    tr = (u[~hold], i[~hold], v[~hold])
    ho = (u[hold], i[hold], v[hold])
    user_sg = ragged.build_segmented_groups(
        tr[0], tr[1], tr[2], len(cs.entity_vocab), **knobs)
    item_sg = ragged.build_segmented_groups(
        tr[1], tr[0], tr[2], len(cs.target_vocab), **knobs)
    return (cs, tr, ho,
            compress_side(user_sg, 0), compress_side(item_sg, 0))


def _assert_side_equal(ref, got):
    np.testing.assert_array_equal(ref.idx_lo, got.idx_lo)
    assert (ref.idx_hi is None) == (got.idx_hi is None)
    if ref.idx_hi is not None:
        np.testing.assert_array_equal(ref.idx_hi, got.idx_hi)
    assert ref.affine == got.affine
    np.testing.assert_array_equal(np.asarray(ref.val), np.asarray(got.val))
    assert (ref.mask is None) == (got.mask is None)
    if ref.mask is not None:
        np.testing.assert_array_equal(ref.mask, got.mask)
    np.testing.assert_array_equal(ref.seg, got.seg)
    np.testing.assert_array_equal(ref.counts, got.counts)
    assert (ref.row_block, ref.group_block, ref.groups_per_shard,
            ref.n_shards) == (got.row_block, got.group_block,
                              got.groups_per_shard, got.n_shards)


def _bin(st, **kw):
    kw.setdefault("value_property", "rating")
    kw.setdefault("overrides", {"buy": 4.0})
    kw.setdefault("entity_type", "user")
    kw.setdefault("event_names", ["rate", "buy"])
    kw.setdefault("target_entity_type", "item")
    return st.bin_columnar(1, **kw)


# -- el_bin_columnar equivalence ------------------------------------------------

def test_el_bin_columnar_matches_python_reference(tmp_path):
    st = _store(tmp_path)
    try:
        _fill(st)
        cs, tr, ho, ref_u, ref_i = _reference(st, skip_mod=20, block_size=512)
        out = _bin(st, skip_mod=20, skip_rem=0, block_size=512)
        assert out.n_rows == len(tr[0])
        assert out.entity_vocab == cs.entity_vocab
        assert out.target_vocab == cs.target_vocab
        _assert_side_equal(ref_u, out.user_side)
        _assert_side_equal(ref_i, out.item_side)
        np.testing.assert_array_equal(ho[0], out.holdout[0].astype(np.int64))
        np.testing.assert_array_equal(ho[1], out.holdout[1].astype(np.int64))
        np.testing.assert_array_equal(ho[2], out.holdout[2])
        # kept-value sum backs the bench's global-mean baseline
        assert out.user_side.kept_value_sum == pytest.approx(
            float(np.sum(tr[2], dtype=np.float64)), rel=1e-9)
    finally:
        st.close()


def test_el_bin_columnar_tombstones_and_compaction(tmp_path):
    st = _store(tmp_path)
    try:
        _fill(st, n=30_000, seed=3)
        # tombstone a slice of rows via the row lane (mixed ids)
        ids = st.insert_batch([
            Event(event="rate", entity_type="user", entity_id=f"u{k % 50}",
                  target_entity_type="item", target_entity_id=f"i{k % 30}",
                  properties={"rating": 2.5},
                  event_time=dt.datetime(2026, 3, 1, tzinfo=UTC))
            for k in range(500)
        ], 1)
        for eid in ids[::3]:
            assert st.delete(eid, 1)
        _, _, _, ref_u, ref_i = _reference(st, block_size=256)
        out = _bin(st, block_size=256)
        _assert_side_equal(ref_u, out.user_side)
        _assert_side_equal(ref_i, out.item_side)
        # compaction renumbers nothing visible: live rows keep order
        st.compact(1)
        _, _, _, ref_u2, ref_i2 = _reference(st, block_size=256)
        out2 = _bin(st, block_size=256)
        _assert_side_equal(ref_u2, out2.user_side)
        _assert_side_equal(ref_i2, out2.item_side)
    finally:
        st.close()


def test_el_bin_columnar_empty_groups_and_single_events(tmp_path):
    """A user whose only event lands in the holdout leaves an EMPTY
    group (vocab row with zero kept entries) — counts 0, factors-solve
    pads; the native plan must match the reference's."""
    st = _store(tmp_path)
    try:
        # user u_only's single event is kept-ordinal 0 -> held out
        evs = [Event(event="rate", entity_type="user", entity_id="u_only",
                     target_entity_type="item", target_entity_id="i0",
                     properties={"rating": 5.0},
                     event_time=dt.datetime(2026, 1, 1, tzinfo=UTC))]
        evs += [Event(event="rate", entity_type="user",
                      entity_id=f"u{k % 37}", target_entity_type="item",
                      target_entity_id=f"i{k % 11}",
                      properties={"rating": (k % 9) / 2.0 + 0.5},
                      event_time=dt.datetime(2026, 1, 2, tzinfo=UTC))
                for k in range(4000)]
        st.insert_batch(evs, 1)
        _, tr, _, ref_u, ref_i = _reference(st, skip_mod=20, block_size=64)
        out = _bin(st, skip_mod=20, skip_rem=0, block_size=64)
        assert out.entity_vocab[0] == "u_only"
        assert out.user_side.counts[0] == 0  # all its events held out
        _assert_side_equal(ref_u, out.user_side)
        _assert_side_equal(ref_i, out.item_side)
    finally:
        st.close()


@pytest.mark.slow
def test_el_bin_columnar_idx16_overflow_vocab(tmp_path):
    """A >2^16 opposing vocab must grow the idx_hi stream, identically
    to the reference's _split_idx."""
    st = _store(tmp_path)
    try:
        n_items = 70_000
        n = 90_000
        rng = np.random.default_rng(5)
        # every item code referenced at least once (dense first-seen)
        items = np.concatenate([
            np.arange(n_items, dtype=np.int32),
            rng.integers(0, n_items, n - n_items).astype(np.int32)])
        cols = EventColumns(
            entity_codes=rng.integers(0, 500, n).astype(np.int32),
            target_codes=items,
            name_codes=np.zeros(n, np.int32),
            values=(0.5 + 0.5 * rng.integers(0, 10, n)).astype(np.float64),
            times_us=np.arange(n, dtype=np.int64),
            entity_vocab=[f"u{i}" for i in range(500)],
            target_vocab=[f"i{i}" for i in range(n_items)],
            names=["rate"],
        )
        st.insert_columnar(cols, 1, entity_type="user",
                           target_entity_type="item",
                           value_property="rating")
        _, _, _, ref_u, ref_i = _reference(st, block_size=512)
        out = _bin(st, block_size=512)
        assert out.user_side.idx_hi is not None      # items are >2^16
        assert out.item_side.idx_hi is None          # users are not
        _assert_side_equal(ref_u, out.user_side)
        _assert_side_equal(ref_i, out.item_side)
    finally:
        st.close()


def test_el_bin_columnar_non_affine_values_keep_f32(tmp_path):
    st = _store(tmp_path)
    try:
        n, users, items = 5000, 60, 40
        rng = np.random.default_rng(9)
        cols = EventColumns(
            entity_codes=rng.integers(0, users, n).astype(np.int32),
            target_codes=rng.integers(0, items, n).astype(np.int32),
            name_codes=np.zeros(n, np.int32),
            values=rng.normal(3.0, 1.0, n),   # continuous: not a ladder
            times_us=np.arange(n, dtype=np.int64),
            entity_vocab=[f"u{i}" for i in range(users)],
            target_vocab=[f"i{i}" for i in range(items)],
            names=["rate"],
        )
        st.insert_columnar(cols, 1, entity_type="user",
                           target_entity_type="item",
                           value_property="rating")
        _, _, _, ref_u, ref_i = _reference(st, block_size=64)
        out = _bin(st, block_size=64)
        assert out.user_side.affine is None
        assert out.user_side.mask is not None
        _assert_side_equal(ref_u, out.user_side)
        _assert_side_equal(ref_i, out.item_side)
    finally:
        st.close()


def test_el_bin_columnar_rejects_unknown_filter(tmp_path):
    st = _store(tmp_path)
    try:
        _fill(st, n=1000)
        with pytest.raises(TypeError):
            _bin(st, limit=5)
    finally:
        st.close()


# -- rb_bin_compressed fuzz -----------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("max_len,n_shards", [(None, 1), (64, 1), (None, 4)])
def test_rb_bin_compressed_fuzz(monkeypatch, seed, max_len, n_shards):
    """Ragged-shape fuzz: the COO-level native builder vs the Python
    two-stage reference across group skew, truncation, sharding, and
    both value regimes (affine ladder / continuous)."""
    monkeypatch.setattr(ragged, "_NATIVE_MIN_NNZ", 0)
    rng = np.random.default_rng(seed)
    n = int(rng.integers(5_000, 40_000))
    n_groups = int(rng.integers(50, 3_000))
    n_items = int(rng.integers(20, 2_000))
    g = rng.integers(0, n_groups, n).astype(np.int64)
    i = (rng.zipf(1.3, n) % n_items).astype(np.int64)
    if seed % 2:
        v = (1.0 + 0.5 * rng.integers(0, 9, n)).astype(np.float32)
    else:
        v = rng.normal(size=n).astype(np.float32)
    # leave a tail of groups EMPTY (vocab larger than touched groups)
    g = np.minimum(g, max(1, n_groups - 10))
    bs = int(rng.choice([64, 512, 4096]))
    got = ragged.build_compressed_segmented(
        g, i, v, n_groups, max_len=max_len, n_shards=n_shards,
        block_size=bs)
    assert got is not None
    sg = ragged.build_segmented_groups(
        g, i, v, n_groups, max_len=max_len, n_shards=n_shards,
        block_size=bs)
    ref = compress_side(sg, 0)
    _assert_side_equal(ref, got)
    assert got.kept_entries == int(sg.counts.sum())


def test_rb_bin_compressed_bad_group_raises(monkeypatch):
    monkeypatch.setattr(ragged, "_NATIVE_MIN_NNZ", 0)
    with pytest.raises(ValueError):
        ragged.build_compressed_segmented(
            np.array([0, 99], np.int64), np.zeros(2, np.int64),
            np.ones(2, np.float32), 10)


# -- chunked H2D pipeline -------------------------------------------------------

def test_chunked_device_put_matches_single_shot():
    from predictionio_tpu.ops.als import _chunked_device_put
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    for a in (rng.integers(0, 255, (4096, 64)).astype(np.uint8),
              rng.normal(size=(1000, 33)).astype(np.float32),
              rng.integers(0, 9, 100_000).astype(np.int32)):
        chunked = _chunked_device_put(a, chunk_bytes=32_768)
        np.testing.assert_array_equal(np.asarray(chunked),
                                      np.asarray(jnp.asarray(a)))
    # below-threshold arrays take the single-shot path unchanged
    small = rng.normal(size=(4, 4)).astype(np.float32)
    np.testing.assert_array_equal(
        np.asarray(_chunked_device_put(small, chunk_bytes=1 << 20)), small)


def test_from_sides_trains_identically_to_coo(monkeypatch):
    """The zero-copy construction (prebuilt sides -> from_sides) must
    produce the exact factors of the classic COO construction."""
    from predictionio_tpu.ops.als import build_compressed_side

    monkeypatch.setattr(ragged, "_NATIVE_MIN_NNZ", 0)
    rng = np.random.default_rng(4)
    n, users, items = 40_000, 500, 200
    u = rng.integers(0, users, n)
    i = rng.integers(0, items, n)
    v = (1.0 + 0.5 * rng.integers(0, 9, n)).astype(np.float64)
    cfg = ALSConfig(rank=8, iterations=3, block_size=512,
                    compute_dtype="float32", cg_dtype="float32")
    ref = ALSTrainer((u, i, v), users, items, cfg).run()
    user_side = build_compressed_side(u, i, v, users, cfg, 1, None)
    item_side = build_compressed_side(i, u, v, items, cfg, 1, None)
    got = ALSTrainer.from_sides(user_side, item_side, users, items, n,
                                cfg).run()
    np.testing.assert_allclose(ref.user_factors, got.user_factors,
                               atol=1e-6)
    np.testing.assert_allclose(ref.item_factors, got.item_factors,
                               atol=1e-6)


def test_double_buffer_env_off_still_equivalent(monkeypatch):
    monkeypatch.setenv("PIO_TRANSFER_DOUBLE_BUFFER", "0")
    from predictionio_tpu.ops.als import build_compressed_side

    rng = np.random.default_rng(6)
    n, users, items = 20_000, 200, 100
    u, i = rng.integers(0, users, n), rng.integers(0, items, n)
    v = (1.0 + 0.5 * rng.integers(0, 9, n)).astype(np.float64)
    cfg = ALSConfig(rank=8, iterations=2, block_size=256,
                    compute_dtype="float32", cg_dtype="float32")
    user_side = build_compressed_side(u, i, v, users, cfg, 1, None)
    item_side = build_compressed_side(i, u, v, items, cfg, 1, None)
    t = ALSTrainer.from_sides(user_side, item_side, users, items, n, cfg)
    f1 = t.run()
    f2 = ALSTrainer((u, i, v), users, items, cfg).run()
    np.testing.assert_allclose(f1.user_factors, f2.user_factors, atol=1e-6)


# -- mmap-backed warm loads -----------------------------------------------------

def test_warm_mmap_load_survives_concurrent_prune(tmp_path, monkeypatch):
    """A warm load holds numpy views over the entry file's mmap; a
    prune (this process or another) unlinking the file must not break
    the in-flight training run — POSIX keeps the mapping alive."""
    monkeypatch.setenv("PIO_BIN_CACHE_DIR", str(tmp_path / "bc"))
    from predictionio_tpu.ops import bincache
    from predictionio_tpu.ops.als import SideLayout, build_compressed_side

    rng = np.random.default_rng(8)
    n, users, items = 30_000, 300, 120
    u, i = rng.integers(0, users, n), rng.integers(0, items, n)
    v = (1.0 + 0.5 * rng.integers(0, 9, n)).astype(np.float64)
    cfg = ALSConfig(rank=8, iterations=2, block_size=256,
                    compute_dtype="float32", cg_dtype="float32")
    user_side = build_compressed_side(u, i, v, users, cfg, 1, None)
    item_side = build_compressed_side(i, u, v, items, cfg, 1, None)
    arrays = {**user_side.to_arrays("u_"), **item_side.to_arrays("i_")}
    meta = {"n_users": users, "n_items": items, "n_shards": 1,
            "total_entries": n, **user_side.meta("u_"),
            **item_side.meta("i_")}
    bincache.save("warmkey", arrays, meta)

    loaded = bincache.load("warmkey")
    assert loaded is not None
    arrs, m2 = loaded
    # concurrent prune: the entry vanishes from disk mid-use
    os.remove(os.path.join(bincache.cache_dir(), "warmkey.bin"))
    assert bincache.load("warmkey") is None
    us = SideLayout.from_arrays(arrs, "u_", m2)
    it = SideLayout.from_arrays(arrs, "i_", m2)
    got = ALSTrainer.from_sides(us, it, users, items, n, cfg).run()
    ref = ALSTrainer((u, i, v), users, items, cfg).run()
    np.testing.assert_allclose(ref.user_factors, got.user_factors,
                               atol=1e-6)


def test_bincache_save_is_atomic_and_prune_skips_fresh_temps(
        tmp_path, monkeypatch):
    monkeypatch.setenv("PIO_BIN_CACHE_DIR", str(tmp_path / "bc"))
    monkeypatch.setenv("PIO_BIN_CACHE_KEEP", "2")
    from predictionio_tpu.ops import bincache

    a = {"x": np.arange(100, dtype=np.int32)}
    for k in ("k1", "k2", "k3"):
        bincache.save(k, a, {"k": k})
    names = sorted(os.listdir(bincache.cache_dir()))
    assert len([f for f in names if f.endswith(".bin")]) == 2  # pruned
    # a FRESH temp (another process's save in flight) survives a prune;
    # a stale one is swept
    fresh = os.path.join(bincache.cache_dir(), "inflight.bin.tmp")
    stale = os.path.join(bincache.cache_dir(), "dead.bin.tmp")
    open(fresh, "wb").write(b"x")
    open(stale, "wb").write(b"x")
    old = 4000.0
    os.utime(stale, (old, old))
    bincache._prune(2)
    assert os.path.exists(fresh)
    assert not os.path.exists(stale)
    # a torn entry (truncated write published by force) degrades to None
    path = os.path.join(bincache.cache_dir(), "torn.bin")
    bincache.save("torn", a, {})
    data = open(path, "rb").read()
    open(path, "wb").write(data[: len(data) // 2])
    assert bincache.load("torn") is None


def test_rb_bin_compressed_nan_values_stay_uncoded(monkeypatch):
    """Review regression: a NaN among the raw values must force the
    f32+mask layout (np.unique keeps the NaN and the ladder check
    fails in the reference) — the old last-value sentinel collided
    with canonical-NaN bits and dropped it from the distinct set,
    silently affine-coding NaN slots to uniq[0]."""
    monkeypatch.setattr(ragged, "_NATIVE_MIN_NNZ", 0)
    g = np.arange(64, dtype=np.int64) % 8
    i = np.arange(64, dtype=np.int64) % 16
    v = np.where(np.arange(64) % 2 == 0, 2.0, 1.0).astype(np.float32)
    v[0] = np.nan
    got = ragged.build_compressed_segmented(g, i, v, 8, block_size=64)
    assert got.affine is None and got.mask is not None
    ref = compress_side(
        ragged.build_segmented_groups(g, i, v, 8, block_size=64), 0)
    _assert_side_equal(ref, got)


def test_twotower_engine_materializes_coo_from_binned_lane(tmp_path):
    """Review regression: the default-on binned lane hands a COO-less
    PreparedRatings to every algorithm sharing RecoDataSource — the
    two-tower trainer (and the hybrid engine) must materialize the COO
    through the columnar fallback instead of crashing on
    ``pd.ratings >= min_rating``."""
    from predictionio_tpu.core.params import EngineParams
    from predictionio_tpu.data.storage import Storage, set_storage
    from predictionio_tpu.models.twotower import TwoTowerParams
    from predictionio_tpu.parallel.mesh import MeshContext
    from predictionio_tpu.templates.recommendation import (
        RecoDataSourceParams,
    )
    from predictionio_tpu.templates.twotower import twotower_engine

    st = Storage.from_env({
        "PIO_STORAGE_SOURCES_EL_TYPE": "eventlog",
        "PIO_STORAGE_SOURCES_EL_PATH": str(tmp_path),
        **{f"PIO_STORAGE_REPOSITORIES_{r}_{k}": v
           for r in ("METADATA", "EVENTDATA", "MODELDATA")
           for k, v in (("NAME", r.lower()), ("SOURCE", "EL"))}})
    set_storage(st)
    try:
        app = st.apps().insert("tt")
        assert app.id == 1  # _fill writes to app 1
        st.events().init(app.id)
        _fill(st.events(), n=4000, users=60, items=30, seed=7)
        engine = twotower_engine()
        ep = EngineParams(
            data_source_params=("", RecoDataSourceParams(app_name="tt")),
            preparator_params=("", None),
            algorithm_params_list=[("twotower", TwoTowerParams(
                dim=8, embed_dim=8, hidden=(8,), epochs=1,
                batch_size=64))],
            serving_params=("", None))
        result = engine.train(MeshContext(), ep)
        model = result.models[0]
        assert len(model.user_ids) > 0 and len(model.item_ids) > 0
    finally:
        st.events().close()
        set_storage(None)


def test_holdout_views_do_not_pin_side_buffers(tmp_path):
    """Review regression: the holdout COO gets its OWN native owner —
    a retained holdout (bench keeps it for the RMSE gates) must not
    keep the multi-hundred-MB side buffers allocated after the trainer
    released them."""
    def owner_of(arr):
        a = arr
        while a is not None and not hasattr(a, "_owner"):
            a = a.base
        return a._owner

    st = _store(tmp_path)
    try:
        _fill(st, n=5000, users=60, items=30)
        out = _bin(st, skip_mod=20, skip_rem=0, block_size=64)
        side_owner = owner_of(out.user_side.idx_lo)
        hold_owner = owner_of(out.holdout[0])
        assert side_owner is not hold_owner
        assert owner_of(out.item_side.seg) is side_owner
    finally:
        st.close()


def test_read_prepared_is_memoized_per_request():
    from predictionio_tpu.templates.recommendation import BinnedReadRequest

    calls = []
    req = BinnedReadRequest(
        app_name="x", channel_name=None, entity_type="user",
        event_names=["rate"], target_entity_type="item",
        value_property="rating", overrides={})
    sentinel = object()
    req._prepared = sentinel  # a prior consumer's materialization
    assert req.read_prepared() is sentinel  # no second scan
    del calls
