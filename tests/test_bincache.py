"""Binned-layout cache + transfer compression.

Retraining on unchanged events must not re-pay read->bin: the
compressed device layout persists under the bin cache keyed by the
event log's O(1) fingerprint, and the compressed wire form
(lo/hi-split indexes, uint8 value codes) must train to exactly the
same factors as the uncompressed one.
"""

import numpy as np
import pytest

from predictionio_tpu.ops import als as als_mod
from predictionio_tpu.ops.als import (
    ALSConfig,
    ALSTrainer,
    LayoutCacheMiss,
    SideLayout,
    compress_side,
)


def _coo(n=60_000, users=800, items=300, seed=3):
    rng = np.random.default_rng(seed)
    u = rng.integers(0, users, n)
    i = rng.integers(0, items, n)
    v = (1.0 + (rng.integers(0, 9, n) * 0.5)).astype(np.float64)  # 9 values
    return (u, i, v), users, items


CFG = ALSConfig(rank=8, iterations=3, block_size=512,
                compute_dtype="float32", cg_dtype="float32")


def test_compressed_layout_trains_identically(monkeypatch):
    """uint8 value codes + int16 indexes decode to the exact floats the
    uncompressed path streams — factors must match to float tolerance."""
    coo, users, items = _coo()
    f_coded = ALSTrainer(coo, users, items, CFG).run()

    def no_compress(sg, n_opposing):
        lo, hi = als_mod._split_idx(sg.idx)
        return SideLayout(
            idx_lo=lo, idx_hi=hi, val=sg.val,
            mask=sg.mask.astype(np.uint8),
            seg=sg.seg, counts=sg.counts, affine=None,
            row_block=sg.row_block, group_block=sg.group_block,
            groups_per_shard=sg.groups_per_shard, n_shards=sg.n_shards)

    monkeypatch.setattr(als_mod, "compress_side", no_compress)
    f_plain = ALSTrainer(coo, users, items, CFG).run()
    np.testing.assert_allclose(
        f_coded.user_factors, f_plain.user_factors, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(
        f_coded.item_factors, f_plain.item_factors, rtol=2e-5, atol=2e-5)


def test_compression_kicks_in_and_shrinks_the_wire():
    coo, users, items = _coo()
    (u, i, v) = coo
    from predictionio_tpu.ops.als import _build_side

    side = compress_side(_build_side(u, i, v, users, CFG, 1, None), items)
    assert side.val.dtype == np.uint8 and side.mask is None
    # 300-item vocab: the hi index byte is dropped from the wire
    assert side.idx_lo.dtype == np.uint16 and side.idx_hi is None
    # value ladder is 1.0..5.0 in 0.5 steps -> affine; the pads' 0.0
    # filler stays OUT of the codebook (it would break the ladder)
    assert side.affine == (1.0, 0.5)
    assert side.slot_bytes == 3  # vs 9 uncompressed (idx4+val4+mask1)

    # >255 distinct values: stays float32 + mask
    v_many = v + np.arange(len(v)) * 1e-6
    side2 = compress_side(_build_side(u, i, v_many, users, CFG, 1, None), items)
    assert side2.val.dtype == np.float32 and side2.mask is not None
    assert side2.affine is None

    # few distinct values but NOT an affine ladder: a table decode
    # would need a second gather per slot, so it stays float32 + mask
    v_nonaffine = np.where(v > 3.0, 7.25, v)
    side3 = compress_side(
        _build_side(u, i, v_nonaffine, users, CFG, 1, None), items)
    assert side3.affine is None and side3.val.dtype == np.float32




def test_layout_cache_roundtrip(tmp_path, monkeypatch):
    monkeypatch.setenv("PIO_BIN_CACHE_DIR", str(tmp_path))
    coo, users, items = _coo()

    t1 = ALSTrainer(coo, users, items, CFG, cache_key="fp-abc")
    assert t1.cache_hit is False
    f1 = t1.run()

    # second trainer: NO COO at all — everything from the cache
    t2 = ALSTrainer(None, None, None, CFG, cache_key="fp-abc")
    assert t2.cache_hit is True
    assert (t2.n_users, t2.n_items) == (users, items)
    assert t2.kept_user_entries == t1.kept_user_entries
    assert t2.transfer_bytes == t1.transfer_bytes
    f2 = t2.run()
    np.testing.assert_allclose(f1.user_factors, f2.user_factors,
                               rtol=1e-6, atol=1e-6)

    # a different data fingerprint is a MISS, loudly
    with pytest.raises(LayoutCacheMiss):
        ALSTrainer(None, None, None, CFG, cache_key="fp-other")

    # layout-affecting config changes the key too (a rank change alters
    # the auto seg_len planning)
    with pytest.raises(LayoutCacheMiss):
        ALSTrainer(None, None, None,
                   ALSConfig(rank=16, iterations=3, block_size=512),
                   cache_key="fp-abc")


def test_eventlog_fingerprint_tracks_data(tmp_path):
    from tests.test_eventlog_backend import _mk, ev

    st = _mk(tmp_path)
    st.events().init(1)
    fp0 = st.events().data_fingerprint(1)
    st.events().insert_batch([ev("u1")], 1)
    fp1 = st.events().data_fingerprint(1)
    assert fp0 != fp1
    # unchanged data -> unchanged fingerprint (the warm-retrain key)
    assert st.events().data_fingerprint(1) == fp1
    ids = st.events().insert_batch([ev("u2", 1)], 1)
    fp2 = st.events().data_fingerprint(1)
    assert fp2 != fp1
    st.events().delete(ids[0], 1)
    assert st.events().data_fingerprint(1) != fp2
    st.events().close()

def test_index_wire_split_round_trips_past_16_bits():
    """lo-uint16 (+ hi-uint8 when the vocab crosses 2^16) must
    recombine to the exact int32 indexes, and a >65535-vocab side must
    train to the same factors as the uncompressed layout."""
    from predictionio_tpu.ops.als import _split_idx

    idx = np.array([[0, 1, 65_535, 65_536, 70_001, (1 << 24) - 1]],
                   dtype=np.int32)
    lo, hi = _split_idx(idx)
    assert lo.dtype == np.uint16 and hi.dtype == np.uint8
    np.testing.assert_array_equal(
        lo.astype(np.int32) | (hi.astype(np.int32) << 16), idx)
    # small vocab: no hi stream
    lo2, hi2 = _split_idx(np.array([[3, 65_535]], np.int32))
    assert hi2 is None
    # 24-bit overflow is a loud error, never silent truncation (a real
    # ValueError: asserts vanish under -O)
    with pytest.raises(ValueError):
        _split_idx(np.array([[1 << 24]], np.int32))


def test_wide_vocab_trains_identically(monkeypatch):
    """A >2^16 opposing vocab engages the hi byte; decoded gathers must
    match the uncompressed path bit-for-bit (same solves)."""
    rng = np.random.default_rng(5)
    n, users, items = 20_000, 300, 70_000
    u = rng.integers(0, users, n)
    i = rng.integers(0, items, n)
    v = (1.0 + (rng.integers(0, 9, n) * 0.5)).astype(np.float64)
    cfg = ALSConfig(rank=4, iterations=1, block_size=512,
                    compute_dtype="float32", cg_dtype="float32")
    f_coded = ALSTrainer((u, i, v), users, items, cfg).run()

    def no_compress(sg, n_opposing):
        lo, hi = als_mod._split_idx(sg.idx)
        return SideLayout(
            idx_lo=lo, idx_hi=hi, val=sg.val,
            mask=sg.mask.astype(np.uint8),
            seg=sg.seg, counts=sg.counts, affine=None,
            row_block=sg.row_block, group_block=sg.group_block,
            groups_per_shard=sg.groups_per_shard, n_shards=sg.n_shards)

    monkeypatch.setattr(als_mod, "compress_side", no_compress)
    f_plain = ALSTrainer((u, i, v), users, items, cfg).run()
    np.testing.assert_allclose(
        f_coded.user_factors, f_plain.user_factors, rtol=2e-5, atol=2e-5)
