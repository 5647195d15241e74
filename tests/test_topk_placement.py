"""Latency-aware serving placement (ops.topk): the host path must be
semantically identical to the device path, and the auto route must pick
the device only when batch*catalog FLOPs amortize the measured dispatch
floor. Reference role: MLlib's recommendProducts is a driver-side scan
(SURVEY.md §7.5) — the host path IS that contract; the device path and
the sharded scorer are the TPU upgrades on it."""

import numpy as np
import pytest

from predictionio_tpu.ops import topk as T


@pytest.fixture()
def factors():
    rng = np.random.default_rng(3)
    return rng.normal(size=(257, 16)).astype(np.float32)


def test_host_matches_device(factors):
    rng = np.random.default_rng(4)
    uv = rng.normal(size=(5, 16)).astype(np.float32)
    excl = np.array([[0, 1, -1], [5, -1, -1], [-1, -1, -1],
                     [250, 251, 252], [7, 8, 9]], np.int32)
    host = T.TopKScorer(factors, placement="host")
    dev = T.TopKScorer(factors, placement="device")
    hs, hi = host.score(uv, 7, excl)
    ds, di = dev.score(uv, 7, excl)
    np.testing.assert_array_equal(hi, di)
    np.testing.assert_allclose(hs, ds, rtol=1e-4, atol=1e-4)
    # excluded ids never appear
    for b in range(5):
        assert not set(excl[b][excl[b] >= 0]) & set(hi[b])


def test_host_matches_device_masked(factors):
    rng = np.random.default_rng(5)
    uv = rng.normal(size=(3, 16)).astype(np.float32)
    mask = rng.random(257) > 0.5
    host = T.TopKScorer(factors, placement="host")
    dev = T.TopKScorer(factors, placement="device")
    hs, hi = host.score_masked(uv, 9, mask)
    ds, di = dev.score_masked(uv, 9, mask)
    np.testing.assert_array_equal(hi, di)
    np.testing.assert_allclose(hs, ds, rtol=1e-4, atol=1e-4)
    assert mask[hi].all()


def test_host_k_exceeds_catalog(factors):
    host = T.TopKScorer(factors[:5], placement="host")
    s, i = host.score(np.ones((1, 16), np.float32), 10)
    assert s.shape == (1, 5) and sorted(i[0]) == list(range(5))


def test_host_respects_max_exclude_cap(factors):
    """Entries beyond max_exclude are dropped oldest-first on BOTH paths."""
    host = T.TopKScorer(factors, placement="host", max_exclude=2)
    dev = T.TopKScorer(factors, placement="device", max_exclude=2)
    uv = np.ones((1, 16), np.float32)
    excl = np.array([[3, 4, 5, 6]], np.int32)  # 3, 4 dropped (oldest)
    # k=255 keeps the comparison away from the tied NEG_INF tail (the
    # two excluded entries), where ordering is legitimately unspecified
    _, hi = host.score(uv, 255, excl)
    _, di = dev.score(uv, 255, excl)
    np.testing.assert_array_equal(hi, di)
    assert not {5, 6} & set(hi[0])
    assert {3, 4} <= set(hi[0])  # the dropped-oldest ids still rank


def test_auto_routing_crossover(factors, monkeypatch):
    scorer = T.TopKScorer(factors, placement="auto")
    # a backend with a slow dispatch: lone queries must go host-side
    monkeypatch.setattr(T, "_dispatch_latency", 0.1)
    assert scorer._route(1) == "host"
    # ...but a big batch amortizes the dispatch floor
    assert scorer._route(200_000) == "device"
    # a locally-attached chip: even lone queries stay on device only if
    # the host matvec is slower — tiny catalog => host still wins
    monkeypatch.setattr(T, "_dispatch_latency", 1e-4)
    assert scorer._route(1) == "host"
    big = T.TopKScorer(np.zeros((3_000_000, 64), np.float32), placement="auto")
    assert big._route(64) == "device"


def test_env_override(factors, monkeypatch):
    monkeypatch.setenv("PIO_SERVE_PLACEMENT", "device")
    assert T.TopKScorer(factors).placement == "device"
    monkeypatch.setenv("PIO_SERVE_PLACEMENT", "bogus")
    with pytest.raises(ValueError):
        T.TopKScorer(factors)


def test_host_route_never_touches_device(factors, monkeypatch):
    """A host-placed deployment must not allocate the catalog in HBM."""
    scorer = T.TopKScorer(factors, placement="host")
    scorer.score(np.ones((2, 16), np.float32), 5)
    assert scorer._device_factors is None
