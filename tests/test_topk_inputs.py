"""One crossing each way (ISSUE 30): a search prepares its inputs in numpy,
hands them to the device inside the one compiled call and takes both results
back in one wait.

* the shape discipline (``ops/topk._prepare_score_inputs``) against the eager
  ``jnp`` discipline it replaced, kept here as the reference: the same values
  bit for bit, numpy out for host data in, a ``jax.Array`` kept on the device;
* a search runs no eager program: a raw batch size or exclusion width inside
  a bucket that is warm compiles nothing, through the index's kernel
  (interpret mode) and through the XLA fallback;
* numpy and ``jax.Array`` inputs give identical answers, and the scorers keep
  theirs.
"""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.index.exact import ExactIndex
from predictionio_tpu.ops import topk
from predictionio_tpu.ops.topk import (NEG_INF, ShardedTopKScorer, TopKScorer,
                                       _pow2_bucket, _prepare_score_inputs)

N_ITEMS, DIM, MAX_EXCLUDE = 300, 8, 64
RNG = np.random.default_rng(30)
ITEMS = RNG.normal(size=(N_ITEMS, DIM)).astype(np.float32)


def _old_prepare_score_inputs(user_vecs, k, exclude_idx, n_items, max_exclude):
    """The discipline as it stood before ISSUE 30 (eager ``jnp`` padding, two
    transfers): the reference the numpy one is held to."""
    user_vecs = jnp.atleast_2d(jnp.asarray(user_vecs, dtype=jnp.float32))
    B = user_vecs.shape[0]
    if exclude_idx is None:
        exclude_idx = np.full((B, 1), -1, dtype=np.int32)
    exclude_idx = np.asarray(exclude_idx, dtype=np.int32)
    if exclude_idx.ndim == 1:
        exclude_idx = np.broadcast_to(exclude_idx, (B, exclude_idx.shape[0]))
    exclude_idx = exclude_idx[:, -max_exclude:]
    e_bucket = _pow2_bucket(exclude_idx.shape[1], 1, max_exclude)
    if exclude_idx.shape[1] < e_bucket:
        pad = np.full((B, e_bucket - exclude_idx.shape[1]), -1, dtype=np.int32)
        exclude_idx = np.concatenate([exclude_idx, pad], axis=1)
    b_bucket = _pow2_bucket(B, 1, 1 << 30)
    if B < b_bucket:
        user_vecs = jnp.concatenate(
            [user_vecs,
             jnp.zeros((b_bucket - B, user_vecs.shape[1]), user_vecs.dtype)]
        )
        exclude_idx = np.concatenate(
            [exclude_idx,
             np.full((b_bucket - B, exclude_idx.shape[1]), -1, np.int32)]
        )
    k = min(k, n_items)
    k_bucket = min(_pow2_bucket(k, 8, 1 << 20), n_items)
    return user_vecs, jnp.asarray(exclude_idx), k, k_bucket, B


def _same(new, old):
    """Arrays equal bit for bit (shape, dtype, bytes), scalars equal."""
    for got, want in zip(new[:2], old[:2]):
        want = np.asarray(want)
        assert got.shape == want.shape and got.dtype == want.dtype
        assert np.asarray(got).tobytes() == want.tobytes()
    assert tuple(new[2:]) == tuple(old[2:])


class _NumpyThatTakesNoDeviceArray:
    """``numpy`` for the modules under test: a function handed a
    ``jax.Array`` raises. On the CPU backend a copy to the host is a view and
    no transfer guard sees it, so the test watches the only door there is:
    every copy the retrieval layer makes goes through its ``np``. (``shape``
    reads an attribute and copies nothing.)"""

    def __getattr__(self, name):
        real = getattr(np, name)
        if isinstance(real, type) or not callable(real) or name == "shape":
            return real

        def guarded(*args, **kwargs):
            assert not any(isinstance(a, jax.Array)
                           for a in (*args, *kwargs.values())), (
                f"np.{name} was handed a device array")
            return real(*args, **kwargs)
        return guarded


@contextlib.contextmanager
def no_copy_to_host():
    """Inside, ``ops/topk.py`` and ``index/exact.py`` cannot hand a
    ``jax.Array`` to numpy."""
    from predictionio_tpu.index import exact
    from predictionio_tpu.ops import topk

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(topk, "np", _NumpyThatTakesNoDeviceArray())
        patch.setattr(exact, "np", _NumpyThatTakesNoDeviceArray())
        yield


@pytest.mark.parametrize("B", range(1, 34))
def test_host_inputs_are_prepared_in_numpy_to_the_old_values(B):
    vecs = RNG.normal(size=(B, DIM)).astype(np.float32)
    for width in range(0, 71):
        excl = RNG.integers(-1, N_ITEMS, size=(B, width)).astype(np.int32)
        for k in (1, 10, 100, N_ITEMS - 1):
            new = _prepare_score_inputs(vecs, k, excl, N_ITEMS, MAX_EXCLUDE)
            assert type(new[0]) is np.ndarray and type(new[1]) is np.ndarray
            _same(new, _old_prepare_score_inputs(
                vecs, k, excl, N_ITEMS, MAX_EXCLUDE))
    # no list, one list for every row, float64 rows, a lone [D] vector
    shared = RNG.integers(0, N_ITEMS, size=5)
    for v, e in ((vecs, None), (vecs, shared), (vecs.astype(np.float64), None),
                 (vecs[0], shared), (vecs[0].tolist(), None)):
        new = _prepare_score_inputs(v, 10, e, N_ITEMS, MAX_EXCLUDE)
        assert type(new[0]) is np.ndarray and type(new[1]) is np.ndarray
        _same(new, _old_prepare_score_inputs(v, 10, e, N_ITEMS, MAX_EXCLUDE))


@pytest.mark.parametrize("B", range(1, 34))
def test_a_device_array_stays_on_the_device(B):
    host = RNG.normal(size=(B, DIM)).astype(np.float32)
    vecs = jnp.asarray(host)
    excl = RNG.integers(-1, N_ITEMS, size=(B, 3)).astype(np.int32)
    with no_copy_to_host():
        with pytest.raises(AssertionError, match="handed a device array"):
            topk.np.asarray(vecs)
        new = _prepare_score_inputs(vecs, 10, excl, N_ITEMS, MAX_EXCLUDE)
    assert isinstance(new[0], jax.Array) and type(new[1]) is np.ndarray
    if B == _pow2_bucket(B, 1, 1 << 30):
        assert new[0] is vecs       # a bucketed output is handed on as it is
    _same(new, _old_prepare_score_inputs(
        host, 10, excl, N_ITEMS, MAX_EXCLUDE))


# -- a search runs no eager program ------------------------------------------

class _Compiles:
    """Backend compilations, counted as ``benchmarks/run.py`` counts a
    window's (``window_compiles``): a ``jax.monitoring`` listener on the
    backend-compile event. Listeners cannot be taken off again, so there is
    one for the module."""

    count = 0


@pytest.fixture(scope="module")
def compiles():
    from jax import monitoring

    def on_duration(event, duration, **kw):
        if event.endswith("backend_compile_duration"):
            _Compiles.count += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    return _Compiles


def _index(kernel):
    # placement "device": the fallback's XLA program and not its host scan
    index = ExactIndex(kernel=kernel, block_items=256, placement="device")
    index.build(ITEMS)
    return index


@pytest.mark.parametrize("kernel,route", [("on", "kernel"),
                                          ("off", "xla_device")])
def test_a_search_inside_a_warm_bucket_compiles_nothing(compiles, kernel,
                                                        route):
    index = _index(kernel)
    queries = RNG.normal(size=(8, DIM)).astype(np.float32)
    excl = RNG.integers(0, N_ITEMS, size=(8, 4)).astype(np.int32)
    index.search(queries, 10)
    index.search(queries, 10, excl)
    assert compiles.count > 0          # the listener hears this backend
    warm = compiles.count
    for B in (5, 6, 7):
        # (before ISSUE 30 every raw B compiled its own zeros / concatenate)
        s, i = index.search(queries[:B], 10)
        assert s.shape == i.shape == (B, 10)
        index.search(queries[:B], 10, excl[:B, :3])
    assert compiles.count == warm
    assert index.stats()["routes"][route] == 8
    assert index.stats()["inputs"] == {"host": 8, "device": 0}


# -- the same answers ---------------------------------------------------------

def _reference(queries, k, excl=None, mask=None):
    scores = queries @ ITEMS.T
    if excl is not None:
        np.put_along_axis(scores, excl.astype(np.int64), float(NEG_INF),
                          axis=1)
    if mask is not None:
        scores = np.where(mask, scores, float(NEG_INF))
    best = np.argsort(-scores, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(scores, best, axis=1), best


@pytest.mark.parametrize("kernel", ["on", "off"])
@pytest.mark.parametrize("B", [1, 5, 8])
def test_numpy_and_device_inputs_give_identical_answers(kernel, B):
    index = _index(kernel)
    queries = RNG.normal(size=(B, DIM)).astype(np.float32)
    excl = RNG.integers(0, N_ITEMS, size=(B, 3)).astype(np.int32)
    on_device = jnp.asarray(queries)
    for e in (None, excl):
        host_s, host_i = index.search(queries, 10, e)
        with no_copy_to_host():
            dev_s, dev_i = index.search(on_device, 10, e)
        assert type(dev_s) is np.ndarray and type(dev_i) is np.ndarray
        assert dev_s.tobytes() == host_s.tobytes()
        assert dev_i.tobytes() == host_i.tobytes()
        # and they are the plain scan's
        want_s, want_i = _reference(queries, 10, e)
        np.testing.assert_array_equal(host_i, want_i)
        np.testing.assert_allclose(host_s, want_s, rtol=1e-5)
    assert index.stats()["inputs"] == {"host": 2, "device": 2}


@pytest.mark.parametrize("B", [1, 3, 4, 13])
def test_the_scorers_keep_their_results(B):
    from predictionio_tpu.parallel.mesh import create_mesh

    queries = RNG.normal(size=(B, DIM)).astype(np.float32)
    excl = RNG.integers(0, N_ITEMS, size=(B, 5)).astype(np.int32)
    row_mask = RNG.random(N_ITEMS) < 0.5
    batch_mask = RNG.random((B, N_ITEMS)) < 0.5
    device = TopKScorer(ITEMS, placement="device")
    sharded = ShardedTopKScorer(ITEMS, create_mesh({"data": 8}))
    for scorer in (device, sharded):
        for e in (None, excl):
            s, i = scorer.score(queries, 10, e)
            want_s, want_i = _reference(queries, 10, e)
            np.testing.assert_array_equal(i, want_i)
            np.testing.assert_allclose(s, want_s, rtol=1e-5)
    for mask in (row_mask, batch_mask):
        s, i = device.score_masked(queries, 10, mask)
        want_s, want_i = _reference(queries, 10, mask=mask)
        np.testing.assert_array_equal(i, want_i)
        np.testing.assert_allclose(s, want_s, rtol=1e-5)
        # a program's output is taken as it is
        s2, i2 = device.score_masked(jnp.asarray(queries), 10, mask)
        assert s2.tobytes() == s.tobytes() and i2.tobytes() == i.tobytes()
