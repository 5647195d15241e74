"""One retriever for every batch size (ISSUE 28): a factor model's lone
queries and its micro-batches are searches of the SAME retrieval index, a
query inside a batch gets the answer it would get alone, and only a mesh
keeps a scorer of its own."""

import json
import logging
import threading
import urllib.request

import numpy as np
import pytest

from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.models.als import ALSAlgorithm, ALSModel, ALSParams
from predictionio_tpu.models.twotower import (TwoTowerAlgorithm,
                                              TwoTowerModel, TwoTowerParams)
from predictionio_tpu.ops.als import ALSFactors
from predictionio_tpu.ops.topk import ShardedTopKScorer, TopKScorer
from predictionio_tpu.parallel.mesh import MeshContext, create_mesh


def _model(kernel="auto", n_users=12, n_items=300, rank=8, cls=ALSModel):
    rng = np.random.default_rng(28)
    return cls(
        ALSFactors(
            user_factors=rng.normal(size=(n_users, rank)).astype(np.float32),
            item_factors=rng.normal(size=(n_items, rank)).astype(np.float32)),
        BiMap.string_int([f"u{j}" for j in range(n_users)]),
        BiMap.string_int([f"i{j}" for j in range(n_items)]),
        index_kernel=kernel)


def _best(model, user, n):
    row = model.user_ids[user]
    order = np.argsort(-(model.item_factors @ model.user_factors[row]))
    return [f"i{j}" for j in order[:n]]


def _same(batched, alone):
    assert [e["item"] for e in batched["itemScores"]] == \
        [e["item"] for e in alone["itemScores"]]
    np.testing.assert_allclose(
        [e["score"] for e in batched["itemScores"]],
        [e["score"] for e in alone["itemScores"]], rtol=1e-5, atol=1e-6)


def _payloads(kind, model):
    """The batch of one case: the payloads under test among plain ones."""
    plain = [{"user": "u1", "num": 5}, {"user": "u2", "num": 5}]
    if kind == "plain":
        return plain + [{"user": "u3"}, {"user": "u1", "num": 5}]
    if kind == "blacklist":
        top = _best(model, "u4", 3)
        # a blacklist past the index's cap of 64 beside a short one: each
        # row keeps its OWN newest entries
        long = [f"i{j}" for j in range(100, 170)] + top[:1]
        return plain + [
            {"user": "u4", "num": 4, "blacklist": top[:2] + ["no-such-item"]},
            {"user": "u4", "num": 4, "blacklist": long},
            {"user": "u5", "num": 4, "blacklist": []}]
    if kind == "whitelist":
        return plain + [
            {"user": "u6", "num": 3, "whitelist": ["i1", "i2", "i3", "i9"],
             "blacklist": ["i2"]},
            {"user": "u7", "num": 3, "whitelist": []}]
    if kind == "item":
        return plain + [
            {"item": "i7", "num": 6},
            {"item": "i8", "num": 4, "blacklist": ["i7", "i8", "i11"]},
            {"item": "no-such-item", "num": 4}]
    if kind == "unknown-user":
        return [{"user": "nobody", "num": 5}] + plain + [{"user": "ghost"}]
    if kind == "mixed-num":
        return [{"user": "u1", "num": 3}, {"user": "u2", "num": 12},
                {"user": "u3", "num": 1}, {"item": "i5", "num": 9}]
    raise AssertionError(kind)


@pytest.mark.parametrize("kernel", ["on", "off"])
@pytest.mark.parametrize("kind", ["plain", "blacklist", "whitelist", "item",
                                  "unknown-user", "mixed-num"])
def test_a_batched_query_gets_the_answer_it_gets_alone(kind, kernel):
    model = _model(kernel)
    algo = ALSAlgorithm(ALSParams(rank=8))
    payloads = _payloads(kind, model)
    batched = dict(algo.batch_predict(model, list(enumerate(payloads))))
    assert sorted(batched) == list(range(len(payloads)))
    for i, payload in enumerate(payloads):
        _same(batched[i], algo.predict(model, payload))
    routes = model.retrieval_index().stats()["routes"]
    assert (routes["kernel"] > 0) == (kernel == "on")
    if kind == "blacklist":
        top = _best(model, "u4", 3)
        short, long = batched[2]["itemScores"], batched[3]["itemScores"]
        assert [e["item"] for e in short][0] == top[2]
        assert top[0] not in [e["item"] for e in long]
    if kind == "whitelist":
        assert {e["item"] for e in batched[2]["itemScores"]} \
            == {"i1", "i3", "i9"}
        assert batched[3] == {"itemScores": []}
    if kind == "item":
        assert all(e["item"] != "i7" for e in batched[2]["itemScores"])
        assert not {"i7", "i8", "i11"} & {
            e["item"] for e in batched[3]["itemScores"]}
        assert batched[4] == {"itemScores": []}
    if kind == "unknown-user":
        assert batched[0] == batched[3] == {"itemScores": []}
    if kind == "mixed-num":
        assert [len(batched[i]["itemScores"]) for i in range(4)] \
            == [3, 12, 1, 9]


def test_a_malformed_query_still_raises_from_the_batch():
    """The batcher isolates a poison query by re-running its batch one by
    one: a payload with neither ``user`` nor ``item`` must keep raising."""
    model = _model()
    algo = ALSAlgorithm(ALSParams(rank=8))
    with pytest.raises(KeyError):
        algo.batch_predict(model, [(0, {"user": "u1"}), (1, {"num": 3})])


@pytest.mark.parametrize("kernel", ["on", "off"])
def test_warmup_builds_one_retriever_and_counts_its_searches(kernel):
    model = _model(kernel)
    algo = ALSAlgorithm(ALSParams(rank=8))
    algo.warmup(model, MeshContext())
    assert model.scorer() is None               # no scorer of the model's own
    index = model.retrieval_index()
    stats = index.stats()
    # B buckets 1..64 x k buckets 8 and 16, every one the index's
    assert stats["searches"] == 14
    assert sum(stats["routes"].values()) == 14
    if kernel == "on":
        assert stats["routes"]["kernel"] == 14
        assert index._scorer is None            # nor a fallback's copy
        assert sorted(index._fns) == sorted(
            (b, 1, k) for b in (1, 2, 4, 8, 16, 32, 64) for k in (8, 16))
    before = sum(stats["routes"].values())
    algo.batch_predict(model, list(enumerate(
        [{"user": f"u{j}", "num": 10} for j in range(5)])))
    stats = index.stats()
    assert stats["searches"] == 15
    assert sum(stats["routes"].values()) == before + 1
    if kernel == "on":
        assert len(index._fns) == 14            # nothing new to compile


def test_twotower_shares_the_one_retriever():
    model = _model("on", rank=16, cls=TwoTowerModel)
    algo = TwoTowerAlgorithm(TwoTowerParams())
    payloads = [{"user": "u1", "num": 4}, {"item": "i3", "num": 4},
                {"user": "u2", "num": 2, "blacklist": _best(model, "u2", 1)}]
    batched = dict(algo.batch_predict(model, list(enumerate(payloads))))
    for i, payload in enumerate(payloads):
        _same(batched[i], algo.predict(model, payload))
    assert model.scorer() is None
    assert model.retrieval_index().stats()["routes"]["kernel"] == 4


def test_a_sharded_model_batches_through_its_mesh_scorer(monkeypatch):
    model = _model(n_items=301)                 # not a multiple of 8: padded
    algo = ALSAlgorithm(ALSParams(rank=8))
    payloads = [{"user": "u1", "num": 5},
                {"user": "u2", "num": 3, "blacklist": _best(model, "u2", 2)},
                {"item": "i4", "num": 4}]
    unsharded = dict(algo.batch_predict(model, list(enumerate(payloads))))

    sharded = _model(n_items=301)
    sharded.enable_sharded_serving(create_mesh({"data": 8}))
    assert isinstance(sharded.scorer(), ShardedTopKScorer)
    calls = []
    score = ShardedTopKScorer.score

    def spy(self, vecs, k, exclude_idx=None):
        calls.append(np.atleast_2d(vecs).shape[0])
        return score(self, vecs, k, exclude_idx)

    monkeypatch.setattr(ShardedTopKScorer, "score", spy)
    algo.warmup(sharded, MeshContext())
    assert sorted(set(calls)) == [1, 2, 4, 8, 16, 32, 64]
    del calls[:]
    batched = dict(algo.batch_predict(sharded, list(enumerate(payloads))))
    assert calls == [3]                         # one dispatch, on the mesh
    assert sharded._index is None               # no single-device index
    for i in range(len(payloads)):
        _same(batched[i], unsharded[i])
        _same(batched[i], algo.predict(sharded, payloads[i]))


def test_the_model_builds_no_scorer_of_its_own(monkeypatch):
    """With the kernel engaged nothing constructs a ``TopKScorer``: the
    second device copy of the item table is never made."""
    built = []
    init = TopKScorer.__init__

    def counting(self, *a, **kw):
        built.append(1)
        init(self, *a, **kw)

    monkeypatch.setattr(TopKScorer, "__init__", counting)
    model = _model("on")
    algo = ALSAlgorithm(ALSParams(rank=8))
    algo.warmup(model, MeshContext())
    algo.predict(model, {"user": "u1", "num": 3})
    algo.batch_predict(model, [(0, {"user": "u1"}), (1, {"item": "i2"})])
    assert built == []
    # beyond the kernel's caps the INDEX falls back, on its own scorer
    rows = model.user_factors[np.arange(129) % 12]
    model.retrieve(rows, 3)
    assert built == [1] and model.scorer() is None
    assert model.retrieval_index().stats()["routes"]["kernel"] == 16


# ---------------------------------------------------------------------------
# through the deployed server: a batch that holds an item query stays a batch
# ---------------------------------------------------------------------------

def _post(port, payload, into):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/queries.json",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as resp:
        into.append((payload, json.loads(resp.read())))


def test_a_batch_with_an_item_query_is_not_rerun_one_by_one(
        memory_storage, caplog):
    from predictionio_tpu.resilience import chaos

    from tests.test_device_spans import deploy_tiny_als

    server = deploy_tiny_als(memory_storage)
    try:
        answers = []
        _post(server.port, {"user": "u1", "num": 5}, answers)   # warm
        before = server._batcher.histogram()["batchSizeHistogram"]
        payloads = [{"item": "i3", "num": 5},
                    {"user": "u2", "num": 5, "blacklist": ["i1", "i2"]},
                    {"user": "u3", "num": 4, "whitelist": ["i5", "i6"]},
                    {"item": "i9", "num": 3, "blacklist": ["i3"]},
                    {"user": "nobody", "num": 5}]
        with caplog.at_level(logging.WARNING):
            # the worker sleeps at its chaos seam with one query in hand:
            # those that arrive meanwhile leave as one batch
            chaos.configure("batcher:latency:200ms")
            try:
                threads = [threading.Thread(target=_post,
                                            args=(server.port, p, answers))
                           for p in payloads]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(30)
                assert not any(t.is_alive() for t in threads)
            finally:
                chaos.reset()
        assert "re-running individually" not in caplog.text
        after = server._batcher.histogram()["batchSizeHistogram"]
        assert any(int(size) > 1 and n > before.get(size, 0)
                   for size, n in after.items()), after
        assert len(answers) == 1 + len(payloads)
        for payload, answer in answers[1:]:
            _same(answer, server.deployment.query(payload))
        routes = server.deployment.models[0].retrieval_index().stats()
        assert server.deployment.models[0].scorer() is None
        assert sum(routes["routes"].values()) == routes["searches"]
    finally:
        server.stop()
