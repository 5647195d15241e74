"""Stored models and mid-training checkpoints from before the block stack
(PR 27) carry the flax module's parameter tree; they load and resume with
the code that stands. The blobs under ``tests/fixtures/sessionrec_flax`` were
written by the commit before (``make.py`` there says how)."""

import importlib.util
import json
import os
import pickle
import shutil

import jax
import numpy as np
import pytest

from predictionio_tpu.models.sessionrec import SessionRecAlgorithm
from predictionio_tpu.ops.sessionrec import (
    SessionRecConfig, SessionRecTrainer, init_stack, stack_tree_from_flax,
    stack_trees_from_flax)

HERE = os.path.join(os.path.dirname(__file__), "fixtures", "sessionrec_flax")


def _load(name):
    with open(os.path.join(HERE, name), "rb") as f:
        return pickle.load(f)


@pytest.fixture(scope="module")
def made():
    spec = importlib.util.spec_from_file_location(
        "sessionrec_flax_make", os.path.join(HERE, "make.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def expected():
    with open(os.path.join(HERE, "expected.json")) as f:
        return json.load(f)


def test_the_blobs_hold_the_flax_tree():
    with open(os.path.join(HERE, "ckpt", "ckpt_1.pkl"), "rb") as f:
        raw = pickle.load(f)["state"]["params"]["params"]
    assert "block_0" in raw and "blocks" not in raw
    assert set(raw["block_0"]) == {
        "LayerNorm_0", "DenseGeneral_0", "DenseGeneral_1", "LayerNorm_1",
        "Dense_0", "Dense_1"}


@pytest.mark.parametrize("query", [0, 1])
def test_a_stored_model_answers_as_the_commit_that_stored_it(expected, made,
                                                             query):
    model = _load("model.pkl")
    algo = SessionRecAlgorithm(made.SessionRecParams(**made.PARAMS))
    got = algo.predict(model, expected["queries"][query])["itemScores"]
    want = expected["answers"][query]["itemScores"]
    assert [s["item"] for s in got] == [s["item"] for s in want]
    np.testing.assert_allclose([s["score"] for s in got],
                               [s["score"] for s in want], rtol=1e-4,
                               atol=1e-5)


def test_a_stored_model_has_the_stacks_tree_after_loading(made):
    tree = _load("model.pkl").state.params["params"]
    cfg = SessionRecConfig(**made.PARAMS)
    fresh = init_stack(cfg.stack(), jax.random.PRNGKey(0), made.N_ITEMS + 1)
    assert (jax.tree_util.tree_structure(tree)
            == jax.tree_util.tree_structure(fresh))
    assert all(a.shape == b.shape for a, b in zip(
        jax.tree_util.tree_leaves(tree), jax.tree_util.tree_leaves(fresh)))


def test_a_checkpoint_from_before_resumes(tmp_path, monkeypatch, made,
                                          expected):
    shutil.copytree(os.path.join(HERE, "ckpt"), tmp_path / "ckpt")
    monkeypatch.chdir(tmp_path)
    pd = made.prepared()
    cfg = SessionRecConfig(**made.PARAMS, checkpoint_dir="ckpt")
    trainer = SessionRecTrainer((pd.user_idx, pd.item_idx, pd.times),
                                pd.n_users, pd.n_items, cfg)
    assert trainer._epochs_done == 1
    assert trainer._losses == expected["first_epoch_loss"]
    raw = _load(os.path.join("ckpt", "ckpt_1.pkl"))["state"]
    np.testing.assert_array_equal(
        trainer._params["params"]["blocks"][1]["ffn_a"]["w2"],
        raw["params"]["params"]["block_1"]["Dense_1"]["kernel"])
    np.testing.assert_array_equal(
        trainer._opt_state[0].mu["params"]["blocks"][0]["mixer_a"]["wqkv"],
        raw["opt_state"][0].mu["params"]["block_0"]["DenseGeneral_0"]
        ["kernel"])
    losses = trainer.run()
    assert len(losses) == 2 and losses[0] == expected["first_epoch_loss"][0]
    assert np.isfinite(losses[1]) and losses[1] < losses[0]


def test_a_tree_in_the_stacks_layout_is_left_as_it_is(made):
    cfg = SessionRecConfig(**made.PARAMS)
    tree = init_stack(cfg.stack(), jax.random.PRNGKey(1), 5)
    assert stack_tree_from_flax(tree) is tree
    both = stack_trees_from_flax({"params": tree})
    assert (jax.tree_util.tree_structure(both["params"])
            == jax.tree_util.tree_structure(tree))
    assert both["params"]["pos_embed"] is tree["pos_embed"]
