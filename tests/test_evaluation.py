"""Metric family + MetricEvaluator + FastEval memoization
(ref specs: MetricTest.scala, MetricEvaluatorTest.scala,
FastEvalEngineTest.scala, EvaluationWorkflowTest.scala)."""

import json
import math

import pytest

from predictionio_tpu.core import Engine, EngineParams
from predictionio_tpu.core.evaluation import (
    AverageMetric,
    EngineParamsGenerator,
    Evaluation,
    FunctionMetric,
    MetricEvaluator,
    OptionAverageMetric,
    StdevMetric,
    SumMetric,
)
from predictionio_tpu.core.fast_eval import FastEvalEngineWorkflow
from predictionio_tpu.parallel.mesh import MeshContext
from predictionio_tpu.workflow.evaluate import run_evaluation

from tests.sample_engine import (
    Algo0,
    DataSource0,
    IdParams,
    Preparator0,
    Serving0,
)

ctx = MeshContext()


def make_eval_data(scores):
    """One fold whose qpa triples carry the given 'actual' scores."""
    return [(None, [(i, i, s) for i, s in enumerate(scores)])]


class ActualMetric(AverageMetric):
    def calculate_qpa(self, q, p, a):
        return float(a)


class OptionalMetric(OptionAverageMetric):
    def calculate_qpa(self, q, p, a):
        return None if a is None else float(a)


class StdevOfActual(StdevMetric):
    def calculate_qpa(self, q, p, a):
        return float(a)


class SumOfActual(SumMetric):
    def calculate_qpa(self, q, p, a):
        return float(a)


def test_metric_family():
    data = make_eval_data([1.0, 2.0, 3.0, 4.0])
    assert ActualMetric().calculate(ctx, data) == 2.5
    assert SumOfActual().calculate(ctx, data) == 10.0
    assert StdevOfActual().calculate(ctx, data) == pytest.approx(math.sqrt(1.25))
    opt = OptionalMetric().calculate(ctx, make_eval_data([1.0, None, 3.0]))
    assert opt == 2.0
    # multi-fold union (ref: sc.union across folds)
    two_folds = make_eval_data([1.0, 2.0]) + make_eval_data([3.0, 4.0])
    assert ActualMetric().calculate(ctx, two_folds) == 2.5
    assert ActualMetric().calculate(ctx, []) != ActualMetric().calculate(ctx, [])  # nan


def make_engine():
    return Engine(
        data_source_classes={"ds": DataSource0},
        preparator_classes={"prep": Preparator0},
        algorithm_classes={"algo": Algo0},
        serving_classes={"serve": Serving0},
    )


def make_params(algo_id):
    return EngineParams(
        data_source_params=("ds", IdParams(id=1)),
        preparator_params=("prep", IdParams(id=2)),
        algorithm_params_list=[("algo", IdParams(id=algo_id))],
        serving_params=("serve", IdParams(id=0)),
    )


def test_metric_evaluator_ranks_and_saves_best(tmp_path):
    # metric = algo id carried through prediction tags: higher algo id wins
    metric = FunctionMetric(lambda q, p, a: float(p.algo_id), name="algo-id")
    evaluation = Evaluation(engine=make_engine(), metric=metric)
    candidates = [make_params(3), make_params(7), make_params(5)]
    best_json = tmp_path / "best.json"
    evaluator = MetricEvaluator(best_json_path=str(best_json))
    result = evaluator.evaluate(ctx, evaluation, candidates)
    assert result.best_idx == 1
    assert result.best_score == 7.0
    assert result.metric_header == "algo-id"
    saved = json.loads(best_json.read_text())
    assert saved["algorithmParamsList"][0]["params"]["id"] == 7
    assert "7.0000" in result.to_one_liner()
    parsed = json.loads(result.to_json())
    assert parsed["bestIdx"] == 1 and len(parsed["engineParamsScores"]) == 3
    assert "<table" in result.to_html()


def test_lower_is_better_ordering():
    class LossMetric(FunctionMetric):
        higher_is_better = False

    metric = LossMetric(lambda q, p, a: float(p.algo_id), name="loss")
    evaluation = Evaluation(engine=make_engine(), metric=metric)
    result = MetricEvaluator().evaluate(
        ctx, evaluation, [make_params(3), make_params(7)]
    )
    assert result.best_idx == 0


def test_secondary_metrics_reported():
    m1 = FunctionMetric(lambda q, p, a: float(p.algo_id), name="primary")
    m2 = FunctionMetric(lambda q, p, a: float(q.q), name="mean-q")
    evaluation = Evaluation(engine=make_engine(), metric=m1, metrics=[m2])
    result = MetricEvaluator().evaluate(ctx, evaluation, [make_params(2)])
    assert result.other_metric_headers == ["mean-q"]
    assert len(result.engine_params_scores[0].other_scores) == 1


def test_fast_eval_memoizes_prefixes():
    """ref: FastEvalEngineTest.scala — shared prefixes computed once."""
    engine = make_engine()
    workflow = FastEvalEngineWorkflow(engine, ctx)
    # 3 candidates: same ds+prep, two distinct algo params
    eps = [make_params(3), make_params(3), make_params(9)]
    results = [workflow.eval(ep) for ep in eps]
    assert workflow.counts == {"read": 1, "prepare": 1, "train": 2, "predict": 2,
                               "grid_dispatches": 0}
    # identical candidates give identical results
    assert str(results[0]) == str(results[1])
    # different data source params invalidate the whole prefix
    ep_new_ds = make_params(3)
    ep_new_ds.data_source_params = ("ds", IdParams(id=42))
    workflow.eval(ep_new_ds)
    assert workflow.counts["read"] == 2
    assert workflow.counts["prepare"] == 2
    assert workflow.counts["train"] == 3
    # fast-eval result matches the plain engine eval
    plain = engine.eval(ctx, eps[0])
    fast = results[0]
    assert str(plain) == str(fast)


def test_run_evaluation_persists_instance(memory_storage):
    metric = FunctionMetric(lambda q, p, a: float(p.algo_id), name="m")
    evaluation = Evaluation(engine=make_engine(), metric=metric)
    gen = EngineParamsGenerator([make_params(3), make_params(8)])
    result = run_evaluation(
        evaluation,
        generator=gen,
        evaluation_class="tests.MyEval",
        storage=memory_storage,
    )
    assert result.best_score == 8.0
    instances = memory_storage.evaluation_instances().get_completed()
    assert len(instances) == 1
    inst = instances[0]
    assert inst.status == "EVALCOMPLETED"
    assert inst.evaluation_class == "tests.MyEval"
    assert "8.0000" in inst.evaluator_results
    assert json.loads(inst.evaluator_results_json)["bestScore"] == 8.0
    assert "<table" in inst.evaluator_results_html


def test_run_evaluation_failure_marks_instance(memory_storage):
    class BoomMetric(FunctionMetric):
        def calculate(self, ctx, eval_data):
            raise RuntimeError("boom")

    evaluation = Evaluation(engine=make_engine(), metric=BoomMetric(lambda q, p, a: 0.0))
    with pytest.raises(RuntimeError):
        run_evaluation(evaluation, engine_params_list=[make_params(1)], storage=memory_storage)
    instances = memory_storage.evaluation_instances().get_all()
    assert instances[0].status == "FAILED"


def test_nan_score_never_wins_lower_is_better():
    """A NaN-scored candidate (no eval data) must rank worst even when
    higher_is_better=False (sign flip must not turn NaN into +inf)."""

    class LossMetric(FunctionMetric):
        higher_is_better = False

    metric = LossMetric(lambda q, p, a: float(p.algo_id), name="loss")
    evaluation = Evaluation(engine=make_engine(), metric=metric)

    calls = {"n": 0}
    real_engine_eval = make_engine().eval

    def eval_fn(c, ep):
        # candidate 0 yields no eval data -> NaN score
        calls["n"] += 1
        if calls["n"] == 1:
            return []
        return evaluation.engine.eval(c, ep)

    result = MetricEvaluator().evaluate(
        ctx, evaluation, [make_params(9), make_params(4)], eval_fn=eval_fn
    )
    assert result.best_idx == 1
    assert result.best_score == 4.0


# ---------------------------------------------------------------------------
# Vmapped grid tuning through `pio eval`: when the
# candidates differ only in ALS reg, MetricEvaluator's candidates train
# in ONE compiled dispatch per fold (ALSAlgorithm.grid_train), with
# leaderboard/ranking/best.json identical to the sequential path.
# ---------------------------------------------------------------------------

def _reco_eval_setup(memory_storage, n_users=30, n_items=12, per_user=6):
    import numpy as np

    from predictionio_tpu.data.event import Event
    from predictionio_tpu.templates import recommendation as reco_t

    app = memory_storage.apps().insert("grid-app")
    memory_storage.events().init(app.id)
    rng = np.random.default_rng(5)
    events, m = [], 0
    import datetime as dt

    for u in range(n_users):
        for i in rng.choice(n_items, size=per_user, replace=False):
            events.append(Event(
                event="rate", entity_type="user", entity_id=f"u{u}",
                target_entity_type="item", target_entity_id=f"i{int(i)}",
                properties={"rating": float(1 + (u * int(i)) % 5)},
                event_time=dt.datetime(2026, 1, 1, tzinfo=dt.timezone.utc)
                + dt.timedelta(minutes=m)))
            m += 1
    memory_storage.events().insert_batch(events, app.id)
    return reco_t


def _grid_candidates(reco_t, regs):
    from predictionio_tpu.core.params import EngineParams
    from predictionio_tpu.models.als import ALSParams

    return [
        EngineParams(
            data_source_params=("", reco_t.RecoDataSourceParams(
                app_name="grid-app", columnar=False, eval_k=2)),
            preparator_params=("", None),
            algorithm_params_list=[("als", ALSParams(
                rank=4, num_iterations=3, lambda_=reg, block_size=32,
                compute_dtype="float32", cg_dtype="float32"))],
            serving_params=("", None),
        )
        for reg in regs
    ]


class _RatingMSE(AverageMetric):
    higher_is_better = False

    def calculate_qpa(self, q, p, a):
        match = [s["score"] for s in p["itemScores"]
                 if s["item"] == a["item"]]
        if not match:
            return None
        return (match[0] - a["rating"]) ** 2


def test_als_reg_grid_single_dispatch_matches_sequential(memory_storage):
    """6-point reg grid: one vmapped train dispatch per fold, identical
    ranking to the sequential path."""
    from predictionio_tpu.core.fast_eval import FastEvalEngineWorkflow
    from predictionio_tpu.parallel.mesh import MeshContext

    reco_t = _reco_eval_setup(memory_storage)
    regs = [0.01, 0.05, 0.1, 0.5, 1.0, 5.0]
    candidates = _grid_candidates(reco_t, regs)
    metric = _RatingMSE()
    engine = reco_t.recommendation_engine()
    ctx = MeshContext()

    # grid path, instrumented
    wf = FastEvalEngineWorkflow(engine, ctx)
    assert wf.prefetch_grid(candidates) == len(regs)
    n_folds = 2
    assert wf.counts["grid_dispatches"] == n_folds
    assert wf.counts["train"] == 0  # no sequential trains happened
    grid_results = [wf.eval(ep) for ep in candidates]
    assert wf.counts["train"] == 0  # scoring hit the seeded cache only
    grid_scores = [metric.calculate(ctx, r) for r in grid_results]

    # sequential oracle: plain per-candidate eval
    wf_seq = FastEvalEngineWorkflow(engine, ctx)
    seq_scores = [metric.calculate(ctx, wf_seq.eval(ep))
                  for ep in candidates]
    assert wf_seq.counts["train"] == len(regs)

    import numpy as np

    np.testing.assert_allclose(grid_scores, seq_scores, rtol=1e-4, atol=1e-5)
    assert np.argsort(grid_scores).tolist() == np.argsort(seq_scores).tolist()


def test_grid_prefetch_declines_heterogeneous_candidates(memory_storage):
    """Candidates differing beyond the reg scalar keep the sequential
    path (grid_train returns None; nothing is mis-cached)."""
    import dataclasses

    from predictionio_tpu.core.fast_eval import FastEvalEngineWorkflow
    from predictionio_tpu.parallel.mesh import MeshContext

    reco_t = _reco_eval_setup(memory_storage)
    candidates = _grid_candidates(reco_t, [0.01, 0.1])
    # second candidate also changes rank -> not a pure reg sweep
    slot_name, p1 = candidates[1].algorithm_params_list[0]
    candidates[1].algorithm_params_list[0] = (
        slot_name, dataclasses.replace(p1, rank=8))
    wf = FastEvalEngineWorkflow(reco_t.recommendation_engine(), MeshContext())
    assert wf.prefetch_grid(candidates) == 0
    assert wf.counts["grid_dispatches"] == 0


def test_run_evaluation_uses_grid_path(memory_storage, caplog):
    """The product `pio eval` path logs the one-dispatch proof."""
    import logging

    reco_t = _reco_eval_setup(memory_storage)
    candidates = _grid_candidates(reco_t, [0.01, 0.1, 1.0])
    evaluation = Evaluation(
        engine=reco_t.recommendation_engine(), metric=_RatingMSE())
    with caplog.at_level(logging.INFO, logger="predictionio_tpu.core.fast_eval"):
        result = run_evaluation(evaluation, engine_params_list=candidates,
                                storage=memory_storage)
    assert any("grid tuning: 3 candidates" in r.message for r in caplog.records)
    assert len(result.engine_params_scores) == 3
    assert result.best_idx in range(3)
