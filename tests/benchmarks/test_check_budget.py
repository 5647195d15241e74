"""The budget of the comparison that decides ``correct`` (ISSUE 48), on the
CPU at the tiny sizes ``tiny_glm``, ``tiny_axk``, ``tiny_seq``, ``tiny_hyb``
and ``tiny_gen`` have: the order and the floor of the compared sample, the
traffic files' two keys and the ``# run:`` line; and the helpers of
``test_reference_lengths.py`` (one compilation for every history length) and
``test_reference_rows.py`` (an open cut crossed as one row), which were part
of this file until they held one worker for 156 s of the suite between them.
A CPU run is a control-flow check, never a device number."""

import argparse
import json
import os

import numpy as np
import pytest

from tests.benchmarks.test_program_spans import BENCHMARKS, HERE, load_file
from tests.benchmarks.test_seq_cell import harness  # noqa: F401

#: the traffic files of the session and slate cells: check_sample as the
#: issues that made them named it, and the two keys ISSUE 48 adds
BUDGETS = {
    "lifelong32k-c4": (12, 100, {"answers": 8, "first_queries": 2,
                                 "later_queries": 1, "later_past": 8192}),
    "lifelong-c4": (16, 80, {"answers": 10, "first_queries": 3,
                             "later_queries": 1, "later_past": 4096}),
    "sessions-c8": (32, 90, {"answers": 20, "first_queries": 5,
                             "later_queries": 1, "later_past": 2048}),
    "sessions-c16": (32, 80, {"answers": 20, "first_queries": 5,
                              "later_queries": 1, "later_past": 2048}),
    "slates-c8": (8, 60, {"answers": 5, "first_queries": 1,
                          "later_queries": 1, "later_past": 1024}),
}


def bench_of(harness, tiny: str, seed=7):
    import jax

    root = os.path.join(HERE, tiny)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    bench = harness.Bench(root, spec, spec["workloads"][0],
                          argparse.Namespace(seed=seed, seconds=1, trace=0))
    bench.devices = jax.devices()[:1]
    return bench


@pytest.fixture(scope="module")
def world(harness):
    """{tiny tree: (reference module, seeded weights, dims, vocabulary)},
    each made once."""
    made = {}

    def get(tiny):
        if tiny not in made:
            bench = bench_of(harness, tiny)
            builder = bench.load_module("models", bench.config["engine"])
            ref = bench.load_module("reference", bench.config["reference"])
            made[tiny] = (ref, builder.make_weights(bench),
                          ref.dims_of(bench.config),
                          int(bench.config["vocab_size"]))
        return made[tiny]

    return get


@pytest.fixture
def small_blocks(world):
    """The references' block of 1,024 rows cut to 16, so that histories of
    40-200 run the blocked form; the jitted parts are traced anew."""
    touched = []

    def cut(tiny):
        ref = world(tiny)[0]
        touched.append((ref, ref.BLOCK))
        ref.BLOCK = 16
        ref._jitted.cache_clear()
        return ref

    yield cut
    for ref, block in touched:
        ref.BLOCK = block
        ref._jitted.cache_clear()


def histories(vocab, *lengths, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=n).tolist() for n in lengths]


def logits_of(ref, *args, **kw):
    forward = getattr(ref, "_forward", ref.forward)
    return forward(*args, **kw)[0]


def span(logits):
    return float(logits.max() - logits.min())


# -- the order, the floor and the budget --------------------------------------

def entries_of(*shape):
    """``(length, first)`` pairs as a sample's entries."""
    return [{"rows": list(range(n)), "first": first, "body": ""}
            for n, first in shape]


MIX = {"check_sample": 8, "check_budget_s": 10,
       "check_floor": {"answers": 4, "first_queries": 1, "later_queries": 1,
                       "later_past": 100}}
SAMPLE = [(30, True), (500, False), (90, True), (120, False), (40, False),
          (450, True), (60, True), (70, False)]


class Clock:
    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now


def budget_of(mix=MIX, sample=SAMPLE, answered=50, longest=500):
    lib = load_file(os.path.join(BENCHMARKS, "check_budget.py"))
    clock = Clock()
    return lib.Budget(mix, entries_of(*sample), answered, longest,
                      clock), clock


def test_the_sample_is_compared_longest_first_and_the_longest_later_query_second():
    budget, _ = budget_of()
    order = [(len(e["rows"]), e["first"]) for e in budget.entries]
    assert order == [(500, False), (120, False), (450, True), (90, True),
                     (70, False), (60, True), (40, False), (30, True)]
    # the longest a first query: the longest later query still goes second
    budget, _ = budget_of(sample=[(900, True)] + SAMPLE, longest=900)
    assert [len(e["rows"]) for e in budget.entries][:3] == [900, 500, 450]


@pytest.mark.parametrize("spent, stops_at", [(0.0, None), (9.9, None),
                                             (10.0, 4), (1e9, 4)])
def test_no_further_answer_is_started_once_the_budget_is_spent_and_never_below_the_floor(
        spent, stops_at):
    budget, clock = budget_of()
    clock.now = spent
    asked = [budget.stop(done) for done in range(9)]
    if stops_at is None:
        assert not any(asked) and budget.stopped_at is None
        check = budget.check(8)
        assert check["ok"] and check["limit"] == ">= 8"
        assert not budget.check(7)["ok"]
        assert "did not fire" in budget.note(8)
    else:
        # four answers, one of them a first query, one a later query past
        # 100, the longest among them: not before
        assert asked.index(True) == stops_at
        budget.stop(stops_at)
        check = budget.check(stops_at)
        assert check["ok"] and check["limit"] == ">= 4"
        assert not budget.check(stops_at - 1)["ok"]
        assert "no further answer was started" in budget.note(stops_at)


def test_a_budget_of_nought_still_compares_the_floor():
    budget, _ = budget_of(dict(MIX, check_budget_s=0))
    done = 0
    while not budget.stop(done):
        done += 1
    assert done == 4 and budget.check(done)["ok"]
    # a floor that wants three first queries goes on until it has them
    mix = dict(MIX, check_budget_s=0, check_floor=dict(
        MIX["check_floor"], first_queries=3))
    budget, _ = budget_of(mix)
    done = 0
    while not budget.stop(done):
        done += 1
    assert done == 6 and budget.check(done)["ok"]


@pytest.mark.parametrize("sample, longest, why", [
    ([(30, True), (80, False), (90, True), (95, False), (40, False),
      (99, True), (60, True), (70, False)], 99, "no later query past 100"),
    (SAMPLE, 501, "the window's longest answered history is not in it"),
    ([(500, False), (120, False), (70, False), (40, False), (90, False),
      (450, False), (60, False), (30, False)], 500, "no first query"),
])
def test_a_sample_that_lacks_what_the_floor_names_fails_answers_compared(
        sample, longest, why):
    """Enforced, not printed: all eight compared, and still not correct."""
    budget, clock = budget_of(sample=sample, longest=longest)
    assert not any(budget.stop(done) for done in range(9))
    assert not budget.check(8)["ok"], why
    clock.now = 1e9
    assert not any(budget.stop(done) for done in range(9)), why


def test_a_file_without_the_two_keys_has_no_budget_and_the_whole_sample_as_floor():
    budget, clock = budget_of({"check_sample": 8})
    clock.now = 1e9
    assert not any(budget.stop(done) for done in range(9))
    assert budget.check(8)["ok"] and not budget.check(7)["ok"]
    # a window that answered fewer than check_sample is held to what it has
    budget, _ = budget_of({"check_sample": 8}, SAMPLE[:3], answered=3)
    assert budget.check(3)["ok"] and budget.check(3)["limit"] == ">= 3"
    budget, _ = budget_of({"check_sample": 8}, [], answered=0, longest=0)
    assert not budget.check(0)["ok"]


@pytest.mark.parametrize("tiny", ["tiny_glm", "tiny_axk", "tiny_seq",
                                  "tiny_hyb"])
def test_compare_stops_where_the_budget_says_and_keeps_the_samples_order(
        world, tiny):
    ref, weights, dm, vocab = world(tiny)
    hists = histories(vocab, 60, 45, 30, 20, seed=6)
    sample = [(h, ref.top_k_answer(ref.forward(weights, h, dm)[0], 5))
              for h in hists]
    asked = []
    got = ref.compare(weights, sample, 5, dm, reach=60,
                      stop=lambda done: (asked.append(done), done >= 2)[1])
    assert asked == [0, 1, 2]
    assert got["compared"] == 2 and got["longest_history"] == 60
    assert got["score_err"] < 1e-5
    assert got["positions_x_layers"] % (60 + 45) == 0
    whole = ref.compare(weights, sample, 5, dm)
    assert whole["compared"] == 4


# -- the traffic files and the run's own account ------------------------------

@pytest.mark.parametrize("name", sorted(BUDGETS))
def test_the_traffic_file_holds_its_budget_and_its_floor(name):
    with open(os.path.join(BENCHMARKS, "traffic", name + ".json")) as f:
        mix = json.load(f)
    sample, seconds, floor = BUDGETS[name]
    assert mix["check_sample"] == sample
    assert mix["check_budget_s"] == seconds
    assert mix["check_floor"] == floor
    # a floor the sample can hold: half of it are first queries
    assert floor["answers"] <= sample
    assert floor["first_queries"] <= sample // 2
    assert floor["later_past"] < mix["history_max"]
    lib = load_file(os.path.join(BENCHMARKS, "check_budget.py"))
    assert lib.floor_of(mix, 1000) == floor
    assert lib.floor_of(mix, 3)["answers"] == 3


@pytest.mark.parametrize("name", sorted(BUDGETS))
def test_the_windows_own_sessions_hold_the_floors_long_histories(name):
    """The sessions a window opens on are the same in every run
    (``sessions_seed``, the start in connection order): among the first
    three each connection plays after its warm-up there is one whose later
    queries pass the floor's length."""
    with open(os.path.join(BENCHMARKS, "traffic", name + ".json")) as f:
        mix = json.load(f)
    traffic = load_file(os.path.join(BENCHMARKS, "session_traffic.py"))
    warm = int(mix["warmup_sessions_per_connection"])
    played = [traffic.Sessions(mix, 10_000).order(c)[warm:warm + 3]
              for c in range(int(mix["connections"]))]
    assert sum(h > mix["check_floor"]["later_past"]
               for order in played for h in order) >= 2


@pytest.mark.parametrize("tiny, cell", [
    ("tiny_glm", "glm-tiny.lifelong-c2"), ("tiny_gen", "sdar-tiny.slates-c4")])
def test_a_run_says_where_its_seconds_went(harness, capsys, tiny, cell):
    code = harness.main(["--bench-root", os.path.join(HERE, tiny),
                         "--rehearse-cpu", "--workload", cell, "--seed",
                         "5000000012", "--seconds", "1"])
    lines = capsys.readouterr().out.strip().splitlines()
    assert code == 0 and json.loads(lines[-1])["correct"] is True
    account = [l for l in lines if l.startswith("# run: seconds by phase: ")]
    assert len(account) == 1 and lines.index(account[0]) == len(lines) - 2
    for phase in ("deploy ", "warm-up sessions ", "window and drain ",
                  "readers, server stopped ", "comparison ",
                  "whole process "):
        assert phase in account[0], phase
    assert "compiling" in account[0]
    # not in the result, read by no metric
    assert "run" not in json.loads(lines[-1])
    budget = [l for l in lines if l.startswith("# comparison budget: ")]
    assert len(budget) == 1 and "the budget did not fire" in budget[0]
    assert any(l.startswith("# check answers_compared: ") and l.endswith("ok")
               for l in lines)
