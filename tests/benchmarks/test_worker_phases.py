"""The worker's cycle on two clocks (ISSUE 37): the accounting of
``obs/trace.py`` on a thread that asked for it, where the two serving workers
and the sequence model's step show it, the two readers of
``MicroBatcher.histogram()["phases"]``, and their entries in the repo's
``BENCHMARK.json``."""

import gc
import logging
import os
import threading
import time

import numpy as np
import pytest

from predictionio_tpu.obs import health, trace
from predictionio_tpu.resilience import chaos
from predictionio_tpu.serving import engine_server as engine_server_mod
from predictionio_tpu.serving.engine_server import MicroBatcher
from tests.benchmarks import repo_spec
from tests.benchmarks.test_program_spans import (BENCHMARKS, FakeBench,
                                                 load_file, read)

MS = 1_000_000
HOST, IDLE = "worker_host_ms.serve", "worker_idle_ms.serve"
BOTH = ["als-amazon14.serve-c32", "als-amazon14.serve-c1"]
ENTRIES = {
    name: {"name": name, "unit": "ms", "better": "lower",
           "source": "program_counter", "layer": "engine", "moves": moves,
           "workloads": cells}
    for name, moves, cells in ((HOST, "query_p50_ms", BOTH),
                               (IDLE, "query_rate", BOTH[:1]))}


def on_a_thread(fn, account=True):
    """``fn()``'s result from a thread of its own, which asked for an
    account first (or did not)."""
    out = {}

    def body():
        if account:
            trace.account_thread()
        out["result"] = fn()
        out["account"] = trace.thread_account()

    t = threading.Thread(target=body)
    t.start()
    t.join()
    return out["result"], out["account"]


def spin(seconds):
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        pass


# -- the accounting ------------------------------------------------------------

def test_nested_spans_self_times_add_up_to_the_outer_spans():
    def work():
        t0 = time.perf_counter_ns()
        with trace.device_span("t.outer"):
            spin(0.004)
            with trace.device_span("t.inner", size=1):
                spin(0.006)
                with trace.device_span("t.leaf"):
                    spin(0.002)
            with trace.device_span("t.inner", size=2):
                spin(0.003)
        return time.perf_counter_ns() - t0

    outer_wall, account = on_a_thread(work)
    got = account.snapshot()
    assert [got[n][0] for n in ("t.outer", "t.inner", "t.leaf")] == [1, 2, 1]
    selfs = sum(got[n][1] for n in ("t.outer", "t.inner", "t.leaf"))
    # the names add up to the outer span, whatever a loaded machine adds to
    # each (the clock reads around it apart): nothing is counted twice,
    # nothing is lost
    assert 15 * MS <= selfs <= outer_wall
    assert got["t.outer"][1] >= 4 * MS
    assert got["t.inner"][1] >= 9 * MS
    assert got["t.leaf"][1] >= 2 * MS
    assert account.innermost() is None


def test_a_sleeping_span_reads_no_cpu_and_a_busy_one_what_it_burnt():
    def work():
        with trace.device_span("t.sleep"):
            time.sleep(0.05)
        with trace.device_span("t.busy"):
            # 50 ms of this thread's own CPU, however long a loaded test
            # machine takes to grant them
            c0 = time.thread_time_ns()
            while time.thread_time_ns() - c0 < 50 * MS:
                pass

    _, account = on_a_thread(work)
    got = account.snapshot()
    _, wall, cpu = got["t.sleep"]
    assert wall >= 50 * MS and cpu < 5 * MS
    _, wall, cpu = got["t.busy"]
    # (the two clocks are read one after the other: a microsecond apart)
    assert 50 * MS <= cpu <= wall + MS // 10 and cpu < 60 * MS


def test_a_cpu_clock_too_dear_to_read_is_not_read(monkeypatch):
    """A sandboxed kernel answers the thread's CPU clock in microseconds
    and in steps of 10 ms (the benchmark's machine, PR 37): the account then
    keeps the wall clock alone and says None, never a number."""
    monkeypatch.setattr(trace, "CPU_READ_LIMIT_NS", -1)
    reads = []
    monkeypatch.setattr(time, "thread_time_ns",
                        lambda: reads.append(1) or 0)

    def work():
        calibrated = len(reads)
        with trace.device_span("t.outer"):
            with trace.device_span("t.busy"):
                spin(0.005)
        return calibrated

    calibrated, account = on_a_thread(work)
    assert account.cpu_clock is None
    assert calibrated == len(reads) == 5      # asked five times, then never
    got = account.snapshot()
    assert got["t.busy"][:2] == [1, got["t.busy"][1]] and got["t.busy"][1] \
        >= 5 * MS
    assert all(v[2] is None for v in got.values())
    # a cheap clock is read
    monkeypatch.undo()
    _, account = on_a_thread(lambda: None)
    assert account.cpu_clock is time.thread_time_ns


def test_the_time_between_outermost_spans_is_unspanned():
    def work():
        t0 = time.perf_counter_ns()
        with trace.device_span("t.a"):
            pass
        time.sleep(0.02)
        with trace.device_span("t.b"):
            pass
        return time.perf_counter_ns() - t0

    took, account = on_a_thread(work)
    got = account.snapshot()
    assert got[trace.UNSPANNED][0] == 2
    assert 20 * MS <= got[trace.UNSPANNED][1] <= took + 5 * MS
    assert sum(v[1] for v in got.values()) >= took - 1 * MS


def test_a_snapshot_holds_what_the_open_spans_have_had_so_far():
    parked, go, accounts = threading.Event(), threading.Event(), []

    def work():
        accounts.append(trace.account_thread())
        with trace.device_span("t.outer"):
            spin(0.002)
            with trace.device_span("batch.idle"):
                parked.set()
                go.wait(10)

    t = threading.Thread(target=work)
    t.start()
    assert parked.wait(10)
    account = accounts[0]
    t0 = time.perf_counter_ns()
    first = account.snapshot()
    time.sleep(0.03)
    second = account.snapshot()
    between = time.perf_counter_ns() - t0
    assert account.innermost() == "batch.idle"
    go.set()
    t.join()
    # nothing closed yet: the counts are 0, the time is there, and two
    # snapshots differ by the thread's time between them
    assert second["batch.idle"][0] == second["t.outer"][0] == 0
    assert second["t.outer"][1] == first["t.outer"][1] >= 2 * MS
    grown = second["batch.idle"][1] - first["batch.idle"][1]
    assert 30 * MS <= grown <= between
    last = account.snapshot()
    assert last["batch.idle"][0] == last["t.outer"][0] == 1
    assert last["batch.idle"][1] >= second["batch.idle"][1]
    assert last["t.outer"][1] >= second["t.outer"][1]


def test_a_thread_that_did_not_ask_records_nothing():
    def work():
        with trace.device_span("t.quiet"):
            with trace.span("t.record", device="t.device"):
                pass
        return type(trace.device_span("t.quiet"))

    kind, account = on_a_thread(work, account=False)
    assert account is None
    assert kind is not trace._Accounted
    # and one that asked gets the same account however often it asks
    (first, second), account = on_a_thread(
        lambda: (trace.account_thread(), trace.account_thread()))
    assert first is second is account


def test_a_span_that_records_is_accounted_under_its_device_name():
    def work():
        with trace.new_trace():
            with trace.span("serve.dispatch", device="batch.dispatch", size=1):
                pass
        with trace.span("serve.dispatch", device="batch.dispatch", size=1):
            pass

    _, account = on_a_thread(work)
    assert account.snapshot()["batch.dispatch"][0] == 2
    assert "serve.dispatch" not in account.snapshot()


def test_a_full_collection_is_a_span_on_the_thread_that_collects():
    trace.span_collections()
    trace.span_collections()
    assert gc.callbacks.count(trace._gc_span) == 1

    def work():
        with trace.device_span("t.outer"):
            gc.collect(0)           # the young generations': no span
            gc.collect(1)
            young = "gc" in trace.thread_account().snapshot()
            gc.collect()            # a full one
        return young

    young, account = on_a_thread(work)
    got = account.snapshot()
    assert not young
    assert got["gc"][0] >= 1 and got["gc"][1] > 0
    assert not hasattr(trace._thread, "gc")
    # on a thread that did not ask it is an annotation only
    _, account = on_a_thread(gc.collect, account=False)
    assert account is None


# -- the batcher ---------------------------------------------------------------

@pytest.fixture
def no_chaos():
    chaos.clear()
    yield
    chaos.clear()


def test_the_batchers_histogram_holds_its_workers_phases(no_chaos):
    delay = {"sec": 0.0}

    def run_one(payload):
        time.sleep(delay["sec"])
        return payload

    batcher = MicroBatcher(lambda ps: [run_one(p) for p in ps], run_one)
    try:
        batcher.submit("warm")
        time.sleep(0.05)                    # the worker stands idle
        batcher.submit("again")
        h0 = batcher.histogram()
        assert {"batch.idle", "batch.collect", "batch.dispatch",
                "batch.deliver", trace.UNSPANNED} <= set(h0["phases"])
        assert h0["phases"]["batch.dispatch"][0] == h0["dispatches"] == 2
        assert h0["phases"]["batch.idle"][1] >= 50 * MS
        # a slow engine call lands in batch.dispatch, off the CPU
        delay["sec"] = 0.1
        batcher.submit("slow")
        h1 = batcher.histogram()
        _, wall0, cpu0 = h0["phases"]["batch.dispatch"]
        _, wall1, cpu1 = h1["phases"]["batch.dispatch"]
        assert wall1 - wall0 >= 100 * MS and cpu1 - cpu0 < 20 * MS
        # a chaos delay at the batcher's seam lies before the dispatch's
        # span: it lands between the worker's spans
        delay["sec"] = 0.0
        chaos.configure("batcher:latency:100ms")
        batcher.submit("delayed")
        chaos.clear()
        h2 = batcher.histogram()
        assert (h2["phases"][trace.UNSPANNED][1]
                - h1["phases"][trace.UNSPANNED][1]) >= 100 * MS
        assert (h2["phases"]["batch.dispatch"][1] - wall1) < 50 * MS
        # the names add up to the worker's time between two snapshots (a
        # span is charged as it closes: the ends may differ by one)
        t0 = time.perf_counter_ns()
        before = batcher.histogram()["phases"]
        for i in range(20):
            batcher.submit(i)
        after = batcher.histogram()["phases"]
        took = time.perf_counter_ns() - t0
        added = sum(after[n][1] - before.get(n, (0, 0, 0))[1] for n in after)
        assert added == pytest.approx(took, rel=0.1, abs=2 * MS)
    finally:
        batcher.stop()


def test_a_stall_names_the_workers_innermost_open_span(monkeypatch, caplog):
    tight = health.Watchdog("dispatch-phase-test", min_seconds=0.01,
                            min_history=1, factor=2.0)
    monkeypatch.setattr(engine_server_mod, "_DISPATCH_WATCHDOG", tight)
    delay = {"sec": 0.0}

    def run_one(payload):
        with trace.device_span("index.fetch"):
            time.sleep(delay["sec"])
        return payload

    batcher = MicroBatcher(lambda ps: ps, run_one)
    try:
        batcher.submit("warm")
        delay["sec"] = 0.3
        with caplog.at_level(logging.WARNING, logger="pio.stall"):
            batcher.submit("slow")
        fired = [r.pio for r in caplog.records
                 if getattr(r, "pio", {}).get("watchdog")
                 == "dispatch-phase-test"]
        assert fired and fired[0]["span"] == "pio:index.fetch"
    finally:
        batcher.stop()


# -- the sequence model's step -------------------------------------------------

class Recorded:
    """``trace.device_span`` with every opened span kept as ``(name, its
    parent's name, attrs)``, on the calling thread."""

    def __init__(self, monkeypatch):
        self.spans, self._open = [], []
        self._inner = trace.device_span
        monkeypatch.setattr(trace, "device_span", self)

    def __call__(self, name, **attrs):
        recorded, inner = self, self._inner(name, **attrs)

        class Span:
            def __enter__(self):
                recorded.spans.append(
                    (name, recorded._open[-1] if recorded._open else None,
                     attrs))
                recorded._open.append(name)
                return inner.__enter__()

            def __exit__(self, *exc):
                recorded._open.pop()
                return inner.__exit__(*exc)

        return Span()

    def parents_of(self, name, **attrs):
        return [p for n, p, a in self.spans if n == name
                and all(a.get(k) == v for k, v in attrs.items())]


def phase_sums(stats):
    return {what: sum(v for k, v in stats.items()
                      if k.startswith("phase_") and k.endswith("_" + what))
            for what in ("wall_ns", "cpu_ns")}


def small_seq_model():
    from predictionio_tpu.data.bimap import BiMap
    from predictionio_tpu.models.sessionrec import SeqStackModel
    from tests.test_seqstack import (N_ITEMS, SHAPE, seeded_params,
                                     small_spec)

    spec = small_spec()
    items = BiMap.from_vocab([f"i{r}" for r in range(N_ITEMS)])
    return SeqStackModel(spec, seeded_params(spec), items, SHAPE)


def seq_query(rows):
    return {"items": [f"i{r}" for r in rows], "num": 5}


def test_a_step_splits_each_program_call_in_launch_wait_and_count(
        monkeypatch):
    from predictionio_tpu.models.sessionrec import STEP_PHASES

    model = small_seq_model()
    model.programs()                                  # compiled outside
    rng = np.random.default_rng(3)
    short = rng.integers(0, 50, size=9).tolist()
    model.answer(seq_query(short))                    # its slot is warm

    def steps():
        recorded = Recorded(monkeypatch)
        before = model.stats()
        t0 = time.perf_counter_ns()
        long = model.begin(seq_query(rng.integers(0, 50, size=40).tolist()))
        ext = model.begin(seq_query(short + [1, 2]))
        with trace.device_span("batch.collect"):
            pass
        while long.result is None:
            model.step([t for t in (long, ext) if t.result is None])
        return recorded, before, model.stats(), time.perf_counter_ns() - t0

    (recorded, before, after, took), _ = on_a_thread(steps)
    for program, span in (("extend", "seq.extend"),
                          ("prefill", "seq.prefill_chunk")):
        for name in ("seq.launch", "seq.wait"):
            got = recorded.parents_of(name, program=program)
            assert got and set(got) == {span}, (name, program, got)
        # the counters' fetch follows its program's span, inside the step
        got = recorded.parents_of("seq.count", program=program)
        assert got and set(got) == {"seq.step"}
    assert len(recorded.parents_of("seq.launch", program="prefill")) == 3
    assert recorded.parents_of("seq.head") == ["seq.step"] * 2
    assert not recorded.parents_of("seq.cache.found")
    assert not recorded.parents_of("seq.cache.evict")
    # stats(): every name of STEP_PHASES always, and they add up to the
    # thread's time between the two snapshots
    for name in STEP_PHASES + ("other",):
        for what in ("n", "wall_ns", "cpu_ns"):
            assert f"phase_{name}_{what}" in before
    assert set(before) == set(after)
    added = phase_sums(after)["wall_ns"] - phase_sums(before)["wall_ns"]
    assert added == pytest.approx(took, rel=0.10)
    assert after["phase_seq.launch_n"] - before["phase_seq.launch_n"] == 4
    assert after["phase_seq.wait_n"] - before["phase_seq.wait_n"] == 4
    assert after["phase_seq.count_n"] - before["phase_seq.count_n"] == 4
    assert after["phase_batch.collect_n"] - before["phase_batch.collect_n"] == 1
    assert "extensions_waited" not in after
    # a host whose CPU clock is not read: every CPU entry None, so that the
    # drivers' numeric differences leave them out
    model._account.cpu_clock = None
    quiet = model.stats()
    assert all(v is None for k, v in quiet.items() if k.endswith("_cpu_ns"))
    assert quiet["phase_seq.launch_wall_ns"] == after["phase_seq.launch_wall_ns"]


def test_a_block_step_splits_likewise_and_decides_under_a_span(monkeypatch):
    from tests.test_seqgen import STATIC, history, query, small_model

    model, _ = small_model(STATIC)
    model.programs()

    def steps():
        recorded = Recorded(monkeypatch)
        before = model.stats()
        model.answer(query(history(1, 22), 8))
        return recorded, before, model.stats()

    (recorded, before, after), _ = on_a_thread(steps)
    for name in ("seq.launch", "seq.wait"):
        got = recorded.parents_of(name, program="block")
        assert got and set(got) == {"seq.block_step"}
    n_blocks = after["block_runs"] - before["block_runs"]
    assert recorded.parents_of("seq.count", program="block") \
        == ["seq.step"] * n_blocks
    assert recorded.parents_of("seq.decide") == ["seq.step"] * n_blocks
    assert after["phase_seq.decide_n"] - before["phase_seq.decide_n"] \
        == n_blocks
    # a history's chunk first, then its blocks: one ticket in the FIFO
    assert after["prefill_tickets"] - before["prefill_tickets"] == 1
    assert after["extend_tickets"] == before["extend_tickets"]
    # a follow-up finds its blocks cached: its first program is a block
    # forward, which counts as an extension of its slate
    model.answer(query(history(1, 22) + [5, 6], 8))
    assert model.stats()["extend_tickets"] - after["extend_tickets"] == 1


def test_the_second_of_two_first_queries_waits_a_step_for_its_chunk():
    model = small_seq_model()
    model.programs()
    rng = np.random.default_rng(5)
    first = model.begin(seq_query(rng.integers(0, 50, size=30).tolist()))
    second = model.begin(seq_query(rng.integers(0, 50, size=30).tolist()))
    took = []
    while second.result is None:
        t0 = time.perf_counter_ns()
        model.step([t for t in (first, second) if t.result is None])
        took.append(time.perf_counter_ns() - t0)
    c = model.counters
    assert c["prefill_tickets"] == 2 and c["extend_tickets"] == 0
    # the first's chunk is launched in the first step; the second's only
    # when the first's two chunks are through: two whole steps later
    assert first.launched_ns - first.admitted_ns < took[0] + (
        second.admitted_ns - first.admitted_ns)
    assert second.launched_ns - second.admitted_ns >= took[0] + took[1]
    assert c["prefill_queue_ns"] == sum(
        t.launched_ns - t.admitted_ns for t in (first, second))


def test_the_step_workers_histogram_holds_its_phases():
    from tests.test_seqstack import deploy_small, post

    server, _, _ = deploy_small()
    try:
        post(server, np.random.default_rng(2).integers(0, 50, 20).tolist())
        deadline = time.time() + 10
        while time.time() < deadline:           # until the worker idles again
            hist = server._batcher.histogram()
            if hist["phases"].get("batch.idle", (0,))[0] >= 1:
                break
            time.sleep(0.01)
        assert {"batch.idle", "batch.collect", "seq.step", "seq.launch",
                "seq.wait", "seq.count", "seq.head", "batch.deliver",
                trace.UNSPANNED} <= set(hist["phases"])
        assert server.status()["batcher"]["phases"]["seq.step"][0] >= 2
        stats = server.deployment.models[0].stats()
        assert stats["phase_seq.step_n"] == hist["phases"]["seq.step"][0]
    finally:
        server.stop()


# -- the readers ---------------------------------------------------------------

def hist(dispatches, phases):
    out = {"maxBatch": 64, "dispatches": dispatches,
           "batchSizeHistogram": {"1": dispatches}, "abandonedRequests": 0}
    if phases is not None:
        out["phases"] = phases
    return out


H0 = hist(10, {"batch.idle": [10, 500 * MS, 1 * MS],
               "batch.dispatch": [10, 40 * MS, 30 * MS],
               "index.fetch": [10, 30 * MS, 2 * MS]})
H1 = hist(110, {"batch.idle": [110, 800 * MS, 3 * MS],        # + 3 ms wall
                "batch.dispatch": [110, 540 * MS, 230 * MS],  # + 5, 2 ms
                "index.enqueue": [100, 300 * MS, 100 * MS],   # new: 3, 1 ms
                "index.fetch": [110, 380 * MS, 4 * MS],       # a wait
                "unspanned": [400, 50 * MS, 40 * MS]})        # 0.5, 0.4 ms


def test_the_readers_take_host_work_and_idle_a_dispatch(capsys):
    assert read(HOST, None, hist0=H0, hist1=H1) == pytest.approx(8.5)
    assert read(IDLE, None, hist0=H0, hist1=H1) == pytest.approx(3.0)
    said = capsys.readouterr().out
    assert "# worker phases" in said and "over 100 dispatches" in said
    assert "index.fetch* 3.5000 0.0200" in said and "sum 15.0000" in said
    # one table a run, whichever reader asks first
    ctx = {"bench": FakeBench({}), "hist0": H0, "hist1": H1}
    for metric in (IDLE, HOST):
        load_file(os.path.join(BENCHMARKS, "layer_metrics",
                               metric + ".py")).read(ctx)
    assert capsys.readouterr().out.count("# worker phases") == 1


def test_the_readers_read_a_histogram_whose_cpu_clock_was_not_read(capsys):
    def no_cpu(h):
        return hist(h["dispatches"], {k: [n, wall, None] for k, (n, wall, _)
                                      in h["phases"].items()})

    assert read(HOST, None, hist0=no_cpu(H0), hist1=no_cpu(H1)) \
        == pytest.approx(8.5)
    assert read(IDLE, None, hist0=no_cpu(H0), hist1=no_cpu(H1)) \
        == pytest.approx(3.0)
    assert "index.fetch* 3.5000 -," in capsys.readouterr().out


@pytest.mark.parametrize("h0,h1", [
    (hist(10, None), hist(110, None)),          # the parent commit's program
    (None, None),                               # no batcher, no stretch
    (H1, H1),                                   # no dispatch in the stretch
    (hist(10, {}), hist(110, {})),              # a worker that never looped
])
@pytest.mark.parametrize("metric", [HOST, IDLE])
def test_nothing_to_read_is_none(metric, h0, h1):
    assert read(metric, None, hist0=h0, hist1=h1) is None
    assert read(metric, None) is None


def test_idle_is_none_where_the_worker_never_idled():
    busy = {k: v for k, v in H1["phases"].items() if k != "batch.idle"}
    assert read(IDLE, None, hist0=hist(10, {}), hist1=hist(110, busy)) is None
    assert read(HOST, None, hist0=hist(10, {}), hist1=hist(110, busy)) \
        is not None


@pytest.mark.parametrize("case", repo_spec.CASES)
@pytest.mark.parametrize("metric", [HOST, IDLE])
def test_benchmark_json_names_the_reader_and_its_cells(case, metric):
    spec = repo_spec.load(case)
    repo_spec.assert_names_the_reader(spec, ENTRIES[metric])
    # no sequence cell and no training cell is listed
    assert all(cell.startswith("als-amazon14.serve-")
               for cell in ENTRIES[metric]["workloads"])
