"""What a sequence step's programs count stays on the device, and what the
host knows of a call goes in as one array (PR 40): for one small stack of
each kind the suite builds, ``SeqStackModel.stats()`` against the
per-program counter arrays summed on the host as ``_count`` summed them until
then, drains that lose nothing, reads from a second thread, and the packed
call against the program's function called with its arguments apart."""

import collections
import dataclasses
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.models.sessionrec import (
    SeqStackAlgorithm,
    SeqStackModel,
    SeqStackParams,
)
from predictionio_tpu.ops import moe as moe_ops
from predictionio_tpu.ops.sessionrec import StackPrograms
from tests import test_seqaxk, test_seqgen, test_seqhyb, test_seqstack

STACKS = ("scmoe", "generation", "hybrid", "grouped-router")
N_ITEMS = 50


def small_model(stack: str) -> SeqStackModel:
    """MLA + ScMoE with a chunk above one tile (its expert layers take the
    grouped form), GQA block diffusion, the Mamba-2 hybrid, A.X-K1's dense
    layer and group-limited router."""
    if stack == "generation":
        return test_seqgen.small_model(test_seqgen.STATIC)[0]
    if stack == "hybrid":
        return test_seqhyb.small_model()[0]
    of = test_seqstack if stack == "scmoe" else test_seqaxk
    spec = of.small_spec()
    shape = (dataclasses.replace(of.SHAPE, capacity=256, chunk=128)
             if stack == "scmoe" else of.SHAPE)
    return SeqStackModel(
        spec, of.seeded_params(spec),
        BiMap.from_vocab([f"i{r}" for r in range(N_ITEMS)]), shape)


def query(model, rows):
    q = {"items": [f"i{r}" for r in rows]}
    return {**q, "generate": 8} if model.gen else {**q, "num": 5}


def history(seed, n):
    """Items every stack knows, the generating one's mask row not among
    them (a mask is never part of a history)."""
    known = np.delete(np.arange(N_ITEMS - 1), test_seqgen.MASK)
    return np.random.default_rng(seed).choice(known, n).tolist()


def mixed_run(model):
    """Whole prefills, extensions alone, and steps that carry an extension
    (or a slate's blocks) beside another session's chunk."""
    long = history(1, 150 if model.shape.chunk > 64 else 40)
    model.answer(query(model, long))
    model.answer(query(model, long + [3, 4]))
    first = model.begin(query(model, history(2, 2 * model.shape.chunk + 5)))
    grown = model.begin(query(model, long + [3, 4, 5, 6, 7]))
    tickets = [first, grown]
    while tickets:
        model.step(tickets)
        tickets = [t for t in tickets if t.result is None]


def summed_on_the_host(model, calls) -> dict:
    """``SeqStackModel._count`` as it was while it fetched each program's
    arrays: the reference for what the device sums now."""
    c = collections.Counter()
    for kind, counted in calls:
        counted = jax.device_get(counted)
        c[f"{kind}_runs"] += 1
        c[f"{kind}_tokens"] += int(counted["tokens"])
        if "expert_load" in counted:
            load = np.asarray(counted["expert_load"], np.int64)
            c[f"{kind}_held_picks"] += int(load.sum())
            c[f"{kind}_experts_touched"] += int((load > 0).sum())
            small = moe_ops.small_forward(model.programs().tokens[kind])
            c[f"{kind}_dense_expert_runs"] += small
            if not small:
                c[f"{kind}_expert_row_tiles"] += int(
                    moe_ops.row_tiles(load).sum())
            c[f"{kind}_zero_picks"] += int(counted["zero_picks"].sum())
            c["load_max_sum"] += float(load.max(axis=1).sum())
            c["load_mean_sum"] += float(load.mean(axis=1).sum())
        if "group_hits" in counted:
            c[f"{kind}_group_hit_tokens"] += int(counted["group_hits"].sum())
    return c


COUNTED = [f"{kind}_{field}" for kind in StackPrograms.TOTAL_KINDS
           for field in StackPrograms.TOTAL_FIELDS if field != "load_max_sum"]


@pytest.mark.parametrize("drain_every", [None, 3])
@pytest.mark.parametrize("stack", STACKS)
def test_stats_read_what_the_programs_arrays_sum_to(stack, drain_every,
                                                    monkeypatch):
    model = small_model(stack)
    programs = model.programs()
    if drain_every:
        programs.drain_every = drain_every
    calls = test_seqstack.recording(model, monkeypatch)
    before = model.stats()
    assert all(before[key] == 0 for key in COUNTED)
    mixed_run(model)
    after = model.stats()
    want = summed_on_the_host(model, calls)
    kinds = {kind for kind, _ in calls}
    assert kinds == set(programs.tokens) and len(calls) >= 6
    for key in COUNTED:
        assert type(after[key]) is int
        assert after[key] - before[key] == want[key], key
    for key in ("load_max_sum", "load_mean_sum"):
        assert type(after[key]) is float
        assert after[key] == pytest.approx(want[key], rel=1e-12), key
    if model.spec.moe is not None:
        assert want["load_max_sum"] > 0
        small = [moe_ops.small_forward(programs.tokens[k]) for k in kinds]
        assert any(want[f"{k}_dense_expert_runs"] for k in kinds) == any(small)
        assert any(want[f"{k}_expert_row_tiles"] for k in kinds) != all(small)
    # the two reads, and a drain every so many runs: not one a run
    drains = len(calls) // drain_every if drain_every else 0
    assert after["count_fetches"] - before["count_fetches"] == 1 + drains
    if drain_every:
        assert int(np.asarray(programs.totals)[:, 0].sum()) \
            == len(calls) % drain_every
        assert sum(int(n) for n in model._drained[:, 0]) \
            == len(calls) - len(calls) % drain_every


@pytest.mark.parametrize("stack", STACKS)
def test_the_chunks_whose_attention_ran_in_the_kernel_are_counted(stack):
    """``prefill_attend_kernel_chunks``: every chunk of a stack of latent
    mixers (``ops/mla.prefill_chunk`` walks in the ``chunk_attend`` kernel,
    with an index or without), none of a stack of grouped-query or recurrent
    mixers, whose walks are XLA's own."""
    model = small_model(stack)
    latent = stack in ("scmoe", "grouped-router")
    assert model.programs().attend_kernel is latent
    assert model.stats()["prefill_attend_kernel_chunks"] == 0
    mixed_run(model)
    after = model.stats()
    assert after["prefill_runs"] > 0
    assert after["prefill_attend_kernel_chunks"] == (
        after["prefill_runs"] if latent else 0)


@pytest.mark.parametrize("stack", STACKS)
def test_the_batches_whose_rows_walked_in_the_kernel_are_counted(stack):
    """``extend_walk_kernel_batches``: once an extension batch of a causal
    stack with a ``gqa`` layer (its rows walk the span in ``span_walk``, each
    as far as its own reach), none of a stack of latent mixers (the absorbed
    walk is XLA's own) or of a block-diffusion stack (no extension at all)."""
    model = small_model(stack)
    assert model.programs().walk_kernel is (stack == "hybrid")
    assert model.stats()["extend_walk_kernel_batches"] == 0
    mixed_run(model)
    after = model.stats()
    assert after["extend_walk_kernel_batches"] == (
        after["extend_runs"] if stack == "hybrid" else 0)
    assert (after["extend_runs"] > 0) == (stack != "generation")


@pytest.mark.parametrize("stack", STACKS)
def test_the_drain_comes_before_an_int32_could_wrap(stack):
    model = small_model(stack)
    programs, moe = model.programs(), model.spec.moe
    layers = sum(b.ffn == "moe" or b.topology == "scmoe"
                 for b in model.spec.blocks) if moe else 1
    a_run = layers * (max(programs.tokens.values())
                      * (moe.top_k if moe else 1)
                      + (moe.held[1] if moe else 0))
    assert 1000 < programs.drain_every <= (2 ** 31 - 1) // a_run
    # what was drained is Python's own integers: past 32 and 64 bits, exact
    model.answer(query(model, history(3, 20)))
    once = model.stats()
    model._drained += 2 ** 70
    assert all(model.stats()[key] - once[key] == 2 ** 70 for key in COUNTED)


def test_a_warm_up_run_is_not_counted():
    for stack in ("hybrid", "generation"):
        model = small_model(stack)
        SeqStackAlgorithm(SeqStackParams()).warmup(model, None)
        stats = model.stats()
        assert all(stats[key] == 0 for key in COUNTED)
        assert stats["count_fetches"] == 0


def test_a_block_forward_fetches_its_decision_and_nothing_else(monkeypatch):
    model = small_model("generation")
    fetched = []
    get = jax.device_get
    monkeypatch.setattr(jax, "device_get", lambda tree: (
        fetched.append(tree), get(tree))[1])
    model.answer(query(model, history(1, 22)))
    assert fetched and all(
        set(tree) == {"ids", "picked", "score", "confidence"}
        for tree in fetched)
    assert model.counters["count_fetches"] == 0     # nobody has asked yet
    assert model.stats()["block_runs"] == len(fetched)


@pytest.mark.parametrize("stack", STACKS)
def test_a_second_thread_reads_stats_between_the_steps_of_the_first(stack):
    """Every snapshot is after a whole number of counted runs: what the
    device counted and what the host added for the same program agree."""
    model = small_model(stack)
    model.programs().drain_every = 5
    long = history(4, model.shape.chunk)
    B = model.gen.block_len if model.gen else None
    model.answer(query(model, long))
    stop, seen, failed = threading.Event(), [], []

    def read():
        try:
            while not stop.is_set():
                seen.append(model.stats())
        except BaseException as e:          # noqa: BLE001 (re-raised below)
            failed.append(e)
            raise

    def step():
        try:
            rows = list(long)
            for i in range(12):
                rows += [5 + i % 7, 9]      # every extension adds two items
                model.answer(query(model, rows))
        except BaseException as e:          # noqa: BLE001 (re-raised below)
            failed.append(e)
            raise
        finally:
            stop.set()

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=f) for f in (read, step)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    if failed:
        raise failed[0]
    seen.append(model.stats())
    assert len(seen) > 10
    for s in seen:
        assert all(isinstance(s[key], int) for key in COUNTED)
        assert isinstance(s["load_max_sum"], float)
        if model.gen:
            assert s["block_tokens"] == B * (s["denoise_rows"]
                                             + s["commit_rows"])
            assert s["block_kv_positions"] >= s["block_tokens"]
        else:
            assert s["extend_rows"] == s["extend_runs"]
            assert s["extend_tokens"] == 2 * s["extend_runs"]
            assert s["prefill_tokens"] == model.shape.chunk * s["prefill_runs"]
    runs = [sum(s[f"{kind}_runs"] for kind in StackPrograms.TOTAL_KINDS)
            for s in seen]
    assert runs == sorted(runs) and len(set(runs)) > 2
    # a read is a fetch, a run is not
    assert seen[-1]["count_fetches"] == len(seen) + runs[-1] // 5


@pytest.mark.parametrize("stack", STACKS)
def test_the_one_array_gives_the_answers_of_arguments_apart(stack):
    """Bit for bit, result and caches: the packed call against the
    program's own function under ``jit`` with today's separate arguments."""
    model = small_model(stack)
    programs, sh = model.programs(), model.shape
    i32 = np.int32

    def apart(fn, *args):
        cache = jax.tree.map(jnp.copy, programs.cache)
        return jax.jit(fn)(programs.params, cache, *args)

    def same(apart_out, packed_result):
        cache, result, _ = apart_out
        for a, b in zip(jax.tree.leaves((cache, result)),
                        jax.tree.leaves((programs.cache, packed_result))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    n = sh.chunk - 3
    ids = np.array(history(6, sh.chunk), i32)
    ids[n:] = 0
    want = apart(programs._prefill_fn, ids, i32(n), i32(1), i32(0))
    same(want, programs.prefill(ids[:n], 1, 0)[0])
    if model.gen is None:
        B, S = sh.extend_batch, sh.extend_len
        new = np.zeros((B, S), i32)
        new[0, :2] = history(7, 2)
        n_new = np.array([2] + [0] * (B - 1), i32)
        slots = np.array([1] + [sh.n_slots] * (B - 1), i32)
        pos0 = np.array([n] + [0] * (B - 1), i32)
        want = apart(programs._extend_fn, new, n_new, slots, pos0,
                     programs.n_blocks(n + S))
        same(want, programs.extend([(new[0, :2], 1, n)])[0])
    else:
        B, S = sh.gen_batch, model.gen.block_len
        at = sh.chunk
        blocks = np.zeros((B, S), i32)
        blocks[0] = model.gen.mask_row
        blocks[1] = ids[:S]
        slots = np.array([1, 2] + [sh.n_slots] * (B - 2), i32)
        pos0 = np.array([at, 0] + [0] * (B - 2), i32)
        denoise = np.array([True] + [False] * (B - 1))
        n_unmask = np.array([2] + [0] * (B - 1), i32)
        want = apart(programs._block_fn, blocks, slots, pos0, denoise,
                     n_unmask, programs.n_blocks(at + S))
        same(want, programs.block([(blocks[0], 1, at, True, 2),
                                   (blocks[1], 2, 0, False, 0)])[0])
        assert np.asarray(want[1]["picked"])[0].sum() >= 2
