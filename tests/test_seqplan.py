"""``models/sessionrec.plan_step``: which tickets a step extends, which rows its
block forward carries and who gets its one chunk, over hand-built tickets: no
program is compiled and no array is on a device."""

import copy
import inspect
import re

import numpy as np
import pytest

from predictionio_tpu.models.sessionrec import SeqTicket, StepPlan, plan_step
from predictionio_tpu.ops.sessionrec import Generation, ServeShape

SHAPE = ServeShape(n_slots=8, capacity=256, chunk=16, extend_len=4,
                   extend_batch=3)
#: a stack that generates: up to four whole blocks of a history go through
#: the block forward, four rows a forward
GEN_SHAPE = ServeShape(n_slots=8, capacity=256, chunk=16, extend_len=16,
                       gen_batch=4)
M = 99          # the item row that stands for a mask
#: blocks of 4, two denoise forwards a block: 2 positions each at least
GEN = Generation(mask_row=M, block_len=4, denoising_steps=2,
                 rule="low_confidence_static")
#: three forwards over a block of 4: 2, then 1, then 1
UNEVEN = Generation(mask_row=M, block_len=4, denoising_steps=3,
                    rule="low_confidence_static")


def ticket(n_rows, done, slot=0):
    """A query over ``n_rows`` items of which the slot holds ``done``."""
    return SeqTicket(np.arange(1, n_rows + 1, dtype=np.int32), 10, slot, done)


def answered():
    t = SeqTicket(np.zeros(0, np.int32), 10, None, 0)
    t.result = []
    return t


def slate(n_rows, done, generate=8, block=None, denoised=0, slot=0):
    """A ticket of a stack that generates, as ``SeqStackModel.begin`` leaves
    it and the block forwards since: ``block`` the block being denoised."""
    t = ticket(n_rows, done, slot)
    t.generate = generate
    t.known = n_rows - n_rows % 4
    t.end = -(-(n_rows + generate) // 4) * 4
    t.block = None if block is None else np.asarray(block, np.int32)
    t.denoised = denoised
    return t


def fields(t):
    """Every field of a ticket, by value, so that two readings compare."""
    return {name: (v.tolist() if isinstance(v, np.ndarray) else copy.copy(v))
            for name in SeqTicket.__slots__ for v in [getattr(t, name)]}


ANSWERS_ONCE = {
    "nothing_pending": (lambda: [], ([], [], None, 0)),
    "only_tickets_answered_at_admission": (
        lambda: [answered(), answered()], ([0, 1], [], None, 0)),
    "more_short_tickets_than_a_batch_the_oldest_first": (
        lambda: [ticket(30 + i, 28 + i, i) for i in range(5)],
        ([], [0, 1, 2], None, 0)),
    "a_long_and_a_short_ticket_in_one_step": (
        lambda: [ticket(40, 0), ticket(21, 19, 1)], ([], [1], 0, 16)),
    "two_long_tickets_the_older_ones_chunk_only": (
        lambda: [ticket(40, 16), ticket(90, 0, 1)], ([], [], 0, 16)),
    "the_last_chunk_is_what_is_left": (
        lambda: [ticket(40, 32)], ([], [], 0, 8)),
    "exactly_extend_len_left_is_an_extension": (
        lambda: [ticket(24, 20)], ([], [0], None, 0)),
    "one_more_than_extend_len_left_is_a_prefill": (
        lambda: [ticket(24, 19)], ([], [], 0, 5)),
    "an_answered_ticket_among_pending_ones": (
        lambda: [ticket(12, 10), answered(), ticket(50, 0, 1),
                 ticket(9, 8, 2)], ([1], [0, 3], 2, 16)),
    "a_long_ticket_behind_a_full_batch": (
        lambda: [ticket(10 + i, 9 + i, i) for i in range(4)]
        + [ticket(33, 0, 5)], ([], [0, 1, 2], 4, 16)),
}


@pytest.mark.parametrize("case", sorted(ANSWERS_ONCE))
def test_a_step_of_a_stack_that_answers_once(case):
    """``(answered, extended, who is prefilled, its tokens)`` by the
    tickets' places in the list; never a block row."""
    make, (done, ext, pre, tokens) = ANSWERS_ONCE[case]
    tickets = make()
    before = [fields(t) for t in tickets]
    plan = plan_step(tickets, SHAPE, None)
    assert isinstance(plan, StepPlan) and plan.block == []
    assert [tickets.index(t) for t in plan.answered] == done
    assert [tickets.index(t) for t in plan.extend] == ext
    assert (plan.prefill is None) == (pre is None)
    if pre is not None:
        assert plan.prefill is tickets[pre]
    assert plan.prefill_tokens == tokens
    assert [fields(t) for t in tickets] == before


GENERATES = {
    # 22 items: five whole blocks of which the slot holds three, two items
    # left over; the first generated block is still to open
    "known_blocks_before_the_block_it_generates": (
        GEN, lambda: [slate(22, 12)],
        [(0, "known", 12, 0), (0, "known", 16, 0), (0, "denoise", 20, 2)]),
    "the_cap_falls_inside_the_first_tickets_known_blocks": (
        GEN, lambda: [slate(22, 4), slate(9, 8, slot=1)],
        [(0, "known", 4, 0), (0, "known", 8, 0), (0, "known", 12, 0),
         (0, "known", 16, 0)]),
    "the_cap_leaves_the_second_ticket_its_known_block_only": (
        GEN, lambda: [slate(10, 0), slate(14, 8, slot=1)],
        [(0, "known", 0, 0), (0, "known", 4, 0), (0, "denoise", 8, 2),
         (1, "known", 8, 0)]),
    "denoise_while_a_mask_is_left": (
        GEN, lambda: [slate(8, 8, block=[5, M, 7, M], denoised=1)],
        [(0, "denoise", 8, 2)]),
    "commit_once_no_mask_is_left": (
        GEN, lambda: [slate(8, 8, block=[5, 6, 7, 8], denoised=2)],
        [(0, "commit", 8, 0)]),
    "n_unmask_is_the_generations_by_the_blocks_forwards": (
        UNEVEN, lambda: [slate(8, 8, block=[M] * 4, denoised=0),
                         slate(8, 8, block=[3, 4, M, M], denoised=1, slot=1),
                         slate(8, 8, block=[3, 4, 5, M], denoised=2, slot=2),
                         slate(8, 8, block=[3, 4, 5, M], denoised=7, slot=3)],
        [(0, "denoise", 8, 2), (1, "denoise", 8, 1), (2, "denoise", 8, 1),
         (3, "denoise", 8, 1)]),
    "a_block_still_to_open_counts_its_forwards_from_zero": (
        UNEVEN, lambda: [slate(8, 12, denoised=2)], [(0, "denoise", 12, 2)]),
    "a_ticket_answered_at_admission_gives_no_row": (
        GEN, lambda: [answered(), slate(6, 4, block=[5, 6, M, M])],
        [(1, "denoise", 4, 2)]),
    # more than ``extend_len`` positions of whole blocks left: chunks, as in
    # a stack that answers once, and no block row of that ticket
    "a_long_history_is_prefilled_beside_the_others_block_rows": (
        GEN, lambda: [slate(70, 0), slate(9, 8, slot=1)],
        [(1, "denoise", 8, 2)]),
}


@pytest.mark.parametrize("case", sorted(GENERATES))
def test_a_block_forwards_rows_of_a_stack_that_generates(case):
    """``(ticket, kind, position, n_unmask)`` row by row, never an
    extension; the tickets' blocks are neither opened nor touched."""
    gen, make, rows = GENERATES[case]
    tickets = make()
    before = [fields(t) for t in tickets]
    plan = plan_step(tickets, GEN_SHAPE, gen)
    assert plan.extend == []
    long = [t for t in tickets if t.remaining > GEN_SHAPE.extend_len]
    assert (plan.prefill, plan.prefill_tokens) == (
        (long[0], GEN_SHAPE.chunk) if long else (None, 0))
    assert [(tickets.index(t), kind, at, n)
            for t, kind, at, n in plan.block] == rows
    assert len(plan.block) <= GEN_SHAPE.gen_batch
    assert [fields(t) for t in tickets] == before


def test_the_plan_reads_tickets_and_nothing_of_a_device():
    """Its code names no module, no model and no span: what it knows it is
    handed."""
    code = inspect.getsource(plan_step).split('"""')[2]
    assert not re.search(r"\b(jax|jnp|np|trace|self|programs|time)\b", code)
    assert {"extend_len", "extend_batch", "gen_batch", "chunk"} <= set(
        re.findall(r"shape\.(\w+)", code))
