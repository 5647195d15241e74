"""The references' forms under a real length (ISSUE 48), on the CPU at the tiny
sizes: the blocked form gives the unblocked form's logits, histories of
different padded lengths cost one compilation a layer kind, the shorter
references pad to at most three lengths. A file of its own beside
``test_check_budget.py`` (whose helpers it takes) so that the two run side by
side: together they held one worker for 156 s."""

import os

import numpy as np
import pytest

from tests.benchmarks.test_check_budget import (  # noqa: F401
    histories, logits_of, small_blocks, span, world)
from tests.benchmarks.test_program_spans import BENCHMARKS, load_file
from tests.benchmarks.test_seq_cell import harness  # noqa: F401


# -- one compilation for every history length ---------------------------------

@pytest.mark.parametrize("tiny", ["tiny_glm", "tiny_axk"])
@pytest.mark.parametrize("length", [37, 150, 203])
def test_the_blocked_form_under_a_real_length_gives_the_unblocked_forms_logits(
        world, small_blocks, tiny, length):
    """Blocks of 16 rows, the history padded to 208 positions and its real
    length bounding every loop, against the whole history in one block."""
    ref, weights, dm, vocab = world(tiny)
    hist, = histories(vocab, length)
    want = logits_of(ref, weights, hist, dm)
    small_blocks(tiny)
    assert ref.padded_length(length, 203) == 256
    got = logits_of(ref, weights, hist, dm, reach=203)
    assert np.abs(got - want).max() / span(want) < 2e-6
    # the near ties counted are the real positions' alone
    assert ref.forward(weights, hist, dm)[1] >= 0


@pytest.mark.parametrize("tiny", ["tiny_glm", "tiny_axk"])
def test_histories_of_different_padded_lengths_cost_one_compilation_a_layer_kind(
        harness, world, small_blocks, tiny):
    """Under the old rule 37, 150 and 203 positions were three padded
    lengths (64, 192, 256 in blocks of 16) and compiled each layer kind three
    times; under a cell's reach they are one shape."""
    ref, weights, dm, vocab = world(tiny)
    small_blocks(tiny)
    counter = harness.CompileCounter()
    counter.install()
    hists = histories(vocab, 37, 150, 203, seed=4)
    logits_of(ref, weights, hists[0], dm, reach=203)
    first = counter.count
    # the item table's rows, a dense layer, an expert layer, the head, and
    # the last row read for it
    assert 4 <= first <= 6
    for hist in hists[1:]:
        logits_of(ref, weights, hist, dm, reach=203)
    assert counter.count == first
    assert counter.seconds > 0
    assert ref.shapes(203) == (256,)
    assert len({ref.padded_length(n, 203) for n in (37, 150, 203)}) == 1


@pytest.mark.parametrize("name", ["longcat_forward", "granite_forward",
                                  "sdar_forward"])
def test_the_shorter_references_pad_to_at_most_three_lengths(name):
    ref = load_file(os.path.join(BENCHMARKS, "reference", name + ".py"))
    pad = ref.PAD_TO
    assert ref.shapes(6 * pad - 5) == (2 * pad, 4 * pad, 6 * pad)
    assert ref.shapes(pad) == (pad,)
    for reach in (5751, 5757, 4136):
        ladder = ref.shapes(reach)
        assert len(ladder) <= 3 and ladder[-1] >= reach
        assert all(s % pad == 0 for s in ladder)
        lengths = {ref.padded_length(n, reach) for n in range(1, reach + 1,
                                                             7)}
        assert lengths == set(ladder)
    # a history alone (the control, the tests) is padded as it always was
    assert ref.padded_length(40) == pad
    assert ref.padded_length(2 * pad + 1) == 3 * pad


@pytest.mark.parametrize("tiny", ["tiny_seq", "tiny_hyb"])
def test_a_history_in_a_longer_shape_reads_the_same_logits(world, tiny):
    ref, weights, dm, vocab = world(tiny)
    hist, = histories(vocab, 61)
    want = ref.forward(weights, hist, dm)[0]
    got = ref.forward(weights, hist, dm, reach=3 * ref.PAD_TO)[0]
    assert np.abs(got - want).max() / span(want) < 2e-6
    assert ref.forward(weights, hist, dm)[1] == ref.forward(
        weights, hist, dm, reach=3 * ref.PAD_TO)[1]
