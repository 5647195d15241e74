"""``benchmarks/layer_metrics/chunk_attend_roofline_pct.glm.py`` (ISSUE 50) on
hand-built events, on a trace with nothing to read, and its needed work at
the published widths. The reader has NO entry in ``BENCHMARK.json`` yet:
``tests/benchmarks/test_glm_cell.py`` (a file this PR may not edit) refuses
any per-layer entry it does not know that lists GLM-5's cell (``PERF.md``
section 7), so this file lives beside the benchmark's tests, not among them."""

import json
import os

import pytest

from tests.benchmarks.test_program_spans import (BENCHMARKS, FakeBench, HERE,
                                                 load_file, make_trace)
from tests.benchmarks.test_seq_cell import OLD_FIXTURE

METRIC = "chunk_attend_roofline_pct.glm"


@pytest.fixture(scope="module")
def ps():
    return load_file(os.path.join(BENCHMARKS, "program_spans.py"))


@pytest.fixture(scope="module")
def reader():
    return load_file(os.path.join(BENCHMARKS, "layer_metrics",
                                  METRIC + ".py"))


def config():
    with open(os.path.join(BENCHMARKS, "configs", "glm-5.json")) as f:
        return json.load(f)


def read(reader, trace, **ctx):
    return reader.read({"bench": FakeBench(config()),
                        "_program_spans": trace, **ctx})


def test_the_needed_work_at_the_published_widths(reader):
    cfg = config()
    # one block of 512 cached positions, a layer: the expansion's 15.0 GFLOP
    # and the two products' 17.2 of ISSUE 50 (8.6 + 8.6 over whole blocks)
    expand = 2 * 512 * 64 * (192 + 256)
    assert 512 * expand / 1e9 == pytest.approx(15.03, abs=0.01)
    pair = (2 * 256 + 2 * 256) * 64
    assert 512 * 512 * pair / 1e9 == pytest.approx(17.18, abs=0.01)
    out = 2 * 64 * 256 * 6144
    # a whole chunk at offset 0 sees a triangle; past it, all before it too
    assert reader.needed_flops(cfg, [(0, 512)]) == 5 * (
        512 * expand + 512 * 513 // 2 * pair + 512 * out)
    assert reader.needed_flops(cfg, [(32256, 512), (1000, 40)]) == 5 * (
        32768 * expand + (512 * 32256 + 512 * 513 // 2) * pair + 512 * out
        + 1040 * expand + (40 * 1000 + 40 * 41 // 2) * pair + 40 * out)
    # a chunk at 32,256, a layer: 0.96 TFLOP of expansion, 1.09 of products,
    # 0.10 of W_o
    assert reader.needed_flops(cfg, [(32256, 512)]) / 5e12 == pytest.approx(
        2.156, abs=0.001)


def test_the_reader_on_hand_built_operations(reader, ps):
    """A chunk program whose walk takes 20 ms under the mixer's ``.attend``
    scope (a kernel's instruction or a loop's fusions: the scope decides,
    not the name) beside an extension program's 2 ms under the same scope,
    which is not this number's."""
    ops = [("%fusion.1", 0, 8, "seq.layer3.mla_a.index"),
           ("%chunk_attend.2", 8, 24, "seq.layer3.mla_a.attend"),
           ("%fusion.3", 24, 28, "seq.layer3.mla_a.attend"),
           ("%fusion.4", 28, 32, "seq.layer3.mla_a"),
           ("%fusion.3", 40, 42, "seq.layer3.mla_a.attend")]
    modules = ["jit__prefill_fn"] * 4 + ["jit__extend_fn"]
    trace = make_trace(ps, [("pio:seq.prefill_chunk", 0, 33, 1,
                             {"offset": 8192, "tokens": 500})], ops)
    for dev in trace.ops.values():
        dev[:] = [o._replace(module=m) for o, m in zip(dev, modules)]
    need = reader.needed_flops(config(), [(8192, 500)])
    assert read(reader, trace) == pytest.approx(
        100.0 * need / 197e12 / 0.020)
    # counted too high, or part of the time left out: no reading
    far = make_trace(ps, [("pio:seq.prefill_chunk", 0, 33, 1,
                           {"offset": 32256, "tokens": 512})] * 4, ops)
    for dev in far.ops.values():
        dev[:] = [o._replace(module=m) for o, m in zip(dev, modules)]
    assert read(reader, far) is None


def test_the_reader_returns_none_where_there_is_nothing_to_read(reader, ps):
    """No trace; a trace of a program without this engine (PR 25's fixture);
    a latent-attention stack without an index's scopes (PR 39's, recorded on
    the chip: chunks and no ``.mla_a.attend``): no number, no error."""
    assert read(reader, None) is None
    assert read(reader, ps.load(OLD_FIXTURE, {})) is None
    with open(os.path.join(HERE, "fixtures", "axk_small.scopes.json")) as f:
        axk = ps.load(os.path.join(HERE, "fixtures", "axk_small.xplane.pb"),
                      json.load(f))
    assert ps.named(axk, "pio:seq.prefill_chunk")
    assert read(reader, axk) is None
