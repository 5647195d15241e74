"""The index scorer's share of the bf16 peak over the traced stretch, in %:
the operations of its score products AS SCANNED (``glm_counts
.index_score_flops``: every block of index keys the two programs scanned,
against a chunk's 512 rows or an extension's 4, 32 heads of 128) and of its
three projections (``index_projection_flops``), over the self time of the
device operations under ``seq.layer<i>.mla_a.index``. Blocks and tokens from
the engine's counters over the stretch (``*_index_blocks``, ``*_tokens``);
None where the program has no such scope or counter (the parent)."""


def read(ctx):
    bench = ctx["bench"]
    spans, counts = bench.lib("program_spans"), bench.lib("seq_counts")
    trace = spans.trace_of(ctx)
    if trace is None:
        return None
    blocks = [counts.delta(ctx, f"{kind}_index_blocks")
              for kind in ("prefill", "extend")]
    tokens = [counts.delta(ctx, f"{kind}_tokens")
              for kind in ("prefill", "extend")]
    if None in blocks or None in tokens or not sum(blocks):
        return None
    kernel, need = bench.lib("kernel_counts"), bench.lib("glm_counts")
    busy_s = need.scope_self_ns(spans, trace, ".mla_a.index") / 1e9
    if busy_s <= 0:
        return None
    peaks = bench.lib("peaks").peaks_for(bench.devices[0].device_kind)
    flops = (need.index_score_flops(bench.config, *blocks)
             + need.index_projection_flops(bench.config, sum(tokens)))
    return kernel.roofline_pct(kernel.least_seconds(peaks, flops=flops),
                               busy_s)
