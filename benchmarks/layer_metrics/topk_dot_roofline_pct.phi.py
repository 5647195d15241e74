"""The head's ``topk_dot`` kernel's share of its roofline over the traced
stretch, in %: the least time the chip could take for one search (the tied
embedding as the head holds it, float32, 200,064 x 2,560 = 2.05 GB read once
at the peak memory rate: ``phi_counts.head_bytes``, 2.5 ms; the product over
at most 8 hidden states needs far less) over the median device time of the
kernel's own events (instruction ``topk_dot.N``: the one-row search after a
history's last chunk and the search after an extension batch alike):
``topk_dot_roofline_pct.seq``'s reading over this stack's table, the largest
a cell holds."""

import statistics


def read(ctx):
    bench = ctx["bench"]
    spans = bench.lib("program_spans")
    trace = spans.trace_of(ctx)
    if trace is None:
        return None
    calls = spans.ops_named(trace, "topk_dot")
    if not calls:
        return None
    need, kernel = bench.lib("phi_counts"), bench.lib("kernel_counts")
    peaks = bench.lib("peaks").peaks_for(bench.devices[0].device_kind)
    least_s = kernel.least_seconds(
        peaks, flops=need.head_flops(bench.config),
        nbytes=need.head_bytes(bench.config))
    measured_s = statistics.median(o.end - o.start for o in calls) / 1e9
    return kernel.roofline_pct(least_s, measured_s)
