"""The ``topk_dot`` kernel's share of its roofline, in %: the least time the
chip could take for one call (the item table read once at the peak memory
rate: ``kernel_counts.topk_dot_bytes``; the matmul needs far less) over the
median device time of the kernel's own events (instruction ``topk_dot.N``).
The re-tiling copy in front of it is another operation and not in it."""

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
    counts = bench.lib("kernel_counts")
    peaks = bench.lib("peaks").peaks_for(bench.devices[0].device_kind)
    least_s = counts.least_seconds(
        peaks, flops=counts.topk_dot_flops(bench.config),
        nbytes=counts.topk_dot_bytes(bench.config))
    measured_s = statistics.median(o.end - o.start for o in calls) / 1e9
    return counts.roofline_pct(least_s, measured_s)
