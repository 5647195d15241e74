"""The block program's share of its roofline over the traced stretch, in %:
the bytes its runs NEEDED (``gen_counts.block_bytes``: every layer's attention
and router weights and the head once a run, every expert that got a token,
the cached keys and values the rows' attention read; from the engine's
counters) at the peak memory rate, over the device time of the program's own
operations."""


def read(ctx):
    bench = ctx["bench"]
    spans, counts = bench.lib("program_spans"), bench.lib("seq_counts")
    trace = spans.trace_of(ctx)
    if trace is None:
        return None
    runs = counts.delta(ctx, "block_runs")
    touched = counts.delta(ctx, "block_experts_touched")
    reach = counts.delta(ctx, "block_kv_positions")
    busy_s = counts.program_busy_ns(spans, trace, "block_fn") / 1e9
    if not runs or touched is None or reach is None or busy_s <= 0:
        return None
    kernel = bench.lib("kernel_counts")
    peaks = bench.lib("peaks").peaks_for(bench.devices[0].device_kind)
    least_s = kernel.least_seconds(peaks, nbytes=bench.lib(
        "gen_counts").block_bytes(bench.config, runs, touched, reach))
    return kernel.roofline_pct(least_s, busy_s)
