"""The extension program's share of its roofline over the traced stretch, in
%: the bytes its runs NEEDED (``seq_counts.extend_bytes``: the non-expert
weights once a run, every held expert that got a token, the cached latents
the rows' attention read; from the engine's counters) at the peak memory
rate, over the device time of the program's own operations."""


def read(ctx):
    bench = ctx["bench"]
    spans, counts = bench.lib("program_spans"), bench.lib("seq_counts")
    trace = spans.trace_of(ctx)
    if trace is None:
        return None
    runs = counts.delta(ctx, "extend_runs")
    touched = counts.delta(ctx, "extend_experts_touched")
    latents = counts.delta(ctx, "extend_latent_positions")
    busy_s = counts.program_busy_ns(spans, trace, "extend_fn") / 1e9
    if not runs or touched is None or latents is None or busy_s <= 0:
        return None
    kernel = bench.lib("kernel_counts")
    peaks = bench.lib("peaks").peaks_for(bench.devices[0].device_kind)
    least_s = kernel.least_seconds(peaks, nbytes=counts.extend_bytes(
        bench.config, runs, touched, latents))
    return kernel.roofline_pct(least_s, busy_s)
