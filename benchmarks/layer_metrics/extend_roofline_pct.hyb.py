"""The extension program's share of its roofline over the traced stretch, in
%: the bytes its runs NEEDED (``hyb_counts.extend_bytes``: the non-expert
weights once a run, every held expert that got a token, each row's recurrent
state read and written once, the cached keys and values the rows' attention
read; from the engine's counters) at the peak memory rate, over the device
time of the program's own operations."""


def read(ctx):
    bench = ctx["bench"]
    spans, counts = bench.lib("program_spans"), bench.lib("seq_counts")
    trace = spans.trace_of(ctx)
    if trace is None:
        return None
    runs = counts.delta(ctx, "extend_runs")
    touched = counts.delta(ctx, "extend_experts_touched")
    state_rows = counts.delta(ctx, "extend_state_rows")
    reach = counts.delta(ctx, "extend_kv_positions")
    busy_s = counts.program_busy_ns(spans, trace, "extend_fn") / 1e9
    if (not runs or touched is None or state_rows is None or reach is None
            or busy_s <= 0):
        return None
    kernel = bench.lib("kernel_counts")
    peaks = bench.lib("peaks").peaks_for(bench.devices[0].device_kind)
    least_s = kernel.least_seconds(peaks, nbytes=bench.lib(
        "hyb_counts").extend_bytes(bench.config, runs, touched, state_rows,
                                   reach))
    return kernel.roofline_pct(least_s, busy_s)
