"""The extension program's share of its roofline over the traced stretch, in
%: the bytes its runs NEEDED (``phi_counts.extend_bytes``: every layer's
weights once a run, each row's nine states in and out, the span's positions
in each row's OWN reach once for each of the eight layers that walk it, the
rings' rows under the rows' windows; from the engine's counters) at the peak
memory rate, over the device time of the program's own operations. None where
the program counts no ``extend_cross_rows`` (the parent)."""


def read(ctx):
    bench = ctx["bench"]
    spans, counts = bench.lib("program_spans"), bench.lib("seq_counts")
    trace = spans.trace_of(ctx)
    if trace is None:
        return None
    runs, cross, state, reach, window = (counts.delta(ctx, key) for key in (
        "extend_runs", "extend_cross_rows", "extend_state_rows",
        "extend_kv_positions", "extend_window_positions"))
    busy_s = counts.program_busy_ns(spans, trace, "extend_fn") / 1e9
    if not runs or None in (cross, state, reach, window) or busy_s <= 0:
        return None
    kernel = bench.lib("kernel_counts")
    peaks = bench.lib("peaks").peaks_for(bench.devices[0].device_kind)
    least_s = kernel.least_seconds(peaks, nbytes=bench.lib(
        "phi_counts").extend_bytes(bench.config, runs, state, reach, window))
    return kernel.roofline_pct(least_s, busy_s)
