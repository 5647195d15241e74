"""The extension program's share of its roofline over the traced stretch, in
%: the bytes its runs NEEDED (``mimo_counts.extend_bytes``: the non-expert
weights once a run, every held expert that got a token, the full layers'
cached keys and values the rows' attention read, 2,560 B a position a layer,
and the window layers' over each row's new positions and the window before
them, 5,120 B a position a layer; from the engine's counters) at the peak
memory rate, over the device time of the program's own operations. None
where the program counts no ``extend_window_positions`` (the parent)."""


def read(ctx):
    bench = ctx["bench"]
    spans, counts = bench.lib("program_spans"), bench.lib("seq_counts")
    trace = spans.trace_of(ctx)
    if trace is None:
        return None
    runs, touched, reach, window = (counts.delta(ctx, key) for key in (
        "extend_runs", "extend_experts_touched", "extend_kv_positions",
        "extend_window_positions"))
    busy_s = counts.program_busy_ns(spans, trace, "extend_fn") / 1e9
    if not runs or None in (touched, reach, window) or busy_s <= 0:
        return None
    kernel = bench.lib("kernel_counts")
    peaks = bench.lib("peaks").peaks_for(bench.devices[0].device_kind)
    least_s = kernel.least_seconds(peaks, nbytes=bench.lib(
        "mimo_counts").extend_bytes(bench.config, runs, touched, reach,
                                    window))
    return kernel.roofline_pct(least_s, busy_s)
