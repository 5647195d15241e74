"""The chunk program's share of its roofline over the traced stretch, in %:
the larger of the time its chunks' NEEDED operations take at the bf16 peak
(``mimo_counts.prefill_flops``: two a parameter a real token over the
non-expert parameters and over the held experts actually picked; causal
attention at each chunk's own offset in the two full layers, the window's 128
keys a row in the five window layers) and the time their needed bytes take at
the peak memory rate (``mimo_counts.prefill_bytes``), over the device time of
the program's own operations. Chunks from the ``pio:seq.prefill_chunk``
spans' ``offset`` / ``tokens``, picks and touched experts from the engine's
counters; None where the program counts no ``prefill_window_blocks`` (the
parent)."""


def read(ctx):
    bench = ctx["bench"]
    spans, counts = bench.lib("program_spans"), bench.lib("seq_counts")
    trace = spans.trace_of(ctx)
    if trace is None or counts.delta(ctx, "prefill_window_blocks") is None:
        return None
    chunks = [(int(s.attrs.get("offset", 0)), int(s.attrs.get("tokens", 0)))
              for s in spans.named(trace, "pio:seq.prefill_chunk")]
    picks = counts.delta(ctx, "prefill_held_picks")
    touched = counts.delta(ctx, "prefill_experts_touched")
    busy_s = counts.program_busy_ns(spans, trace, "prefill_fn") / 1e9
    if not chunks or picks is None or touched is None or busy_s <= 0:
        return None
    kernel, need = bench.lib("kernel_counts"), bench.lib("mimo_counts")
    peaks = bench.lib("peaks").peaks_for(bench.devices[0].device_kind)
    least_s = kernel.least_seconds(
        peaks, flops=need.prefill_flops(bench.config, chunks, picks),
        nbytes=need.prefill_bytes(bench.config, chunks, touched))
    return kernel.roofline_pct(least_s, busy_s)
