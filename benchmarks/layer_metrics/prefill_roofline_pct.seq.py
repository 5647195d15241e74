"""The chunked-prefill program's share of its roofline over the traced
stretch, in %: the operations its chunks NEEDED (``seq_counts.prefill_flops``:
two a parameter a real token over the non-expert parameters and over the held
experts actually picked, plus causal attention at each chunk's offset, from
the ``pio:seq.prefill_chunk`` spans' ``offset`` / ``tokens``) at the bf16
peak, over the device time of the program's own operations."""


def read(ctx):
    bench = ctx["bench"]
    spans, counts = bench.lib("program_spans"), bench.lib("seq_counts")
    trace = spans.trace_of(ctx)
    if trace is None:
        return None
    chunks = [(int(s.attrs.get("offset", 0)), int(s.attrs.get("tokens", 0)))
              for s in spans.named(trace, "pio:seq.prefill_chunk")]
    picks = counts.delta(ctx, "prefill_held_picks")
    busy_s = counts.program_busy_ns(spans, trace, "prefill_fn") / 1e9
    if not chunks or picks is None or busy_s <= 0:
        return None
    kernel = bench.lib("kernel_counts")
    peaks = bench.lib("peaks").peaks_for(bench.devices[0].device_kind)
    # the counters cover the stretch; so do the spans, give or take the
    # chunk in flight at either end
    least_s = kernel.least_seconds(
        peaks, flops=counts.prefill_flops(bench.config, chunks, picks))
    return kernel.roofline_pct(least_s, busy_s)
