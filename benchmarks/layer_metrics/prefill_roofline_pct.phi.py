"""The two chunk programs' share of their roofline over the traced stretch,
in %: the larger of the time their chunks' NEEDED operations take at the bf16
peak (``phi_counts.prefill_flops``: two a parameter a real token over layers
0-17, and over layers 18-31 for the one row a history's last chunk carries
on; differential attention at each chunk's offset, under the window and
without; the recurrence) and the time their needed bytes take at the peak
memory rate (``prefill_bytes``), over the device time of both programs' own
operations. Chunks and which of them ended a history from the
``pio:seq.prefill_chunk`` spans' ``offset`` / ``tokens`` / ``last``. None
where the spans carry no ``last`` (the parent)."""


def read(ctx):
    bench = ctx["bench"]
    spans, counts = bench.lib("program_spans"), bench.lib("seq_counts")
    trace = spans.trace_of(ctx)
    if trace is None:
        return None
    need = bench.lib("phi_counts")
    chunks = need.chunks_of(spans, trace)
    busy_s = counts.program_busy_ns(spans, trace, "_prefill_") / 1e9
    if not chunks or any(c[2] is None for c in chunks) or busy_s <= 0:
        return None
    own = [(o, n) for o, n, _ in chunks]
    carried = [o + n for o, n, last in chunks if int(last)]
    kernel = bench.lib("kernel_counts")
    peaks = bench.lib("peaks").peaks_for(bench.devices[0].device_kind)
    least_s = kernel.least_seconds(
        peaks, flops=need.prefill_flops(bench.config, own, carried),
        nbytes=need.prefill_bytes(bench.config, own, carried))
    return kernel.roofline_pct(least_s, busy_s)
