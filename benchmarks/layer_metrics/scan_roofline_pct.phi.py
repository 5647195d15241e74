"""The Mamba-1 scans' share of their roofline in the chunk programs over the
traced stretch, in %: the larger of the bytes a scan that keeps ``S`` on the
chip would move (``x``, ``delta``, ``B``, ``C`` in, ``y`` out, the state in
and out) at the peak memory rate and the recurrence's operations at the
float32 vector rate ``phi_counts.F32_VECTOR_OPS_PER_S`` states
(``scan_least_seconds``; chunks from the ``pio:seq.prefill_chunk`` spans),
over the self time of both chunk programs' device operations under
``seq.layer<i>.mamba1_a.ssm.scan`` (``glm_counts.scope_self_ns``: the
``selective_scan`` kernel and XLA's copies that lay its rows out around it;
the copies are on the measured side alone). None where the program has no
such scope (the parent)."""


def read(ctx):
    bench = ctx["bench"]
    spans = bench.lib("program_spans")
    trace = spans.trace_of(ctx)
    if trace is None:
        return None
    need = bench.lib("phi_counts")
    chunks = [(o, n) for o, n, _ in need.chunks_of(spans, trace)]
    busy_s = bench.lib("glm_counts").scope_self_ns(
        spans, trace, ".mamba1_a.ssm.scan", "_prefill_") / 1e9
    if not chunks or busy_s <= 0:
        return None
    peaks = bench.lib("peaks").peaks_for(bench.devices[0].device_kind)
    return bench.lib("kernel_counts").roofline_pct(
        need.scan_least_seconds(bench.config, peaks, chunks), busy_s)
