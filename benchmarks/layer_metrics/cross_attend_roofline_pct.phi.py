"""The span walks' share of their roofline in the extension program over the
traced stretch, in %: each extended row's OWN reach x 5,120 B x the eight
layers that walk the span (layer 17 and the seven cross layers;
``phi_counts.span_walk_bytes`` of ``extend_kv_positions``) at the peak memory
rate, over the self time of the program's device operations under
``seq.layer17.gqa_a.attend`` and ``seq.layer<i>.gqa_cross_a.attend``
(``glm_counts.scope_self_ns``: every row walks as far as the batch's longest,
and the masked part of each block, on the measured side alone). None where
the program has no such scope or counter (the parent)."""


def read(ctx):
    bench = ctx["bench"]
    spans, counts = bench.lib("program_spans"), bench.lib("seq_counts")
    trace = spans.trace_of(ctx)
    reach = counts.delta(ctx, "extend_kv_positions")
    if trace is None or not reach or counts.delta(
            ctx, "extend_cross_rows") is None:
        return None
    self_ns = bench.lib("glm_counts").scope_self_ns
    busy_s = sum(self_ns(spans, trace, suffix, "extend_fn") for suffix in (
        ".gqa_a.attend", ".gqa_cross_a.attend")) / 1e9
    if busy_s <= 0:
        return None
    kernel = bench.lib("kernel_counts")
    peaks = bench.lib("peaks").peaks_for(bench.devices[0].device_kind)
    return kernel.roofline_pct(kernel.least_seconds(
        peaks, nbytes=bench.lib("phi_counts").span_walk_bytes(
            bench.config, reach)), busy_s)
