"""The grouped expert kernel's share of its roofline over the traced stretch,
in %: the larger of the time the touched experts' bytes take at the peak
memory rate and the time the picks' operations take at the bf16 peak
(``axk_counts.expert_groups_need``: every held expert that got a token of a
chunk read once, 88.1 MB in bfloat16; two operations a parameter a (token,
pick) pair), over the device time of the kernel's own events (instruction
``expert_groups.N``: one an expert layer of a prefill chunk, at 7168 x 2048 in
eight column chunks). Touched experts and picks from the engine's
``prefill_*`` counters over the stretch."""


def read(ctx):
    bench = ctx["bench"]
    spans, counts = bench.lib("program_spans"), bench.lib("seq_counts")
    trace = spans.trace_of(ctx)
    if trace is None:
        return None
    calls = spans.ops_named(trace, "expert_groups")
    picks = counts.delta(ctx, "prefill_held_picks")
    touched = counts.delta(ctx, "prefill_experts_touched")
    if not calls or not touched or picks is None:
        return None
    kernel, need = bench.lib("kernel_counts"), bench.lib("axk_counts")
    peaks = bench.lib("peaks").peaks_for(bench.devices[0].device_kind)
    flops, nbytes = need.expert_groups_need(bench.config, touched, picks)
    least_s = kernel.least_seconds(peaks, flops=flops, nbytes=nbytes)
    return kernel.roofline_pct(least_s, spans.busy_ns(calls) / 1e9)
