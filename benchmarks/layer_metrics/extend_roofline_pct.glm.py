"""The extension program's share of its roofline over the traced stretch, in
%: the larger of the time its runs' needed bytes take at the peak memory rate
(``glm_counts.extend_bytes``: the non-expert weights once a run, every held
expert that got a token, the index keys AS SCANNED, 256 B a position a layer,
and the latents each new position SELECTED, its own 2,048: since PR 44 the
program walks the slot's latents in place under each row's mask and gathers
nothing, so this is a lower bound of the bytes it reads) and the time
their operations take at the bf16 peak (``glm_counts.extend_flops``), over
the device time of the program's own operations. From the engine's counters;
None where the program counts no ``extend_index_blocks`` (the parent)."""


def read(ctx):
    bench = ctx["bench"]
    spans, counts = bench.lib("program_spans"), bench.lib("seq_counts")
    trace = spans.trace_of(ctx)
    if trace is None:
        return None
    runs, tokens, picks, touched, blocks, gathered = (
        counts.delta(ctx, key) for key in (
            "extend_runs", "extend_tokens", "extend_held_picks",
            "extend_experts_touched", "extend_index_blocks",
            "extend_latents_gathered"))
    busy_s = counts.program_busy_ns(spans, trace, "extend_fn") / 1e9
    if not runs or None in (tokens, picks, touched, blocks, gathered) \
            or busy_s <= 0:
        return None
    kernel, need = bench.lib("kernel_counts"), bench.lib("glm_counts")
    peaks = bench.lib("peaks").peaks_for(bench.devices[0].device_kind)
    cfg = bench.config
    least_s = kernel.least_seconds(
        peaks,
        flops=need.extend_flops(cfg, tokens, picks, blocks, gathered),
        nbytes=need.extend_bytes(cfg, runs, touched, blocks, gathered))
    return kernel.roofline_pct(least_s, busy_s)
