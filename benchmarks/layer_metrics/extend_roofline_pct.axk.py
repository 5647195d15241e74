"""The extension program's share of its roofline over the traced stretch, in
%: the larger of the time its runs' needed bytes take at the peak memory rate
(``axk_counts.extend_bytes``: the non-expert weights once a run, every held
expert that got a token, the cached latents AS ATTENDED: each real row as far
as its batch's longest) and the time their operations take at the bf16 peak
(``axk_counts.extend_flops``), over the device time of the program's own
operations. From the engine's counters; None where the program counts no
``extend_latent_blocks_attended``."""


def read(ctx):
    bench = ctx["bench"]
    spans, counts = bench.lib("program_spans"), bench.lib("seq_counts")
    trace = spans.trace_of(ctx)
    if trace is None:
        return None
    runs = counts.delta(ctx, "extend_runs")
    tokens = counts.delta(ctx, "extend_tokens")
    picks = counts.delta(ctx, "extend_held_picks")
    touched = counts.delta(ctx, "extend_experts_touched")
    blocks = counts.delta(ctx, "extend_latent_blocks_attended")
    busy_s = counts.program_busy_ns(spans, trace, "extend_fn") / 1e9
    if not runs or None in (tokens, picks, touched, blocks) or busy_s <= 0:
        return None
    kernel, need = bench.lib("kernel_counts"), bench.lib("axk_counts")
    peaks = bench.lib("peaks").peaks_for(bench.devices[0].device_kind)
    cfg = bench.config
    least_s = kernel.least_seconds(
        peaks,
        flops=need.extend_flops(cfg, tokens, picks, blocks,
                                int(cfg["serve"]["extend_len"])),
        nbytes=need.extend_bytes(cfg, runs, touched, blocks))
    return kernel.roofline_pct(least_s, busy_s)
