"""The flash-CE kernels' share of their roofline, in %: the least time the
chip could take for one step's cross-entropy (``kernel_counts
.flash_ce_flops_per_step`` at the peak bf16 rate) over the device time the
kernels took per traced step: every device operation whose instruction holds
``flash_ce``. Two since PR 45 (``flash_ce_fwd`` and the one ``flash_ce_bwd``
that feeds both gradients; three before it, and past the widths where the
whole ``dv`` stays in VMEM: ``flash_ce_bwd_du``, ``flash_ce_bwd_dv``)."""


def read(ctx):
    bench, traced = ctx["bench"], ctx.get("traced")
    spans = bench.lib("program_spans")
    trace = spans.trace_of(ctx)
    if trace is None or not traced or not traced.get("steps"):
        return None
    kernels = spans.ops_named(trace, "flash_ce")
    if not kernels:
        return None
    counts = bench.lib("kernel_counts")
    peaks = bench.lib("peaks").peaks_for(bench.devices[0].device_kind)
    least_s = counts.least_seconds(
        peaks, flops=counts.flash_ce_flops_per_step(bench.config))
    measured_s = (sum(o.end - o.start for o in kernels) / 1e9
                  / len(trace.ops) / traced["steps"])
    return counts.roofline_pct(least_s, measured_s)
