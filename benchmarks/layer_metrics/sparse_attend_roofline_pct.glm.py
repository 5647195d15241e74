"""The extension's sparse attention's share of the peak memory rate over the
traced stretch, in %: the bytes of the latents each row SELECTED
(``glm_counts.gathered_bytes``: each new position's own ``min(reach, 2048)``
selected latents, 1,152 B each a layer: ``extend_latents_gathered``; the
names date from the gather that PR 44 took out) over the
self time of the extension program's device operations under
``seq.layer<i>.mla_a.attend`` (since PR 44 a walk over the slot's cached
latents where they lie, under each row's mask: the absorbed scores, the
softmax, the weighted sum, the value expansion; no sort, no gather: the
selected bytes are a lower bound of what the walk reads). A chunk's attention
under the same scope walks blocks under a mask too and is not in this number. None where the program has no such scope or counter
(the parent)."""


def read(ctx):
    bench = ctx["bench"]
    spans, counts = bench.lib("program_spans"), bench.lib("seq_counts")
    trace = spans.trace_of(ctx)
    gathered = counts.delta(ctx, "extend_latents_gathered")
    if trace is None or not gathered:
        return None
    kernel, need = bench.lib("kernel_counts"), bench.lib("glm_counts")
    busy_s = need.scope_self_ns(spans, trace, ".mla_a.attend",
                                "extend_fn") / 1e9
    if busy_s <= 0:
        return None
    peaks = bench.lib("peaks").peaks_for(bench.devices[0].device_kind)
    return kernel.roofline_pct(
        kernel.least_seconds(
            peaks, nbytes=need.gathered_bytes(bench.config, gathered)),
        busy_s)
