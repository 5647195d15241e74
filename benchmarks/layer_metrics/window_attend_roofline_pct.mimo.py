"""The window layers' WALK's share of its roofline over the traced stretch,
in %: the larger of the time the pairs the window's mask leaves take at the
bf16 peak (``mimo_counts.window_attend_flops``: each chunk's rows at its own
offset from the ``pio:seq.prefill_chunk`` spans, each extension's new
positions against the window's 128: ``extend_tokens``) and the time the keys
and values those rows need take at the peak memory rate
(``window_attend_bytes`` of the chunks, ``extend_window_positions`` of the
extensions), over the self time of both programs' device operations under
``seq.layer<i>.gqa_window_a.attend`` (``glm_counts.scope_self_ns``; XLA over ``attend_over_blocks``: up to
six rounds of 128 keys against all of a chunk's 512 rows, three against an
extension batch's: the masked part of each round is on the measured side
alone). None where the program has no such scope or counter (the parent)."""


def read(ctx):
    bench = ctx["bench"]
    spans, counts = bench.lib("program_spans"), bench.lib("seq_counts")
    trace = spans.trace_of(ctx)
    tokens = counts.delta(ctx, "extend_tokens")
    positions = counts.delta(ctx, "extend_window_positions")
    if trace is None or tokens is None or positions is None:
        return None
    kernel, need = bench.lib("kernel_counts"), bench.lib("mimo_counts")
    busy_s = bench.lib("glm_counts").scope_self_ns(
        spans, trace, ".gqa_window_a.attend") / 1e9
    if busy_s <= 0:
        return None
    cfg = bench.config
    chunks = [(int(s.attrs.get("offset", 0)), int(s.attrs.get("tokens", 0)))
              for s in spans.named(trace, "pio:seq.prefill_chunk")]
    # an extension's reach is never under the window here (histories of 128
    # and more): each new position attends the window's 128
    window = int(cfg["sliding_window"])
    flops = (need.window_attend_flops(cfg, chunks)
             + need.window_attend_flops(cfg, [(window, tokens)]))
    nbytes = (need.window_attend_bytes(cfg, chunks)
              + positions * need.ring_bytes_per_position(cfg))
    peaks = bench.lib("peaks").peaks_for(bench.devices[0].device_kind)
    return kernel.roofline_pct(
        kernel.least_seconds(peaks, flops=flops, nbytes=nbytes), busy_s)
