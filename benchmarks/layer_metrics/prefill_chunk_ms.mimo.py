"""Median ``pio:seq.prefill_chunk`` span of the traced stretch, in ms: one
chunk of 512 positions of one session at its own offset (0 to 24,064): five
window layers over their rings (the same work at any offset) and two full
layers over their spans (all of a chunk's growth with its offset), dispatch
to result: ``prefill_chunk_ms.seq``'s reading, in this stack's cell under a
name of its own (``MIMO_SPANS.md`` says why)."""


def read(ctx):
    return ctx["bench"].lib("layer_metrics/prefill_chunk_ms.seq").read(ctx)
