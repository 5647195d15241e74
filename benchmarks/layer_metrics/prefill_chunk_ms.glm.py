"""Median ``pio:seq.prefill_chunk`` span of the traced stretch, in ms: one
chunk of 512 positions of one session at its own offset (0 to 32,256),
scored, selected and attended row by row, dispatch to result:
``prefill_chunk_ms.seq``'s reading, in this stack's cell under a name of its
own (``GLM_SPANS.md`` says why)."""


def read(ctx):
    return ctx["bench"].lib("layer_metrics/prefill_chunk_ms.seq").read(ctx)
