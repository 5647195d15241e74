"""Median ``pio:seq.prefill_chunk`` span of the traced stretch, in ms: one
chunk of 512 positions of one session at its own offset (0 to 32,256) through
layers 0-17 (nine scans, eight window layers over their rings, the full layer
over its span: all of a chunk's growth with its offset), and where the chunk
ends its history one row through layers 18-31, dispatch to result:
``prefill_chunk_ms.seq``'s reading, in this stack's cell under a name of its
own (``PHI_SPANS.md`` says why)."""


def read(ctx):
    return ctx["bench"].lib("layer_metrics/prefill_chunk_ms.seq").read(ctx)
