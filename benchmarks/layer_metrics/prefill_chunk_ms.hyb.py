"""Median ``pio:seq.prefill_chunk`` span of the traced stretch, in ms:
``prefill_chunk_ms.seq``'s reading, in this stack's cell under a name of its
own (``HYB_SPANS.md`` says why). One chunk of 512 positions of one session
through the hybrid stack's chunk program (the chunked scan carrying each
Mamba-2 layer's state across the chunk's boundary), dispatch to result."""


def read(ctx):
    return ctx["bench"].lib("layer_metrics/prefill_chunk_ms.seq").read(ctx)
