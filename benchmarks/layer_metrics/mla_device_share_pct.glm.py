"""Share of the device's busy time over the traced stretch spent in the
latent-attention mixers (``seq.layer<i>.mla_a`` and the scopes inside it:
projections, RoPE, both cache writes, the index's scores and selection, the
attention over the selected positions, ``W_o``), in %:
``mla_device_share_pct.seq``'s reading, in this stack's cell under a name of
its own. ``indexer_device_share_pct.glm`` is the part of it under ``.index``
and ``.select``."""


def read(ctx):
    return ctx["bench"].lib(
        "layer_metrics/mla_device_share_pct.seq").read(ctx)
