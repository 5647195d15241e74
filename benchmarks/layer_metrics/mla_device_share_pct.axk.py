"""Share of the device's busy time over the traced stretch spent in the
latent-attention mixers (``seq.layer<i>.mla_a``: projections, RoPE under
YaRN, the cache write, the block loop over the cached latents, ``W_o``), in
%: ``mla_device_share_pct.seq``'s reading, in this stack's cell under a name
of its own."""


def read(ctx):
    return ctx["bench"].lib(
        "layer_metrics/mla_device_share_pct.seq").read(ctx)
