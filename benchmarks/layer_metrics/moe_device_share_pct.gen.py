"""Share of the device's busy time over the traced stretch spent in the
expert layer (``seq.layer<i>.moe.route|experts``) of a stack whose whole FFN
it is, in %: ``moe_device_share_pct.seq``'s reading, in this engine's cell
under a name of its own (``GEN_SPANS.md`` says why)."""


def read(ctx):
    return ctx["bench"].lib(
        "layer_metrics/moe_device_share_pct.seq").read(ctx)
