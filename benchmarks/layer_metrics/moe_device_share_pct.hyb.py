"""Share of the device's busy time over the traced stretch spent in the
expert layer (``seq.layer<i>.moe.route|experts|shared``), in %:
``moe_device_share_pct.seq``'s reading, in this stack's cell under a name of
its own (``HYB_SPANS.md`` says why)."""


def read(ctx):
    return ctx["bench"].lib(
        "layer_metrics/moe_device_share_pct.seq").read(ctx)
