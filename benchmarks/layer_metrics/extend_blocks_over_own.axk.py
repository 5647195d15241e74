"""Blocks of cached latents a layer's attention walked for the extended rows
over what each row's own reach would have taken, over the traced run's
measured window (``extend_latent_blocks_attended`` over
``extend_latent_blocks_own``: the engine's own counters at the window's two
ends): every row of an extension batch attends as far as its LONGEST, so a
590-event session batched with a 24,576-event one reads 49 blocks for its
own 2. 1 is no waste. None where the program does not count them."""


def read(ctx):
    counts = ctx["bench"].lib("seq_counts")
    attended = counts.delta(ctx, "extend_latent_blocks_attended", window=True)
    own = counts.delta(ctx, "extend_latent_blocks_own", window=True)
    if attended is None or not own:
        return None
    return attended / own
