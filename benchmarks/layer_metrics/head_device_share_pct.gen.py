"""Share of the device's busy time over the traced stretch spent in the head
inside the block program (``seq.head``: the final norm, the product with the
bfloat16 table over the whole vocabulary, the softmax's normaliser, best item
and confidence at every position), in % (``gen_counts.head_share_pct``)."""


def read(ctx):
    return ctx["bench"].lib("gen_counts").head_share_pct(ctx)
