"""How uneven the routing is among the held experts over the traced run's
measured window: per program run and layer, the fullest held expert's tokens over the held
experts' mean, as the ratio of their sums (1 = even; 16 = one expert of the
16 gets every token that reaches this chip)."""


def read(ctx):
    counts = ctx["bench"].lib("seq_counts")
    top, mean = (counts.delta(ctx, "load_max_sum", window=True),
                 counts.delta(ctx, "load_mean_sum", window=True))
    if top is None or not mean:
        return None
    return top / mean
