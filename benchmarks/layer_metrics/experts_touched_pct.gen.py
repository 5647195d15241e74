"""Share of a layer's held experts that got at least one token in a block
forward, in %, over the traced run's measured window (``block_experts_touched``
over ``block_runs`` x layers x experts held): the share of the expert weights
a forward has to read. 32 tokens x 8 picks over 128 experts touch 87%."""


def read(ctx):
    bench = ctx["bench"]
    counts = bench.lib("seq_counts")
    touched = counts.delta(ctx, "block_experts_touched", window=True)
    runs = counts.delta(ctx, "block_runs", window=True)
    if touched is None or not runs:
        return None
    cfg = bench.config
    held = int(cfg["experts_held"][1]) * int(cfg["num_hidden_layers"])
    return 100.0 * touched / (runs * held)
