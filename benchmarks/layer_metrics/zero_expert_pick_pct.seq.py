"""Share of the router's picks that went to a zero-compute (identity) expert
over the traced run's measured window, in %: zero picks over real tokens x
layers x top-k (the engine's counters). 256 of 768 outputs are zero-compute: about a third."""


def read(ctx):
    bench = ctx["bench"]
    counts = bench.lib("seq_counts")
    parts = [counts.delta(ctx, f"{kind}_{what}", window=True)
             for kind in ("extend", "prefill")
             for what in ("zero_picks", "tokens")]
    if any(p is None for p in parts):
        return None
    zero, tokens = parts[0] + parts[2], parts[1] + parts[3]
    cfg = bench.config
    picks = tokens * int(cfg["num_layers"]) * int(cfg["moe_topk"])
    return 100.0 * zero / picks if picks > 0 else None
