"""Share of the (valid token, expert layer) pairs of the traced run's
measured window whose kept groups include one with experts held here, in %
(``prefill_group_hit_tokens + extend_group_hit_tokens`` over the two
programs' ``*_tokens`` times the expert layers: the engine's own counters at
the window's two ends). The router keeps 4 of 8 groups and this chip holds
half of group 0: about 50% under unbiased routing; only such a token can
reach a held expert. None where the program counts no group hits."""


def read(ctx):
    bench = ctx["bench"]
    counts, need = bench.lib("seq_counts"), bench.lib("axk_counts")
    hits = [counts.delta(ctx, f"{kind}_group_hit_tokens", window=True)
            for kind in ("prefill", "extend")]
    tokens = [counts.delta(ctx, f"{kind}_tokens", window=True)
              for kind in ("prefill", "extend")]
    if None in hits or None in tokens or sum(tokens) <= 0:
        return None
    return 100.0 * sum(hits) / (sum(tokens) * need.expert_layers(bench.config))
