"""A chunk's latent attention's share of the bf16 peak over the traced
stretch, in %, whatever implements it: the operations the walk over a slot's
blocks NEEDS over the self time of the chunk program's device operations
under ``seq.layer<i>.mla_a.attend`` (``glm_counts.scope_self_ns``: PR 43's
scope, so the number reads on a program whose walk is a loop of XLA's fusions
as on one whose walk is the ``chunk_attend`` kernel).

Needed, a traced chunk (the ``pio:seq.prefill_chunk`` spans' ``offset`` /
``tokens``) and layer: the expansion of every cached position in reach ONCE
(``kv_lora_rank`` x heads x (nope + value widths), two operations each); the
two products over every position a real row can SEE (``tokens * offset +
tokens (tokens + 1) / 2`` pairs, the rope and nope widths for the score and
the value width for the sum, every head): the causal reach and not what the
index selected, because a row's mask saves no product in a walk over whole
blocks and this number does not pretend it does (``prefill_roofline_pct.glm``
keeps counting what was selected); and ``W_o``. A chunk's padding to 512
rows, the masked part of its last blocks and the positions of a last block
past the reach are on the measured side alone, so the share cannot pass 100
while whole blocks are multiplied. None where the trace holds no chunk or no
operation under the scope."""


def needed_flops(cfg: dict, chunks) -> float:
    """``chunks``: [(offset, real tokens)] of the chunk programs traced."""
    H = int(cfg["num_attention_heads"])
    nope, rope, value = (int(cfg[k]) for k in (
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    expand = 2.0 * int(cfg["kv_lora_rank"]) * H * (nope + value)
    pair = (2.0 * (nope + rope) + 2.0 * value) * H
    out = 2.0 * H * value * int(cfg["hidden_size"])
    return int(cfg["num_hidden_layers"]) * sum(
        (o + n) * expand + (n * o + n * (n + 1) // 2) * pair + n * out
        for o, n in chunks)


def read(ctx):
    bench = ctx["bench"]
    spans = bench.lib("program_spans")
    trace = spans.trace_of(ctx)
    if trace is None:
        return None
    chunks = [(int(s.attrs.get("offset", 0)), int(s.attrs.get("tokens", 0)))
              for s in spans.named(trace, "pio:seq.prefill_chunk")]
    busy_s = bench.lib("glm_counts").scope_self_ns(
        spans, trace, ".mla_a.attend", "prefill_fn") / 1e9
    if not chunks or busy_s <= 0:
        return None
    kernel = bench.lib("kernel_counts")
    peaks = bench.lib("peaks").peaks_for(bench.devices[0].device_kind)
    return kernel.roofline_pct(
        kernel.least_seconds(peaks,
                             flops=needed_flops(bench.config, chunks)),
        busy_s)
