"""What the comparison of ``ax-k1.lifelong-c4`` must catch, shown on the chip
at the cell's own size (a builder's tool; no run of the benchmark runs it):

    python3 benchmarks/tools/axk_ablation.py --seeds 1,2 [--histories 2744,6114,12390,24576]

For each seed the cell's own seeded weights go to THREE programs in turn, each
a ``SeqStackModel`` driven through its steps (chunked prefill, then the last
positions through the extension program, the head through the index) for a
few of the mix's own histories: the program as the benchmark deploys it
(``sound``), one with plain RoPE in YaRN's place (``plain_rope``), one with
plain top-8 in the group rule's place (``plain_top_k``)
(``models/axkrec.stack_spec``'s two flags). Each answer is held against the
reference's full forward over the same history, one line a (seed, program,
history) with the two numbers the cell's checks compare beside their limits.
Below the original length (4,096) YaRN's angles barely differ from plain
RoPE's; past it the plain program must FAIL.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCHMARKS = os.path.dirname(HERE)
sys.path.insert(0, BENCHMARKS)
sys.path.insert(0, os.path.dirname(BENCHMARKS))

CELL = "ax-k1.lifelong-c4"
VARIANTS = {"sound": {}, "plain_rope": {"plain_rope": True},
            "plain_top_k": {"plain_top_k": True}}


def answers_of(builder, bench, weights, flags, histories, k):
    """The program's answers: [(ids, [(item row, score), ...]), ...]."""
    from predictionio_tpu.data.bimap import BiMap
    from predictionio_tpu.models.sessionrec import (SeqStackModel,
                                                    SeqStackParams)

    spec = builder.stack_spec(bench.config, **flags)
    items = BiMap.from_vocab(list(map("i%d".__mod__,
                                      range(weights["embed"].shape[0]))))
    stack = {"item_embed": {"embedding": weights["embed"]},
             "head": weights["head"], "final_norm": weights["final_norm"],
             "blocks": weights["layers"]}
    model = SeqStackModel(spec, stack, items,
                          SeqStackParams(**bench.config["serve"]).shape())
    out = []
    for ids in histories:
        got = model.recommend({"items": ["i%d" % r for r in ids], "num": k})
        out.append((ids, [(builder.item_row(item), score)
                          for item, score in got]))
    model._programs = model._index = None
    del model
    gc.collect()
    return out


def main() -> int:
    import run as harness

    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--histories", default="2744,6114,12390,24576")
    ap.add_argument("--bench-root", default=harness.CHECKOUT)
    ap.add_argument("--workload", default=CELL)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    if args.rehearse_cpu:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    root = os.path.abspath(args.bench_root)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cell = next(w for w in spec["workloads"] if w["name"] == args.workload)
    wanted = [int(h) for h in args.histories.split(",")]
    for seed in (int(s) for s in args.seeds.split(",")):
        bench = harness.Bench(root, spec, cell, argparse.Namespace(
            seed=seed, seconds=0, trace=0))
        builder = bench.load_module("models", bench.config["engine"])
        reference = bench.load_module("reference", bench.config["reference"])
        sessions = bench.lib("session_traffic").Sessions(
            bench.traffic, int(bench.config["vocab_size"]))
        order = sessions.order(0)
        nearest = [min(range(len(order)), key=lambda i: abs(order[i] - h))
                   for h in wanted]
        histories = [sessions.session(0, i)[0] for i in nearest]
        k, limits = int(bench.traffic["num"]), bench.config["limits"]
        weights = builder.make_weights(bench)
        served = {name: answers_of(builder, bench, weights, flags,
                                   histories, k)
                  for name, flags in VARIANTS.items()}
        dm = reference.dims_of(bench.config)
        for n, ids in enumerate(histories):
            # one forward of the reference a history, whatever the programs
            logits = reference.forward(weights, ids, dm)[0]
            for name in VARIANTS:
                got = reference.measure(logits, served[name][n][1], k)
                err, gap = got or (float("inf"), float("inf"))
                ok = err <= limits["score_err"] and gap <= limits["rank_gap"]
                print(json.dumps({
                    "seed": seed, "program": name, "history": len(ids),
                    "score_err": err, "rank_gap": gap, "limits": limits,
                    "malformed": got is None,
                    "verdict": "ok" if ok else "FAILED"}), flush=True)
        del weights, served
        gc.collect()
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
