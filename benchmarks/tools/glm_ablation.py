"""What the comparison of ``glm-5.lifelong32k-c4`` must catch, shown on the
chip at the cell's own size (a builder's tool; no run of the benchmark runs
it). The cell's own seeded weights go through a program whose SELECTION is
broken underneath (``broken``):

* ``no_index``: no selection, every cached position attended (dense latent
  attention: ``models/glmrec.stack_spec(no_index=True)``);
* ``last_positions``: the last ``index_topk`` positions in the index's place
  (``ops/mla.project_index`` replaced by one whose score IS the key's
  position, exactly: 256 * hi + lo through one head);
* ``no_rope``: the index without its RoPE (``project_index`` at position 0
  for every row).

Three modes:

    python3 benchmarks/tools/glm_ablation.py --window no_index --seed 7
        a WHOLE WINDOW of the cell (``benchmarks/run.py``'s own ``main``, in
        this process) with that program in the sound one's place: the
        result line must read ``"correct": false``

    python3 benchmarks/tools/glm_ablation.py --seeds 1,2 [--histories 2143,8192,32768]
        single answers: sound and broken programs, each a ``SeqStackModel``
        driven through its steps, against the reference's full forward; one
        line a (seed, program, history)

    python3 benchmarks/tools/glm_ablation.py --sets --seed 7 [--rows 256]
        how many of a row's 2,048 positions change sides between the
        program's index (bfloat16 products, bfloat16 cached keys) and the
        reference's (float32, highest), over the last ``--rows`` rows of the
        mix's longest history at layer 0, and the margin at the cut
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCHMARKS = os.path.dirname(HERE)
sys.path.insert(0, BENCHMARKS)
sys.path.insert(0, os.path.dirname(BENCHMARKS))

CELL = "glm-5.lifelong32k-c4"
VARIANTS = ("no_index", "last_positions", "no_rope")


def _last_positions(p, dims, x, cq, pos):
    import jax.numpy as jnp

    where = pos.astype(jnp.float32)
    ki = jnp.zeros(x.shape[:-1] + (dims.index_dim,)).at[..., 0].set(
        jnp.floor(where / 256)).at[..., 1].set(where % 256)
    qi = jnp.zeros(x.shape[:-1] + (dims.index_heads, dims.index_dim)).at[
        ..., 0, 0].set(256.0).at[..., 0, 1].set(1.0)
    w = jnp.zeros(x.shape[:-1] + (dims.index_heads,)).at[..., 0].set(1.0)
    return qi, ki, w


@contextlib.contextmanager
def broken(what: str, builder):
    """The program's selection broken as ``what`` says, underneath
    ``builder`` (the module whose ``stack_spec`` the run deploys), until the
    block ends."""
    import jax.numpy as jnp

    from predictionio_tpu.ops import mla

    sound_spec, sound_index = builder.stack_spec, mla.project_index
    if what == "no_index":
        builder.stack_spec = lambda cfg, **kw: sound_spec(cfg, no_index=True)
    elif what == "last_positions":
        mla.project_index = _last_positions
    elif what == "no_rope":
        mla.project_index = lambda p, d, x, cq, pos: sound_index(
            p, d, x, cq, jnp.zeros_like(pos))
    else:
        raise ValueError(f"unknown ablation {what!r}")
    try:
        yield
    finally:
        builder.stack_spec, mla.project_index = sound_spec, sound_index


def answers_of(builder, bench, weights, histories, k):
    """The program's answers: [(ids, [(item row, score), ...]), ...]."""
    from predictionio_tpu.data.bimap import BiMap
    from predictionio_tpu.models.sessionrec import (SeqStackModel,
                                                    SeqStackParams)

    spec = builder.stack_spec(bench.config)
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


def single_answers(harness, bench, wanted) -> None:
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
    served = {"sound": answers_of(builder, bench, weights, histories, k)}
    for what in VARIANTS:
        with broken(what, builder):
            served[what] = answers_of(builder, bench, weights, histories, k)
    dm = reference.dims_of(bench.config)
    for n, ids in enumerate(histories):
        logits = reference.forward(weights, ids, dm)[0]
        for name, answers in served.items():
            got = reference.measure(logits, answers[n][1], k)
            err, gap = got or (float("inf"), float("inf"))
            ok = err <= limits["score_err"] and gap <= limits["rank_gap"]
            print(json.dumps({
                "seed": bench.seed, "program": name, "history": len(ids),
                "score_err": err, "rank_gap": gap, "limits": limits,
                "malformed": got is None,
                "verdict": "ok" if ok else "FAILED"}), flush=True)


def sets_at_the_cut(bench, rows: int) -> None:
    """Layer 0's sets of the longest history's last ``rows`` rows, the
    program's against the reference's."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from predictionio_tpu.ops import mla

    builder = bench.load_module("models", bench.config["engine"])
    reference = bench.load_module("reference", bench.config["reference"])
    sessions = bench.lib("session_traffic").Sessions(
        bench.traffic, int(bench.config["vocab_size"]))
    order = sessions.order(0)
    ids = sessions.session(0, order.index(max(order)))[0]
    weights = builder.make_weights(bench)
    dims, dm = builder.stack_spec(bench.config).mla, reference.dims_of(
        bench.config)
    layer = weights["layers"][0]
    p = layer["mixer_a"]
    T = len(ids)
    pos = jnp.arange(T, dtype=jnp.int32)
    x = weights["embed"][jnp.asarray(ids, jnp.int32)].astype(jnp.float32)
    u = mla.rms_norm(x, layer["norm_a"], dims.eps)
    last = slice(T - rows, T)

    @jax.jit
    def program(u):
        cq = mla.compress_q(p, dims, u)
        qi, ki, w = mla.project_index(p, dims, u, cq, pos)
        keys = ki.astype(jnp.bfloat16)                  # as cached
        scores = mla.index_scores(qi[last].astype(jnp.bfloat16), w[last],
                                  keys)
        scores = jnp.where(pos[last, None] >= pos[None], scores, -jnp.inf)
        top = jax.lax.top_k(scores, dims.index_topk + 1)[0]
        return mla.topk_mask(scores, dims.index_topk), top

    @jax.jit
    def plain(u):
        with jax.default_matmul_precision("highest"):
            _, (_, _, ki), (qi, w) = reference.latents(p, u, pos, dm)
            return reference.selected(qi[last], w[last], pos[last], ki, pos,
                                      dm)

    got, top = program(u)
    want = plain(u)
    moved = np.asarray((got != want).sum(axis=1)) // 2
    top = np.asarray(top, np.float64)
    spread = top[:, 0] - top[:, -1]
    margin = (top[:, -2] - top[:, -1]) / np.maximum(spread, 1e-30)
    print(json.dumps({
        "seed": bench.seed, "history": T, "rows": rows, "layer": 0,
        "positions_changed_sides_mean": float(moved.mean()),
        "positions_changed_sides_max": int(moved.max()),
        "rows_with_none": int((moved == 0).sum()),
        "cut_margin_over_the_kept_range_median": float(np.median(margin)),
        "index_topk": dims.index_topk}), flush=True)


def main() -> int:
    import run as harness

    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", default="")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--histories", default="2143,8192,32768")
    ap.add_argument("--window", choices=VARIANTS)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--sets", action="store_true")
    ap.add_argument("--rows", type=int, default=256)
    ap.add_argument("--bench-root", default=harness.CHECKOUT)
    ap.add_argument("--workload", default=CELL)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    root = os.path.abspath(args.bench_root)
    if args.window:
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            spec = json.load(f)
        cell = next(w for w in spec["workloads"]
                    if w["name"] == args.workload)
        cfg_file = next(c["file"] for c in spec["configs"]
                        if c["name"] == cell["config"])
        with open(os.path.join(root, cfg_file)) as f:
            engine = json.load(f)["engine"]
        # the module instance the run itself will load
        builder = harness.load_file(os.path.join(
            BENCHMARKS, "models", engine + ".py"))
        print(f"# ablation: a window of {args.workload} with {args.window} "
              "underneath", flush=True)
        with broken(args.window, builder):
            return harness.main(
                ["--workload", args.workload, "--seed", str(args.seed),
                 "--seconds", str(args.seconds), "--trace", "0",
                 "--bench-root", root]
                + (["--rehearse-cpu"] if args.rehearse_cpu else []))
    if args.rehearse_cpu:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cell = next(w for w in spec["workloads"] if w["name"] == args.workload)
    seeds = [int(s) for s in args.seeds.split(",") if s] or [args.seed]
    for seed in seeds:
        bench = harness.Bench(root, spec, cell, argparse.Namespace(
            seed=seed, seconds=0, trace=0))
        if args.sets:
            sets_at_the_cut(bench, args.rows)
        else:
            single_answers(harness, bench,
                           [int(h) for h in args.histories.split(",")])
        gc.collect()
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
