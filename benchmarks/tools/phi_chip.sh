#!/bin/sh
# The builder's chip calls behind PERF.md's PR 53 numbers, one phase a call:
#     chiprun --chips 1 --timeout <s> -- sh benchmarks/tools/phi_chip.sh <phase> [arguments]
# Every run's output goes to chiprun_out/pr53/<name>.log (its errors to .err);
# the result line and the "# check" / "# run:" lines come back on stdout.
#   cell <trace 0|1> <seed>...     the cell, a run a seed
#   decays <seed> <seedings>       tools/phi_decays.py: the state and window controls a seeding
#   guard <seed> <seeding> <factor> exit 4 unless that seeding's bfloat16 state read over factor x the limit on both long histories
#   control <seeds>                control.py, every variant (seeds comma-separated)
#   ablation <seed>                tools/phi_ablation.py: a whole window over a window of 511
#   pair <cell> <seed> <seed>      parent | change | change | parent of an accepted cell
#                                  (.parent/: git archive HEAD | tar -x -C .parent, made before the call)
#   archive <seed>                 the committed files alone, cold and traced
#                                  (.change/: git archive $(git write-tree) | tar -x -C .change)
#   parent_newcell                 the parent on the new cell's name, and under this PR's benchmark files
CELL=phi-4-mini-flash-reasoning.longlived-c8
OUT=$PWD/chiprun_out/pr53
mkdir -p "$OUT"

show() {    # <name>: what a run said, for the call's own output
    echo "== $1 rc=$2"
    grep -h "^# check\|^# run:\|^# latency\|^# reference" "$OUT/$1.log" | cut -c1-600
    tail -n 1 "$OUT/$1.log" | cut -c1-3000
}

bench() {   # <name> <dir> <cell> <seed> <trace>
    (cd "$2" && timeout 900 python3 benchmarks/run.py --workload "$3" --seed "$4" \
        --seconds 20 --trace "$5") > "$OUT/$1.log" 2> "$OUT/$1.err"
    show "$1" $?
}

phase=$1; shift
case $phase in
cell)
    trace=$1; shift
    for seed in "$@"; do bench "cell_t${trace}_$seed" . $CELL "$seed" "$trace"; done;;
decays)
    timeout 1200 python3 benchmarks/tools/phi_decays.py --seed "$1" --seedings "$2" \
        --variants state_bfloat16,window_less_one,bfloat16 \
        > "$OUT/decays_$1.log" 2> "$OUT/decays_$1.err"
    echo "== decays rc=$?"; cat "$OUT/decays_$1.log";;
guard)      # <seed> <seeding> <factor>: stop the call where the state control is not that far outside
    python3 - "$OUT/decays_$1.log" "$2" "$3" <<'PY'
import json, sys
path, seeding, factor = sys.argv[1], sys.argv[2], float(sys.argv[3])
line = next(l for l in map(json.loads, open(path)) if l["seeding"] == seeding
            and l["variant"] == "state_bfloat16")
long_ones = line["score_err"][-2:]
need = factor * line["limits"]["score_err"]
print("== guard", seeding, long_ones, "need >", need)
sys.exit(0 if min(long_ones) > need else 4)
PY
    ;;
control)
    timeout 1200 python3 benchmarks/control.py --workload $CELL --seeds "$1" \
        > "$OUT/control_$1.log" 2> "$OUT/control_$1.err"
    echo "== control rc=$?"; cat "$OUT/control_$1.log";;
ablation)
    timeout 900 python3 benchmarks/tools/phi_ablation.py --window window_less_one --seed "$1" \
        > "$OUT/ablation_$1.log" 2> "$OUT/ablation_$1.err"
    show "ablation_$1" $?;;
pair)
    bench "P1_$1" .parent "$1" "$2" 0; bench "C1_$1" . "$1" "$2" 0
    bench "C2_$1" . "$1" "$3" 0; bench "P2_$1" .parent "$1" "$3" 0;;
archive)
    mkdir -p "$PWD/.change/.pio_run/empty_cache"
    JAX_COMPILATION_CACHE_DIR=$PWD/.change/.pio_run/empty_cache \
        bench "archive_t1_$1" .change $CELL "$1" 1;;
parent_newcell)
    (cd .parent && python3 benchmarks/run.py --workload $CELL --seed 1 --seconds 20 --trace 0) \
        > "$OUT/parent_newcell.log" 2>&1
    echo "== parent on the new cell's name rc=$? $(tail -n 2 "$OUT/parent_newcell.log")"
    mkdir -p .overlay && cp -r .parent/. .overlay/ && cp BENCHMARK.json .overlay/ \
        && cp -r benchmarks/. .overlay/benchmarks/
    (cd .overlay && python3 benchmarks/run.py --workload $CELL --seed 1 --seconds 20 --trace 0) \
        > "$OUT/overlay_newcell.log" 2>&1
    echo "== parent under this PR's benchmark files rc=$? $(tail -n 2 "$OUT/overlay_newcell.log" | cut -c1-400)";;
*)
    echo "unknown phase $phase" >&2; exit 2;;
esac
