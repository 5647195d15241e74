#!/bin/sh
# The builder's chip calls behind PERF.md's PR 54 numbers, one phase a call:
#     chiprun --chips 1 --timeout <s> -- sh benchmarks/tools/pr54_chip.sh <phase> [arguments]
# Every run's output goes to chiprun_out/pr54/<name>.log (its errors to .err);
# the result line and the "# check" / "# run:" / "# first queries" lines come back.
#   timeline <seed>...             tools/window_timeline.py, an untraced run a seed
#   cell <cell> <trace 0|1> <seed>...   a cell, a run a seed
#   first <seed> x 12              timelines of three seeds, three traced runs, an untraced set of six
#   others <seed>                  the five other session_queries cells, an untraced run each (seed, seed+1, ...)
#   archive <cell> <seed>          the committed files alone, cold and traced
#                                  (.change/: git archive $(git write-tree) | tar -x -C .change)
CELL=glm-5.lifelong32k-c4
OUT=$PWD/chiprun_out/pr54
mkdir -p "$OUT"

show() {    # <name> <rc>: what a run said, for the call's own output
    echo "== $1 rc=$2"
    grep -h "^# check\|^# run:\|^# latency\|^# first queries\|^# requests sent\|^# timeline\|^# extensions started\|^# stretches\|^# first query:" \
        "$OUT/$1.log" | cut -c1-1200
    grep -v "^#" "$OUT/$1.log" | tail -n 1 | cut -c1-3500      # the result line
    tail -n 3 "$OUT/$1.err" | cut -c1-400
}

bench() {   # <name> <dir> <cell> <seed> <trace>
    (cd "$2" && timeout 900 python3 benchmarks/run.py --workload "$3" --seed "$4" \
        --seconds 20 --trace "$5") > "$OUT/$1.log" 2> "$OUT/$1.err"
    rc=$?
    if [ "$5" = 1 ]; then   # the spans the traced stretch held, by name
        (cd "$2" && JAX_PLATFORMS=cpu python3 benchmarks/tools/stretch_spans.py "$3") \
            | cut -c1-600 | tee -a "$OUT/$1.spans"
    fi
    show "$1" $rc
}

phase=$1; shift
case $phase in
timeline)
    for seed in "$@"; do
        timeout 900 python3 benchmarks/tools/window_timeline.py --workload $CELL --seed "$seed" \
            > "$OUT/timeline_$seed.log" 2> "$OUT/timeline_$seed.err"
        show "timeline_$seed" $?
    done;;
cell)
    cell=$1; trace=$2; shift; shift
    for seed in "$@"; do bench "${cell}_t${trace}_$seed" . "$cell" "$seed" "$trace"; done;;
first)
    sh "$0" timeline "$1" "$2" "$3"
    sh "$0" cell $CELL 1 "$4" "$5" "$6"
    shift 6
    sh "$0" cell $CELL 0 "$@";;
others)
    seed=$1
    for cell in longcat-flash-chat.sessions-c8 granite-4.0-h-small.sessions-c16 ax-k1.lifelong-c4 \
            mimo-v2.5.mixed-c8 phi-4-mini-flash-reasoning.longlived-c8; do
        bench "${cell}_t0_$seed" . "$cell" "$seed" 0
        seed=$((seed + 1))
    done
    # and, where the call brought the unpacked archive, the proof beside them
    if [ -d .change ]; then sh "$0" archive $CELL "$seed"; fi;;
archive)
    mkdir -p "$PWD/.change/.pio_run/empty_cache"
    JAX_COMPILATION_CACHE_DIR=$PWD/.change/.pio_run/empty_cache \
        bench "archive_t1_$2" .change "$1" "$2" 1;;
*)
    echo "unknown phase $phase" >&2; exit 2;;
esac
