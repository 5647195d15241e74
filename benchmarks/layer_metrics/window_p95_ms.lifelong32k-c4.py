"""The 95th percentile of latency over the WINDOW of a traced run, in ms:
nearest rank over every request that started in it, the number this cell
reported end to end as ``query_p95_ms`` until PR 54 (the driver computes both
from one list). ``lifelong32k-c4`` keeps it in sight per layer: of ~194
requests a window ~23 are first queries, so 9.7 requests lie beyond the
rank, the tail is the ~10th slowest FIRST query, and which one that is moves
with the window's count of extensions; a change that made extensions 7 ms
faster read +17% here (ledger, PR 52). The cell's end-to-end tail is the
first queries' own median, ``first_query_p50_ms`` (``PERF.md`` §2)."""


def read(ctx):
    window = ctx.get("window_end_to_end")
    if not window or "query_p95_ms" not in window:
        return None
    return window["query_p95_ms"]
