"""The 95th percentile of latency over the WINDOW of a traced run, in ms:
nearest rank over every request that started in it, the number an untraced
run of another serve cell reports as ``query_p95_ms`` (the driver computes
both from one list). ``serve-c32`` reports it per layer since PR 48: a
saturated closed loop passes between regimes of two and of three alternating
batches, and the tail reads how long a run spent in the second, which no two
runs of one code agree on (``PERF.md`` §2)."""


def read(ctx):
    window = ctx.get("window_end_to_end")
    if not window or "query_p95_ms" not in window:
        return None
    return window["query_p95_ms"]
