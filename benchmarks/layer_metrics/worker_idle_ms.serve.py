"""How long the batcher's worker thread stood with nothing queued, in ms a
dispatch: the wall time of ``pio:batch.idle`` (the worker blocked in
``queue.get()``) over the dispatches of the traced stretch, from
``MicroBatcher.histogram()["phases"]`` (``worker_host_ms.serve``). Under a
closed loop it is the part of the worker's turnaround in which the handler
threads were still writing the last answers and parsing the next requests. A
program whose histogram holds no ``phases`` gives nothing to read."""


def read(ctx):
    got = ctx["bench"].lib(
        "layer_metrics/worker_host_ms.serve").per_dispatch(ctx)
    if got is None or "batch.idle" not in got[0]:
        return None
    return got[0]["batch.idle"][0]
