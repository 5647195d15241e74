"""What the batcher's worker thread spent in host work, in ms a dispatch: the
self wall time of every accounted span that is not a wait, over the dispatches
of the traced stretch.

``MicroBatcher.histogram()["phases"]`` holds, per span name the worker thread
opened, ``[count, self wall ns, self CPU ns]`` since the server started
(``obs/trace.py`` ``ThreadAccount``: a span's own time less its child spans',
the time between the outermost spans under ``unspanned``, so the names add up
to the thread's time; the CPU figure is None where the host's thread clock is
too dear to read, as on the benchmark's machine). ``per_dispatch`` takes the
difference over the stretch (``hist0`` / ``hist1``); the first reader of a run
that asks prints the whole table. A name of the program's ``WAIT_PHASES``
(``batch.idle``: nothing queued; ``index.fetch``: the device at work) is a
wait the worker is meant to make; every other name is host work: its own
work, and its waits for the interpreter. ``serve-c1`` (one connection, nobody
to hand the interpreter to) prices the work; what ``serve-c32`` reads above
that at equal work is waiting. A program whose histogram holds no ``phases``
(the parent of PR 37) gives nothing to read."""


def per_dispatch(ctx):
    """``({name: (wall ms, CPU ms or None) a dispatch}, the program's wait
    names)`` over the traced stretch; None where the histogram has no
    ``phases``."""
    if "_worker_phases" in ctx:
        return ctx["_worker_phases"]
    out = None
    h0, h1 = ctx.get("hist0"), ctx.get("hist1")
    if h0 and h1 and h1.get("phases") and "phases" in h0:
        from predictionio_tpu.obs.trace import WAIT_PHASES

        dispatches = h1["dispatches"] - h0["dispatches"]
        if dispatches > 0:
            table = {}
            for name, (_, wall, cpu) in h1["phases"].items():
                _, wall0, cpu0 = h0["phases"].get(name, (0, 0, 0))
                table[name] = (
                    (wall - wall0) / dispatches / 1e6,
                    None if cpu is None
                    else (cpu - (cpu0 or 0)) / dispatches / 1e6)
            out = (table, WAIT_PHASES)

            def ms(v):
                return "-" if v is None else f"{v:.4f}"

            print("# worker phases, ms a dispatch (self wall, self CPU) "
                  f"over {dispatches} dispatches: " + ", ".join(
                      f"{name}{'*' if name in WAIT_PHASES else ''} "
                      f"{ms(wall)} {ms(cpu)}" for name, (wall, cpu)
                      in sorted(table.items(), key=lambda kv: -kv[1][0]))
                  + f"; sum {sum(w for w, _ in table.values()):.4f} "
                  "(* a wait; - not measured)", flush=True)
    ctx["_worker_phases"] = out
    return out


def read(ctx):
    got = per_dispatch(ctx)
    if got is None:
        return None
    table, waits = got
    return sum(wall for name, (wall, _) in table.items()
               if name not in waits)
