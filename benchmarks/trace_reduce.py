"""From a profiler trace to device numbers: the benchmark's own reduction.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes with nothing but JAX
(``jax.profiler.ProfileData``). Three things come out of a traced window:

* **busy**: the UNION of the intervals in which an operation ran on a device
  — nested events (a ``while`` and the ops inside it) count once, which is
  what a sum of durations gets wrong;
* **device_ops**: each operation's SELF time (its duration less the part its
  children cover), summed by name, largest first;
* **idle_gaps**: every gap between busy intervals, attributed to the
  innermost of the benchmark's own host spans (``bench:*``
  ``TraceAnnotation``s, same clock) that covers the gap's middle.

The window is the ``bench:window`` span the driver holds open while it
traces, so profiler start-up and tear-down are outside it.
"""

from __future__ import annotations

import glob
import os
import shutil
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]          # (start_ns, end_ns)
Event = Tuple[str, float, float]        # (name, start_ns, end_ns)

WINDOW_SPAN = "bench:window"
SPAN_PREFIX = "bench:"
#: the device line that holds one event per executed HLO op
OPS_LINE = "XLA Ops"


def start_trace(trace_dir: str) -> None:
    """Start the profiler into an emptied ``trace_dir``, with its tracing of
    Python calls off: that tracer slows every call of the host code under
    test (the benchmark's own spans are TraceAnnotations and stay)."""
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_events(xplane_path: str) -> dict:
    """{"devices": {plane name: [Event]}, "spans": [Event]} — device op
    events per chip, and the benchmark's host spans."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    devices: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in data.planes:
        name = plane.name
        if name.startswith("/device:") and "TPU" in name:
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == OPS_LINE] or [
                ln for ln in lines if "Ops" in ln.name]
            events = [(e.name, float(e.start_ns),
                       float(e.start_ns) + float(e.duration_ns))
                      for ln in ops for e in ln.events]
            if events:
                devices[name] = events
        elif name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, float(e.start_ns),
                                      float(e.start_ns)
                                      + float(e.duration_ns)))
    return {"devices": devices, "spans": spans}


def union(intervals: List[Interval]) -> List[Interval]:
    """Sorted, disjoint cover of ``intervals``."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(events: List[Event], t0: float, t1: float) -> List[Event]:
    return [(n, max(s, t0), min(e, t1)) for n, s, e in events
            if e > t0 and s < t1]


def self_times(events: List[Event]) -> Dict[str, float]:
    """Self nanoseconds by name on one line of properly nested events."""
    out: Dict[str, float] = {}
    stack: List[list] = []          # [name, end, child_ns, start]

    def close(item):
        name, end, child, start = item
        out[name] = out.get(name, 0.0) + max(0.0, (end - start) - child)

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        if stack:
            # a child is counted against its parent, never past its end
            stack[-1][2] += min(e, stack[-1][1]) - s
        stack.append([name, e, 0.0, s])
    while stack:
        close(stack.pop())
    return out


def gaps(busy: List[Interval], t0: float, t1: float) -> List[Interval]:
    out, cur = [], t0
    for s, e in busy:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if t1 > cur:
        out.append((cur, t1))
    return out


def attribute(gap: Interval, spans: List[Event]) -> str:
    mid = 0.5 * (gap[0] + gap[1])
    best, best_len = "outside the benchmark's spans", None
    for name, s, e in spans:
        if name == WINDOW_SPAN or not (s <= mid < e):
            continue
        if best_len is None or (e - s) < best_len:
            best, best_len = name, e - s
    return best


def window_of(events: dict) -> Optional[Interval]:
    w = [(s, e) for n, s, e in events["spans"] if n == WINDOW_SPAN]
    if w:
        return max(w, key=lambda iv: iv[1] - iv[0])
    every = [ev for evs in events["devices"].values() for ev in evs]
    if not every:
        return None
    return (min(s for _, s, _ in every), max(e for _, _, e in every))


def reduce_events(events: dict, top: int = 10) -> dict:
    """The numbers of one traced window (seconds; averaged over chips)."""
    window = window_of(events)
    if window is None or not events["devices"]:
        return {"busy_s": 0.0, "window_s": 0.0, "device_ops": [],
                "idle_gaps": [], "n_devices": 0, "op_events": 0}
    t0, t1 = window
    busy_ns, ops, gap_ns, n_events = 0.0, {}, {}, 0
    n_dev = len(events["devices"])
    for evs in events["devices"].values():
        evs = clip(evs, t0, t1)
        n_events += len(evs)
        busy = union([(s, e) for _, s, e in evs])
        busy_ns += sum(e - s for s, e in busy)
        for name, ns in self_times(evs).items():
            ops[name] = ops.get(name, 0.0) + ns
        for g in gaps(busy, t0, t1):
            who = attribute(g, events["spans"])
            gap_ns[who] = gap_ns.get(who, 0.0) + (g[1] - g[0])

    def ranked(d):
        return [[k, v / n_dev / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"busy_s": busy_ns / n_dev / 1e9,
            "window_s": (t1 - t0) / 1e9,
            "device_ops": ranked(ops), "idle_gaps": ranked(gap_ns),
            "n_devices": n_dev, "op_events": n_events}


def reduce_trace(trace_dir: str, top: int = 10) -> dict:
    return reduce_events(load_events(find_xplane(trace_dir)), top=top)


def idle_pct(reduced) -> Optional[float]:
    """100 * (1 - busy / window) of a reduction, or None where no operation
    ran on a device in it."""
    if not reduced or reduced.get("window_s", 0) <= 0 \
            or reduced["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])


def count_spans(events: dict, name: str) -> int:
    """How many ``name`` spans lie inside the window of ``load_events``'
    result."""
    window = window_of(events)
    if window is None:
        return 0
    t0, t1 = window
    return sum(1 for n, s, e in events["spans"]
               if n == name and s >= t0 and e <= t1)
