"""The program's own spans and its named device work, from a traced run.

``trace_reduce.py`` reads busy time, operations and gaps and knows only the
benchmark's ``bench:`` spans. This file reads what the PROGRAM writes into the
same ``.xplane.pb`` (``predictionio_tpu/obs/trace.py device_span``,
``pallas_call(name=...)``, ``jax.named_scope``), with ``ProfileData`` alone:

* **spans**: every ``pio:`` annotation of every host line, with its
  attributes; a span's parent is the innermost span of the same line (thread)
  that contains it, so a layer's self time is its duration less its children;
* **ops**: each device operation with its instruction's name (an event's name
  on this chip is the instruction's text: ``%topk_dot.1 = ...`` is
  ``topk_dot.1``), the compiled program it ran in (the ``XLA Modules`` line)
  and, through the program's own map (``obs/jaxmon.SCOPE_MAPS``), the
  ``named_scope`` it was traced under. A kernel is found by its ``name=``
  inside the instruction's name, one of XLA's fusions by its scope, nothing
  by a number or a shape;
* the two clocks: a device event's time is the device's clock, which on this
  chip runs one to two milliseconds BEHIND the host's (a kernel seems to
  start before the call that launched it). ``clock_offset`` finds the shifts
  under which every executed program lies inside a span that can have
  launched it (``pio:index.search``, ``pio:train.epoch``), ``load`` applies
  the middle of them and the ``#`` lines print both ends: what lies between
  them is the launch and the copy back, which no trace can tell apart;
* the device work of one span: the operations that STARTED between the span's
  start and the next span of its name on its thread (by order, so that what
  is left of the clocks' difference moves no operation to a neighbour);
* the idle seconds of the traced stretch by innermost ``pio:`` span
  (``idle_by_span``), printed as ``#`` lines the first time a reader asks.

The stretch is the driver's ``bench:window`` span, as in ``trace_reduce``. A
reader gets everything through ``trace_of(ctx)``, which returns None where
there is no trace, no ``pio:`` span (the parent commit) or no JAX profile
reader: the metric is then left out of the line.
"""

from __future__ import annotations

import bisect
import importlib.util
import os
import statistics
import sys
from typing import Dict, List, NamedTuple, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
SPAN_PREFIX = "pio:"
MODULES_LINE = "XLA Modules"
#: a thread in this span only waits for another: never what holds the chip
WAITING = ("pio:serve.wait",)
NO_SPAN = "(no pio: span)"
#: the profiler records a program's operations when the program ends: what
#: was in flight when the stretch ended is missing, and reads as idle
UNRECORDED_TAIL = "(after the last recorded operation)"

Interval = Tuple[float, float]


class Span(NamedTuple):
    name: str
    start: float            # ns, the trace's clock
    end: float
    line: int               # which host thread
    attrs: dict
    parent: Optional[int]   # index into Trace.spans


class Op(NamedTuple):
    instr: str              # "topk_dot.1", "fusion.108"
    start: float
    end: float
    module: str             # "jit_epoch"
    scope: Optional[str]    # "twotower.adagrad_user"


class Trace(NamedTuple):
    spans: List[Span]                    # inside the window, by start
    ops: Dict[str, List[Op]]             # device plane -> ops, by start
    window: Interval
    #: (low, high) ns: the device clock minus the host clock lies between
    #: them; the ops' times are already on the host's clock (the middle)
    clock: Interval = (0.0, 0.0)


#: spans inside which the device can be given work
LAUNCHING = ("pio:index.search", "pio:train.epoch")


def clock_offset(execs: List[Interval], launching: List[Interval],
                 reach_us: int = 5000, step_us: int = 20) -> Interval:
    """(low, high) ns of device clock minus host clock: the widest run of
    shifts, within ``reach_us``, under which least of the executed programs'
    time (``execs``, the device's clock) lies outside every span that can
    have launched it (``launching``, the host's). (0, 0) with nothing to
    hold against."""
    cover = _sibling("trace_reduce").union(launching)
    if not cover or not execs:
        return (0.0, 0.0)
    starts = [c[0] for c in cover]

    def outside(a: float, b: float) -> float:
        inside, i = 0.0, max(0, bisect.bisect_right(starts, a) - 1)
        while i < len(cover) and cover[i][0] < b:
            inside += max(0.0, min(b, cover[i][1]) - max(a, cover[i][0]))
            i += 1
        return (b - a) - inside

    shifts = [us * 1e3 for us in range(-reach_us, reach_us + 1, step_us)]
    cost = [sum(outside(s - d, e - d) for s, e in execs) for d in shifts]
    least = min(cost) + 1e3                   # a microsecond's slack
    best, run = (0, -1), None
    for i, c in enumerate(cost + [float("inf")]):
        if c <= least:
            run = i if run is None else run
        elif run is not None:
            if i - 1 - run > best[1] - best[0]:
                best = (run, i - 1)
            run = None
    return (shifts[best[0]], shifts[best[1]])


def _sibling(name: str):
    """A shared file of the benchmark that lies beside this one."""
    modname = "_bench_sibling_" + name
    if modname not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            modname, os.path.join(HERE, name + ".py"))
        module = importlib.util.module_from_spec(spec)
        sys.modules[modname] = module
        spec.loader.exec_module(module)
    return sys.modules[modname]


def instruction(event_name: str) -> str:
    """``%fusion.108 = f32[..] fusion(..)`` -> ``fusion.108``."""
    return event_name.split(" ", 1)[0].lstrip("%")


def nest(flat: List[tuple]) -> List[Span]:
    """(name, start, end, line, attrs) tuples -> Spans with parents, sorted
    by start. Spans of one line nest properly (they come from ``with``)."""
    order = sorted(range(len(flat)),
                   key=lambda i: (flat[i][1], -flat[i][2]))
    spans: List[Span] = []
    stacks: Dict[int, List[int]] = {}
    for i in order:
        name, start, end, line, attrs = flat[i]
        stack = stacks.setdefault(line, [])
        while stack and spans[stack[-1]].end <= start:
            stack.pop()
        spans.append(Span(name, start, end, line, attrs,
                          stack[-1] if stack else None))
        stack.append(len(spans) - 1)
    return spans


def program_scope_maps() -> dict:
    """The running program's instruction -> scope maps, per compiled program
    ({} on a commit that keeps none)."""
    try:
        from predictionio_tpu.obs import jaxmon
    except ImportError:
        return {}
    return getattr(jaxmon, "SCOPE_MAPS", {})


def load(xplane_path: str, scope_maps: Optional[dict] = None
         ) -> Optional[Trace]:
    """The window, the ``pio:`` spans and the device operations of one
    ``.xplane.pb``; None where the trace holds no window or no such span."""
    from jax.profiler import ProfileData

    trace_reduce = _sibling("trace_reduce")
    scope_maps = program_scope_maps() if scope_maps is None else scope_maps
    data = ProfileData.from_file(xplane_path)
    flat, windows, ops, execs, line_no = [], [], {}, [], 0
    for plane in data.planes:
        pname = plane.name
        if pname.startswith("/device:") and "TPU" in pname:
            modules, events = [], []
            for ln in plane.lines:
                if ln.name == MODULES_LINE:
                    modules = sorted(
                        (float(e.start_ns), e.name.split("(", 1)[0],
                         float(e.start_ns) + float(e.duration_ns))
                        for e in ln.events)
                    execs += [(m[0], m[2]) for m in modules]
                elif ln.name == trace_reduce.OPS_LINE:
                    events = [(float(e.start_ns), float(e.duration_ns),
                               instruction(e.name)) for e in ln.events]
            starts = [m[0] for m in modules]
            plane_ops = []
            for start, dur, instr in sorted(events):
                at = bisect.bisect_right(starts, start) - 1
                module = modules[at][1] if at >= 0 else ""
                plane_ops.append(Op(instr, start, start + dur, module,
                                    scope_maps.get(module, {}).get(instr)))
            if plane_ops:
                ops[pname] = plane_ops
        elif pname.startswith("/host:"):
            for ln in plane.lines:
                line_no += 1
                for e in ln.events:
                    name = e.name
                    if name.startswith(SPAN_PREFIX):
                        start = float(e.start_ns)
                        flat.append((name, start,
                                     start + float(e.duration_ns), line_no,
                                     dict(e.stats)))
                    elif name == trace_reduce.WINDOW_SPAN:
                        start = float(e.start_ns)
                        windows.append((start,
                                        start + float(e.duration_ns)))
    if not flat:
        return None
    window = (max(windows, key=lambda w: w[1] - w[0]) if windows else
              (min(f[1] for f in flat), max(f[2] for f in flat)))
    t0, t1 = window
    spans = nest([f for f in flat if f[1] >= t0 and f[2] <= t1])
    clock = clock_offset(execs, [(f[1], f[2]) for f in flat
                                 if f[0] in LAUNCHING])
    shift = 0.5 * (clock[0] + clock[1])
    ops = {k: [o._replace(start=o.start - shift, end=o.end - shift)
               for o in v if o.end - shift > t0 and o.start - shift < t1]
           for k, v in ops.items()}
    return Trace(spans, ops, window, clock)


def trace_of(ctx: dict) -> Optional[Trace]:
    """The traced run's Trace, read once per run and kept on ``ctx``; the
    first call also prints the idle seconds by span. None: nothing to read."""
    if "_program_spans" not in ctx:
        trace = None
        try:
            trace_reduce = _sibling("trace_reduce")
            path = trace_reduce.find_xplane(
                os.path.join(ctx["bench"].scratch, "trace"))
            trace = load(path)
        except (ImportError, OSError) as e:
            print(f"# program spans: not read ({e})", flush=True)
        ctx["_program_spans"] = trace
        if trace is not None:
            for line in report_lines(trace):
                print("# " + line, flush=True)
    return ctx["_program_spans"]


# -- spans -------------------------------------------------------------------

def named(trace: Trace, name: str, **attrs) -> List[Span]:
    return [s for s in trace.spans if s.name == name
            and all(s.attrs.get(k) == v for k, v in attrs.items())]


def children(trace: Trace, index: int) -> List[Span]:
    return [s for s in trace.spans if s.parent == index]


def self_ns(trace: Trace, index: int) -> float:
    """A span's duration less the part its child spans cover."""
    span = trace.spans[index]
    union = _sibling("trace_reduce").union
    covered = union([(c.start, c.end) for c in children(trace, index)])
    return (span.end - span.start) - sum(e - s for s, e in covered)


def less_children(trace: Trace, name: str, child: str) -> List[float]:
    """Per ``name`` span that has a ``child`` span directly or deeper on its
    thread: its duration less the time those children cover, in ns."""
    inner: Dict[int, List[Span]] = {}
    for c in trace.spans:
        if c.name == child:
            inner.setdefault(c.line, []).append(c)
    out = []
    for span in trace.spans:
        if span.name != name:
            continue
        covered = [c.end - c.start for c in inner.get(span.line, ())
                   if c.start >= span.start and c.end <= span.end]
        if covered:
            out.append((span.end - span.start) - sum(covered))
    return out


def median_ms(values_ns: List[float]) -> Optional[float]:
    return statistics.median(values_ns) / 1e6 if values_ns else None


# -- device work -------------------------------------------------------------

def all_ops(trace: Trace) -> List[Op]:
    return [o for plane in trace.ops.values() for o in plane]


def ops_named(trace: Trace, kernel: str) -> List[Op]:
    """The events of one kernel: those whose instruction name holds the
    ``pallas_call``'s ``name=``. XLA appends ``.N``; under autodiff with no
    scope around it JAX wraps the name too (``jvp_flash_ce_fwd_.1``)."""
    return [o for o in all_ops(trace) if kernel in o.instr]


def ops_in_scope(trace: Trace, scopes) -> List[Op]:
    return [o for o in all_ops(trace) if o.scope in scopes]


def busy_ns(ops: List[Op]) -> float:
    """The union of the operations' intervals (nested events count once)."""
    union = _sibling("trace_reduce").union
    return sum(e - s for s, e in union([(o.start, o.end) for o in ops]))


def self_ns_of_ops(trace: Trace, wanted) -> float:
    """Summed SELF time of the operations ``wanted(op)`` accepts: a loop's
    event covers the operations inside it, which must not count twice."""
    self_times = _sibling("trace_reduce").self_times
    total = 0.0
    for plane in trace.ops.values():
        keep = {}
        for o in plane:
            keep.setdefault(o.instr, wanted(o))
        by_name = self_times([(o.instr, o.start, o.end) for o in plane])
        total += sum(ns for instr, ns in by_name.items() if keep[instr])
    return total


def ops_of_spans(trace: Trace, spans: List[Span]) -> List[List[Op]]:
    """For each span (all of one name and thread), the device operations
    that started from its start on and before the next one's start."""
    ops = sorted(all_ops(trace), key=lambda o: o.start)
    starts = [o.start for o in ops]
    spans = sorted(spans, key=lambda s: s.start)
    out = []
    for i, span in enumerate(spans):
        limit = spans[i + 1].start if i + 1 < len(spans) else span.end
        out.append(ops[bisect.bisect_left(starts, span.start):
                       bisect.bisect_left(starts, limit)])
    return out


def host_ns_per_span(trace: Trace, spans: List[Span]) -> List[float]:
    """Per span: its duration less the device-busy time of its own
    operations. A span with no device operation counts in full."""
    spans = sorted(spans, key=lambda s: s.start)
    return [(s.end - s.start) - busy_ns(own)
            for s, own in zip(spans, ops_of_spans(trace, spans))]


# -- idle seconds by span ----------------------------------------------------

def _innermost_segments(spans: List[Span]) -> List[Tuple[float, float, str]]:
    """One thread's nested spans as disjoint (start, end, innermost name),
    sorted."""
    out, stack, cur = [], [], 0.0

    def emit(until: float) -> None:
        if until > cur:
            out.append((cur, until, stack[-1].name))

    for span in sorted(spans, key=lambda s: (s.start, -s.end)):
        while stack and stack[-1].end <= span.start:
            emit(stack[-1].end)
            cur = max(cur, stack.pop().end)
        if stack:
            emit(span.start)
        stack.append(span)
        cur = span.start
    while stack:
        emit(stack[-1].end)
        cur = max(cur, stack.pop().end)
    return out


def _overlap(segments, gaps: List[Interval]):
    """(name, overlap ns) of sorted disjoint ``segments`` with sorted
    disjoint ``gaps``, and the parts of the gaps nothing covered."""
    got: Dict[str, float] = {}
    left: List[Interval] = []
    starts = [s[0] for s in segments]
    for g0, g1 in gaps:
        cur = g0
        i = max(0, bisect.bisect_right(starts, g0) - 1)
        while i < len(segments) and segments[i][0] < g1:
            s0, s1, name = segments[i]
            a, b = max(s0, cur), min(s1, g1)
            if b > a:
                if a > cur:
                    left.append((cur, a))
                got[name] = got.get(name, 0.0) + (b - a)
                cur = b
            i += 1
        if g1 > cur:
            left.append((cur, g1))
    return got, left


def idle_by_span(trace: Trace) -> Dict[str, float]:
    """Seconds of the stretch in which no operation ran on the device, by
    the innermost ``pio:`` span open at the time. The threads that drive the
    device (those holding ``pio:batch.*`` / ``pio:train.*`` spans) are asked
    first; what they leave uncovered goes to the other threads' spans, and
    to a thread that merely waits (``pio:serve.wait``) only what is then
    still left: nobody at work, one thread handing over to another. What no
    span covers of the gap that reaches the stretch's end is not idle time:
    the operations in flight there were not recorded."""
    trace_reduce = _sibling("trace_reduce")
    t0, t1 = trace.window
    busy = trace_reduce.union([(max(o.start, t0), min(o.end, t1))
                               for o in all_ops(trace)])
    gaps = trace_reduce.gaps(busy, t0, t1)
    by_line: Dict[int, List[Span]] = {}
    for s in trace.spans:
        by_line.setdefault(s.line, []).append(s)

    def drives(spans):
        return any(s.name.startswith(("pio:batch.", "pio:train."))
                   for s in spans)

    working, waiting = [], []
    for spans in sorted(by_line.values(), key=lambda sp: not drives(sp)):
        segments = _innermost_segments(spans)
        working.append([g for g in segments if g[2] not in WAITING])
        waiting.append([g for g in segments if g[2] in WAITING])
    out: Dict[str, float] = {}

    def attribute(gaps: List[Interval], rest_name: str) -> None:
        for segments in working + waiting:
            got, gaps = _overlap(segments, gaps)
            for name, ns in got.items():
                out[name] = out.get(name, 0.0) + ns / 1e9
        rest = sum(e - s for s, e in gaps) / 1e9
        if rest > 0:
            out[rest_name] = rest

    tail = [gaps.pop()] if busy and gaps and gaps[-1][1] >= t1 else []
    attribute(gaps, NO_SPAN)
    attribute(tail, UNRECORDED_TAIL)
    return out


def report_lines(trace: Trace) -> List[str]:
    idle = idle_by_span(trace)
    total = sum(idle.values())
    window_s = (trace.window[1] - trace.window[0]) / 1e9
    lines = [f"program spans: {len(trace.spans)} pio: spans, "
             f"{sum(len(v) for v in trace.ops.values())} device operations "
             f"in a stretch of {window_s:.4f} s; idle {total:.6f} s"]
    for name, s in sorted(idle.items(), key=lambda kv: -kv[1]):
        share = 100.0 * s / total if total > 0 else 0.0
        lines.append(f"idle by pio: span: {name} {s:.6f} s {share:.2f}%")
    low, high = trace.clock
    lines.append(f"device clock minus host clock: between {low / 1e6:.3f} "
                 f"and {high / 1e6:.3f} ms; {0.5 * (low + high) / 1e6:.3f} "
                 f"taken off the device's times")
    return lines
