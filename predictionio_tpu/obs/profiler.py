"""On-demand JAX/XLA profiling: capture windows + device-time breakdown.

The Spark-era literature found its wins by profiling the actual
runtime (arxiv 1612.01437); the TPU rebuild's equivalent is the JAX
profiler's xplane trace. This module makes it first-party:

  - ``capture(seconds)`` records a profiling window of the LIVE process
    (serving or training) and returns the artifact directory — wired to
    ``POST /admin/profile?seconds=N`` on every PIO server
    (serving/http.py) and ``pio profile``. On a CPU backend there is no
    device timeline worth the overhead: ``available()`` is False and
    the endpoint answers a clean 501 (``PIO_PROFILE_FORCE=1`` overrides
    for tests).
  - ``parse_xplane(dir)`` reads the trace with ``jax.profiler
    .ProfileData`` alone: device time as the union of the intervals in
    which an operation ran, each group's self time (the program's
    ``named_scope``s and kernel names, resolved through the
    ``scope_maps.json`` a capture leaves beside its trace), and the
    idle seconds by the program's own ``pio:`` spans — shared by
    workflow/train.py's post-train breakdown and ``python -m
    predictionio_tpu.obs.profiler <dir>`` (one JSON line).

Artifacts land under ``PIO_PROFILE_DIR`` (default: a fresh temp dir per
capture) and open with TensorBoard or xprof.
"""

from __future__ import annotations

import bisect
import json
import logging
import os
import tempfile
import threading
import time
from typing import Any, Dict, Optional

log = logging.getLogger(__name__)


class ProfilerUnavailable(RuntimeError):
    """No profilable device backend (or jax missing entirely)."""


class ProfilerBusy(RuntimeError):
    """A capture window is already open (jax allows one at a time)."""


_capture_lock = threading.Lock()


def backend() -> str:
    """The active jax backend name, or 'none' when jax is unavailable."""
    try:
        import jax

        return jax.default_backend()
    except Exception as e:  # noqa: BLE001 — probing must not raise
        log.debug("jax backend probe failed: %s", e)
        return "none"


def available() -> bool:
    """Whether a capture would record a device timeline worth having.
    CPU tier-1 runs answer False (the endpoint no-ops with 501);
    ``PIO_PROFILE_FORCE=1`` forces True so tests can drive the full
    capture path on CPU."""
    if os.environ.get("PIO_PROFILE_FORCE") == "1":
        return True
    return backend() not in ("cpu", "none")


def clamp_seconds(seconds: float) -> float:
    """The EFFECTIVE capture window for a requested length (bounds a
    typo'd N at 5 minutes). Callers that report the window to an
    operator must echo this value, not the request."""
    seconds = float(seconds)
    if not seconds >= 0.0:  # negatives AND NaN ("nan" parses as float)
        return 0.0
    return min(seconds, 300.0)


def capture(seconds: float, out_dir: Optional[str] = None) -> str:
    """Record a profiling window of this process; returns the artifact
    directory. Raises ProfilerUnavailable on CPU/no-jax and
    ProfilerBusy when a window is already open — including one this
    module did not start (a ``PIO_PROFILE_DIR`` train capture holds no
    lock here, but jax refuses the second start_trace)."""
    if not available():
        raise ProfilerUnavailable(
            f"jax profiler needs a device backend (active: {backend()}); "
            "no-op on CPU")
    seconds = clamp_seconds(seconds)
    if not _capture_lock.acquire(blocking=False):
        raise ProfilerBusy("a profiler capture is already running")
    try:
        import jax

        path = (out_dir or os.environ.get("PIO_PROFILE_DIR")
                or tempfile.mkdtemp(prefix="pio_profile_"))
        os.makedirs(path, exist_ok=True)
        try:
            jax.profiler.start_trace(path)
        except Exception as e:  # noqa: BLE001 — map to the busy answer
            raise ProfilerBusy(
                f"profiler could not start (a capture started elsewhere "
                f"— e.g. a PIO_PROFILE_DIR train — may be in progress): "
                f"{e}") from e
        try:
            time.sleep(seconds)
        finally:
            jax.profiler.stop_trace()
        save_scope_maps(path)
        log.info("profiler capture of %.1fs written to %s", seconds, path)
        return path
    finally:
        _capture_lock.release()


def trace_capture(out_dir: str):
    """``with trace_capture(dir):`` — the block runs under the JAX
    profiler; start/stop failures are logged, never raised (profiling
    must not change whether training runs). Returns a context manager
    whose ``__exit__`` reports whether the capture actually recorded."""
    import contextlib

    @contextlib.contextmanager
    def _cm():
        started = False
        try:
            import jax

            jax.profiler.start_trace(out_dir)
            started = True
            log.info("profiling to %s", out_dir)
        except Exception:  # noqa: BLE001 — observability is optional
            log.exception("profiler failed to start; continuing without")
        try:
            yield started
        finally:
            if started:
                try:
                    import jax

                    jax.profiler.stop_trace()
                    save_scope_maps(out_dir)
                except Exception:  # noqa: BLE001
                    log.exception("profiler failed to stop")

    return _cm()


# -- xplane decoding ----------------------------------------------------------

#: the program's spans on the profiler's host plane (obs/trace.device_span)
SPAN_PREFIX = "pio:"
#: the file a capture leaves beside its trace: jaxmon.SCOPE_MAPS as JSON
SCOPE_MAPS_FILE = "scope_maps.json"
#: a thread in this span only waits for another thread's work
_WAITING_SPANS = ("pio:serve.wait",)
NO_SPAN = "(no pio: span)"


def save_scope_maps(profile_dir: str) -> None:
    """Leave this process's instruction -> scope maps beside a captured
    trace: the parse runs in another process, and on a device trace an
    event carries its instruction's name but not its ``named_scope``."""
    from predictionio_tpu.obs import jaxmon

    if not jaxmon.SCOPE_MAPS:
        return
    try:
        with open(os.path.join(profile_dir, SCOPE_MAPS_FILE), "w") as f:
            json.dump(jaxmon.SCOPE_MAPS, f)
    except OSError as e:
        log.warning("could not write %s/%s: %s", profile_dir,
                    SCOPE_MAPS_FILE, e)


def _union(intervals):
    """Sorted, disjoint cover of ``intervals``."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _self_times(events):
    """[(key, self ns)] of properly nested (key, start, end) events: an
    event's duration less the part its children cover, so a ``while``
    and the operations inside it are not counted twice."""
    out, stack = [], []            # stack: [key, start, end, child_ns]
    for key, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][2] <= s:
            k, s0, e0, child = stack.pop()
            out.append((k, max(0.0, (e0 - s0) - child)))
        if stack:
            stack[-1][3] += min(e, stack[-1][2]) - s
        stack.append([key, s, e, 0.0])
    for k, s0, e0, child in stack:
        out.append((k, max(0.0, (e0 - s0) - child)))
    return out


def _innermost(spans):
    """One thread's nested (name, start, end) spans as disjoint
    (start, end, innermost name) segments."""
    out, stack, cur = [], [], 0.0
    for name, s, e in sorted(spans, key=lambda sp: (sp[1], -sp[2])):
        while stack and stack[-1][2] <= s:
            if stack[-1][2] > cur:
                out.append((cur, stack[-1][2], stack[-1][0]))
            cur = max(cur, stack.pop()[2])
        if stack and s > cur:
            out.append((cur, s, stack[-1][0]))
        stack.append((name, s, e))
        cur = s
    while stack:
        if stack[-1][2] > cur:
            out.append((cur, stack[-1][2], stack[-1][0]))
        cur = max(cur, stack.pop()[2])
    return out


def _idle_by_span(gaps, lines):
    """Seconds of ``gaps`` (sorted, disjoint) by the innermost ``pio:``
    span open at the time. Threads that drive the device (they hold
    ``pio:batch.*`` / ``pio:train.*`` spans) are asked first; what they
    leave uncovered goes to the other threads' spans, and to a thread
    that merely waits only what is then still left (nobody at work: one
    thread handing over to another)."""
    def drives(spans):
        return any(n.startswith(("pio:batch.", "pio:train."))
                   for n, _, _ in spans)

    out: Dict[str, float] = {}

    def take(segments, gaps):
        left = []
        for g0, g1 in gaps:
            cur = g0
            for s0, s1, name in segments:
                a, b = max(s0, cur), min(s1, g1)
                if b > a:
                    if a > cur:
                        left.append((cur, a))
                    out[name] = out.get(name, 0.0) + (b - a) / 1e9
                    cur = b
            if g1 > cur:
                left.append((cur, g1))
        return left

    waiting = []
    for spans in sorted(lines, key=lambda sp: not drives(sp)):
        segments = _innermost(spans)
        gaps = take([g for g in segments if g[2] not in _WAITING_SPANS],
                    gaps)
        waiting.append([g for g in segments if g[2] in _WAITING_SPANS])
    for segments in waiting:
        gaps = take(segments, gaps)
    rest = sum(e - s for s, e in gaps) / 1e9
    if rest > 0:
        out[NO_SPAN] = rest
    return out


#: spans inside which the device can be given work
_LAUNCHING_SPANS = ("pio:index.search", "pio:train.epoch")


def _clock_offset(execs, launching, reach_us: int = 5000,
                  step_us: int = 20):
    """(low, high) ns of device clock minus host clock. A device event
    carries the device's clock, which runs a millisecond or two off the
    host's (a kernel seems to start before the call that launched it):
    the widest run of shifts, within ``reach_us``, under which least of
    the executed programs' time (``execs``) lies outside every span
    that can have launched it (``launching``). (0, 0) with nothing to
    hold against."""
    cover = _union(launching)
    if not cover or not execs:
        return (0.0, 0.0)
    starts = [c[0] for c in cover]

    def outside(a, b):
        inside, i = 0.0, max(0, bisect.bisect_right(starts, a) - 1)
        while i < len(cover) and cover[i][0] < b:
            inside += max(0.0, min(b, cover[i][1]) - max(a, cover[i][0]))
            i += 1
        return (b - a) - inside

    shifts = [us * 1e3 for us in range(-reach_us, reach_us + 1, step_us)]
    cost = [sum(outside(s - d, e - d) for s, e in execs) for d in shifts]
    least = min(cost) + 1e3
    best, run = (0, -1), None
    for i, c in enumerate(cost + [float("inf")]):
        if c <= least:
            run = i if run is None else run
        elif run is not None:
            if i - 1 - run > best[1] - best[0]:
                best = (run, i - 1)
            run = None
    return (shifts[best[0]], shifts[best[1]])


def group_of(instr: str, scope: Optional[str]) -> str:
    """What an operation is reported under: its instruction's name
    without XLA's number (``topk_dot.1`` -> ``topk_dot``, ``fusion.108``
    -> ``fusion``), under the ``named_scope`` the program traced it in
    where its map knows one (``twotower.adagrad_user/fusion``)."""
    base, _, number = instr.rpartition(".")
    name = base if base and number.isdigit() else instr
    return f"{scope}/{name}" if scope else name


def parse_xplane(profile_dir: str) -> Dict[str, Any]:
    """Parse the newest ``*.xplane.pb`` under ``profile_dir`` into
    MEASURED occupancy numbers, with nothing but ``jax.profiler
    .ProfileData``: the traced stretch, device time as the UNION of the
    intervals in which an operation ran (per chip), each group's SELF
    time (``group_of``: the program's scopes and kernel names), and the
    idle seconds by the program's own ``pio:`` spans. Returns
    ``{"error": ...}`` instead of raising — a failed parse must never
    fail the run that captured the trace."""
    import glob

    try:
        from jax.profiler import ProfileData
    except Exception as e:  # noqa: BLE001 — parser deps are optional
        return {"error": f"xplane parser unavailable: {e}"}
    files = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        return {"error": "no xplane trace found"}
    try:
        data = ProfileData.from_file(sorted(files)[-1])
    except Exception as e:  # noqa: BLE001
        return {"error": f"xplane decode failed: {e}"}
    scope_maps: Dict[str, Dict[str, str]] = {}
    try:
        with open(os.path.join(profile_dir, SCOPE_MAPS_FILE)) as f:
            scope_maps = json.load(f)
    except (OSError, ValueError):
        pass                      # a capture of a program that kept none
    devices, host_lines = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            modules, ops = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules = sorted(
                        (float(e.start_ns), e.name.split("(", 1)[0],
                         float(e.start_ns) + float(e.duration_ns))
                        for e in line.events)
                elif line.name == "XLA Ops":
                    ops = [(e.name.split(" ", 1)[0].lstrip("%"),
                            float(e.start_ns),
                            float(e.start_ns) + float(e.duration_ns))
                           for e in line.events]
            if ops:
                devices.append((modules, ops))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans = [(e.name, float(e.start_ns),
                          float(e.start_ns) + float(e.duration_ns))
                         for e in line.events
                         if e.name.startswith(SPAN_PREFIX)]
                if spans:
                    host_lines.append(spans)
    if not devices:
        return {"error": "no TPU plane with an XLA Ops line in trace"}
    # onto the host's clock: the middle of what the trace allows
    clock = _clock_offset(
        [(m[0], m[2]) for modules, _ in devices for m in modules],
        [(s, e) for spans in host_lines for n, s, e in spans
         if n in _LAUNCHING_SPANS])
    shift = 0.5 * (clock[0] + clock[1])
    devices = [([(s - shift, n, e - shift) for s, n, e in modules],
                [(i, s - shift, e - shift) for i, s, e in ops])
               for modules, ops in devices]
    every = [op for _, ops in devices for op in ops]
    t0 = min(s for _, s, _ in every)
    t1 = max(e for _, _, e in every)
    busy_ns, groups, gaps = 0.0, {}, []
    for modules, ops in devices:
        busy = _union([(s, e) for _, s, e in ops])
        busy_ns += sum(e - s for s, e in busy)
        cur = t0
        for s, e in busy:
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        starts = [m[0] for m in modules]

        def group(instr, start):
            at = bisect.bisect_right(starts, start) - 1
            module = modules[at][1] if at >= 0 else ""
            return group_of(instr, scope_maps.get(module, {}).get(instr))

        for key, ns in _self_times(
                [(group(i, s), s, e) for i, s, e in ops]):
            groups[key] = groups.get(key, 0.0) + ns
    n_dev = len(devices)
    total_self = sum(groups.values()) or 1.0
    top = sorted(groups.items(), key=lambda kv: -kv[1])[:12]
    # gaps of several chips overlap in time: attribute each chip's own
    idle = _idle_by_span(sorted(gaps), host_lines) if n_dev == 1 else {}
    return {
        "devices": n_dev,
        "window_sec": round((t1 - t0) / 1e9, 6),
        "device_time_sec": round(busy_ns / n_dev / 1e9, 6),
        "idle_sec": round(((t1 - t0) - busy_ns / n_dev) / 1e9, 6),
        "by_category": {
            k: {"time_sec": round(v / n_dev / 1e9, 6),
                "time_frac": round(v / total_self, 4)}
            for k, v in top},
        "idle_by_span": {k: round(v, 6) for k, v in sorted(
            idle.items(), key=lambda kv: -kv[1])},
        "device_clock_minus_host_ms": [round(clock[0] / 1e6, 3),
                                       round(clock[1] / 1e6, 3)],
    }


def per_step(parsed: Dict[str, Any], steps: int) -> Optional[Dict[str, Any]]:
    """Per-STEP device-time breakdown from an already-parsed trace that
    covered ``steps`` train steps: device ms/step overall and per group
    (``group_of``) — the number a step-time regression investigation starts
    from. workflow/train.py's post-train log calls it. None when the trace carries no
    device time or ``steps`` is unknown (<= 0) — a whole-train total
    must never masquerade as a per-step number."""
    if not parsed.get("device_time_sec") or steps <= 0:
        return None
    dev = parsed["device_time_sec"]
    return {
        "steps": int(steps),
        "device_ms_per_step": round(dev / steps * 1e3, 4),
        "by_category_ms_per_step": {
            cat: round(v["time_frac"] * dev / steps * 1e3, 4)
            for cat, v in (parsed.get("by_category") or {}).items()
        },
    }


def step_breakdown(profile_dir: str, steps: int) -> Dict[str, Any]:
    """parse_xplane + per_step over a trace directory; the full parsed
    trace rides along under ``trace``."""
    parsed = parse_xplane(profile_dir)
    if "error" in parsed:
        return parsed
    out = per_step(parsed, steps)
    if out is None:
        return {"error": f"no per-step breakdown (steps={steps}, "
                         f"device_time_sec="
                         f"{parsed.get('device_time_sec')})",
                "trace": parsed}
    out["trace"] = parsed
    return out


def main(argv=None) -> int:
    """``python -m predictionio_tpu.obs.profiler <dir> [--steps N]``:
    parse a trace in a clean process, print ONE JSON line."""
    import argparse

    parser = argparse.ArgumentParser(
        description="parse a JAX xplane profile into device-time numbers")
    parser.add_argument("profile_dir")
    parser.add_argument("--steps", type=int, default=0,
                        help="train steps the trace covered (adds the "
                             "per-step breakdown)")
    args = parser.parse_args(argv)
    if args.steps > 0:
        print(json.dumps(step_breakdown(args.profile_dir, args.steps)))
    else:
        print(json.dumps(parse_xplane(args.profile_dir)))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
