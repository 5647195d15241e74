"""The benchmark's one entry point: one cell, one process, one result line.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one traffic mix, one driver,
one model builder, one reference or one per-layer metric is a file of its
own, found here by the name ``BENCHMARK.json`` (or the traffic / config file)
gives it. This file holds no list of cells, metrics or models: adding one is
adding a file under a directory of ``paths`` and an entry in
``BENCHMARK.json`` (benchmarks/README.md).

The process loads, warms up, measures for ``--seconds`` seconds, checks what
the timed path produced against the plain reference, prints one JSON object
as its last line and exits. It fails, printing no result, on a machine whose
JAX finds no TPU or fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.time()  # set-up is counted from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)

#: exit codes other than 0 (no result line is printed with any of them)
EXIT_USAGE, EXIT_NO_DEVICE, EXIT_NO_PROGRAM = 2, 3, 4


def say(*parts) -> None:
    """A line of the run's log. The result is the LAST line of stdout, so
    everything else may go to stdout before it."""
    print(*parts, flush=True)


def load_file(path: str):
    """Import one of the benchmark's files by its path (once)."""
    modname = "_bench_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path, CHECKOUT))
    if modname in sys.modules:
        return sys.modules[modname]
    spec = importlib.util.spec_from_file_location(modname, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[modname] = module
    spec.loader.exec_module(module)
    return module


class Bench:
    """What a driver gets: the cell, its files, and the finders."""

    def __init__(self, root, spec, cell, args):
        self.root = root
        self.spec = spec
        self.cell = cell
        self.name = cell["name"]
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.trace = bool(int(args.trace))
        self.t_start = T_PROCESS_START
        self.paths = list(spec["paths"])
        cfg_entry = next(c for c in spec["configs"]
                         if c["name"] == cell["config"])
        with open(os.path.join(root, cfg_entry["file"])) as f:
            self.config = json.load(f)
        self.traffic = self.load_json("traffic", cell["traffic"])
        self.compiles = CompileCounter()
        #: where the run's seconds went: [(phase, seconds, seconds of them
        #: compiling, compilations)], each phase ending at its ``mark``
        self.phases = []
        self._marked = (T_PROCESS_START, 0.0, 0)
        #: a scratch directory inside the checkout (traces, child output)
        self.scratch = os.path.join(CHECKOUT, ".pio_run", "bench",
                                    self.name)
        os.makedirs(self.scratch, exist_ok=True)

    # -- finding files by name ------------------------------------------
    def find(self, kind: str, name: str, ext: str) -> str:
        # the tree BENCHMARK.json was read from first, then the files
        # that ship beside this one (a tree of new files may use them)
        for base in [os.path.join(self.root, p) for p in self.paths] + [HERE]:
            path = os.path.normpath(os.path.join(base, kind, name + ext))
            if os.path.isfile(path):
                return path
        raise FileNotFoundError(
            f"no {kind}/{name}{ext} under any of paths={self.paths} "
            f"(root {self.root})")

    def load_json(self, kind: str, name: str) -> dict:
        with open(self.find(kind, name, ".json")) as f:
            return json.load(f)

    def load_module(self, kind: str, name: str):
        return load_file(self.find(kind, name, ".py"))

    def lib(self, name: str):
        """A shared module of the benchmark (``seeded``, ``trace_reduce``,
        ...): ``<path>/<name>.py``."""
        return self.load_module(".", name)

    # -- the run's own account ------------------------------------------
    def mark(self, phase: str) -> None:
        """The phase that ends now (it began at the last mark, the first at
        the process's start). For the ``# run:`` line only: no metric reads
        it."""
        t, seconds, count = self._marked
        self._marked = (time.time(), self.compiles.seconds,
                        self.compiles.count)
        self.phases.append((phase, self._marked[0] - t,
                            self._marked[1] - seconds,
                            self._marked[2] - count))

    def account(self) -> str:
        """``# run:``'s line: every marked phase, what it spent compiling
        apart, then what is left and the whole process."""
        self.mark("rest")
        parts = [f"{name} {s:.1f}" + (f" ({c:.1f} compiling, {n} programs)"
                                      if n else "")
                 for name, s, c, n in self.phases
                 if name != "rest" or s > 0.05]
        return ("run: seconds by phase: " + ", ".join(parts)
                + f"; whole process {time.time() - T_PROCESS_START:.1f}")

    # -- metrics --------------------------------------------------------
    def metrics_for(self, group: str):
        """The entries of ``end_to_end`` / ``per_layer`` this cell reports:
        those that list it under ``workloads``, and those with no such key
        (which every cell that reports the moved metric must report)."""
        e2e_here = {m["name"] for m in self.spec["end_to_end"]
                    if "workloads" not in m or self.name in m["workloads"]}
        out = []
        for m in self.spec[group]:
            if "workloads" in m:
                if self.name in m["workloads"]:
                    out.append(m)
            elif group == "end_to_end" or m["moves"] in e2e_here:
                out.append(m)
        return out

    def memory_peak(self) -> int:
        """Peak bytes in use on the fullest chip, as the backend reports."""
        peak = 0
        for d in self.devices:
            stats = d.memory_stats() or {}
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        return peak

    def read_layer_metrics(self, layer_ctx: dict) -> dict:
        """Call each of this cell's per-layer readers
        (``layer_metrics/<name>.py: read(ctx)``). A reader that finds
        nothing to read returns None and is left out of the line."""
        out = {}
        for m in self.metrics_for("per_layer"):
            try:
                reader = self.load_module("layer_metrics", m["name"])
            except FileNotFoundError as e:
                say(f"# per-layer metric {m['name']}: no reader ({e})")
                continue
            value = reader.read(layer_ctx)
            if value is not None:
                out[m["name"]] = {"value": float(value), "unit": m["unit"]}
        return out


class CompileCounter:
    """Counts XLA compilations (cache hits included: a program the window
    had to ask for is a shape that set-up did not warm)."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0        # the backend's own, for the account
        self._installed = False

    def install(self) -> None:
        if self._installed:
            return
        from jax import monitoring

        def on_duration(event, duration, **kw):
            if event.endswith("backend_compile_duration"):
                self.count += 1
                self.seconds += float(duration)

        monitoring.register_event_duration_secs_listener(on_duration)
        self._installed = True


def device_report(chips: int, allow_cpu: bool):
    """The devices JAX found, or exit: a measurement path that finds no
    chip fails, it does not fall back to the CPU."""
    import jax

    devices = jax.devices()
    d0 = devices[0]
    found = (f"platform={d0.platform} kind={d0.device_kind!r} "
             f"count={len(devices)}")
    say(f"# device: {found}")
    if d0.platform != "tpu" and not allow_cpu:
        sys.stderr.write(
            f"benchmarks/run.py: JAX found no TPU ({found}); a benchmark "
            "number comes only from the chip\n")
        sys.exit(EXIT_NO_DEVICE)
    if len(devices) < chips:
        sys.stderr.write(
            f"benchmarks/run.py: the cell asks for {chips} chips, JAX "
            f"found {found}\n")
        sys.exit(EXIT_NO_DEVICE)
    if d0.platform == "tpu":
        # a chip the table of peaks does not know is an error
        peaks = load_file(os.path.join(HERE, "peaks.py")).peaks_for(
            d0.device_kind)
        say(f"# peaks: {peaks['bf16_flops_per_s']:.3g} FLOP/s bf16, "
            f"{peaks['hbm_bytes_per_s']:.3g} B/s, {peaks['hbm_bytes']:.3g} B")
    return devices[:chips]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # rehearsal only, never in BENCHMARK.json: another tree of benchmark
    # files (tests build one of new files only), and the CPU backend
    ap.add_argument("--bench-root", default=CHECKOUT)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    root = os.path.abspath(args.bench_root)
    try:
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            spec = json.load(f)
    except OSError as e:
        sys.stderr.write(f"benchmarks/run.py: {e}\n")
        return EXIT_USAGE
    cell = next((w for w in spec["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        sys.stderr.write(
            f"benchmarks/run.py: no workload {args.workload!r} in "
            f"{root}/BENCHMARK.json\n")
        return EXIT_USAGE

    # the system under test; a directory that holds only the benchmark's
    # files has none, and the run ends here with no result
    sys.path.insert(0, CHECKOUT)
    try:
        from predictionio_tpu.parallel.compile_cache import (
            enable_persistent_cache)
    except ImportError as e:
        sys.stderr.write(
            f"benchmarks/run.py: the program is not in this checkout "
            f"({e})\n")
        return EXIT_NO_PROGRAM

    if args.rehearse_cpu:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    # JAX_COMPILATION_CACHE_DIR where set, else the fixed
    # <checkout>/.pio_run/compile_cache (the program's own rule)
    cache_dir = enable_persistent_cache()
    say(f"# compile cache: {cache_dir}")
    devices = device_report(int(cell["chips"]), args.rehearse_cpu)

    bench = Bench(root, spec, cell, args)
    bench.devices = devices
    bench.compiles.install()
    driver = bench.load_module("drivers", bench.traffic["driver"])
    result = driver.run(bench)

    # every number compared, beside its limit
    correct = True
    for check in result["checks"]:
        ok = bool(check["ok"])
        correct = correct and ok
        say(f"# check {check['name']}: value={check['value']!r} "
            f"limit={check['limit']!r} {'ok' if ok else 'FAILED'}")
    say(f"# compilations inside the window: {result['window_compiles']}")
    for line in result.get("notes", ()):
        say(f"# {line}")
    say(f"# {bench.account()}")

    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices),
              "memory_peak_bytes": result["memory_peak_bytes"]}
    units = {m["name"]: m["unit"] for m in spec["end_to_end"]}
    traced = result.get("traced") or {}
    if bench.trace:
        metrics = result["layer_metrics"]
        device["busy_s"] = traced.get("busy_s", 0.0)
        device["window_s"] = traced.get("window_s", 0.0)
    else:
        wanted = [m["name"] for m in bench.metrics_for("end_to_end")]
        metrics = {name: {"value": float(result["end_to_end"][name]),
                          "unit": units[name]} for name in wanted}
    line = {"correct": bool(correct and result["failed"] == 0),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]),
            "metrics": metrics, "device": device}
    if bench.trace and traced:
        line["breakdown"] = {"device_ops": traced["device_ops"],
                             "idle_gaps": traced["idle_gaps"]}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # server threads of the system under test are daemons; nothing of
    # this run may outlive it
    os._exit(code)
