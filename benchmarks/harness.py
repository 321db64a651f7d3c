"""One run of one cell: set-up, measured window, check, one result line.

Driven by data.  ``BENCHMARK.json`` names the cell's configuration file
and traffic mix; the configuration names its runner
(``benchmarks/runners/<runner>.py``); every per-layer metric has a
file of its own (``benchmarks/layer_metrics/<metric>.json``) that
names a reader (``benchmarks/readers/<reader>.py``) and its
parameters.  A new cell, scale, root count, client count or scope
metric is new files and new entries in ``BENCHMARK.json``; no file
that is here needs an edit (``benchmarks/README.md``).

From the program the harness takes the system under test through the
functions ``lux_tpu/cli.py`` and ``serve.main`` call themselves, its
named scopes and its telemetry events.  Clock, generators, references,
peaks and the trace reduction are the benchmark's own.
"""

from __future__ import annotations

import argparse
import contextlib
import glob
import importlib
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE = os.path.join(HERE, ".cache")
# how much of the window a --trace 1 run traces: long enough for whole
# solves and some tens of serving boundaries, short enough that the
# profiler's buffers and the file stay small
TRACE_SECONDS = 8.0

clock = time.perf_counter


class BenchmarkError(Exception):
    """A run that cannot produce a result line (exit code 2)."""


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_benchmark() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def cell_of(bench: dict, workload: str):
    """(cell entry, configuration file contents, traffic parameters)."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchmarkError(f"unknown workload {workload!r}; "
                             f"BENCHMARK.json has {sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(os.path.join(ROOT, entry["file"]))
    traffic = load_json(os.path.join(HERE, "traffic",
                                     cell["traffic"] + ".json"))
    return cell, config, traffic


def metrics_for(bench: dict, group: str, workload: str) -> list:
    """The entries of ``end_to_end`` / ``per_layer`` this cell reports:
    those without a ``workloads`` key, and those that list it."""
    return [m for m in bench[group]
            if "workloads" not in m or workload in m["workloads"]]


def device_peaks(kind: str) -> dict:
    peaks = load_json(os.path.join(HERE, "peaks.json"))
    if kind not in peaks or kind.startswith("_"):
        raise BenchmarkError(
            f"device kind {kind!r} is not in benchmarks/peaks.json; a "
            f"device without published peaks is an error, not a default")
    return peaks[kind]


class Run:
    """What one run knows and gathers; handed to runner and readers."""

    def __init__(self, workload, cell, config, traffic, seed, seconds,
                 trace, rehearsal, t_process):
        self.workload = workload
        self.cell = cell
        self.config = dict(config)
        if rehearsal:
            # the configuration's own small stand-in sizes
            self.config.update(config.get("rehearsal", {}))
        self.traffic = traffic
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.rehearsal = bool(rehearsal)
        self.chips = int(cell["chips"])
        self.t_process = t_process
        self.spans = []            # (name, start, end) on ``clock``
        self.counters = {}         # runner's counts and seconds
        self.events = []           # telemetry events the observer saw
        self.checks = []           # (name, value, limit, ok)
        self.attempted = 0
        self.failed = 0
        self.metrics = {}          # end-to-end values from the runner
        self.graph = {}            # nv, stored_edges, generated_edges
        self.peaks = None
        self.trace_summary = None
        self.trace_window_s = None
        self.t_window = None       # set by begin_window()
        self._tracing = False
        self._trace_dir = None
        self._trace_t0 = None

    def begin_window(self) -> float:
        """The runner calls this at the instant measurement starts:
        set-up ends here, and a traced run starts its trace here."""
        self.trace_begin()
        self.t_window = clock()
        print(f"setup_s = {self.t_window - self.t_process:.3f} "
              f"(load_layout {self.span_seconds('load_layout'):.2f}, "
              f"engine_build {self.span_seconds('engine_build'):.2f}, "
              f"compile_warm {self.span_seconds('compile_warm'):.2f})",
              flush=True)
        return self.t_window

    # -- spans ---------------------------------------------------------

    @contextlib.contextmanager
    def span(self, name: str):
        """A host span on the benchmark's clock; inside a traced
        window it is also written into the profiler's trace."""
        import jax
        t0 = clock()
        with jax.profiler.TraceAnnotation("bench:" + name):
            try:
                yield
            finally:
                self.spans.append((name, t0, clock()))

    def span_seconds(self, name: str) -> float:
        return sum(e - s for n, s, e in self.spans if n == name)

    # -- checks --------------------------------------------------------

    def check(self, name: str, value, limit) -> bool:
        """One number compared beside its limit; printed in every
        run.  NaN fails."""
        ok = bool(value <= limit)
        self.checks.append((name, value, limit, ok))
        print(f"check {name} = {value!r} limit {limit!r} "
              f"{'ok' if ok else 'FAILED'}", flush=True)
        return ok

    # -- tracing -------------------------------------------------------

    def trace_begin(self):
        if not self.trace:
            return
        import jax
        self._trace_dir = os.path.join(
            CACHE, "trace", self.workload)
        shutil.rmtree(self._trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self._trace_dir, profiler_options=opts)
        self._tracing = True
        self._trace_t0 = clock()

    def trace_tick(self, force: bool = False) -> bool:
        """Called by the runner between operations; closes the traced
        part once it is long enough.  True while tracing goes on."""
        if not self._tracing:
            return False
        if force or clock() - self._trace_t0 >= TRACE_SECONDS:
            import jax
            self.trace_window_s = clock() - self._trace_t0
            jax.profiler.stop_trace()
            self._tracing = False
        return self._tracing

    def trace_reduce(self):
        if self._trace_dir is None:
            return
        from benchmarks import trace_reduce
        paths = glob.glob(os.path.join(self._trace_dir, "**",
                                       "*.xplane.pb"), recursive=True)
        if not paths:
            raise BenchmarkError("the profiler wrote no .xplane.pb")
        self.trace_summary = trace_reduce.reduce_file(paths[0])
        keep = os.environ.get("BENCH_KEEP_TRACE")
        if keep:
            os.makedirs(keep, exist_ok=True)
            shutil.copy(paths[0], os.path.join(
                keep, f"{self.workload}.xplane.pb"))
        shutil.rmtree(self._trace_dir, ignore_errors=True)


def _observer(run: Run):
    keep = {"query_done", "serve_refill", "segment", "query_start"}

    def on_event(ev):
        if ev.get("kind") in keep:
            ev = dict(ev)
            ev["clock"] = clock()
            run.events.append(ev)
    return on_event


def _devices(run: Run):
    import jax
    devs = jax.devices()
    platform = devs[0].platform
    if not run.rehearsal:
        if platform != "tpu":
            raise BenchmarkError(
                f"platform is {platform!r}, not 'tpu': this benchmark "
                f"measures the chip and has no fallback")
        if len(devs) < run.chips:
            raise BenchmarkError(
                f"cell {run.workload} needs {run.chips} chip(s), jax "
                f"found {len(devs)}")
        run.peaks = device_peaks(devs[0].device_kind)
    return devs


def _memory_peaks(devs, chips):
    peaks = []
    for d in devs[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return peaks


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             rehearsal: bool = False, t_process: float | None = None):
    """Drive one run; returns the result object of the contract's last
    line (the caller prints it)."""
    t_process = clock() if t_process is None else t_process
    bench = load_benchmark()
    cell, config, traffic = cell_of(bench, workload)
    run = Run(workload, cell, config, traffic, seed, seconds, trace,
              rehearsal, t_process)

    import jax
    from lux_tpu import runtime, telemetry
    runtime.use_compile_cache()
    # every program goes to the cache, also those that compile fast:
    # a second run of the cell then compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devs = _devices(run)

    runner = importlib.import_module(
        f"benchmarks.runners.{run.config['runner']}")
    observer = _observer(run)
    telemetry.add_observer(observer)
    try:
        state = runner.prepare(run)
        runner.window(run, state)      # calls run.begin_window()
        if run.t_window is None:
            raise BenchmarkError("the runner never began its window")
        setup_s = run.t_window - t_process
        run.trace_tick(force=True)
        mem = _memory_peaks(devs, run.chips)
        with run.span("check"):
            runner.verify(run, state)
    finally:
        telemetry.remove_observer(observer)
        run.trace_tick(force=True)      # no-op unless a failure left it on
    run.trace_reduce()

    values = dict(run.metrics)
    values["setup_s"] = setup_s
    if mem and run.graph.get("stored_edges"):
        values["hbm_bytes_per_edge"] = sum(mem) / run.graph["stored_edges"]
    out = {}
    if trace:
        for m in metrics_for(bench, "per_layer", workload):
            spec = load_json(os.path.join(HERE, "layer_metrics",
                                          m["name"] + ".json"))
            reader = importlib.import_module(
                f"benchmarks.readers.{spec['reader']}")
            value = reader.read(spec, run)
            if value is not None:
                out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        for m in metrics_for(bench, "end_to_end", workload):
            if m["name"] in values:
                out[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}
    correct = (run.failed == 0 and run.attempted > 0
               and bool(run.checks) and all(c[3] for c in run.checks))
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": max(mem) if mem else None}
    result = {"correct": correct, "attempted": run.attempted,
              "failed": run.failed, "metrics": out, "device": device}
    if trace and run.trace_summary is not None:
        device["busy_s"] = run.trace_summary.busy_s
        device["window_s"] = run.trace_window_s
        result["breakdown"] = {
            "device_ops": run.trace_summary.top_ops(10),
            "idle_gaps": run.trace_summary.top_gaps(10)}
    return result


def parse_args(argv):
    ap = argparse.ArgumentParser(prog="benchmarks/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, t_process: float | None = None) -> int:
    args = parse_args(sys.argv[1:] if argv is None else argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_process=t_process)
    except BenchmarkError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0
