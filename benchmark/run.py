"""Run one benchmark cell once and print the contract's result line.

    python3 -m benchmark.run --workload <config>.<traffic> --seed <n> \
        --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix, driver, reader or
metric is a file of its own, found by the name `BENCHMARK.json` gives it
(README.md).  This file only wires them together.
"""

from __future__ import annotations

import time

_T_PROCESS = time.perf_counter()  # set-up is counted from here

import argparse
import contextlib
import gc
import glob
import importlib
import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(REPO, "benchmark")
TRACE_SECONDS = 5.0  # a traced run measures this much, traced end to end


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


class Run:
    """What a driver is handed, and what it hands back to the readers."""

    def __init__(self, bench, cell, seed, seconds, trace, control=None):
        self.cell = cell
        self.chips = int(cell["chips"])
        self.seed = int(seed)
        self.trace = bool(trace)
        self.seconds = min(float(seconds), TRACE_SECONDS) if trace else float(seconds)
        entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
        self.config = load_json(REPO, entry["file"])
        self.traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
        self.control = None
        if control:
            self.control = self.config["controls"][control]
            self.config.update(self.control.get("overrides", {}))
        self.work_dir = os.path.join(REPO, ".benchwork", cell["name"])
        shutil.rmtree(self.work_dir, ignore_errors=True)
        os.makedirs(self.work_dir)
        self.t_process = _T_PROCESS
        self.clock = {}      # host-clock spans, seconds
        self.series = {}     # raw samples: name -> 1-D array
        self.counts = {}     # e.g. iterations or frames inside the window
        self.checks = []     # every number compared, beside its limit
        self.attempted = 0
        self.failed = 0
        self.devices = []
        self.held_at_close = []
        self.snap_before = self.snap_after = None
        self.window = None   # (start, end), perf_counter
        self.trace_path = None
        self._reduced = None

    def load(self, *parts):
        """A data file of the benchmark, by its path under benchmark/."""
        return load_json(HERE, *parts)

    def apply_patches(self):
        """A control's `patch` switches on the program's own lower-precision
        path: module attributes set before the program traces anything."""
        for dotted, value in (self.control or {}).get("patch", {}).items():
            module, attr = dotted.rsplit(".", 1)
            setattr(importlib.import_module(module), attr, value)

    @contextlib.contextmanager
    def span(self, name):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.clock[name] = self.clock.get(name, 0.0) + time.perf_counter() - t0

    def acquire(self):
        """The program's device rule: raises where jax found no accelerator
        and JAX_PLATFORMS=cpu was not asked for."""
        from flink_ms_tpu.parallel.mesh import acquire_devices

        with self.span("backend_init_s"):
            devices = acquire_devices()
        if len(devices) < self.chips:
            raise RuntimeError(
                f"cell {self.cell['name']} needs {self.chips} chip(s), "
                f"jax reports {len(devices)}")
        self.devices = devices[:self.chips]
        return self.devices

    def snapshot(self):
        from flink_ms_tpu.obs import metrics as obs_metrics

        return obs_metrics.get_registry().snapshot()

    def counter(self, name, at_open=False):
        """A registry counter's value when the window closed (or opened)."""
        snap = self.snap_before if at_open else self.snap_after
        return next((c["value"] for c in snap["counters"]
                     if c["name"] == name and not c["labels"]), None)

    def hist_delta(self, name):
        """(sum, count) a registry histogram gained over the window."""
        def at(snap):
            return next(((h["sum"], h["count"]) for h in snap["histograms"]
                         if h["name"] == name and not h["labels"]), (0.0, 0))

        (s0, n0), (s1, n1) = at(self.snap_before), at(self.snap_after)
        return s1 - s0, n1 - n0

    def start_trace(self):
        """Last act of set-up (a traced run also opens its trace here)."""
        # a full collection now, so that none is owed inside the window: with
        # a 16.7M-entry id dict alive, one costs the server a quarter second
        gc.collect()
        if not self.trace:
            return
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # host TraceMes only: small and cheap
        jax.profiler.start_trace(os.path.join(self.work_dir, "trace"),
                                 profiler_options=opts)

    def begin_window(self, at=None):
        """Set-up ends here.  `at` is a perf_counter instant already agreed
        with a load generator; without it the window starts now."""
        self.snap_before = self.snapshot()
        start = time.perf_counter() if at is None else at
        self.clock["setup_s"] = start - self.t_process
        self.window = (start, None)
        if self.trace:
            import jax

            from benchmark import trace_reduce

            self._annotation = jax.profiler.TraceAnnotation(trace_reduce.WINDOW)
            self._annotation.__enter__()

    def end_window(self, at=None):
        end = time.perf_counter() if at is None else at
        self.window = (self.window[0], end)
        self.snap_after = self.snapshot()
        self.held_at_close = [
            int(s.get("bytes_in_use", 0)) + int(s.get("bytes_reserved", 0))
            for s in ((d.memory_stats() or {}) for d in self.devices)]
        if self.trace:
            import jax

            self._annotation.__exit__(None, None, None)
            jax.profiler.stop_trace()
            found = glob.glob(os.path.join(
                self.work_dir, "trace", "plugins", "profile", "*", "*.xplane.pb"))
            self.trace_path = found[0] if found else None

    def reduced_trace(self):
        if self._reduced is None and self.trace_path:
            from benchmark import trace_reduce

            self._reduced = trace_reduce.reduce_file(
                self.trace_path, self.window[1] - self.window[0])
        return self._reduced

    def check(self, name, value, limit, at_least=False):
        ok = bool(value >= limit) if at_least else bool(value <= limit)
        self.checks.append({"name": name, "value": float(value),
                            "limit": float(limit),
                            "rule": ">=" if at_least else "<=", "ok": ok})
        print(f"[check] {name} = {value:.6g} (must be "
              f"{'>=' if at_least else '<='} {limit:g}) "
              f"{'ok' if ok else 'FAILED'}", file=sys.stderr, flush=True)


def find_cell(bench, workload):
    for cell in bench["workloads"]:
        if cell["name"] == workload:
            return cell
    raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")


def metrics_of(bench, cell_name, kind):
    """The metrics of one kind that this cell reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell_name in m["workloads"]]


def read_metrics(run, specs):
    out = {}
    for spec in specs:
        meta = load_json(HERE, "metrics", spec["name"] + ".json")
        reader = importlib.import_module("benchmark.readers." + meta["reader"])
        got = reader.read(run, **meta.get("args", {}))
        if got is None:
            continue  # nothing to read in this run: the metric is left out
        value, extra = got if isinstance(got, tuple) else (got, {})
        out[spec["name"]] = {"value": float(value), "unit": spec["unit"], **extra}
    return out


def device_info(run):
    dev = run.devices[0]
    peak = 0
    for d, held in zip(run.devices, run.held_at_close):
        # the allocator's peak leaves out the scratch the runtime reserves
        # for a program's temporaries, which it keeps between calls: what
        # was held when the window closed counts too
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)), held)
        print(f"[memory] {d}: {stats}, held at close {held}",
              file=sys.stderr, flush=True)
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(run.devices), "memory_peak_bytes": peak}
    red = run.reduced_trace()
    if red:
        info["busy_s"] = red["busy_s"]
        info["window_s"] = red["window_s"]
    return info


def run_cell(bench, workload, seed, seconds, trace, control=None):
    cell = find_cell(bench, workload)
    run = Run(bench, cell, seed, seconds, trace, control)
    driver = importlib.import_module("benchmark.drivers." + run.traffic["driver"])
    driver.run(run)
    secs, compiled = (
        (run.counter(name) or 0) - (run.counter(name, at_open=True) or 0)
        for name in ("tpums_jax_compile_seconds_total",
                     "tpums_jax_compile_cache_misses_total"))
    run.check("compile_seconds_in_window", secs, 0.0)
    run.check("compile_cache_misses_in_window", compiled, 0)
    run.check("failed_operations", run.failed, 0)
    print("[clock] " + ", ".join(f"{k} {v:.3f}" for k, v in run.clock.items()),
          file=sys.stderr, flush=True)
    kind = "per_layer" if trace else "end_to_end"
    line = {
        "correct": all(c["ok"] for c in run.checks),
        "attempted": int(run.attempted),
        "failed": int(run.failed),
        "metrics": read_metrics(run, metrics_of(bench, workload, kind)),
        "device": device_info(run),
        "checks": run.checks,
        # what the per-layer readers find without a trace, for diagnosis
        "layers": read_metrics(run, metrics_of(bench, workload, "per_layer"))
        if not trace else {},
        "workload": workload, "seed": run.seed, "seconds": run.seconds,
    }
    red = run.reduced_trace()
    if red:
        line["breakdown"] = {"device_ops": red["device_ops"],
                             "idle_gaps": red["idle_gaps"]}
    return line


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--bench", default=os.path.join(REPO, "BENCHMARK.json"),
                    help="another BENCHMARK.json (the tests' tiny one)")
    ap.add_argument("--control", default=None,
                    help="run a configuration's named control (tests, limits.py)")
    args = ap.parse_args(argv)
    bench = load_json(args.bench)
    line = run_cell(bench, args.workload, args.seed, args.seconds,
                    args.trace, args.control)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
