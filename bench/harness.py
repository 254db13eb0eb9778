"""The benchmark's driver: one cell, one run, one result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name.  ``BENCHMARK.json`` names the cell's
configuration and traffic mix; the configuration's file names the surface
(``bench/surfaces/<surface>.py``) that drives the program; the traffic mix
is ``bench/traffic/<traffic>.json``; each per-layer metric is read by
``bench/metrics/<metric>.py``.  A new configuration, traffic mix or metric
is a new file and an entry in ``BENCHMARK.json``.

A run: find the chips (no TPU, or fewer chips than the cell asks for, is an
error and prints no result); set up and warm up (``setup_s`` runs from the
start of the process to the start of the window); measure for ``--seconds``
with a count of the compilations inside the window; with ``--trace 1``
trace that window (for at most the traffic's ``trace_seconds``) and read
the per-layer metrics from it; read the peak device memory; then compare
what the window produced with the plain reference.  Each compared number
is printed beside its limit as the last lines of standard error and under
``checks``, the last key of the result,
which is the last line of standard output.
"""
from __future__ import annotations

import argparse
import gc
import importlib
import importlib.util
import json
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

ROOT = Path(__file__).resolve().parents[1]
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
Check = Tuple[str, float, float]  # (name, value, limit): passes if value <= limit


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One workload of ``BENCHMARK.json`` with its configuration and
    traffic files loaded."""

    def __init__(self, name: str, root: Path = ROOT):
        self.root = Path(root)
        self.bench = load_json(self.root / "BENCHMARK.json")
        by_name = {w["name"]: w for w in self.bench["workloads"]}
        if name not in by_name:
            raise SystemExit(f"unknown workload {name!r}; known: "
                             f"{sorted(by_name)}")
        self.workload = by_name[name]
        self.name = name
        configs = {c["name"]: c for c in self.bench["configs"]}
        self.config = load_json(self.root / configs[
            self.workload["config"]]["file"])
        self.traffic = load_json(self.root / "bench" / "traffic" /
                                 f"{self.workload['traffic']}.json")
        self.chips = int(self.workload["chips"])

    def _reports(self, metric: dict) -> bool:
        return self.name in metric.get("workloads", [self.name])

    def end_to_end(self) -> List[dict]:
        return [m for m in self.bench["end_to_end"] if self._reports(m)]

    def per_layer(self) -> List[dict]:
        """Per-layer metrics of this cell: those that list it, and those
        without a list whose end-to-end metric this cell reports."""
        e2e = {m["name"] for m in self.end_to_end()}
        return [m for m in self.bench["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in e2e)]

    def surface(self):
        return importlib.import_module(
            f"bench.surfaces.{self.config['surface']}")


def read_metric(root: Path, name: str, run: "RunInfo") -> Optional[float]:
    """Run ``bench/metrics/<name>.py``'s ``read(run)``; None where it finds
    nothing to read."""
    path = Path(root) / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    value = mod.read(run)
    return None if value is None else float(value)


class RunInfo:
    """What a per-layer metric reader may read: the window's host-clock
    record from the surface (``window``), the work counts (``work``), the
    trace summary (``trace``, None when the run is not traced), the chip's
    peaks (``peak``) and the device record (``device``)."""

    def __init__(self, window: dict, work: dict, trace: Optional[dict],
                 peak: Optional[dict], device: dict):
        self.window, self.work, self.trace = window, work, trace
        self.peak, self.device = peak, device


def find_chips(chips: int) -> dict:
    """The platform, kind and count of the devices; raises ``NoChip``
    without a TPU or with fewer chips than ``chips``."""
    import jax
    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu":
        raise NoChip(f"no TPU: JAX found platform {platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devs)}")
    return {"platform": platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def enable_compile_cache() -> str:
    """The program's persistent compile cache (``JAX_COMPILATION_CACHE_DIR``
    where that is set, else the checkout's fixed ``.jax_cache``), holding
    every program however fast it compiled and however large: the sweep's
    executables embed their data and outgrow a cache capped by size."""
    import jax
    from repro.compile_cache import enable_compile_cache as program_cache
    path = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


class CompileCounter:
    """Counts the programs JAX compiles or loads from its cache while
    armed."""

    def __init__(self):
        import jax
        self.armed, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if self.armed and event == COMPILE_EVENT:
            self.count += 1


def memory_peak_bytes(chips: int) -> Optional[int]:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class Marks:
    """Marks the measured window from inside a surface: ``start`` arms the
    compile count and, where ``trace_dir`` is given, starts the profiler
    and opens the ``bench.window`` span; ``stop`` undoes both.  Set-up runs
    from the start of the process to ``start``."""

    def __init__(self, counter: CompileCounter,
                 trace_dir: Optional[str] = None):
        self.counter, self.trace_dir = counter, trace_dir
        self.t_start = self.t_stop = self._span = None
        self.gc_pauses: List[float] = []  # full collections in the window
        self._gc_t = 0.0

    def _on_gc(self, phase: str, info: dict) -> None:
        if info["generation"] == 2:
            if phase == "start":
                self._gc_t = time.perf_counter()
            else:
                self.gc_pauses.append(time.perf_counter() - self._gc_t)

    def start(self) -> None:
        import jax
        from bench import trace as trace_mod
        if self.trace_dir is not None:
            jax.profiler.start_trace(self.trace_dir)
            self._span = jax.profiler.TraceAnnotation(trace_mod.WINDOW)
            self._span.__enter__()
        self.counter.armed = True
        gc.callbacks.append(self._on_gc)
        self.t_start = time.perf_counter()

    def stop(self) -> None:
        import jax
        self.t_stop = time.perf_counter()
        self.counter.armed = False
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
        if self._span is not None:
            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self._span = None

    def trace(self):
        """The ``trace.Trace`` of the window, read from ``trace_dir``."""
        from bench import trace as trace_mod
        return trace_mod.load(str(next(Path(self.trace_dir).rglob(
            "*.xplane.pb"))))


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, *, device: Optional[dict] = None) -> dict:
    """One run of ``cell``; returns the result object.  ``device`` skips
    the look for the chip (tests on the CPU pass their own)."""
    from bench import trace as trace_mod
    from bench.peaks import peaks

    if device is None:
        device = find_chips(cell.chips)
    enable_compile_cache()
    counter = CompileCounter()
    surface = cell.surface()
    state = surface.setup(cell.config, cell.traffic, seed)
    summary = None
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as d:
        marks = Marks(counter, d if trace else None)
        if trace:
            seconds = min(seconds, float(cell.traffic["trace_seconds"]))
        win = surface.window(state, seconds, marks)
        if trace:
            summary = trace_mod.summarize(marks.trace())
            top = sorted(summary["module_s"].items(), key=lambda kv: -kv[1])
            print(f"trace: busy {summary['busy_s']!r} s of "
                  f"{summary['window_s']!r} s; device seconds by program: "
                  f"{dict(top[:8])}", file=sys.stderr, flush=True)
    setup_s = marks.t_start - t_start
    print(f"window: {win['seconds']!r} s, compiles inside the window: "
          f"{counter.count}, set-up {setup_s!r} s, full garbage "
          f"collections inside the window: {len(marks.gc_pauses)} "
          f"(longest {max(marks.gc_pauses, default=0.0)!r} s)",
          file=sys.stderr, flush=True)
    device = dict(device, memory_peak_bytes=memory_peak_bytes(cell.chips))
    work = surface.work(state, win)

    checks: List[Check] = surface.check(state, seed)
    checks.append(("window_compiles", float(counter.count), 0.0))
    del state
    gc.collect()
    correct = all(v <= lim for _, v, lim in checks)

    metrics: Dict[str, dict] = {}
    result: Dict[str, Any] = {}
    if trace:
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        peak = peaks(device["kind"]) if device["platform"] == "tpu" else None
        info = RunInfo(win, work, summary, peak, device)
        for m in cell.per_layer():
            v = read_metric(cell.root, m["name"], info)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["breakdown"] = summary["breakdown"]
    else:
        values = dict(surface.end_to_end(win), setup_s=setup_s)
        for m in cell.end_to_end():
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    out = {"correct": bool(correct), "attempted": int(win["attempted"]),
           "failed": int(win["failed"]) + (0 if correct else 1),
           "metrics": metrics, "device": device}
    out.update(result)
    out["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    for n, v, lim in checks:
        print(f"check {n}: {v!r} limit {lim!r} "
              f"{'ok' if v <= lim else 'FAIL'}", file=sys.stderr)
    sys.stderr.flush()
    return out


def main(argv: Optional[List[str]] = None,
         t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = Cell(args.workload)
    try:
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       t_start)
    except NoChip as e:
        print(f"error: {e}", file=sys.stderr)
        return 3
    print(json.dumps(out), flush=True)
    return 0
