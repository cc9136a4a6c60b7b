"""Cell lookup, device check, timed window, trace and result line.

A cell is found by name in BENCHMARK.json.  Its configuration file and its
traffic file (bench/traffic/<traffic>.json) are data; the traffic's
`driver` names the cell class that runs it ("module:Class" under benchlib),
and each per-layer metric is a reader of its own (bench/metrics/<name>.py).  So a
cell or a metric is added with files and BENCHMARK.json entries alone.

A cell class has four methods, called in this order:
  setup()    builds the system under test and warms up every shape the
             window uses (counted in setup_s);
  step()     one whole unit of the window's work, waited for; returns the
             amount of work it did (designs);
  release()  drops the system's device state, after the peak memory has
             been read and before the reference runs;
  check()    compares what the window produced with the plain reference:
             a list of Check(name, value, limit), each passing when
             value <= limit, plus the units that failed.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

# JAX monitoring events that mean a program was compiled or fetched from the
# persistent cache: none may fire inside the measured window
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_hits")


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(self.value <= self.limit)


@dataclasses.dataclass
class Cell:
    """Everything a cell class and a metric reader may read."""
    name: str
    workload: dict          # the BENCHMARK.json entry
    config: dict            # bench/configs/<file>
    traffic: dict           # bench/traffic/<traffic>.json
    seed: int
    seconds: float
    trace: bool
    chips: int
    counters: dict = dataclasses.field(default_factory=dict)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_file_module(path: Path, name: Optional[str] = None):
    """A module from a file whose name need not be an identifier
    (`engine.front_mask_ms.py`, `interposer.reference.py`)."""
    spec = importlib.util.spec_from_file_location(
        name or "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference_module(config: dict):
    return load_file_module(BENCH / "configs" / config["reference"])


def cell_spec(bench: dict, name: str):
    """(workload entry, configuration file contents, traffic contents)."""
    wls = {w["name"]: w for w in bench["workloads"]}
    if name not in wls:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(wls)}")
    wl = wls[name]
    cfg = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    config = load_json(ROOT / cfg["file"])
    traffic = load_json(BENCH / "traffic" / f"{wl['traffic']}.json")
    return wl, config, traffic


def metric_entries(bench: dict, workload: str, kind: str) -> List[dict]:
    """The end_to_end or per_layer entries this cell reports."""
    return [m for m in bench[kind]
            if workload in m.get("workloads", [workload])]


def driver_class(traffic: dict):
    mod, cls = traffic["driver"].split(":")
    return getattr(importlib.import_module(f"benchlib.{mod}"), cls)


class CompileCounter:
    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event in COMPILE_EVENTS:
            self.count += 1

    def _duration(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.count += 1


def use_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, unless JAX_COMPILATION_CACHE_DIR names one.  Every program is
    cached, however quickly it compiled, so that set-up repeats."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()[:chips]
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": max(peaks) if peaks else None}


def run_window(cell_obj, seconds: float, counter: CompileCounter,
               max_units: Optional[int] = None):
    """Whole units back to back until `seconds` have passed (or `max_units`
    are done); the unit in flight at the deadline is finished.  Returns the
    units' (start, end, work) and the compiles seen inside."""
    import jax
    units = []
    c0 = counter.count
    t_start = time.perf_counter()
    while True:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.unit"):
            work = cell_obj.step()
        t1 = time.perf_counter()
        units.append((t0, t1, work))
        if t1 - t_start >= seconds or len(units) == max_units:
            break
    return units, counter.count - c0


def profiler_options(traffic: dict):
    """Host spans without the Python tracer; the device trace mode the
    traffic file asks for (`TRACE_ONLY_XLA` keeps program executions and
    drops per-instruction events, for windows with millions of them)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    if traffic.get("trace_mode"):
        opts.advanced_configuration = {"tpu_trace_mode": traffic["trace_mode"]}
    return opts


def run_cell(name: str, seed: int, seconds: float, trace: bool, start: float,
             *, bench: Optional[dict] = None, overrides: Optional[dict] = None,
             require_tpu: bool = True, out=sys.stdout, err=sys.stderr) -> dict:
    """Run one cell and print its result line; returns the result.

    `overrides` ({"config": {...}, "traffic": {...}}) replaces keys of the
    cell's files: the CPU rehearsal and the tests run the whole path at a
    small size with it.  The command line never passes it."""
    import jax
    bench = bench or load_json(ROOT / "BENCHMARK.json")
    wl, config, traffic = cell_spec(bench, name)
    for key, part in (overrides or {}).items():
        {"config": config, "traffic": traffic}[key].update(part)
    chips = int(wl["chips"])
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoAccelerator(
            f"cell {name} needs {chips} TPU chip(s); JAX sees {len(devs)} "
            f"{devs[0].platform} device(s)")
    print(f"device: platform {devs[0].platform}, kind {devs[0].device_kind!r},"
          f" count {len(devs)}, used {chips}", file=err, flush=True)

    cell = Cell(name=name, workload=wl, config=config, traffic=traffic,
                seed=int(seed), seconds=float(seconds), trace=bool(trace),
                chips=chips)
    counter = CompileCounter()
    cell_obj = driver_class(traffic)(cell)
    cell_obj.setup()
    setup_s = time.perf_counter() - start
    print(f"setup: {setup_s:.3f} s, {counter.count} programs compiled or "
          f"fetched from the cache", file=err, flush=True)

    # a traced run traces a few whole units only: the per-layer metrics are
    # per unit, and a trace of a whole window would be gigabytes
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir,
                                 profiler_options=profiler_options(traffic))
    try:
        units, window_compiles = run_window(
            cell_obj, cell.seconds, counter,
            int(traffic["trace_units"]) if trace else None)
    finally:
        if trace:
            jax.profiler.stop_trace()
    window_s = units[-1][1] - units[0][0]
    work = sum(u[2] for u in units)
    print(f"window: {len(units)} units, {work} work in {window_s:.3f} s; "
          f"compiles inside the window: {window_compiles}", file=err,
          flush=True)

    device = device_info(chips)
    result_metrics: Dict[str, dict] = {}
    breakdown = None
    if trace:
        from benchlib import traces
        try:
            data = traces.load(trace_dir, chips)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        win = traces.Window(data)
        device["busy_s"] = win.busy_s
        device["window_s"] = win.window_s
        breakdown = win.breakdown()
        for entry in metric_entries(bench, name, "per_layer"):
            reader = load_file_module(BENCH / "metrics" / f"{entry['name']}.py")
            value = reader.read(win, cell)
            if value is not None:
                result_metrics[entry["name"]] = {"value": value,
                                                 "unit": entry["unit"]}
    else:
        e2e = cell_obj.end_to_end(units)
        e2e["setup_s"] = setup_s
        for entry in metric_entries(bench, name, "end_to_end"):
            if entry["name"] in e2e:
                result_metrics[entry["name"]] = {"value": e2e[entry["name"]],
                                                 "unit": entry["unit"]}

    cell_obj.release()
    checks, failed = cell_obj.check()
    for k, v in cell.counters.items():
        print(f"counter {k}: {v}", file=err)
    correct = (all(c.ok for c in checks) and failed == 0
               and all(math.isfinite(m["value"])
                       for m in result_metrics.values()))
    for c in checks:
        print(f"check {c.name}: {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAIL'}", file=err)
    err.flush()
    result = {"correct": correct, "attempted": len(units), "failed": failed,
              "metrics": result_metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    print(json.dumps(result), file=out, flush=True)
    return result


class NoAccelerator(RuntimeError):
    pass


def main(argv, start: float) -> int:
    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench_file = ROOT / "BENCHMARK.json"
    if not (ROOT / "src" / "repro").is_dir() or not bench_file.is_file():
        print("the checkout holds no program (src/repro) or no "
              "BENCHMARK.json", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    use_compile_cache()
    try:
        run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                 start)
    except NoAccelerator as e:
        print(f"no accelerator: {e}", file=sys.stderr)
        return 3
    return 0
