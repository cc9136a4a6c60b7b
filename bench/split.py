"""Where an engine cell's host fold spends its time, from the program's spans.

    python3 bench/split.py --workload <name> [--untraced N] [--keep DIR]
                           [--overrides JSON]

Sets the cell up as `bench/run.py` does, times N whole units untraced, then
one unit under the profiler with the benchmark's options, and prints one
JSON line: the per-chunk split of the fold (`benchlib.spans.fold_split`),
the share of the unit's wall time the folds cover, the idle gaps named by
the program's spans, the lag of each chunk program's execution behind the
dispatch that launched it, the existing readers' values, and the traced
unit's time against the untraced ones (what tracing costs when it is on).
`--keep` copies the trace there; `--overrides` ({"config": {...},
"traffic": {...}}) replaces keys of the cell's files, as the tests do to
run a cell at a small size.  Needs a TPU, like `bench/run.py`; no
benchmark run calls it.
"""

import argparse
import json
import shutil
import statistics
import sys
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from benchlib import harness, spans, traces  # noqa: E402


def main(argv) -> int:
    ap = argparse.ArgumentParser(prog="bench/split.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--untraced", type=int, default=2)
    ap.add_argument("--keep")
    ap.add_argument("--overrides", type=json.loads, default={})
    args = ap.parse_args(argv)

    import jax
    harness.use_compile_cache()
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    wl, config, traffic = harness.cell_spec(bench, args.workload)
    for key, part in args.overrides.items():
        {"config": config, "traffic": traffic}[key].update(part)
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 3
    cell = harness.Cell(name=args.workload, workload=wl, config=config,
                        traffic=traffic, seed=0, seconds=0.0,
                        trace=True, chips=int(wl["chips"]))
    unit = harness.driver_class(traffic)(cell)
    unit.setup()

    untraced = []
    for _ in range(args.untraced):
        t0 = time.perf_counter()
        unit.step()
        untraced.append(time.perf_counter() - t0)

    trace_dir = tempfile.mkdtemp(prefix="split_trace_")
    jax.profiler.start_trace(
        trace_dir, profiler_options=harness.profiler_options(traffic))
    try:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.unit"):
            unit.step()
        traced = time.perf_counter() - t0
    finally:
        jax.profiler.stop_trace()
    path = traces.find_xplane(trace_dir)
    if args.keep:
        Path(args.keep).mkdir(parents=True, exist_ok=True)
        shutil.copy(path, Path(args.keep) / f"{args.workload}.xplane.pb")

    win = traces.Window(traces.load(path, cell.chips))
    found = spans.load(path)
    split = spans.fold_split(found)
    readers = {}
    for entry in harness.metric_entries(bench, args.workload, "per_layer"):
        reader = harness.load_file_module(
            BENCH / "metrics" / f"{entry['name']}.py")
        readers[entry["name"]] = reader.read(win, cell)
    lags = spans.dispatch_lags(win, found)
    shutil.rmtree(trace_dir, ignore_errors=True)
    result = {
        "workload": args.workload,
        "device": jax.devices()[0].device_kind,
        "unit_s": win.window_s,
        "split": split,
        "fold_cover": (split["chunks"] * split["fold_ms"] * 1e-3
                       / win.window_s if split else None),
        "idle_share": win.idle_share,
        "shift_ms": win.shift * 1e-6,
        "dispatch_lag_ms": {k: [v * 1e-6 for v in vs]
                            for k, vs in lags.items()},
        "gaps": spans.gap_names(win, found),
        "readers": readers,
        "untraced_s": untraced,
        "traced_s": traced,
        "tracing_cost": (traced / statistics.median(untraced) - 1
                         if untraced else None),
        "counters": cell.counters,
    }
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
