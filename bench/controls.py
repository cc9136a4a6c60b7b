"""Readings of a cell's control, for setting its limits.

    python3 bench/controls.py --workload <name> --seeds 1,2,3

The control puts the plain reference, computed one precision below the
configuration's, in the program's place: float32 for the engine's float64.
Each seed prints one JSON line with the numbers the cell's check compares,
as the benchmark's own runs would read them.  Limits are set between these
readings and those of sound runs (PERF.md).  Planted faults are read by the
CPU tests (tests/test_bench_faults.py).  The benchmark's own runs never run
this.
"""

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchlib import harness  # noqa: E402


def main(argv) -> int:
    ap = argparse.ArgumentParser(prog="bench/controls.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(harness.ROOT / "src"))
    harness.use_compile_cache()
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    wl, config, traffic = harness.cell_spec(bench, args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        cell = harness.Cell(name=args.workload, workload=wl, config=config,
                            traffic=traffic, seed=seed, seconds=0.0,
                            trace=False, chips=int(wl["chips"]))
        checks, _ = harness.driver_class(traffic)(cell).control()
        print(json.dumps({
            "workload": args.workload, "kind": "control", "seed": seed,
            "readings": {c.name: float(c.value) for c in checks},
            "limits": {c.name: c.limit for c in checks},
            "fails": [c.name for c in checks if not c.ok]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
