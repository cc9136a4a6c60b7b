"""Readings of a cell's sound program, its controls and planted faults, for
setting the limits of its check.

    python3 bench/controls.py --workload <name> --seeds 1,2,3 \
        [--kinds control,sound,control_state,fault:half_batch]

`control` puts the control in the program's place: the plain reference
computed in float32 for the engine's float64; for a training cell, the
program with its photonic weights one precision down.  `sound` (training
cells) reads the program itself without a window, `control_state` the
program with its AdamW state in bfloat16, and `fault:<name>` the program
with a fault of `benchlib/faults.py` planted.  Each seed and kind prints
one JSON line with the numbers the cell's check compares, as the
benchmark's own runs would read them.  Limits are set between these
readings (PERF.md).  The benchmark's own runs never run this.
"""

import argparse
import contextlib
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchlib import harness  # noqa: E402


def main(argv) -> int:
    ap = argparse.ArgumentParser(prog="bench/controls.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--kinds", default="control")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(harness.ROOT / "src"))
    harness.use_compile_cache()
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    wl, config, traffic = harness.cell_spec(bench, args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        for kind in args.kinds.split(","):
            cell = harness.Cell(name=args.workload, workload=wl,
                                config=config, traffic=traffic, seed=seed,
                                seconds=0.0, trace=False,
                                chips=int(wl["chips"]))
            obj = harness.driver_class(traffic)(cell)
            planted = contextlib.nullcontext()
            if kind.startswith("fault:"):
                from benchlib import faults
                planted = faults.plant(kind.split(":", 1)[1])
            with planted:
                checks, _ = getattr(obj, kind if ":" not in kind
                                    else "sound")()
            print(json.dumps({
                "workload": args.workload, "kind": kind, "seed": seed,
                "readings": {c.name: float(c.value) for c in checks},
                "limits": {c.name: c.limit for c in checks},
                "fails": [c.name for c in checks if not c.ok],
                "counters": {k: v for k, v in cell.counters.items()
                             if isinstance(v, (int, float, list))}}),
                flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
