"""Run one benchmark cell on the accelerator and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (configuration x traffic mix) is looked up by name in
BENCHMARK.json at the root of the checkout; everything else lives under
bench/ (see bench/README.md).  The last line of standard output is one JSON
object: correct, attempted, failed, metrics, device, and the numbers the
correctness check compared, each beside its limit.  Without a TPU, or with
fewer chips than the cell asks for, it exits non-zero and prints no result.
"""

import time

START = time.perf_counter()  # set-up is timed from here

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchlib import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], START))
