"""Small sizes at which the tests drive whole cells on the CPU."""

import io
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from benchlib import harness  # noqa: E402

SMALL = {
    "interposer.net_front": {
        "config": {"network_axes": {"n_gateways": [8, 16, 32],
                                    "n_lambda": [4, 8],
                                    "mem_bw_bytes_per_s": [5e10, 1e11]},
                   "memory_axis": {"n_mem_chiplets": [2, 4]}},
        "traffic": {"chunk_size": 16, "reference_block": 16}},
    "interposer.codesign_front": {
        "config": {"network_axes": {"n_gateways": [8, 16, 32],
                                    "n_lambda": [4, 8],
                                    "mem_bw_bytes_per_s": [5e10, 1e11]}},
        "traffic": {"chunk_size": 12, "reference_block": 12}},
}


def benchmark() -> dict:
    return harness.load_json(BENCH.parent / "BENCHMARK.json")


def overrides(workload: str) -> dict:
    return json.loads(json.dumps(SMALL[workload]))


def run(workload: str, seed: int = 2**31 + 17, seconds: float = 0.5):
    """Drive the whole cell at a small size; returns (result, stderr)."""
    out, err = io.StringIO(), io.StringIO()
    result = harness.run_cell(workload, seed, seconds, False,
                              time.perf_counter(), bench=benchmark(),
                              overrides=overrides(workload),
                              require_tpu=False, out=out, err=err)
    assert json.loads(out.getvalue().strip().splitlines()[-1]) == \
        json.loads(json.dumps(result))
    return result, err.getvalue()


def control_cell(workload: str, seed: int = 3):
    """The cell object at the small size, not set up: its `control()` puts
    the reference, one precision down, in the program's place."""
    cell = harness.Cell(name=workload, workload={}, config={}, traffic={},
                        seed=seed, seconds=0.0, trace=False, chips=1)
    cell.workload, cell.config, cell.traffic = harness.cell_spec(benchmark(),
                                                                 workload)
    for key, part in overrides(workload).items():
        getattr(cell, key).update(part)
    return harness.driver_class(cell.traffic)(cell)
