"""Small sizes at which the tests drive whole cells on the CPU: each cell's
traffic file gives its own under the key `small` (overrides of the
configuration's and the traffic's keys)."""

import io
import json
import sys
import time
from pathlib import Path
from unittest import mock

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from benchlib import harness  # noqa: E402


def benchmark() -> dict:
    return harness.load_json(BENCH.parent / "BENCHMARK.json")


def _small() -> dict:
    """{workload: its traffic file's `small` overrides}, for every cell."""
    out = {}
    for w in benchmark()["workloads"]:
        traffic = harness.load_json(BENCH / "traffic" / f"{w['traffic']}.json")
        out[w["name"]] = traffic["small"]
    return out


SMALL = _small()


def overrides(workload: str) -> dict:
    return json.loads(json.dumps(SMALL[workload]))


def run(workload: str, seed: int = 2**31 + 17, seconds: float = 0.5):
    """Drive the whole cell at a small size; returns (result, stderr).  The
    CPU gets a nominal peak, so that a training cell reports its
    utilization; no CPU number is ever reported as a chip's."""
    import jax
    from benchlib import roofline
    out, err = io.StringIO(), io.StringIO()
    nominal = {jax.devices()[0].device_kind: {"bf16_flops": 1e12,
                                              "hbm_bytes_per_s": 1e11}}
    with mock.patch.dict(roofline.PEAKS, nominal):
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
