"""The benchmark's inputs, its cells driven end to end on the CPU at small
sizes, and its refusal to report without an accelerator."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from rehearsal import BENCH, SMALL, benchmark, harness, run

ROOT = BENCH.parent
SEED = 2**31 + 5  # above 32 signed bits, as the driver's seeds may be


@pytest.mark.parametrize("workload", sorted(SMALL))
def test_cell_runs_end_to_end_on_the_cpu(workload):
    result, err = run(workload)
    assert result["correct"], err
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    bench = benchmark()
    want = {m["name"] for m in harness.metric_entries(bench, workload,
                                                      "end_to_end")}
    assert set(result["metrics"]) == want
    assert list(result)[-1] == "checks"
    assert "compiles inside the window: 0" in err


def test_same_seed_same_inputs_same_answers():
    a, _ = run("interposer.net_front", seed=SEED)
    b, _ = run("interposer.net_front", seed=SEED)
    assert a["checks"] == b["checks"]


def _command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "interposer.net_front",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_without_a_tpu():
    proc = _command(ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no accelerator" in proc.stderr


def test_command_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _command(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_file_names_its_parts():
    """Every cell finds its configuration, traffic and metric readers by
    name, and the names and units keep to the benchmark's alphabet."""
    bench = harness.load_json(ROOT / "BENCHMARK.json")
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for c in bench["configs"]:
        assert NAME.match(c["name"]) and (ROOT / c["file"]).is_file()
        assert harness.load_json(ROOT / c["file"])["name"] == c["name"]
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        traffic = harness.load_json(BENCH / "traffic" / f"{w['traffic']}.json")
        assert callable(harness.driver_class(traffic))
        e2e = harness.metric_entries(bench, w["name"], "end_to_end")
        assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
        assert harness.metric_entries(bench, w["name"], "per_layer")
    for m in bench["per_layer"]:
        assert NAME.match(m["name"])
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
    json.dumps(bench)
