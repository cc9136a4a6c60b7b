"""The training cells' check against its controls and planted faults, for
every cell whose traffic runs `lm:Training`.

The harness is driven as a run drives it, without its look for a chip, at
the traffic file's small size, with the timed path broken underneath;
`correct` has to come out false.  The controls are the program one
precision below the configuration: 4-bit photonic weight levels for its
8-bit ones, and the AdamW state in bfloat16 for its float32."""

import pytest

from rehearsal import control_cell, run, workloads_driven_by
from benchlib import faults

TRAINING = workloads_driven_by("lm:Training")


@pytest.mark.parametrize("workload", TRAINING)
@pytest.mark.parametrize("kind", faults.FAULTS)
def test_training_fault_is_not_correct(kind, workload):
    with faults.plant(kind):
        result, err = run(workload)
    assert not result["correct"], err


@pytest.mark.parametrize("workload", TRAINING)
@pytest.mark.parametrize("control", ["control", "control_state"])
def test_training_controls_fail(control, workload):
    checks, _ = getattr(control_cell(workload), control)()
    assert not all(c.ok for c in checks), checks
