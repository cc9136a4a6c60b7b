"""The training cell's check against its controls and planted faults.

The harness is driven as a run drives it, without its look for a chip, at
the traffic file's small size, with the timed path broken underneath;
`correct` has to come out false.  The controls are the program one
precision below the configuration: 4-bit photonic weight levels for its
8-bit ones, and the AdamW state in bfloat16 for its float32."""

import pytest

from rehearsal import control_cell, run
from benchlib import faults

WORKLOAD = "yi-6b.train_4k"


@pytest.mark.parametrize("kind", faults.FAULTS)
def test_training_fault_is_not_correct(kind):
    with faults.plant(kind):
        result, err = run(WORKLOAD)
    assert not result["correct"], err


@pytest.mark.parametrize("control", ["control", "control_state"])
def test_training_controls_fail(control):
    checks, _ = getattr(control_cell(WORKLOAD), control)()
    assert not all(c.ok for c in checks), checks
