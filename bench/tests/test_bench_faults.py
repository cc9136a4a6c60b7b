"""Each cell's check against its control and against planted faults.

The harness is driven as a run drives it, without its look for a chip, at
the small sizes of rehearsal.py, with the timed path broken underneath;
`correct` has to come out false.  The controls put the plain reference,
computed one precision below the configuration's, in the program's place
and have to fail one of the cell's numbers."""

import pytest

from rehearsal import control_cell, run

ENGINE = ["interposer.net_front", "interposer.codesign_front"]


def _merge_fault(kind):
    from repro.core import search
    merge = search._merge_into

    def broken(front, pts, idx, objectives):
        if kind == "state_unchanged" and front is not None:
            return front                      # the fold keeps its state
        if kind == "half_batch":
            pts, idx = pts[::2], idx[::2]     # half the chunk's rows
        if kind == "answer_altered":
            pts = pts.copy()
            pts[:, 1] *= 1 + 1e-6             # energy off in the 6th digit
        return merge(front, pts, idx, objectives)
    return broken


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch",
                                  "answer_altered"])
@pytest.mark.parametrize("workload", ENGINE)
def test_engine_fault_is_not_correct(workload, kind, monkeypatch):
    from repro.core import search
    monkeypatch.setattr(search, "_merge_into", _merge_fault(kind))
    result, err = run(workload)
    assert not result["correct"], err


@pytest.mark.parametrize("workload", ENGINE)
def test_engine_control_fails(workload):
    checks, _ = control_cell(workload).control()
    assert not all(c.ok for c in checks), checks
