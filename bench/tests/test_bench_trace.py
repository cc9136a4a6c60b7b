"""Trace reductions: busy union, idle share, device time by name, idle gaps
tagged by the enclosing benchmark span, breakdown.

One test set runs on a hand-made trace whose answers are known; the other
on a small trace recorded on a TPU v5e (bench/testdata): two `bench.unit`
spans, each a photonic_mac kernel call, a 20 ms host sleep inside a
`bench.host_wait` span, and an ssm_scan kernel call."""

import glob
import importlib

import pytest

from rehearsal import BENCH, benchmark, harness
from benchlib import traces
from benchlib.traces import Event, TraceData

MS = 1e6  # ns


def _made():
    ops = [Event("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %a)", 1 * MS, 3 * MS),
           Event("%photonic_mac.2 = f32[256,512]{1,0} custom-call("
                 "bf16[256,512]{1,0} %x, s8[512,512]{1,0} %w, "
                 "f32[4,4,1,1]{3,2,1,0} %s)", 2 * MS, 5 * MS),
           Event("%while.3 = (s32[]) while((s32[]) %t)", 7 * MS, 8 * MS)]
    mods = [Event("jit_step(123)", 1 * MS, 5 * MS),
            Event("jit_pf(9)", 7 * MS, 8 * MS)]
    spans = [Event("bench.unit", 0, 6 * MS), Event("bench.unit", 6 * MS, 10 * MS),
             Event("bench.host_wait", 8 * MS, 9.5 * MS)]
    return TraceData({0: mods}, {0: ops}, spans)


def test_union_gaps_and_idle_share():
    assert traces.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert traces.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]
    win = traces.Window(_made())
    assert win.window_s == pytest.approx(0.010)
    assert win.busy(0) == [(1 * MS, 5 * MS), (7 * MS, 8 * MS)]
    assert win.busy_s == pytest.approx(0.005)
    assert win.idle_share == pytest.approx(0.5)


def test_time_by_program_and_kernel_name():
    win = traces.Window(_made())
    assert win.time_of("modules", r"^jit_pf$") == pytest.approx(0.001)
    assert win.time_of("ops", r"^photonic_mac$") == pytest.approx(0.003)
    assert traces.op_name(win.matching("ops", "photonic", 0)[0].name) == \
        "photonic_mac"
    assert traces.op_shapes(win.matching("ops", "photonic", 0)[0].name)[:3] == [
        ("f32", (256, 512)), ("bf16", (256, 512)), ("s8", (512, 512))]


def test_self_time_takes_nested_events_out_of_their_parent():
    loop = Event("%while.1 = s32[] while(s32[] %a)", 0, 10 * MS)
    body = [Event("%fusion.2 = f32[8] fusion(f32[8] %b)", 1 * MS, 3 * MS),
            Event("%fusion.3 = f32[8] fusion(f32[8] %c)", 4 * MS, 8 * MS)]
    inner = Event("%add.4 = f32[8] add(f32[8] %d)", 5 * MS, 6 * MS)
    own = {e.name: t for e, t in traces.self_times([loop, *body, inner])}
    assert own[loop.name] == 4 * MS
    assert own[body[1].name] == 3 * MS and own[inner.name] == 1 * MS


def test_breakdown_tags_gaps_by_innermost_span():
    b = traces.Window(_made()).breakdown()
    assert b["device_ops"][0] == ["photonic_mac", pytest.approx(0.003)]
    gaps = {round(s * 1e3, 6): name for name, s in b["idle_gaps"]}
    assert gaps[2.0] == "bench.host_wait"      # 8..10 ms, midpoint 9 ms
    assert gaps[1.0] == "bench.unit"           # 0..1 ms
    assert len(b["idle_gaps"]) <= 10 and len(b["device_ops"]) <= 10


def _chip():
    found = glob.glob(str(BENCH / "testdata" / "**" / "*.xplane.pb"),
                      recursive=True)
    assert found, "the recorded chip trace is missing from bench/testdata"
    return traces.Window(traces.load(found[0]))


def test_chip_trace_units_kernels_and_idle():
    win = _chip()
    assert len(win.units) == 2 and win.chips == [0]
    macs = win.matching("ops", r"^photonic_mac$", 0)
    scans = win.matching("ops", r"^ssm_scan$", 0)
    assert len(macs) == 2 and len(scans) == 2
    (_, out), (xdt, x), (wdt, w) = traces.op_shapes(macs[0].name)[:3]
    assert (out, xdt, x, wdt, w) == ((256, 512), "bf16", (256, 512), "s8",
                                     (512, 512))
    assert traces.op_shapes(scans[0].name)[0] == ("f32", (8, 256, 64))
    # two 20 ms host sleeps inside the window: the chip idles through them
    assert 0.04 < win.window_s - win.busy_s
    assert 0 < win.busy_s < win.window_s
    b = win.breakdown()
    assert b["idle_gaps"][0][0] == "bench.host_wait"
    assert b["idle_gaps"][0][1] == pytest.approx(0.02, rel=0.25)


def test_made_training_step_runs_the_recorded_scan():
    """The made training step's ssm_scan call is the instruction the chip
    recorded, so a reader of the scan kernel finds it in both traces."""
    from benchlib import lm
    trace, _ = lm.made_unit()
    made = traces.Window(trace).matching("ops", r"^ssm_scan$", 0)
    recorded = _chip().matching("ops", r"^ssm_scan$", 0)
    assert len(made) == 2
    assert {e.name for e in made} == {e.name for e in recorded}


@pytest.mark.parametrize("entry", benchmark()["per_layer"],
                         ids=lambda m: m["name"])
def test_engine_readers_on_a_made_search(entry):
    """Each reader, on the made unit trace of the cell class that drives its
    first cell (`made_unit()` of that class's module: an engine search, a
    training step), reads what its file states (`MADE_READING`)."""
    wl, _, traffic = harness.cell_spec(benchmark(), entry["workloads"][0])
    module = importlib.import_module(
        "benchlib." + traffic["driver"].split(":")[0])
    trace, counters = module.made_unit()
    cell = harness.Cell(name=wl["name"], workload=wl, config={},
                        traffic=traffic, seed=0, seconds=0.0, trace=True,
                        chips=1, counters=dict(counters))
    reader = harness.load_file_module(BENCH / "metrics" / f"{entry['name']}.py")
    value = reader.read(traces.Window(trace), cell)
    assert value == pytest.approx(reader.MADE_READING, rel=1e-12)
