"""The program's `repro.*` spans as `benchlib.spans` reads them.

Made traces check the arithmetic: the per-chunk split of the fold, idle
gaps named by the folding thread's innermost span (a dispatch span of the
worker thread that overlaps never names one), and each chunk program's lag
behind its dispatch.  A small trace recorded on a TPU v5e
(bench/testdata/v5e_net_front_spans.xplane.pb.gz: one `pareto_search` of
147,456 designs in 3 chunks of 65,536, traced by `bench/split.py --keep`
with `net_front`'s axes cut to 4 gateway and 3 wavelength counts, then
reduced to the chip's `XLA Modules` line and the host's `bench.` and
`repro.` events, 4 kB) checks the same reductions on what the chip and the
profiler really write, and that the existing readers still read beside
the spans."""

import gzip
import math

import pytest

from rehearsal import BENCH, benchmark, harness
from benchlib import spans, traces
from benchlib.spans import Span
from benchlib.traces import Event, TraceData

MS = 1e6  # ns
RECORDED = BENCH / "testdata" / "v5e_net_front_spans.xplane.pb.gz"


def _made_spans():
    """Two chunks.  Fold 1 (2..10 ms): wait 1 ms, fetch 1 ms, a merge of
    5 ms with a 3 ms prefilter, 0.5 ms of ranks and a 1 ms front call.
    Fold 2 (12..16 ms): wait 2 ms, fetch 1 ms, a 1 ms front call.  The
    worker's dispatch spans overlap the folds."""
    return [
        Span("repro.search", 0, 20 * MS, {"designs": 200, "chunks": 2}),
        Span("repro.chunk.dispatch", 0.5 * MS, 1 * MS, {"start": 0}),
        Span("repro.chunk.fold", 2 * MS, 10 * MS, {"start": 0, "rows": 100}),
        Span("repro.chunk.wait", 2 * MS, 3 * MS),
        Span("repro.chunk.fetch", 3 * MS, 4 * MS, {"bytes": 800}),
        Span("repro.chunk.dispatch", 4.5 * MS, 7.5 * MS, {"start": 100}),
        Span("repro.merge", 4.5 * MS, 9.5 * MS,
             {"rows": 100, "front_in": 0, "front_out": 7}),
        Span("repro.merge.prefilter", 5 * MS, 8 * MS,
             {"rows": 100, "front": 5, "kept": 10}),
        Span("repro.front.rank", 8 * MS, 8.5 * MS, {"points": 15}),
        Span("repro.front.mask", 8.5 * MS, 9.5 * MS,
             {"points": 15, "padded": 16}),
        Span("repro.chunk.fold", 12 * MS, 16 * MS, {"start": 100, "rows": 100}),
        Span("repro.chunk.wait", 12 * MS, 14 * MS),
        Span("repro.chunk.fetch", 14 * MS, 15 * MS, {"bytes": 800}),
        Span("repro.front.mask", 15 * MS, 16 * MS,
             {"points": 7, "padded": 16}),
        Span("repro.front.mask", 18 * MS, 19 * MS),   # outside every fold
    ]


def test_fold_split_per_chunk():
    got = spans.fold_split(_made_spans())
    want = {"chunks": 2, "fold_ms": 6.0, "fold_wait_ms": 1.5, "fetch_ms": 1.0,
            "fetch_bytes": 800.0, "merge_ms": 2.5, "prefilter_ms": 1.5,
            "survivor_share": 10.0, "rank_ms": 0.25, "front_call_ms": 1.0,
            "front_calls": 1.0, "uncovered_ms": 0.75}
    assert got == pytest.approx(want)
    assert spans.fold_split([s for s in _made_spans()
                             if s.name != "repro.chunk.fold"]) is None


def test_an_overlapping_dispatch_never_names_a_gap():
    # the chip runs 1..2 ms and 10..11 ms of a 20 ms unit
    mods = [Event("jit_decode(1)", 1 * MS, 2 * MS),
            Event("jit_decode(1)", 10 * MS, 11 * MS)]
    win = traces.Window(TraceData({0: mods}, {},
                                  [Event("bench.unit", 0.5 * MS, 20 * MS)]))
    named = {round(s * 1e3, 6): name
             for name, s in spans.gap_names(win, _made_spans())}
    # 2..10 ms, midpoint 6 ms: inside the dispatch (4.5..7.5, the shortest
    # span there) but named by the fold's prefilter
    assert named[8.0] == "repro.merge.prefilter"
    assert named[9.0] == "repro.front.mask"      # 11..20 ms, midpoint 15.5
    # 0.5..1 ms: the dispatch is the only span there besides the search
    assert named[0.5] == "repro.search"


def test_dispatch_lags_pair_kth_launch_with_kth_run():
    mods = [Event("jit_decode(1)", 1 * MS, 2 * MS),
            Event("jit_body(2)", 2 * MS, 3 * MS),
            Event("jit_decode(1)", 8 * MS, 9 * MS),
            Event("jit_body(2)", 9 * MS, 10 * MS)]
    win = traces.Window(TraceData({0: mods}, {},
                                  [Event("bench.unit", 0, 20 * MS)]))
    lags = spans.dispatch_lags(win, _made_spans())
    assert lags["jit_decode"] == [0.5 * MS, 3.5 * MS]
    assert lags["jit_body"] == [1.5 * MS, 4.5 * MS]


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    assert RECORDED.is_file(), "the recorded v5e trace is missing"
    path = tmp_path_factory.mktemp("v5e") / "unit.xplane.pb"
    path.write_bytes(gzip.decompress(RECORDED.read_bytes()))
    found = spans.load(str(path))
    return traces.Window(traces.load(str(path))), found


def test_recorded_unit_spans_and_split(recorded):
    win, found = recorded
    search, = [s for s in found if s.name == "repro.search"]
    assert search.args == {"designs": 147456, "chunks": 3}
    split = spans.fold_split(found)
    assert split["chunks"] == 3
    for key in ("fold_ms", "fold_wait_ms", "fetch_ms", "fetch_bytes",
                "prefilter_ms", "survivor_share", "front_call_ms"):
        assert math.isfinite(split[key]) and split[key] >= 0, key
    # every metric and network column as float64, the topology ids as int64
    assert split["fetch_bytes"] == 65536 * 8 * (6 + 12 + 1)
    assert split["chunks"] * split["fold_ms"] * 1e-3 <= win.window_s


def test_recorded_chunk_programs_run_after_their_dispatch(recorded):
    win, found = recorded
    lags = spans.dispatch_lags(win, found)
    for prog in ("jit_decode", "jit_body"):
        assert len(lags[prog]) == 3
        assert all(lag >= 0 for lag in lags[prog]), (prog, lags[prog])


@pytest.mark.parametrize("name", ["engine.front_mask_ms",
                                  "engine.chunk_device_ms"])
def test_existing_readers_read_beside_the_spans(recorded, name):
    win, _ = recorded
    assert name in {m["name"] for m in benchmark()["per_layer"]}
    cell = harness.Cell(name="interposer.net_front", workload={}, config={},
                        traffic={}, seed=0, seconds=0.0, trace=True, chips=1,
                        counters={"chunks_per_search": 3})
    reader = harness.load_file_module(BENCH / "metrics" / f"{name}.py")
    value = reader.read(win, cell)
    assert value is not None and math.isfinite(value) and value > 0
