"""The search engine's profiler spans (`repro.*`, jax.profiler.TraceAnnotation).

Each search is run on a small grid of several chunks under
`jax.profiler.trace`, and the host events of the written `.xplane.pb` are
read back with `jax.profiler.ProfileData`: one `repro.search`, one dispatch
and one fold per chunk, a wait and a fetch inside every fold, the counts on
each span consistent with the chunks and fronts, and the front identical to
the one an untraced run returns."""

import dataclasses
import glob
import os

import numpy as np
import pytest

import jax
from jax.profiler import ProfileData

from repro.core import CNN_WORKLOADS, ChipletSpec, Traffic
from repro.core.search import ACCEL_OBJECTIVES, codesign_pareto, pareto_search
from repro.core.sweep import METRIC_FIELDS, grid_spec
from repro.core.topology import MODEL_FIELDS

T = Traffic(bytes_read=2e9, bytes_written=1e9, n_transfers=128)
AXES = dict(n_gateways=(16.0, 32.0, 64.0), n_lambda=(4.0, 8.0),
            mem_bw_bytes_per_s=(50e9, 100e9))
CHUNK = 16


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float
    args: dict

    def holds(self, other: "Span") -> bool:
        return self.start <= other.start and other.end <= self.end


def _traced(tmp_path, fn):
    with jax.profiler.trace(str(tmp_path)):
        result = fn()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    spans = [Span(e.name, e.start_ns, e.end_ns, dict(e.stats))
             for plane in ProfileData.from_file(path).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events
             if e.name.startswith("repro.")]
    by_name = {}
    for s in sorted(spans, key=lambda s: s.start):
        by_name.setdefault(s.name, []).append(s)
    return result, by_name


def _check_pipeline(spans, designs, rows, starts, fetch_bytes):
    search, = spans["repro.search"]
    assert search.args == {"designs": designs, "chunks": len(starts)}
    assert [s.args["start"] for s in spans["repro.chunk.dispatch"]] == starts
    folds = spans["repro.chunk.fold"]
    assert [f.args["start"] for f in folds] == starts
    assert [f.args["rows"] for f in folds] == rows
    for fold in folds:
        assert search.holds(fold)
        waits = [w for w in spans["repro.chunk.wait"] if fold.holds(w)]
        fetches = [f for f in spans["repro.chunk.fetch"] if fold.holds(f)]
        assert len(waits) == 1 and len(fetches) == 1
        assert waits[0].end <= fetches[0].start
        assert fetches[0].args["bytes"] == fetch_bytes
    assert len(spans["repro.chunk.wait"]) == len(folds)
    assert len(spans["repro.chunk.fetch"]) == len(folds)

    # one merge per fold, each taking in the front the one before returned
    merges = spans["repro.merge"]
    assert len(merges) == len(folds)
    assert all(f.holds(m) for f, m in zip(folds, merges))
    assert [m.args["rows"] for m in merges] == rows
    assert merges[0].args["front_in"] == 0
    assert [m.args["front_in"] for m in merges[1:]] == \
        [m.args["front_out"] for m in merges[:-1]]
    prefilters = spans["repro.merge.prefilter"]
    assert prefilters and all(
        0 <= p.args["kept"] <= p.args["rows"] for p in prefilters)
    # how the sort-based query engaged: staircases built, exact matches
    assert all(0 <= p.args["equal"] <= p.args["rows"] - p.args["kept"]
               and 1 <= p.args["prefixes"] <= p.args["front"]
               for p in prefilters)
    assert all(any(m.holds(p) for m in merges) for p in prefilters)
    masks = spans["repro.front.mask"]
    assert len(masks) == len(spans["repro.front.rank"]) >= len(merges)
    for m in masks:
        padded = m.args["padded"]
        assert m.args["points"] <= padded and padded & (padded - 1) == 0
    return merges[-1].args["front_out"]


def _same_front(a, b):
    a, b = a.canonical(), b.canonical()
    assert np.array_equal(a.points, b.points)
    assert np.array_equal(a.indices, b.indices)


@pytest.mark.parametrize("prefetch", [0, 2])
def test_network_search_spans(tmp_path, prefetch):
    """Both schedules: inline (prefetch 0) and the worker thread."""
    n = grid_spec(**AXES).n
    starts = list(range(0, n, CHUNK))
    assert len(starts) >= 3
    plain = pareto_search(T, chunk_size=CHUNK, **AXES)
    front, spans = _traced(tmp_path, lambda: pareto_search(
        T, chunk_size=CHUNK, prefetch=prefetch, **AXES))
    # every metric and network column as float64, the topology ids as int64
    per_row = 8 * (len(METRIC_FIELDS) + len(MODEL_FIELDS) + 1)
    size = _check_pipeline(spans, n, [min(CHUNK, n - s) for s in starts],
                           starts, CHUNK * per_row)
    assert size == front.size
    _same_front(front, plain)


def test_codesign_search_spans(tmp_path):
    wl = CNN_WORKLOADS["LeNet5"]()
    mixes = [[ChipletSpec(512, 32)], [ChipletSpec(256, 9), ChipletSpec(128, 49)]]
    kw = dict(topologies=("tree", "trine", "elec"), chunk_size=5,
              n_gateways=(16.0, 32.0), n_lambda=(4.0, 8.0))
    plain, spec = codesign_pareto(wl, mixes, **kw)
    (front, _), spans = _traced(tmp_path,
                                lambda: codesign_pareto(wl, mixes, **kw))
    starts = list(range(0, spec.n, 5))
    assert len(starts) >= 3
    # the objectives of every mix, float64, for a whole (padded) chunk
    size = _check_pipeline(
        spans, len(mixes) * spec.n,
        [len(mixes) * min(5, spec.n - s) for s in starts], starts,
        8 * len(ACCEL_OBJECTIVES) * len(mixes) * 5)
    assert size == front.size
    _same_front(front, plain)
