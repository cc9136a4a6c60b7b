"""The program's own spans (`repro.*`) on the profiler's host plane, with
their arguments, and what they say about the engine's host fold.

The design-space engine opens `jax.profiler.TraceAnnotation` spans at each
boundary of its chunk pipeline and front merge (`core/sweep.py`,
`core/search.py`); they land on `/host:CPU` in the same `.xplane.pb` as the
chip's events:

  repro.search            designs, chunks
  repro.chunk.dispatch    start               (the pipeline's worker thread)
  repro.chunk.fold        start, rows
    repro.chunk.wait      -
    repro.chunk.fetch     bytes               (copied off the device)
    repro.merge           rows, front_in, front_out
      repro.merge.prefilter  rows, front, kept
      repro.front.rank       points
      repro.front.mask       points, padded

`traces.load` keeps only the benchmark's `bench.*` spans, so these are read
here from the file itself: `bench/split.py` records a unit and reduces it
with `fold_split`, `gap_names` and `dispatch_lags`.
"""

from __future__ import annotations

import dataclasses
import gzip
import os
from typing import Dict, List, Optional, Sequence

from benchlib import traces

PREFIX = "repro."
FOLD = "repro.chunk.fold"
DISPATCH = "repro.chunk.dispatch"
# the fold's leaves: every named piece of host work inside a fold
LEAVES = ("repro.chunk.wait", "repro.chunk.fetch", "repro.merge.prefilter",
          "repro.front.rank", "repro.front.mask")


@dataclasses.dataclass
class Span:
    name: str
    start: float   # ns, host clock
    end: float     # ns
    args: dict = dataclasses.field(default_factory=dict)

    @property
    def dur(self) -> float:
        return self.end - self.start

    def holds(self, other: "Span") -> bool:
        return self.start <= other.start and other.end <= self.end


def profile(path: str):
    """`ProfileData` of a `.xplane.pb` (or a gzipped one)."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def load(trace_dir_or_file: str) -> List[Span]:
    """The program's host spans, sorted by start."""
    path = trace_dir_or_file
    if os.path.isdir(path):
        path = traces.find_xplane(path)
    spans = [Span(e.name, e.start_ns, e.start_ns + e.duration_ns,
                  {k: v for k, v in e.stats})
             for plane in profile(path).planes if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events
             if e.name.startswith(PREFIX)]
    spans.sort(key=lambda s: s.start)
    return spans


def _inside(spans: Sequence[Span], outer: Sequence[Span]) -> List[Span]:
    return [s for s in spans if any(o.holds(s) for o in outer)]


def fold_split(spans: Sequence[Span]) -> Optional[Dict[str, float]]:
    """Per chunk (one chunk = one `repro.chunk.fold`): the fold's time and
    that of each named piece inside it (ms), the bytes fetched, the front
    calls, and the share of prefiltered rows that survived (%).
    `uncovered_ms` is the fold's time that no leaf span names.  None when
    the trace holds no fold."""
    folds = [s for s in spans if s.name == FOLD]
    if not folds:
        return None
    chunks = len(folds)
    inner = _inside([s for s in spans if s.name != FOLD], folds)

    def named(name):
        return [s for s in inner if s.name == name]

    def ms(name):
        return sum(s.dur for s in named(name)) / chunks * 1e-6

    pre = named("repro.merge.prefilter")
    rows = sum(s.args.get("rows", 0) for s in pre)
    out = {"chunks": chunks,
           "fold_ms": sum(f.dur for f in folds) / chunks * 1e-6,
           "fold_wait_ms": ms("repro.chunk.wait"),
           "fetch_ms": ms("repro.chunk.fetch"),
           "fetch_bytes": sum(s.args.get("bytes", 0)
                              for s in named("repro.chunk.fetch")) / chunks,
           "merge_ms": ms("repro.merge"),
           "prefilter_ms": ms("repro.merge.prefilter"),
           "survivor_share": (100.0 * sum(s.args.get("kept", 0) for s in pre)
                              / rows if rows else None),
           "rank_ms": ms("repro.front.rank"),
           "front_call_ms": ms("repro.front.mask"),
           "front_calls": len(named("repro.front.mask")) / chunks}
    out["uncovered_ms"] = out["fold_ms"] - sum(ms(n) for n in LEAVES)
    return out


def innermost(spans: Sequence[Span], t: float) -> Optional[Span]:
    """The shortest span of the folding thread that holds time t.  The
    worker thread's dispatch spans overlap the folds (on some hosts the
    profiler puts both threads on one line), so they never name a time."""
    best = None
    for s in spans:
        if s.name != DISPATCH and s.start <= t < s.end and \
                (best is None or s.dur < best.dur):
            best = s
    return best


def gap_names(win: traces.Window, spans: Sequence[Span],
              top: int = 10) -> List[list]:
    """The longest idle gaps of the first chip, each named by the innermost
    program span around its midpoint (else by the benchmark's span)."""
    chip = win.chips[0]
    idle = traces.gaps(win.busy(chip), win.lo, win.hi)
    idle.sort(key=lambda g: -(g[1] - g[0]))
    out = []
    for s, e in idle[:top]:
        span = innermost(spans, (s + e) / 2)
        out.append([span.name if span else win.tag((s + e) / 2),
                    (e - s) * 1e-9])
    return out


def dispatch_lags(win: traces.Window, spans: Sequence[Span],
                  programs: Sequence[str] = ("jit_decode", "jit_body")
                  ) -> Dict[str, List[float]]:
    """For each chunk program, the k-th execution's start less the start of
    the k-th dispatch span that launched it (ns, the chip's clock moved by
    `win.shift`).  A negative lag means the chip's clock runs ahead."""
    dispatches = [s for s in spans if s.name == DISPATCH]
    chip = win.chips[0]
    out = {}
    for prog in programs:
        runs = [e for e in win.events("modules", chip)
                if traces.module_name(e.name) == prog]
        out[prog] = [e.start - d.start for d, e in zip(dispatches, runs)]
    return out
