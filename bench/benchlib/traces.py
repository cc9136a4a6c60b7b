"""Reductions from a profiler trace to device busy time, idle gaps and the
device time of named programs and kernels.

The trace is the `.xplane.pb` that `jax.profiler` writes, read with
`jax.profiler.ProfileData`.  On a TPU each chip is a plane named
`/device:TPU:<i>`; its line `XLA Modules` holds one event per program
execution (`jit_<name>(<fingerprint>)`) and its line `XLA Ops` one event
per HLO instruction executed, named by the instruction's text, e.g.
`%photonic_mac.1 = f32[512,1024]{...} custom-call(bf16[512,1024]{...} ...`.
The host plane `/host:CPU` holds the benchmark's own spans
(`jax.profiler.TraceAnnotation`, names starting `bench.`).  Times are in
nanoseconds; see `Window` for how the chip's clock is aligned to the host's.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

SPAN_PREFIX = "bench."
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_SHAPE = re.compile(r"\b(pred|s8|u8|s16|u16|s32|u32|s64|u64|bf16|f16|f32|f64)"
                    r"\[([0-9,]*)\]")


@dataclasses.dataclass
class Event:
    name: str
    start: float   # ns
    end: float     # ns

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class TraceData:
    modules: Dict[int, List[Event]]   # chip -> program executions
    ops: Dict[int, List[Event]]       # chip -> HLO instructions executed
    spans: List[Event]                # the benchmark's host spans


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(trace_dir_or_file: str, chips: Optional[int] = None) -> TraceData:
    from jax.profiler import ProfileData
    path = trace_dir_or_file
    if os.path.isdir(path):
        path = find_xplane(path)
    pd = ProfileData.from_file(path)
    modules: Dict[int, List[Event]] = {}
    ops: Dict[int, List[Event]] = {}
    spans: List[Event] = []
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            if chips is not None and chip >= chips:
                continue
            for line in plane.lines:
                target = {"XLA Modules": modules, "XLA Ops": ops}.get(line.name)
                if target is None:
                    continue
                target.setdefault(chip, []).extend(
                    Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                spans.extend(Event(e.name, e.start_ns,
                                   e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    for d in (modules, ops):
        for evs in d.values():
            evs.sort(key=lambda e: e.start)
    spans.sort(key=lambda e: e.start)
    return TraceData(modules, ops, spans)


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The stretches of [lo, hi) that `busy` (disjoint, sorted) leaves."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def self_times(events: Sequence[Event]) -> List[Tuple[Event, float]]:
    """Each event with its own time: its duration less that of the events
    nested directly inside it."""
    out: List[list] = []
    stack: List[list] = []
    for e in sorted(events, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1][0].end <= e.start:
            stack.pop()
        item = [e, e.dur]
        if stack and e.end <= stack[-1][0].end:
            stack[-1][1] -= e.dur
        stack.append(item)
        out.append(item)
    return [(e, own) for e, own in out]


def module_name(event_name: str) -> str:
    """`jit_body(1041...)` -> `jit_body`."""
    return event_name.split("(", 1)[0]


def op_name(event_name: str) -> str:
    """`%photonic_mac.1 = f32[...] custom-call(...)` -> `photonic_mac`."""
    head = event_name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def op_shapes(event_name: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """(dtype, dims) of the instruction's result, then of its operands, as
    the instruction's text gives them (tuple results list every element)."""
    text = event_name.split(" = ", 1)[1] if " = " in event_name else ""
    return [(dt, tuple(int(x) for x in dims.split(",") if x))
            for dt, dims in _SHAPE.findall(text)]


class Window:
    """The traced window: from the start of the first `bench.unit` span to
    the end of the last, on the host's clock.

    The chip's events come on a clock about a millisecond behind the host's
    (on a v5e the first kernel of a unit shows 1.2-1.3 ms before the span
    that launched it).  Every device event is shifted so that the first
    one starts no earlier than the first unit: the device runs nothing
    before the benchmark launches it."""

    def __init__(self, data: TraceData):
        units = [s for s in data.spans if s.name == "bench.unit"]
        if not units:
            raise ValueError("the trace holds no bench.unit span")
        self.chips = sorted(set(data.modules) | set(data.ops))
        if not self.chips:
            raise ValueError("the trace holds no device events")
        first = min(e.start for d in (data.modules, data.ops)
                    for evs in d.values() for e in evs)
        self.shift = max(0.0, units[0].start - first)

        def moved(d):
            return {c: [Event(e.name, e.start + self.shift, e.end + self.shift)
                        for e in evs] for c, evs in d.items()}

        self.data = TraceData(moved(data.modules), moved(data.ops), data.spans)
        self.units = units
        self.lo, self.hi = units[0].start, units[-1].end

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def events(self, kind: str, chip: int) -> List[Event]:
        src = self.data.ops if kind == "ops" else self.data.modules
        return [e for e in src.get(chip, [])
                if e.end > self.lo and e.start < self.hi]

    def busy(self, chip: int) -> List[Interval]:
        """Union of the instructions' intervals (of the programs' where the
        trace holds no instruction events), clipped to the window."""
        evs = self.events("ops", chip) or self.events("modules", chip)
        return union(clip([(e.start, e.end) for e in evs], self.lo, self.hi))

    @property
    def busy_s(self) -> float:
        return sum(total(self.busy(c)) for c in self.chips) \
            / len(self.chips) * 1e-9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def time_of(self, kind: str, pattern: str) -> float:
        """Seconds of device time, averaged over chips, of the programs
        (kind "modules") or instructions ("ops") whose name matches."""
        rx = re.compile(pattern)
        name = module_name if kind == "modules" else op_name
        t = 0.0
        for c in self.chips:
            t += sum(e.dur for e in self.events(kind, c) if rx.search(name(e.name)))
        return t / len(self.chips) * 1e-9

    def matching(self, kind: str, pattern: str, chip: int) -> List[Event]:
        rx = re.compile(pattern)
        name = module_name if kind == "modules" else op_name
        return [e for e in self.events(kind, chip) if rx.search(name(e.name))]

    def tag(self, t: float) -> str:
        """The innermost benchmark span that holds time t."""
        best = None
        for s in self.data.spans:
            if s.start <= t < s.end and (best is None or s.dur < best.dur):
                best = s
        return best.name if best else "outside spans"

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time (their own time: an
        event nested in another, such as a loop's body under the loop, is
        taken out of its parent's) and the longest idle gaps, each named by
        the innermost benchmark span around it."""
        chip = self.chips[0]
        by_name: Dict[str, float] = {}
        evs = self.events("ops", chip)
        name = op_name if evs else module_name
        for e, own in self_times(evs or self.events("modules", chip)):
            by_name[name(e.name)] = by_name.get(name(e.name), 0.0) + own
        ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
        idle = gaps(self.busy(chip), self.lo, self.hi)
        idle.sort(key=lambda g: -(g[1] - g[0]))
        return {"device_ops": [[k, v * 1e-9] for k, v in ops],
                "idle_gaps": [[self.tag((s + e) / 2), (e - s) * 1e-9]
                              for s, e in idle[:top]]}
