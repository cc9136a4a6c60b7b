"""Design-space engine cells: whole Pareto searches back to back.

The window drives `pareto_search` (network grid) or `codesign_pareto`
(network grid x chiplet mixes) as an architect calls it, and keeps every
front it returns.  After the window the plain reference beside the
configuration scores every design point of the grid in float64 on the host
and extracts its own front; each returned front is compared with it:

  front_rel_err  the largest relative gap between a front point's
                 objectives and the reference's for the same design
                 (covers the device programs, emulated float64);
  front_gap      how far the returned front falls short of the reference
                 front: for each reference front point, the least relative
                 excess by which some returned point covers it, and for each
                 returned point, the margin by which a reference front point
                 dominates it; the largest of these (0 when the fronts match
                 to rounding).  Covers the chunk pipeline and the front
                 extraction: a front point dropped or a dominated point kept
                 reads as its distance to the nearest point that should have
                 stood in its place.
"""

from __future__ import annotations

import numpy as np

from benchlib.harness import Check, reference_module
from benchlib.traces import Event, TraceData

# Limits: set from the readings in PERF.md (sound runs on the chip against
# the float32 control); see the `checks` key of each traffic file.


def grid_of(config: dict, axis_groups) -> dict:
    axes = {}
    for group in axis_groups:
        axes.update(config[group])
    base = {k: v for k, v in config["base"].items() if k not in axes}
    return {"topologies": list(config["topologies"]), "axes": axes,
            "base": base}


def grid_size(grid: dict) -> int:
    n = len(grid["topologies"])
    for v in grid["axes"].values():
        n *= len(v)
    return n


def front_gaps(front_pts, front_idx, ref_scores, ref_front_pts):
    """(front_rel_err, front_gap) of one returned front."""
    want = ref_scores(front_idx)
    rel = float(np.max(np.abs(front_pts - want) / np.abs(want))) \
        if len(front_idx) else float("inf")
    if not len(front_idx):
        return rel, float("inf")
    fp = np.asarray(front_pts, np.float64)
    rp = np.asarray(ref_front_pts, np.float64)
    # cover: for reference point p, min over returned q of max_j (q-p)/|p|
    excess = (fp[None, :, :] - rp[:, None, :]) / np.abs(rp[:, None, :])
    cover = np.max(np.min(np.max(excess, -1), -1))
    # spurious: for returned q, max over reference p of min_j (q-p)/|q|
    margin = (fp[:, None, :] - rp[None, :, :]) / np.abs(fp[:, None, :])
    spurious = np.max(np.max(np.min(margin, -1), -1))
    return rel, float(max(cover, spurious, 0.0))


class _Search:
    """Shared by both engine cells: the grid, the window's fronts, and their
    check against the reference's scores of every design point."""

    rate = "designs_per_s"      # the end-to-end metric of the cell

    def __init__(self, cell):
        self.cell = cell
        self.config, self.traffic = cell.config, cell.traffic
        self.grid = grid_of(self.config, self.traffic["axes"])
        self.n = grid_size(self.grid)
        self.designs = self.n
        self.block = int(self.traffic["reference_block"])
        self.fronts = []

    def setup(self):
        self.fronts.append(self._search())  # warm-up: compiles every program

    def step(self):
        self.fronts.append(self._search())
        c = self.cell.counters
        c["searches"] = len(self.fronts) - 1
        c["chunks_per_search"] = -(-self.n // int(self.traffic["chunk_size"]))
        c["front_size"] = self.fronts[-1].size
        c["device_to_host_bytes_per_chunk"] = self._d2h_bytes()
        return self.designs

    def end_to_end(self, units):
        t = units[-1][1] - units[0][0]
        return {self.rate: sum(u[2] for u in units) / t}

    def release(self):
        pass  # the engine holds no device state between searches

    def _scores(self, ref, xp=np, dtype=np.float64):
        return np.concatenate([
            np.asarray(self._score(ref, s, min(s + self.block, self.designs),
                                   xp, dtype), np.float64)
            for s in range(0, self.designs, self.block)])

    def _check(self, fronts):
        ref = reference_module(self.config)
        scores = self._scores(ref)
        ref_front, _ = ref.pareto_front(scores, np.arange(self.designs))
        worst_rel = worst_gap = 0.0
        for pts, idx in fronts:
            rel, gap = front_gaps(pts, idx, lambda i: scores[i], ref_front)
            worst_rel, worst_gap = max(worst_rel, rel), max(worst_gap, gap)
        self.cell.counters["reference_front_size"] = len(ref_front)
        lim = self.traffic["checks"]
        return [Check("front_rel_err", worst_rel, lim["front_rel_err"]),
                Check("front_gap", worst_gap, lim["front_gap"])], 0

    def check(self):
        return self._check([(f.points, f.indices) for f in self.fronts[1:]])

    def control(self):
        """The reference in the program's place, in float32 on the chip:
        its front, checked as the program's fronts are."""
        import jax.numpy as jnp
        ref = reference_module(self.config)
        pts = self._scores(ref, jnp, jnp.float32)
        return self._check([ref.pareto_front(pts, np.arange(self.designs))])


class NetworkSearch(_Search):
    """`pareto_search` over the network grid, device materialization."""

    def setup(self):
        from repro.core.power import Traffic
        self.flow = Traffic(**self.config["traffic"])
        super().setup()

    def _search(self):
        from repro.core.search import pareto_search
        return pareto_search(self.flow,
                             topologies=tuple(self.grid["topologies"]),
                             chunk_size=int(self.traffic["chunk_size"]),
                             **self.grid["axes"])

    def _d2h_bytes(self):
        """The fold copies every metric and network column of a chunk back
        as float64, and its topology ids as int64."""
        from repro.core.sweep import METRIC_FIELDS
        from repro.core.topology import MODEL_FIELDS
        rows = int(self.traffic["chunk_size"])
        return rows * 8 * (len(METRIC_FIELDS) + len(MODEL_FIELDS) + 1)

    def _score(self, ref, s, e, xp, dtype):
        return ref.score_rows(self.grid, self.config["traffic"], s, e, xp,
                              dtype)


class CodesignSearch(_Search):
    """`codesign_pareto` over the network grid x the chiplet mixes; design
    point `mix * n + network row`, as the program numbers them."""

    rate = "joint_designs_per_s"

    def __init__(self, cell):
        super().__init__(cell)
        self.mixes = self.config["mixes"]
        self.designs = self.n * len(self.mixes)
        if self.n % self.block:
            raise ValueError("reference_block must divide the network grid")

    def setup(self):
        from repro.core.accelerator import ChipletSpec
        from repro.core.workloads import Layer, Workload
        w = self.config["workload"]
        self.wl = Workload(w["name"], [Layer(**l) for l in w["layers"]])
        self.chiplets = [[ChipletSpec(**c) for c in mix] for mix in self.mixes]
        super().setup()

    def _search(self):
        from repro.core.search import codesign_pareto
        front, _ = codesign_pareto(
            self.wl, self.chiplets, topologies=tuple(self.grid["topologies"]),
            chunk_size=int(self.traffic["chunk_size"]),
            **self.config["accelerator"], **self.grid["axes"])
        return front

    def _d2h_bytes(self):
        """The fold copies the objectives of every mix back as float64."""
        from repro.core.search import ACCEL_OBJECTIVES
        return (8 * len(ACCEL_OBJECTIVES) * len(self.mixes)
                * int(self.traffic["chunk_size"]))

    def _score(self, ref, s, e, xp, dtype):
        mix, r0 = divmod(s, self.n)     # a block never spans two mixes
        return ref.score_joint_rows(self.grid, self.config["workload"]["layers"],
                                    self.mixes[mix], self.config["accelerator"],
                                    r0, r0 + (e - s), xp, dtype)


MS = 1e6  # ns


def made_unit():
    """A made trace of one search, for the readers' tests: (trace,
    counters).  Two chunks of decode + body + single, one front scan, in a
    20 ms unit."""
    mods = [Event("jit_decode(1)", 1 * MS, 2 * MS),
            Event("jit_body(2)", 2 * MS, 4 * MS),
            Event("jit_single(3)", 4 * MS, 5 * MS),
            Event("jit_decode(1)", 6 * MS, 7 * MS),
            Event("jit_body(2)", 7 * MS, 9 * MS),
            Event("jit__pareto_mask_core(4)", 12 * MS, 18 * MS)]
    return (TraceData({0: mods}, {}, [Event("bench.unit", 0, 20 * MS)]),
            {"chunks_per_search": 2})
