"""Pareto/co-design search benchmark: chunked streaming vs monolithic vs
scalar evaluation, with exact front verification.

Four sections:

  * network grid — the pure interposer-network design space (topology x
    gateways x lambda x memory BW x modulation x geometry x device corner):
    monolithic `sweep` vs `sweep_chunked` streaming vs the scalar dataclass
    loop (sampled), plus streaming-vs-monolithic Pareto front equality.
  * streaming pipeline — the same streaming engine timed in its three
    execution modes on a >= 1e6-point grid (full mode): host-serial
    (per-chunk numpy materialization, prefetch 0), device-serial (jitted
    mixed-radix decode, prefetch 0), and device-pipelined (decode + a
    depth-2 prefetch queue overlapping host folds with device compute).
    All three must return bit-identical MinReducer states; the pipelined
    path must beat host-serial by >= 1.2x in full mode (reported but
    exempted in smoke, where per-chunk dispatch dominates the tiny grid).
  * co-design grid — the same network axes crossed with a chiplet-mix
    library through the vmapped accelerator kernel: >= 1e6 joint design
    points in full mode, evaluated chunked under bounded memory, with the
    extracted (latency, energy, power) front verified *exactly* against the
    full point cloud (every front point mutually non-dominated by O(k^2)
    brute force; every grid point dominated by or equal to a front member —
    with transitive dominance this is equivalent to the O(n^2) pairwise
    reference, but streams in O(n * front) blocks).  Smoke mode additionally
    runs the literal O(n^2) brute force.
  * refined front — `refine_codesign` on the top-3 best-EDP frontier seeds:
    joint relaxed gradient descent over accelerator + network axes, rounded
    back to feasible integer designs and exactly re-scored, merged into the
    seed front.  The merged front must weakly dominate the seed front
    (required check, verified against `pareto_mask_reference`); in full mode
    at least one seed must strictly improve (exempted in smoke, where the
    shortened descent may not escape an exactly-scored seed).
  * trust-region refined front — the same seeds refined with
    `method="trust_region"` (second-order log-space trust-region descent +
    coordinate-wise integer line search, n_gateways added to the discrete
    axes) jointly against a three-CNN workload batch (weighted-geomean EDP).
    Two required checks in BOTH modes: the trust-region front must weakly
    dominate the first-order refined front (merging unions the point sets,
    so this holds by construction — the gate re-verifies with the O(n^2)
    brute-force reference that the merge machinery lost nothing), and every
    trust-region design's per-workload metrics must re-score bit-identically
    through a standalone `evaluate_accelerator_grid` call.

Acceptance bars (recorded in the artifact, asserted by the smoke tests and
benchmarks/run.py): chunked evaluation throughput within 1.5x of the
monolithic jitted call (2x in smoke, where per-chunk dispatch overhead is
not amortized), batched >= 20x the scalar loop (2x in smoke), fronts exactly
equal between the streaming and monolithic paths.

REPRO_SMOKE=1 shrinks both grids so CI finishes in seconds.
"""

from __future__ import annotations

import json
import resource
import time
from pathlib import Path

import numpy as np

from repro.core import CNN_WORKLOADS, ChipletSpec
from repro.core.accelerator import evaluate_accelerator_grid
from repro.core.search import (
    OBJECTIVES,
    _front_of,
    codesign_config_at,
    codesign_pareto,
    front_is_exact,
    merge_fronts,
    pareto_front,
    pareto_mask_reference,
    pareto_search,
    refine_front,
    refine_front_point,
)
from repro.core.sweep import (
    ChunkReducer,
    MinReducer,
    _network_columns_arrays,
    build_grid,
    grid_spec,
    network_columns_device,
    sweep,
    sweep_chunked,
)
from repro.core.power import evaluate_network
from repro.env import smoke_mode

ARTIFACTS = Path(__file__).resolve().parent / "artifacts"

TOPOLOGIES = ("sprint", "spacx", "tree", "trine")

# 15 * 6 * 6 * 4 * 4 * 4 = 34560 per topology; x4 topologies = 138240
FULL_NET_AXES = dict(
    n_gateways=tuple(range(8, 68, 4)),
    n_lambda=(2, 4, 8, 12, 16, 24),
    mem_bw_bytes_per_s=(25e9, 50e9, 75e9, 100e9, 150e9, 200e9),
    modulation_rate_bps=(8e9, 10e9, 12e9, 16e9),
    interposer_side_cm=(2.0, 3.0, 4.0, 5.0),
)
FULL_NET_AXES["mzi.insertion_loss_db"] = (0.5, 1.0, 1.5, 2.0)

# big enough that one jitted call amortizes dispatch (the throughput bars
# compare steady-state paths, not fixed overheads), small enough for CI
SMOKE_NET_AXES = dict(
    n_gateways=(8, 16, 32, 64),
    n_lambda=(4, 8, 16),
    mem_bw_bytes_per_s=(50e9, 100e9, 200e9),
    modulation_rate_bps=(10e9, 12e9),
)

# extra axis for the pipeline section: 138240 x 8 = 1,105,920 streaming rows
PIPE_EXTRA_AXIS = dict(n_mem_chiplets=(2, 3, 4, 6, 8, 12, 16, 24))

# the device-pipelined streaming path must beat the host-serial streaming
# path by this factor on the full-mode (>= 1e6 point) grid
PIPELINE_SPEEDUP_BAR = 1.2


def _mix_library(smoke: bool):
    """Chiplet-mix axis of the co-design grid (x8 in full mode -> the
    138240-network grid becomes a 1,105,920-point joint space)."""
    C = ChipletSpec
    mixes = [
        [C(512, 32)],                                      # CrossLight homog.
        [C(512, 9), C(512, 27), C(512, 49), C(512, 128)],  # paper Fig. 5 mix
        [C(1024, 16)],
        [C(256, 9), C(256, 49)],
        [C(512, 9), C(512, 128)],
        [C(256, 16), C(256, 64), C(256, 256)],
        [C(2048, 8)],
        [C(384, 27), C(384, 81), C(256, 243)],
    ]
    return mixes[:3] if smoke else mixes


class _NullReducer(ChunkReducer):
    """Counts rows; used to time pure streaming evaluation throughput."""

    def step(self, carry, chunk):
        return (carry or 0) + (chunk.stop - chunk.start)


def _best_of(fn, repeats: int = 3):
    """(best wall seconds, last result) — damps 2-core CI timer noise."""
    best, out = float("inf"), None
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn()
        best = min(best, time.perf_counter() - t0)
    return best, out


def _scalar_sample_cps(traffic, grid, sample: int = 96) -> float:
    """configs/sec of the scalar dataclass loop on a strided grid sample."""
    idx = np.linspace(0, grid.n - 1, num=min(sample, grid.n)).astype(int)
    t0 = time.perf_counter()
    for i in idx:
        net, d = grid.row_network(int(i))
        evaluate_network(net, traffic, d)
    return idx.size / (time.perf_counter() - t0)


def _plot_front(front, points: np.ndarray, path: Path, title: str) -> bool:
    """artifacts/pareto_front.png: the evaluated cloud (neutral context, a
    strided sample) with the extracted frontier as the single highlighted
    series, log-log latency x energy.  One series -> direct labels, no
    legend; the JSON artifact is the data/table view.  Returns False when
    matplotlib is unavailable (optional dependency)."""
    try:
        import matplotlib
        matplotlib.use("Agg")
        import matplotlib.pyplot as plt
    except Exception:
        return False
    surface, ink, muted, series = "#fcfcfb", "#0b0b0b", "#52514e", "#2a78d6"
    cloud = points[::max(1, points.shape[0] // 20000)]
    order = np.argsort(front.points[:, 0])
    fx, fy = front.points[order, 0], front.points[order, 1]
    fig, ax = plt.subplots(figsize=(7, 4.6), dpi=130)
    fig.patch.set_facecolor(surface)
    ax.set_facecolor(surface)
    ax.scatter(cloud[:, 0], cloud[:, 1], s=3, c="#c9c8c2", linewidths=0,
               rasterized=True, zorder=1)
    ax.plot(fx, fy, color=series, lw=2, zorder=3)
    ax.scatter(fx, fy, s=18, c=series, edgecolors=surface, linewidths=0.8,
               zorder=4)
    i = int(np.argmin(fx * fy))
    ax.annotate("best EDP", (fx[i], fy[i]), textcoords="offset points",
                xytext=(8, -12), color=muted, fontsize=9)
    ax.set_xscale("log")
    ax.set_yscale("log")
    ax.set_xlabel("latency (s)", color=muted)
    ax.set_ylabel("energy (J)", color=muted)
    ax.set_title(title, color=ink, fontsize=11, loc="left")
    ax.tick_params(colors=muted, labelsize=8)
    for s in ax.spines.values():
        s.set_color("#d8d7d2")
    ax.grid(True, which="major", color="#ececea", lw=0.6, zorder=0)
    fig.tight_layout()
    fig.savefig(path, facecolor=surface)
    plt.close(fig)
    return True


def _edp_argmin(front) -> int:
    lat = front.points[:, list(front.objectives).index("latency_s")]
    en = front.points[:, list(front.objectives).index("energy_j")]
    return int(front.indices[int(np.argmin(lat * en))])


def run(csv: bool = True, smoke: bool = None) -> dict:
    if smoke is None:
        smoke = smoke_mode()
    axes = SMOKE_NET_AXES if smoke else FULL_NET_AXES
    mixes = _mix_library(smoke)
    wl = CNN_WORKLOADS["ResNet18"]()
    traffic = wl.traffic()
    spec = grid_spec(TOPOLOGIES, **axes)
    n_net = spec.n
    n_joint = n_net * len(mixes)
    # smoke times the chunked machinery on a single full-grid chunk (per-
    # chunk dispatch is a fixed cost the tiny CI grid cannot amortize);
    # streaming with many chunks is exercised by the pareto_search call and
    # the co-design section either way
    net_chunk = n_net if smoke else 65536
    search_chunk = max(1, n_net // 3) if smoke else 65536
    cd_chunk = n_net if smoke else 9216  # timed path; 9216 divides 138240
    cd_search_chunk = max(1, n_net // 2) if smoke else 9216
    ratio_bar = 2.0 if smoke else 1.5
    speedup_bar = 2.0 if smoke else 20.0

    # ---- section A: network grid, chunked vs monolithic vs scalar --------
    mono_s, res = _best_of(lambda: sweep(traffic, topologies=TOPOLOGIES,
                                         **axes))
    chunk_s, counted = _best_of(lambda: sweep_chunked(
        traffic, _NullReducer(), topologies=TOPOLOGIES,
        chunk_size=net_chunk, **axes))
    assert counted == n_net
    grid = build_grid(TOPOLOGIES, **axes)
    scalar_cps = _scalar_sample_cps(traffic, grid)
    mono_front = pareto_front(res)
    t0 = time.perf_counter()
    stream_front = pareto_search(traffic, topologies=TOPOLOGIES,
                                 chunk_size=search_chunk, **axes)
    net_search_s = time.perf_counter() - t0
    net_pts = np.stack([res.metrics[k] for k in OBJECTIVES], -1)
    net_fronts_equal = (
        np.array_equal(mono_front.points, stream_front.points)
        and np.array_equal(mono_front.indices, stream_front.indices))
    net_front_exact = front_is_exact(stream_front, net_pts)
    if smoke:
        net_front_exact = net_front_exact and np.array_equal(
            np.sort(stream_front.indices),
            np.where(pareto_mask_reference(net_pts))[0])

    network = {
        "n_configs": n_net,
        "chunk_size": net_chunk,
        "monolithic_s": mono_s,
        "chunked_s": chunk_s,
        "monolithic_configs_per_s": n_net / mono_s,
        "chunked_configs_per_s": n_net / chunk_s,
        "chunked_over_monolithic": chunk_s / mono_s,
        "scalar_configs_per_s": scalar_cps,
        "batched_over_scalar": (n_net / mono_s) / scalar_cps,
        "front_size": stream_front.size,
        "pareto_search_s": net_search_s,
        "best_config": stream_front.configs(spec)[0],
    }

    # ---- section A2: streaming pipeline, host-serial vs device-pipelined -
    pipe_axes = dict(axes) if smoke else dict(axes, **PIPE_EXTRA_AXIS)
    n_pipe = grid_spec(TOPOLOGIES, **pipe_axes).n
    pipe_chunk = max(1, n_pipe // 3) if smoke else 65536

    def _stream(mat: str, depth: int):
        return sweep_chunked(
            traffic, MinReducer("energy_j"), topologies=TOPOLOGIES,
            chunk_size=pipe_chunk, materialize=mat, prefetch=depth,
            **pipe_axes)

    _stream("device", 2)  # compile decode + engine at the pipeline shape
    reps = 3 if smoke else 2
    host_s, host_best = _best_of(lambda: _stream("host", 0), repeats=reps)
    dev_s, dev_best = _best_of(lambda: _stream("device", 0), repeats=reps)
    pipe_s, pipe_best = _best_of(lambda: _stream("device", 2), repeats=reps)
    pipe_identical = (
        host_best["index"] == dev_best["index"] == pipe_best["index"]
        and host_best["value"] == dev_best["value"] == pipe_best["value"])
    pipe_speedup = host_s / pipe_s
    pipeline = {
        "n_configs": n_pipe,
        "chunk_size": pipe_chunk,
        "prefetch_depth": 2,
        "host_serial_s": host_s,
        "device_serial_s": dev_s,
        "pipelined_s": pipe_s,
        "host_serial_configs_per_s": n_pipe / host_s,
        "device_serial_configs_per_s": n_pipe / dev_s,
        "pipelined_configs_per_s": n_pipe / pipe_s,
        "pipelined_over_host_serial": pipe_speedup,
        "overlap_gain_over_device_serial": dev_s / pipe_s,
        "speedup_bar": PIPELINE_SPEEDUP_BAR,
        "best_index": int(host_best["index"]),
        "best_energy_j": float(host_best["value"]),
    }

    # ---- section B: co-design grid (network x chiplet mix) ---------------
    # both reference paths build nets with the SAME traced program the
    # streaming co-design engine runs (network_columns_device) — XLA and
    # numpy transcendentals differ in the last ulp, so the exact-front
    # equality checks below require the traced nets, not the numpy path
    def eval_chunked():
        rows = 0
        for start in range(0, n_net, cd_chunk):
            stop = min(start + cd_chunk, n_net)
            cols, topo_id = spec.chunk_cols(start, stop)
            nets = network_columns_device(cols, topo_id, spec.topologies)
            evaluate_accelerator_grid(
                wl, mixes, nets, cols,
                cols["n_mem_chiplets"] * cols["mem_bw_bytes_per_s"])
            rows += stop - start
        return rows

    def eval_monolithic():
        cols, topo_id = spec.chunk_cols(0, n_net)
        nets = network_columns_device(cols, topo_id, spec.topologies)
        return evaluate_accelerator_grid(
            wl, mixes, nets, cols,
            cols["n_mem_chiplets"] * cols["mem_bw_bytes_per_s"])

    # warm the chunk-shaped kernel so the chunked timing is steady-state
    # (the monolithic _best_of self-warms: its first repeat compiles, and
    # best-of keeps the warm repeat)
    cols_w, topo_w = spec.chunk_cols(0, min(cd_chunk, n_net))
    evaluate_accelerator_grid(
        wl, mixes, network_columns_device(cols_w, topo_w, spec.topologies),
        cols_w, cols_w["n_mem_chiplets"] * cols_w["mem_bw_bytes_per_s"])
    cd_chunk_s, _ = _best_of(eval_chunked, repeats=3 if smoke else 2)

    t0 = time.perf_counter()
    cd_front, _ = codesign_pareto(wl, mixes, topologies=TOPOLOGIES,
                                  chunk_size=cd_search_chunk, **axes)
    cd_search_s = time.perf_counter() - t0

    # bounded-memory evidence: the process high-water mark is sampled after
    # ALL chunked co-design work but before the monolithic full-grid
    # evaluation below ever runs, so it reflects the streaming path (plus
    # section A's much smaller network-only monolithic sweep), not the
    # monolithic co-design working set
    peak_rss_after_chunked_mb = (
        resource.getrusage(resource.RUSAGE_SELF).ru_maxrss // 1024)

    cd_mono_s, cd_out = _best_of(eval_monolithic, repeats=3 if smoke else 2)

    cd_pts = np.stack([cd_out[k] for k in OBJECTIVES], -1).reshape(-1, 3)
    cd_mono_front = _front_of(cd_pts, np.arange(cd_pts.shape[0]), OBJECTIVES)
    cd_fronts_equal = (
        np.array_equal(cd_front.points, cd_mono_front.points)
        and np.array_equal(cd_front.indices, cd_mono_front.indices))
    cd_front_exact = front_is_exact(cd_front, cd_pts)
    if smoke:
        cd_front_exact = cd_front_exact and np.array_equal(
            np.sort(cd_front.indices),
            np.where(pareto_mask_reference(cd_pts))[0])

    ARTIFACTS.mkdir(exist_ok=True)
    plotted = _plot_front(
        cd_front, cd_pts, ARTIFACTS / "pareto_front.png",
        f"ResNet18 co-design (lat, energy, power) frontier — "
        f"{n_joint:,} network x chiplet-mix points, {cd_front.size} on "
        f"front (latency-energy projection)")

    # bounded memory: streaming holds one chunk of joint lanes + the front
    n_layers = len(wl.layers)
    chunk_bytes = len(mixes) * cd_chunk * n_layers * 8
    mono_bytes = len(mixes) * n_net * n_layers * 8

    # ---- gradient refinement from the best EDP front point ---------------
    best_joint = _edp_argmin(cd_front)
    best_cfg = codesign_config_at(spec, mixes, best_joint)
    refine = refine_front_point(spec, traffic, best_joint % n_net,
                                steps=8 if smoke else 48, lr=0.1)

    # ---- refined front: joint accelerator+network refinement -------------
    # refine the top-k best-EDP frontier seeds jointly over accelerator axes
    # (per-chiplet n_units/vector_size, mac_rate_hz, lambda_slot_energy_j)
    # and network axes, round-and-rescore to feasible integer designs, and
    # merge back into the seed front
    t0 = time.perf_counter()
    rf = refine_front(cd_front, spec, mixes, wl, top_k=3,
                      steps=6 if smoke else 32, lr=0.1)
    refined_front_s = time.perf_counter() - t0
    merged_front = rf["front"]
    # required dominance gate, re-verified with the O(n^2) reference
    # independent of refine_front's internal assertion: the merged front is
    # the exact front of (seed points ∪ refined points), so every seed point
    # still on that union front must appear verbatim in the merged front,
    # and every other seed point is dominated by a merged member
    union = np.concatenate([merged_front.points, cd_front.points])
    seed_on_union = pareto_mask_reference(union)[merged_front.size:]
    seed_present = np.array([
        bool((merged_front.points == p).all(-1).any())
        for p in cd_front.points])
    refined_dominates = bool(np.all(~seed_on_union | seed_present))

    # ---- trust-region multi-workload refined front -----------------------
    # refine the same top-3 seeds with the second-order engine, jointly
    # against a three-CNN batch (scalarized as weighted-geomean EDP), with
    # n_gateways added to the refined axes so the coordinate-wise integer
    # line search walks a network axis as well as the chiplet counts; the
    # front's points stay the FIRST workload's (ResNet18) exact metrics, so
    # they are directly comparable with the first-order front
    tr_workloads = [wl, CNN_WORKLOADS["MobileNetV2"](),
                    CNN_WORKLOADS["EfficientNetB0"]()]
    tr_axes = ("modulation_rate_bps", "mem_bw_bytes_per_s",
               "interposer_side_cm", "mzi.insertion_loss_db", "n_gateways")
    t0 = time.perf_counter()
    rf_tr = refine_front(cd_front, spec, mixes, tr_workloads, top_k=3,
                         method="trust_region", refine_axes=tr_axes,
                         steps=6 if smoke else 32)
    tr_front_s = time.perf_counter() - t0
    # union the trust-region front with the first-order front: weak
    # dominance over the first-order front then holds by construction, and
    # the brute-force re-verification below confirms the merge machinery
    # lost nothing (same pattern as the seed-front gate above)
    tr_front = merge_fronts(rf_tr["front"], merged_front)
    tr_union = np.concatenate([tr_front.points, merged_front.points])
    fo_on_union = pareto_mask_reference(tr_union)[tr_front.size:]
    fo_present = np.array([
        bool((tr_front.points == p).all(-1).any())
        for p in merged_front.points])
    tr_dominates_fo = bool(np.all(~fo_on_union | fo_present))

    # every trust-region design's per-workload metrics must re-score
    # bit-identically through a standalone evaluate_accelerator_grid call
    # on its reported integer config
    def _rescore_exact(r) -> bool:
        cfg = dict(r["refined"]["config"])
        chips = cfg.pop("chiplets")
        cfg.pop("mix")
        topo = cfg.pop("topology")
        mac = cfg.pop("mac_rate_hz")
        slot = cfg.pop("lambda_slot_energy_j")
        c1 = {k: np.full(1, v, np.float64)
              for k, v in dict(spec.base, **cfg).items()}
        n1 = _network_columns_arrays(c1, np.zeros(1, np.int64), (topo,))
        mbw = c1["n_mem_chiplets"] * c1["mem_bw_bytes_per_s"]
        for w, per in zip(tr_workloads, r["refined"]["per_workload"]):
            o = evaluate_accelerator_grid(
                w, [chips], n1, c1, mbw, mac_rate_hz=mac,
                lambda_slot_energy_j=slot)
            if any(float(o[k][0, 0]) != v for k, v in per.items()):
                return False
        return True

    tr_rescore_exact = all(_rescore_exact(r) for r in rf_tr["results"])

    codesign = {
        "n_networks": n_net,
        "n_mixes": len(mixes),
        "n_joint_points": n_joint,
        "n_layers": n_layers,
        "chunk_size": cd_chunk,
        "chunked_s": cd_chunk_s,
        "monolithic_s": cd_mono_s,
        "chunked_points_per_s": n_joint / cd_chunk_s,
        "monolithic_points_per_s": n_joint / cd_mono_s,
        "chunked_over_monolithic": cd_chunk_s / cd_mono_s,
        "pareto_search_s": cd_search_s,
        "front_size": cd_front.size,
        "chunk_working_set_bytes": chunk_bytes,
        "monolithic_working_set_bytes": mono_bytes,
        "peak_rss_after_chunked_mb": peak_rss_after_chunked_mb,
        "best_edp_config": {k: (v if not isinstance(v, list) else
                                [str(c) for c in v])
                            for k, v in best_cfg.items()},
        "refined_edp_improvement": refine["improvement"],
        "plot": "pareto_front.png" if plotted else None,
    }

    best_gain = max(r["improvement"] for r in rf["results"])
    refined_front = {
        "seeds_refined": len(rf["results"]),
        "seed_front_size": cd_front.size,
        "merged_front_size": merged_front.size,
        "n_improved": rf["n_improved"],
        "best_improvement": best_gain,
        "refine_front_s": refined_front_s,
        "sensitivity": rf["sensitivity"],
        "improvements": [r["improvement"] for r in rf["results"]],
        "n_candidates": [r["n_candidates"] for r in rf["results"]],
    }

    tr_best_gain = max(r["improvement"] for r in rf_tr["results"])
    trust_region_front = {
        "seeds_refined": len(rf_tr["results"]),
        "workloads": rf_tr["results"][0]["workloads"],
        "first_order_front_size": merged_front.size,
        "trust_region_front_size": tr_front.size,
        "n_improved": rf_tr["n_improved"],
        "best_improvement": tr_best_gain,
        "refine_front_s": tr_front_s,
        "improvements": [r["improvement"] for r in rf_tr["results"]],
        "tr_accepted": [r["tr_stats"]["accepted"]
                        for r in rf_tr["results"]],
        "tr_rejected": [r["tr_stats"]["rejected"]
                        for r in rf_tr["results"]],
        "line_search": [r["line_search"] for r in rf_tr["results"]],
        "sensitivity": rf_tr["sensitivity"],
    }

    checks = {
        "codesign_grid_at_least_1e6": n_joint >= 1_000_000,
        "net_front_streaming_equals_monolithic": bool(net_fronts_equal),
        "net_front_matches_bruteforce": bool(net_front_exact),
        "codesign_front_streaming_equals_monolithic": bool(cd_fronts_equal),
        "codesign_front_matches_bruteforce": bool(cd_front_exact),
        "chunked_within_ratio_bar_network":
            network["chunked_over_monolithic"] <= ratio_bar,
        "chunked_within_ratio_bar_codesign":
            codesign["chunked_over_monolithic"] <= ratio_bar,
        "batched_over_scalar_bar": network["batched_over_scalar"]
            >= speedup_bar,
        "pipeline_modes_bit_identical": bool(pipe_identical),
        "pipeline_grid_at_least_1e6": n_pipe >= 1_000_000,
        "pipelined_speedup_at_least_1p2":
            pipe_speedup >= PIPELINE_SPEEDUP_BAR,
        "refinement_improves": refine["improvement"] >= -1e-12,
        "refined_front_dominates_seed": refined_dominates,
        "refined_improves_a_seed": rf["n_improved"] >= 1,
        "trust_region_front_dominates_first_order": tr_dominates_fo,
        "trust_region_rescore_bit_identical": tr_rescore_exact,
    }
    # mode-dependent expectations (the grid sizes, timing bars that a tiny
    # CI grid cannot amortize, and whether a handful of smoke-length descent
    # steps must strictly beat an exactly-scored seed) are exempted in smoke
    # but still computed and flagged — never silently rewritten; every other
    # check must hold in both modes.  The dominance gate and the pipeline
    # bit-identity gate are required in BOTH modes: merging can never lose
    # seed points, and scheduling can never change results.
    smoke_exempt = ("codesign_grid_at_least_1e6", "refined_improves_a_seed",
                    "pipeline_grid_at_least_1e6",
                    "pipelined_speedup_at_least_1p2")
    required = [k for k in checks if smoke is False or k not in smoke_exempt]
    out = {
        "smoke": smoke,
        "ratio_bar": ratio_bar,
        "speedup_bar": speedup_bar,
        "network": network,
        "pipeline": pipeline,
        "codesign": codesign,
        "refine": {k: refine[k] for k in
                   ("start_value", "refined_value", "improvement",
                    "refine_axes", "refined")},
        "refined_front": refined_front,
        "trust_region_front": trust_region_front,
        "checks": checks,
        "required_checks": required,
        "pass": all(checks[k] for k in required),
    }

    (ARTIFACTS / "pareto_bench.json").write_text(json.dumps(out, indent=2))

    if csv:
        print(f"pareto/net,{mono_s * 1e6 / n_net:.2f},"
              f"monolithic {n_net / mono_s:,.0f} cfg/s over {n_net}")
        print(f"pareto/net_chunked,{chunk_s * 1e6 / n_net:.2f},"
              f"chunked {n_net / chunk_s:,.0f} cfg/s "
              f"({network['chunked_over_monolithic']:.2f}x mono, "
              f"bar {ratio_bar}x)")
        print(f"pareto/net_scalar,{1e6 / scalar_cps:.2f},"
              f"{scalar_cps:,.0f} cfg/s; batched "
              f"{network['batched_over_scalar']:.0f}x (bar {speedup_bar}x)")
        print(f"pareto/pipeline,{pipe_s * 1e6 / n_pipe:.2f},"
              f"{n_pipe} rows: host-serial {n_pipe / host_s:,.0f} cfg/s, "
              f"device-serial {n_pipe / dev_s:,.0f} cfg/s, pipelined "
              f"{n_pipe / pipe_s:,.0f} cfg/s "
              f"({pipe_speedup:.2f}x host-serial, bar "
              f"{PIPELINE_SPEEDUP_BAR}x)")
        print(f"pareto/codesign,{cd_mono_s * 1e6 / n_joint:.3f},"
              f"{n_joint} joint pts, chunked "
              f"{codesign['chunked_over_monolithic']:.2f}x mono, "
              f"front {cd_front.size}, peak rss after chunked "
              f"{codesign['peak_rss_after_chunked_mb']} MB")
        print(f"pareto/refine,0,EDP {refine['start_value']:.3e} -> "
              f"{refine['refined_value']:.3e} "
              f"({100 * refine['improvement']:.1f}% better)")
        print(f"pareto/refined_front,{refined_front_s * 1e6:.0f},"
              f"{refined_front['seeds_refined']} seeds refined, "
              f"{refined_front['n_improved']} improved "
              f"(best {100 * best_gain:.1f}%), front "
              f"{cd_front.size} -> {merged_front.size}")
        print(f"pareto/trust_region_front,{tr_front_s * 1e6:.0f},"
              f"{trust_region_front['seeds_refined']} seeds x "
              f"{len(trust_region_front['workloads'])} workloads, "
              f"{trust_region_front['n_improved']} improved "
              f"(best {100 * tr_best_gain:.1f}%), front "
              f"{merged_front.size} -> {tr_front.size}")
        for k, v in checks.items():
            flag = "PASS" if v else (
                "FAIL" if k in required else "SKIP(smoke)")
            print(f"pareto/check/{k},0,{flag}")
    return out


if __name__ == "__main__":
    run()
