"""Tests for the Pareto/co-design search engine (core.search) and the
chunked streaming evaluator (core.sweep.sweep_chunked):

  * jitted O(n log n) front extraction == O(n^2) brute force, on random
    clouds with ties/duplicates and on real sweep metrics for every topology
  * chunked streaming evaluation == monolithic evaluation, element for
    element, including the padded last chunk and multi-workload batching
  * merge-fronts associativity (front(A ∪ B) == front(front A ∪ front B))
  * co-design (network x chiplet-mix) front == brute force over the joint
    grid
  * jax.grad through the xp-generic topology kernels == float64 central
    finite differences of the scalar dataclass path
"""

import functools
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (
    CNN_WORKLOADS,
    ChipletSpec,
    NetworkParams,
    Traffic,
    evaluate_network,
)
from repro.core.devices import DEFAULT_DEVICES, replace_device_leaves
from repro.core.topology import TOPOLOGIES, TOPOLOGY_ARRAYS
from repro.core.power import EVAL_DEVICE_FIELDS, eval_network_math
from repro.core.sweep import (
    DEFAULT_TOPOLOGIES,
    ChunkReducer,
    MinReducer,
    build_grid,
    grid_spec,
    sweep,
    sweep_chunked,
)
from repro.core.search import (
    OBJECTIVES,
    ParetoFront,
    _coordinate_int_search,
    _trust_region_descent,
    codesign_pareto,
    merge_fronts,
    pareto_front,
    pareto_mask,
    pareto_mask_reference,
    pareto_search,
    refine_codesign,
    refine_continuous,
    refine_front,
    refine_front_point,
    refine_trust_region,
)

TRAFFIC = Traffic(bytes_read=2e8, bytes_written=7e7, n_transfers=320)

GRID_AXES = dict(
    n_gateways=(8, 16, 32, 64),
    n_lambda=(4, 8, 16),
    mem_bw_bytes_per_s=(50e9, 100e9, 200e9),
)


# ---------------------------------------------------------------------------
# pareto_mask vs brute force
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m", [2, 3])
@pytest.mark.parametrize("n", [1, 2, 3, 17, 400, 5000])
def test_pareto_mask_matches_bruteforce_random(m, n):
    rng = np.random.default_rng(n * 10 + m)
    pts = rng.normal(size=(n, m))
    assert np.array_equal(pareto_mask(pts), pareto_mask_reference(pts))


@pytest.mark.parametrize("m", [2, 3])
def test_pareto_mask_matches_bruteforce_ties_and_duplicates(m):
    rng = np.random.default_rng(7)
    # coarse integer grid => many per-objective ties and exact duplicates
    pts = rng.integers(0, 5, size=(600, m)).astype(float)
    mask, ref = pareto_mask(pts), pareto_mask_reference(pts)
    assert np.array_equal(mask, ref)
    # exact duplicates never dominate each other: all copies share a verdict
    dup = np.concatenate([pts, pts[:25]], axis=0)
    mask2 = pareto_mask(dup)
    assert np.array_equal(mask2[:600][:25] if False else mask2[600:],
                          mask2[:25])
    assert np.array_equal(mask2, pareto_mask_reference(dup))


def test_pareto_mask_all_identical_points_all_on_front():
    pts = np.ones((37, 3))
    assert pareto_mask(pts).all()


def test_pareto_mask_rejects_bad_shapes():
    with pytest.raises(ValueError):
        pareto_mask(np.zeros((4, 5)))
    assert pareto_mask(np.zeros((0, 3))).shape == (0,)


@pytest.mark.parametrize("topology", list(DEFAULT_TOPOLOGIES))
def test_front_on_real_sweep_metrics_per_topology(topology):
    """Front of real (latency, energy, power) sweep metrics == brute force,
    for every topology family including the electrical mesh."""
    res = sweep(TRAFFIC, topologies=(topology,), **GRID_AXES)
    front = pareto_front(res)
    pts = np.stack([res.metrics[k] for k in OBJECTIVES], -1)
    ref_idx = set(np.where(pareto_mask_reference(pts))[0].tolist())
    assert set(front.indices.tolist()) == ref_idx
    assert front.objectives == OBJECTIVES


def test_merge_fronts_associativity():
    rng = np.random.default_rng(3)
    pts = rng.normal(size=(900, 3))
    idx = np.arange(900)
    whole = merge_fronts(ParetoFront(OBJECTIVES, pts, idx))
    parts = [ParetoFront(OBJECTIVES, pts[s:s + 300], idx[s:s + 300])
             for s in (0, 300, 600)]
    part_fronts = [merge_fronts(p) for p in parts]
    merged = merge_fronts(*part_fronts)
    assert np.array_equal(whole.points, merged.points)
    assert np.array_equal(whole.indices, merged.indices)


# ---------------------------------------------------------------------------
# chunked streaming == monolithic
# ---------------------------------------------------------------------------


class _CollectReducer(ChunkReducer):
    """Test-only: concatenates every chunk's metrics (NOT bounded memory)."""

    def step(self, carry, chunk):
        carry = carry or []
        carry.append(chunk.metrics)
        return carry

    def finish(self, carry, spec):
        return {k: np.concatenate([c[k] for c in carry], axis=-1)
                for k in carry[0]}


@pytest.mark.parametrize("chunk_size", [1, 7, 64, 10_000])
def test_chunked_matches_monolithic(chunk_size):
    """Streaming chunks (including the repeat-padded last one) reproduce the
    monolithic metrics element for element."""
    res = sweep(TRAFFIC, **GRID_AXES)
    got = sweep_chunked(TRAFFIC, _CollectReducer(), chunk_size=chunk_size,
                        **GRID_AXES)
    for k, v in res.metrics.items():
        np.testing.assert_allclose(got[k], v, rtol=1e-15, err_msg=k)


def test_chunked_multi_workload_and_min_reducer():
    traffics = [CNN_WORKLOADS[n]().traffic() for n in ("LeNet5", "ResNet18")]
    got = sweep_chunked(traffics, _CollectReducer(), chunk_size=13,
                        **GRID_AXES)
    best = sweep_chunked(traffics, MinReducer("energy_j"), chunk_size=13,
                         **GRID_AXES)
    assert got["latency_s"].shape[0] == 2
    for w, t in enumerate(traffics):
        ref = sweep(t, **GRID_AXES)
        np.testing.assert_allclose(got["energy_j"][w], ref.metrics["energy_j"],
                                   rtol=1e-15)
        i, _ = ref.best("energy_j")
        assert int(best["index"][w]) == i


def test_streaming_pareto_matches_monolithic_and_bruteforce():
    res = sweep(TRAFFIC, **GRID_AXES)
    mono = pareto_front(res)
    stream = pareto_search(TRAFFIC, chunk_size=61, **GRID_AXES)
    assert np.array_equal(mono.points, stream.points)
    assert np.array_equal(mono.indices, stream.indices)
    pts = np.stack([res.metrics[k] for k in OBJECTIVES], -1)
    assert set(stream.indices.tolist()) == set(
        np.where(pareto_mask_reference(pts))[0].tolist())
    cfg = stream.configs(grid_spec(**GRID_AXES))[0]
    assert cfg["topology"] in DEFAULT_TOPOLOGIES


def test_pareto_search_multi_workload_returns_per_workload_fronts():
    traffics = [CNN_WORKLOADS[n]().traffic() for n in ("LeNet5", "VGG16")]
    fronts = pareto_search(traffics, chunk_size=40, **GRID_AXES)
    assert isinstance(fronts, list) and len(fronts) == 2
    for w, t in enumerate(traffics):
        mono = pareto_front(sweep(t, **GRID_AXES))
        assert np.array_equal(fronts[w].points, mono.points)


def test_chunked_shard_flag_single_device_noop():
    """shard=True must be a no-op (same results) on a single device; on
    multi-device hosts it lays chunk columns across devices."""
    a = sweep_chunked(TRAFFIC, _CollectReducer(), chunk_size=50, shard=True,
                      **GRID_AXES)
    b = sweep_chunked(TRAFFIC, _CollectReducer(), chunk_size=50, shard=False,
                      **GRID_AXES)
    for k in a:
        np.testing.assert_allclose(a[k], b[k], rtol=1e-15, err_msg=k)


def test_chunked_shard_multi_device_subprocess():
    """Real NamedSharding coverage: 4 simulated host devices (subprocess so
    the XLA flag applies), chunk size rounded up to a device multiple, and
    the sharded streaming argmin must match the monolithic sweep."""
    import subprocess
    import sys
    from pathlib import Path
    code = (
        "import jax, numpy as np\n"
        "assert jax.device_count() == 4, jax.device_count()\n"
        "from repro.core import CNN_WORKLOADS\n"
        "from repro.core.sweep import sweep, sweep_chunked, MinReducer\n"
        "t = CNN_WORKLOADS['ResNet18']().traffic()\n"
        "axes = dict(n_gateways=(8, 16, 32, 64), n_lambda=(2, 4, 8, 16))\n"
        "res = sweep(t, **axes)\n"
        "i, _ = res.best('energy_j')\n"
        "out = sweep_chunked(t, MinReducer('energy_j'), chunk_size=37,\n"
        "                    shard=True, **axes)\n"
        "assert out['index'] == i, (out['index'], i)\n"
        "assert abs(out['value'] - res.metrics['energy_j'][i]) < 1e-12\n")
    env = dict(os.environ)
    repo = Path(__file__).resolve().parents[1]
    env["PYTHONPATH"] = (str(repo / "src") + os.pathsep
                         + env.get("PYTHONPATH", "")).rstrip(os.pathsep)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stderr[-2000:]


def test_grid_spec_chunks_match_build_grid():
    spec = grid_spec(("tree", "trine"), n_gateways=(16, 32),
                     **{"mzi.insertion_loss_db": (0.5, 1.0, 2.0)})
    grid = build_grid(("tree", "trine"), n_gateways=(16, 32),
                      **{"mzi.insertion_loss_db": (0.5, 1.0, 2.0)})
    assert spec.n == grid.n
    cols, topo_id = spec.chunk_cols(5, 11)
    assert np.array_equal(topo_id, grid.topo_id[5:11])
    for k in grid.cols:
        assert np.array_equal(cols[k], grid.cols[k][5:11]), k
    for i in (0, 5, grid.n - 1):
        cfg = spec.config_at(i)
        assert cfg["topology"] == grid.row_topology(i)
        assert cfg["n_gateways"] == grid.cols["n_gateways"][i]


# ---------------------------------------------------------------------------
# co-design grid search
# ---------------------------------------------------------------------------


def test_codesign_front_matches_bruteforce():
    wl = CNN_WORKLOADS["LeNet5"]()
    mixes = [[ChipletSpec(512, 32)],
             [ChipletSpec(512, 9), ChipletSpec(512, 49)],
             [ChipletSpec(256, 16), ChipletSpec(256, 64),
              ChipletSpec(128, 128)]]
    axes = dict(n_gateways=(16, 32), n_lambda=(4, 8))
    front, spec = codesign_pareto(
        wl, mixes, topologies=("trine", "tree", "elec"), chunk_size=5, **axes)
    # brute force over the joint (mix x config) grid
    from repro.core.accelerator import evaluate_accelerator_grid
    from repro.core.sweep import _network_columns_arrays
    cols, topo_id = spec.chunk_cols(0, spec.n)
    nets = _network_columns_arrays(cols, topo_id, spec.topologies)
    out = evaluate_accelerator_grid(
        wl, mixes, nets, cols,
        cols["n_mem_chiplets"] * cols["mem_bw_bytes_per_s"])
    pts = np.stack([out[k] for k in OBJECTIVES], -1).reshape(-1, 3)
    assert set(front.indices.tolist()) == set(
        np.where(pareto_mask_reference(pts))[0].tolist())
    # padded-mix kernel: the 1-chiplet mix must behave as if unpadded
    assert out["latency_s"].shape == (3, spec.n)


def test_accelerator_grid_device_corner_sweep_scalar_nets():
    """(N,) device columns with scalar network fields must broadcast: a
    device-corner sweep at a fixed network is a supported grid shape."""
    from repro.core.accelerator import evaluate_accelerator_grid
    from repro.core.devices import device_columns
    from repro.core.topology import MODEL_FIELDS
    from repro.core import trine_network
    wl = CNN_WORKLOADS["LeNet5"]()
    net = trine_network(NetworkParams())
    nets = {f: np.float64(getattr(net, f)) for f in MODEL_FIELDS}
    dev = dict(device_columns())
    dev["mr.tuning_power_w"] = np.asarray([137e-6, 275e-6, 550e-6])
    out = evaluate_accelerator_grid(wl, [[ChipletSpec(512, 32)]], nets, dev,
                                    100e9)
    assert out["latency_s"].shape == (1, 3)
    # more trimming power per MR -> network energy must not decrease
    assert np.all(np.diff(out["network_energy_j"][0]) >= 0)


# ---------------------------------------------------------------------------
# gradient refinement
# ---------------------------------------------------------------------------


def _scalar_log_edp(topology, traffic, **overrides):
    """float64 scalar-dataclass-path log(EDP) — the FD reference."""
    dev_leaves = {k: v for k, v in overrides.items() if "." in k}
    params = {k: v for k, v in overrides.items() if "." not in k}
    p = NetworkParams(**params)
    d = replace_device_leaves(DEFAULT_DEVICES, dev_leaves)
    net = TOPOLOGIES[topology](p, d=d)
    rep = evaluate_network(net, traffic, d)
    return np.log(rep.energy_j) + np.log(rep.latency_s)


@pytest.mark.parametrize("axis,x0", [
    ("modulation_rate_bps", 12e9),
    ("mem_bw_bytes_per_s", 100e9),
    ("mzi.insertion_loss_db", 1.0),
])
def test_grad_matches_finite_differences(axis, x0):
    """One jax.grad step through the xp-generic trine kernel equals a
    float64 central finite difference of the scalar reference path (in
    log-log space, away from ceil/round quantization boundaries)."""
    spec = grid_spec(("trine",))
    cols = dict(spec.base)

    def loss(theta):
        c = {k: jnp.asarray(v) for k, v in cols.items()}
        c[axis] = jnp.exp(theta)
        fields = TOPOLOGY_ARRAYS["trine"](c, xp=jnp)
        dev = {k: c[k] for k in EVAL_DEVICE_FIELDS}
        m = eval_network_math(fields, dev, jnp.asarray(TRAFFIC.total_bits),
                              jnp.asarray(float(TRAFFIC.n_transfers)),
                              jnp.asarray(1.0))
        return jnp.log(m["energy_j"]) + jnp.log(m["latency_s"])

    theta0 = float(np.log(x0))
    g = float(jax.grad(loss)(jnp.asarray(theta0, jnp.float32)))
    h = 0.02
    f_hi = _scalar_log_edp("trine", TRAFFIC, **{axis: float(np.exp(theta0 + h))})
    f_lo = _scalar_log_edp("trine", TRAFFIC, **{axis: float(np.exp(theta0 - h))})
    fd = (f_hi - f_lo) / (2 * h)
    assert g == pytest.approx(fd, rel=5e-2, abs=5e-3), (g, fd)


def _relaxed_accel_log_edp(axis, j, value):
    """log-EDP of the relaxed accelerator kernel with one accelerator axis
    overridden by (traced) `value` — the loss `refine_codesign` descends.
    mac_rate is tiny so compute binds and the accelerator axes genuinely
    carry gradient; adaptive PCMC is off so the FD interval crosses no
    activation-step quantization boundary."""
    from repro.core.accelerator import _accel_mix_math, layer_columns
    from repro.core.topology import MODEL_FIELDS
    wl = CNN_WORKLOADS["LeNet5"]()
    spec = grid_spec(("trine",))
    cols = {k: jnp.asarray(np.float64(v)) for k, v in spec.base.items()}
    lc = {k: jnp.asarray(v) for k, v in layer_columns(wl).items()}
    units = jnp.asarray(np.asarray([96.0, 48.0]))
    vec = jnp.asarray(np.asarray([9.0, 49.0]))
    mac = jnp.asarray(np.float64(1e8))
    slot = jnp.asarray(np.float64(30e-15))
    if axis == "n_units":
        units = units.at[j].set(value)
    elif axis == "vector_size":
        vec = vec.at[j].set(value)
    elif axis == "mac_rate_hz":
        mac = value
    else:
        slot = value
    fields = TOPOLOGY_ARRAYS["trine"](cols, xp=jnp)
    nets1 = {k: jnp.reshape(fields[k], (1,)) for k in MODEL_FIELDS}
    dev1 = {k: jnp.reshape(cols[k], (1,)) for k in EVAL_DEVICE_FIELDS}
    mem_bw1 = jnp.reshape(
        cols["n_mem_chiplets"] * cols["mem_bw_bytes_per_s"], (1,))
    m = _accel_mix_math({"n_units": units, "vector_size": vec}, None, lc,
                        nets1, dev1, mem_bw1, mac, slot,
                        jnp.asarray(np.float64(16.0)),
                        adaptive=False, relaxed=True)
    return jnp.log(m["energy_j"][0]) + jnp.log(m["latency_s"][0])


@pytest.mark.parametrize("axis,j,x0", [
    ("n_units", 0, 96.0),
    ("n_units", 1, 48.0),
    ("vector_size", 0, 9.0),
    ("mac_rate_hz", None, 1e8),
    ("lambda_slot_energy_j", None, 30e-15),
])
def test_relaxed_accel_grad_matches_finite_differences(axis, j, x0):
    """jax.grad through the relaxed accelerator kernel (max(L/V, 1) pass
    count) equals float64 central finite differences of the same relaxed
    function, for every relaxable accelerator axis — mirroring the network-
    axis gradient checks above."""
    def loss(theta):
        return _relaxed_accel_log_edp(axis, j, jnp.exp(theta))

    theta0 = float(np.log(x0))
    g = float(jax.grad(loss)(jnp.asarray(theta0, jnp.float32)))
    h = 0.02
    with jax.enable_x64(True):
        f_hi = float(loss(jnp.asarray(theta0 + h, jnp.float64)))
        f_lo = float(loss(jnp.asarray(theta0 - h, jnp.float64)))
    fd = (f_hi - f_lo) / (2 * h)
    assert g == pytest.approx(fd, rel=5e-2, abs=5e-3), (g, fd)
    if axis in ("n_units", "mac_rate_hz"):
        # compute-bound by construction: these axes must genuinely move EDP
        assert abs(fd) > 1e-3, fd


def test_refine_continuous_improves_and_respects_bounds():
    t = CNN_WORKLOADS["ResNet18"]().traffic()
    r = refine_continuous("trine", {"n_gateways": 32}, t, steps=25, lr=0.1,
                          span=4.0)
    assert r["refined_value"] <= r["start_value"]
    for nm, v in r["refined"].items():
        lo, hi = r["start"][nm] / 4.0, r["start"][nm] * 4.0
        assert lo * (1 - 1e-9) <= v <= hi * (1 + 1e-9), nm
    assert set(r["metrics"]) >= {"latency_s", "energy_j", "power_w"}


def test_refine_front_point_from_pareto_search():
    t = CNN_WORKLOADS["ResNet18"]().traffic()
    axes = dict(n_gateways=(16, 32), n_lambda=(4, 8))
    front = pareto_search(t, topologies=("trine", "tree"), **axes)
    spec = grid_spec(("trine", "tree"), **axes)
    r = refine_front_point(spec, t, int(front.indices[0]), steps=10, lr=0.1)
    assert r["refined_value"] <= r["start_value"]
    assert r["topology"] in ("trine", "tree")


# ---------------------------------------------------------------------------
# guards: empty grids / mixes and eager objective validation
# ---------------------------------------------------------------------------


def test_codesign_pareto_empty_grid_and_mixes_raise():
    """Regression: an empty grid used to reach range(0, 0, 0) deep in the
    chunk loop (ValueError: range() arg 3 must not be zero); empty mixes
    crashed inside the mix-column builder.  Both must fail up front."""
    wl = CNN_WORKLOADS["LeNet5"]()
    mixes = [[ChipletSpec(256, 9)]]
    with pytest.raises(ValueError, match="empty grid"):
        codesign_pareto(wl, mixes, n_gateways=())
    with pytest.raises(ValueError, match="empty grid"):
        codesign_pareto(wl, mixes, topologies=())
    with pytest.raises(ValueError, match="chiplet mix"):
        codesign_pareto(wl, [])


def test_refine_objective_validated_eagerly():
    """Regression: an unknown objective used to surface as a bare KeyError
    from deep inside the jitted loss; both refiners must reject it before
    tracing, naming the valid vocabulary."""
    t = CNN_WORKLOADS["LeNet5"]().traffic()
    with pytest.raises(ValueError, match="valid objectives"):
        refine_continuous("trine", {}, t, objective="edp_j")
    wl, mixes, front, spec = _codesign_refine_setup()
    with pytest.raises(ValueError, match="valid objectives"):
        refine_codesign(spec, mixes, wl, int(front.indices[0]),
                        objective="edp_j")
    # metric objectives from each vocabulary still work
    r = refine_continuous("trine", {}, t, objective="power_w", steps=2)
    assert r["objective"] == "power_w"


# ---------------------------------------------------------------------------
# co-design refinement: relaxed descent + round-and-rescore
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=1)
def _codesign_refine_setup():
    wl = CNN_WORKLOADS["LeNet5"]()
    mixes = [[ChipletSpec(256, 9), ChipletSpec(128, 49)],
             [ChipletSpec(512, 32)],
             [ChipletSpec(128, 9), ChipletSpec(128, 27),
              ChipletSpec(64, 128)]]
    axes = dict(n_gateways=(16, 32), n_lambda=(4, 8))
    front, spec = codesign_pareto(wl, mixes, topologies=("trine", "tree"),
                                  chunk_size=7, **axes)
    return wl, mixes, front, spec


def test_refine_codesign_round_and_rescore_feasible_and_exact():
    """The refined point is always a feasible integer design, and its
    reported metrics are bit-identical to a standalone exact re-score of
    the refined config through `evaluate_accelerator_grid`."""
    from repro.core.accelerator import evaluate_accelerator_grid
    from repro.core.sweep import _network_columns_arrays
    wl, mixes, front, spec = _codesign_refine_setup()
    r = refine_codesign(spec, mixes, wl, int(front.indices[0]), steps=8)
    cfg = r["refined"]["config"]
    for c in cfg["chiplets"]:
        assert isinstance(c.n_units, int) and isinstance(c.vector_size, int)
        assert c.vector_size >= 1 and c.n_units >= 0
    assert any(c.n_units > 0 for c in cfg["chiplets"])
    # grid axes the refiner does not touch keep admissible integer values
    for nm in ("n_gateways", "n_lambda"):
        assert cfg[nm] == float(int(cfg[nm]))
    cols = {k: np.full(1, v, np.float64) for k, v in spec.base.items()}
    for k, v in cfg.items():
        if k in cols:
            cols[k][:] = float(v)
    nets = _network_columns_arrays(cols, np.zeros(1, np.int64),
                                   (cfg["topology"],))
    out = evaluate_accelerator_grid(
        wl, [cfg["chiplets"]], nets, cols,
        cols["n_mem_chiplets"] * cols["mem_bw_bytes_per_s"],
        mac_rate_hz=cfg["mac_rate_hz"],
        lambda_slot_energy_j=cfg["lambda_slot_energy_j"])
    for k, v in r["refined"]["metrics"].items():
        assert float(out[k][0, 0]) == v, k


def test_refine_codesign_improves_at_least_one_seed():
    """Acceptance: on >= 3 frontier seeds the refiner returns feasible
    integer designs, never worse than the seed, strictly better on at
    least one."""
    wl, mixes, front, spec = _codesign_refine_setup()
    order = np.argsort(front.points[:, 0] * front.points[:, 1])
    results = [refine_codesign(spec, mixes, wl, int(front.indices[i]),
                               steps=12)
               for i in order[:3]]
    for r in results:
        for c in r["refined"]["chiplets"]:
            assert isinstance(c.n_units, int)
            assert isinstance(c.vector_size, int)
        assert r["refined"]["value"] <= r["seed"]["value"]
        assert r["improvement"] >= 0.0
        assert set(r["sensitivity"]) >= {"modulation_rate_bps",
                                         "mac_rate_hz"}
    assert any(r["improvement"] > 0 for r in results)


def test_refine_front_dominates_seed_and_configs_roundtrip():
    """Property: the merged refined front weakly dominates the seed front
    (checked against the O(n^2) reference), and every merged row decodes to
    a config (refined rows to their refined design)."""
    wl, mixes, front, spec = _codesign_refine_setup()
    out = refine_front(front, spec, mixes, wl, top_k=3, steps=6)
    merged, seed = out["front"], out["seed_front"]
    union = np.concatenate([merged.points, seed.points])
    seed_on_union = pareto_mask_reference(union)[merged.size:]
    seed_present = np.array([bool((merged.points == p).all(-1).any())
                             for p in seed.points])
    assert np.all(~seed_on_union | seed_present)
    assert len(out["configs"]) == merged.size
    for cfg in out["configs"]:
        assert cfg["topology"] in ("trine", "tree")
        assert "chiplets" in cfg
    assert 0 <= out["n_improved"] <= len(out["results"])
    # sensitivities cover both network and accelerator axes
    assert set(out["sensitivity"]) >= {"modulation_rate_bps",
                                       "lambda_slot_energy_j"}


# ---------------------------------------------------------------------------
# second-order refinement: trust-region descent + integer line search
# ---------------------------------------------------------------------------


def test_trust_region_descent_exact_quadratic_converges():
    """On an anisotropic quadratic with its exact Hessian the loop takes
    pure accepted Newton steps (the model is exact, so rho == 1, nothing
    is ever rejected) and reaches the minimizer."""
    A = np.diag([1.0, 25.0])
    c = np.array([0.4, -0.7])

    def vg(x):
        d = np.asarray(x, np.float64) - c
        return 0.5 * float(d @ A @ d), A @ d

    lo, hi = np.full(2, -3.0), np.full(2, 3.0)
    best, theta, trace, g0, st = _trust_region_descent(
        vg, lambda x: A, np.zeros(2), lo, hi, steps=12)
    assert best == trace[-1] <= trace[0]
    assert np.allclose(theta, c, atol=1e-6)
    assert best == pytest.approx(0.0, abs=1e-10)
    assert st["rejected"] == 0 and st["accepted"] >= 1
    assert np.allclose(g0, -A @ c)  # float64 gradient at the seed


def test_trust_region_rejects_lying_gradient_and_shrinks_radius():
    """A gradient that points uphill makes every proposed step increase
    the exact objective: each one must be rejected on the exact re-score,
    the radius must shrink strictly after every rejection until it
    collapses, and the returned design is the untouched seed — the
    never-worse-than-seed guarantee under a hostile model."""
    def vg(x):
        x = np.asarray(x, np.float64)
        return float(x @ x), -2.0 * x  # honest value, lying gradient

    x0 = np.array([1.0, -1.5])
    best, theta, trace, _, st = _trust_region_descent(
        vg, lambda x: 2.0 * np.eye(2), x0,
        np.full(2, -4.0), np.full(2, 4.0), steps=30)
    assert st["accepted"] == 0 and st["rejected"] >= 3
    rt = st["radius_trace"]
    assert len(rt) == st["rejected"]
    assert all(b < a for a, b in zip(rt, rt[1:]))  # strictly shrinking
    assert st["stopped_early"] and st["final_radius"] < 1e-5
    assert best == trace[0] and len(trace) == 1
    assert np.array_equal(theta, x0)  # never worse than the seed


def test_trust_region_pins_against_box():
    """A minimizer outside the box: the loop walks to the boundary, then
    stops early once the box admits no further move, reporting the clipped
    boundary point."""
    def vg(x):
        d = np.asarray(x, np.float64) - 10.0
        return float(d @ d), 2.0 * d

    best, theta, trace, _, st = _trust_region_descent(
        vg, lambda x: 2.0 * np.eye(2), np.zeros(2),
        np.full(2, -1.0), np.full(2, 1.0), steps=20, radius=0.5)
    assert np.allclose(theta, 1.0)  # pinned at the upper corner
    assert st["stopped_early"]
    assert best == pytest.approx(2 * 81.0)


def test_coordinate_int_search_separable_optimum_and_memoization():
    """Separable convex scores: the walk reaches the exact integer optimum
    and the memo cache guarantees each design is scored exactly once."""
    calls = []

    def score(v):
        calls.append(1)
        return (v["a"] - 7) ** 2 + (v["b"] - 3) ** 2

    best, val, st = _coordinate_int_search(
        {"a": 2, "b": 10}, {"a": 1, "b": 1}, {"a": 16, "b": 16}, score)
    assert best == {"a": 7, "b": 3} and val == 0.0
    assert st["n_scored"] == len(calls)  # never re-scored
    assert st["n_sweeps"] >= 2


def test_coordinate_int_search_bounds_and_infeasible():
    """Bounds clamp the walk and +inf marks infeasible designs: the search
    settles on the best reachable feasible design, never leaving the box."""
    def score(v):
        if v["a"] + v["b"] > 9:
            return float("inf")
        return -(v["a"] + v["b"])

    best, val, st = _coordinate_int_search(
        {"a": 4, "b": 4}, {"a": 1, "b": 1}, {"a": 6, "b": 6}, score)
    assert best["a"] + best["b"] == 9 and val == -9.0
    assert 1 <= best["a"] <= 6 and 1 <= best["b"] <= 6


TR_AXES = ("modulation_rate_bps", "mem_bw_bytes_per_s",
           "interposer_side_cm", "n_gateways")


def test_refine_codesign_trust_region_never_worse_and_rescores_exact():
    """method="trust_region": the refined point is a feasible integer
    design, never worse than its seed, and its reported metrics re-score
    bit-identically through a standalone `evaluate_accelerator_grid` call
    — the same exactness contract the first-order engine is held to."""
    from repro.core.accelerator import evaluate_accelerator_grid
    from repro.core.sweep import _network_columns_arrays
    wl, mixes, front, spec = _codesign_refine_setup()
    r = refine_trust_region(spec, mixes, wl, int(front.indices[0]),
                            steps=6, refine_axes=TR_AXES)
    assert r["method"] == "trust_region"
    assert r["refined"]["value"] <= r["seed"]["value"]
    assert r["improvement"] >= 0.0
    st = r["tr_stats"]
    assert st["accepted"] + st["rejected"] == len(st["radius_trace"]) <= 6
    cfg = r["refined"]["config"]
    for c in cfg["chiplets"]:
        assert isinstance(c.n_units, int) and isinstance(c.vector_size, int)
    assert any(c.n_units > 0 for c in cfg["chiplets"])
    assert cfg["n_gateways"] == float(int(cfg["n_gateways"]))
    cols = {k: np.full(1, v, np.float64) for k, v in spec.base.items()}
    for k, v in cfg.items():
        if k in cols:
            cols[k][:] = float(v)
    nets = _network_columns_arrays(cols, np.zeros(1, np.int64),
                                   (cfg["topology"],))
    out = evaluate_accelerator_grid(
        wl, [cfg["chiplets"]], nets, cols,
        cols["n_mem_chiplets"] * cols["mem_bw_bytes_per_s"],
        mac_rate_hz=cfg["mac_rate_hz"],
        lambda_slot_energy_j=cfg["lambda_slot_energy_j"])
    for k, v in r["refined"]["metrics"].items():
        assert float(out[k][0, 0]) == v, k


def test_refine_codesign_tr_line_search_dominates_snap():
    """The integer line search is seeded at the floor/ceil snap winner, so
    its value weakly dominates the snap value on every seed; it must also
    actually explore (score additional integer designs) somewhere across
    three frontier seeds."""
    wl, mixes, front, spec = _codesign_refine_setup()
    order = np.argsort(front.points[:, 0] * front.points[:, 1])
    searches = []
    for i in order[:3]:
        r = refine_trust_region(spec, mixes, wl, int(front.indices[i]),
                                steps=4, refine_axes=TR_AXES)
        assert r["refined"]["value"] <= r["seed"]["value"]
        searches.append(r["line_search"])
    for s in searches:
        assert s["value"] <= s["snap_value"]
    assert any(s["n_scored"] > 1 for s in searches)


def test_refine_codesign_multiworkload_geomean_and_per_workload_rescore():
    """Joint refinement over two weighted workloads: per-workload exact
    metrics come back for seed and refined designs, the combined value is
    their weighted geometric mean, each per-workload dict re-scores
    bit-identically, and malformed weights are rejected eagerly."""
    from repro.core.accelerator import evaluate_accelerator_grid
    from repro.core.sweep import _network_columns_arrays
    wl, mixes, front, spec = _codesign_refine_setup()
    wls = [wl, CNN_WORKLOADS["ResNet18"]()]
    r = refine_trust_region(spec, mixes, wls, int(front.indices[0]),
                            steps=4, refine_axes=TR_AXES,
                            weights=(3.0, 1.0))
    assert r["workloads"] == [w.name for w in wls]
    assert r["weights"] == pytest.approx([0.75, 0.25])
    for blk in (r["seed"], r["refined"]):
        assert len(blk["per_workload"]) == 2
        edps = [m["energy_j"] * m["latency_s"] for m in blk["per_workload"]]
        geo = float(np.exp(0.75 * np.log(edps[0]) + 0.25 * np.log(edps[1])))
        assert blk["value"] == pytest.approx(geo, rel=1e-12)
    cfg = r["refined"]["config"]
    cols = {k: np.full(1, v, np.float64) for k, v in spec.base.items()}
    for k, v in cfg.items():
        if k in cols:
            cols[k][:] = float(v)
    nets = _network_columns_arrays(cols, np.zeros(1, np.int64),
                                   (cfg["topology"],))
    for w, per in zip(wls, r["refined"]["per_workload"]):
        out = evaluate_accelerator_grid(
            w, [cfg["chiplets"]], nets, cols,
            cols["n_mem_chiplets"] * cols["mem_bw_bytes_per_s"],
            mac_rate_hz=cfg["mac_rate_hz"],
            lambda_slot_energy_j=cfg["lambda_slot_energy_j"])
        for k, v in per.items():
            assert float(out[k][0, 0]) == v, (w.name, k)
    with pytest.raises(ValueError, match="weights"):
        refine_codesign(spec, mixes, wls, int(front.indices[0]),
                        weights=(1.0,))
    with pytest.raises(ValueError, match="positive"):
        refine_codesign(spec, mixes, wls, int(front.indices[0]),
                        weights=(1.0, -1.0))


def test_refine_codesign_method_validated_eagerly():
    wl, mixes, front, spec = _codesign_refine_setup()
    with pytest.raises(ValueError, match="method"):
        refine_codesign(spec, mixes, wl, int(front.indices[0]),
                        method="newton")


def test_refine_continuous_metrics_describe_clipped_design():
    """Regression: with a tight box the projection is active at the end of
    the descent, and the reported metrics used to be evaluated at the
    pre-clip iterate — silently describing a different design than the
    reported one.  The metrics must re-evaluate, at the reported refined
    values, to the reported numbers."""
    t = CNN_WORKLOADS["LeNet5"]().traffic()
    axes = ("modulation_rate_bps", "mem_bw_bytes_per_s")
    probe = refine_continuous("trine", {}, t, refine_axes=axes, steps=0)
    tight = {nm: (v * 0.999, v * 1.001) for nm, v in probe["start"].items()}
    r = refine_continuous("trine", {}, t, refine_axes=axes, steps=10,
                          lr=0.5, bounds=tight)
    assert r["refined_value"] <= r["start_value"] * (1 + 1e-12)
    # the big log-space steps pin at least one axis against the tight box
    # (projection happens in float32 log-space, so "at the bound" means
    # within float32 resolution of it, not bit-exactly on it)
    at_bound = [nm for nm, v in r["refined"].items()
                if min(abs(v - tight[nm][0]),
                       abs(v - tight[nm][1])) <= 1e-5 * v]
    assert at_bound, r["refined"]
    # re-evaluate the metrics AT the reported design via a steps=0 probe
    r2 = refine_continuous("trine", dict(r["refined"]), t, refine_axes=axes,
                           steps=0)
    for k, v in r["metrics"].items():
        assert r2["metrics"][k] == pytest.approx(v, rel=1e-9), k
