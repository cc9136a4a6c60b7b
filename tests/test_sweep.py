"""Golden-value tests for the vectorized sweep engine (core.sweep): the
batched struct-of-arrays path must match the scalar dataclass path
element-for-element across sampled grids, for every topology, every metric,
device-corner axes, PCMC activation fractions, traffic broadcasting, and the
batched accelerator evaluator."""

import dataclasses

import numpy as np
import pytest

from repro.core import (
    CNN_WORKLOADS,
    NetworkParams,
    Traffic,
    crosslight_25d_elec,
    crosslight_25d_siph,
    evaluate_accelerator,
    evaluate_accelerator_batch,
    evaluate_accelerator_grid,
    evaluate_network,
    monolithic_crosslight,
    trine_network,
    tree_network,
)
from repro.core.sweep import (
    DEFAULT_TOPOLOGIES,
    METRIC_FIELDS,
    _network_columns_arrays,
    build_grid,
    evaluate_columns,
    network_columns,
    sweep,
    sweep_scalar_reference,
)

TRAFFIC = Traffic(bytes_read=2e8, bytes_written=7e7, n_transfers=320)

# the kernel is float32 unless jax_enable_x64; the scalar path is float64
RTOL = 1e-4

GRID_AXES = dict(
    n_gateways=(8, 16, 32, 64),
    n_lambda=(4, 8, 16),
    mem_bw_bytes_per_s=(50e9, 100e9, 200e9),
)


def _assert_metrics_match(res, ref):
    for k in METRIC_FIELDS:
        np.testing.assert_allclose(res.metrics[k], ref[k], rtol=RTOL,
                                   atol=0, err_msg=k)


@pytest.mark.parametrize("topology", list(DEFAULT_TOPOLOGIES))
def test_batched_matches_scalar_per_topology(topology):
    """Element-for-element parity on a 36-point grid, per topology (bus,
    tree, TRINE, electrical mesh)."""
    res = sweep(TRAFFIC, topologies=(topology,), **GRID_AXES)
    ref = sweep_scalar_reference(TRAFFIC, topologies=(topology,), **GRID_AXES)
    assert res.grid.n == 36
    _assert_metrics_match(res, ref)


def test_batched_matches_scalar_device_axes():
    """Dotted DeviceLibrary leaves are grid axes; parity must hold across
    device corners too."""
    axes = {"mzi.insertion_loss_db": (0.5, 1.0, 2.0),
            "mr.tuning_power_w": (137e-6, 275e-6, 550e-6)}
    res = sweep(TRAFFIC, topologies=("tree", "trine"), **axes)
    ref = sweep_scalar_reference(TRAFFIC, topologies=("tree", "trine"), **axes)
    _assert_metrics_match(res, ref)


def test_batched_matches_scalar_subnetwork_override():
    axes = dict(n_subnetworks=(1, 2, 4, 8, 16, 32))
    res = sweep(TRAFFIC, topologies=("trine",), **axes)
    ref = sweep_scalar_reference(TRAFFIC, topologies=("trine",), **axes)
    _assert_metrics_match(res, ref)


@pytest.mark.parametrize("frac", [0.4, 0.75, 1.0])
def test_batched_matches_scalar_active_fraction(frac):
    """PCMC gateway-activation fractions follow the identical rounding."""
    res = sweep(TRAFFIC, topologies=("trine", "sprint"),
                active_fraction=frac, n_lambda=(4, 8, 16))
    ref = sweep_scalar_reference(TRAFFIC, topologies=("trine", "sprint"),
                                 active_fraction=frac, n_lambda=(4, 8, 16))
    _assert_metrics_match(res, ref)


def test_traffic_broadcasting_matches_per_workload_calls():
    """(W, 1)-shaped traffic against an (N,) config axis gives (W, N)
    metrics equal to evaluating each workload separately."""
    grid = build_grid(("sprint", "tree", "trine"), n_lambda=(4, 8))
    nets = network_columns(grid)
    traffics = [CNN_WORKLOADS[n]().traffic() for n in ("LeNet5", "ResNet18")]
    bits = np.asarray([[t.total_bits] for t in traffics])
    xfers = np.asarray([[t.n_transfers] for t in traffics])
    both = evaluate_columns(nets, grid.cols, bits, xfers)
    assert both["latency_s"].shape == (2, grid.n)
    for wi, t in enumerate(traffics):
        one = evaluate_columns(nets, grid.cols, t.total_bits, t.n_transfers)
        for k in METRIC_FIELDS:
            np.testing.assert_allclose(both[k][wi], one[k], rtol=1e-6,
                                       err_msg=k)


def test_model_at_equals_scalar_factory():
    """A grid row reconstitutes to the identical NetworkModel dataclass the
    scalar factory builds."""
    res = sweep(TRAFFIC, topologies=("tree", "trine"))
    p = NetworkParams()
    assert res.model_at(0) == tree_network(p)
    assert res.model_at(1) == trine_network(p)


def test_scalar_row_reconstruction():
    grid = build_grid(("trine",), n_gateways=(16, 64),
                      **{"mzi.insertion_loss_db": (1.0, 2.0)})
    p = grid.row_params(3)
    assert isinstance(p.n_gateways, int) and p.n_gateways == 64
    d = grid.row_devices(3)
    assert d.mzi.insertion_loss_db == 2.0
    assert d.mr == grid.row_devices(0).mr  # unswept leaves untouched


def test_build_grid_rejects_unknown_axis_and_topology():
    with pytest.raises(KeyError):
        build_grid(("trine",), not_a_field=(1, 2))
    with pytest.raises(KeyError):
        build_grid(("warp-drive",))


def test_spacx_rejects_subcluster_gateway_counts():
    """g < 8 would mean zero SPACX clusters (zero bandwidth); both the
    batched kernel and the scalar wrapper must fail loudly, not emit inf."""
    with pytest.raises(ValueError):
        sweep(TRAFFIC, topologies=("spacx",), n_gateways=(4,))
    from repro.core import spacx_bus
    with pytest.raises(ValueError):
        spacx_bus(NetworkParams(n_gateways=4))


# The TPU emulates float64 with about 48 significand bits, and its division
# and square root do not round as IEEE does: a quotient that is exactly an
# integer on the host (400e9 / 80e9 = 5) can land an ulp above it there, and
# its ceil would take the next count.  Nudging every input by a few such
# ulps stands in for that backend on the CPU.
NUDGE = 1e-13

# axes on which the planner's quotients and products sit exactly on ties
TIE_AXES = dict(n_gateways=(8, 16, 32), n_lambda=(4, 8),
                mem_bw_bytes_per_s=(25e9, 50e9, 100e9),
                modulation_rate_bps=(10e9, 12e9), n_mem_chiplets=(2, 4))


def _nudge(v, rng):
    v = np.asarray(v, np.float64)
    return v * (1 + NUDGE * rng.choice([-1.0, 1.0], v.shape))


@pytest.mark.parametrize("seed", [0, 1])
def test_network_counts_survive_last_bit_error(seed):
    """Subnetwork, stage, cluster, mesh-side and lit-wavelength counts are
    taken on exact ties across this grid (3/8 of 4 wavelengths is 1.5); a
    few ulps of error in every input must move none of them, so no metric
    moves beyond rounding."""
    grid = build_grid(DEFAULT_TOPOLOGIES, **TIE_AXES)
    frac = np.full(grid.n, 0.375)
    exact = evaluate_columns(network_columns(grid), grid.cols,
                             TRAFFIC.total_bits, TRAFFIC.n_transfers, frac)
    rng = np.random.default_rng(seed)
    cols = {k: _nudge(v, rng) for k, v in grid.cols.items()}
    nets = _network_columns_arrays(cols, grid.topo_id, grid.topologies)
    got = evaluate_columns(nets, cols, TRAFFIC.total_bits,
                           TRAFFIC.n_transfers, _nudge(frac, rng))
    for k in METRIC_FIELDS:
        np.testing.assert_allclose(got[k], exact[k], rtol=1e-10, err_msg=k)


@pytest.mark.parametrize("seed", [0, 1])
def test_accelerator_counts_survive_last_bit_error(seed):
    """The co-design kernel's pass counts (ceil of dot length over vector
    size), gateway counts and activation steps hold under the same nudge."""
    wl = CNN_WORKLOADS["ResNet18"]()
    grid = build_grid(("sprint", "spacx", "trine"), **TIE_AXES)
    mixes = [crosslight_25d_siph().chiplets, crosslight_25d_elec().chiplets]

    def score(mixes, nets, cols):
        mem_bw = cols["n_mem_chiplets"] * cols["mem_bw_bytes_per_s"]
        return evaluate_accelerator_grid(wl, mixes, nets, cols, mem_bw)

    exact = score(mixes, network_columns(grid), grid.cols)
    rng = np.random.default_rng(seed)
    cols = {k: _nudge(v, rng) for k, v in grid.cols.items()}
    nets = _network_columns_arrays(cols, grid.topo_id, grid.topologies)
    mixes = [[dataclasses.replace(c, vector_size=float(_nudge(c.vector_size,
                                                              rng)))
              for c in mix] for mix in mixes]
    got = score(mixes, nets, cols)
    for k in ("latency_s", "power_w", "energy_j", "compute_s", "network_s"):
        np.testing.assert_allclose(got[k], exact[k], rtol=1e-10, err_msg=k)


@pytest.mark.parametrize("accel_factory", [
    monolithic_crosslight, crosslight_25d_elec, crosslight_25d_siph])
@pytest.mark.parametrize("wl_name", ["LeNet5", "ResNet18"])
def test_accelerator_batch_matches_scalar(accel_factory, wl_name):
    """The batched per-layer accelerator evaluation reproduces the scalar
    layer loop for all three paper variants."""
    accel = accel_factory()
    wl = CNN_WORKLOADS[wl_name]()
    a = evaluate_accelerator(accel, wl)
    b = evaluate_accelerator_batch(accel, wl)
    for f in ("latency_s", "power_w", "energy_j", "epb_j", "compute_s",
              "network_s", "memory_s", "network_energy_j"):
        assert getattr(b, f) == pytest.approx(getattr(a, f), rel=RTOL), f


def test_accelerator_zero_unit_padding_parity():
    """Zero-unit chiplets (mix padding) must be inert on the scalar path,
    exactly as the vmapped kernel masks them: a padded accelerator scores
    identically to its unpadded twin, on both evaluation paths.  Regression:
    the scalar `_layer_compute` used to let a ChipletSpec(0, 1) row pollute
    slots_per_dot_best (vec=1 always wins the slot minimum)."""
    from repro.core import ChipletSpec
    wl = CNN_WORKLOADS["LeNet5"]()
    clean = crosslight_25d_siph()
    padded = dataclasses.replace(
        clean, chiplets=list(clean.chiplets) + [ChipletSpec(0, 1)])
    for f in ("latency_s", "power_w", "energy_j", "epb_j", "compute_s",
              "network_s", "memory_s", "network_energy_j"):
        assert getattr(evaluate_accelerator(padded, wl), f) == \
            getattr(evaluate_accelerator(clean, wl), f), f
        assert getattr(evaluate_accelerator_batch(padded, wl), f) == \
            pytest.approx(getattr(evaluate_accelerator_batch(clean, wl), f),
                          rel=RTOL), f


def test_accelerator_all_zero_mix_raises():
    """An all-zero chiplet mix has no compute throughput: both the scalar
    path and the batched mix-columns builder must fail loudly instead of
    dividing by zero."""
    from repro.core import ChipletSpec
    from repro.core.accelerator import chiplet_mix_columns
    wl = CNN_WORKLOADS["LeNet5"]()
    clean = crosslight_25d_siph()
    dead = dataclasses.replace(
        clean, chiplets=[ChipletSpec(0, 9), ChipletSpec(0, 49)])
    with pytest.raises(ValueError, match="no active"):
        evaluate_accelerator(dead, wl)
    with pytest.raises(ValueError, match="no active"):
        chiplet_mix_columns([[ChipletSpec(512, 32)], [ChipletSpec(0, 9)]])
