"""Network power / latency / energy evaluation (paper Fig. 4 methodology).

Given a `NetworkModel` (topology) and a traffic summary (bytes moved, number
of transfers), produce the three quantities the paper reports: network power
(W), total network latency (s), and energy (J) — plus energy-per-bit.

Power breakdown (photonic):
  laser     — sized by worst-case path loss (exponential in dB loss; the
              paper's core argument for stage-minimal topologies)
  trimming  — static thermal tuning, ∝ total MR count (TRINE pays more here
              than SPACX/Tree; paper acknowledges this)
  switch    — MZI bias/driver static power
  dynamic   — modulator driver + SerDes + receiver energy per bit

Electrical: per-bit link+router energy, router static power.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.devices import DeviceLibrary, DEFAULT_DEVICES, laser_electrical_power_w
from repro.core.planner import snap_ties
from repro.core.topology import NetworkModel


def engine_x64():
    """Context manager forcing float64 tracing AND execution for the
    analytical engine's jitted programs, regardless of the session-wide
    ``jax_enable_x64`` setting.

    The streaming sweep/search engine promises bit-identical folds across
    execution modes (host-materialized vs device-decoded, serial vs
    pipelined, monolithic vs chunked).  That promise only holds if every
    engine program is traced and executed at one fixed precision: float32
    would additionally put discrete planner decisions (TRINE's K*, stage
    counts) one rounding error away from flipping between grid rows.  The
    flag is thread-local, so pipeline worker threads must enter their own
    context — `core.sweep` does this at every fold/enqueue site."""
    return jax.enable_x64(True)

# metric columns `eval_network_math` emits == NetworkReport fields — the
# network-side metric vocabulary.  `core.sweep.METRIC_FIELDS` aliases this,
# and `core.search.refine_continuous` validates objective names against it.
EVAL_METRIC_FIELDS = ("power_w", "latency_s", "energy_j", "energy_per_bit_j",
                      "laser_power_w", "trimming_power_w")

# device leaves the batched metric kernel reads (the topology kernels consume
# the rest); `eval_network_math` expects exactly these keys in its `dev` dict
EVAL_DEVICE_FIELDS = (
    "pd.sensitivity_dbm", "pd.energy_per_bit_j",
    "laser.power_margin_db", "laser.coupling_loss_db",
    "laser.wall_plug_efficiency", "laser.bank_overhead_w",
    "mr.tuning_power_w",
    "mzi.static_power_w", "mzi.switch_energy_j",
    "driver.energy_per_bit_j", "driver.serdes_energy_per_bit_j",
    "elec.energy_per_bit_j", "elec.router_power_w",
)


@dataclasses.dataclass(frozen=True)
class Traffic:
    """Aggregate interposer traffic of one workload (from workloads.py)."""

    bytes_read: float       # memory -> compute (SWMR)
    bytes_written: float    # compute -> memory (SWSR)
    n_transfers: int        # distinct layer-level transfer events

    @property
    def total_bytes(self) -> float:
        return self.bytes_read + self.bytes_written

    @property
    def total_bits(self) -> float:
        return 8.0 * self.total_bytes


@dataclasses.dataclass(frozen=True)
class NetworkReport:
    name: str
    power_w: float          # static + average dynamic power
    latency_s: float
    energy_j: float
    energy_per_bit_j: float
    laser_power_w: float
    trimming_power_w: float


def eval_network_math(nets: Dict[str, jax.Array], dev: Dict[str, jax.Array],
                      total_bits: jax.Array, n_transfers: jax.Array,
                      active_fraction: jax.Array) -> Dict[str, jax.Array]:
    """Branch-free batched mirror of `evaluate_network` in pure jax.numpy:
    both the photonic and the electrical formula evaluate on every lane and
    `is_electrical` selects.  All operands broadcast elementwise, so callers
    batch over configurations, workload traffics, per-layer traffic, or any
    combination.  Pure (un-jitted) so it composes: `core.sweep` jits it as
    the grid kernel (optionally with buffer donation), `core.accelerator`
    inlines it per (chiplet-mix, network, layer) lane, and
    `core.search.refine_continuous` differentiates through it (the round()
    wavelength/bank quantization is piecewise-constant — zero gradient)."""
    # ---- photonic ----
    frac = jnp.clip(active_fraction, 1e-3, 1.0)
    n_lambda_active = jnp.maximum(
        1.0, jnp.round(snap_ties(nets["n_wavelengths"] * frac, jnp)))
    n_banks_active = jnp.maximum(
        1.0, jnp.round(snap_ties(nets["n_laser_banks"] * frac, jnp)))
    p_tx_dbm = (dev["pd.sensitivity_dbm"] + dev["laser.power_margin_db"]
                + nets["worst_path_loss_db"] + dev["laser.coupling_loss_db"])
    per_lambda_w = 1e-3 * 10.0 ** (p_tx_dbm / 10.0)
    laser_p = (n_lambda_active * per_lambda_w / dev["laser.wall_plug_efficiency"]
               + n_banks_active * dev["laser.bank_overhead_w"])
    trimming_p = nets["n_mr"] * dev["mr.tuning_power_w"] * frac
    switch_p = nets["n_mzi"] * dev["mzi.static_power_w"] * frac
    static_p = laser_p + trimming_p + switch_p

    bw = nets["effective_bw_bps"] * frac
    lat_ph = total_bits / bw + n_transfers * nets["per_transfer_s"]
    per_bit = (dev["driver.energy_per_bit_j"]
               + dev["driver.serdes_energy_per_bit_j"]
               + dev["pd.energy_per_bit_j"])
    dyn_e = total_bits * per_bit
    switch_e = n_transfers * nets["n_stages"] * dev["mzi.switch_energy_j"]
    energy_ph = static_p * lat_ph + dyn_e + switch_e
    power_ph = static_p + (dyn_e + switch_e) / jnp.maximum(lat_ph, 1e-30)

    # ---- electrical ----
    lat_el = (total_bits / nets["effective_bw_bps"]
              + n_transfers * nets["per_transfer_s"])
    dyn_el = total_bits * dev["elec.energy_per_bit_j"] * nets["avg_hops"]
    static_el = nets["n_routers"] * dev["elec.router_power_w"]
    energy_el = dyn_el + static_el * lat_el
    power_el = static_el + dyn_el / jnp.maximum(lat_el, 1e-30)

    is_el = nets["is_electrical"] > 0
    latency = jnp.where(is_el, lat_el, lat_ph)
    energy = jnp.where(is_el, energy_el, energy_ph)
    return {
        "power_w": jnp.where(is_el, power_el, power_ph),
        "latency_s": latency,
        "energy_j": energy,
        "energy_per_bit_j": energy / jnp.maximum(total_bits, 1.0),
        "laser_power_w": jnp.where(is_el, 0.0, laser_p),
        "trimming_power_w": jnp.where(is_el, 0.0, trimming_p),
    }


def broadcast_metrics(out: Dict[str, jax.Array], xp=jnp) -> Dict[str, jax.Array]:
    """Broadcast every metric column to the common (traffic x scenario x
    config) result shape.  `eval_network_math` leaves each metric at its
    natural broadcast shape (a workload-independent column stays (N,)); the
    streaming engine needs uniform shapes so padded lanes slice off with one
    ``[..., :valid]`` — this helper is shared by the traced chunk program and
    the host-side `core.sweep.evaluate_columns` so both pad identically."""
    shape = np.broadcast_shapes(*(np.shape(v) for v in out.values()))
    return {k: xp.broadcast_to(v, shape) for k, v in out.items()}


def evaluate_network(
    net: NetworkModel,
    traffic: Traffic,
    devices: Optional[DeviceLibrary] = None,
    active_fraction: float = 1.0,
) -> NetworkReport:
    """Evaluate one topology under one workload's traffic.

    `active_fraction` models 2.5D-CrossLight's PCMC gateway adaptation: only
    that fraction of wavelengths/gateways is lit (laser + trimming scale
    down); bandwidth scales with it too.
    """
    d = devices or DEFAULT_DEVICES

    if net.is_electrical:
        # latency: serialization at effective BW + per-transfer hop latency
        ser = traffic.total_bits / net.effective_bw_bps
        lat = ser + traffic.n_transfers * net.per_transfer_s
        dyn_e = traffic.total_bits * d.elec.energy_per_bit_j * net.avg_hops
        static_p = net.n_routers * d.elec.router_power_w
        energy = dyn_e + static_p * lat
        return NetworkReport(
            name=net.name,
            power_w=float(static_p + dyn_e / max(lat, 1e-30)),
            latency_s=float(lat),
            energy_j=float(energy),
            energy_per_bit_j=float(energy / max(traffic.total_bits, 1.0)),
            laser_power_w=0.0,
            trimming_power_w=0.0,
        )

    frac = float(np.clip(active_fraction, 1e-3, 1.0))
    n_lambda_active = max(1, round(float(snap_ties(net.n_wavelengths * frac))))

    n_banks_active = max(1, round(float(snap_ties(net.n_laser_banks * frac))))
    laser_p = float(
        laser_electrical_power_w(
            net.worst_path_loss_db, n_lambda_active, d, n_banks=n_banks_active
        )
    )
    trimming_p = net.n_mr * d.mr.tuning_power_w * frac
    switch_p = net.n_mzi * d.mzi.static_power_w * frac
    static_p = laser_p + trimming_p + switch_p

    bw = net.effective_bw_bps * frac
    ser = traffic.total_bits / bw
    lat = ser + traffic.n_transfers * net.per_transfer_s

    per_bit = (
        d.driver.energy_per_bit_j
        + d.driver.serdes_energy_per_bit_j
        + d.pd.energy_per_bit_j
    )
    dyn_e = traffic.total_bits * per_bit
    switch_e = traffic.n_transfers * net.n_stages * d.mzi.switch_energy_j
    energy = static_p * lat + dyn_e + switch_e

    return NetworkReport(
        name=net.name,
        power_w=float(static_p + (dyn_e + switch_e) / max(lat, 1e-30)),
        latency_s=float(lat),
        energy_j=float(energy),
        energy_per_bit_j=float(energy / max(traffic.total_bits, 1.0)),
        laser_power_w=laser_p,
        trimming_power_w=float(trimming_p),
    )
