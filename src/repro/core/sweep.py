"""Vectorized design-space sweep engine for the interposer-network models.

The paper's headline figures come from sweeping network configurations across
gateways / wavelengths / modulation rates / device corners.  The scalar
dataclass path (`NetworkParams` -> `NetworkModel` -> `evaluate_network`)
evaluates one configuration per Python call; this module flattens whole
parameter grids into struct-of-arrays columns and evaluates every metric the
power model produces — laser, trimming, latency, energy, energy-per-bit — for
10k+ configurations in one jitted call.

Pipeline:

  build_grid(...)          cartesian product of a topology axis, any
                           NetworkParams field, any dotted DeviceLibrary leaf
                           ("mzi.insertion_loss_db", ...), and the TRINE
                           "n_subnetworks" override -> SweepGrid of float64
                           columns.
  network_columns(grid)    struct-of-arrays NetworkModel fields, via the
                           columnar topology kernels in core.topology.
  evaluate_columns(...)    the jitted batched power/latency/energy kernel
                           (mirrors power.evaluate_network branch-free).
  sweep(traffic, ...)      all of the above in one call -> SweepResult.

`sweep_scalar_reference` walks the identical grid through the scalar
dataclass path one row at a time; it is the golden reference the parity tests
(and benchmarks/sweep_bench.py) compare the batched engine against.

`evaluate_accelerator_batch` is the same treatment for the Fig. 6 accelerator
model: all layers of a workload evaluated as one batch instead of a Python
loop per layer.

Device-resident streaming execution
-----------------------------------

`sweep(...)` materializes every grid column in host memory — ~45 float64
columns, so a 1e7-point grid costs ~3.6 GB before a single metric exists.
The streaming path bounds that AND keeps the hot loop off the host:

  grid_spec(...)           the same validation/axis vocabulary as
                           `build_grid`, but *lazy*: a GridSpec holds only
                           the axis value tuples and can materialize any
                           [start, stop) row window in O(window) memory
                           (mixed-radix decode of the flat index).
  sweep_chunked(traffic, reducer, ...)
                           streams fixed-size chunks through one universal
                           jitted chunk program, feeding each chunk's metrics
                           to a running `ChunkReducer` and keeping nothing
                           else.  Peak memory is O(chunk_size), independent
                           of grid size.

Two materialization modes feed the same chunk program:

  materialize="device"     (default) a chunk is generated from the `start`
                           scalar alone: a jitted mixed-radix *decode
                           program* gathers each column from small
                           device-resident axis-value tables, so steady-state
                           streaming performs zero per-chunk host numpy work
                           and zero per-chunk H2D column transfers.
  materialize="host"       the serial reference layout: `GridSpec.chunk_cols`
                           builds the columns on the host (the golden
                           mixed-radix decode the device program is
                           parity-tested against) and ships them to the
                           device.  Forced when ``shard=True`` (columns are
                           laid out across devices with NamedSharding) or
                           when a legacy `columns_fn` callable needs host
                           columns.

Both modes hand the *same* program instance the same column values, so their
reducer folds are bit-identical; `chunk_cols` stays the golden host
reference.  All engine programs trace AND execute under float64
(`power.engine_x64`), independent of the session-wide x64 setting —
bit-reproducibility across chunk boundaries requires one fixed precision.

On top of either mode sits a double-buffered prefetch pipeline: a
single-worker executor enqueues chunk k+1 while chunk k's results fold on
the main thread (`jax.block_until_ready` at the fold point — XLA releases
the GIL during device execution, so reducer host work overlaps device
compute).  The depth comes from ``prefetch=`` or the REPRO_PREFETCH
environment flag (default 2); depth 0 is the fully serial schedule.  Folds
happen in chunk order regardless of depth, so any depth produces
bit-identical reducer states.

The fault hook composes on-device: `faults.faulted_columns_fn(scenario)`
returns a scenario-carrying hook whose six fields become *runtime inputs* of
the chunk program (degradation algebra traced, not re-compiled per
scenario).  A healthy scenario feeds exact IEEE identities (x+0, x*1), so a
faulted-healthy sweep is bitwise equal to a plain sweep.  Arbitrary legacy
``columns_fn(cols, topo_id, topologies) -> (nets, dev_cols)`` callables
still run on host-materialized columns.

On non-CPU backends the chunk program donates its column buffers
(`donate_argnums`), so steady-state chunk evaluation reuses device memory.

Reducers are associative folds over chunks: `MinReducer` tracks a metric's
running argmin + config, `core.search.ParetoReducer` keeps the running
(latency, energy, power) Pareto front via the merge-fronts property
front(A ∪ B) = front(front(A) ∪ front(B)).
"""

from __future__ import annotations

import dataclasses
import functools
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, Mapping, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.env import prefetch_depth
from repro.core.devices import (
    DeviceLibrary,
    DEFAULT_DEVICES,
    device_columns,
    replace_device_leaves,
)
from repro.core.topology import (
    MODEL_FIELDS,
    PARAM_FIELDS,
    TOPOLOGIES,
    TOPOLOGY_ARRAYS,
    NetworkParams,
    model_from_row,
)
from repro.core.power import (
    EVAL_DEVICE_FIELDS,
    EVAL_METRIC_FIELDS,
    Traffic,
    broadcast_metrics,
    engine_x64,
    eval_network_math as eval_math,
    evaluate_network,
)
from repro.core.accelerator import (  # noqa: F401  (re-exported; see below)
    evaluate_accelerator_batch,
    evaluate_accelerator_grid,
)

__all__ = [
    "SweepGrid", "SweepResult", "build_grid", "network_columns",
    "network_columns_device",
    "evaluate_columns", "sweep", "sweep_scalar_reference",
    "evaluate_accelerator_batch", "METRIC_FIELDS", "INTEGER_AXES",
    "DEFAULT_TOPOLOGIES",
    "GridSpec", "grid_spec", "SweepChunk", "ChunkReducer", "MinReducer",
    "sweep_chunked", "eval_math",
]

DEFAULT_TOPOLOGIES: Tuple[str, ...] = ("sprint", "spacx", "tree", "trine", "elec")

# int-typed NetworkParams fields (scalar-reference reconstruction)
_INT_PARAM_FIELDS = frozenset({"n_gateways", "n_mem_chiplets", "n_lambda",
                               "gateway_width_bits"})

# grid axes whose admissible values are integers: the int NetworkParams
# fields plus the TRINE subnetwork override.  `core.search.refine_codesign`
# snaps relaxed values of these axes back to integer neighbors during
# round-and-rescore; everything else in the axis vocabulary is continuous.
INTEGER_AXES = _INT_PARAM_FIELDS | {"n_subnetworks"}

# metric columns emitted by the batched evaluator == NetworkReport fields
# (defined in core.power next to the math that emits them)
METRIC_FIELDS = EVAL_METRIC_FIELDS

# device leaves the power kernel reads (re-exported; defined in core.power
# next to the shared metric math)
_EVAL_DEVICE_FIELDS = EVAL_DEVICE_FIELDS


# --------------------------------------------------------------------------
# Grid construction
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class GridSpec:
    """Lazy cartesian grid: the axis vocabulary and defaults of `build_grid`
    without the materialized columns.  Any [start, stop) row window can be
    produced on demand by mixed-radix decoding the flat index, so a window
    costs O(window) memory regardless of grid size — the foundation of
    `sweep_chunked`'s bounded-memory streaming evaluation.

    axis order: ("topology", *axes), C-order raveled — identical flat-index
    layout to the eager SweepGrid `build_grid` returns.
    """

    topologies: Tuple[str, ...]
    axes: Dict[str, Tuple[float, ...]]
    base: Dict[str, float]
    shape: Tuple[int, ...]

    @property
    def n(self) -> int:
        return int(np.prod(self.shape))

    def chunk_cols(self, start: int, stop: int
                   ) -> Tuple[Dict[str, np.ndarray], np.ndarray]:
        """(cols, topo_id) for flat rows [start, stop) — element-for-element
        the values eager `build_grid` places at those rows.  The golden host
        reference the jitted decode program is parity-tested against."""
        return self._cols_at(np.arange(start, stop))

    def _cols_at(self, idx: np.ndarray
                 ) -> Tuple[Dict[str, np.ndarray], np.ndarray]:
        digits = np.unravel_index(idx, self.shape)
        cols = {name: np.full(idx.size, v, np.float64)
                for name, v in self.base.items()}
        for ai, (name, vals) in enumerate(self.axes.items()):
            cols[name] = np.asarray(vals, np.float64)[digits[1 + ai]]
        return cols, np.ascontiguousarray(digits[0])

    def rows(self, idx) -> "SweepGrid":
        """Eager SweepGrid of the flat rows `idx` (any order): row j of the
        result is grid row idx[j]."""
        idx = np.asarray(idx, np.int64)
        cols, topo_id = self._cols_at(idx)
        return SweepGrid(topologies=self.topologies, axes=self.axes,
                         cols=cols, topo_id=topo_id, shape=(idx.size,))

    def config_at(self, i: int) -> Dict[str, float]:
        """Human-readable swept-axis settings of flat row `i`."""
        digits = np.unravel_index(int(i), self.shape)
        out: Dict[str, float] = {"topology": self.topologies[int(digits[0])]}
        for ai, (name, vals) in enumerate(self.axes.items()):
            out[name] = float(vals[int(digits[1 + ai])])
        return out


def grid_spec(
    topologies: Sequence[str] = DEFAULT_TOPOLOGIES,
    devices: Optional[DeviceLibrary] = None,
    **axes: Sequence[float],
) -> GridSpec:
    """Validate and describe a grid without materializing it (see
    `build_grid` for the axis vocabulary)."""
    base: Dict[str, float] = {name: float(getattr(NetworkParams(), name))
                              for name in PARAM_FIELDS}
    base.update(device_columns(devices or DEFAULT_DEVICES))
    base["n_subnetworks"] = 0.0

    for name in axes:
        if name not in base:
            raise KeyError(
                f"unknown sweep axis {name!r}; valid axes are NetworkParams "
                f"fields, dotted device leaves, or 'n_subnetworks'")
    unknown = [t for t in topologies if t not in TOPOLOGY_ARRAYS]
    if unknown:
        raise KeyError(f"unknown topologies {unknown!r}")

    axes_vals = {k: tuple(float(x) for x in v) for k, v in axes.items()}
    shape = (len(topologies),) + tuple(len(v) for v in axes_vals.values())
    return GridSpec(topologies=tuple(topologies), axes=axes_vals,
                    base=base, shape=shape)


def _validate_grid_values(spec: GridSpec) -> None:
    """Eager data-dependent validation the traced chunk program cannot do.

    The numpy SPACX kernel raises on n_gateways < 8 (zero clusters => zero
    bandwidth); the traced kernel evaluates every topology on every lane and
    selects, so it cannot raise data-dependently.  The grid is cartesian —
    every gateway value reaches the SPACX lanes — so the whole-axis check is
    exactly the condition the per-chunk numpy kernel would have tripped on.
    """
    if "spacx" not in spec.topologies:
        return
    gvals = spec.axes.get("n_gateways") or (spec.base["n_gateways"],)
    if min(gvals) < 8:
        raise ValueError(
            "SPACX requires n_gateways >= 8 (one 8-gateway cluster minimum; "
            "fewer means zero clusters and zero bandwidth)")


@dataclasses.dataclass(frozen=True)
class SweepGrid:
    """A flattened cartesian parameter grid (struct-of-arrays columns).

    axis order: ("topology", *axes) — `shape` follows it, every column and
    `topo_id` is raveled to length `n = prod(shape)`.
    """

    topologies: Tuple[str, ...]
    axes: Dict[str, Tuple[float, ...]]
    cols: Dict[str, np.ndarray]
    topo_id: np.ndarray
    shape: Tuple[int, ...]

    @property
    def n(self) -> int:
        return int(self.topo_id.size)

    @functools.cached_property
    def topo_masks(self) -> Tuple[np.ndarray, ...]:
        """Per-topology boolean row masks, computed once per grid object and
        reused by every `network_columns` call on it (cached_property writes
        to the instance __dict__, bypassing the frozen-dataclass setattr)."""
        return tuple(self.topo_id == ti for ti in range(len(self.topologies)))

    def row_params(self, i: int) -> NetworkParams:
        kw = {}
        for name in PARAM_FIELDS:
            v = self.cols[name][i]
            kw[name] = int(v) if name in _INT_PARAM_FIELDS else float(v)
        return NetworkParams(**kw)

    def row_devices(self, i: int,
                    base: Optional[DeviceLibrary] = None) -> DeviceLibrary:
        base = base or DEFAULT_DEVICES
        swept = {k: float(self.cols[k][i]) for k in self.axes if "." in k}
        return replace_device_leaves(base, swept) if swept else base

    def row_topology(self, i: int) -> str:
        return self.topologies[int(self.topo_id[i])]

    def row_network(self, i: int, base: Optional[DeviceLibrary] = None):
        """(NetworkModel, DeviceLibrary) of flat row `i` built through the
        scalar dataclass path — the golden host reference for the row."""
        p, d = self.row_params(i), self.row_devices(i, base)
        name = self.row_topology(i)
        if name == "trine":
            k = int(self.cols["n_subnetworks"][i])
            return TOPOLOGIES[name](p, n_subnetworks=k or None, d=d), d
        return TOPOLOGIES[name](p, d=d), d


def build_grid(
    topologies: Sequence[str] = DEFAULT_TOPOLOGIES,
    devices: Optional[DeviceLibrary] = None,
    **axes: Sequence[float],
) -> SweepGrid:
    """Cartesian product of `topologies` x every keyword axis.

    Axis names may be NetworkParams fields (``n_gateways=(16, 32, 64)``),
    dotted DeviceLibrary leaves (``mzi.insertion_loss_db`` — pass via a dict
    expansion since dots aren't identifiers: ``**{"mzi.insertion_loss_db":
    (1.0, 2.0)}``), or ``n_subnetworks`` (TRINE K override; 0 = bandwidth-
    matched auto).  Unswept columns take their NetworkParams/DeviceLibrary
    defaults.
    """
    spec = grid_spec(topologies, devices=devices, **axes)
    cols, topo_id = spec.chunk_cols(0, spec.n)
    return SweepGrid(topologies=spec.topologies, axes=spec.axes,
                     cols=cols, topo_id=topo_id, shape=spec.shape)


def _network_columns_arrays(cols: Mapping[str, np.ndarray],
                            topo_id: np.ndarray,
                            topologies: Sequence[str],
                            masks: Optional[Sequence[np.ndarray]] = None,
                            ) -> Dict[str, np.ndarray]:
    """Struct-of-arrays NetworkModel fields for (cols, topo_id) rows (host
    numpy reference path).  `masks` short-circuits the per-topology row-mask
    computation with precomputed masks (see `SweepGrid.topo_masks`)."""
    out = {f: np.zeros(topo_id.size, np.float64) for f in MODEL_FIELDS}
    for ti, name in enumerate(topologies):
        mask = masks[ti] if masks is not None else topo_id == ti
        if not mask.any():
            continue  # chunk windows may not contain every topology
        sub = {k: v[mask] for k, v in cols.items()}
        fields = TOPOLOGY_ARRAYS[name](sub)
        for f in MODEL_FIELDS:
            out[f][mask] = fields[f]
    return out


def network_columns(grid: SweepGrid) -> Dict[str, np.ndarray]:
    """Struct-of-arrays NetworkModel fields for every grid row."""
    return _network_columns_arrays(grid.cols, grid.topo_id, grid.topologies,
                                   masks=grid.topo_masks)


# --------------------------------------------------------------------------
# Batched evaluation (the jitted kernels)
# --------------------------------------------------------------------------

# the metric math itself lives in core.power.eval_network_math (shared with
# the co-design accelerator kernel and the gradient-refinement path); this
# module owns the jit/donation/sharding machinery around it
_eval_kernel = jax.jit(eval_math)
# donating nets/dev lets XLA reuse the chunk input buffers for the outputs in
# steady-state streaming; CPU ignores donation (and warns), so gate on backend
_eval_kernel_donated = jax.jit(eval_math, donate_argnums=(0, 1))


def _chunk_eval_kernel():
    return (_eval_kernel if jax.default_backend() == "cpu"
            else _eval_kernel_donated)


def _as_f64(x):
    # float64 whenever x64 is enabled (the engine always enters engine_x64()
    # around conversions + kernel calls), float32 otherwise — jnp downcasts
    return jnp.asarray(np.asarray(x, np.float64))


def evaluate_columns(
    nets: Mapping[str, np.ndarray],
    cols: Mapping[str, np.ndarray],
    total_bits,
    n_transfers,
    active_fraction=1.0,
) -> Dict[str, np.ndarray]:
    """Run the jitted batched evaluator over struct-of-arrays NetworkModel
    fields.  `total_bits` / `n_transfers` / `active_fraction` broadcast
    against the config axis (e.g. shape (W, 1) traffic x (N,) configs ->
    (W, N) metrics).  Always evaluates in float64 (`engine_x64`), matching
    the streaming engine's fixed precision."""
    with engine_x64():
        nets_j = {k: _as_f64(nets[k]) for k in MODEL_FIELDS}
        dev_j = {k: _as_f64(cols[k]) for k in _EVAL_DEVICE_FIELDS}
        out = _eval_kernel(nets_j, dev_j, _as_f64(total_bits),
                           _as_f64(n_transfers), _as_f64(active_fraction))
        out = {k: np.asarray(v, np.float64) for k, v in out.items()}
    # static-only metrics (laser, trimming) don't see the traffic operands;
    # broadcast everything to the full (traffic x config) result shape
    return broadcast_metrics(out, np)


# ---- the universal chunk programs -----------------------------------------
#
# Bitwise reproducibility across execution modes pins the program structure:
# two *different* jit programs of the same math may fuse FMAs differently and
# disagree in the last ulp, but one program instance is bitwise-stable across
# input shapes.  So there is exactly ONE evaluation program per topology
# tuple — shared by `sweep` (full shape), host-materialized chunks, and
# device-decoded chunks — and the mixed-radix decode is a SEPARATE program
# whose gather outputs are exact (bit-identical to `GridSpec.chunk_cols`),
# rather than being fused into the evaluation (fusion would change the
# evaluation's FMA decisions and break monolithic-vs-chunked parity).

_DECODE_PROGRAMS: Dict[tuple, Callable] = {}
_ENGINE_PROGRAMS: Dict[tuple, Callable] = {}
_NETS_PROGRAMS: Dict[tuple, Callable] = {}


def _decode_program(spec: GridSpec, chunk: int) -> Callable:
    """Jitted mixed-radix decode: (axis tables, base scalars, start) ->
    (cols, topo_id) for flat rows [start, start+chunk), clamped to the last
    row — exactly `chunk_cols`' repeat-last-row padding.  Gathers and integer
    strides are exact, so the decoded columns are bit-identical to the host
    reference."""
    key = (spec.shape, tuple(spec.axes), tuple(spec.base), int(chunk))
    fn = _DECODE_PROGRAMS.get(key)
    if fn is not None:
        return fn
    shape = spec.shape
    n = int(np.prod(shape))
    strides = tuple(int(np.prod(shape[i + 1:], dtype=np.int64))
                    for i in range(len(shape)))
    axes_names = tuple(spec.axes)
    base_names = tuple(spec.base)

    def decode(tables, base, start):
        idx = jnp.minimum(start + jnp.arange(chunk), n - 1)
        cols = {name: jnp.broadcast_to(base[name], (chunk,))
                for name in base_names}
        for ai, name in enumerate(axes_names):
            digit = (idx // strides[1 + ai]) % shape[1 + ai]
            cols[name] = tables[name][digit]
        return cols, idx // strides[0]

    fn = jax.jit(decode)
    _DECODE_PROGRAMS[key] = fn
    return fn


def _engine_program(topologies: Tuple[str, ...], donate: bool) -> Callable:
    """The universal chunk-evaluation program: (cols, topo_id, scenario,
    bits, xfers, frac) -> (nets, metrics).

    Every topology kernel evaluates on every lane and `topo_id` selects —
    the traced mirror of `_network_columns_arrays`' masking.  The fault
    algebra (`core.faults`) is part of the trace with the six scenario
    fields as runtime inputs: a healthy scenario feeds exact IEEE identities
    (x + 0.0, x * 1.0, banks/banks), so plain and faulted-healthy sweeps are
    bitwise equal without a second program.  Metrics come back broadcast to
    the common (traffic x scenario x config) shape so padded lanes slice off
    uniformly."""
    key = (tuple(topologies), bool(donate))
    fn = _ENGINE_PROGRAMS.get(key)
    if fn is not None:
        return fn
    # runtime import: core.faults imports this module at load time
    from repro.core import faults as _faults

    def body(cols, topo_id, scen, bits, xfers, frac):
        scenario = _faults.FaultScenario(**scen)
        dcols = _faults.degrade_device_columns(cols, scenario, jnp)
        nets = None
        for ti, name in enumerate(topologies):
            fields = TOPOLOGY_ARRAYS[name](dcols, jnp)
            fields = _faults._degrade_fields(
                fields, cols["n_gateways"], scenario, name, jnp)
            sel = topo_id == ti
            if nets is None:
                nets = {f: jnp.where(sel, fields[f],
                                     jnp.zeros_like(fields[f]))
                        for f in MODEL_FIELDS}
            else:
                nets = {f: jnp.where(sel, fields[f], nets[f])
                        for f in MODEL_FIELDS}
        dev = {k: dcols[k] for k in _EVAL_DEVICE_FIELDS}
        metrics = broadcast_metrics(
            eval_math(nets, dev, bits, xfers, frac), jnp)
        return nets, metrics

    fn = jax.jit(body, donate_argnums=(0,)) if donate else jax.jit(body)
    _ENGINE_PROGRAMS[key] = fn
    return fn


def _engine_kernel(topologies: Sequence[str]) -> Callable:
    """Backend-appropriate universal chunk program (donation off on CPU)."""
    return _engine_program(tuple(topologies),
                           donate=jax.default_backend() != "cpu")


def _nets_program(topologies: Tuple[str, ...]) -> Callable:
    """Jitted healthy network-column builder: (cols, topo_id) -> (nets,
    mem_bw_bytes_per_s_total).  The co-design search routes BOTH its
    materialization modes through this one instance so their fronts are
    bit-identical; `network_columns_device` exposes the nets to host callers
    (benchmark/bruteforce parity)."""
    key = tuple(topologies)
    fn = _NETS_PROGRAMS.get(key)
    if fn is not None:
        return fn

    def body(cols, topo_id):
        nets = None
        for ti, name in enumerate(topologies):
            fields = TOPOLOGY_ARRAYS[name](cols, jnp)
            sel = topo_id == ti
            if nets is None:
                nets = {f: jnp.where(sel, fields[f],
                                     jnp.zeros_like(fields[f]))
                        for f in MODEL_FIELDS}
            else:
                nets = {f: jnp.where(sel, fields[f], nets[f])
                        for f in MODEL_FIELDS}
        mem_bw = cols["n_mem_chiplets"] * cols["mem_bw_bytes_per_s"]
        return nets, mem_bw

    fn = jax.jit(body)
    _NETS_PROGRAMS[key] = fn
    return fn


def network_columns_device(cols: Mapping[str, np.ndarray],
                           topo_id: np.ndarray,
                           topologies: Sequence[str],
                           ) -> Dict[str, np.ndarray]:
    """Traced-kernel network columns as host float64 — the device-path
    analog of `_network_columns_arrays`, bit-identical to the nets the
    streaming co-design engine evaluates (XLA and numpy transcendentals
    differ in the last ulp, so exact-front comparisons against the engine
    must build their reference nets here, not on the numpy path)."""
    prog = _nets_program(tuple(topologies))
    with engine_x64():
        cols_j = {k: _as_f64(v) for k, v in cols.items()}
        nets, _ = prog(cols_j, jnp.asarray(np.asarray(topo_id)))
        return {k: np.asarray(v, np.float64) for k, v in nets.items()}


def _scenario_inputs(scenario=None) -> Dict[str, jax.Array]:
    """The six fault-scenario operands as device arrays (healthy identity
    values when None).  Must be called under `engine_x64`."""
    from repro.core.faults import _SCENARIO_FIELDS, HEALTHY  # runtime: cycle
    s = HEALTHY if scenario is None else scenario
    return {f: _as_f64(getattr(s, f)) for f in _SCENARIO_FIELDS}


# --------------------------------------------------------------------------
# Top-level sweep API
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SweepResult:
    """Metrics + model fields for every grid point (flat, length grid.n)."""

    grid: SweepGrid
    nets: Dict[str, np.ndarray]
    metrics: Dict[str, np.ndarray]

    def metric(self, name: str) -> np.ndarray:
        """One metric reshaped to the grid's (topology, *axes) shape."""
        return self.metrics[name].reshape(self.grid.shape)

    def config_at(self, i: int) -> Dict[str, float]:
        """Human-readable swept-axis settings of flat row `i`."""
        out: Dict[str, float] = {"topology": self.grid.row_topology(i)}
        for name in self.grid.axes:
            out[name] = float(self.grid.cols[name][i])
        return out

    def best(self, name: str = "energy_j") -> Tuple[int, Dict[str, float]]:
        """(flat index, swept-axis settings) of the metric's minimizer."""
        i = int(np.argmin(self.metrics[name]))
        return i, self.config_at(i)

    def model_at(self, i: int):
        """Scalar NetworkModel dataclass view of flat row `i`."""
        key = self.grid.row_topology(i)
        name = {"sprint": "SPRINT", "spacx": "SPACX", "tree": "Tree",
                "elec": "ElecMesh"}.get(key)
        if name is None:  # trine carries its subnetwork count
            name = f"TRINE-{int(self.nets['n_laser_banks'][i])}"
        return model_from_row(self.nets, name, i=i)


def sweep(
    traffic: Traffic,
    topologies: Sequence[str] = DEFAULT_TOPOLOGIES,
    devices: Optional[DeviceLibrary] = None,
    active_fraction: float = 1.0,
    **axes: Sequence[float],
) -> SweepResult:
    """Evaluate one workload's traffic over a full configuration grid.

    `nets` stays on the host numpy reference path (exact dataclass
    round-trips via `model_at`); the metrics run through the same universal
    chunk program the streaming paths use, at the full grid shape — one
    program instance is bitwise-stable across input shapes, which is what
    makes chunked results bit-identical to this monolithic call."""
    grid = build_grid(topologies, devices=devices, **axes)
    nets = network_columns(grid)  # host reference (also validates, eagerly)
    kernel = _engine_kernel(grid.topologies)
    with engine_x64():
        cols_j = {k: _as_f64(v) for k, v in grid.cols.items()}
        topo_j = jnp.asarray(np.asarray(grid.topo_id))
        out = kernel(cols_j, topo_j, _scenario_inputs(),
                     _as_f64(traffic.total_bits),
                     _as_f64(traffic.n_transfers), _as_f64(active_fraction))
        metrics = {k: np.asarray(v, np.float64) for k, v in out[1].items()}
    return SweepResult(grid=grid, nets=nets, metrics=metrics)


# --------------------------------------------------------------------------
# Chunked streaming evaluation (bounded memory for 1e7-point grids)
# --------------------------------------------------------------------------


def _traffic_arrays(traffic) -> Tuple[np.ndarray, np.ndarray]:
    """(total_bits, n_transfers) operands: scalar for one Traffic, (W, 1)
    columns for a sequence of workload traffics (broadcast against configs)."""
    if isinstance(traffic, Traffic):
        return np.float64(traffic.total_bits), np.float64(traffic.n_transfers)
    ts = list(traffic)
    bits = np.asarray([[t.total_bits] for t in ts], np.float64)
    xfers = np.asarray([[t.n_transfers] for t in ts], np.float64)
    return bits, xfers


@dataclasses.dataclass(frozen=True)
class SweepChunk:
    """One evaluated grid window [start, stop): metrics (and model fields)
    for those rows only.  `metrics` values have shape (..., stop-start) —
    a leading workload axis appears when the sweep batches traffics."""

    spec: GridSpec
    start: int
    stop: int
    topo_id: np.ndarray
    nets: Dict[str, np.ndarray]
    metrics: Dict[str, np.ndarray]

    @property
    def indices(self) -> np.ndarray:
        """Flat grid row indices of this chunk."""
        return np.arange(self.start, self.stop)


class ChunkReducer:
    """Associative fold over SweepChunks.  Implementations hold only running
    reductions (argmin scalars, Pareto fronts, histograms ...) so streaming
    sweeps stay O(chunk_size) regardless of grid size."""

    def init(self, spec: GridSpec):
        return None

    def step(self, carry, chunk: SweepChunk):
        raise NotImplementedError

    def finish(self, carry, spec: GridSpec):
        return carry


class MinReducer(ChunkReducer):
    """Running argmin of one metric — the bounded-memory `SweepResult.best`.
    Tracks per-workload minima when the sweep batches traffics."""

    def __init__(self, metric: str = "energy_j"):
        self.metric = metric

    def step(self, carry, chunk: SweepChunk):
        m = chunk.metrics[self.metric]
        j = np.argmin(m, axis=-1)
        v = np.take_along_axis(m, j[..., None], -1)[..., 0]
        i = chunk.start + j
        if carry is None:
            return v, i
        best_v, best_i = carry
        upd = v < best_v
        return np.where(upd, v, best_v), np.where(upd, i, best_i)

    def finish(self, carry, spec: GridSpec):
        if carry is None:
            raise ValueError("empty sweep")
        v, i = carry
        if np.ndim(i) == 0:
            return {"value": float(v), "index": int(i),
                    "config": spec.config_at(int(i))}
        flat_i = np.asarray(i).ravel()
        return {"value": np.asarray(v), "index": np.asarray(i),
                "config": [spec.config_at(int(k)) for k in flat_i]}


def _config_sharding():
    """NamedSharding over the config axis when >1 device is visible (the
    jax.sharding scale-out hook for grids past one device's memory); None on
    a single device."""
    devs = jax.devices()
    if len(devs) <= 1:
        return None
    mesh = jax.sharding.Mesh(np.array(devs), ("configs",))
    return jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec("configs"))


def _run_pipeline(starts, make_task, fold, depth: int) -> None:
    """Double-buffered chunk pipeline: at most `depth` chunk tasks in flight
    beyond the one being folded, folds strictly in submission order (so any
    depth — including 0, the inline serial schedule — produces bit-identical
    reducer states).  Tasks run on one worker thread; XLA releases the GIL
    during device execution, so the main thread's reducer folds overlap the
    next chunk's compute.  Single-chunk grids run inline: there is nothing
    to overlap, and worker-thread startup would only add latency."""
    starts = list(starts)
    if depth <= 0 or len(starts) <= 1:
        for start in starts:
            fold(make_task(start)())
        return
    pending = deque()
    with ThreadPoolExecutor(max_workers=1) as ex:
        for start in starts:
            pending.append(ex.submit(make_task(start)))
            while len(pending) > depth:
                fold(pending.popleft().result())
        while pending:
            fold(pending.popleft().result())


def sweep_chunked(
    traffic,
    reducer: ChunkReducer,
    topologies: Sequence[str] = DEFAULT_TOPOLOGIES,
    devices: Optional[DeviceLibrary] = None,
    active_fraction: float = 1.0,
    chunk_size: int = 65536,
    shard: bool = False,
    columns_fn=None,
    materialize: str = "auto",
    prefetch: Optional[int] = None,
    **axes: Sequence[float],
):
    """Stream a configuration grid through the universal jitted chunk
    program in fixed-size chunks, folding each chunk into `reducer` and
    keeping nothing else.

    Every chunk has exactly `chunk_size` columns (the last one is padded by
    clamping the decode at the final row — repeat-last-row — then sliced
    back) so the program compiles once; peak host memory is
    O(chunk_size * n_columns), independent of grid size.  `traffic` may be
    one Traffic or a sequence (per-workload metric rows).

    `materialize` picks where chunk columns come from:
      * "device" — the jitted mixed-radix decode program generates the chunk
        from the `start` scalar and small device-resident axis tables: zero
        per-chunk host numpy, zero per-chunk H2D column transfer.
      * "host"   — `GridSpec.chunk_cols` builds the columns on the host and
        ships them (the serial reference layout; with ``shard=True`` they
        are laid out across devices along the config axis).
      * "auto"   — "device" unless sharding or a legacy `columns_fn`
        requires host columns.
    Both modes feed the same program instance, so reducer folds are
    bit-identical between them.

    `prefetch` (default: the REPRO_PREFETCH env flag, 2) chunks may be in
    flight ahead of the reducer fold; folds happen in chunk order, so every
    depth produces bit-identical reducer states.

    `columns_fn` hooks fault injection.  A scenario-carrying hook from
    `faults.faulted_columns_fn(scenario)` composes on-device: the scenario
    fields become runtime inputs of the chunk program (its numpy __call__
    stays available as the host reference).  Any other callable
    ``columns_fn(cols, topo_id, topologies) -> (nets, dev_cols)`` runs
    legacy-style on host-materialized columns, whose returned columns may
    carry a leading scenario axis ((S, chunk)).  The config-axis sharding
    path assumes 1-D columns; don't combine it with a batched `columns_fn`.
    """
    spec = grid_spec(topologies, devices=devices, **axes)
    n = spec.n
    if n == 0:
        raise ValueError("empty grid")
    _validate_grid_values(spec)

    scenario = getattr(columns_fn, "scenario", None)
    legacy_fn = columns_fn is not None and scenario is None

    if materialize not in ("auto", "host", "device"):
        raise ValueError(f"materialize must be 'auto', 'host', or 'device', "
                         f"got {materialize!r}")
    if materialize == "auto":
        materialize = "host" if (shard or legacy_fn) else "device"
    elif materialize == "device" and (shard or legacy_fn):
        # sharded layouts and legacy hooks consume host-built columns
        materialize = "host"

    depth = prefetch_depth() if prefetch is None else max(0, int(prefetch))

    sharding = _config_sharding() if shard else None
    chunk_size = int(min(max(1, chunk_size), n))
    if sharding is not None:
        ndev = len(jax.devices())
        chunk_size = ((chunk_size + ndev - 1) // ndev) * ndev

    with engine_x64():
        bits, xfers = _traffic_arrays(traffic)
        bits_j, xfers_j = _as_f64(bits), _as_f64(xfers)
        frac_j = _as_f64(active_fraction)
        scen_j = None if legacy_fn else _scenario_inputs(scenario)
        if materialize == "device":
            tables_j = {k: _as_f64(v) for k, v in spec.axes.items()}
            base_j = {k: _as_f64(v) for k, v in spec.base.items()}

    kernel = _engine_kernel(spec.topologies) if not legacy_fn \
        else _chunk_eval_kernel()
    decode = (_decode_program(spec, chunk_size)
              if materialize == "device" else None)

    def _host_chunk(start, stop):
        cols, topo_id = spec.chunk_cols(start, stop)
        pad = chunk_size - (stop - start)
        if pad:  # repeat the last (valid) row; padded lanes are sliced off
            cols = {k: np.concatenate([v, np.repeat(v[-1:], pad)])
                    for k, v in cols.items()}
            topo_id = np.concatenate([topo_id, np.repeat(topo_id[-1:], pad)])
        return cols, topo_id

    def make_task(start):
        stop = min(start + chunk_size, n)

        if legacy_fn:
            def task():
                with engine_x64():
                    cols, topo_id = _host_chunk(start, stop)
                    nets, dev_cols = columns_fn(cols, topo_id,
                                                spec.topologies)
                    nets_j = {k: _as_f64(nets[k]) for k in MODEL_FIELDS}
                    dev_j = {k: _as_f64(dev_cols[k])
                             for k in _EVAL_DEVICE_FIELDS}
                    if sharding is not None:
                        nets_j = {k: jax.device_put(v, sharding)
                                  for k, v in nets_j.items()}
                        dev_j = {k: jax.device_put(v, sharding)
                                 for k, v in dev_j.items()}
                    mets = kernel(nets_j, dev_j, bits_j, xfers_j, frac_j)
                    return start, stop, topo_id, nets, mets
            return task

        if materialize == "host":
            def task():
                with engine_x64():
                    cols, topo_id = _host_chunk(start, stop)
                    cols_j = {k: _as_f64(v) for k, v in cols.items()}
                    topo_j = jnp.asarray(topo_id)
                    if sharding is not None:
                        cols_j = {k: jax.device_put(v, sharding)
                                  for k, v in cols_j.items()}
                        topo_j = jax.device_put(topo_j, sharding)
                    nets, mets = kernel(cols_j, topo_j, scen_j,
                                        bits_j, xfers_j, frac_j)
                    return start, stop, topo_id, nets, mets
            return task

        def task():  # device-resident materialization: start scalar only
            with engine_x64():
                cols, topo_id = decode(tables_j, base_j, np.int64(start))
                nets, mets = kernel(cols, topo_id, scen_j,
                                    bits_j, xfers_j, frac_j)
                return start, stop, topo_id, nets, mets
        return task

    carry = reducer.init(spec)

    def fold(result):
        nonlocal carry
        start, stop, topo_id, nets, mets = result
        jax.block_until_ready(mets)
        valid = stop - start
        out = {k: np.asarray(v, np.float64) for k, v in mets.items()}
        out = {k: v[..., :valid] for k, v in broadcast_metrics(out, np).items()}
        nets = {k: np.asarray(v)[..., :valid] for k, v in nets.items()}
        topo_id = np.asarray(topo_id)[:valid]
        carry = reducer.step(carry, SweepChunk(
            spec=spec, start=start, stop=stop, topo_id=topo_id,
            nets=nets, metrics=out))

    _run_pipeline(range(0, n, chunk_size), make_task, fold, depth)
    return reducer.finish(carry, spec)


def sweep_scalar_reference(
    traffic: Traffic,
    topologies: Sequence[str] = DEFAULT_TOPOLOGIES,
    devices: Optional[DeviceLibrary] = None,
    active_fraction: float = 1.0,
    **axes: Sequence[float],
) -> Dict[str, np.ndarray]:
    """Golden reference: the identical grid walked through the scalar
    dataclass path (`NetworkParams` -> topology factory -> `evaluate_network`)
    one configuration per Python call.  Returns the same metric columns as
    `sweep(...).metrics`."""
    grid = build_grid(topologies, devices=devices, **axes)
    base = devices or DEFAULT_DEVICES
    out = {k: np.zeros(grid.n, np.float64) for k in METRIC_FIELDS}
    for i in range(grid.n):
        net, d = grid.row_network(i, base)
        rep = evaluate_network(net, traffic, d, active_fraction=active_fraction)
        for key in METRIC_FIELDS:
            out[key][i] = getattr(rep, key)
    return out


# --------------------------------------------------------------------------
# Batched accelerator evaluation (paper Fig. 6 path)
# --------------------------------------------------------------------------

# `evaluate_accelerator_batch` historically lived here; it is now one (mix,
# config) cell of the vmapped co-design grid kernel in core.accelerator and
# re-exported (via the import at the top) for existing callers.
