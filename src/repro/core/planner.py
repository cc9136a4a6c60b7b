"""Bandwidth-matching planners.

TRINE's quantitative core (paper Sec. IV): "The number of subnetworks can be
tailored to match the bandwidth that the memory can provide, ensuring that the
network bandwidth of memory aligns with the memory bandwidth.  This approach
maximizes performance without wasting network resources."

The same matching principle drives two planners here:

  * `choose_subnetworks`     -- Layer A: pick K tree subnetworks so
                                K * waveguide_BW ~= memory_BW.
  * `plan_collective_channels` -- Layer B: pick how many parallel collective
                                chunks (channels) to launch per layer so the
                                collective time matches the compute time it
                                can hide under (the TPU-mesh analog: ICI
                                bandwidth is the "memory", overlap window is
                                the "network").
  * `plan_gateway_activation` -- 2.5D-CrossLight's PCMC adaptation: fraction
                                of gateways to keep lit given a layer's
                                traffic demand.
"""

from __future__ import annotations

import math
from typing import TYPE_CHECKING

import numpy as np

__all__ = [
    "choose_subnetworks", "choose_subnetworks_arr",
    "plan_gateway_activation", "plan_gateway_activation_arr",
    "plan_collective_channels", "ceil_log2", "snap_ties",
]

if TYPE_CHECKING:  # pragma: no cover
    from repro.core.topology import NetworkParams


def _asx(xp, v):
    """float64 on the numpy path, namespace default under jax tracing."""
    return np.asarray(v, np.float64) if xp is np else xp.asarray(v)


# How near a planner quantity must come to a tie (an integer, or a half for
# round-half-even) to count as on it.  The TPU emulates float64 with about 48
# significand bits, and its division and square root do not round as IEEE
# does: an exact quotient such as 400e9 / 80e9 = 5 can land an ulp above 5
# there and ceil to 6.  1e-9 is far above that error (the engine's metrics
# agree with the host's to 5e-14 on a v5e) and far below how near any
# quotient of the grids' axis values comes to a tie without being on it.
TIE_RTOL = 1e-9


def snap_ties(v, xp=np):
    """`v` with every value within TIE_RTOL of a multiple of 1/2 moved onto
    it, so that the ceil, floor and round taken on it decide alike on every
    backend.  Scaling by 2 and 1/2 is exact in emulated float64 too."""
    v = _asx(xp, v)
    h = xp.round(v * 2.0) * 0.5
    return xp.where(xp.abs(v - h) <= TIE_RTOL * xp.abs(h), h, v)


_POW2_MIN = 64
_POW2 = 2.0 ** np.arange(-_POW2_MIN, _POW2_MIN + 1)  # exact in f32 and f64


def ceil_log2(v, xp=np):
    """Exact elementwise ceil(log2(v)) for v > 0, with zero gradient.

    XLA's `log2` is not correctly rounded at exact powers of two (e.g.
    log2(16) can evaluate to 4.000000000000001 inside a fused program), so
    `ceil(log2(v))` may overshoot by a whole stage precisely at the integral
    points the topology kernels care about.  frexp is exact by construction:
    v = m * 2**e with m in [0.5, 1), hence ceil(log2 v) = e, except at exact
    powers of two where m == 0.5 and ceil(log2 v) = e - 1.

    The traced path counts the powers of two below v instead, which is as
    exact for 2**-64 < v <= 2**64: frexp bitcasts float64 to int64, which
    the TPU's float64 emulation cannot compile.  It wraps the input in
    stop_gradient — the result is piecewise constant, so its gradient is
    zero exactly like ceil(log2(.)) would give.  Counts within TIE_RTOL of
    an integer are taken as that integer (`snap_ties`).
    """
    if xp is np:
        m, e = np.frexp(snap_ties(v))
        return _asx(xp, xp.where(m == 0.5, e - 1, e))
    import jax  # runtime import: the numpy path must stay jax-free
    v = snap_ties(jax.lax.stop_gradient(xp.asarray(v)), xp)
    below = v[..., None] > xp.asarray(_POW2, v.dtype)
    return _asx(xp, below.sum(-1) - _POW2_MIN)


def choose_subnetworks_arr(n_lambda, modulation_rate_bps, n_mem_chiplets,
                           mem_bw_bytes_per_s, n_gateways, xp=np,
                           round_mode: str = "paper"):
    """Vectorized K*: elementwise over struct-of-arrays parameter columns
    (the sweep-engine path; `choose_subnetworks` is the scalar wrapper).
    Pass ``xp=jax.numpy`` to trace it inside a jitted/differentiated kernel;
    the round/ceil quantization is piecewise-constant (zero gradient).

    `round_mode` picks the power-of-two snap for the raw K = ceil(mem/wg):
      "paper"  geometrically (log-space) nearest power of two — the paper's
               9 -> 8 choice, implemented as 2**round(log2 K).  This differs
               from the arithmetically nearest power of two (k=6 ->
               2**round(2.585) = 8, though |6-4| = |6-8|) and may round DOWN
               below the memory bandwidth,
      "cover"  next power of two up — the smallest pow2 K that actually
               covers mem_bw (never under-provisions).
    Both are clamped to the gateway count."""
    wg_bw = _asx(xp, n_lambda) * _asx(xp, modulation_rate_bps)
    mem_bw = _asx(xp, n_mem_chiplets) * _asx(xp, mem_bw_bytes_per_s) * 8.0
    k = xp.maximum(1.0, xp.ceil(snap_ties(mem_bw / wg_bw, xp)))
    # power-of-two so subnet trees stay balanced (paper uses 8)
    if round_mode == "paper":
        k_pow2 = 2.0 ** xp.round(xp.log2(k))
    elif round_mode == "cover":
        k_pow2 = 2.0 ** ceil_log2(k, xp)
    else:
        raise ValueError(
            f"round_mode must be 'paper' or 'cover', got {round_mode!r}")
    return xp.minimum(k_pow2, _asx(xp, n_gateways))


def choose_subnetworks(p: "NetworkParams", round_mode: str = "paper") -> int:
    """Subnetwork count K for TRINE, a power of two clamped to the gateway
    count.

    With the paper's numbers (the TRINE eval provisions against one
    100 GB/s memory interface per subnet group): 100 GB/s = 800 Gb/s,
    waveguide = 8 lambda * 12 Gb/s = 96 Gb/s  =>  raw K = ceil(800/96) = 9.
    The default ``round_mode="paper"`` reproduces the paper's choice — the
    GEOMETRICALLY (log-space) nearest power of two, 2**round(log2 K)
    (9 -> 8: "we opted for 8 subnetworks to use the maximum bandwidth
    offered by memory chiplets").  Note this is not the arithmetically
    nearest power of two (k=6 snaps up to 8, not down to 4) and it can
    round DOWN below the memory bandwidth it nominally matches.  Pass
    ``round_mode="cover"`` for the smallest power-of-two K with
    K * wg_bw >= mem_bw (next power of two up; 9 -> 16), which never
    under-provisions.
    """
    return int(choose_subnetworks_arr(
        p.n_lambda, p.modulation_rate_bps, p.n_mem_chiplets,
        p.mem_bw_bytes_per_s, p.n_gateways, round_mode=round_mode))


def plan_gateway_activation_arr(demand_bytes_per_s, max_bw_bytes_per_s,
                                n_gateways, xp=np):
    """Vectorized PCMC gateway-activation fraction (sweep/batched path).
    ``xp=jax.numpy`` makes it traceable inside the co-design grid kernel."""
    demand = _asx(xp, demand_bytes_per_s)
    maxbw = _asx(xp, max_bw_bytes_per_s)
    n = _asx(xp, n_gateways)
    frac = xp.clip(demand / xp.where(maxbw > 0, maxbw, np.inf), 0.0, 1.0)
    steps = xp.maximum(1.0, xp.ceil(snap_ties(frac * n, xp)))
    return xp.where(maxbw > 0, steps / n, 1.0)


def plan_gateway_activation(
    demand_bytes_per_s: float,
    max_bw_bytes_per_s: float,
    n_gateways: int,
) -> float:
    """2.5D-CrossLight PCMC gateway activation: keep the smallest fraction of
    gateways lit that still covers the traffic demand.  Returns the active
    fraction in {1/n, 2/n, ..., 1}.  Deactivated gateways are power-gated and
    their PCMC couplers divert laser power (laser scales with the fraction).
    """
    return float(plan_gateway_activation_arr(
        demand_bytes_per_s, max_bw_bytes_per_s, n_gateways))


def plan_collective_channels(
    collective_bytes: float,
    overlap_window_s: float,
    link_bw_bytes_per_s: float = None,
    max_channels: int = 8,
    min_chunk_bytes: float = 1 << 20,
    fabric=None,
) -> int:
    """Layer B bandwidth matching: number of parallel collective channels
    (chunks in flight) so transfer time ~= the compute window it hides under.

    channels = ceil(bytes / (window * bw)) -- i.e. provision exactly enough
    parallelism, never more (TRINE: "without wasting network resources").
    Clamped so chunks stay large enough to amortize per-collective latency.

    The link bandwidth may be given directly (`link_bw_bytes_per_s`) or
    derived from a network design point (`fabric` — a `core.fabric.Fabric`,
    a preset name like "trine_siph", or anything with a
    ``cross_pod_bw_bytes_per_s`` attribute); `fabric` wins when both are
    passed, since it reflects the design under evaluation.
    """
    if fabric is not None:
        link_bw_bytes_per_s = getattr(fabric, "cross_pod_bw_bytes_per_s", None)
        if link_bw_bytes_per_s is None:
            from repro.core.fabric import get_fabric  # runtime: no cycle
            link_bw_bytes_per_s = get_fabric(fabric).cross_pod_bw_bytes_per_s
    if link_bw_bytes_per_s is None:
        raise ValueError("pass link_bw_bytes_per_s or fabric")
    if link_bw_bytes_per_s <= 0:
        # a fully-degraded fabric: no channel count can carry the collective
        from repro.core.faults import FabricUnusableError  # runtime: no cycle
        raise FabricUnusableError(
            "collective cannot be scheduled: link bandwidth is zero "
            "(fabric degraded beyond use)")
    if collective_bytes <= 0:
        return 1
    need = collective_bytes / max(overlap_window_s * link_bw_bytes_per_s, 1e-30)
    ch = max(1, math.ceil(need))
    ch = min(ch, max_channels, max(1, int(collective_bytes // min_chunk_bytes)))
    return int(ch)
