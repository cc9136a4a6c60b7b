"""TRINE-inspired explicit collective schedules (shard_map + jax.lax).

The paper's interposer insight mapped to mesh collectives (DESIGN.md §2):

  * `trine_all_reduce`   — stage-minimal hierarchical all-reduce: reduce-
    scatter inside the pod (one "subnetwork" stage), all-reduce across the
    tiny pod axis (the only slow-link stage), all-gather back inside the pod.
    A flat all-reduce over 512 devices rings through every device — the bus
    topology; the hierarchical schedule crosses the slow axis exactly once —
    TRINE's 2-stage tree vs the 5-stage tree / N-stage bus.

  * `compressed_all_reduce` — int8 + per-chunk scale on the cross-pod stage
    only (the bandwidth-starved link), with error-feedback residual: the PCMC
    bandwidth-adaptation analog (spend fewer "wavelengths" on low-value
    traffic).

  * `plan_channels` — re-exports the Layer-A bandwidth-matching planner for
    collective chunking (how many chunks in flight to hide a collective under
    a compute window).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


from repro.core.planner import plan_collective_channels as plan_channels  # noqa: F401 — re-export


def _pad_to(x: jax.Array, mult: int):
    n = x.shape[0]
    pad = (-n) % mult
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
    return x, n


def flat_all_reduce(x: jax.Array, mesh: Mesh, axes: Tuple[str, ...] = ("pod", "data")):
    """Baseline: single-stage all-reduce over the full device set (the
    bus-topology analog)."""
    axes = tuple(a for a in axes if a in mesh.axis_names)

    def f(v):
        return jax.lax.psum(v, axes)

    return jax.shard_map(f, mesh=mesh, in_specs=P(), out_specs=P(),
                     check_vma=False)(x)


def trine_all_reduce(x: jax.Array, mesh: Mesh):
    """Hierarchical: RS(data) -> AR(pod) -> AG(data).  Cross-pod (slow) bytes
    drop by the data-axis size versus the flat schedule."""
    if "pod" not in mesh.axis_names:
        return flat_all_reduce(x, mesh, axes=("data",))
    data_n = dict(zip(mesh.axis_names, mesh.devices.shape))["data"]

    def f(v):
        flatshape = v.shape
        flat = v.reshape(-1)
        flat, orig = _pad_to(flat, data_n)
        piece = jax.lax.psum_scatter(flat, "data", scatter_dimension=0, tiled=True)
        piece = jax.lax.psum(piece, "pod")
        full = jax.lax.all_gather(piece, "data", axis=0, tiled=True)
        return full[:orig].reshape(flatshape)

    return jax.shard_map(f, mesh=mesh, in_specs=P(), out_specs=P(),
                     check_vma=False)(x)


def _quantize_int8(v: jax.Array, chunk_elems: Optional[int] = None):
    """Symmetric int8 quantization of a 1-D tensor with per-chunk max-abs
    scales.  `chunk_elems=None` degenerates to one global scale (a single
    chunk spanning the tensor).

    Returns (q, scale): q is (n_chunks, chunk_elems) int8 (v zero-padded up
    to a chunk multiple), scale is (n_chunks,) f32.  Per-chunk scales
    localize outliers — one huge entry inflates only its own chunk's step
    size instead of the whole tensor's (the PCMC bandwidth-adaptation
    analog: spend precision where the signal is) — at a wire cost of one
    f32 per chunk.
    """
    n = v.shape[0]
    chunk = n if chunk_elems is None else max(1, min(int(chunk_elems), n))
    vp, _ = _pad_to(v, chunk)
    blocks = vp.reshape(-1, chunk)
    scale = (jnp.maximum(jnp.max(jnp.abs(blocks), axis=1), 1e-20)
             / 127.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(blocks / scale[:, None]), -127, 127).astype(jnp.int8)
    return q, scale


def _dequantize_int8(q: jax.Array, scale: jax.Array, n: int) -> jax.Array:
    """Inverse of `_quantize_int8`: (n_chunks, chunk) int8 x (n_chunks,)
    scales -> the first `n` dequantized f32 elements."""
    return (q.astype(jnp.float32) * scale[:, None]).reshape(-1)[:n]


def compressed_all_reduce(
    x: jax.Array,
    mesh: Mesh,
    residual: Optional[jax.Array] = None,
    chunk_elems: Optional[int] = None,
):
    """Hierarchical all-reduce with int8 compression on the cross-pod stage
    and error feedback.  Returns (result, new_residual).

    Intra-pod runs full precision (fast links); only the pod axis — the
    bandwidth-starved stage — carries 8-bit payloads (each pod's int8
    shard + per-chunk f32 scales are all-gathered and dequant-summed
    locally; an int8 psum would overflow and an f32 psum would put
    full-width bytes on the slow link).  `chunk_elems` sets the
    quantization granularity (None = one global scale per shard).  The
    quantization error is fed back into the next step's gradients
    (standard EF-SGD, keeps convergence).
    """
    if residual is None:
        residual = jnp.zeros_like(x)
    if "pod" not in mesh.axis_names:
        # Nothing is quantized on a single-axis mesh, but the carried
        # residual still holds gradient mass from earlier compressed steps:
        # fold it into the payload and drain it, rather than dropping it.
        out = flat_all_reduce(x + residual, mesh, axes=("data",))
        return out, jnp.zeros_like(x)

    data_n = dict(zip(mesh.axis_names, mesh.devices.shape))["data"]

    def f(v, res):
        flatshape = v.shape
        flat = (v + res).reshape(-1)
        flat, orig = _pad_to(flat, data_n)
        piece = jax.lax.psum_scatter(flat, "data", scatter_dimension=0, tiled=True)
        q, scale = _quantize_int8(piece, chunk_elems)
        deq_local = _dequantize_int8(q, scale, piece.shape[0])
        new_res_flat = (piece - deq_local)  # local quantization error
        # cross-pod stage at int8 wire width: gather every pod's (q, scale)
        # and dequantize+sum locally
        qg = jax.lax.all_gather(q, "pod", axis=0, tiled=False)
        sg = jax.lax.all_gather(scale, "pod", axis=0, tiled=False)
        deq = (qg.astype(jnp.float32) * sg[:, :, None])
        summed = jnp.sum(deq.reshape(deq.shape[0], -1)[:, :piece.shape[0]],
                         axis=0)
        full = jax.lax.all_gather(summed, "data", axis=0, tiled=True)
        res_full = jax.lax.all_gather(new_res_flat, "data", axis=0, tiled=True)
        return (full[:orig].reshape(flatshape),
                res_full[:orig].reshape(flatshape))

    out, new_res = jax.shard_map(
        f, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
        check_vma=False,
    )(x, residual)
    return out, new_res


def collective_bytes_estimate(n_elems: int, dtype_bytes: int, mesh: Mesh,
                              schedule: str,
                              chunk_elems: Optional[int] = None) -> dict:
    """Napkin-math model used by the planner & EXPERIMENTS.md: bytes crossing
    the slow (pod) links per device under each schedule.

    Mirrors the shard_map kernels op for op (ring-algorithm factors, the
    same padding, and — for ``trine_int8`` — the residual all-gather and
    per-chunk f32 scale payloads the kernel actually issues), so the
    estimate matches bytes measured from the compiled HLO by
    `repro.launch.hlo_analysis.analyze_hlo`; tests assert that match.
    `chunk_elems` must agree with the value passed to
    `compressed_all_reduce` (None = one global scale per shard).
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_pod = sizes.get("pod", 1)
    n_data = sizes.get("data", 1)
    total = n_elems * dtype_bytes
    if schedule == "flat":
        n = n_pod * n_data
        ring = 2 * (n - 1) / n * total
        # a flat ring crosses pod boundaries ~ (n_pod-1)/n_pod of its hops
        cross = ring * (n_pod - 1) / max(n_pod, 1)
        return {"total_bytes": ring, "cross_pod_bytes": cross}
    if schedule == "trine":
        rs = (n_data - 1) / n_data * total
        ar = 2 * (n_pod - 1) / n_pod * (total / n_data)
        ag = (n_data - 1) / n_data * total
        return {"total_bytes": rs + ar + ag, "cross_pod_bytes": ar}
    if schedule == "trine_int8":
        shard = -(-n_elems // n_data)          # kernel pads to a data multiple
        padded = shard * n_data * dtype_bytes
        chunk = shard if chunk_elems is None else max(1, min(int(chunk_elems),
                                                             shard))
        n_chunks = -(-shard // chunk)
        rs = (n_data - 1) / n_data * padded
        # cross-pod all-gathers: int8 shard + f32 per-chunk scales
        q_ag = (n_pod - 1) * n_chunks * chunk * 1
        scale_ag = (n_pod - 1) * n_chunks * 4
        # intra-pod all-gathers: the f32 result AND the f32 EF residual the
        # kernel gathers back to full shape
        ag = 2 * (n_data - 1) / n_data * padded
        cross = q_ag + scale_ag
        return {"total_bytes": rs + cross + ag, "cross_pod_bytes": cross}
    raise ValueError(schedule)
