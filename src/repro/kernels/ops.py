"""Public jit'd wrappers around the Pallas kernels.

Dispatch policy (DESIGN.md §7):
  * TPU      -> compiled Pallas kernels (the target).
  * CPU      -> `interpret=True` (kernel body executed in Python/XLA-CPU) for
                correctness tests, or the pure-jnp reference for speed.
  * dry-run  -> reference path (`use_kernels=False` in model configs), so
                `cost_analysis()` sees the FLOPs/bytes (Pallas custom-calls
                are opaque to HLO cost analysis).

Training: kernel-forward / oracle-backward via custom_vjp — the Pallas
kernels here are forward-only; backward runs the jnp reference's VJP (same
math, XLA-fused).  `photonic_matmul` adds a straight-through estimator so the
quantized (MR-bank) forward trains with full-precision master weights.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.flash_attention import DEFAULT_BQ as BQ
from repro.kernels.flash_attention import flash_attention as _flash_fwd
from repro.kernels.photonic_mac import (
    photonic_mac as _mac_fwd,
    quantize_weights,
    DEFAULT_BK,
    DEFAULT_BN,
)
from repro.kernels.ssm_scan import DEFAULT_CHUNK as SSM_CHUNK
from repro.kernels.ssm_scan import ssm_scan as _ssm_fwd


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


# ---------------------------------------------------------------------------
# photonic matmul with straight-through quantization
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def photonic_matmul(x: jax.Array, w: jax.Array, bits: int = 8,
                    use_kernel: bool = True) -> jax.Array:
    """out = x @ quantize(w): forward through the photonic-MAC numerics
    (per-tile int quantization), backward straight-through to full-precision
    w (standard QAT; the photonic weight banks are programmed from the master
    weights at deploy time)."""
    return _photonic_fwd_impl(x, w, bits, use_kernel)


def _photonic_fwd_impl(x, w, bits, use_kernel):
    k, n = w.shape
    if k % DEFAULT_BK or n % DEFAULT_BN or x.shape[0] % 128:
        # shape not tileable -> reference numerics (same quantization math)
        w_q, scale = _tile_quantize_any(w, bits)
        return jnp.dot(x.astype(jnp.float32), w_q,
                       precision=jax.lax.Precision.HIGHEST)
    w_q, scale = quantize_weights(w, bits=bits)
    if use_kernel:
        return _mac_fwd(x, w_q, scale, interpret=_on_cpu())
    return _ref.photonic_mac_ref(x, w_q, scale)


def _tile_quantize_any(w, bits):
    """Whole-matrix fallback quantization (per-column scale) for non-tileable
    shapes; returns dequantized weights + scale."""
    qmax = 2 ** (bits - 1) - 1
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=0), 1e-8) / qmax
    w_q = jnp.clip(jnp.round(w / scale[None, :]), -qmax, qmax) * scale[None, :]
    return w_q.astype(jnp.float32), scale


def _photonic_vjp_fwd(x, w, bits, use_kernel):
    out = _photonic_fwd_impl(x, w, bits, use_kernel)
    return out, (x, w)


def _photonic_vjp_bwd(bits, use_kernel, res, g):
    x, w = res
    g = g.astype(jnp.float32)
    # straight-through: gradient flows as if w were unquantized
    dx = jnp.dot(g, w.T.astype(jnp.float32)).astype(x.dtype)
    dw = jnp.dot(x.T.astype(jnp.float32), g).astype(w.dtype)
    return dx, dw


photonic_matmul.defvjp(_photonic_vjp_fwd, _photonic_vjp_bwd)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7)
)
def attention(q, k, v, causal: bool = True, window: int = 0,
              scale: float | None = None, q_offset: int = 0,
              use_kernel: bool = True):
    """Flash attention (kernel fwd) with oracle VJP. Shapes (B,H*,S,D)."""
    return _attention_impl(q, k, v, causal, window, scale, q_offset, use_kernel)


def _attention_impl(q, k, v, causal, window, scale, q_offset, use_kernel):
    sq, sk = q.shape[2], k.shape[2]
    if use_kernel and causal and q_offset == 0 and sq == sk > BQ and sq % BQ:
        # causal self-attention at a ragged length (exact-length prefill):
        # pad to the block, where the causal mask hides the padded keys from
        # every real query, and slice the padded query rows off
        pad = ((0, 0), (0, 0), (0, -sq % BQ), (0, 0))
        out = _flash_fwd(jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad),
                         causal=True, window=window, scale=scale,
                         interpret=_on_cpu())
        return out[:, :, :sq]
    tileable = (
        use_kernel
        and sq % min(128, sq) == 0
        and sk % min(128, sk) == 0
        and q_offset % min(128, sq) == 0
        and sk >= 8 and sq >= 8
    )
    if tileable:
        return _flash_fwd(q, k, v, causal=causal, window=window, scale=scale,
                          q_offset=q_offset, interpret=_on_cpu())
    return _ref.attention_ref(q, k, v, causal=causal, window=window,
                              scale=scale, q_offset=q_offset)


def _attention_vjp_fwd(q, k, v, causal, window, scale, q_offset, use_kernel):
    out = _attention_impl(q, k, v, causal, window, scale, q_offset, use_kernel)
    return out, (q, k, v)


def _attention_vjp_bwd(causal, window, scale, q_offset, use_kernel, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _ref.attention_ref(
            q_, k_, v_, causal=causal, window=window, scale=scale,
            q_offset=q_offset),
        q, k, v)
    return vjp(g)


attention.defvjp(_attention_vjp_fwd, _attention_vjp_bwd)


# ---------------------------------------------------------------------------
# ssm scan
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def ssm(x, a, b, c, use_kernel: bool = True):
    """Chunked selective scan (kernel fwd, oracle VJP).
    x (BH,L,P), a (BH,L), b/c (BH,L,N)."""
    return _ssm_impl(x, a, b, c, use_kernel)


def _ssm_impl(x, a, b, c, use_kernel):
    l = x.shape[1]
    if use_kernel and l >= 8:
        # a ragged length pads to the chunk with zero inputs and unit decay:
        # the scan is causal, so the padded tail never reaches a real output
        tail = -l % SSM_CHUNK if l > SSM_CHUNK else 0
        if tail:
            x, b, c = (jnp.pad(t, ((0, 0), (0, tail), (0, 0)))
                       for t in (x, b, c))
            a = jnp.pad(a, ((0, 0), (0, tail)), constant_values=1.0)
        return _ssm_fwd(x, a, b, c, interpret=_on_cpu())[:, :l]
    # XLA fallback = the same chunked SSD algorithm (L/chunk trips, MXU-shaped
    # dots), NOT the sequential oracle — §Perf zamba2 iteration 2
    return _ref.ssm_scan_chunked_ref(x, a, b, c)


def _ssm_vjp_fwd(x, a, b, c, use_kernel):
    return _ssm_impl(x, a, b, c, use_kernel), (x, a, b, c)


def _ssm_vjp_bwd(use_kernel, res, g):
    x, a, b, c = res
    _, vjp = jax.vjp(_ref.ssm_scan_chunked_ref, x, a, b, c)
    return vjp(g)


ssm.defvjp(_ssm_vjp_fwd, _ssm_vjp_bwd)
