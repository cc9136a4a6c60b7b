"""Photonic MAC kernel — 2.5D-CrossLight's broadcast-and-weight numerics on TPU.

The paper's photonic MAC units (Sec. V) imprint weights onto per-wavelength
optical amplitudes with MR filters (limited amplitude resolution — the MR
tuning DAC gives 4..8 bits), multiply noncoherently, and sum partial products
in balanced photodetectors (analog, effectively full-precision accumulation).

TPU adaptation (DESIGN.md §3): a blocked matmul whose weights are
**integer-quantized per (bk × bn) tile with a per-tile scale** — each tile is
one "MR weight bank" whose dynamic range is set by its own tuning — while
activations stay bf16 and accumulation runs in f32 on the MXU (the
photodetector analog-sum analog).  Wavelength-parallelism (#WDM λ) maps to the
K-dimension tile width.

Layout:
  x        (M, K)   bf16/f32 activations
  w_q      (K, N)   int8 quantized weights
  w_scale  (K/bk, N/bn) f32 per-tile scales
  out      (M, N)   f32

Grid (M/bm, N/bn, K/bk); K is the sequential (arbitrary) dimension with an
f32 VMEM accumulator. Tile defaults are MXU-aligned (128).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 128


def _mac_kernel(x_ref, wq_ref, scale_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)
    # dequantize this weight-bank tile: int levels * per-tile scale
    w = wq_ref[...].astype(jnp.float32) * scale_ref[...]
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def photonic_mac(
    x: jax.Array,
    w_q: jax.Array,
    w_scale: jax.Array,
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
    interpret: bool = False,
) -> jax.Array:
    """Quantized-weight matmul: out = x @ (w_q * per-tile scale).

    Shapes need not be tile-aligned: non-multiples (vocab tails, odd hidden
    dims) are zero-padded up to the (bm, bn, bk) grid and the result sliced
    back — padded activation columns multiply padded zero weight rows, so
    the f32 accumulator sees exact +0 contributions and aligned shapes are
    bit-identical to the unpadded kernel.  `w_scale` is per weight-bank tile
    on the ceil grid: shape (ceil(k/bk), ceil(n/bn)).
    """
    m, k = x.shape
    k2, n = w_q.shape
    assert k == k2, (x.shape, w_q.shape)
    n_i = pl.cdiv(m, bm)
    n_j = pl.cdiv(n, bn)
    n_k = pl.cdiv(k, bk)
    assert w_scale.shape == (n_k, n_j), (w_scale.shape, (n_k, n_j))

    mp, kp, np_ = n_i * bm, n_k * bk, n_j * bn
    if (mp, kp) != (m, k):
        x = jnp.pad(x, ((0, mp - m), (0, kp - k)))
    if (kp, np_) != (k, n):
        w_q = jnp.pad(w_q, ((0, kp - k), (0, np_ - n)))

    out = pl.pallas_call(
        functools.partial(_mac_kernel, n_k=n_k),
        grid=(n_i, n_j, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            # one scale per weight-bank tile; the trailing (1, 1) dims equal
            # the array's, which the TPU's (8, 128) block tiling rule admits
            pl.BlockSpec((None, None, 1, 1), lambda i, j, kk: (kk, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, w_q, w_scale.reshape(n_k, n_j, 1, 1))
    return out if (mp, np_) == (m, n) else out[:m, :n]


def quantize_weights(
    w: jax.Array, bits: int = 8, bk: int = DEFAULT_BK, bn: int = DEFAULT_BN
):
    """Per-(bk x bn)-tile symmetric quantization — one scale per MR weight
    bank, range set by the bank's own max |w| (the MR tuning range).

    Non-tile-aligned weights quantize on the zero-padded ceil grid (padding
    is exact zero, so it never widens a bank's absmax range; all-padding
    tiles fall back to the epsilon scale) and `w_q` is sliced back to (k, n).
    `w_scale` comes back (ceil(k/bk), ceil(n/bn)) — exactly what
    `photonic_mac` expects for the same (bk, bn)."""
    k, n = w.shape
    kp, np_ = -(-k // bk) * bk, -(-n // bn) * bn
    if (kp, np_) != (k, n):
        w = jnp.pad(w, ((0, kp - k), (0, np_ - n)))
    tiles = w.reshape(kp // bk, bk, np_ // bn, bn)
    qmax = 2 ** (bits - 1) - 1
    absmax = jnp.max(jnp.abs(tiles), axis=(1, 3))  # (ceil(k/bk), ceil(n/bn))
    scale = jnp.maximum(absmax, 1e-8) / qmax
    w_q = jnp.clip(
        jnp.round(tiles / scale[:, None, :, None]), -qmax, qmax
    ).astype(jnp.int8)
    return w_q.reshape(kp, np_)[:k, :n], scale.astype(jnp.float32)
