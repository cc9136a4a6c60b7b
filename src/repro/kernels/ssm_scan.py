"""Chunked selective-state-space scan kernel (Mamba2/SSD-style) for the
zamba2 hybrid and xLSTM mLSTM blocks.

Recurrence (per batch*head, matrix state S in R^{P x N}):
    S_t = a_t * S_{t-1} + x_t ⊗ b_t          (a_t scalar decay per step)
    y_t = S_t c_t

Chunked closed form (chunk length C, cum_t = prod_{s<=t} a_s within chunk):
    y_t   = cum_t * (S_in c_t) + sum_{s<=t} (cum_t/cum_s) (b_s·c_t) x_s
    S_out = cum_C * S_in + sum_s (cum_C/cum_s) x_s ⊗ b_s

Grid (B*H, n_chunks) with the chunk dimension sequential; S carried in VMEM
scratch.  The intra-chunk term is two MXU matmuls ((M⊙G)ᵀX and the gram
B Cᵀ) — this is the standard SSD chunking, mapped to TPU tiles.

Shapes: x (BH, L, P), a (BH, L), b (BH, L, N), c (BH, L, N) -> y (BH, L, P).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 128


def _ssm_kernel(x_ref, cum_col_ref, cum_row_ref, last_ref, b_ref, c_ref,
                y_ref, s_ref, *, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    x = x_ref[0].astype(jnp.float32)            # (C, P)
    cum_c = cum_col_ref[0]                      # (C, 1) inclusive cumprod
    cum_r = cum_row_ref[0]                      # (1, C) the same, as a row
    b = b_ref[0].astype(jnp.float32)            # (C, N)
    c = c_ref[0].astype(jnp.float32)            # (C, N)
    s_in = s_ref[...]                           # (P, N)

    # carry-in contribution: y_carry[t] = cum_t * (c_t @ S_in^T)
    y_carry = cum_c * jax.lax.dot_general(
        c, s_in, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                           # (C, P)

    # intra-chunk: decay matrix M[s,t] = cum_t / cum_s for s <= t
    ratio = cum_r / jnp.maximum(cum_c, 1e-37)
    st_mask = (
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        <= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    )
    m = jnp.where(st_mask, ratio, 0.0)          # (C, C), rows=s, cols=t
    g = jax.lax.dot_general(b, c, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (C_s, C_t)
    w = (m * g)                                 # (s, t)
    y_intra = jax.lax.dot_general(
        w, x, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )                                           # (t, P)

    y_ref[0] = (y_carry + y_intra).astype(y_ref.dtype)

    # state update: S_out = cum_C S_in + sum_s (cum_C / cum_s) x_s b_s^T
    last = last_ref[...]                        # (1, 1) cum_C
    wgt = last / jnp.maximum(cum_c, 1e-37)      # (C, 1)
    s_ref[...] = last * s_in + jax.lax.dot_general(
        x * wgt, b, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssm_scan(
    x: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array,
    *, chunk: int = DEFAULT_CHUNK, interpret: bool = False,
) -> jax.Array:
    bh, l, p = x.shape
    n = b.shape[-1]
    ch = min(chunk, l)
    assert l % ch == 0, (l, ch)
    assert a.shape == (bh, l) and b.shape == (bh, l, n) and c.shape == (bh, l, n)

    # within-chunk inclusive cumprod of the decay, in log space (f32).  The
    # kernel reads it as a column, as a row and (its last entry) as a
    # (1, 1) block per chunk: Mosaic has no in-kernel scan, and cannot
    # broadcast a (1, 1) slice over sublanes and lanes at once
    nc = l // ch
    log_a = jnp.log(jnp.maximum(a.astype(jnp.float32), 1e-37))
    cum = jnp.exp(jnp.cumsum(log_a.reshape(bh, nc, ch), axis=-1))
    last = cum[:, :, -1:, None]                                 # (BH,nc,1,1)
    cum = cum.reshape(bh, l)

    return pl.pallas_call(
        functools.partial(_ssm_kernel, chunk=ch),
        grid=(bh, l // ch),
        in_specs=[
            pl.BlockSpec((1, ch, p), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, ch, 1), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, ch), lambda i, j: (i, 0, j)),
            pl.BlockSpec((None, None, 1, 1), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, ch, n), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, ch, n), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, ch, p), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, l, p), jnp.float32),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(x, cum[:, :, None], cum[:, None, :], last, b, c)
