"""Building blocks for every assigned architecture family.

Pure functions over explicit parameter dicts.  Each `init_*` returns
`(params, specs)` where `specs` mirrors `params` with logical-axis tuples
(consumed by `repro.parallel.sharding`).  Each `apply_*` takes `(cfg, params,
x, ...)`, casts to the compute dtype, and is scan/remat friendly.

Every matmul routes through `linear()`, which optionally applies the
photonic-MAC QAT numerics (2.5D-CrossLight broadcast-and-weight quantization)
— the paper's compute engine as a first-class model feature.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.models.config import ModelConfig
from repro.parallel import actx

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# init / linear helpers
# ---------------------------------------------------------------------------


def _dense_init(key, shape, in_axes=(0,), dtype=jnp.float32):
    fan_in = max(1, math.prod(shape[a] for a in in_axes))
    return (jax.random.normal(key, shape, dtype) / math.sqrt(fan_in)).astype(dtype)


def compute_dtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def linear(cfg: ModelConfig, w: jax.Array, x: jax.Array) -> jax.Array:
    """x (..., K) @ w (K, ...out) with optional photonic-MAC numerics."""
    k = w.shape[0]
    out_shape = w.shape[1:]
    if cfg.use_photonic_mac:
        x2 = x.reshape(-1, k)
        w2 = w.reshape(k, -1)
        y = ops.photonic_matmul(x2, w2, cfg.photonic_bits, cfg.use_kernels)
        return y.reshape(*x.shape[:-1], *out_shape).astype(x.dtype)
    # NOTE: wire formats (bf16/int8 param all-gathers) are applied at TREE
    # level by `repro.parallel.wire` at step entry — an in-layer constraint
    # here cannot know the leaf's sharded spec and measurably backfires
    # (EXPERIMENTS.md §Perf, deepseek iter.3a).
    y = jax.lax.dot_general(
        x, w.astype(x.dtype),
        (((x.ndim - 1,), (0,)), ((), ())),
    )
    return y


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return ((x * jax.lax.rsqrt(var + eps)) * (1.0 + scale.astype(jnp.float32))).astype(dt)


# ---------------------------------------------------------------------------
# RoPE (+ M-RoPE)
# ---------------------------------------------------------------------------


def rope_freqs(cfg: ModelConfig) -> jax.Array:
    dh = cfg.head_dim_
    return 1.0 / (cfg.rope_theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))


def apply_rope(cfg: ModelConfig, x: jax.Array, positions: jax.Array) -> jax.Array:
    """x (B, S, H, Dh). positions (B, S) int32, or (3, B, S) for M-RoPE
    (temporal/height/width streams; equal streams == standard RoPE)."""
    dh = x.shape[-1]
    freqs = rope_freqs(cfg)  # (Dh/2,)
    if cfg.mrope and positions.ndim == 3:
        # split rotary dims into 3 contiguous sections (t, h, w)
        n = dh // 2
        s0, s1 = n - 2 * (n // 3), n // 3  # t gets the remainder
        sect = jnp.concatenate([
            jnp.zeros((s0,), jnp.int32),
            jnp.ones((s1,), jnp.int32),
            jnp.full((n - s0 - s1,), 2, jnp.int32),
        ])
        pos = positions.astype(jnp.float32)  # (3, B, S)
        angles = pos[..., None] * freqs[None, None, None, :]  # (3, B, S, n)
        angle = jnp.take_along_axis(
            jnp.moveaxis(angles, 0, -1), sect[None, None, :, None], axis=-1
        )[..., 0]  # (B, S, n)
    else:
        angle = positions.astype(jnp.float32)[..., None] * freqs[None, None, :]
    cos = jnp.cos(angle)[:, :, None, :]  # (B, S, 1, n)
    sin = jnp.sin(angle)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def init_attention(cfg: ModelConfig, key) -> Tuple[Params, Params]:
    m, h, hk, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "wq": _dense_init(k1, (m, h, dh)),
        "wk": _dense_init(k2, (m, hk, dh)),
        "wv": _dense_init(k3, (m, hk, dh)),
        "wo": _dense_init(k4, (h, dh, m), in_axes=(0, 1)),
        "norm": jnp.zeros((m,)),
    }
    s = {
        "wq": ("embed", "heads", "head_dim"),
        "wk": ("embed", "kv_heads", "head_dim"),
        "wv": ("embed", "kv_heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
        "norm": (None,),
    }
    return p, s


def _qkv(cfg: ModelConfig, p: Params, x: jax.Array, positions: jax.Array):
    b, s, m = x.shape
    h, hk, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    q = linear(cfg, p["wq"].reshape(m, h * dh), x).reshape(b, s, h, dh)
    k = linear(cfg, p["wk"].reshape(m, hk * dh), x).reshape(b, s, hk, dh)
    v = linear(cfg, p["wv"].reshape(m, hk * dh), x).reshape(b, s, hk, dh)
    q = apply_rope(cfg, q, positions)
    k = apply_rope(cfg, k, positions)
    return q, k, v


def apply_attention(
    cfg: ModelConfig,
    p: Params,
    x: jax.Array,
    positions: jax.Array,
    *,
    window: int = 0,
    causal: bool = True,
    cache: Optional[Params] = None,
    cache_pos: Optional[jax.Array] = None,
    return_kv: bool = False,
):
    """Pre-norm attention block with residual.

    Train/prefill: cache is None -> full-sequence attention (flash kernel or
    reference).  Decode: cache {'k','v'} (B,Hk,Sc,Dh) + cache_pos scalar ->
    one-step attention over the cache.
    """
    b, s, m = x.shape
    h, hk, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    if cache is None:
        x = actx.constrain_seq(x)  # seq_tp: context-parallel attention
    xn = rms_norm(x, p["norm"])
    q, k, v = _qkv(cfg, p, xn, positions)
    q = jnp.moveaxis(q, 2, 1)  # (B,H,S,Dh)
    k = jnp.moveaxis(k, 2, 1)
    v = jnp.moveaxis(v, 2, 1)

    new_cache = None
    if cache is None:
        out = ops.attention(q, k, v, causal, window, None, 0, cfg.use_kernels)
    elif s > 1:
        # prefill: full-sequence attention, then materialize the cache
        out = ops.attention(q, k, v, causal, window, None, 0, cfg.use_kernels)
        wlen = cache["k"].shape[2]
        kd, vd = k.astype(cache["k"].dtype), v.astype(cache["v"].dtype)
        if s >= wlen:  # windowed (or exact-length) cache: keep the last wlen
            new_cache = {"k": kd[:, :, s - wlen:], "v": vd[:, :, s - wlen:]}
        else:
            new_cache = {
                "k": jax.lax.dynamic_update_slice_in_dim(cache["k"], kd, cache_pos, axis=2),
                "v": jax.lax.dynamic_update_slice_in_dim(cache["v"], vd, cache_pos, axis=2),
            }
    else:
        # single-step decode; windowed caches roll once full.  cache_pos may
        # be a scalar (lockstep batch) or a (B,) vector (continuous batching:
        # each slot decodes at its own position).
        wlen = cache["k"].shape[2]
        kd, vd = k.astype(cache["k"].dtype), v.astype(cache["v"].dtype)
        pos_b = jnp.broadcast_to(jnp.asarray(cache_pos), (b,))

        def upd1(c, new, pos):
            rolled = jax.lax.cond(
                pos >= wlen,
                lambda a: jnp.roll(a, -1, axis=1),
                lambda a: a,
                c)                                     # (Hk, W, Dh) per example
            slot = jnp.minimum(pos, wlen - 1)
            return jax.lax.dynamic_update_slice_in_dim(rolled, new, slot, axis=1)

        upd = jax.vmap(upd1)
        ck, cv = upd(cache["k"], kd, pos_b), upd(cache["v"], vd, pos_b)
        new_cache = {"k": ck, "v": cv}
        pos_eff = jnp.minimum(
            cache_pos, wlen - 1)                       # scalar or (B,)
        out = decode_attention(q, ck, cv, pos_eff, window=0)

    out = jnp.moveaxis(out.astype(x.dtype), 1, 2).reshape(b, s, h * dh)
    y = linear(cfg, p["wo"].reshape(h * dh, m), out)
    res = x + y
    if return_kv:
        return res, new_cache, (k, v)
    return res, new_cache


def decode_attention(q, k, v, pos, *, window: int = 0):
    """One-step (or few-step) attention over a statically-shaped KV cache.
    q (B,H,Sq,Dh); k,v (B,Hk,Sc,Dh); pos = absolute position of the last
    query — a scalar, or a (B,) vector for continuous batching.
    GSPMD shards Sc; softmax renormalizes globally (flash-decoding style)."""
    b, h, sq, dh = q.shape
    hk, sc = k.shape[1], k.shape[2]
    group = h // hk
    qg = q.reshape(b, hk, group, sq, dh).astype(jnp.float32)
    s = jnp.einsum("bkgqd,bksd->bkgqs", qg, k.astype(jnp.float32)) * dh ** -0.5
    kpos = jnp.arange(sc)
    pos = jnp.asarray(pos)
    qpos = (pos[:, None] if pos.ndim else pos) - jnp.arange(sq)[::-1]  # (B?,Sq)
    valid = kpos <= qpos[..., None]                    # (Sq,Sc) or (B,Sq,Sc)
    if window > 0:
        valid &= kpos > qpos[..., None] - window
    mask = valid[:, None, None] if pos.ndim else valid[None, None, None]
    s = jnp.where(mask, s, -1e30)
    pm = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bksd->bkgqd", pm, v.astype(jnp.float32))
    return out.reshape(b, h, sq, dh)


def init_cross_attention(cfg: ModelConfig, key) -> Tuple[Params, Params]:
    return init_attention(cfg, key)


def apply_cross_attention(cfg: ModelConfig, p: Params, x, enc_out, positions):
    """Decoder cross-attention: queries from x, keys/values from enc_out."""
    b, s, m = x.shape
    se = enc_out.shape[1]
    h, hk, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    xn = rms_norm(x, p["norm"])
    q = linear(cfg, p["wq"].reshape(m, h * dh), xn).reshape(b, s, h, dh)
    k = linear(cfg, p["wk"].reshape(m, hk * dh), enc_out).reshape(b, se, hk, dh)
    v = linear(cfg, p["wv"].reshape(m, hk * dh), enc_out).reshape(b, se, hk, dh)
    out = ops.attention(
        jnp.moveaxis(q, 2, 1), jnp.moveaxis(k, 2, 1), jnp.moveaxis(v, 2, 1),
        False, 0, None, 0, cfg.use_kernels)
    out = jnp.moveaxis(out.astype(x.dtype), 1, 2).reshape(b, s, h * dh)
    return x + linear(cfg, p["wo"].reshape(h * dh, m), out)


# ---------------------------------------------------------------------------
# MLP / MoE
# ---------------------------------------------------------------------------


def init_mlp(cfg: ModelConfig, key) -> Tuple[Params, Params]:
    m, f = cfg.d_model, cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "wi": _dense_init(k1, (m, f)),
        "wg": _dense_init(k2, (m, f)),
        "wo": _dense_init(k3, (f, m)),
        "norm": jnp.zeros((m,)),
    }
    s = {"wi": ("embed", "ffn"), "wg": ("embed", "ffn"),
         "wo": ("ffn", "embed"), "norm": (None,)}
    return p, s


def apply_mlp(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    x = actx.constrain_unseq(x)  # seq_tp: hand the TP axis back to the MLP
    xn = rms_norm(x, p["norm"])
    g = jax.nn.silu(linear(cfg, p["wg"], xn).astype(jnp.float32)).astype(x.dtype)
    h = linear(cfg, p["wi"], xn) * g
    return x + linear(cfg, p["wo"], h)


def init_moe(cfg: ModelConfig, key) -> Tuple[Params, Params]:
    m, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "router": _dense_init(k1, (m, e)),
        "wi": _dense_init(k2, (e, m, f), in_axes=(1,)),
        "wg": _dense_init(k3, (e, m, f), in_axes=(1,)),
        "wo": _dense_init(k4, (e, f, m), in_axes=(1,)),
        "norm": jnp.zeros((m,)),
    }
    s = {"router": ("embed", None),
         "wi": ("experts", "embed", "ffn"), "wg": ("experts", "embed", "ffn"),
         "wo": ("experts", "ffn", "embed"), "norm": (None,)}
    return p, s


def _moe_index_path(cfg: ModelConfig, p: Params, xn, idx, gate_vals, keep,
                    pos_ce, cap: int):
    """Index-based MoE dispatch body (may run inside a batch-manual
    shard_map — all shapes here are per-shard local)."""
    b, s, m = xn.shape
    e, k = cfg.n_experts, cfg.top_k
    dt = xn.dtype
    t_e = idx.transpose(0, 2, 1).reshape(b, k * s)             # expert per choice
    keep_t = jnp.sum(keep, axis=-1) > 0                        # (B,kS)
    c_t = pos_ce
    s_t = jnp.broadcast_to(
        jnp.tile(jnp.arange(s, dtype=jnp.int32), k)[None], (b, k * s))
    dump = jnp.where(keep_t, c_t, cap)                         # dropped -> dump slot
    flat_slot = t_e * (cap + 1) + dump                         # (B,kS)

    def scat(vals, dtype):
        def one(fs, v):
            return jnp.zeros((e * (cap + 1),), dtype).at[fs].set(v)
        return jax.vmap(one)(flat_slot, vals)                  # (B, E*(cap+1))

    slot_token = scat(s_t, jnp.int32).reshape(b, e, cap + 1)[..., :cap]
    slot_valid = scat(keep_t, jnp.bool_).reshape(b, e, cap + 1)[..., :cap]
    xe = jnp.take_along_axis(
        xn, slot_token.reshape(b, e * cap)[..., None], axis=1)
    xe = jnp.where(slot_valid.reshape(b, e * cap)[..., None], xe, 0)
    xe = jnp.moveaxis(xe.reshape(b, e, cap, m).astype(dt), 0, 1)  # (E,B,C,M)
    gme = jax.nn.silu(jnp.einsum("ebcm,emf->ebcf", xe, p["wg"].astype(dt))
                      .astype(jnp.float32)).astype(dt)
    hme = jnp.einsum("ebcm,emf->ebcf", xe, p["wi"].astype(dt)) * gme
    ye = jnp.einsum("ebcf,efm->ebcm", hme, p["wo"].astype(dt))
    ye_b = jnp.moveaxis(ye, 0, 1).reshape(b, e * cap, m)       # (B,E*C,M)
    flat_ec = t_e * cap + jnp.minimum(c_t, cap - 1)            # (B,kS)
    yt = jnp.take_along_axis(ye_b, flat_ec[..., None], axis=1)
    yt = jnp.where(keep_t[..., None], yt, 0)
    gate_t = gate_vals.transpose(0, 2, 1).reshape(b, k * s)    # choices-major
    return jnp.sum((yt * gate_t[..., None].astype(yt.dtype))
                   .reshape(b, k, s, m), axis=1)


def apply_moe(cfg: ModelConfig, p: Params, x: jax.Array):
    """Top-k routed MoE with capacity (GShard-style dispatch/combine einsums;
    expert dim shards over the mesh for expert parallelism).  Returns
    (y, aux_loss)."""
    b, s, m = x.shape
    e, k = cfg.n_experts, cfg.top_k
    cap = max(1, int(cfg.capacity_factor * s * k / e))

    xn = rms_norm(x, p["norm"])
    logits = linear(cfg, p["router"], xn).astype(jnp.float32)  # (B,S,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, idx = jax.lax.top_k(probs, k)                   # (B,S,k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    # position of each (token, choice) within its expert's capacity buffer
    onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)         # (B,S,k,E)
    flat = onehot.transpose(0, 2, 1, 3).reshape(b, k * s, e)   # choices-major
    pos_in_expert = jnp.cumsum(flat, axis=1) - flat            # (B,k*S,E)
    keep = (pos_in_expert < cap) * flat
    pos_ce = jnp.einsum("bte,bte->bt", pos_in_expert, keep)    # (B,k*S)
    if cfg.moe_dispatch != "index":
        disp_flat = keep[..., None] * jax.nn.one_hot(pos_ce, cap)[:, :, None, :]  # (B,k*S,E,C)
        dispatch = disp_flat.reshape(b, k, s, e, cap).transpose(0, 2, 1, 3, 4)
        combine = dispatch * gate_vals[..., None, None]        # (B,S,k,E,C)
        dispatch = dispatch.sum(axis=2)                        # (B,S,E,C)
        combine = combine.sum(axis=2)

    if cfg.moe_dispatch == "index":
        # gather/scatter dispatch: identical capacity-drop rule, but tokens
        # move by indexing instead of one-hot matmuls — removes the
        # O(B·S·E·cap·M) dispatch/combine FLOPs (quadratic in S since
        # cap ∝ S) that dominate the einsum path at long sequence.
        # Under a mesh the index math runs inside a shard_map that is MANUAL
        # on the batch axes (gathers/scatters stay device-local — GSPMD's
        # gather partitioner would otherwise replicate them, measured 258 GB
        # of all-to-all) and AUTO on the model axis (expert TP still GSPMD).
        args = (xn, idx, gate_vals, keep, pos_ce.astype(jnp.int32))
        if actx.active() and actx._STATE["dp"]:
            mesh, dp = actx._STATE["mesh"], actx._STATE["dp"]
            dpt = (dp,) if isinstance(dp, str) else tuple(dp)
            from jax.sharding import PartitionSpec as _P
            b3 = _P(dpt, None, None)
            b2 = _P(dpt, None)
            y = jax.shard_map(
                lambda pw, xn_, idx_, gv_, kp_, pc_: _moe_index_path(
                    cfg, pw, xn_, idx_, gv_, kp_, pc_, cap),
                mesh=mesh,
                in_specs=(_P(), b3, b3, b3, b3, b2),
                out_specs=b3,
                axis_names=set(dpt),
                check_vma=False,
            )(p, *args)
        else:
            y = _moe_index_path(cfg, p, *args, cap)
        y = y.astype(x.dtype)
    else:
        xe = jnp.einsum("bsec,bsm->ebcm", dispatch.astype(x.dtype), xn)
        gme = jax.nn.silu(jnp.einsum("ebcm,emf->ebcf", xe, p["wg"].astype(x.dtype))
                          .astype(jnp.float32)).astype(x.dtype)
        hme = jnp.einsum("ebcm,emf->ebcf", xe, p["wi"].astype(x.dtype)) * gme
        ye = jnp.einsum("ebcf,efm->ebcm", hme, p["wo"].astype(x.dtype))
        y = jnp.einsum("bsec,ebcm->bsm", combine.astype(x.dtype), ye)

    # load-balance aux loss (Switch) + router z-loss
    me = probs.mean(axis=(0, 1))                               # (E,)
    ce = onehot.sum(axis=2).mean(axis=(0, 1))                  # fraction routed
    aux = e * jnp.sum(me * ce) + 1e-3 * jnp.mean(
        jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
    return x + y, aux


# ---------------------------------------------------------------------------
# Mamba2 block (zamba2 hybrid)
# ---------------------------------------------------------------------------


def init_mamba(cfg: ModelConfig, key) -> Tuple[Params, Params]:
    m, din, n, hm = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    k1, k2, k3, k4 = jax.random.split(key, 4)
    proj_out = 2 * din + 2 * n + hm  # [z, x, B, C, dt]
    p = {
        "in_proj": _dense_init(k1, (m, proj_out)),
        "conv": _dense_init(k2, (cfg.conv_width, din)) * 0.1,
        "A_log": jnp.zeros((hm,)) + math.log(0.5),
        "D": jnp.ones((hm,)),
        "dt_bias": jnp.zeros((hm,)),
        "out_proj": _dense_init(k3, (din, m)),
        "norm": jnp.zeros((m,)),
        "gate_norm": jnp.zeros((din,)),
    }
    s = {"in_proj": ("embed", "ffn"), "conv": (None, "ffn"),
         "A_log": (None,), "D": (None,), "dt_bias": (None,),
         "out_proj": ("ffn", "embed"), "norm": (None,), "gate_norm": (None,)}
    return p, s


def _causal_conv(x, w, state=None):
    """Depthwise causal conv.  x (B,L,C), w (W,C).  state (B,W-1,C) or None.
    Returns (y, new_state)."""
    b, l, c = x.shape
    wlen = w.shape[0]
    if state is None:
        state = jnp.zeros((b, wlen - 1, c), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)  # (B, L+W-1, C)
    y = sum(xp[:, i:i + l, :] * w[i][None, None, :] for i in range(wlen))
    new_state = xp[:, -(wlen - 1):, :] if wlen > 1 else state
    return y, new_state


def apply_mamba(cfg: ModelConfig, p: Params, x: jax.Array,
                cache: Optional[Params] = None):
    """Mamba2-style selective SSM block (scalar per-head decay, matrix state).
    Train: chunked scan kernel.  Decode: single-step recurrence on cached
    state.  Returns (y, new_cache)."""
    b, l, m = x.shape
    din, n, hm, pdim = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_headdim
    xn = rms_norm(x, p["norm"])
    proj = linear(cfg, p["in_proj"], xn)
    z, xs, bmat, cmat, dt = jnp.split(
        proj, [din, 2 * din, 2 * din + n, 2 * din + 2 * n], axis=-1)

    conv_state = cache["conv"] if cache is not None else None
    xs, new_conv = _causal_conv(xs, p["conv"].astype(xs.dtype), conv_state)
    xs = jax.nn.silu(xs.astype(jnp.float32)).astype(x.dtype)

    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])      # (B,L,Hm)
    a = jnp.exp(-jnp.exp(p["A_log"])[None, None, :] * dt)            # (B,L,Hm)
    xh = xs.reshape(b, l, hm, pdim)

    if cache is None or l > 1:
        # (B,L,Hm,P) -> (B*Hm, L, P); decay (B*Hm, L); b/c shared across heads
        # big scan operands stay in the compute dtype (bf16) — the chunked
        # SSD path accumulates in f32 via preferred_element_type, and the
        # decay math (log/cumsum) is always f32 inside the scan.  Halves the
        # scan's HBM traffic (§Perf zamba2 iteration 4).
        sdt = compute_dtype(cfg)
        xf = jnp.moveaxis(xh, 2, 1).reshape(b * hm, l, pdim).astype(sdt)
        af = jnp.moveaxis(a, 2, 1).reshape(b * hm, l)
        bf = jnp.repeat(bmat.astype(sdt), hm, axis=0).reshape(b * hm, l, n)
        cf = jnp.repeat(cmat.astype(sdt), hm, axis=0).reshape(b * hm, l, n)
        y = ops.ssm(xf, af, bf, cf, cfg.use_kernels)
        y = jnp.moveaxis(y.reshape(b, hm, l, pdim), 1, 2)            # (B,L,Hm,P)
        new_cache = None
        if cache is not None:  # prefill: also materialize the final state
            log_a = jnp.log(jnp.maximum(a, 1e-37))                   # (B,L,Hm)
            cum = jnp.cumsum(log_a, axis=1)
            w = jnp.exp(cum[:, -1:, :] - cum)                        # Π_{r>s} a_r
            s_fin = jnp.einsum("blh,blhp,bln->bhpn", w,
                               xh.astype(jnp.float32),
                               bmat.astype(jnp.float32))
            new_cache = {"state": s_fin, "conv": new_conv}
    else:
        s_prev = cache["state"]                                      # (B,Hm,P,N)
        a1 = a[:, 0]                                                 # (B,Hm)
        upd = jnp.einsum("bhp,bn->bhpn", xh[:, 0].astype(jnp.float32),
                         bmat[:, 0].astype(jnp.float32))
        s_new = a1[..., None, None] * s_prev + upd
        y = jnp.einsum("bhpn,bn->bhp", s_new, cmat[:, 0].astype(jnp.float32))
        y = y[:, None]                                               # (B,1,Hm,P)
        new_cache = {"state": s_new, "conv": new_conv}

    y = y + (p["D"][None, None, :, None] * xh.astype(jnp.float32))
    y = y.reshape(b, l, din).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype),
                 p["gate_norm"])
    return x + linear(cfg, p["out_proj"], y), new_cache


# ---------------------------------------------------------------------------
# xLSTM blocks
# ---------------------------------------------------------------------------


def init_mlstm(cfg: ModelConfig, key) -> Tuple[Params, Params]:
    m, dh, h = cfg.d_model, cfg.head_dim_, cfg.n_heads
    din = h * dh
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "wqkv": _dense_init(k1, (m, 3 * din)),
        "wif": _dense_init(k2, (m, 2 * h)) * 0.1,
        "wo": _dense_init(k3, (din, m)),
        "norm": jnp.zeros((m,)),
    }
    s = {"wqkv": ("embed", "ffn"), "wif": ("embed", None),
         "wo": ("ffn", "embed"), "norm": (None,)}
    return p, s


def apply_mlstm(cfg: ModelConfig, p: Params, x: jax.Array,
                cache: Optional[Params] = None):
    """mLSTM: matrix-memory LSTM.  C_t = f_t C + i_t v k^T ; h = C q / max(|n.q|,1).
    Maps onto the chunked SSM kernel (state = C, plus a 1-row state for n)."""
    b, l, m = x.shape
    h, dh = cfg.n_heads, cfg.head_dim_
    din = h * dh
    xn = rms_norm(x, p["norm"])
    qkv = linear(cfg, p["wqkv"], xn)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    gates = linear(cfg, p["wif"], xn).astype(jnp.float32)
    ig, fg = jnp.split(gates, 2, axis=-1)                            # (B,L,H)
    i = jax.nn.sigmoid(ig)
    f = jax.nn.sigmoid(fg + 3.0)  # bias toward remembering

    qh = q.reshape(b, l, h, dh) * dh ** -0.5
    kh = k.reshape(b, l, h, dh) * dh ** -0.5
    vh = v.reshape(b, l, h, dh)

    def flat(t):  # (B,L,H,D) -> (B*H, L, D) — compute dtype; the chunked
        # scan accumulates in f32 (§Perf zamba2 iteration 4 applies here too)
        return jnp.moveaxis(t, 2, 1).reshape(b * h, l, -1).astype(compute_dtype(cfg))

    xf = flat(vh * i[..., None].astype(vh.dtype))
    af = jnp.moveaxis(f, 2, 1).reshape(b * h, l)
    bf, cf = flat(kh), flat(qh)

    if cache is None or l > 1:
        y = ops.ssm(xf, af, bf, cf, cfg.use_kernels)                 # (BH,L,D)
        iflat = jnp.moveaxis(i, 2, 1).reshape(b * h, l)
        ones = jnp.ones((b * h, l, 1), jnp.float32) * iflat[..., None]
        nsum = ops.ssm(ones, af, bf, cf, cfg.use_kernels)            # (BH,L,1)
        new_cache = None
        if cache is not None:  # prefill: final (C, n) state
            log_a = jnp.log(jnp.maximum(af, 1e-37))                  # (BH,L)
            cum = jnp.cumsum(log_a, axis=1)
            w = jnp.exp(cum[:, -1:] - cum)                           # (BH,L)
            C_fin = jnp.einsum("zl,zlp,zln->zpn", w, xf, bf,
                               preferred_element_type=jnp.float32)
            n_fin = jnp.einsum("zl,zl,zln->zn", w, iflat, bf,
                               preferred_element_type=jnp.float32)[:, None]
            new_cache = {"C": C_fin, "n": n_fin}
    else:
        C_prev, n_prev = cache["C"], cache["n"]                      # (BH,D,N),(BH,1,N)
        a1 = af[:, 0][:, None, None]
        C_new = a1 * C_prev + jnp.einsum("zp,zn->zpn", xf[:, 0], bf[:, 0])
        n_new = a1 * n_prev + jnp.einsum("z,zn->zn", jnp.moveaxis(i, 2, 1)
                                         .reshape(b * h, l)[:, 0], bf[:, 0])[:, None]
        y = jnp.einsum("zpn,zn->zp", C_new, cf[:, 0])[:, None]
        nsum = jnp.einsum("zqn,zn->zq", n_new, cf[:, 0])[:, None]
        new_cache = {"C": C_new, "n": n_new}

    hout = y / jnp.maximum(jnp.abs(nsum), 1.0)
    hout = jnp.moveaxis(hout.reshape(b, h, l, dh), 1, 2).reshape(b, l, din)
    return x + linear(cfg, p["wo"], hout.astype(x.dtype)), new_cache


def init_slstm(cfg: ModelConfig, key) -> Tuple[Params, Params]:
    m = cfg.d_model
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "wx": _dense_init(k1, (m, 4 * m)),
        "wr": _dense_init(k2, (m, 4 * m)) * 0.5,
        "bias": jnp.zeros((4 * m,)),
        "wo": _dense_init(k3, (m, m)),
        "norm": jnp.zeros((m,)),
    }
    s = {"wx": ("embed", "ffn"), "wr": ("embed", "ffn"), "bias": (None,),
         "wo": ("embed", "embed"), "norm": (None,)}
    return p, s


def apply_slstm(cfg: ModelConfig, p: Params, x: jax.Array,
                cache: Optional[Params] = None):
    """sLSTM with stabilized exponential gating (sequential scan — the
    inherently-recurrent xLSTM component)."""
    b, l, m = x.shape
    xn = rms_norm(x, p["norm"])
    xproj = (linear(cfg, p["wx"], xn) + p["bias"].astype(xn.dtype)).astype(jnp.float32)

    if cache is None:
        h0 = jnp.zeros((b, m), jnp.float32)
        state0 = (h0, h0, h0, h0 - 10.0)  # h, c, n, mstab
    else:
        state0 = (cache["h"], cache["c"], cache["n"], cache["m"])

    wr = p["wr"].astype(jnp.float32)

    def step(state, xt):
        hprev, cprev, nprev, mprev = state
        pre = xt + hprev @ wr
        zt, it, ft, ot = jnp.split(pre, 4, axis=-1)
        z = jnp.tanh(zt)
        o = jax.nn.sigmoid(ot)
        mnew = jnp.maximum(ft + mprev, it)
        i = jnp.exp(it - mnew)
        f = jnp.exp(ft + mprev - mnew)
        c = f * cprev + i * z
        n = f * nprev + i
        hnew = o * c / jnp.maximum(n, 1.0)
        return (hnew, c, n, mnew), hnew

    statef, hs = jax.lax.scan(step, state0, jnp.moveaxis(xproj, 1, 0))
    hs = jnp.moveaxis(hs, 0, 1).astype(x.dtype)                      # (B,L,M)
    new_cache = None
    if cache is not None:
        new_cache = {"h": statef[0], "c": statef[1], "n": statef[2], "m": statef[3]}
    return x + linear(cfg, p["wo"], hs), new_cache
