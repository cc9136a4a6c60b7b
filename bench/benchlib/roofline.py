"""Peaks of the chips the benchmark runs on, and the operations and bytes
that the measured work needs, counted from shapes (never from the program).

A roofline share is the least time the chip could take for the work, the
larger of operations over peak FLOP/s and bytes over peak bytes/s, over the
time the work took.  Operations are those the algorithm needs: a causal
attention counts the query-key pairs at or below the diagonal, and
recomputation in the backward pass is not counted.
"""

from __future__ import annotations

from typing import Dict, Sequence, Tuple

from benchlib import traces

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM.
# Keyed by jax's `device_kind`; a chip not listed is an error, not a default.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}

DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
               "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
               "f64": 8}

Shape = Tuple[str, Tuple[int, ...]]


def peak(kind: str) -> Dict[str, float]:
    if kind not in PEAKS:
        raise KeyError(f"no peak known for device kind {kind!r}; "
                       f"bench/benchlib/roofline.py knows {sorted(PEAKS)}")
    return PEAKS[kind]


def nbytes(shape: Shape) -> int:
    dtype, dims = shape
    n = DTYPE_BYTES[dtype]
    for d in dims:
        n *= d
    return n


def lm_sizes(config: dict) -> dict:
    """The sizes of a dense GQA decoder, from a configuration file's keys
    (those of the model's published config.json)."""
    d = config["hidden_size"]
    h = config["num_attention_heads"]
    return {"layers": config["num_hidden_layers"], "d": d, "heads": h,
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config.get("head_dim") or d // h,
            "ffn": config["intermediate_size"], "vocab": config["vocab_size"]}


def lm_matmul_params(config: dict) -> Tuple[int, int]:
    """(matmul parameters of one layer, of the output head)."""
    s = lm_sizes(config)
    d, dh = s["d"], s["head_dim"]
    attn = d * dh * (s["heads"] + 2 * s["kv_heads"]) + s["heads"] * dh * d
    mlp = 3 * d * s["ffn"]
    return attn + mlp, d * s["vocab"]


def lm_train_flops_per_token(config: dict, seq_len: int,
                             causal: bool = True) -> float:
    """Model FLOPs of one training token, forward and backward: 6 x (layer
    matmul parameters + output head), plus attention's two matmuls at 6 x
    heads x head_dim x keys attended per layer (seq / 2 on average for a
    causal mask, seq without one)."""
    s = lm_sizes(config)
    layer, head = lm_matmul_params(config)
    keys = (seq_len + 1) / 2 if causal else seq_len
    attn = 6 * s["layers"] * s["heads"] * s["head_dim"] * keys * 2
    return 6.0 * (s["layers"] * layer + head) + attn


def photonic_mac_cost(shapes: Sequence[Shape]) -> Tuple[float, float]:
    """(operations, bytes) of one photonic_mac call, from its instruction's
    shapes: result f32[M,N], then x [M,K], w_q s8[K,N], per-tile scales."""
    (_, (m, n)), x, w, *rest = shapes
    k = x[1][1]
    return 2.0 * m * n * k, float(nbytes(shapes[0]) + nbytes(x) + nbytes(w)
                                  + sum(nbytes(r) for r in rest))


def flash_attention_cost(shapes: Sequence[Shape]) -> Tuple[float, float]:
    """(operations, bytes) of one flash-attention forward call: result
    f32[B*Hq, Sq, D], then q [B*Hq, Sq, D], k and v [B*Hk, Sk, D].  Two
    matmuls of 2 x D operations per query-key pair; a self-attention call
    (Sq == Sk) is the decoder's causal one and keeps Sq (Sq + 1) / 2 pairs
    of each head."""
    out, q, k, v = shapes[:4]
    bh, sq, d = out[1]
    sk = k[1][1]
    pairs = sq * (sq + 1) / 2 if sq == sk else sq * sk
    return 4.0 * bh * pairs * d, float(sum(nbytes(s) for s in (out, q, k, v)))


def roofline_share(win, pattern: str, cost, kind: str) -> float:
    """Share (%) of the roofline of the instructions whose name matches,
    summed over the traced window (first chip); None where none ran."""
    p = peak(kind)
    evs = win.matching("ops", pattern, win.chips[0])
    took = sum(e.dur for e in evs) * 1e-9
    if not evs or took <= 0:
        return None
    least = 0.0
    for e in evs:
        flops, nb = cost(traces.op_shapes(e.name))
        least += max(flops / p["bf16_flops"], nb / p["hbm_bytes_per_s"])
    return 100.0 * least / took
