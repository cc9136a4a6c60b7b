"""Plain float32 reference for training a dense GQA decoder of the Yi family
(Yi-6B: arXiv:2403.04652, https://huggingface.co/01-ai/Yi-6B): forward,
loss, gradient and AdamW, in straightforward jax.numpy at the highest
matmul precision.  It imports nothing of the program and uses no kernel,
cache or batching.  It also makes the weights that both sides start from.

The model, as the published config states it: token embedding; per layer
x + Attn(RMSNorm(x)) then x + MLP(RMSNorm(x)); a final RMSNorm and an
untied output head.  Attention has `num_attention_heads` query heads over
`num_key_value_heads` key/value heads of hidden/heads each, rotary
positions (rotate-half, base `rope_theta`), a causal mask and the
1/sqrt(head_dim) scale; no biases.  The MLP is SwiGLU:
down(silu(gate(x)) * up(x)).  The loss is the mean next-token cross
entropy over every position of the batch.

Departures from Yi-6B, each as the configuration file states it:
- every matmul weight goes through the photonic MAC's weight quantization
  (`photonic_bits`, symmetric, rounded to the nearest level): one scale per
  128 x 128 tile of the (in, out) matrix where both sides tile by 128, one
  per output column otherwise (the 8,000-row head slice).  Its gradient is
  straight-through: the backward pass uses the unquantized weight for both
  the input's and the weight's gradient;
- RMSNorm multiplies by (1 + scale) with the scale stored from 0, the same
  function as Yi's weight stored from 1, so that AdamW's weight decay pulls
  it toward the identity as the trained system's does;
- `rms_norm_eps` is the configuration's 1e-6 (Yi-6B: 1e-5);
- depth and vocabulary are the configuration's cut (a slice of the
  vocabulary is a smaller vocabulary: ids, logits and loss are over it).

Memory: each layer is rematerialized in the backward pass, and attention
runs one key/value head's group of query heads at a time, so that the
f32 scores of one group (heads/kv_heads x seq x seq) are the largest
temporary.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

TILE = 128
LAYER_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo",
                "mlp_norm", "wg", "wi", "wd")


def sizes(config: dict) -> dict:
    d = config["hidden_size"]
    h = config["num_attention_heads"]
    return {"L": config["num_hidden_layers"], "d": d, "h": h,
            "hk": config["num_key_value_heads"],
            "dh": config.get("head_dim") or d // h,
            "f": config["intermediate_size"], "V": config["vocab_size"],
            "eps": config["rms_norm_eps"], "theta": config["rope_theta"],
            "bits": config["photonic_bits"]}


def key_of(seed: int):
    """A key from any whole seed, more than 32 bits included."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**32), seed // 2**32)


def leaf_shapes(config: dict) -> dict:
    s = sizes(config)
    L, d, h, hk, dh, f, V = (s[k] for k in ("L", "d", "h", "hk", "dh", "f", "V"))
    return {"embed": (V, d), "lm_head": (d, V), "final_norm": (d,),
            "attn_norm": (L, d), "wq": (L, d, h, dh), "wk": (L, d, hk, dh),
            "wv": (L, d, hk, dh), "wo": (L, h, dh, d), "mlp_norm": (L, d),
            "wg": (L, d, f), "wi": (L, d, f), "wd": (L, f, d)}


def _fan_in(name: str, shape) -> int:
    if name in ("embed", "lm_head"):
        return shape[-1] if name == "embed" else shape[0]
    if name == "wo":
        return shape[1] * shape[2]
    return shape[1]


@functools.partial(jax.jit, static_argnums=(0,))
def _init(shapes_items, key):
    out = {}
    for i, (name, shape) in enumerate(shapes_items):
        if name.endswith("norm"):
            out[name] = jnp.zeros(shape, jnp.float32)
        else:
            out[name] = (jax.random.normal(jax.random.fold_in(key, i), shape,
                                           jnp.float32)
                         / math.sqrt(_fan_in(name, shape)))
    return out


def init_params(config: dict, seed: int) -> dict:
    """The float32 weights both sides start from, in one jitted call on the
    default device: N(0, 1/fan_in) matrices (the embedding and the head
    1/hidden), norm scales 0."""
    return _init(tuple(sorted(leaf_shapes(config).items())), key_of(seed))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def quantize(w2, bits: int):
    """The photonic weight levels of an (in, out) matrix, dequantized."""
    k, n = w2.shape
    qmax = 2 ** (bits - 1) - 1
    if k % TILE == 0 and n % TILE == 0:
        t = w2.reshape(k // TILE, TILE, n // TILE, TILE)
        scale = jnp.maximum(jnp.max(jnp.abs(t), axis=(1, 3)), 1e-8) / qmax
        q = jnp.clip(jnp.round(t / scale[:, None, :, None]), -qmax, qmax)
        return (q * scale[:, None, :, None]).reshape(k, n)
    scale = jnp.maximum(jnp.max(jnp.abs(w2), axis=0), 1e-8) / qmax
    return jnp.clip(jnp.round(w2 / scale), -qmax, qmax) * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def qmatmul(x, w2, bits):
    return x @ quantize(w2, bits)


def _qmatmul_fwd(x, w2, bits):
    return qmatmul(x, w2, bits), (x, w2)


def _qmatmul_bwd(bits, res, g):
    x, w2 = res
    return g @ w2.T, x.T @ g


qmatmul.defvjp(_qmatmul_fwd, _qmatmul_bwd)


def linear(x, w, bits: int):
    """x (..., in) times a weight read as an (in, out) matrix: wq (d, h, dh)
    as (d, h * dh), the attention output's (h, dh, d) as (h * dh, d)."""
    k = x.shape[-1]
    y = qmatmul(x.reshape(-1, k), w.reshape(k, -1), bits)
    return y.reshape(*x.shape[:-1], -1)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + scale)


def rope(x, theta):
    """x (B, S, H, dh): rotate-half rotary embedding at positions 0..S-1."""
    s, dh = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, blocked: bool = True):
    """q (B, S, H, dh); k, v (B, S, Hk, dh): causal softmax attention.
    Blocked: one key/value head (and its group of query heads) at a time,
    recomputed in the backward pass; else all heads at once."""
    b, s, h, dh = q.shape
    hk = k.shape[2]
    qg = jnp.moveaxis(q.reshape(b, s, hk, h // hk, dh), 2, 0)
    kg, vg = jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0)
    mask = jnp.tril(jnp.ones((s, s), bool))

    def group(args):
        qi, ki, vi = args                       # (B,S,G,dh), (B,S,dh) x 2
        sc = jnp.einsum("bqgd,bkd->bgqk", qi, ki) / math.sqrt(dh)
        p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
        return jnp.einsum("bgqk,bkd->bqgd", p, vi)

    if blocked:
        out = jax.lax.map(jax.checkpoint(group), (qg, kg, vg))
    else:
        out = jax.vmap(group)((qg, kg, vg))     # (Hk, B, S, G, dh)
    return jnp.moveaxis(out, 0, 2).reshape(b, s, h * dh)


def layer(p, x, s, blocked: bool = True):
    b, n, d = x.shape
    bits = s["bits"]
    xn = rms_norm(x, p["attn_norm"], s["eps"])
    q = linear(xn, p["wq"], bits).reshape(b, n, s["h"], s["dh"])
    k = linear(xn, p["wk"], bits).reshape(b, n, s["hk"], s["dh"])
    v = linear(xn, p["wv"], bits).reshape(b, n, s["hk"], s["dh"])
    a = attention(rope(q, s["theta"]), rope(k, s["theta"]), v, blocked)
    x = x + linear(a, p["wo"], bits)
    xn = rms_norm(x, p["mlp_norm"], s["eps"])
    h = jax.nn.silu(linear(xn, p["wg"], bits)) * linear(xn, p["wi"], bits)
    return x + linear(h, p["wd"], bits)


def loss(params, tokens, labels, config, blocked: bool = True):
    """Mean next-token cross entropy of a (B, S) batch.  Blocked: layer by
    layer, each recomputed in the backward pass (as in `train`); else
    plainly, nothing recomputed (for counting operations)."""
    s = sizes(config)
    x = params["embed"][tokens]
    per_layer = {k: params[k] for k in LAYER_LEAVES}
    if blocked:
        def body(x, p):
            return jax.checkpoint(lambda p, x: layer(p, x, s))(p, x), None

        x, _ = jax.lax.scan(body, x, per_layer)
    else:
        for i in range(s["L"]):
            x = layer({k: v[i] for k, v in per_layer.items()}, x, s, False)
    x = rms_norm(x, params["final_norm"], s["eps"])
    logits = linear(x, params["lm_head"], s["bits"])
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


# ---------------------------------------------------------------------------
# AdamW
# ---------------------------------------------------------------------------


def learning_rate(opt: dict, step: int) -> float:
    """Linear warm-up to `lr` over `warmup_steps`, then a cosine to a tenth
    of it at `total_steps`; `step` counts from 1."""
    warm = min(step / max(opt["warmup_steps"], 1), 1.0)
    t = min(max((step - opt["warmup_steps"])
                / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0), 1.0)
    return opt["lr"] * warm * (0.1 + 0.9 * 0.5 * (1 + math.cos(math.pi * t)))


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def _adamw(params, grads, m, v, hyper):
    lr, b1, b2, eps, wd, clip, bc1, bc2 = hyper
    gn = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, clip / jnp.maximum(gn, 1e-9))
    g = jax.tree.map(lambda g: g * scale, grads)
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
    params = jax.tree.map(
        lambda p, m, v: p - lr * ((m / bc1) / (jnp.sqrt(v / bc2) + eps)
                                  + wd * p), params, m, v)
    return params, m, v, g


def train(config: dict, opt: dict, seed: int, batches):
    """AdamW steps from `init_params(config, seed)` on `batches`, a list of
    (tokens, labels) pairs.  Returns the losses, the first step's clipped
    gradient (the gradient the optimizer takes), copied to the host so that
    the device holds one set of weights, gradients and moments at a time,
    and the weights after the last step."""
    grad_fn = jax.jit(jax.value_and_grad(loss), static_argnums=(3,))
    frozen = _Frozen(config)
    with jax.default_matmul_precision("highest"):
        params = init_params(config, seed)
        m = jax.tree.map(jnp.zeros_like, params)
        v = jax.tree.map(jnp.zeros_like, params)
        losses, first = [], None
        for t, (tokens, labels) in enumerate(batches, start=1):
            value, grads = grad_fn(params, jnp.asarray(tokens),
                                   jnp.asarray(labels), frozen)
            hyper = (learning_rate(opt, t), opt["b1"], opt["b2"], opt["eps"],
                     opt["weight_decay"], opt["clip_norm"],
                     1 - opt["b1"] ** t, 1 - opt["b2"] ** t)
            params, m, v, g = _adamw(params, grads, m, v,
                                     tuple(np.float32(h) for h in hyper))
            losses.append(float(value))
            if first is None:
                first = jax.device_get(g)
            del grads, g
    return losses, first, params


class _Frozen(dict):
    """A configuration as a static, hashable jit argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, repr(v)) for k, v in self.items())))
