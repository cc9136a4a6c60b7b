"""Per-kernel correctness: Pallas (interpret mode on CPU) vs pure-jnp oracles,
swept over shapes and dtypes, plus hypothesis property tests on the
quantization (MR weight-bank) numerics."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.photonic_mac import photonic_mac, quantize_weights
from repro.kernels.ssm_scan import ssm_scan
from repro.kernels import ops


# ---------------------------------------------------------------------------
# photonic MAC
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (256, 384, 128),
                                   (128, 256, 512), (384, 128, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("bits", [8, 4])
def test_photonic_mac_matches_oracle(m, k, n, dtype, bits):
    kx, kw = jax.random.split(jax.random.PRNGKey(m + k + n + bits))
    x = jax.random.normal(kx, (m, k), dtype)
    w = jax.random.normal(kw, (k, n), jnp.float32)
    wq, sc = quantize_weights(w, bits=bits)
    out_k = photonic_mac(x, wq, sc, interpret=True)
    out_r = ref.photonic_mac_ref(x, wq, sc)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=tol, atol=tol * 10)


def test_photonic_mac_block_shapes():
    x = jax.random.normal(jax.random.PRNGKey(0), (256, 256), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (256, 256), jnp.float32)
    wq, sc = quantize_weights(w, bits=8, bk=128, bn=128)
    base = ref.photonic_mac_ref(x, wq, sc)
    for bm in (128, 256):
        out = photonic_mac(x, wq, sc, bm=bm, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                                   rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("m,k,n", [(100, 128, 128),    # ragged M only
                                   (128, 200, 300),    # ragged K and N
                                   (1, 128, 50257 % 512),  # vocab-tail-ish
                                   (130, 129, 131)])   # every dim ragged
def test_photonic_mac_non_aligned_shapes(m, k, n):
    """Non-MXU-aligned shapes (vocab tails, odd hidden dims) run via the
    kernel's zero-pad + slice and match the oracle on the valid window."""
    kx, kw = jax.random.split(jax.random.PRNGKey(m * 7 + k * 3 + n))
    x = jax.random.normal(kx, (m, k), jnp.float32)
    w = jax.random.normal(kw, (k, n), jnp.float32)
    wq, sc = quantize_weights(w, bits=8)
    assert wq.shape == (k, n)
    assert sc.shape == (-(-k // 128), -(-n // 128))
    out_k = photonic_mac(x, wq, sc, interpret=True)
    out_r = ref.photonic_mac_ref(x, wq, sc)
    assert out_k.shape == (m, n)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-4, atol=1e-3)


def test_photonic_mac_padding_is_exact_on_aligned_shapes():
    """The pad+slice path must be a no-op for aligned shapes: quantizing a
    weight matrix embedded in a larger zero-padded one yields identical
    levels and scales, and the kernel output is bit-identical."""
    w = jax.random.normal(jax.random.PRNGKey(3), (256, 256), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(4), (128, 256), jnp.float32)
    wq_a, sc_a = quantize_weights(w, bits=8)
    wq_b, sc_b = quantize_weights(w[:200, :250], bits=8)
    # zero padding never widens a bank's absmax: shared tiles agree exactly
    np.testing.assert_array_equal(np.asarray(sc_b[:1, :1]),
                                  np.asarray(sc_a[:1, :1]))
    np.testing.assert_array_equal(np.asarray(wq_b[:128, :128]),
                                  np.asarray(wq_a[:128, :128]))
    out_a = photonic_mac(x, wq_a, sc_a, interpret=True)
    out_b = photonic_mac(x[:100], wq_a, sc_a, interpret=True)
    np.testing.assert_array_equal(np.asarray(out_b), np.asarray(out_a)[:100])


@settings(max_examples=25, deadline=None)
@given(bits=st.integers(min_value=2, max_value=8),
       seed=st.integers(min_value=0, max_value=2**31 - 1))
def test_quantization_error_bound(bits, seed):
    """Per-tile symmetric quantization error is bounded by scale/2 — the MR
    amplitude-resolution guarantee the accelerator model assumes."""
    w = jax.random.normal(jax.random.PRNGKey(seed), (128, 128), jnp.float32)
    wq, sc = quantize_weights(w, bits=bits)
    deq = ref.dequantize_ref(wq, sc)
    err = jnp.max(jnp.abs(deq - w))
    assert float(err) <= float(jnp.max(sc)) / 2 + 1e-6


def test_photonic_matmul_ste_gradients():
    """Straight-through estimator: gradient wrt w equals the unquantized
    matmul gradient."""
    x = jax.random.normal(jax.random.PRNGKey(0), (128, 128), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (128, 128), jnp.float32)
    g = jax.grad(lambda w_: jnp.sum(ops.photonic_matmul(x, w_, 8, False)))(w)
    g_ref = jax.grad(lambda w_: jnp.sum(x @ w_))(w)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), rtol=1e-5)


def test_wire_quant_leaf_numerics_and_ste():
    """int8 wire leaf: dequantized weights within one quant step of the
    master (per-tensor scale for 2-D, per-layer for stacked), gradients
    straight-through (QAT identity)."""
    from repro.parallel.wire import _quant_leaf
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 32), jnp.float32)
    wd = _quant_leaf(w, 8, None, jnp.float32)
    step = jnp.max(jnp.abs(w)) / 127.0
    assert float(jnp.max(jnp.abs(wd - w))) <= float(step) / 2 + 1e-6
    g = jax.grad(lambda w_: jnp.sum(_quant_leaf(w_, 8, None, jnp.float32) ** 2))(w)
    np.testing.assert_allclose(np.asarray(g), np.asarray(2 * wd), rtol=1e-5)
    # stacked (layers, K, N): scale per layer
    ws = jnp.stack([w, 100.0 * w])
    wds = _quant_leaf(ws, 8, None, jnp.float32)
    np.testing.assert_allclose(np.asarray(wds[1] / 100.0), np.asarray(wds[0]),
                               rtol=1e-5, atol=1e-6)


def test_wire_grads_close_to_master():
    """End-to-end: wire-transformed loss gradients stay close to the f32
    master gradients (bf16 tight, int8 within QAT tolerance)."""
    import dataclasses as _dc
    from repro import configs as C
    from repro.models import model as M
    from repro.parallel import wire as W
    from repro.parallel import sharding as S
    CFG = C.get_reduced("yi_6b")
    params, specs = M.init(CFG, jax.random.PRNGKey(0))
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    rules = S.rules_for(CFG, mesh)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, CFG.vocab),
             "labels": jax.random.randint(jax.random.PRNGKey(2), (2, 64), 0, CFG.vocab)}
    g0 = jax.grad(lambda p: M.loss_fn(CFG, p, batch)[0])(params)
    for bits, tol in ((16, 0.05), (8, 0.25)):
        pw = W.make_param_wire(_dc.replace(CFG, wire_bits=bits), mesh, rules, specs)
        qtree = pw.quantize(params)
        g = jax.grad(lambda v: M.loss_fn(CFG, pw.graft(qtree, v), batch)[0])(
            pw.carrier(params))
        for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g)):
            na = float(jnp.linalg.norm(a.astype(jnp.float32)))
            nd = float(jnp.linalg.norm((a - b).astype(jnp.float32)))
            assert nd <= tol * na + 1e-6, (bits, nd, na)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sq,sk,hq,hk,d", [
    (128, 128, 4, 4, 64),      # MHA
    (256, 256, 8, 2, 64),      # GQA 4:1
    (128, 256, 8, 1, 128),     # MQA, longer KV
    (512, 512, 2, 2, 32),      # long, small heads
    (128, 384, 16, 8, 64),     # GQA 2:1, 3x KV
])
@pytest.mark.parametrize("window", [0, 64])
def test_flash_attention_matches_oracle(sq, sk, hq, hk, d, window):
    ks = jax.random.split(jax.random.PRNGKey(sq + sk + hq + window), 3)
    q = jax.random.normal(ks[0], (2, hq, sq, d), jnp.float32)
    k = jax.random.normal(ks[1], (2, hk, sk, d), jnp.float32)
    v = jax.random.normal(ks[2], (2, hk, sk, d), jnp.float32)
    off = sk - sq
    out = flash_attention(q, k, v, causal=True, window=window,
                          q_offset=off, interpret=True)
    exp = ref.attention_ref(q, k, v, causal=True, window=window, q_offset=off)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_bf16():
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (1, 4, 128, 64), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 4, 128, 64), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 4, 128, 64), jnp.bfloat16)
    out = flash_attention(q, k, v, interpret=True)
    exp = ref.attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-2, atol=2e-2)


def test_flash_attention_noncausal():
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (1, 2, 128, 32), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, 128, 32), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, 128, 32), jnp.float32)
    out = flash_attention(q, k, v, causal=False, interpret=True)
    exp = ref.attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("s,window", [(200, 0), (1023, 0), (300, 64)])
def test_attention_ragged_causal_length_runs_kernel(s, window, monkeypatch):
    """A causal self-attention of a length that is not a multiple of the
    block (exact-length prefill) runs the kernel on a padded copy and
    matches the oracle on the real rows."""
    calls = []
    real = ops._flash_fwd
    monkeypatch.setattr(ops, "_flash_fwd",
                        lambda *a, **kw: calls.append(1) or real(*a, **kw))
    ks = jax.random.split(jax.random.PRNGKey(s + window), 3)
    q = jax.random.normal(ks[0], (1, 4, s, 32), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, s, 32), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, s, 32), jnp.float32)
    out = ops.attention(q, k, v, True, window, None, 0, True)
    exp = ref.attention_ref(q, k, v, causal=True, window=window)
    assert calls and out.shape == exp.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# ssm scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bh,l,p,n", [(2, 128, 16, 8), (4, 256, 32, 16),
                                      (1, 512, 64, 64), (8, 128, 8, 4),
                                      (2, 1024, 32, 32)])
def test_ssm_scan_matches_oracle(bh, l, p, n):
    ks = jax.random.split(jax.random.PRNGKey(bh * l), 4)
    x = jax.random.normal(ks[0], (bh, l, p)) * 0.5
    a = jax.nn.sigmoid(jax.random.normal(ks[1], (bh, l))) * 0.3 + 0.68
    b = jax.random.normal(ks[2], (bh, l, n)) * 0.3
    c = jax.random.normal(ks[3], (bh, l, n)) * 0.3
    out = ssm_scan(x, a, b, c, interpret=True)
    exp = ref.ssm_scan_ref(x, a, b, c)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("l", [200, 1023])
def test_ssm_ragged_length_runs_kernel(l, monkeypatch):
    """A scan length that is not a multiple of the chunk runs the kernel on
    a zero-input, unit-decay padded tail and matches the oracle."""
    calls = []
    real = ops._ssm_fwd
    monkeypatch.setattr(ops, "_ssm_fwd",
                        lambda *a, **kw: calls.append(1) or real(*a, **kw))
    ks = jax.random.split(jax.random.PRNGKey(l), 4)
    x = jax.random.normal(ks[0], (2, l, 16)) * 0.5
    a = jax.nn.sigmoid(jax.random.normal(ks[1], (2, l))) * 0.3 + 0.68
    b = jax.random.normal(ks[2], (2, l, 8)) * 0.3
    c = jax.random.normal(ks[3], (2, l, 8)) * 0.3
    out = ops.ssm(x, a, b, c, True)
    exp = ref.ssm_scan_ref(x, a, b, c)
    assert calls and out.shape == exp.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-4, atol=2e-4)


def test_ssm_scan_bf16_inputs():
    """The kernel accepts the model's bf16 operands (f32 VMEM accumulation);
    must track the f32 sequential oracle within bf16 tolerance."""
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    x = (jax.random.normal(ks[0], (2, 256, 16)) * 0.5).astype(jnp.bfloat16)
    a = jax.nn.sigmoid(jax.random.normal(ks[1], (2, 256))) * 0.3 + 0.68
    b = (jax.random.normal(ks[2], (2, 256, 8)) * 0.3).astype(jnp.bfloat16)
    c = (jax.random.normal(ks[3], (2, 256, 8)) * 0.3).astype(jnp.bfloat16)
    out = ssm_scan(x, a, b, c, interpret=True)
    exp = ref.ssm_scan_ref(x.astype(jnp.float32), a,
                           b.astype(jnp.float32), c.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=5e-2, atol=5e-2)


def test_ssm_scan_chunk_invariance():
    """Chunk size must not change the result (associativity of the scan)."""
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(ks[0], (2, 256, 16)) * 0.5
    a = jax.nn.sigmoid(jax.random.normal(ks[1], (2, 256))) * 0.3 + 0.68
    b = jax.random.normal(ks[2], (2, 256, 8)) * 0.3
    c = jax.random.normal(ks[3], (2, 256, 8)) * 0.3
    o1 = ssm_scan(x, a, b, c, chunk=64, interpret=True)
    o2 = ssm_scan(x, a, b, c, chunk=256, interpret=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("bh,l,p,n,chunk", [
    (2, 128, 16, 8, 128), (4, 256, 32, 16, 128), (1, 512, 64, 64, 128),
    (2, 256, 16, 8, 64), (3, 96, 8, 4, 128),       # non-tileable -> sequential
])
def test_ssm_chunked_ref_matches_sequential(bh, l, p, n, chunk):
    """The chunked SSD reference (the XLA fallback + dry-run path) must equal
    the sequential oracle for any chunking."""
    ks = jax.random.split(jax.random.PRNGKey(bh * l + p), 4)
    x = jax.random.normal(ks[0], (bh, l, p)) * 0.5
    a = jax.nn.sigmoid(jax.random.normal(ks[1], (bh, l))) * 0.3 + 0.68
    b = jax.random.normal(ks[2], (bh, l, n)) * 0.3
    c = jax.random.normal(ks[3], (bh, l, n)) * 0.3
    out = ref.ssm_scan_chunked_ref(x, a, b, c, chunk=chunk)
    exp = ref.ssm_scan_ref(x, a, b, c)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-4, atol=2e-4)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(min_value=0, max_value=2**31 - 1),
       l=st.sampled_from([128, 256, 384]),
       decay_lo=st.floats(min_value=0.05, max_value=0.95))
def test_ssm_chunked_ref_property(seed, l, decay_lo):
    """Property sweep: chunked == sequential across decay ranges (incl. strong
    decay, where the log-space segsum must not under/overflow)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (2, l, 8))
    a = jax.nn.sigmoid(jax.random.normal(ks[1], (2, l))) * (0.999 - decay_lo) + decay_lo
    b = jax.random.normal(ks[2], (2, l, 4)) * 0.3
    c = jax.random.normal(ks[3], (2, l, 4)) * 0.3
    out = ref.ssm_scan_chunked_ref(x, a, b, c)
    exp = ref.ssm_scan_ref(x, a, b, c)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=5e-4, atol=5e-4)


def test_ssm_chunked_ref_grads_match_sequential():
    """ops.ssm backward runs the chunked VJP — it must match the sequential
    oracle's gradients."""
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    x = jax.random.normal(ks[0], (2, 128, 8)) * 0.5
    a = jax.nn.sigmoid(jax.random.normal(ks[1], (2, 128))) * 0.3 + 0.68
    b = jax.random.normal(ks[2], (2, 128, 4)) * 0.3
    c = jax.random.normal(ks[3], (2, 128, 4)) * 0.3
    g1 = jax.grad(lambda *t: jnp.sum(ref.ssm_scan_chunked_ref(*t)), (0, 1, 2, 3))(x, a, b, c)
    g2 = jax.grad(lambda *t: jnp.sum(ref.ssm_scan_ref(*t)), (0, 1, 2, 3))(x, a, b, c)
    for u, v in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(u), np.asarray(v),
                                   rtol=5e-4, atol=5e-4)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(min_value=0, max_value=2**31 - 1))
def test_ssm_decay_contraction(seed):
    """|a| < 1 everywhere => output magnitude is bounded by
    sum of geometric series of input magnitudes (stability property)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (1, 128, 8))
    a = jnp.full((1, 128), 0.9)
    b = jax.random.normal(ks[2], (1, 128, 4)) * 0.1
    c = jax.random.normal(ks[3], (1, 128, 4)) * 0.1
    out = ref.ssm_scan_ref(x, a, b, c)
    bound = (jnp.max(jnp.abs(x)) * jnp.max(jnp.abs(b)) * jnp.max(jnp.abs(c))
             * 4 / (1 - 0.9))
    assert float(jnp.max(jnp.abs(out))) <= float(bound) + 1e-3
