"""The training cell's parts on the CPU: the program against the plain
reference at a small size, the model-FLOP count against XLA's own count of
the reference step, and the roofline costs of the kernels' instructions."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rehearsal import benchmark, control_cell, harness, overrides
from benchlib import lm, roofline, traces

WORKLOAD = "yi-6b.train_4k"


def _small_spec():
    _, config, traffic = harness.cell_spec(benchmark(), WORKLOAD)
    small = overrides(WORKLOAD)
    config.update(small["config"])
    traffic.update(small["traffic"])
    return config, traffic


def test_program_in_float32_matches_the_reference():
    """With the activations in float32, the program (Pallas kernels in
    interpret mode, photonic 8-bit weights, AdamW) and the reference differ
    only in summation order: the quantized weights are the same numbers.
    Loss, first gradient and the three steps' change agree to float32
    rounding; the change leaves room for AdamW's sign-like first update,
    which flips where a gradient element is at rounding level."""
    obj = control_cell(WORKLOAD, seed=2**31 + 3)
    obj.program_overrides = {"dtype": "float32"}
    checks, failed = obj.sound()
    got = {c.name: c.value for c in checks}
    assert failed == 0
    assert got["tokens_differ"] == 0
    for name in ("loss_rel_err", "grad_norm_gap", "grad_rel_err",
                 "change_norm_gap"):
        assert got[name] < 1e-5, got
    assert got["change_rel_err"] < 1e-3, got
    assert got["moment_bf16_exact"] < 1e-3, got


def test_flop_count_matches_xla_on_the_reference_step():
    """The benchmark's model-FLOP count (without the causal halving, since
    the reference computes every query-key pair) against XLA's count of the
    reference's forward and backward, nothing recomputed.  XLA also counts
    the weight quantization, softmax, norms and the loss's logsumexp: 1.8%
    at this size, so 3% holds it, while a matmul counted twice or left out
    would move the count by far more."""
    config, traffic = _small_spec()
    seq = int(traffic["seq_len"])
    ref = harness.reference_module(config)
    params = {k: jax.ShapeDtypeStruct(v, jnp.float32)
              for k, v in ref.leaf_shapes(config).items()}
    ids = jax.ShapeDtypeStruct((1, seq), jnp.int32)
    compiled = jax.jit(jax.value_and_grad(ref.loss),
                       static_argnums=(3, 4)).lower(
        params, ids, ids, ref._Frozen(config), False).compile()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    want = roofline.lm_train_flops_per_token(config, seq, causal=False) * seq
    assert cost["flops"] == pytest.approx(want, rel=0.03)


def test_causal_count_halves_the_attention_pairs():
    config, _ = _small_spec()
    full = roofline.lm_train_flops_per_token(config, 4096, causal=False)
    causal = roofline.lm_train_flops_per_token(config, 4096)
    layer, head = roofline.lm_matmul_params(config)
    dense = 6.0 * (config["num_hidden_layers"] * layer + head)
    assert (causal - dense) / (full - dense) == pytest.approx(4097 / 8192)


def test_kernel_costs_from_the_instructions_shapes():
    trace, _ = lm.made_unit()
    mac, fa = [e.name for e in trace.ops[0][:2]]
    flops, nbytes = roofline.photonic_mac_cost(traces.op_shapes(mac))
    assert flops == 2 * 4096 ** 3
    assert nbytes == 4096 * 4096 * (4 + 2 + 1) + 32 * 32 * 4
    flops, nbytes = roofline.flash_attention_cost(traces.op_shapes(fa))
    assert flops == 4 * 32 * (4096 * 4097 // 2) * 128
    assert nbytes == 32 * 4096 * 128 * (4 + 2) + 2 * 4 * 4096 * 128 * 2


def test_a_chip_without_a_peak_is_an_error():
    with pytest.raises(KeyError):
        roofline.peak("cpu")


def test_weights_are_the_seeds_and_laid_out_as_the_programs():
    """The same seed makes the same weights (a seed above 32 bits too), and
    the program's tree takes them without a copy."""
    config, _ = _small_spec()
    ref = harness.reference_module(config)
    a = ref.init_params(config, 2**33 + 1)
    b = ref.init_params(config, 2**33 + 1)
    c = ref.init_params(config, 1)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["wq"], c["wq"])
    back = lm.from_program(lm.to_program(a))
    assert all(back[k] is a[k] for k in a)


def test_benchmark_batches_are_the_pipelines():
    from repro.data.pipeline import DataConfig, SyntheticLM
    config, traffic = _small_spec()
    cfg = lm.model_config(config, {})
    src = SyntheticLM(cfg, DataConfig(global_batch=int(traffic["batch"]),
                                      seq_len=int(traffic["seq_len"]),
                                      seed=2**31 + 9))
    for step in (0, 5):
        tokens, labels = lm.batch_at(traffic, cfg.vocab, 2**31 + 9, step)
        got = src.batch_at(step)
        assert np.array_equal(got["tokens"], tokens)
        assert np.array_equal(got["labels"], labels)
