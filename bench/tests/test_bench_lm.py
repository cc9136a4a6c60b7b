"""The training cells' parts on the CPU, for every cell whose traffic runs
`lm:Training`: the program against its configuration's plain reference at
a small size, the reference's model-FLOP count against XLA's own count of
the reference step, the weights' layout, and the roofline costs of the
kernels' instructions."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rehearsal import (benchmark, control_cell, harness, overrides,
                       workloads_driven_by)
from benchlib import lm, roofline, traces

TRAINING = workloads_driven_by("lm:Training")


def _small_spec(workload):
    _, config, traffic = harness.cell_spec(benchmark(), workload)
    small = overrides(workload)
    config.update(small["config"])
    traffic.update(small["traffic"])
    return config, traffic


@pytest.mark.parametrize("workload", TRAINING)
def test_program_in_float32_matches_the_reference(workload):
    """With the activations in float32, the program (Pallas kernels in
    interpret mode, photonic 8-bit weights, AdamW) and the reference differ
    only in summation order: the quantized weights are the same numbers.
    Loss, first gradient and the three steps' change agree to float32
    rounding; the change leaves room for AdamW's sign-like first update,
    which flips where a gradient element is at rounding level."""
    obj = control_cell(workload, seed=2**31 + 3)
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


@pytest.mark.parametrize("workload", TRAINING)
def test_flop_count_matches_xla_on_the_reference_step(workload):
    """The reference's model-FLOP count (without the causal halving, since
    the reference computes every query-key pair) against XLA's count of the
    reference's forward and backward (`loss_fn`, nothing recomputed).  XLA
    also counts the weight quantization, softmax, norms and the loss's
    logsumexp: 1.8% for yi-6b-stage4 at this size, so 3% holds it, while a
    matmul counted twice or left out would move the count by far more."""
    config, traffic = _small_spec(workload)
    seq = int(traffic["seq_len"])
    ref = harness.reference_module(config)
    params = {k: jax.ShapeDtypeStruct(v, jnp.float32)
              for k, v in ref.leaf_shapes(config).items()}
    ids = jax.ShapeDtypeStruct((1, seq), jnp.int32)
    compiled = jax.jit(jax.value_and_grad(ref.loss_fn(config))).lower(
        params, ids, ids).compile()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    want = ref.model_flops_per_token(config, seq, causal=False) * seq
    assert cost["flops"] == pytest.approx(want, rel=0.03)


@pytest.mark.parametrize("workload", TRAINING)
def test_causal_count_halves_the_attention_pairs(workload):
    """The causal count leaves out the pairs above the diagonal: at 4,096
    tokens, (4,096 - 1) / 2 keys a query fewer than the full count, each
    key costing what the full count gains per token of sequence (the
    slope from 2,048 to 4,096)."""
    config, _ = _small_spec(workload)
    ref = harness.reference_module(config)
    full = ref.model_flops_per_token(config, 4096, causal=False)
    causal = ref.model_flops_per_token(config, 4096)
    per_key = (full - ref.model_flops_per_token(config, 2048,
                                                causal=False)) / 2048
    assert per_key > 0
    assert full - causal == pytest.approx(per_key * 4095 / 2, rel=1e-12)


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


@pytest.mark.parametrize("workload", TRAINING)
def test_weights_are_the_seeds_and_laid_out_as_the_programs(workload):
    """The same seed makes the same weights (a seed above 32 bits too), of
    the shapes `leaf_shapes` states; the program's tree takes them without
    a copy, laid out as the program's own `init`; and the check's leaves
    hold every weight once."""
    from repro.models import model
    config, _ = _small_spec(workload)
    ref = harness.reference_module(config)
    a = ref.init_params(config, 2**33 + 1)
    b = ref.init_params(config, 2**33 + 1)
    c = ref.init_params(config, 1)
    assert {k: tuple(x.shape) for k, x in a.items()} == \
        {k: tuple(s) for k, s in ref.leaf_shapes(config).items()}
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert any(not np.array_equal(a[k], c[k]) for k in a)
    tree = ref.to_program(a, config)
    program, _ = model.init_abstract(lm.model_config(config, {}))
    assert jax.tree.map(lambda x: (x.shape, x.dtype), tree) == \
        jax.tree.map(lambda x: (x.shape, x.dtype), program)
    back = ref.from_program(tree, config)
    assert all(back[k] is a[k] for k in a)
    parts = ref.leaves(a, config)
    assert sum(x.size for x in parts.values()) == \
        sum(x.size for x in a.values())


@pytest.mark.parametrize("workload", TRAINING)
def test_benchmark_batches_are_the_pipelines(workload):
    from repro.data.pipeline import DataConfig, SyntheticLM
    config, traffic = _small_spec(workload)
    cfg = lm.model_config(config, {})
    src = SyntheticLM(cfg, DataConfig(global_batch=int(traffic["batch"]),
                                      seq_len=int(traffic["seq_len"]),
                                      seed=2**31 + 9))
    for step in (0, 5):
        tokens, labels = lm.batch_at(traffic, cfg.vocab, 2**31 + 9, step)
        got = src.batch_at(step)
        assert np.array_equal(got["tokens"], tokens)
        assert np.array_equal(got["labels"], labels)
