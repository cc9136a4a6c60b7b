"""The contract between the training cell class (`benchlib/lm.py`) and a
configuration's reference module (`bench/README.md`): the harness drives a
reference whose weights are laid out unlike Yi's through the contract's
functions alone, and reads the same numbers; and Yi's model-FLOP count is
the one its cell has reported since it was added."""

import types
from unittest import mock

import jax.numpy as jnp
import pytest

from rehearsal import BENCH, benchmark, control_cell, harness, \
    workloads_driven_by
from benchlib import lm

CONTRACT = ("init_params", "leaf_shapes", "train", "to_program",
            "from_program", "leaves", "model_flops_per_token", "loss_fn")


def _yi_workload():
    """The first training cell whose configuration `yi.reference.py`
    describes."""
    for name in workloads_driven_by("lm:Training"):
        _, config, _ = harness.cell_spec(benchmark(), name)
        if config["reference"] == "yi.reference.py":
            return name
    raise LookupError("no training cell runs yi.reference.py")


def _by_layer(yi, used: set):
    """`yi.reference.py` with each layer's weights under keys of their own
    (`layer0.wq`, ...) instead of stacked, and with the contract's
    functions and nothing else; each records in `used` that it was called."""
    def unstack(p, config):
        out = {k: x for k, x in p.items() if k not in yi.LAYER_LEAVES}
        for k in yi.LAYER_LEAVES:
            for i in range(config["num_hidden_layers"]):
                out[f"layer{i}.{k}"] = p[k][i]
        return out

    def stack(p, config):
        out = {k: x for k, x in p.items() if not k.startswith("layer")}
        for k in yi.LAYER_LEAVES:
            out[k] = jnp.stack([p[f"layer{i}.{k}"]
                                for i in range(config["num_hidden_layers"])])
        return out

    def train(config, opt, seed, batches):
        losses, first, last = yi.train(config, opt, seed, batches)
        return losses, unstack(first, config), unstack(last, config)

    fns = {
        "init_params": lambda config, seed: unstack(
            yi.init_params(config, seed), config),
        "leaf_shapes": lambda config: unstack(yi.leaf_shapes(config), config),
        "train": train,
        "to_program": lambda p, config: yi.to_program(stack(p, config), config),
        "from_program": lambda tree, config: unstack(
            yi.from_program(tree, config), config),
        "leaves": lambda p, config: dict(p),
        "model_flops_per_token": yi.model_flops_per_token,
        "loss_fn": lambda config: lambda p, tokens, labels: yi.loss_fn(
            config)(stack(p, config), tokens, labels),
    }
    assert set(fns) == set(CONTRACT)

    def recorded(name, fn):
        def call(*args, **kw):
            used.add(name)
            return fn(*args, **kw)
        return call
    return types.SimpleNamespace(**{k: recorded(k, f) for k, f in fns.items()})


def _readings(obj):
    checks, failed = obj.sound()
    c = obj.cell.counters
    return ({ch.name: ch.value for ch in checks}, failed,
            {k: c[k] for k in ("checked_losses", "reference_losses",
                               "leaves", "leaves_moved",
                               "model_flops_per_step")})


def test_the_harness_needs_only_the_reference_contract():
    """The training cell, set up and checked at the small size through a
    reference that keeps each layer under keys of its own, reads every
    check number and loss of the unwrapped reference bit for bit, on one
    seed; `lm.py` calls nothing of it beyond the contract."""
    workload = _yi_workload()
    seed = 2**31 + 5
    plain = _readings(control_cell(workload, seed))
    used: set = set()
    yi = harness.reference_module(control_cell(workload).config)
    with mock.patch.object(lm, "reference_module",
                           lambda config: _by_layer(yi, used)):
        wrapped = _readings(control_cell(workload, seed))
    assert wrapped == plain
    assert used >= {"init_params", "train", "to_program", "from_program",
                    "leaves", "model_flops_per_token"}, used


def test_yi_model_flops_are_pinned():
    """yi-6b-stage4 at 4,096 tokens: 173,015,040 matmul parameters a layer,
    32,768,000 in the head, and 3,612,942,336 model FLOPs a token, of which
    the causal count keeps 4,097 / 8,192 of the attention pairs."""
    config = harness.load_json(BENCH / "configs" / "yi-6b-stage4.json")
    yi = harness.reference_module(config)
    assert yi.matmul_params(config) == (173_015_040, 32_768_000)
    causal = yi.model_flops_per_token(config, 4096)
    assert causal == 3_612_942_336.0
    full = yi.model_flops_per_token(config, 4096, causal=False)
    dense = 6.0 * (3 * 173_015_040 + 32_768_000)
    assert (causal - dense) / (full - dense) == pytest.approx(4097 / 8192)
