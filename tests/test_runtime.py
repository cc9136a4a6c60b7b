"""Fault tolerance, checkpointing, data determinism, straggler accounting."""

import dataclasses
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as C
from repro.checkpoint import store
from repro.data.pipeline import DataConfig, DeadlineMonitor, Prefetcher, SyntheticLM
from repro.optim.adamw import OptConfig
from repro.runtime.trainer import (FailureInjected, Trainer, TrainerConfig,
                                   run_with_restarts)

CFG = C.get_reduced("yi_6b")
OPT = OptConfig(lr=1e-3, warmup_steps=2, total_steps=16)
DATA = DataConfig(global_batch=2, seq_len=64)


def _trainer(tmp, resume=True):
    return Trainer(CFG, OPT, DATA,
                   TrainerConfig(ckpt_dir=str(tmp), ckpt_every=2, log_every=1000),
                   resume=resume)


def _leaves(state):
    return [np.asarray(x) for x in jax.tree.leaves(state)]


def test_resume_bitwise_identical(tmp_path):
    """Crash at step 3 + restart == uninterrupted run (bitwise)."""
    a = tmp_path / "a"
    b = tmp_path / "b"

    t_straight = Trainer(CFG, OPT, DATA, TrainerConfig(
        ckpt_dir=str(a), ckpt_every=2, log_every=1000), resume=False)
    t_straight.run(6, quiet=True)

    t_crash = run_with_restarts(
        lambda: _trainer(b), total_steps=6, fail_at=(4,))

    for x, y in zip(_leaves(t_straight.state), _leaves(t_crash.state)):
        np.testing.assert_array_equal(x, y)


def test_checkpoint_atomicity_and_retention(tmp_path):
    t = _trainer(tmp_path, resume=False)
    t.run(8, quiet=True)
    assert store.latest_step(tmp_path) == 8
    kept = sorted(d.name for d in tmp_path.iterdir() if d.name.startswith("step_"))
    assert len(kept) <= 3  # retention
    assert not any(d.name.endswith(".tmp") for d in tmp_path.iterdir())


def test_checkpoint_corruption_detected(tmp_path):
    t = _trainer(tmp_path, resume=False)
    t.run(2, quiet=True)
    step = store.latest_step(tmp_path)
    ck = tmp_path / f"step_{step:08d}"
    victim = next(ck.glob("leaf_*.npy"))
    victim.write_bytes(b"corrupted!" + victim.read_bytes()[10:])
    with pytest.raises(IOError, match="corruption"):
        store.restore(tmp_path, step, t.state)


def test_elastic_restore_roundtrip(tmp_path):
    """Checkpoints restore independently of the device layout that wrote them
    (full logical arrays + new shardings on load)."""
    t = _trainer(tmp_path, resume=False)
    t.run(2, quiet=True)
    step = store.latest_step(tmp_path)
    restored = store.restore(tmp_path, step, t.state, shardings=None)
    for x, y in zip(_leaves(t.state), _leaves(restored)):
        np.testing.assert_array_equal(x, y)


def test_data_step_indexed_determinism():
    src = SyntheticLM(CFG, DATA)
    b1 = src.batch_at(7)
    b2 = src.batch_at(7)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    b3 = src.batch_at(8)
    assert not np.array_equal(b1["tokens"], b3["tokens"])


def test_data_host_sharding_disjoint():
    d = DataConfig(global_batch=4, seq_len=32)
    h0 = SyntheticLM(CFG, d, host_index=0, host_count=2).batch_at(0)
    h1 = SyntheticLM(CFG, d, host_index=1, host_count=2).batch_at(0)
    assert h0["tokens"].shape == (2, 32)
    assert not np.array_equal(h0["tokens"], h1["tokens"])


def test_prefetcher_yields_in_order():
    src = SyntheticLM(CFG, DATA)
    pf = Prefetcher(iter(src), depth=2)
    first = next(pf)
    np.testing.assert_array_equal(first["tokens"], src.batch_at(0)["tokens"])
    second = next(pf)
    np.testing.assert_array_equal(second["tokens"], src.batch_at(1)["tokens"])
    pf.close()


def test_straggler_deadline_accounting():
    mon = DeadlineMonitor(deadline_s=0.5)
    assert mon.admit(0.1)
    assert not mon.admit(0.9)
    assert mon.stats.steps == 2 and mon.stats.dropped == 1
    assert mon.stats.drop_rate == pytest.approx(0.5)
    assert mon.survivor_scale(16, 1) == pytest.approx(16 / 15)


def test_wire_format_training_converges(tmp_path):
    """int8 param wire (QAT straight-through) trains: loss decreases and ends
    within a modest factor of the f32 baseline on the same data."""
    from repro.parallel import wire as W
    from repro.runtime.trainer import make_train_step
    from repro.optim import adamw
    from repro.models import model as M

    cfg8 = dataclasses.replace(CFG, wire_bits=8)
    key = jax.random.PRNGKey(0)
    params, specs = M.init(CFG, key)

    src = SyntheticLM(CFG, DATA)

    def run(cfg, pw):
        step = jax.jit(make_train_step(cfg, OPT, param_wire=pw),
                       donate_argnums=(0,))
        st = adamw.init_state(OPT, jax.tree.map(jnp.copy, params))
        losses = []
        for i in range(12):
            st, m = step(st, src.batch_at(i))
            losses.append(float(m["loss"]))
        return losses

    # single-device: the sharding constraint inside wire needs a mesh, so
    # emulate the numerics-only path with a trivial 1x1 mesh
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    from repro.parallel import sharding as S
    rules = S.rules_for(cfg8, mesh)
    pw = W.make_param_wire(cfg8, mesh, rules, specs)

    base = run(CFG, None)
    quant = run(cfg8, pw)
    assert base[-1] < base[0]
    assert quant[-1] < quant[0]            # QAT still learns
    assert quant[-1] < base[0]             # and beats the untrained loss
    assert quant[-1] < base[-1] * 1.5 + 0.5


def test_grad_accumulation_matches_full_batch():
    """accum_steps=2 over half-microbatches == one full-batch step (the CE is
    a per-token mean and microbatches are equal-sized, so mean-of-means is
    exact up to f32 reassociation)."""
    from repro.models import model as M
    from repro.optim import adamw
    from repro.runtime.trainer import make_train_step

    params, _ = M.init(CFG, jax.random.PRNGKey(0))
    src = SyntheticLM(CFG, DataConfig(global_batch=4, seq_len=64))
    batch = src.batch_at(0)

    s1 = adamw.init_state(OPT, jax.tree.map(jnp.copy, params))
    s2 = adamw.init_state(OPT, jax.tree.map(jnp.copy, params))
    step1 = jax.jit(make_train_step(CFG, OPT))
    step2 = jax.jit(make_train_step(CFG, OPT, accum_steps=2))
    s1, m1 = step1(s1, batch)
    s2, m2 = step2(s2, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-5)
    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_failure_injection_raises(tmp_path):
    t = _trainer(tmp_path, resume=False)
    with pytest.raises(FailureInjected):
        t.run(6, fail_at=2, quiet=True)
    # checkpoint from before the failure exists
    assert store.latest_step(tmp_path) == 2
