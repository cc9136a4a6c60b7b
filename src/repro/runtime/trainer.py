"""Fault-tolerant training runtime.

Builds the jitted, sharded train step (GSPMD over the production mesh or
plain jit on one device), wires the data pipeline, checkpoints step-atomically
and resumes bitwise-identically, injects/absorbs failures, and accounts
stragglers via the deadline policy.

train_step = forward (chunked CE) -> backward -> AdamW update, donated state.
Gradient synchronization is GSPMD-implicit by default; the TRINE hierarchical
/ compressed schedules in `repro.parallel.collectives` are exercised by the
manual-DP path (`grad_sync="trine"|"trine_int8"`) used in tests and the
collective benchmarks.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import store
from repro.core.fabric import degrade, get_fabric, overlapped_step_s
from repro.core.faults import FabricUnusableError, FaultScenario
from repro.core.planner import plan_collective_channels
from repro.data.pipeline import DataConfig, DeadlineMonitor, SyntheticLM
from repro.models import model as M
from repro.models.config import ModelConfig
from repro.optim import adamw
from repro.parallel import sharding as S


class FailureInjected(RuntimeError):
    """Raised by the failure hook to simulate a node loss mid-run."""


def _split_microbatches(batch: Dict[str, jax.Array], accum: int):
    """(B, ...) leaves -> (accum, B/accum, ...); the (3,B,S) M-RoPE positions
    leaf splits on axis 1."""
    def leaf(x):
        if x.ndim >= 3 and x.shape[0] == 3:          # M-RoPE positions
            return jnp.moveaxis(
                x.reshape(3, accum, -1, *x.shape[2:]), 1, 0)
        return x.reshape(accum, -1, *x.shape[1:])
    return jax.tree.map(leaf, batch)


def make_train_step(cfg: ModelConfig, opt: adamw.OptConfig, param_wire=None,
                    accum_steps: int = 1):
    """`param_wire` (repro.parallel.wire.ParamWire) puts the narrow payload
    on the parameter all-gathers: scanned stacks cross the wire as int8
    pairs dequantized inside the scan body; gradients flow back to the f32
    masters through the zero-delta carrier (see wire.py docstring).

    `accum_steps` > 1 runs gradient accumulation: the global batch is split
    into microbatches scanned sequentially, gradients averaged, ONE optimizer
    update — the standard way to hold global batch fixed while per-device
    memory shrinks (or devices are lost: the elastic path re-plans accum)."""

    def grads_of(params_or_carrier, batch, loss_closure):
        return jax.value_and_grad(loss_closure, has_aux=True)(params_or_carrier)

    def step_fn(state: adamw.TrainState, batch: Dict[str, jax.Array]):
        if param_wire is None:
            diff_var = state.params
            def loss_of(v, mb):
                return M.loss_fn(cfg, v, mb)
        else:
            qtree = param_wire.quantize(state.params)   # outside AD, once
            diff_var = param_wire.carrier(state.params)
            def loss_of(v, mb):
                return M.loss_fn(cfg, param_wire.graft(qtree, v), mb)

        if accum_steps == 1:
            (loss, metrics), grads = jax.value_and_grad(
                lambda v: loss_of(v, batch), has_aux=True)(diff_var)
        else:
            mbs = _split_microbatches(batch, accum_steps)

            def acc(carry, mb):
                g_sum, l_sum = carry
                (l, mets), g = jax.value_and_grad(
                    lambda v: loss_of(v, mb), has_aux=True)(diff_var)
                return (jax.tree.map(jnp.add, g_sum, g), l_sum + l), mets

            zeros = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32),
                                 diff_var)
            (g_sum, l_sum), mets = jax.lax.scan(
                acc, (zeros, jnp.zeros((), jnp.float32)), mbs)
            grads = jax.tree.map(lambda g: g / accum_steps, g_sum)
            loss = l_sum / accum_steps
            metrics = jax.tree.map(lambda m: jnp.mean(m), mets)

        new_state = adamw.apply_updates(opt, state, grads)
        metrics = dict(metrics, loss=loss,
                       grad_norm=adamw.global_norm(grads))
        return new_state, metrics
    return step_fn


def build_sharded_step(cfg: ModelConfig, opt: adamw.OptConfig, mesh,
                       param_specs, batch_example):
    """jit the train step with NamedShardings over `mesh` (None -> plain jit)."""
    if mesh is None:
        return jax.jit(make_train_step(cfg, opt), donate_argnums=(0,))
    rules = S.rules_for(cfg, mesh)
    pw = None
    if cfg.wire_bits:
        from repro.parallel import wire as _wire
        pw = _wire.make_param_wire(cfg, mesh, rules, param_specs)
    step_fn = make_train_step(cfg, opt, param_wire=pw)
    state_sh = S.tree_shardings(mesh, adamw.state_specs(param_specs), rules)
    batch_sh = S.train_batch_shardings(cfg, mesh, batch_example)
    # the new state keeps the declared layout, so it can be fed back in
    return jax.jit(step_fn, in_shardings=(state_sh, batch_sh),
                   out_shardings=(state_sh, None),
                   donate_argnums=(0,)), state_sh, batch_sh


@dataclasses.dataclass
class TrainerConfig:
    ckpt_dir: str = "/tmp/repro_ckpt"
    ckpt_every: int = 50
    keep: int = 3
    log_every: int = 10
    straggler_deadline_s: float = 1e9
    seed: int = 0
    overlap_window_s: float = 50e-3   # compute window the gradient collective
                                      # hides under (channel planning)


class Trainer:
    def __init__(self, cfg: ModelConfig, opt: adamw.OptConfig,
                 data: DataConfig, tcfg: TrainerConfig,
                 mesh=None, resume: bool = True, source=None, fabric=None):
        self.cfg, self.opt, self.data_cfg, self.tcfg = cfg, opt, data, tcfg
        self.mesh = mesh
        self.source = source if source is not None else SyntheticLM(cfg, data)
        key = jax.random.PRNGKey(tcfg.seed)
        params, self.param_specs = M.init(cfg, key)
        self.state = adamw.init_state(opt, params)
        self.state_sh = None

        if mesh is None:
            self._step = jax.jit(make_train_step(cfg, opt), donate_argnums=(0,))
        else:
            self._step, self.state_sh, _ = build_sharded_step(
                cfg, opt, mesh, self.param_specs, self.source.batch_at(0))
            self.state = jax.device_put(self.state, self.state_sh)

        self.start_step = 0
        if resume:
            # corrupt/truncated latest checkpoints (bad SHA1, missing
            # manifest) are dropped and the previous retained step restored
            restored = store.restore_latest_valid(tcfg.ckpt_dir, self.state,
                                                  self.state_sh)
            if restored is not None:
                self.state, self.start_step = restored[0], int(restored[1])

        # modeled photonic fabric under the data-parallel gradient collective:
        # channel plan + exposed network time per step, replanned on faults
        self.fabric = None if fabric is None else get_fabric(fabric)
        self.collective_channels = None
        self.net_s = 0.0
        if self.fabric is not None:
            self._grad_bytes = 4.0 * sum(
                int(np.prod(np.shape(l)))
                for l in jax.tree.leaves(self.state.params))
            self._replan()

        self.monitor = DeadlineMonitor(tcfg.straggler_deadline_s)
        self.history: list = []

    # ---- fault-epoch hook -------------------------------------------------
    def _replan(self) -> None:
        """(Re)plan the gradient-collective channels against the current
        fabric and refresh the modeled exposed network time per step.
        Raises FabricUnusableError when the fabric cannot carry the
        collective at all (the hard-fail path)."""
        if self.fabric.cross_pod_bw_bytes_per_s <= 0:
            raise FabricUnusableError(
                f"fabric {self.fabric.name!r} has no surviving bandwidth; "
                f"the gradient collective cannot be scheduled")
        w = self.tcfg.overlap_window_s
        self.collective_channels = plan_collective_channels(
            self._grad_bytes, w, fabric=self.fabric, max_channels=64)
        self.net_s = overlapped_step_s(
            w, self._grad_bytes, self.fabric, self.collective_channels) - w

    def inject_fault(self, scenario: FaultScenario) -> None:
        """Degrade the fabric under `scenario` and replan the collective —
        training continues at the (modeled) reduced throughput, or hard-fails
        with FabricUnusableError when nothing survives."""
        if self.fabric is None:
            raise ValueError("trainer has no fabric to degrade")
        self.fabric = degrade(self.fabric, scenario)
        self._replan()

    def run(self, steps: int, fail_at: Optional[int] = None,
            quiet: bool = False, fault_at: Optional[int] = None,
            fault_scenario: Optional[FaultScenario] = None) -> Dict[str, Any]:
        t0 = time.perf_counter()
        for step in range(self.start_step, steps):
            if fault_at is not None and step + 1 == fault_at:
                self.inject_fault(fault_scenario)
            fetch_t0 = time.perf_counter()
            batch = self.source.batch_at(step)
            delivery = time.perf_counter() - fetch_t0
            if not self.monitor.admit(delivery):
                continue  # straggler drop: skip this host's contribution

            self.state, metrics = self._step(self.state, batch)
            if (step + 1) % self.tcfg.ckpt_every == 0 or step + 1 == steps:
                store.save(self.tcfg.ckpt_dir, step + 1, self.state,
                           keep=self.tcfg.keep)
            if fail_at is not None and step + 1 == fail_at:
                raise FailureInjected(f"injected node failure at step {step + 1}")
            if not quiet and (step + 1) % self.tcfg.log_every == 0:
                print(f"step {step+1}: loss={float(metrics['loss']):.4f} "
                      f"gnorm={float(metrics['grad_norm']):.3f}")
            row = {k: float(v) for k, v in metrics.items()}
            row["step"] = step + 1
            if self.fabric is not None:
                row["net_s"] = self.net_s
            self.history.append(row)
        result = {
            "final_step": steps,
            "wall_s": time.perf_counter() - t0,
            "last_loss": self.history[-1]["loss"] if self.history else None,
            "straggler": dataclasses.asdict(self.monitor.stats),
        }
        if self.fabric is not None:
            result["fabric"] = self.fabric.name
            result["collective_channels"] = self.collective_channels
            result["net_s"] = self.net_s
        return result


def run_with_restarts(make_trainer, total_steps: int, fail_at=(),
                      **run_kwargs):
    """Supervisor loop: on FailureInjected (or a real crash in production),
    rebuild the trainer — which restores the latest checkpoint — and continue.
    Returns the last trainer, with `history` merged across segments so
    post-restart reports cover the full run (steps replayed after a restore
    keep only their re-executed rows — each step appears exactly once)."""
    pending = list(fail_at)
    prior: list = []
    while True:
        tr = make_trainer()
        # drop first-execution rows of steps the restored trainer will replay
        prior = [h for h in prior if h.get("step", 0) <= tr.start_step]
        try:
            tr.run(total_steps, fail_at=pending[0] if pending else None,
                   quiet=True, **run_kwargs)
            tr.history = prior + tr.history
            return tr
        except FailureInjected:
            prior = prior + tr.history
            pending.pop(0)
            continue
