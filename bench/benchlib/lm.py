"""Language-model training cells: whole AdamW steps back to back.

The window drives the program's own training path: a `Trainer`
(runtime/trainer.py) over `SyntheticLM` (data/pipeline.py), built from the
configuration file's keys through `dataclasses.replace`, with the Pallas
kernels and the photonic-MAC numerics on, as `launch/train.py
--photonic-mac` runs it.  One unit is one optimizer step: the pipeline's
batch, `Trainer._step` (the jitted, donating train step), and a wait for its
loss.  No checkpoint is saved.

Set-up makes the weights from the seed with the reference's `init_params`
(one jitted call on the device), puts them in the trainer's state, and
drives the first `checked_steps` steps through the window's own `step()`;
the first compiles.  It keeps the losses of those steps, their batches,
the first AdamW moment after step 1 (the first gradient as the optimizer
took it: m / (1 - b1)) and the weights after the last, on the host.  The
window then goes on from that same trainer.

After the window the plain reference (configs/*.reference.py) replays those
steps from the same seed on batches that the benchmark makes itself, and
the check compares, leaf by leaf (each layer's slice of a stacked weight a
leaf of its own; the norms taken on the device):

  loss_rel_err     the largest relative gap of a checked step's loss;
  grad_norm_gap    the worst leaf's gap between the norms of the program's
                   first gradient and the reference's, over the larger of
                   the reference leaf's norm and the median leaf's;
  grad_rel_err     the median leaf's relative error of that gradient;
  change_norm_gap  the worst leaf's gap between the norms of the weights'
                   change over the checked steps, as grad_norm_gap;
  change_rel_err   the worst leaf's error of that change, over the same
                   denominator;
  tokens_differ    token ids of the checked batches that differ from the
                   benchmark's own generator (exact: 0);
  moment_bf16_exact  the share of the first moment's nonzero elements that
                   bfloat16 holds exactly (low 16 bits zero): about 2**-16
                   for the float32 state the configuration states, 1 for a
                   state kept in bfloat16, whose rounding lies below what
                   the bfloat16 activations leave in the other numbers.

The change leaves out leaves whose reference gradient is under a thousandth
of the median leaf's: AdamW moves them by round-off alone.  A window step
whose loss is not finite counts as failed.
"""

from __future__ import annotations

import dataclasses
import gc
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import roofline
from benchlib.harness import Check, reference_module
from benchlib.traces import Event, TraceData

# the published config's keys -> the program's ModelConfig fields
MODEL_FIELDS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
                "num_attention_heads": "n_heads",
                "num_key_value_heads": "n_kv_heads",
                "intermediate_size": "d_ff", "vocab_size": "vocab",
                "rope_theta": "rope_theta",
                "tie_word_embeddings": "tie_embeddings",
                "photonic_bits": "photonic_bits"}

# leaves whose reference gradient is under this share of the median leaf's
# are left out of the change
STILL_LEAF = 1e-3


def model_config(config: dict, overrides: dict):
    """The program's ModelConfig for a configuration file."""
    from repro import configs
    fields = {f: config[k] for k, f in MODEL_FIELDS.items()}
    fields["head_dim"] = config.get("head_dim") or \
        config["hidden_size"] // config["num_attention_heads"]
    prog = dict(config["program"])
    base = configs.get(prog.pop("arch"))
    return dataclasses.replace(base, **{**fields, **prog, **overrides})


def batch_at(traffic: dict, vocab: int, seed: int, step: int):
    """(tokens, labels) of one step: `batch` rows of `seq_len` + 1 Zipf ids
    over [2, vocab), drawn from the seed and the step; labels are the ids
    shifted by one."""
    rng = np.random.default_rng((seed * 1_000_003 + step) * 97)
    b, s = int(traffic["batch"]), int(traffic["seq_len"])
    ranks = rng.zipf(float(traffic["zipf"]), size=(b, s + 1)).astype(np.int64)
    toks = ((ranks % (vocab - 2)) + 2).astype(np.int32)
    return toks[:, :-1], toks[:, 1:]


def to_program(ref: dict) -> dict:
    """The reference's weights in the program's tree (no copies)."""
    return {"embed": ref["embed"], "lm_head": ref["lm_head"],
            "final_norm": ref["final_norm"],
            "stages": [{"attn_0": {
                "attn": {"wq": ref["wq"], "wk": ref["wk"], "wv": ref["wv"],
                         "wo": ref["wo"], "norm": ref["attn_norm"]},
                "mlp": {"wi": ref["wi"], "wg": ref["wg"], "wo": ref["wd"],
                        "norm": ref["mlp_norm"]}}}]}


def from_program(p: dict) -> dict:
    a = p["stages"][0]["attn_0"]["attn"]
    m = p["stages"][0]["attn_0"]["mlp"]
    return {"embed": p["embed"], "lm_head": p["lm_head"],
            "final_norm": p["final_norm"], "wq": a["wq"], "wk": a["wk"],
            "wv": a["wv"], "wo": a["wo"], "attn_norm": a["norm"],
            "wi": m["wi"], "wg": m["wg"], "wd": m["wo"],
            "mlp_norm": m["norm"]}


def leaves(ref_layout: dict, layers: int) -> dict:
    """{leaf name: array} (host or device), each layer's slice of a stacked
    weight a leaf of its own."""
    out = {}
    for name, x in sorted(ref_layout.items()):
        if name in ("embed", "lm_head", "final_norm"):
            out[name] = x
        else:
            for i in range(layers):
                out[f"{name}.{i}"] = x[i]
    return out


def bf16_exact_share(arrays) -> float:
    n = exact = 0
    for x in arrays:
        x = np.asarray(x, np.float32)
        nz = x != 0
        n += int(np.sum(nz))
        exact += int(np.sum(nz & ((x.view(np.uint32) & 0xFFFF) == 0)))
    return exact / max(n, 1)


@jax.jit
def _norms(a, b, base):
    """(|a - base|, |b - base|, |a - b|) of one leaf, in float32 on the
    device (XLA's tree reduction: a relative error near 1e-7)."""
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)

    def norm(x):
        return jnp.sqrt(jnp.sum(x * x))
    return jnp.stack([norm(a - base), norm(b - base), norm(a - b)])


def leaf_norms(prog: dict, ref: dict, base=None) -> dict:
    """{leaf: (program's norm, reference's norm, norm of their difference)},
    each side taken from `base` (the starting weights) where given."""
    return {k: tuple(float(v) for v in np.asarray(_norms(
        prog[k], ref[k], np.float32(0) if base is None else base[k])))
        for k in ref}


def compare(prog_losses, ref_losses, grads: dict, changes: dict) -> dict:
    """The check's numbers from the losses and `leaf_norms` of the first
    gradient and of the change over the checked steps."""
    g_med = float(np.median([r for _, r, _ in grads.values()]))
    gap = [abs(p - r) / max(r, g_med) for p, r, _ in grads.values()]
    rel = [d / max(r, g_med) for _, r, d in grads.values()]
    moved = {k: v for k, v in changes.items()
             if grads[k][1] >= STILL_LEAF * g_med}
    c_med = float(np.median([r for _, r, _ in moved.values()]))
    return {
        "loss_rel_err": max(abs(p - r) / abs(r)
                            for p, r in zip(prog_losses, ref_losses)),
        "grad_norm_gap": max(gap), "grad_rel_err": float(np.median(rel)),
        "change_norm_gap": max(abs(p - r) / max(r, c_med)
                               for p, r, _ in moved.values()),
        "change_rel_err": max(d / max(r, c_med) for _, r, d in moved.values()),
        "leaves": len(grads), "leaves_moved": len(moved)}


class Training:
    """A closed loop of whole optimizer steps; work = tokens."""

    def __init__(self, cell):
        self.cell = cell
        self.config, self.traffic = cell.config, cell.traffic
        self.opt = dict(self.traffic["optimizer"])
        self.checked = int(self.traffic["checked_steps"])
        self.layers = int(self.config["num_hidden_layers"])
        self.vocab = int(self.config["vocab_size"])
        self.tokens = int(self.traffic["batch"]) * int(self.traffic["seq_len"])
        self.flops_per_token = roofline.lm_train_flops_per_token(
            self.config, int(self.traffic["seq_len"]))
        # the program's settings that a control or a test changes
        self.program_overrides: dict = {}
        self.opt_overrides: dict = {}
        self.trainer = None
        self.losses, self.batches = [], []

    def setup(self):
        from repro.data.pipeline import DataConfig
        from repro.optim import adamw
        from repro.runtime.trainer import Trainer, TrainerConfig
        cfg = model_config(self.config, self.program_overrides)
        opt = adamw.OptConfig(**{**self.opt, **self.opt_overrides})
        data = DataConfig(global_batch=int(self.traffic["batch"]),
                          seq_len=int(self.traffic["seq_len"]),
                          seed=self.cell.seed)
        # resume=False: nothing is read from, or written to, the directory
        self.trainer = Trainer(cfg, opt, data,
                               TrainerConfig(ckpt_dir=tempfile.gettempdir()),
                               resume=False)
        ref = reference_module(self.config)
        params = to_program(ref.init_params(self.config, self.cell.seed))
        want = jax.tree.map(lambda x: (x.shape, x.dtype),
                            self.trainer.state.params)
        got = jax.tree.map(lambda x: (x.shape, x.dtype), params)
        if want != got:
            raise ValueError(f"the program's weights are not laid out as the "
                             f"benchmark's: {want} != {got}")
        self.trainer.state = self.trainer.state._replace(params=params)
        del params
        device = jax.devices()[0]
        self.cell.counters.update(
            device_kind=device.device_kind, tokens_per_step=self.tokens,
            model_flops_per_step=self.flops_per_token * self.tokens)
        for k in range(self.checked):
            self.step()
            if k == 0:
                m = from_program(jax.device_get(self.trainer.state.m))
                self.moment_exact = bf16_exact_share(m.values())
                self.first_grad = leaves(jax.tree.map(
                    lambda m: np.asarray(m, np.float32) / (1 - opt.b1), m),
                    self.layers)
                del m
        self.checked_params = leaves(
            from_program(jax.device_get(self.trainer.state.params)),
            self.layers)

    def step(self):
        k = len(self.losses)
        batch = self.trainer.source.batch_at(k)
        self.trainer.state, metrics = self.trainer._step(self.trainer.state,
                                                         batch)
        self.losses.append(float(metrics["loss"]))   # waits for the step
        if k < self.checked:
            self.batches.append((batch["tokens"], batch["labels"]))
        self.cell.counters["steps"] = len(self.losses)
        return self.tokens

    def end_to_end(self, units):
        t = units[-1][1] - units[0][0]
        tokens = sum(u[2] for u in units)
        peak = roofline.peak(self.cell.counters["device_kind"])["bf16_flops"]
        return {"train_tokens_per_s": tokens / t,
                "train_mfu": 100.0 * self.flops_per_token * tokens
                / (t * peak * self.cell.chips)}

    def release(self):
        self.trainer = None
        gc.collect()

    def check(self):
        t0 = time.perf_counter()
        ref = reference_module(self.config)
        seed = self.cell.seed
        mine = [batch_at(self.traffic, self.vocab, seed, k)
                for k in range(self.checked)]
        differ = sum(int(np.sum(np.asarray(a) != b))
                     for got, want in zip(self.batches, mine)
                     for a, b in zip(got, want))
        ref_losses, first, last = ref.train(self.config, self.opt, seed, mine)
        t1 = time.perf_counter()
        start = leaves(ref.init_params(self.config, seed), self.layers)
        got = compare(self.losses[:self.checked], ref_losses,
                      leaf_norms(self.first_grad, leaves(first, self.layers)),
                      leaf_norms(self.checked_params,
                                 leaves(last, self.layers), start))
        c = self.cell.counters
        c["leaves"], c["leaves_moved"] = got.pop("leaves"), got.pop(
            "leaves_moved")
        c["reference_s"], c["compare_s"] = t1 - t0, time.perf_counter() - t1
        c["checked_losses"] = self.losses[:self.checked]
        c["reference_losses"] = ref_losses
        lim = self.traffic["checks"]
        checks = [Check(k, v, lim[k]) for k, v in got.items()]
        checks.append(Check("tokens_differ", differ, lim["tokens_differ"]))
        checks.append(Check("moment_bf16_exact", self.moment_exact,
                            lim["moment_bf16_exact"]))
        failed = sum(not np.isfinite(x) for x in self.losses[self.checked:])
        return checks, failed

    def sound(self):
        """The program's readings of the checked steps, without a window."""
        self.setup()
        self.release()
        return self.check()

    def control(self):
        """The program with its photonic weights one precision down (int4
        levels for the configuration's int8): its readings, checked as a
        run's are."""
        self.program_overrides = {
            "photonic_bits": int(self.config["photonic_bits"]) // 2}
        return self.sound()

    def control_state(self):
        """The program with its AdamW state one precision down (bfloat16
        for the configuration's float32)."""
        self.opt_overrides = {"state_dtype": "bfloat16"}
        return self.sound()


MS = 1e6  # ns


def made_unit():
    """A made trace of two steps, for the readers' tests: (trace, counters).

    Each 10 ms step runs the train-step program for 8 ms (1-9 ms of it);
    inside it a photonic_mac call of 3 ms (4,096 x 4,096 x 4,096), a causal
    flash-attention call of 2 ms (32 query and 4 key/value heads of 4,096
    x 128) and 3 ms of fusions; a step is 7.88e11 model FLOPs."""
    mac = ("%photonic_mac.1 = f32[4096,4096]{1,0} custom-call("
           "bf16[4096,4096]{1,0} %x, s8[4096,4096]{1,0} %w, "
           "f32[32,32,1,1]{3,2,1,0} %s)")
    fa = ("%flash_attention.2 = f32[32,4096,128]{2,1,0} custom-call("
          "bf16[32,4096,128]{2,1,0} %q, bf16[4,4096,128]{2,1,0} %k, "
          "bf16[4,4096,128]{2,1,0} %v)")
    fusion = "%fusion.3 = f32[8]{0} fusion(f32[8]{0} %a)"
    mods, ops, spans = [], [], []
    for t in (0, 10 * MS):
        mods.append(Event("jit_step_fn(77)", t + 1 * MS, t + 9 * MS))
        ops += [Event(mac, t + 1 * MS, t + 4 * MS),
                Event(fa, t + 4 * MS, t + 6 * MS),
                Event(fusion, t + 6 * MS, t + 9 * MS)]
        spans.append(Event("bench.unit", t, t + 10 * MS))
    return TraceData({0: mods}, {0: ops}, spans), {
        "device_kind": "TPU v5 lite", "model_flops_per_step": 7.88e11}
