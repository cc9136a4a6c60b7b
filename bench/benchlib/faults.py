"""Faults planted in the training program, underneath the timed path, for
the tests and for reading each fault's numbers on the chip
(`controls.py --kinds fault:<name>`).  The benchmark's runs never plant
one.

  state_unchanged     the optimizer step returns its state unchanged;
  half_batch          half of the batch is left out of the loss, the mean
                      taken over the rest (half the rows, or half the
                      positions of a one-row batch);
  token_altered       one label id is altered where the pipeline makes it;
  attention_dropped   the attention blocks return their input: no layer
                      attends.
"""

from __future__ import annotations

import contextlib
from unittest import mock

FAULTS = ("state_unchanged", "half_batch", "token_altered",
          "attention_dropped")


@contextlib.contextmanager
def plant(kind: str):
    if kind not in FAULTS:
        raise ValueError(f"unknown fault {kind!r}; known: {FAULTS}")
    from repro.data import pipeline
    from repro.models import layers, model
    from repro.optim import adamw

    if kind == "state_unchanged":
        target, broken = "apply_updates", lambda cfg, state, grads: state
        owner = adamw
    elif kind == "half_batch":
        loss_fn = model.loss_fn

        def broken(cfg, params, batch):
            b, s = batch["tokens"].shape
            cut = ((lambda x: x[:b // 2]) if b > 1
                   else (lambda x: x[:, :s // 2]))
            return loss_fn(cfg, params, {k: cut(v) for k, v in batch.items()})
        owner, target = model, "loss_fn"
    elif kind == "token_altered":
        batch_at = pipeline.SyntheticLM.batch_at

        def broken(self, step):
            batch = batch_at(self, step)
            labels = batch["labels"].copy()
            labels[0, 0] = 2 + (labels[0, 0] - 1) % (self.cfg.vocab - 2)
            return dict(batch, labels=labels)
        owner, target = pipeline.SyntheticLM, "batch_at"
    else:
        def broken(cfg, p, x, positions, **kw):
            return x, None
        owner, target = layers, "apply_attention"
    with mock.patch.object(owner, target, broken):
        yield
