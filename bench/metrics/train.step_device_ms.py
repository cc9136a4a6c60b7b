"""Device time of the train-step program per optimizer step (ms).

The program `Trainer` jits (`make_train_step`'s `step_fn`: forward, chunked
cross entropy, backward and the AdamW update), from the program executions
in the trace, over the traced steps.
"""

PATTERN = r"^jit_step_fn$"

# the reading of `lm.made_unit()`
MADE_READING = 8.0


def read(win, cell):
    t = win.time_of("modules", PATTERN)
    return t / len(win.units) * 1e3 if t > 0 else None
