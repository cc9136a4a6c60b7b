"""The train-step program's share of the chip's bf16 peak (%).

The model FLOPs of one step (`model_flops_per_step`, counted in
`roofline.py` from the configuration file, recomputation not counted) over
the device time of `jit_step_fn` per traced step times the peak
(`roofline.PEAKS`).  It bounds what any one kernel's roofline share can
buy: a kernel taken off the path leaves its own reading silent, and this
one still reads the whole step.
"""

from benchlib import roofline

PATTERN = r"^jit_step_fn$"

# the reading of `lm.made_unit()`
MADE_READING = 50.0


def read(win, cell):
    t = win.time_of("modules", PATTERN) / len(win.units)
    if t <= 0:
        return None
    peak = roofline.peak(cell.counters["device_kind"])["bf16_flops"]
    return 100.0 * cell.counters["model_flops_per_step"] / (t * peak)
