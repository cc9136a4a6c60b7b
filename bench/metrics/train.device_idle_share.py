"""Share of the traced steps in which nothing ran on the chip (%).

1 - (union of the device's busy intervals) / (traced window), from the
trace: the time the host holds the chip back between and inside steps.
"""

# the reading of `lm.made_unit()`
MADE_READING = 20.0


def read(win, cell):
    return 100.0 * win.idle_share
