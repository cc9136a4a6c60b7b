"""Share of the traced searches in which no program ran on the chip (%).

1 - (union of device busy intervals) / (traced window), from the trace.
"""

# the reading of `engine.made_unit()`
MADE_READING = 35.0


def read(win, cell):
    return 100.0 * win.idle_share
