"""Device time of the front extraction program per whole search (ms).

`pareto_mask` runs its sort and serial Fenwick scan as the jitted program
`_pareto_mask_core`; every chunk's merge calls it on the running front plus
the chunk's survivors.  Read from the program executions in the trace.
"""

# the reading of `engine.made_unit()`
MADE_READING = 6.0

PATTERN = r"^jit__pareto_mask_core$"


def read(win, cell):
    t = win.time_of("modules", PATTERN)
    return t / len(win.units) * 1e3 if t > 0 else None
