"""Device time of the chunk programs per chunk (ms).

The mixed-radix decode (`decode`), the float64 chunk program (`body`: the
network search's metric program, and in co-design the network-column
builder) and, in co-design, the accelerator grid kernel (the vmapped
`single`).  Read from the
program executions in the trace; chunks = searches x chunks per search.
"""

# the reading of `engine.made_unit()`
MADE_READING = 3.5

PATTERN = r"^jit_(decode|body|single)$"


def read(win, cell):
    chunks = len(win.units) * cell.counters["chunks_per_search"]
    t = win.time_of("modules", PATTERN)
    return t / chunks * 1e3 if t > 0 else None
