"""Device time of the photonic-MAC kernel per optimizer step (ms).

Every forward matmul whose weight tiles by 128 runs the Pallas kernel
`photonic_mac` (the 8-bit weight levels dequantized per tile on the chip);
the sum of its calls' durations in the trace, over the traced steps.
"""

PATTERN = r"^photonic_mac$"

# the reading of `lm.made_unit()`
MADE_READING = 3.0


def read(win, cell):
    t = win.time_of("ops", PATTERN)
    return t / len(win.units) * 1e3 if t > 0 else None
