"""The photonic-MAC kernel's share of its roofline (%).

For each `photonic_mac` call in the traced steps, the least time the chip
could take: the larger of 2 M N K operations over the v5e's bf16 peak (the
kernel's f32 dots run at default precision, one bf16 pass) and the bytes
of its operands and result over HBM bandwidth (`roofline.py`), summed and
divided by the calls' summed device time.
"""

from benchlib import roofline

PATTERN = r"^photonic_mac$"

# the reading of `lm.made_unit()`
MADE_READING = 23.255322076480542


def read(win, cell):
    return roofline.roofline_share(win, PATTERN, roofline.photonic_mac_cost,
                                   cell.counters["device_kind"])
