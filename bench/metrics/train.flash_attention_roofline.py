"""The flash-attention forward kernel's share of its roofline (%).

For each `flash_attention` call in the traced steps (the forward pass; the
backward runs XLA's attention VJP), the least time the chip could take:
the larger of the causal attention's operations (4 x head_dim per
query-key pair at or below the diagonal) over the v5e's bf16 peak, and the
bytes of q, k, v and the result over HBM bandwidth (`roofline.py`), summed
and divided by the calls' summed device time.
"""

from benchlib import roofline

PATTERN = r"^flash_attention$"

# the reading of `lm.made_unit()`
MADE_READING = 34.8914994680203


def read(win, cell):
    return roofline.roofline_share(win, PATTERN,
                                   roofline.flash_attention_cost,
                                   cell.counters["device_kind"])
