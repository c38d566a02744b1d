"""Roofline share of the ``decode_attention`` Pallas kernel: the least
time the decode tokens of the window need (their queries against every
live cache position, per layer) over the kernel's device time in the
trace."""
from yard.readers import KERNEL_NAMES, roofline_pct

NAMES = KERNEL_NAMES["decode_attention"]


def read(w):
    return roofline_pct(w, NAMES, w.work.get("decode_attention"))
