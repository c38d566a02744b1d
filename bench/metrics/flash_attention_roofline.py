"""Roofline share of the ``flash_attention`` Pallas kernel: the least time
causal attention over the window's real prompt tokens needs, over the
kernel's device time in the trace."""
from yard.readers import KERNEL_NAMES, roofline_pct

NAMES = KERNEL_NAMES["flash_attention"]


def read(w):
    return roofline_pct(w, NAMES, w.work.get("flash_attention"))
