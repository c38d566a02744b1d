"""Arithmetic that the per-layer metric readers under ``bench/metrics/``
share.  A reader returns None where the window holds nothing for it to
read; a share of a roofline or a peak is never made up as 0."""
from __future__ import annotations

from typing import Optional, Sequence

from .common import Window
from .work import roofline_seconds

#: how each Pallas kernel's events are named in the device trace: its
#: pallas_call's name, and the kernel function's
KERNEL_NAMES = {
    "decode_attention": ("decode_attention", "_decode_kernel"),
    "flash_attention": ("flash_attention", "_flash_kernel"),
}


def idle_share_pct(w: Window) -> Optional[float]:
    if w.trace is None or w.trace.window_s <= 0:
        return None
    return 100.0 * w.trace.idle_share


def roofline_pct(w: Window, patterns: Sequence[str], work: dict
                 ) -> Optional[float]:
    """The least time the work could take on this chip over the device
    time of the kernel's events in the trace, in percent."""
    if w.trace is None or not work or work.get("bytes", 0) <= 0:
        return None
    secs, calls = w.trace.kernel(patterns)
    if calls == 0 or secs <= 0:
        return None
    return 100.0 * roofline_seconds(work, w.peak) / secs


def mfu_pct(w: Window, flops: float) -> Optional[float]:
    """Model operations in the window over the window times the chip's
    peak, in percent."""
    if not flops:
        return None
    return 100.0 * flops / (w.seconds * w.peak["flops_per_s"])

