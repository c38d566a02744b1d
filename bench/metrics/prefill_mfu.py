"""Model operations of the prompts prefilled in the window (``kv.prefill``:
real prompt tokens only, padding not counted, causal attention, the head
at the last position), over the window times the chip's peak."""
from yard.readers import mfu_pct


def read(w):
    return mfu_pct(w, w.work.get("prefill_flops", 0))
