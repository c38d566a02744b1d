"""Model operations of the tokens decoded in the window (``kv.decode_step``:
every layer's weight matmuls, attention over each token's live cache
positions, and the head), over the window times the chip's peak."""
from yard.readers import mfu_pct


def read(w):
    return mfu_pct(w, w.work.get("decode_flops", 0))
