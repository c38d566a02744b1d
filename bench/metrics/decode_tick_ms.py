"""Mean host time of one decode tick (``serving/dataflow.py``): the decode
stage's service seconds over the window, over the decode steps taken in
it (``DecodePellet.n_steps``)."""


def read(w):
    st = w.stages.get("decode")
    steps = w.counters.get("decode_steps", 0)
    if not st or steps <= 0:
        return None
    return 1e3 * st["service_sum"] / steps
