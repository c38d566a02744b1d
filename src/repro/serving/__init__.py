"""Serving: LM inference as a Floe dataflow (the serving plane).

``build_serving_flow`` composes inference on the Session API:
admission/scheduling, a flash-attention prefill stage, a
continuously-batched flash-decode stage with checkpointable KV/slot
state, live weight hot-swap, elastic decode scaling, and exactly-once
response delivery.  The LM itself (``LMSpec``, prefill, decode step,
slot splice) lives in ``serving/kv.py``.
"""
from .dataflow import (TICK, DecodePellet, LMSpec, PrefillPellet,
                       build_serving_flow, init_params, make_request,
                       swapped_flow)
from .scheduler import Scheduler

__all__ = [
    "LMSpec", "init_params", "make_request", "Scheduler",
    "PrefillPellet", "DecodePellet", "build_serving_flow", "swapped_flow",
    "TICK",
]
