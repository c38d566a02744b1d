"""Pallas TPU kernels for the perf-critical compute layers.

Validated on CPU via ``interpret=True`` against the pure-jnp oracles in
``ref.py``; on TPU the same ``pallas_call`` graphs lower to Mosaic.
"""
from .ops import (cluster_distance_op, decode_attention_op,
                  flash_attention_op, moe_decode_op, moe_grouped_op,
                  ssm_scan_op)

__all__ = ["cluster_distance_op", "decode_attention_op",
           "flash_attention_op", "moe_decode_op", "moe_grouped_op",
           "ssm_scan_op"]
