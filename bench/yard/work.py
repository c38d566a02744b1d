"""Operations and bytes that the algorithm needs, counted from shapes.

These count what the mathematics asks for (live cache positions, real
prompt tokens), never what one implementation happens
to read or pad, so the counts stay valid when a later change rewrites a
kernel.  A multiply-add is two operations.  Elementwise work (norms,
softmax, activations) is left out of the operation counts; it is a small
fraction of every count here and would tie the count to one formulation.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import ml_dtypes  # noqa: F401  (names bfloat16 for numpy)
import numpy as np


@dataclasses.dataclass(frozen=True)
class LMDims:
    """The served LM's sizes, from a configuration file."""

    vocab: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    n_layers: int
    d_ff: int
    n_ffn_mats: int = 2      # 2: ungated FFN (up, down); 3: gated
    itemsize: int = 4        # bytes of one cache element

    @classmethod
    def from_config(cls, cfg: Dict[str, Any]) -> "LMDims":
        """Sizes as published; the FFN's matrices from whether the
        configuration departs from the source's gated FFN, the bytes of
        a cache element from ``torch_dtype``."""
        return cls(vocab=int(cfg["vocab_size"]),
                   d_model=int(cfg["hidden_size"]),
                   n_heads=int(cfg["num_attention_heads"]),
                   n_kv_heads=int(cfg["num_key_value_heads"]),
                   head_dim=int(cfg["head_dim"]),
                   n_layers=int(cfg["num_hidden_layers"]),
                   d_ff=int(cfg["intermediate_size"]),
                   n_ffn_mats=2 if "ungated_ffn" in cfg["departures"]
                   else 3,
                   itemsize=np.dtype(cfg["torch_dtype"]).itemsize)


def layer_matmul_flops(d: LMDims) -> int:
    """Weight-matmul operations of one token through one layer."""
    q = d.n_heads * d.head_dim
    kv = d.n_kv_heads * d.head_dim
    return 2 * (d.d_model * q + 2 * d.d_model * kv + q * d.d_model
                + d.n_ffn_mats * d.d_model * d.d_ff)


def head_flops(d: LMDims) -> int:
    """The output head over one position."""
    return 2 * d.d_model * d.vocab


def decode_token_flops(d: LMDims, positions: int) -> int:
    """One decoded token that attends over ``positions`` cache positions
    (its own included), through every layer and the head."""
    attn = 4 * d.n_heads * d.head_dim * positions
    return d.n_layers * (layer_matmul_flops(d) + attn) + head_flops(d)


def prefill_flops(d: LMDims, length: int) -> int:
    """A prompt of ``length`` real tokens: every layer over every token,
    causal attention, and the head over the last position alone (the only
    logits prefill returns)."""
    attn = 4 * d.n_heads * d.head_dim * length * (length + 1) // 2
    return d.n_layers * (length * layer_matmul_flops(d) + attn) \
        + head_flops(d)


def decode_attention_work(d: LMDims, positions: int) -> Dict[str, int]:
    """One slot's query against ``positions`` cache positions, one layer:
    read the query, the keys and values of every live position, write the
    output."""
    hq = d.n_heads * d.head_dim
    return {"flops": 4 * hq * positions,
            "bytes": d.itemsize * (2 * positions * d.n_kv_heads * d.head_dim
                                   + 2 * hq)}


def flash_attention_work(d: LMDims, length: int) -> Dict[str, int]:
    """Causal self-attention over ``length`` real tokens, one layer: read
    q, k and v, write the output."""
    hq = d.n_heads * d.head_dim
    hkv = d.n_kv_heads * d.head_dim
    return {"flops": 4 * hq * length * (length + 1) // 2,
            "bytes": d.itemsize * length * (2 * hq + 2 * hkv)}


def roofline_seconds(work: Dict[str, float], peak: Dict[str, float]) -> float:
    """The least time the chip could take: the larger of operations over
    peak operations per second and bytes over peak bandwidth.  Every call
    counted here lies on the memory side of the chip's ridge, so a sum
    over calls of this bound equals the bound of the sums."""
    return max(work["flops"] / peak["flops_per_s"],
               work["bytes"] / peak["hbm_bytes_per_s"])
