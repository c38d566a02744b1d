"""Operations and bytes of the routed-expert LM with windowed and full
attention layers, counted from shapes, as ``work.py`` counts the dense LM.

A windowed layer's query at position ``p`` attends the ``min(p + 1, W)``
positions ending at itself; a full layer every position up to itself.  An
expert layer's token runs the router (``D x E``) and its ``top_k`` experts,
three ``D x F`` matrices each.  The decode expert kernel's bytes are the
weights of the experts it fetched, counted by the program
(``floe_moe_expert_fetches_total``): each distinct expert once a step.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import ml_dtypes  # noqa: F401  (names bfloat16 for numpy)
import numpy as np


@dataclasses.dataclass(frozen=True)
class MoEDims:
    """The served expert LM's sizes, from a configuration file."""

    vocab: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    n_layers: int
    n_experts: int
    top_k: int
    expert_width: int
    windows: Tuple[Optional[int], ...]
    itemsize: int = 4        # bytes of one weight or cache element

    @classmethod
    def from_config(cls, cfg: Dict[str, Any]) -> "MoEDims":
        """Sizes as published; each held layer's window from the first
        ``num_hidden_layers`` entries of ``layer_types``, the bytes of an
        element from ``torch_dtype``.  The top-k weights are renormalised
        (``norm_topk_prob``), as the program's expert layer does."""
        L = int(cfg["num_hidden_layers"])
        if any(t != "sparse" for t in cfg["mlp_layer_types"][:L]):
            raise ValueError("every held layer must be an expert layer")
        if not cfg["norm_topk_prob"]:
            raise ValueError("the served expert layer renormalises its "
                             "top-k weights (norm_topk_prob)")
        win = int(cfg["sliding_window"])
        windows = tuple(win if t == "sliding_attention" else None
                        for t in cfg["layer_types"][:L])
        return cls(vocab=int(cfg["vocab_size"]),
                   d_model=int(cfg["hidden_size"]),
                   n_heads=int(cfg["num_attention_heads"]),
                   n_kv_heads=int(cfg["num_key_value_heads"]),
                   head_dim=int(cfg["head_dim"]), n_layers=L,
                   n_experts=int(cfg["num_experts"]),
                   top_k=int(cfg["num_experts_per_tok"]),
                   expert_width=int(cfg["moe_intermediate_size"]),
                   windows=windows,
                   itemsize=np.dtype(cfg["torch_dtype"]).itemsize)


def attended(window: Optional[int], position: int) -> int:
    """Positions a query at 0-based ``position`` attends in a layer."""
    return position + 1 if window is None else min(position + 1, window)


def causal_pairs(window: Optional[int], length: int) -> int:
    """(query, key) pairs of causal attention over ``length`` tokens, in
    a layer with ``window`` (None: full)."""
    if window is None or window >= length:
        return length * (length + 1) // 2
    return window * (window + 1) // 2 + (length - window) * window


def expert_bytes(d: MoEDims) -> int:
    """One expert's gate, up and down matrices."""
    return 3 * d.d_model * d.expert_width * d.itemsize


def layer_matmul_flops(d: MoEDims) -> int:
    """Weight-matmul operations of one token through one layer: the
    attention projections, the router and its ``top_k`` experts."""
    q = d.n_heads * d.head_dim
    kv = d.n_kv_heads * d.head_dim
    attn = d.d_model * q + 2 * d.d_model * kv + q * d.d_model
    experts = d.top_k * 3 * d.d_model * d.expert_width
    return 2 * (attn + d.d_model * d.n_experts + experts)


def head_flops(d: MoEDims) -> int:
    return 2 * d.d_model * d.vocab


def decode_token_flops(d: MoEDims, positions: int) -> int:
    """One decoded token whose query sits at ``positions`` cache positions
    (its own the last), through every layer and the head."""
    hq = d.n_heads * d.head_dim
    return sum(layer_matmul_flops(d) + 4 * hq * attended(w, positions - 1)
               for w in d.windows) + head_flops(d)


def prefill_flops(d: MoEDims, length: int) -> int:
    """A prompt of ``length`` real tokens: every layer over every token,
    causal (windowed) attention, the head at the last position alone."""
    hq = d.n_heads * d.head_dim
    return sum(length * layer_matmul_flops(d)
               + 4 * hq * causal_pairs(w, length)
               for w in d.windows) + head_flops(d)


def decode_attention_work(d: MoEDims, positions: int) -> Dict[str, int]:
    """One slot's query at ``positions`` cache positions, summed over the
    layers: read the query, the keys and values it attends, write the
    output."""
    hq = d.n_heads * d.head_dim
    hkv = d.n_kv_heads * d.head_dim
    flops = bytes_ = 0
    for w in d.windows:
        n = attended(w, positions - 1)
        flops += 4 * hq * n
        bytes_ += d.itemsize * (2 * n * hkv + 2 * hq)
    return {"flops": flops, "bytes": bytes_}


def flash_attention_work(d: MoEDims, length: int) -> Dict[str, int]:
    """Causal (windowed) self-attention over ``length`` real tokens, summed
    over the layers: read q, k and v, write the output."""
    hq = d.n_heads * d.head_dim
    hkv = d.n_kv_heads * d.head_dim
    return {"flops": sum(4 * hq * causal_pairs(w, length)
                         for w in d.windows),
            "bytes": d.n_layers * d.itemsize * length * (2 * hq + 2 * hkv)}


def moe_decode_work(d: MoEDims, fetches: int, tokens: int
                    ) -> Dict[str, int]:
    """The decode expert kernel over a window: ``fetches`` distinct
    (layer, expert) pairs read, each once a step; ``tokens`` decoded
    tokens, each through ``top_k`` experts in every layer."""
    return {"flops": tokens * d.n_layers * d.top_k * 6 * d.d_model
            * d.expert_width,
            "bytes": fetches * expert_bytes(d)}
