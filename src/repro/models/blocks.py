"""Transformer/SSM block assembly (pre-norm residual blocks).

Every block kind exposes a full-sequence form (train/prefill) returning
(x, cache_contrib, aux_loss) and a decode form returning (x, new_cache).
Blocks of one kind are stacked along a leading layer axis and driven by
``jax.lax.scan`` in ``model.py``.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .attention import (attn_layout, cross_attention, decode_cross_attention,
                        decode_self_attention, self_attention)
from .common import NO_SHARD, PSpec, ShardCtx, rms_norm
from .mlp import ffn, mlp_layout, moe_layout
from .ssm import (mamba1_decode, mamba1_forward, mamba1_layout, mamba2_decode,
                  mamba2_forward, mamba2_layout)

NO_WINDOW = 2 ** 30  # "global attention" sentinel for traced windows


def norm_spec(cfg: ModelConfig) -> PSpec:
    return PSpec((cfg.d_model,), (None,), init="ones")


# ---------------------------------------------------------------------------
# self-attention block (dense or MoE FFN)
# ---------------------------------------------------------------------------

def attn_block_layout(cfg: ModelConfig) -> Dict[str, Any]:
    return {
        "ln1": norm_spec(cfg),
        "attn": attn_layout(cfg),
        "ln2": norm_spec(cfg),
        "mlp": moe_layout(cfg) if cfg.moe is not None else mlp_layout(cfg),
    }


def residual_constrain(x, cfg: ModelConfig, ctx: ShardCtx):
    """Residual-stream layout between blocks: sequence-parallel (S over
    `model`) when cfg.seq_parallel — saved remat residuals shrink 16×."""
    if cfg.seq_parallel:
        return ctx.constrain(x, ctx.batch_axes(), "model", None)
    return ctx.constrain(x, ctx.batch_axes(), None, None)


def attn_block(p, x, cfg: ModelConfig, *, window=None, causal=True,
               positions=None, ctx: ShardCtx = NO_SHARD):
    h, kv = self_attention(p["attn"], rms_norm(x, p["ln1"], cfg.norm_eps),
                           cfg, window=window, causal=causal,
                           positions=positions, ctx=ctx)
    x = x + h
    x = residual_constrain(x, cfg, ctx)
    y, aux = ffn(p["mlp"], rms_norm(x, p["ln2"], cfg.norm_eps), cfg, ctx)
    return residual_constrain(x + y, cfg, ctx), kv, aux


def attn_block_decode(p, x, cache_k, cache_v, cur_len, cfg: ModelConfig, *,
                      window=None, ctx: ShardCtx = NO_SHARD):
    h, ck, cv = decode_self_attention(
        p["attn"], rms_norm(x, p["ln1"], cfg.norm_eps), cache_k, cache_v,
        cur_len, cfg, window=window, ctx=ctx)
    x = x + h
    y, _ = ffn(p["mlp"], rms_norm(x, p["ln2"], cfg.norm_eps), cfg, ctx)
    return x + y, ck, cv


# ---------------------------------------------------------------------------
# cross-attention block (VLM image layers; own MLP like llama-3.2 vision)
# ---------------------------------------------------------------------------

def cross_block_layout(cfg: ModelConfig) -> Dict[str, Any]:
    return {
        "ln1": norm_spec(cfg),
        "attn": attn_layout(cfg, cross=True),
        "ln2": norm_spec(cfg),
        "mlp": mlp_layout(cfg),
        "gate": PSpec((1,), (None,), init="zeros"),  # tanh-gated residual
    }


def cross_block(p, x, memory, cfg: ModelConfig, *, ctx: ShardCtx = NO_SHARD):
    h, kv = cross_attention(p["attn"], rms_norm(x, p["ln1"], cfg.norm_eps),
                            memory, cfg, ctx=ctx)
    x = x + jnp.tanh(p["gate"].astype(h.dtype)) * h
    from .mlp import swiglu
    y = swiglu(p["mlp"], rms_norm(x, p["ln2"], cfg.norm_eps), ctx)
    return x + y, kv


def cross_block_decode(p, x, mem_k, mem_v, cfg: ModelConfig, *,
                       ctx: ShardCtx = NO_SHARD):
    h = decode_cross_attention(p["attn"],
                               rms_norm(x, p["ln1"], cfg.norm_eps),
                               mem_k, mem_v, cfg, ctx=ctx)
    x = x + jnp.tanh(p["gate"].astype(h.dtype)) * h
    from .mlp import swiglu
    y = swiglu(p["mlp"], rms_norm(x, p["ln2"], cfg.norm_eps), ctx)
    return x + y


# ---------------------------------------------------------------------------
# SSM blocks
# ---------------------------------------------------------------------------

def ssm_block_layout(cfg: ModelConfig) -> Dict[str, Any]:
    inner = mamba1_layout(cfg) if cfg.ssm.version == 1 else mamba2_layout(cfg)
    return {"ln": norm_spec(cfg), "m": inner}


def ssm_block(p, x, cfg: ModelConfig, *, ctx: ShardCtx = NO_SHARD,
              h0=None):
    fwd = mamba1_forward if cfg.ssm.version == 1 else mamba2_forward
    y, cache = fwd(p["m"], rms_norm(x, p["ln"], cfg.norm_eps), cfg, ctx=ctx,
                   h0=h0)
    return residual_constrain(x + y, cfg, ctx), cache


def ssm_block_decode(p, x, cache, cfg: ModelConfig, *,
                     ctx: ShardCtx = NO_SHARD):
    dec = mamba1_decode if cfg.ssm.version == 1 else mamba2_decode
    y, cache = dec(p["m"], rms_norm(x, p["ln"], cfg.norm_eps), cache, cfg,
                   ctx=ctx)
    return x + y, cache


# ---------------------------------------------------------------------------
# whisper-style decoder block: self + cross + mlp
# ---------------------------------------------------------------------------

def decoder_block_layout(cfg: ModelConfig) -> Dict[str, Any]:
    return {
        "ln1": norm_spec(cfg),
        "self": attn_layout(cfg),
        "ln2": norm_spec(cfg),
        "cross": attn_layout(cfg, cross=True),
        "ln3": norm_spec(cfg),
        "mlp": mlp_layout(cfg),
    }


def decoder_block(p, x, memory, cfg: ModelConfig, *,
                  ctx: ShardCtx = NO_SHARD):
    h, kv_self = self_attention(p["self"],
                                rms_norm(x, p["ln1"], cfg.norm_eps), cfg,
                                causal=True, ctx=ctx)
    x = x + h
    h, kv_cross = cross_attention(p["cross"],
                                  rms_norm(x, p["ln2"], cfg.norm_eps),
                                  memory, cfg, ctx=ctx)
    x = x + h
    from .mlp import swiglu
    y = swiglu(p["mlp"], rms_norm(x, p["ln3"], cfg.norm_eps), ctx)
    return x + y, kv_self, kv_cross


def decoder_block_decode(p, x, cache_k, cache_v, mem_k, mem_v, cur_len,
                         cfg: ModelConfig, *, ctx: ShardCtx = NO_SHARD):
    h, ck, cv = decode_self_attention(
        p["self"], rms_norm(x, p["ln1"], cfg.norm_eps), cache_k, cache_v,
        cur_len, cfg, ctx=ctx)
    x = x + h
    h = decode_cross_attention(p["cross"],
                               rms_norm(x, p["ln2"], cfg.norm_eps),
                               mem_k, mem_v, cfg, ctx=ctx)
    x = x + h
    from .mlp import swiglu
    y = swiglu(p["mlp"], rms_norm(x, p["ln3"], cfg.norm_eps), ctx)
    return x + y, ck, cv
