"""Pallas TPU flash-attention kernel (forward).

Blocked online-softmax attention with explicit VMEM tiling:

* grid = (B·H, Sq/block_q, Skv/block_k); the last grid axis is innermost and
  sequential on TPU, so the (m, l, acc) running statistics live in VMEM
  scratch across kv iterations;
* GQA is native: the kv BlockSpec index_map divides the head index by the
  group size, so kv tiles are fetched once per kv head, never materialized
  at H width;
* causal + sliding-window masking via block position arithmetic; fully
  masked blocks still issue (TPU grids are static) but their contribution is
  masked to -inf — the block-skip optimization lives in the index-map-level
  choice of ``block_k`` relative to the window width;
* MXU alignment: block_q/block_k default to 128; head_dim is zero-padded to
  a multiple of 128 by the ops.py wrapper when needed.

Forward only: the kernel is the serving plane's prefill attention.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  sm_scale: float, block_q: int, block_k: int,
                  seq_q: int, seq_kv: int, causal: bool,
                  window: Optional[int]):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * sm_scale          # (bq, hd)
    k = k_ref[0].astype(jnp.float32)                     # (bk, hd)
    v = v_ref[0].astype(jnp.float32)                     # (bk, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bk)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
    mask = k_pos < seq_kv
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_new = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: Optional[int] = None,
                    block_q: int = 128, block_k: int = 128,
                    sm_scale: Optional[float] = None,
                    interpret: bool = False) -> jnp.ndarray:
    """q (B,Sq,H,hd); k/v (B,Skv,Hkv,hd) -> (B,Sq,H,hd).

    Requires Sq % block_q == 0 and hd already padded to the lane multiple
    (handled by ops.flash_attention_op, which also passes the pre-padding
    ``sm_scale``)."""
    B, Sq, H, hd = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    group = H // Hkv
    if sm_scale is None:
        sm_scale = 1.0 / (hd ** 0.5)
    # fold (B, H) into one grid axis; kv index maps divide by the GQA group
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv, hd)
    nk = -(-Skv // block_k)
    pad_k = nk * block_k - Skv
    if pad_k:
        kf = jnp.pad(kf, ((0, 0), (0, pad_k), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad_k), (0, 0)))
    grid = (B * H, Sq // block_q, nk)

    def q_map(b, qi, ki):
        return (b, qi, 0)

    def kv_map(b, qi, ki):
        bb = b // H
        hh = (b % H) // group
        return (bb * Hkv + hh, ki, 0)

    kernel = functools.partial(
        _flash_kernel, sm_scale=sm_scale, block_q=block_q, block_k=block_k,
        seq_q=Sq, seq_kv=Skv, causal=causal, window=window)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), q_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), q_map),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret, name="flash_attention",
    )(qf, kf, vf)
    return out.reshape(B, H, Sq, hd).transpose(0, 2, 1, 3)
