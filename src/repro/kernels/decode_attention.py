"""Pallas TPU flash-decode kernel: one query token against a long KV cache.

Decode attention is memory-bound (roofline: reading the cache dominates), so
the kernel's job is to stream KV tiles through VMEM exactly once at full HBM
bandwidth while keeping the online-softmax state in registers/VMEM:

* grid = (B·H, S/block_k); running (m, l, acc) in VMEM scratch across cache
  tiles (innermost sequential axis);
* per-sequence valid lengths arrive via scalar-prefetch SMEM so masking
  costs no HBM traffic;
* GQA via the kv index_map (cache tiles fetched once per kv head).

This single-token kernel is the unit the serving engine calls per decode
step; the sequence-sharded (model-axis) distribution around it performs the
cross-chip partial-softmax combine (see launch/sharding.cache_pspecs).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *,
                   sm_scale: float, block_k: int, n_heads: int,
                   window: Optional[int]):
    bh = pl.program_id(0)
    ki = pl.program_id(1)
    nk = pl.num_programs(1)
    b = bh // n_heads

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * sm_scale          # (1, hd)
    k = k_ref[0].astype(jnp.float32)                     # (bk, hd)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (1, bk)
    length = len_ref[b]
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k),
                                                    1)
    mask = k_pos < length
    if window is not None:
        mask &= k_pos > length - 1 - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, lengths: jnp.ndarray, *,
                     window: Optional[int] = None, block_k: int = 128,
                     sm_scale: Optional[float] = None,
                     interpret: bool = False) -> jnp.ndarray:
    """q (B,H,hd); caches (B,S,Hkv,hd); lengths (B,) int32 -> (B,H,hd)."""
    B, H, hd = q.shape
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    group = H // Hkv
    if sm_scale is None:
        sm_scale = 1.0 / (hd ** 0.5)
    qf = q.reshape(B * H, 1, hd)
    kf = k_cache.transpose(0, 2, 1, 3).reshape(B * Hkv, S, hd)
    vf = v_cache.transpose(0, 2, 1, 3).reshape(B * Hkv, S, hd)
    nk = -(-S // block_k)
    pad = nk * block_k - S
    if pad:
        kf = jnp.pad(kf, ((0, 0), (0, pad), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad), (0, 0)))

    def q_map(b, ki, lens):
        return (b, 0, 0)

    def kv_map(b, ki, lens):
        bb = b // H
        hh = (b % H) // group
        return (bb * Hkv + hh, ki, 0)

    kernel = functools.partial(
        _decode_kernel, sm_scale=sm_scale, block_k=block_k, n_heads=H,
        window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * H, nk),
        in_specs=[
            pl.BlockSpec((1, 1, hd), q_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, hd), q_map),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * H, 1, hd), q.dtype),
        interpret=interpret, name="decode_attention",
    )(lengths.astype(jnp.int32), qf, kf, vf)
    return out.reshape(B, H, hd)
