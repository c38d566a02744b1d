"""Pallas TPU flash-attention kernel (forward).

Blocked online-softmax attention with explicit VMEM tiling:

* the grid visits only the band: the ``(q block, kv block)`` pairs that
  hold at least one ``(query, key)`` pair the causal, window and
  ``k_pos < seq_kv`` masks leave open (``band``).  A pair outside the band
  is neither fetched nor computed, and is no grid step.  The band is a
  static function of the shapes, built at trace time and flattened into
  one grid axis; the pairs' ``(q block, kv block)`` indices and their
  flags reach the index maps and the kernel by scalar prefetch;
* grid = (B·H, pairs in the band); a query block's kv blocks are
  consecutive and the pair axis is innermost and sequential on TPU, so the
  (m, l, acc) running statistics live in VMEM scratch across them: a query
  block's first pair initialises them, its last writes the output;
* only the band's edge blocks build the position mask; a pair whose every
  ``(query, key)`` is open (the band's interior) skips it;
* GQA is native: the kv BlockSpec index_map divides the head index by the
  group size, so kv tiles are fetched once per kv head, never materialized
  at H width;
* block sizes follow the shape (``block_sizes``): larger blocks mean fewer
  grid steps, each with a fixed cost, against more masked positions on the
  band's edges; head_dim is zero-padded to a multiple of 128 by the ops.py
  wrapper when needed.

Forward only: the kernel is the serving plane's prefill attention.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
#: a band pair's flags: the first and the last pair of its query block, and
#: an edge pair, where some ``(query, key)`` is masked
FIRST, LAST, EDGE = 1, 2, 4
#: positions of queries, and of keys, a grid step takes unless the caller
#: says otherwise
BLOCK = 512


def block_sizes(seq_q: int, seq_kv: int, block_q: Optional[int] = None,
                block_k: Optional[int] = None) -> Tuple[int, int]:
    """The ``(block_q, block_k)`` the kernel runs at for these shapes.

    Explicit sizes win; otherwise ``BLOCK``.  Each is cut to the sequence
    it tiles (a query block to no less than 8 rows)."""
    block_q = BLOCK if block_q is None else block_q
    block_k = BLOCK if block_k is None else block_k
    return min(block_q, max(8, seq_q)), min(block_k, seq_kv)


def band(seq_q: int, seq_kv: int, block_q: int, block_k: int,
         causal: bool = True, window: Optional[int] = None) -> np.ndarray:
    """The kernel's grid: ``(n, 3)`` int32 rows of (q block, kv block,
    flags), query block by query block, kv blocks in order within each.

    A pair is in the band when some query ``q`` of its q block and key
    ``k < seq_kv`` of its kv block are open: ``k <= q`` if ``causal``, and
    ``q - k < window`` if a window is given.  The differences ``q - k`` of
    a pair form one run of integers, so its ends decide.  A query block
    with no open pair (only past ``seq_kv``) keeps its first kv block, so
    its output is written."""
    nq, nk = -(-seq_q // block_q), -(-seq_kv // block_k)
    qi, ki = np.meshgrid(np.arange(nq), np.arange(nk), indexing="ij")
    q0, q1 = qi * block_q, qi * block_q + block_q - 1
    k0 = ki * block_k
    k1 = np.minimum(k0 + block_k, seq_kv) - 1
    # least and greatest q - k over the pair's open keys
    lo, hi = q0 - k1, q1 - k0
    inside = np.ones((nq, nk), bool)
    full = k0 + block_k <= seq_kv
    if causal:
        inside &= hi >= 0
        full &= lo >= 0
    if window is not None:
        inside &= lo < window
        full &= hi < window
    inside[~inside.any(axis=1), 0] = True
    rows = []
    for q in range(nq):
        ks = np.nonzero(inside[q])[0]
        for j, k in enumerate(ks):
            flags = ((FIRST if j == 0 else 0) |
                     (LAST if j == len(ks) - 1 else 0) |
                     (0 if full[q, k] else EDGE))
            rows.append((q, k, flags))
    return np.asarray(rows, np.int32).reshape(-1, 3)


@functools.lru_cache(maxsize=None)
def band_blocks(seq_q: int, seq_kv: int, block_q: int, block_k: int,
                causal: bool = True,
                window: Optional[int] = None) -> Tuple[int, int]:
    """(block pairs the kernel visits, block pairs of the full grid), for
    one batch row and head."""
    nq, nk = -(-seq_q // block_q), -(-seq_kv // block_k)
    return len(band(seq_q, seq_kv, block_q, block_k, causal, window)), \
        nq * nk


def _flash_kernel(qs_ref, ks_ref, flags_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *,
                  sm_scale: float, block_q: int, block_k: int,
                  seq_kv: int, causal: bool, window: Optional[int]):
    i = pl.program_id(1)
    flags = flags_ref[i]

    @pl.when((flags & FIRST) != 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * sm_scale          # (bq, hd)
    k = k_ref[0].astype(jnp.float32)                     # (bk, hd)
    v = v_ref[0].astype(jnp.float32)                     # (bk, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bk)

    def update(s):
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

    @pl.when((flags & EDGE) != 0)
    def _edge():
        q_pos = qs_ref[i] * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = ks_ref[i] * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = k_pos < seq_kv
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        update(jnp.where(mask, s, NEG_INF))

    @pl.when((flags & EDGE) == 0)
    def _interior():
        update(s)

    @pl.when((flags & LAST) != 0)
    def _finish():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: Optional[int] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    sm_scale: Optional[float] = None,
                    interpret: bool = False) -> jnp.ndarray:
    """q (B,Sq,H,hd); k/v (B,Skv,Hkv,hd) -> (B,Sq,H,hd).

    Requires Sq % block_q == 0 and hd already padded to the lane multiple
    (handled by ops.flash_attention_op, which also passes the pre-padding
    ``sm_scale``).  Block sizes not given follow ``block_sizes``."""
    B, Sq, H, hd = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    group = H // Hkv
    block_q, block_k = block_sizes(Sq, Skv, block_q, block_k)
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
    pairs = band(Sq, Skv, block_q, block_k, causal, window)

    def q_map(b, i, qs, ks, flags):
        return (b, qs[i], 0)

    def kv_map(b, i, qs, ks, flags):
        bb = b // H
        hh = (b % H) // group
        return (bb * Hkv + hh, ks[i], 0)

    kernel = functools.partial(
        _flash_kernel, sm_scale=sm_scale, block_q=block_q, block_k=block_k,
        seq_kv=Skv, causal=causal, window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B * H, len(pairs)),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), q_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), q_map),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, hd), q.dtype),
        interpret=interpret, name="flash_attention",
    )(*(jnp.asarray(col) for col in pairs.T), qf, kf, vf)
    return out.reshape(B, H, Sq, hd).transpose(0, 2, 1, 3)
