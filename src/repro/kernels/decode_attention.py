"""Pallas TPU flash-decode kernel: one query token against a long KV cache.

Decode attention is memory-bound (roofline: reading the cache dominates), so
the kernel's job is to move each live KV tile from HBM to VMEM once, and no
other byte:

* the caches are the serving plane's stacked ``(L, B, S, Hkv, hd)`` arrays,
  read in place at the layer that arrives by scalar prefetch, so no slice,
  transpose or pad of the cache runs in front of the kernel;
* grid = (B, S/block_k): one step per (slot, tile) computes every query
  head of the slot against the tile, so the ``H // Hkv`` query heads of a
  kv head share one fetch; running (m, l, acc) for all heads stay in VMEM
  scratch across the tiles (innermost sequential axis);
* per-slot lengths arrive by scalar prefetch too: the KV ``index_map``
  clamps the tile index into the slot's live tiles (``kv_tile_span``), so
  the steps past a slot's length repeat a block index and start no DMA, and
  ``pl.when`` skips their compute.

Each position's ``(Hkv, hd)`` slab is one vreg tile at the serving widths
(8 x 128 f32), so a tile is read as a flat ``(block_k * Hkv, hd)`` matrix:
the query heads score every row of it in one matmul, and the rows of other
kv heads are masked out of each head's softmax.  The queries arrive as
one ``(B, H * hd)`` block, the layout the q projection writes: handed
``(B, H, hd)``, XLA moves that projection onto bf16 copies of its weight.

This single-token kernel is what the serving plane's decode step calls
for each layer.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
#: bytes of K (or V) one grid step brings into VMEM: 128 positions of
#: 8 x 128 f32, which on a TPU v5e ties 64 at short lengths and beats 64
#: (more steps) and 256 (more bytes past the length) elsewhere
TILE_BYTES = 512 * 1024


def kv_block_k(max_len: int, n_kv_heads: int, head_dim: int,
               itemsize: int = 4) -> int:
    """Cache positions per tile: the largest power of two whose K tile,
    with the head dim padded to the 128 lanes, fits ``TILE_BYTES``, and
    no more than the cache holds."""
    row = n_kv_heads * (-(-head_dim // 128) * 128) * itemsize
    bk = 8
    while bk * 2 * row <= TILE_BYTES:
        bk *= 2
    return min(bk, max_len)


def kv_tile_span(lengths, block_k: int, window: Optional[int] = None,
                 xp=jnp):
    """First and last cache tile holding a position a query over
    ``lengths`` positions attends (``lengths >= 1``).

    The kernel's KV ``index_map`` clamps its tile index into this span, and
    the serving plane counts the tiles read from it; ``xp`` is ``numpy``
    for host arrays."""
    last = xp.maximum(lengths - 1, 0) // block_k
    if window is None:
        return 0, last
    return xp.maximum(lengths - window, 0) // block_k, last


def kv_tiles_read(lengths, block_k: int,
                  window: Optional[int] = None) -> int:
    """Tiles of K (or of V) the kernel fetches for one layer, from the
    host's ``lengths`` as the kernel gets them."""
    first, last = kv_tile_span(np.asarray(lengths), block_k, window, np)
    return int(np.sum(last - first + 1))


def _decode_kernel(len_ref, layer_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *,
                   sm_scale: float, block_k: int, n_kv_heads: int,
                   window: Optional[int]):
    del layer_ref                                # used by the index_maps
    b = pl.program_id(0)
    ki = pl.program_id(1)
    length = len_ref[b]
    first, last = kv_tile_span(length, block_k, window)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when((ki >= first) & (ki <= last))
    def _tile():
        H, hd = acc_scr.shape
        rows = block_k * n_kv_heads            # row = position * Hkv + head
        q = q_ref[pl.ds(b, 1), :].reshape(H, hd)
        q = q.astype(jnp.float32) * sm_scale
        k = k_ref[0, 0].astype(jnp.float32).reshape(rows, hd)
        v = v_ref[0, 0].astype(jnp.float32).reshape(rows, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        # (H, rows): keep the rows of each query head's kv head at live
        # positions; a position p is live iff its rows r satisfy
        # p * Hkv <= r < (p + 1) * Hkv, so bounds on p scale by Hkv
        r = ki * rows + jax.lax.broadcasted_iota(jnp.int32, (H, rows), 1)
        head = jax.lax.broadcasted_iota(jnp.int32, (H, rows), 0)
        group = H // n_kv_heads
        kv_head = r % n_kv_heads
        mask = ((head >= kv_head * group) & (head < kv_head * group + group)
                & (r < length * n_kv_heads))
        if window is not None:
            mask &= r >= (length - window) * n_kv_heads
        s = jnp.where(mask, s, NEG_INF)
        # rows past the length may hold anything, NaN included: zero them
        # so a masked weight of 0 cannot meet them in the matmul
        r_v = ki * rows + jax.lax.broadcasted_iota(jnp.int32, (rows, hd), 0)
        v = jnp.where(r_v < length * n_kv_heads, v, 0.0)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == pl.num_programs(1) - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, lengths: jnp.ndarray,
                     layer: jnp.ndarray, *,
                     window: Optional[int] = None,
                     block_k: Optional[int] = None,
                     sm_scale: Optional[float] = None,
                     interpret: bool = False) -> jnp.ndarray:
    """q (B,H,hd); caches (L,B,S,Hkv,hd) read at ``layer``; lengths (B,)
    int32, each >= 1 -> (B,H,hd)."""
    B, H, hd = q.shape
    _, _, S, Hkv, _ = k_cache.shape
    if block_k is None:
        block_k = kv_block_k(S, Hkv, hd, k_cache.dtype.itemsize)
    block_k = min(block_k, S)
    if sm_scale is None:
        sm_scale = 1.0 / (hd ** 0.5)
    nk = pl.cdiv(S, block_k)

    def q_map(b, ki, lens, lay):
        return (0, 0)

    def out_map(b, ki, lens, lay):
        return (b, 0, 0)

    def kv_map(b, ki, lens, lay):
        first, last = kv_tile_span(lens[b], block_k, window)
        return (lay[0], b, jnp.minimum(jnp.maximum(ki, first), last), 0, 0)

    kernel = functools.partial(
        _decode_kernel, sm_scale=sm_scale, block_k=block_k, n_kv_heads=Hkv,
        window=window)
    kv_spec = pl.BlockSpec((1, 1, block_k, Hkv, hd), kv_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nk),
        in_specs=[pl.BlockSpec((B, H * hd), q_map), kv_spec, kv_spec],
        out_specs=pl.BlockSpec((1, H, hd), out_map),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name="decode_attention",
    )(lengths.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q.reshape(B, H * hd),
      k_cache, v_cache)
