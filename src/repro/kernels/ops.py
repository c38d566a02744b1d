"""jit'd wrappers around the Pallas kernels (padding, interpret).

These are the public entry points: they handle TPU lane-alignment padding
(head dims to multiples of 128) and expose an ``interpret=`` switch so the
same code paths run on CPU for validation.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import gmm as _gmm

from . import cluster_distance as _cd
from . import decode_attention as _dec
from . import flash_attention as _fa
from . import moe_decode as _moe_dec
from . import ssm_scan as _ssm

LANE = 128
SUBLANE = 8


def pallas_interpret() -> bool:
    """Whether Pallas kernels run in interpret mode on the default backend.

    ``False`` on a TPU (compiled Mosaic kernels), ``True`` on the CPU (the
    interpreter the tests use).  Any other platform raises: a kernel never
    drops silently to the interpreter.  Ask when a pellet or flow is
    built, never at import — the question initialises JAX's backend.
    """
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on 'tpu' or interpreted on 'cpu'; "
        f"the default JAX backend is {platform!r}")


def _pad_last(x: jnp.ndarray, mult: int = LANE) -> Tuple[jnp.ndarray, int]:
    d = x.shape[-1]
    pad = (-d) % mult
    if pad:
        widths = [(0, 0)] * (x.ndim - 1) + [(0, pad)]
        x = jnp.pad(x, widths)
    return x, d


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention_op(q, k, v, *, causal: bool = True,
                       window: Optional[int] = None,
                       block_q: Optional[int] = None,
                       block_k: Optional[int] = None,
                       interpret: bool = False) -> jnp.ndarray:
    """Padded/aligned flash attention: q (B,S,H,hd), kv (B,S,Hkv,hd).
    Block sizes not given follow ``flash_attention.block_sizes``."""
    B, Sq, H, hd = q.shape
    scale = 1.0 / (hd ** 0.5)
    qp, _ = _pad_last(q)
    kp, _ = _pad_last(k)
    vp, _ = _pad_last(v)
    bq, bk = _fa.block_sizes(Sq, kp.shape[1], block_q, block_k)
    pad_q = (-Sq) % bq
    if pad_q:
        qp = jnp.pad(qp, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    out = _fa.flash_attention(qp, kp, vp, causal=causal, window=window,
                              block_q=bq, block_k=bk, sm_scale=scale,
                              interpret=interpret)
    return out[:, :Sq, :, :hd]


@functools.partial(jax.jit, static_argnames=("window", "block_k",
                                             "interpret"))
def decode_attention_op(q, k_cache, v_cache, lengths, layer=0, *,
                        window: Optional[int] = None,
                        block_k: Optional[int] = None,
                        interpret: bool = False) -> jnp.ndarray:
    """Padded flash-decode: q (B,H,hd); caches (L,B,S,Hkv,hd), read in
    place at ``layer``, or one layer's (B,S,Hkv,hd); lengths (B,), each
    >= 1.  ``block_k`` defaults to ``decode_attention.kv_block_k``."""
    B, H, hd = q.shape
    if k_cache.ndim == 4:
        k_cache, v_cache = k_cache[None], v_cache[None]
    scale = 1.0 / (hd ** 0.5)
    qp, _ = _pad_last(q)
    kp, _ = _pad_last(k_cache)
    vp, _ = _pad_last(v_cache)
    out = _dec.decode_attention(qp, kp, vp, lengths, layer, window=window,
                                block_k=block_k, sm_scale=scale,
                                interpret=interpret)
    return out[:, :, :hd]


@functools.partial(jax.jit, static_argnames=("interpret",))
def moe_decode_op(x, expert_ids, weights, live, w_gate, w_up, w_down,
                  layer=0, *, interpret: bool = False):
    """Dropless decode experts: x (B, D); the top-k ``expert_ids`` and
    renormalised ``weights`` (B, k); ``live`` (B,) bool; stacked experts
    w_gate/w_up (L, E, D, F), w_down (L, E, F, D), read in place at
    ``layer`` -> (y (B, D), experts fetched ())."""
    return _moe_dec.moe_decode(x, expert_ids, weights, live, w_gate, w_up,
                               w_down, layer, interpret=interpret)


def _gmm_block(dim: int, most: int) -> int:
    """The widest multiple of 128 up to ``most`` that divides ``dim``,
    else the whole of ``dim``."""
    for b in range(most, LANE - 1, -LANE):
        if dim % b == 0:
            return b
    return dim


@functools.partial(jax.jit, static_argnames=("interpret",))
def moe_grouped_op(x, expert_ids, weights, w_gate, w_up, w_down, layer=0,
                   *, interpret: bool = False):
    """Dropless experts over many tokens (prefill): x (T, D); top-k
    ``expert_ids``/``weights`` (T, k); stacked experts as in
    ``moe_decode_op``, at ``layer``.

    The T*k picks are sorted by expert and run as grouped matmuls (the
    megablox ``gmm`` Pallas kernel) over the layer's experts, addressed in
    the stacked ``(L*E, ...)`` arrays through zero-sized groups for the
    other layers, so no layer's experts are sliced out.  Every pick is
    computed; each token's rows are gathered back and summed with its
    weights."""
    T, D = x.shape
    k = expert_ids.shape[1]
    L, E, _, F = w_gate.shape
    flat = expert_ids.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    rows = T * k
    tm = 256 if rows >= 256 else rows + (-rows) % SUBLANE
    xs = x[order // k]                                    # (T*k, D) sorted
    xs = jnp.pad(xs, ((0, (-rows) % tm), (0, 0)))
    sizes = jnp.zeros((L, E), jnp.int32).at[layer].set(
        jnp.bincount(flat, length=E).astype(jnp.int32)).reshape(L * E)
    up_tiles = (tm, _gmm_block(D, 768), _gmm_block(F, 896))
    down_tiles = (tm, _gmm_block(F, 896), _gmm_block(D, 768))
    g = _gmm(xs, w_gate.reshape(L * E, D, F), sizes, tiling=up_tiles,
            interpret=interpret)
    u = _gmm(xs, w_up.reshape(L * E, D, F), sizes, tiling=up_tiles,
            interpret=interpret)
    y = _gmm(jax.nn.silu(g) * u, w_down.reshape(L * E, F, D), sizes,
            tiling=down_tiles, interpret=interpret)
    # rows past the picks were never written: only sorted rows are read
    back = y[jnp.argsort(order)].reshape(T, k, D)
    return jnp.sum(back * weights[..., None].astype(jnp.float32), axis=1)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def ssm_scan_op(x, dt, A, B_, C_, h0=None, *, block_d: int = 128,
                interpret: bool = False):
    """Selective scan: x/dt (B,S,di), A (di,N), B_/C_ (B,S,N)."""
    di = x.shape[-1]
    bd = min(block_d, di)
    while di % bd:
        bd //= 2
    return _ssm.ssm_scan(x, dt, A, B_, C_, h0, block_d=bd,
                         interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def cluster_distance_op(x, centroids, *, block_b: int = 128,
                        interpret: bool = False) -> jnp.ndarray:
    """Padded batched point-to-centroid squared L2: (B,D) × (K,D) -> (B,K).

    The streaming-clustering distance stage: with the engine's array fast
    path a whole ArrayBatch of posts is scored against every centroid in
    ONE kernel launch.  Feature dim is padded to the lane width (zero
    features are distance-neutral), centroid count to the sublane width
    (padded centroids sliced off), batch to the block size.
    """
    x = jnp.asarray(x, jnp.float32)
    c = jnp.asarray(centroids, jnp.float32)
    B, _ = x.shape
    K, _ = c.shape
    xp, _ = _pad_last(x)
    cp, _ = _pad_last(c)
    pad_k = (-K) % SUBLANE
    if pad_k:
        cp = jnp.pad(cp, ((0, pad_k), (0, 0)))
    # batch tile must itself be sublane-aligned (f32 tiles are 8x128),
    # so round the block up and pad B to a multiple of it
    bb = min(block_b, B + (-B) % SUBLANE)
    bb = bb + (-bb) % SUBLANE
    pad_b = (-B) % bb
    if pad_b:
        xp = jnp.pad(xp, ((0, pad_b), (0, 0)))
    out = _cd.cluster_distances(xp, cp, block_b=bb, interpret=interpret)
    return out[:B, :K]
