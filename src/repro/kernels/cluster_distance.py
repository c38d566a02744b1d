"""Pallas TPU kernel: batched point-to-centroid squared L2 distances.

The stream-clustering case study (paper §IV.B) assigns each post to its
nearest cluster centroid.  With the engine's array fast path a whole
micro-batch of posts arrives at the distance stage as ONE stacked array
(B, D); this kernel computes the full (B, K) distance matrix in a single
device call — the MXU does the cross term as a matmul, the VPU the norms —
instead of B per-message norm loops.

``dist(i, j) = |x_i|^2 + |c_j|^2 - 2 * x_i . c_j``

Tiled over the batch dimension: each grid step streams one (block_b, D)
tile of points through VMEM against the full (K, D) centroid block (K is
small — cluster counts, not vocabulary sizes).  Callers pad D to the lane
width and K to the sublane width (zeros are distance-neutral in the cross
term and padded centroids are sliced off); see ``ops.cluster_distance_op``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _pdist_kernel(x_ref, c_ref, out_ref):
    x = x_ref[:].astype(jnp.float32)                      # (block_b, D)
    c = c_ref[:].astype(jnp.float32)                      # (K, D)
    xx = jnp.sum(x * x, axis=1, keepdims=True)            # (block_b, 1)
    cc = jnp.sum(c * c, axis=1)[None, :]                  # (1, K)
    xc = jax.lax.dot_general(x, c, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    out_ref[:] = (xx + cc - 2.0 * xc).astype(out_ref.dtype)


def cluster_distances(x: jnp.ndarray, centroids: jnp.ndarray, *,
                      block_b: int = 128,
                      interpret: bool = False) -> jnp.ndarray:
    """Squared L2 distances: x (B, D) × centroids (K, D) -> (B, K).

    B must be a multiple of ``block_b`` (callers pad); D should be
    lane-aligned and K sublane-aligned for TPU layouts — the public
    ``ops.cluster_distance_op`` wrapper handles all padding.
    """
    B, D = x.shape
    K, Dc = centroids.shape
    assert D == Dc, (D, Dc)
    block_b = min(block_b, B)
    assert B % block_b == 0, (B, block_b)
    return pl.pallas_call(
        _pdist_kernel,
        grid=(B // block_b,),
        in_specs=[pl.BlockSpec((block_b, D), lambda i: (i, 0)),
                  pl.BlockSpec((K, D), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((block_b, K), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, K), jnp.float32),
        interpret=interpret, name="cluster_distance",
    )(x, centroids)
