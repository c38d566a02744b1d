"""Pure-jnp oracles for every Pallas kernel in this package.

These define the semantics; the kernels must ``allclose`` against them for
every shape/dtype in the test sweeps (kernels run with ``interpret=True`` on
CPU).  The serving LM's ref twins (``serving/kv.py`` ``prefill_ref``,
``decode_step_ref``) compute through them, so the kernels are validated
against exactly what the served model computes.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# flash attention (full-sequence, causal / sliding-window, GQA)
# ---------------------------------------------------------------------------

def attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
              causal: bool = True,
              window: Optional[int] = None) -> jnp.ndarray:
    """q (B,Sq,H,hd); k/v (B,Skv,Hkv,hd) with H % Hkv == 0 -> (B,Sq,H,hd)."""
    B, Sq, H, hd = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    g = H // Hkv
    qf = q.astype(jnp.float32) / jnp.sqrt(jnp.float32(hd))
    qg = qf.reshape(B, Sq, Hkv, g, hd)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k.astype(jnp.float32))
    q_pos = jnp.arange(Sq)[:, None]
    k_pos = jnp.arange(Skv)[None, :]
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# decode attention (one query against a KV cache of given lengths)
# ---------------------------------------------------------------------------

def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, lengths: jnp.ndarray, *,
                     window: Optional[int] = None) -> jnp.ndarray:
    """q (B,H,hd); caches (B,S,Hkv,hd); lengths (B,) -> (B,H,hd).

    Attends over positions < lengths[b] (optionally sliding-window)."""
    B, H, hd = q.shape
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    g = H // Hkv
    qf = (q.astype(jnp.float32) / jnp.sqrt(jnp.float32(hd))
          ).reshape(B, Hkv, g, hd)
    s = jnp.einsum("bhgd,bkhd->bhgk", qf, k_cache.astype(jnp.float32))
    k_pos = jnp.arange(S)[None, :]
    mask = k_pos < lengths[:, None]
    if window is not None:
        mask &= k_pos > lengths[:, None] - 1 - window
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", p, v_cache.astype(jnp.float32))
    return o.reshape(B, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# selective scan (Mamba recurrence, diagonal)
# ---------------------------------------------------------------------------

def ssm_scan(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
             B_: jnp.ndarray, C_: jnp.ndarray,
             h0: Optional[jnp.ndarray] = None
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x, dt (B,S,di); A (di,N); B_, C_ (B,S,N) -> (y (B,S,di), h (B,di,N))."""
    Bsz, S, di = x.shape
    N = A.shape[-1]
    if h0 is None:
        h0 = jnp.zeros((Bsz, di, N), jnp.float32)

    def step(h, t_in):
        xt, dtt, Bt, Ct = t_in
        decay = jnp.exp(dtt.astype(jnp.float32)[..., None]
                        * A.astype(jnp.float32)[None])
        h = decay * h + (dtt * xt).astype(jnp.float32)[..., None] \
            * Bt.astype(jnp.float32)[:, None, :]
        y = jnp.einsum("bdn,bn->bd", h, Ct.astype(jnp.float32))
        return h, y

    h, ys = jax.lax.scan(step, h0,
                         (x.transpose(1, 0, 2), dt.transpose(1, 0, 2),
                          B_.transpose(1, 0, 2), C_.transpose(1, 0, 2)))
    return ys.transpose(1, 0, 2).astype(x.dtype), h


# ---------------------------------------------------------------------------
# dropless routed experts (serving: moe_decode, moe_grouped)
# ---------------------------------------------------------------------------

def moe_ffn(x: jnp.ndarray, expert_ids: jnp.ndarray, weights: jnp.ndarray,
            w_gate: jnp.ndarray, w_up: jnp.ndarray,
            w_down: jnp.ndarray) -> jnp.ndarray:
    """Every expert on every row, combined by the top-k weights.

    x (T,D); expert_ids/weights (T,k); w_gate/w_up (E,D,F); w_down (E,F,D)
    -> (T,D) f32: ``sum_j weights[t,j] * FFN_{expert_ids[t,j]}(x[t])``
    with ``FFN_e(h) = (silu(h Wg_e) * (h Wu_e)) Wd_e``."""
    T = x.shape[0]
    E = w_gate.shape[0]
    comb = jnp.zeros((T, E), jnp.float32).at[
        jnp.arange(T)[:, None], expert_ids].add(weights.astype(jnp.float32))
    xf = x.astype(jnp.float32)
    g = jnp.einsum("td,edf->tef", xf, w_gate.astype(jnp.float32))
    u = jnp.einsum("td,edf->tef", xf, w_up.astype(jnp.float32))
    a = jax.nn.silu(g) * u * comb[..., None]
    return jnp.einsum("tef,efd->td", a, w_down.astype(jnp.float32))
