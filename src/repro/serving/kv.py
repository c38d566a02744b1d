"""Kernel-backed LM + KV-cache math for the serving plane.

The serving dataflow (``serving/dataflow.py``) needs a model whose prefill
is *driven by* the seed ``flash_attention`` Pallas kernel and whose decode
is driven by ``decode_attention`` — not the dense reference stack in
``models/`` (which re-implements attention inline).  This module is that
model: a compact pre-norm transformer whose only attention entry points
are ``kernels.ops.flash_attention_op`` / ``decode_attention_op``, plus
*ref twins* (same math routed through ``kernels/ref.py``) so kernel-vs-ref
parity can be asserted **through the dataflow** on stage outputs.

Shapes (GQA supported, ``n_heads % n_kv_heads == 0``):

* params: per-layer weights stacked on a leading layer axis ``L``
* prefill: tokens ``(B, S)`` + lengths ``(B,)`` → last-position logits
  ``(B, V)`` and KV caches ``(L, B, max_len, Hkv, hd)`` (padded so every
  request's cache is a fixed-shape row sliceable into decode slots)
* decode:  tokens ``(B,)`` + caches + lengths → logits ``(B, V)`` and the
  caches with the new token's K/V written at position ``lengths[b]``

Cache positions ``>= lengths[b]`` hold garbage (pad-token activations);
``decode_attention`` masks them via ``lengths`` so they are never read.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops as kops
from ..kernels import ref as kref


@dataclasses.dataclass(frozen=True)
class LMSpec:
    """Static model geometry (hashable → usable as a jit static arg)."""

    vocab: int = 64
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 8
    n_layers: int = 2
    max_len: int = 32
    ffn_mult: int = 2

    def __post_init__(self):
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads must be a multiple of n_kv_heads")

    @property
    def d_model(self) -> int:
        return self.n_heads * self.head_dim


def init_params(spec: LMSpec, seed: int = 0,
                scale: float = 0.3) -> Dict[str, jnp.ndarray]:
    """Random weights; different ``seed`` = a different model *version*
    (what a live hot-swap ships).  ``scale`` is large enough that two
    seeds produce visibly different generations."""
    rng = np.random.default_rng(seed)
    D, H, Hkv, hd = spec.d_model, spec.n_heads, spec.n_kv_heads, spec.head_dim
    L, F = spec.n_layers, spec.ffn_mult * spec.d_model

    def w(*shape):
        return jnp.asarray(rng.normal(0.0, scale, shape) / np.sqrt(shape[-2]),
                           dtype=jnp.float32)

    return {
        "embed": jnp.asarray(rng.normal(0.0, scale, (spec.vocab, D)),
                             dtype=jnp.float32),
        # untied output head: a tied head makes greedy decoding collapse
        # to the copy-last-token fixed point (self-similarity always wins
        # the argmax), which would leave nothing for a weight swap or a
        # kernel-parity check to observe
        "head": jnp.asarray(rng.normal(0.0, scale, (spec.vocab, D)),
                            dtype=jnp.float32),
        "wq": w(L, D, H * hd), "wk": w(L, D, Hkv * hd),
        "wv": w(L, D, Hkv * hd), "wo": w(L, H * hd, D),
        "w1": w(L, D, F), "w2": w(L, F, D),
        "ln1": jnp.ones((L, D)), "ln2": jnp.ones((L, D)),
        "ln_f": jnp.ones((D,)),
    }


def _rms(x: jnp.ndarray, g: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    return x * g * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)


# -- prefill ----------------------------------------------------------------

def _prefill_impl(params: Dict[str, jnp.ndarray], tokens: jnp.ndarray,
                  lengths: jnp.ndarray, spec: LMSpec,
                  attn: Callable[..., jnp.ndarray]
                  ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    B, S = tokens.shape
    H, Hkv, hd = spec.n_heads, spec.n_kv_heads, spec.head_dim
    x = params["embed"][tokens]                      # (B, S, D)
    ks, vs = [], []
    for l in range(spec.n_layers):                   # L is small; unrolled
        h = _rms(x, params["ln1"][l])
        q = (h @ params["wq"][l]).reshape(B, S, H, hd)
        k = (h @ params["wk"][l]).reshape(B, S, Hkv, hd)
        v = (h @ params["wv"][l]).reshape(B, S, Hkv, hd)
        o = attn(q, k, v).reshape(B, S, H * hd)
        x = x + o @ params["wo"][l]
        h2 = _rms(x, params["ln2"][l])
        x = x + jax.nn.silu(h2 @ params["w1"][l]) @ params["w2"][l]
        pad = ((0, 0), (0, spec.max_len - S), (0, 0), (0, 0))
        ks.append(jnp.pad(k, pad))
        vs.append(jnp.pad(v, pad))
    x = _rms(x, params["ln_f"])
    last = x[jnp.arange(B), lengths - 1]             # (B, D) at last real tok
    logits = last @ params["head"].T                 # (B, V)
    return logits, jnp.stack(ks), jnp.stack(vs)      # caches (L,B,Smax,Hkv,hd)


@functools.partial(jax.jit, static_argnames=("spec", "interpret"))
def prefill(params, tokens, lengths, *, spec: LMSpec, interpret: bool):
    """Kernel path: causal attention via the flash_attention Pallas kernel.

    ``interpret`` comes from ``kernels.ops.pallas_interpret()``, asked when
    the caller is built."""
    return _prefill_impl(
        params, tokens, lengths, spec,
        lambda q, k, v: kops.flash_attention_op(
            q, k, v, causal=True, interpret=interpret))


def prefill_ref(params, tokens, lengths, *, spec: LMSpec):
    """Ref twin: identical math through ``kernels.ref.attention``."""
    return _prefill_impl(
        params, tokens, lengths, spec,
        lambda q, k, v: kref.attention(q, k, v, causal=True))


# -- decode -----------------------------------------------------------------

def _decode_impl(params: Dict[str, jnp.ndarray], k_cache: jnp.ndarray,
                 v_cache: jnp.ndarray, lengths: jnp.ndarray,
                 tokens: jnp.ndarray, spec: LMSpec,
                 dec_attn: Callable[..., jnp.ndarray]
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    B = tokens.shape[0]
    H, Hkv, hd = spec.n_heads, spec.n_kv_heads, spec.head_dim
    rows = jnp.arange(B)
    x = params["embed"][tokens]                      # (B, D)
    for l in range(spec.n_layers):
        h = _rms(x, params["ln1"][l])
        q = (h @ params["wq"][l]).reshape(B, H, hd)
        kn = (h @ params["wk"][l]).reshape(B, Hkv, hd)
        vn = (h @ params["wv"][l]).reshape(B, Hkv, hd)
        k_cache = k_cache.at[l, rows, lengths].set(kn)
        v_cache = v_cache.at[l, rows, lengths].set(vn)
        # the stacked caches and the layer, not ``k_cache[l]``: the kernel
        # reads layer l where it lies, so XLA never copies a layer out
        o = dec_attn(q, k_cache, v_cache, lengths + 1, l)
        x = x + o.reshape(B, H * hd) @ params["wo"][l]
        h2 = _rms(x, params["ln2"][l])
        x = x + jax.nn.silu(h2 @ params["w1"][l]) @ params["w2"][l]
    x = _rms(x, params["ln_f"])
    return x @ params["head"].T, k_cache, v_cache


@functools.partial(jax.jit, static_argnames=("spec", "interpret"))
def decode_step(params, k_cache, v_cache, lengths, tokens, *, spec: LMSpec,
                interpret: bool):
    """One continuous-batching decode step over every slot, driven by the
    decode_attention (flash-decode) Pallas kernel.

    ``lengths[b]`` is the number of valid cache positions for slot ``b``
    *before* this step; the new token's K/V is written at ``lengths[b]``
    and the caller bumps lengths by one for live slots.  Dead slots must
    keep ``lengths >= 0`` with a pinned token — their logits are garbage
    but finite and simply ignored.
    """
    return _decode_impl(
        params, k_cache, v_cache, lengths, tokens, spec,
        lambda q, k, v, lens, l: kops.decode_attention_op(
            q, k, v, lens, l, interpret=interpret))


def decode_step_ref(params, k_cache, v_cache, lengths, tokens, *,
                    spec: LMSpec):
    """Ref twin through ``kernels.ref.decode_attention``."""
    return _decode_impl(
        params, k_cache, v_cache, lengths, tokens, spec,
        lambda q, k, v, lens, l: kref.decode_attention(q, k[l], v[l],
                                                       lens))


# -- slot splice ------------------------------------------------------------

def splice(cache: jnp.ndarray, row: Any, slot: Any) -> jnp.ndarray:
    """Write one request's prefill cache ``row (L, Smax, Hkv, hd)`` into
    decode-slot ``slot`` of ``cache (L, n_slots, Smax, Hkv, hd)`` — the
    continuous-batching splice (admit → **splice** → free)."""
    return cache.at[:, int(slot)].set(jnp.asarray(row))


def greedy(logits: Any) -> jnp.ndarray:
    """Deterministic next-token choice (argmax) — keeps kernel-vs-ref
    parity falsifiable at the token level."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)
