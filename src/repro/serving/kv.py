"""Kernel-backed LM + KV-cache math for the serving plane.

The serving dataflow (``serving/dataflow.py``) needs a model whose prefill
is *driven by* the seed ``flash_attention`` Pallas kernel and whose decode
is driven by ``decode_attention``.  This module is that model, the one LM
of the package: a compact pre-norm transformer whose only attention entry
points are ``kernels.ops.flash_attention_op`` / ``decode_attention_op``, plus
*ref twins* (same math routed through ``kernels/ref.py``) so kernel-vs-ref
parity can be asserted **through the dataflow** on stage outputs.

Shapes (GQA supported, ``n_heads % n_kv_heads == 0``):

* params: per-layer weights stacked on a leading layer axis ``L``
* prefill: tokens ``(B, S)`` + lengths ``(B,)`` → last-position logits
  ``(B, V)`` and KV caches ``(L, B, max_len, Hkv, hd)`` (padded so every
  request's cache is a fixed-shape row sliceable into decode slots)
* decode:  tokens ``(B,)`` + caches + lengths → logits ``(B, V)`` and the
  caches with the new token's K/V written at position ``lengths[b]``

``LMSpec`` also describes a sparse-expert model with mixed attention: a
residual width of its own, a window per layer (passed to both attention
kernels), and routed experts in place of the FFN.  Prefill runs the
experts as grouped matmuls over expert-sorted tokens
(``kernels.ops.moe_grouped_op``), decode through the dropless
``moe_decode`` kernel, which reads each expert the live slots picked once.
Left at their defaults, those fields give the dense model and its programs
unchanged.

Cache positions ``>= lengths[b]`` hold garbage (pad-token activations);
``decode_attention`` masks them via ``lengths`` so they are never read.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import flash_attention as kfa
from ..kernels import ops as kops
from ..kernels import ref as kref
from ..kernels.moe_decode import fetch_list


@dataclasses.dataclass(frozen=True)
class LMSpec:
    """Static model geometry (hashable → usable as a jit static arg).

    Left at their defaults, the last five fields give the dense model: a
    residual width of ``n_heads * head_dim``, full causal attention in
    every layer and an ungated SiLU FFN of ``ffn_mult * d_model``.
    ``windows`` gives each layer's attention window (None: full; empty:
    every layer full).  With ``n_experts`` set, every layer's FFN is a
    dropless routed-expert layer instead: a softmax router over
    ``n_experts``, the top ``top_k`` renormalised to sum to 1, each a
    SwiGLU expert of width ``expert_width``."""

    vocab: int = 64
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 8
    n_layers: int = 2
    max_len: int = 32
    ffn_mult: int = 2
    d_model: Optional[int] = None
    windows: Tuple[Optional[int], ...] = ()
    n_experts: int = 0
    top_k: int = 0
    expert_width: int = 0

    def __post_init__(self):
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads must be a multiple of n_kv_heads")
        if self.d_model is None:
            object.__setattr__(self, "d_model", self.n_heads * self.head_dim)
        windows = tuple(self.windows) or (None,) * self.n_layers
        if len(windows) != self.n_layers:
            raise ValueError(f"{len(windows)} windows for "
                             f"{self.n_layers} layers")
        object.__setattr__(self, "windows", windows)
        if self.n_experts and not (0 < self.top_k <= self.n_experts
                                   and self.expert_width > 0):
            raise ValueError("an expert layer needs 0 < top_k <= n_experts "
                             "and an expert_width")

    def window(self, layer: int) -> Optional[int]:
        """Layer ``layer``'s attention window, or None for full causal."""
        return self.windows[layer]


def param_shapes(spec: LMSpec) -> Dict[str, Tuple[int, ...]]:
    """``init_params``'s layout, in the order it draws."""
    V, D, L = spec.vocab, spec.d_model, spec.n_layers
    Q, KV = spec.n_heads * spec.head_dim, spec.n_kv_heads * spec.head_dim
    shapes = {"embed": (V, D), "head": (V, D), "wq": (L, D, Q),
              "wk": (L, D, KV), "wv": (L, D, KV), "wo": (L, Q, D)}
    if spec.n_experts:
        E, F = spec.n_experts, spec.expert_width
        shapes.update(router=(L, D, E), wg=(L, E, D, F), wu=(L, E, D, F),
                      wd=(L, E, F, D))
    else:
        F = spec.ffn_mult * D
        shapes.update(w1=(L, D, F), w2=(L, F, D))
    shapes.update(ln1=(L, D), ln2=(L, D), ln_f=(D,))
    return shapes


def init_params(spec: LMSpec, seed: int = 0,
                scale: float = 0.3) -> Dict[str, jnp.ndarray]:
    """Random weights; different ``seed`` = a different model *version*
    (what a live hot-swap ships).  ``scale`` is large enough that two
    seeds produce visibly different generations.  Weight matrices are
    scaled by the square root of their fan-in; the embedding and the
    untied output head are not: a tied head makes greedy decoding
    collapse to the copy-last-token fixed point (self-similarity always
    wins the argmax), which would leave nothing for a weight swap or a
    kernel-parity check to observe."""
    rng = np.random.default_rng(seed)
    params = {}
    for name, shape in param_shapes(spec).items():
        if name.startswith("ln"):
            params[name] = jnp.ones(shape)
            continue
        fan_in = 1.0 if name in ("embed", "head") else shape[-2]
        params[name] = jnp.asarray(
            rng.normal(0.0, scale, shape) / np.sqrt(fan_in),
            dtype=jnp.float32)
    return params


def _rms(x: jnp.ndarray, g: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    return x * g * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)


def _route(h: jnp.ndarray, router: jnp.ndarray, spec: LMSpec
           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Each row's top-k experts under the softmax router, and their
    weights, renormalised to sum to 1.  The
    logits are taken at highest precision (D x E a token): near-ties among
    the top-k then break as the float32 reference breaks them."""
    logits = jnp.matmul(h, router, precision=jax.lax.Precision.HIGHEST)
    w, ids = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), spec.top_k)
    return ids.astype(jnp.int32), w / jnp.sum(w, axis=-1, keepdims=True)


# -- prefill ----------------------------------------------------------------

def _prefill_impl(params: Dict[str, jnp.ndarray], tokens: jnp.ndarray,
                  lengths: jnp.ndarray, spec: LMSpec,
                  attn: Callable[..., jnp.ndarray],
                  experts: Optional[Callable[..., jnp.ndarray]] = None
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
        window = spec.window(l)
        o = attn(q, k, v) if window is None else attn(q, k, v, window=window)
        x = x + o.reshape(B, S, H * hd) @ params["wo"][l]
        h2 = _rms(x, params["ln2"][l])
        if spec.n_experts:
            # one sequence at a time: its T*k sorted expert rows are the
            # most the expert layer holds at once
            def seq_experts(hs, l=l):
                ids, w = _route(hs, params["router"][l], spec)
                return experts(hs, ids, w, params["wg"], params["wu"],
                               params["wd"], l)
            x = x + jax.lax.map(seq_experts, h2)
        else:
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
    """Kernel path: causal (or windowed) attention via the flash_attention
    Pallas kernel; routed experts as grouped matmuls over expert-sorted
    tokens (``kernels.ops.moe_grouped_op``).

    ``interpret`` comes from ``kernels.ops.pallas_interpret()``, asked when
    the caller is built."""
    return _prefill_impl(
        params, tokens, lengths, spec,
        lambda q, k, v, **window: kops.flash_attention_op(
            q, k, v, causal=True, interpret=interpret, **window),
        lambda h, ids, w, wg, wu, wd, l: kops.moe_grouped_op(
            h, ids, w, wg, wu, wd, l, interpret=interpret))


def prefill_attn_blocks(spec: LMSpec, seq: int) -> Tuple[int, int]:
    """(q, kv) block pairs ``prefill``'s flash attention visits over
    ``seq`` prompt positions, and those of its full grid: summed over
    layers, for one batch row and head."""
    visited = full = 0
    for l in range(spec.n_layers):
        w = spec.window(l)
        v, f = kfa.band_blocks(seq, seq, *kfa.block_sizes(seq, seq),
                               True, w)
        visited, full = visited + v, full + f
    return visited, full


def prefill_ref(params, tokens, lengths, *, spec: LMSpec):
    """Ref twin: identical math through ``kernels.ref``."""
    return _prefill_impl(
        params, tokens, lengths, spec,
        lambda q, k, v, window=None: kref.attention(q, k, v, causal=True,
                                                    window=window),
        lambda h, ids, w, wg, wu, wd, l: kref.moe_ffn(h, ids, w, wg[l],
                                                      wu[l], wd[l]))


# -- decode -----------------------------------------------------------------

def _decode_impl(params: Dict[str, jnp.ndarray], k_cache: jnp.ndarray,
                 v_cache: jnp.ndarray, lengths: jnp.ndarray,
                 tokens: jnp.ndarray, live: Optional[jnp.ndarray],
                 spec: LMSpec, dec_attn: Callable[..., jnp.ndarray],
                 experts: Optional[Callable[..., Tuple]] = None) -> Tuple:
    B = tokens.shape[0]
    H, Hkv, hd = spec.n_heads, spec.n_kv_heads, spec.head_dim
    rows = jnp.arange(B)
    x = params["embed"][tokens]                      # (B, D)
    fetched = []
    for l in range(spec.n_layers):
        h = _rms(x, params["ln1"][l])
        q = (h @ params["wq"][l]).reshape(B, H, hd)
        kn = (h @ params["wk"][l]).reshape(B, Hkv, hd)
        vn = (h @ params["wv"][l]).reshape(B, Hkv, hd)
        k_cache = k_cache.at[l, rows, lengths].set(kn)
        v_cache = v_cache.at[l, rows, lengths].set(vn)
        # the stacked caches and the layer, not ``k_cache[l]``: the kernel
        # reads layer l where it lies, so XLA never copies a layer out
        o = dec_attn(q, k_cache, v_cache, lengths + 1, l, spec.window(l))
        x = x + o.reshape(B, H * hd) @ params["wo"][l]
        h2 = _rms(x, params["ln2"][l])
        if spec.n_experts:
            ids, w = _route(h2, params["router"][l], spec)
            y, n = experts(h2, ids, w, live, params["wg"], params["wu"],
                           params["wd"], l)
            x = x + y
            fetched.append(n)
        else:
            x = x + jax.nn.silu(h2 @ params["w1"][l]) @ params["w2"][l]
    x = _rms(x, params["ln_f"])
    out = (x @ params["head"].T, k_cache, v_cache)
    return out + (jnp.stack(fetched),) if fetched else out


@functools.partial(jax.jit, static_argnames=("spec", "interpret"),
                   donate_argnames=("k_cache", "v_cache"))
def decode_step(params, k_cache, v_cache, lengths, tokens, live=None, *,
                spec: LMSpec, interpret: bool):
    """One continuous-batching decode step over every slot, driven by the
    decode_attention (flash-decode) Pallas kernel, and for an expert spec
    by the dropless ``moe_decode`` kernel.

    ``lengths[b]`` is the number of valid cache positions for slot ``b``
    *before* this step; the new token's K/V is written at ``lengths[b]``
    and the caller bumps lengths by one for live slots.  Dead slots must
    keep ``lengths >= 0`` with a pinned token — their logits are garbage
    but finite and simply ignored.

    Returns ``(logits, k_cache, v_cache)``.  An expert spec also takes
    ``live (B,)`` bool, so that dead slots pick no expert, and returns
    each layer's count of experts fetched, ``(L,)`` int32, last.

    The caches are donated: each step writes its new rows into the
    buffers it is handed, and the arrays passed in are deleted.  Rebind
    them to the returned caches; never pass one buffer as both.
    """
    return _decode_impl(
        params, k_cache, v_cache, lengths, tokens, live, spec,
        lambda q, k, v, lens, l, window: kops.decode_attention_op(
            q, k, v, lens, l, window=window, interpret=interpret),
        lambda h, ids, w, live_, wg, wu, wd, l: kops.moe_decode_op(
            h, ids, w, live_, wg, wu, wd, l, interpret=interpret))


def _dense_experts(h, ids, w, live, wg, wu, wd, l):
    y = kref.moe_ffn(h, ids, jnp.where(live[:, None], w, 0.0), wg[l], wu[l],
                     wd[l])
    return y, fetch_list(ids, live, wg.shape[1])[1]


def decode_step_ref(params, k_cache, v_cache, lengths, tokens, live=None,
                    *, spec: LMSpec):
    """Ref twin through ``kernels.ref``: every expert on every slot."""
    return _decode_impl(
        params, k_cache, v_cache, lengths, tokens, live, spec,
        lambda q, k, v, lens, l, window: kref.decode_attention(
            q, k[l], v[l], lens, window=window),
        _dense_experts)


# -- slot splice ------------------------------------------------------------

@functools.partial(jax.jit, donate_argnames=("k_cache", "v_cache"))
def splice(k_cache, v_cache, slots, k_rows, v_rows):
    """Write requests' prefill cache rows ``(B, L, Smax, Hkv, hd)`` into
    decode slots ``slots (B,)`` of the stacked ``(L, n_slots, Smax, Hkv,
    hd)`` caches — the continuous-batching splice (admit → **splice** →
    free).  Donated like ``decode_step``'s: the rows land in the buffers
    handed in, and one program is compiled per row count ``B``.  Returns
    ``(k_cache, v_cache)``."""
    return (k_cache.at[:, slots].set(jnp.moveaxis(k_rows, 0, 1)),
            v_cache.at[:, slots].set(jnp.moveaxis(v_rows, 0, 1)))


def greedy(logits: Any) -> jnp.ndarray:
    """Deterministic next-token choice (argmax) — keeps kernel-vs-ref
    parity falsifiable at the token level."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)
