"""Plain reference of the served routed-expert LM with windowed and full
attention layers, and its weights from a seed.

A pre-norm decoder written straight from its equations, in ``jax.numpy``
with no kernel, cache or batching of requests: for every position of a
token sequence, the logits of the next token.  It shares no code with the
program.  Layer ``l``, with window ``W_l`` (None: full):

    h = rms(x) * ln1;  q, k, v = h Wq, h Wk, h Wv   (no rotary embedding)
    x += softmax(q k^T / sqrt(head_dim) + mask_l) v Wo   (grouped kv heads)
         mask_l: key <= query, and key > query - W_l on a windowed layer
    h = rms(x) * ln2;  p = softmax(h Wr)  over the E experts
    T = top_k(p);  g = p_T / sum(p_T)
    x += sum_{e in T} g_e (silu(h Wg_e) * (h Wu_e)) Wd_e
    logits = (rms(x) * ln_f) head^T                  (untied head)

Every expert runs on every token, and the top-k weights pick what counts.
Attention is computed in blocks of queries and the experts in blocks of
tokens, so that a sequence of the cache's length fits beside the weights.

``init`` draws the weights on the device in one jitted call: the layout
the program's serving flow takes (experts stacked ``(L, E, D, F)`` and
``(L, E, F, D)``), in the type it serves them in.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .ref_lm import SCALE, key_of
from .work_moe import MoEDims

#: most queries (attention) or tokens (experts) computed at once
BLOCK = 512


def shapes(dims: MoEDims) -> Dict[str, Tuple[int, ...]]:
    """The program's parameter layout."""
    V, D, L = dims.vocab, dims.d_model, dims.n_layers
    Q = dims.n_heads * dims.head_dim
    KV = dims.n_kv_heads * dims.head_dim
    E, F = dims.n_experts, dims.expert_width
    return {"embed": (V, D), "head": (V, D), "wq": (L, D, Q),
            "wk": (L, D, KV), "wv": (L, D, KV), "wo": (L, Q, D),
            "router": (L, D, E), "wg": (L, E, D, F), "wu": (L, E, D, F),
            "wd": (L, E, F, D), "ln1": (L, D), "ln2": (L, D), "ln_f": (D,)}


@functools.partial(jax.jit, static_argnames=("dims", "dtype"))
def _init(key: jax.Array, dims: MoEDims, dtype=jnp.float32
          ) -> Dict[str, jax.Array]:
    drawn = {n: s for n, s in shapes(dims).items() if not n.startswith("ln")}
    keys = jax.random.split(key, len(drawn))
    out = {}
    for k, (name, shape) in zip(keys, sorted(drawn.items())):
        fan_in = 1.0 if name in ("embed", "head") else float(shape[-2])
        out[name] = (jax.random.normal(k, shape, jnp.float32)
                     * (SCALE / np.sqrt(fan_in))).astype(dtype)
    for name, shape in shapes(dims).items():
        if name.startswith("ln"):
            out[name] = jnp.ones(shape, dtype)
    return out


def init(dims: MoEDims, seed: int, dtype=jnp.float32
         ) -> Dict[str, jax.Array]:
    params = _init(key_of(seed), dims, dtype)
    jax.block_until_ready(params)
    return params


def _block(n: int) -> int:
    """The largest multiple of 8 up to ``BLOCK`` that divides ``n``, else
    ``n``."""
    for b in range(min(BLOCK, n) // 8 * 8, 7, -8):
        if n % b == 0:
            return b
    return n


def _rms(x, g, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                            + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def _attention(q, k, v, window, prec):
    """q (B, S, Hkv, g, hd), k/v (B, S, Hkv, hd) -> (B, S, Hkv, g, hd), in
    blocks of queries."""
    B, S, Hkv, g, hd = q.shape
    dt = q.dtype
    bq = _block(S)
    neg = jnp.asarray(-1e30 if dt == jnp.float32 else -1e4, dt)
    key_pos = jnp.arange(S)

    def one(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * bq, bq, axis=1)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qi, k, precision=prec)
        s = s / jnp.sqrt(jnp.asarray(hd, dt))
        qpos = i * bq + jnp.arange(bq)[:, None]
        mask = key_pos[None, :] <= qpos
        if window is not None:
            mask &= key_pos[None, :] > qpos - window
        p = jax.nn.softmax(jnp.where(mask, s, neg), axis=-1)
        return jnp.einsum("bhgqk,bkhd->bqhgd", p, v, precision=prec)

    out = jax.lax.map(one, jnp.arange(S // bq))      # (nb, B, bq, ...)
    return jnp.moveaxis(out, 0, 1).reshape(B, S, Hkv, g, hd)


def _experts(h, w, dims, prec):
    """Every expert on every token of ``h (N, D)``, in blocks of tokens,
    combined by the renormalised top-k of the softmax router; also each
    token's top-k experts."""
    N, D = h.shape
    bt = _block(N)
    E = dims.n_experts

    def one(hb):
        p = jax.nn.softmax(jnp.matmul(hb, w["router"], precision=prec)
                           .astype(jnp.float32), axis=-1)
        top, ids = jax.lax.top_k(p, dims.top_k)
        top = top / jnp.sum(top, axis=-1, keepdims=True)
        comb = jnp.sum(jax.nn.one_hot(ids, E, dtype=jnp.float32)
                       * top[..., None], axis=1).astype(hb.dtype)
        gate = jnp.einsum("td,edf->tef", hb, w["wg"], precision=prec)
        up = jnp.einsum("td,edf->tef", hb, w["wu"], precision=prec)
        act = jax.nn.silu(gate) * up * comb[..., None]
        return (jnp.einsum("tef,efd->td", act, w["wd"], precision=prec),
                ids)

    out, ids = jax.lax.map(one, h.reshape(N // bt, bt, D))
    return out.reshape(N, D), ids.reshape(N, dims.top_k)


def _forward(params, tokens, dims: MoEDims, eps: float, precision: str):
    """Logits ``(B, S, V)`` of every position of ``tokens (B, S)``; each
    layer's top-k experts ``(L, B, S, k)`` and keys ``(L, B, S, Hkv, hd)``
    (its input, the previous layer's output, projected) at every position.
    The
    arithmetic is in the type of ``params``; ``precision`` names the
    matmul precision (``highest`` for the float32 reference)."""
    prec = jax.lax.Precision[precision.upper()]
    B, S = tokens.shape
    H, Hkv, hd = dims.n_heads, dims.n_kv_heads, dims.head_dim
    mm = functools.partial(jnp.matmul, precision=prec)
    x = params["embed"][tokens]
    routes, keys = [], []
    for l, window in enumerate(dims.windows):
        w = {n: params[n][l] for n in ("ln1", "wq", "wk", "wv", "wo", "ln2",
                                       "router", "wg", "wu", "wd")}
        h = _rms(x, w["ln1"], eps)
        q = mm(h, w["wq"]).reshape(B, S, Hkv, H // Hkv, hd)
        k = mm(h, w["wk"]).reshape(B, S, Hkv, hd)
        v = mm(h, w["wv"]).reshape(B, S, Hkv, hd)
        o = _attention(q, k, v, window, prec)
        x = x + mm(o.reshape(B, S, H * hd), w["wo"])
        h2 = _rms(x, w["ln2"], eps).reshape(B * S, -1)
        y, ids = _experts(h2, w, dims, prec)
        x = x + y.reshape(x.shape)
        routes.append(ids.reshape(B, S, dims.top_k))
        keys.append(k)
    x = _rms(x, params["ln_f"], eps)
    return mm(x, params["head"].T), jnp.stack(routes), jnp.stack(keys)


@functools.partial(jax.jit, static_argnames=("dims", "eps", "precision"))
def forward(params: Dict[str, jax.Array], tokens: jax.Array, *,
            dims: MoEDims, eps: float, precision: str = "highest"
            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The logits of every position, and every layer's routing and keys."""
    return _forward(params, tokens, dims, eps, precision)


@functools.partial(jax.jit, static_argnames=("dims", "eps", "precision"))
def scores(params: Dict[str, jax.Array], tokens: jax.Array,
           probe: jax.Array, *, dims: MoEDims, eps: float,
           precision: str) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """For every position of ``tokens (B, S)``: the largest next-token
    logit, the logit of ``probe (B, S)``, and the token of the largest."""
    logits = _forward(params, tokens, dims, eps, precision)[0]
    best = jnp.max(logits, axis=-1).astype(jnp.float32)
    top = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    got = jnp.take_along_axis(logits, probe[..., None], axis=-1)[..., 0]
    return best, got.astype(jnp.float32), top


def gaps(params, seqs, probes, *, dims: MoEDims, eps: float, length: int,
         batch: int, precision: str = "highest"):
    """Run ``scores`` over ``seqs`` (lists of token ids) in blocks of
    ``batch`` sequences padded to ``length``.  ``probes[i]`` maps a
    position of sequence ``i`` to the token whose logit is read there.
    Returns, per sequence, the array of gaps ``best - logit(probe)`` at the
    probed positions, and the reference's own top token at every position
    (for the control)."""
    out_gaps, out_top = [], []
    for lo in range(0, len(seqs), batch):
        block = seqs[lo:lo + batch]
        tok = np.zeros((batch, length), np.int32)
        prb = np.zeros((batch, length), np.int32)
        for i, s in enumerate(block):
            tok[i, :len(s)] = s
            for pos, t in probes[lo + i].items():
                prb[i, pos] = t
        best, got, top = jax.device_get(scores(
            params, jnp.asarray(tok), jnp.asarray(prb), dims=dims, eps=eps,
            precision=precision))
        for i in range(len(block)):
            pos = np.fromiter(sorted(probes[lo + i]), np.int64)
            out_gaps.append(best[i, pos] - got[i, pos])
            out_top.append(top[i])
    return out_gaps, out_top
