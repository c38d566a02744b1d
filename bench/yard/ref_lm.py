"""Plain reference of the served LM, and its weights from a seed.

A pre-norm decoder written straight from its equations, in ``jax.numpy``
with no kernel, cache or batching of requests: for every position of a
token sequence, the logits of the next token.  It shares no code with the
program.  Its layers, per the configuration's ``departures``:

    h = rms(x) * ln1;  q, k, v = h Wq, h Wk, h Wv   (no rotary embedding)
    x += softmax(q k^T / sqrt(head_dim) + causal) v Wo   (grouped kv heads)
    h = rms(x) * ln2;  x += silu(h W1) W2            (ungated FFN)
    logits = (rms(x) * ln_f) head^T                  (untied head)

``init`` draws the weights on the device in one jitted call: the layout
the program's serving flow takes, in the type it serves them in.  The
benchmark hands them to the program and keeps them for this reference.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .work import LMDims

#: the spread of the random weights: a weight matrix is N(0, SCALE^2)
#: over the square root of its fan-in; embeddings and head N(0, SCALE^2)
SCALE = 0.3


def key_of(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole seed (64 bits kept)."""
    seed = int(seed) & ((1 << 64) - 1)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


@functools.partial(jax.jit, static_argnames=("dims", "dtype"))
def _init(key: jax.Array, dims: LMDims, dtype=jnp.float32
          ) -> Dict[str, jax.Array]:
    V, D, L = dims.vocab, dims.d_model, dims.n_layers
    Q = dims.n_heads * dims.head_dim
    KV = dims.n_kv_heads * dims.head_dim
    F = dims.d_ff
    shapes = {"embed": (V, D), "head": (V, D), "wq": (L, D, Q),
              "wk": (L, D, KV), "wv": (L, D, KV), "wo": (L, Q, D),
              "w1": (L, D, F), "w2": (L, F, D)}
    keys = jax.random.split(key, len(shapes))
    out = {}
    for k, (name, shape) in zip(keys, sorted(shapes.items())):
        fan_in = 1.0 if name in ("embed", "head") else float(shape[-2])
        out[name] = (jax.random.normal(k, shape, jnp.float32)
                     * (SCALE / np.sqrt(fan_in))).astype(dtype)
    out["ln1"] = jnp.ones((L, D), dtype)
    out["ln2"] = jnp.ones((L, D), dtype)
    out["ln_f"] = jnp.ones((D,), dtype)
    return out


def init(dims: LMDims, seed: int, dtype=jnp.float32) -> Dict[str, jax.Array]:
    params = _init(key_of(seed), dims, dtype)
    jax.block_until_ready(params)
    return params


def _rms(x, g, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                            + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("dims", "eps", "precision"))
def scores(params: Dict[str, jax.Array], tokens: jax.Array,
           probe: jax.Array, *, dims: LMDims, eps: float,
           precision: str) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """For every position of ``tokens (B, S)``: the largest next-token
    logit, the logit of ``probe (B, S)``, and the token of the largest.

    The arithmetic is in the type of ``params``; ``precision`` names the
    matmul precision (``highest`` for the float32 reference)."""
    prec = jax.lax.Precision[precision.upper()]
    dt = params["embed"].dtype
    B, S = tokens.shape
    H, Hkv, hd = dims.n_heads, dims.n_kv_heads, dims.head_dim
    g = H // Hkv
    mm = functools.partial(jnp.matmul, precision=prec)
    causal = jnp.tril(jnp.ones((S, S), bool))

    def layer(x, w):
        h = _rms(x, w["ln1"], eps)
        q = mm(h, w["wq"]).reshape(B, S, Hkv, g, hd)
        k = mm(h, w["wk"]).reshape(B, S, Hkv, hd)
        v = mm(h, w["wv"]).reshape(B, S, Hkv, hd)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k, precision=prec)
        s = s / jnp.sqrt(jnp.asarray(hd, dt))
        s = jnp.where(causal, s, jnp.asarray(-1e30 if dt == jnp.float32
                                             else -1e4, dt))
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v, precision=prec)
        x = x + mm(o.reshape(B, S, H * hd), w["wo"])
        h2 = _rms(x, w["ln2"], eps)
        x = x + mm(jax.nn.silu(mm(h2, w["w1"])), w["w2"])
        return x, None

    stacked = {n: params[n] for n in ("ln1", "wq", "wk", "wv", "wo", "ln2",
                                      "w1", "w2")}
    x = params["embed"][tokens]
    x, _ = jax.lax.scan(layer, x, stacked)
    x = _rms(x, params["ln_f"], eps)
    logits = mm(x, params["head"].T)                       # (B, S, V)
    best = jnp.max(logits, axis=-1).astype(jnp.float32)
    top = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    got = jnp.take_along_axis(logits, probe[..., None], axis=-1)[..., 0]
    return best, got.astype(jnp.float32), top


def gaps(params, seqs, probes, *, dims: LMDims, eps: float, length: int,
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
