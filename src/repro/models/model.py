"""Unified model: one class covering all six assigned families.

Layer stacking uses ``jax.lax.scan`` over stacked per-layer parameters so the
compiled HLO is O(1 layer) regardless of depth (MaxText-style), with
per-layer remat when ``cfg.remat == "full"``.  Heterogeneous patterns use
*grouped* scans:

* dense / moe / ssm / audio-encoder — uniform scan over all layers;
* vlm (llama-3.2-vision)            — scan over groups of (cross_attn_every-1)
  self layers + 1 cross layer;
* hybrid (zamba2)                   — scan over groups of ``hybrid_attn_every``
  mamba2 layers, then ONE shared attention block (single param set, applied
  per group — closure constant, not scanned);
* audio (whisper)                   — encoder scan + decoder scan
  (self+cross+mlp per decoder layer).

Three entry points mirror the serving/training contract:
``forward`` (full-sequence logits), ``prefill`` (logits at the last position
+ populated cache), ``decode`` (one token against the cache).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .attention import layer_window
from .blocks import (NO_WINDOW, attn_block, attn_block_decode,
                     attn_block_layout, cross_block, cross_block_decode,
                     cross_block_layout, decoder_block, decoder_block_decode,
                     decoder_block_layout, norm_spec, ssm_block,
                     ssm_block_decode, ssm_block_layout)
from .common import (DTYPE, NO_SHARD, PSpec, ShardCtx, init_tree, rms_norm,
                     scan_or_loop, shapes_tree, stack_layout)


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # ------------------------------------------------------------------
    # parameter layout
    # ------------------------------------------------------------------
    def layout(self) -> Dict[str, Any]:
        cfg = self.cfg
        d, V = cfg.d_model, cfg.vocab_padded
        out: Dict[str, Any] = {
            "embed": PSpec((V, d), ("model", "fsdp"), init="embed"),
            "ln_f": norm_spec(cfg),
        }
        if not cfg.tie_embeddings:
            out["head"] = PSpec((d, V), ("fsdp", "model"))
        if cfg.family in ("dense", "moe"):
            out["layers"] = stack_layout(attn_block_layout(cfg), cfg.n_layers)
        elif cfg.family == "ssm":
            out["layers"] = stack_layout(ssm_block_layout(cfg), cfg.n_layers)
        elif cfg.family == "vlm":
            per = cfg.cross_attn_every
            n_groups = cfg.n_layers // per
            out["self_layers"] = stack_layout(
                stack_layout(attn_block_layout(cfg), per - 1), n_groups)
            out["cross_layers"] = stack_layout(cross_block_layout(cfg),
                                               n_groups)
        elif cfg.family == "hybrid":
            per = cfg.hybrid_attn_every
            n_groups = cfg.n_layers // per
            out["ssm_layers"] = stack_layout(
                stack_layout(ssm_block_layout(cfg), per), n_groups)
            out["shared_attn"] = attn_block_layout(cfg)  # ONE shared set
        elif cfg.family == "audio":
            out["enc_layers"] = stack_layout(attn_block_layout(cfg),
                                             cfg.n_layers)
            out["ln_enc"] = norm_spec(cfg)
            out["dec_layers"] = stack_layout(decoder_block_layout(cfg),
                                             cfg.n_layers)
        else:
            raise ValueError(cfg.family)
        return out

    def init(self, rng) -> Any:
        return init_tree(rng, self.layout())

    def param_shapes(self) -> Any:
        return shapes_tree(self.layout())

    # ------------------------------------------------------------------
    # helpers
    # ------------------------------------------------------------------
    def _windows(self) -> Optional[jnp.ndarray]:
        cfg = self.cfg
        if cfg.sliding_window is None:
            return None
        return jnp.asarray(
            [layer_window(cfg, i) or NO_WINDOW
             for i in range(cfg.n_layers)], dtype=jnp.int32)

    def _embed(self, params, tokens, ctx: ShardCtx) -> jnp.ndarray:
        x = jnp.take(params["embed"], tokens, axis=0).astype(DTYPE)
        return ctx.constrain(x, ctx.batch_axes(), None, None)

    def _scan(self, body, carry, xs, *, remat: Optional[bool] = None):
        cfg = self.cfg
        return scan_or_loop(
            body, carry, xs, unroll=not cfg.scan_layers,
            remat=(cfg.remat == "full") if remat is None else remat)

    def head_matrix(self, params) -> jnp.ndarray:
        return (params["embed"].T if self.cfg.tie_embeddings
                else params["head"])

    def _logits(self, params, x, ctx: ShardCtx) -> jnp.ndarray:
        logits = x @ self.head_matrix(params)
        logits = ctx.constrain(logits, ctx.batch_axes(), None, "model")
        if self.cfg.vocab_padded != self.cfg.vocab_size:
            logits = logits[..., :self.cfg.vocab_size]
        return logits

    # ------------------------------------------------------------------
    # full-sequence forward (train / prefill)
    # ------------------------------------------------------------------
    def forward(self, params, batch: Dict[str, jnp.ndarray], *,
                ctx: ShardCtx = NO_SHARD
                ) -> Tuple[jnp.ndarray, Dict[str, Any], jnp.ndarray]:
        """-> (logits (B,S,V), cache, aux_loss).  batch keys: tokens, and
        family extras (images / frames)."""
        x, cache, aux = self.forward_hidden(params, batch, ctx=ctx)
        return self._logits(params, x, ctx), cache, aux

    def forward_hidden(self, params, batch: Dict[str, jnp.ndarray], *,
                       ctx: ShardCtx = NO_SHARD
                       ) -> Tuple[jnp.ndarray, Dict[str, Any], jnp.ndarray]:
        """-> (final-norm hidden states (B,S,D), cache, aux_loss).

        The training loss applies the LM head in sequence chunks (see
        ``launch.steps.chunked_cross_entropy``) so full (B,S,V) logits are
        never materialized."""
        cfg = self.cfg
        fam = cfg.family
        if fam in ("dense", "moe"):
            x, cache, aux = self._forward_uniform_attn(params, batch, ctx)
        elif fam == "ssm":
            x, cache, aux = self._forward_ssm(params, batch, ctx)
        elif fam == "vlm":
            x, cache, aux = self._forward_vlm(params, batch, ctx)
        elif fam == "hybrid":
            x, cache, aux = self._forward_hybrid(params, batch, ctx)
        elif fam == "audio":
            x, cache, aux = self._forward_audio(params, batch, ctx)
        else:
            raise ValueError(fam)
        return rms_norm(x, params["ln_f"], cfg.norm_eps), cache, aux

    def _forward_uniform_attn(self, params, batch, ctx):
        cfg = self.cfg
        x = self._embed(params, batch["tokens"], ctx)
        windows = self._windows()

        def body(x, layer):
            if windows is None:
                p = layer
                w = None
            else:
                p, w = layer
            x, kv, aux = attn_block(p, x, cfg, window=w, ctx=ctx)
            return x, (kv["k"], kv["v"], aux)

        xs = params["layers"] if windows is None else (params["layers"],
                                                       windows)
        x, (ks, vs, auxs) = self._scan(body, x, xs)
        cache = {"k": ks, "v": vs,
                 "len": jnp.full((x.shape[0],), x.shape[1], jnp.int32)}
        return x, cache, jnp.sum(auxs)

    def _forward_ssm(self, params, batch, ctx):
        cfg = self.cfg
        x = self._embed(params, batch["tokens"], ctx)

        def body(x, p):
            x, cache = ssm_block(p, x, cfg, ctx=ctx)
            return x, cache

        x, caches = self._scan(body, x, params["layers"])
        caches["len"] = jnp.full((x.shape[0],), x.shape[1], jnp.int32)
        return x, caches, jnp.float32(0.0)

    def _forward_vlm(self, params, batch, ctx):
        cfg = self.cfg
        x = self._embed(params, batch["tokens"], ctx)
        memory = batch["images"].astype(DTYPE)  # (B, P, D) stub frontend

        def group(x, layers):
            self_p, cross_p = layers

            def inner(x, p):
                x, kv, aux = attn_block(p, x, cfg, ctx=ctx)
                return x, (kv["k"], kv["v"], aux)

            x, (ks, vs, auxs) = self._scan(inner, x, self_p, remat=False)
            x, xkv = cross_block(cross_p, x, memory, cfg, ctx=ctx)
            return x, (ks, vs, xkv["k"], xkv["v"], jnp.sum(auxs))

        x, (ks, vs, xks, xvs, auxs) = self._scan(
            group, x, (params["self_layers"], params["cross_layers"]))
        cache = {"k": ks, "v": vs, "xk": xks, "xv": xvs,
                 "len": jnp.full((x.shape[0],), x.shape[1], jnp.int32)}
        return x, cache, jnp.sum(auxs)

    def _forward_hybrid(self, params, batch, ctx):
        cfg = self.cfg
        x = self._embed(params, batch["tokens"], ctx)
        shared = params["shared_attn"]

        def group(x, ssm_p):
            def inner(x, p):
                x, cache = ssm_block(p, x, cfg, ctx=ctx)
                return x, cache

            x, caches = self._scan(inner, x, ssm_p, remat=False)
            x, kv, aux = attn_block(shared, x, cfg, ctx=ctx)
            return x, (caches, kv["k"], kv["v"], aux)

        x, (mcaches, ks, vs, auxs) = self._scan(
            group, x, params["ssm_layers"])
        cache = {"m": mcaches, "attn_k": ks, "attn_v": vs,
                 "len": jnp.full((x.shape[0],), x.shape[1], jnp.int32)}
        return x, cache, jnp.sum(auxs)

    def _forward_audio(self, params, batch, ctx):
        cfg = self.cfg
        frames = batch["frames"].astype(DTYPE)  # (B, S_enc, D) stub frontend
        frames = ctx.constrain(frames, ctx.batch_axes(), None, None)

        def enc_body(x, p):
            x, _, aux = attn_block(p, x, cfg, causal=False, ctx=ctx)
            return x, aux

        enc, enc_auxs = self._scan(enc_body, frames,
                                   params["enc_layers"])
        enc = rms_norm(enc, params["ln_enc"], cfg.norm_eps)

        x = self._embed(params, batch["tokens"], ctx)

        def dec_body(x, p):
            x, kv_self, kv_cross = decoder_block(p, x, enc, cfg, ctx=ctx)
            return x, (kv_self["k"], kv_self["v"], kv_cross["k"],
                       kv_cross["v"])

        x, (ks, vs, xks, xvs) = self._scan(dec_body, x,
                                           params["dec_layers"])
        cache = {"k": ks, "v": vs, "xk": xks, "xv": xvs,
                 "len": jnp.full((x.shape[0],), x.shape[1], jnp.int32)}
        return x, cache, jnp.sum(enc_auxs)

    # ------------------------------------------------------------------
    # prefill: full forward, but return (last-position logits, cache)
    # ------------------------------------------------------------------
    def prefill(self, params, batch, *, max_len: Optional[int] = None,
                ctx: ShardCtx = NO_SHARD):
        logits, cache, _ = self.forward(params, batch, ctx=ctx)
        cache = self._grow_cache(cache, max_len)
        return logits[:, -1:, :], cache

    def _grow_cache(self, cache, max_len: Optional[int]):
        """Pad attention KV caches along the sequence dim to max_len."""
        if max_len is None:
            return cache

        def grow(path_leaf):
            return path_leaf

        def pad_seq(x, seq_axis):
            pad = max_len - x.shape[seq_axis]
            if pad <= 0:
                return x
            widths = [(0, 0)] * x.ndim
            widths[seq_axis] = (0, pad)
            return jnp.pad(x, widths)

        out = dict(cache)
        for key in ("k", "v", "attn_k", "attn_v"):
            if key in out:
                # (..., B, S, H, hd): seq axis = -3
                out[key] = pad_seq(out[key], out[key].ndim - 3)
        return out

    # ------------------------------------------------------------------
    # decode: one token against the cache
    # ------------------------------------------------------------------
    def decode(self, params, cache, tokens, *, ctx: ShardCtx = NO_SHARD):
        """tokens (B,1) int32 -> (logits (B,1,V), new cache)."""
        cfg = self.cfg
        fam = cfg.family
        cur = cache["len"]
        x = self._embed(params, tokens, ctx)
        if fam in ("dense", "moe"):
            windows = self._windows()

            def body(x, layer):
                if windows is None:
                    p, ck, cv = layer
                    w = None
                else:
                    p, ck, cv, w = layer
                x, ck, cv = attn_block_decode(p, x, ck, cv, cur, cfg,
                                              window=w, ctx=ctx)
                return x, (ck, cv)

            xs = ((params["layers"], cache["k"], cache["v"])
                  if windows is None else
                  (params["layers"], cache["k"], cache["v"], windows))
            x, (ks, vs) = self._scan(body, x, xs, remat=False)
            new_cache = {"k": ks, "v": vs, "len": cur + 1}
        elif fam == "ssm":
            def body(x, layer):
                p, c = layer
                x, c = ssm_block_decode(p, x, c, cfg, ctx=ctx)
                return x, c

            mcache = {k: v for k, v in cache.items() if k != "len"}
            x, mc = self._scan(body, x, (params["layers"], mcache),
                               remat=False)
            new_cache = dict(mc)
            new_cache["len"] = cur + 1
        elif fam == "vlm":
            def group(x, layer):
                self_p, cross_p, ck, cv, xk, xv = layer

                def inner(x, l):
                    p, ck1, cv1 = l
                    x, ck1, cv1 = attn_block_decode(p, x, ck1, cv1, cur, cfg,
                                                    ctx=ctx)
                    return x, (ck1, cv1)

                x, (ks, vs) = self._scan(inner, x, (self_p, ck, cv),
                                         remat=False)
                x = cross_block_decode(cross_p, x, xk, xv, cfg, ctx=ctx)
                return x, (ks, vs)

            x, (ks, vs) = self._scan(
                group, x, (params["self_layers"], params["cross_layers"],
                           cache["k"], cache["v"], cache["xk"], cache["xv"]),
                remat=False)
            new_cache = {"k": ks, "v": vs, "xk": cache["xk"],
                         "xv": cache["xv"], "len": cur + 1}
        elif fam == "hybrid":
            shared = params["shared_attn"]

            def group(x, layer):
                ssm_p, mc, ck, cv = layer

                def inner(x, l):
                    p, c = l
                    x, c = ssm_block_decode(p, x, c, cfg, ctx=ctx)
                    return x, c

                x, mc = self._scan(inner, x, (ssm_p, mc), remat=False)
                x, ck, cv = attn_block_decode(shared, x, ck, cv, cur, cfg,
                                              ctx=ctx)
                return x, (mc, ck, cv)

            x, (mc, ks, vs) = self._scan(
                group, x, (params["ssm_layers"], cache["m"],
                           cache["attn_k"], cache["attn_v"]), remat=False)
            new_cache = {"m": mc, "attn_k": ks, "attn_v": vs, "len": cur + 1}
        elif fam == "audio":
            def body(x, layer):
                p, ck, cv, xk, xv = layer
                x, ck, cv = decoder_block_decode(p, x, ck, cv, xk, xv, cur,
                                                 cfg, ctx=ctx)
                return x, (ck, cv)

            x, (ks, vs) = self._scan(
                body, x, (params["dec_layers"], cache["k"], cache["v"],
                          cache["xk"], cache["xv"]), remat=False)
            new_cache = {"k": ks, "v": vs, "xk": cache["xk"],
                         "xv": cache["xv"], "len": cur + 1}
        else:
            raise ValueError(fam)
        x = rms_norm(x, params["ln_f"], cfg.norm_eps)
        return self._logits(params, x, ctx), new_cache

    # ------------------------------------------------------------------
    # decode-cache layout (shapes + shardings) for dry-run construction
    # ------------------------------------------------------------------
    def cache_layout(self, batch: int, max_len: int) -> Dict[str, Any]:
        """PSpec tree describing a decode cache of capacity ``max_len``."""
        cfg = self.cfg
        hkv, hd = cfg.n_kv_heads, cfg.hd
        L = cfg.n_layers

        def kv(l_dims, S):
            # flash-decode layout: KV caches shard their SEQUENCE dim over
            # the model axis (works for any head count; decode attention
            # becomes partial-softmax + small all-reduces)
            return PSpec(tuple(l_dims) + (batch, S, hkv, hd),
                         (None,) * len(l_dims) +
                         (("data",), "model", None, None))

        def ssm_cache(l_dims):
            s = cfg.ssm
            di = s.d_inner(cfg.d_model)
            ld = tuple(l_dims)
            lspec = (None,) * len(l_dims)
            out = {
                "conv": PSpec(ld + (batch, s.d_conv - 1, di),
                              lspec + (("data",), None, "model")),
                "h": PSpec(ld + (batch, di, s.d_state),
                           lspec + (("data",), "model", None),
                           dtype=jnp.float32),
            }
            if s.version == 2:
                out["convBC"] = PSpec(ld + (batch, s.d_conv - 1,
                                            2 * s.d_state),
                                      lspec + (("data",), None, None))
            return out

        ln = PSpec((batch,), (None,), dtype=jnp.int32)
        fam = cfg.family
        if fam in ("dense", "moe"):
            return {"k": kv((L,), max_len), "v": kv((L,), max_len),
                    "len": ln}
        if fam == "ssm":
            d = ssm_cache((L,))
            d["len"] = ln
            return d
        if fam == "vlm":
            per = cfg.cross_attn_every
            G = L // per
            return {"k": kv((G, per - 1), max_len),
                    "v": kv((G, per - 1), max_len),
                    "xk": kv((G,), cfg.n_image_tokens),
                    "xv": kv((G,), cfg.n_image_tokens),
                    "len": ln}
        if fam == "hybrid":
            per = cfg.hybrid_attn_every
            G = L // per
            return {"m": ssm_cache((G, per)),
                    "attn_k": kv((G,), max_len),
                    "attn_v": kv((G,), max_len),
                    "len": ln}
        if fam == "audio":
            return {"k": kv((L,), max_len), "v": kv((L,), max_len),
                    "xk": kv((L,), max_len), "xv": kv((L,), max_len),
                    "len": ln}
        raise ValueError(fam)
