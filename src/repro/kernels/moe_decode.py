"""Pallas TPU dropless decode expert kernel: a decode step's routed experts,
each read from HBM once.

At decode a step holds one token a slot, and its top-k picks land on a few
dozen of the layer's experts.  The step is memory-bound: what it must move
is the weights of the experts that some live slot picked, and no other
byte.  So the kernel walks the step's *distinct* experts, not its picks:

* the sorted list of picked experts arrives by scalar prefetch, and its
  length sets the grid's first axis (a traced grid size, as megablox's
  ``gmm`` uses), so no step runs for an expert nobody picked;
* grid = (picked experts, expert-width tiles): one step brings one tile of
  the expert's gate, up and down matrices into VMEM and multiplies every
  slot's row by it; each slot's row is weighted by its entry of the dense
  ``(B, E)`` combine matrix (0 where the slot did not pick the expert) and
  accumulates into the resident ``(B, D)`` output;
* the expert weights are the serving plane's stacked ``(L, E, D, F)`` /
  ``(L, E, F, D)`` arrays, read in place at the layer that arrives by
  scalar prefetch, so no slice of a layer's experts runs in front of it.

Nothing is dropped: every pick of every live slot is computed, whatever
else shares the batch.  Dead slots pick nothing, so their experts are not
fetched and their output rows are 0.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: widest expert-width tile: three f32 tiles of a 2304-wide expert at
#: 128 columns are 3.5 MB, 7 MB double-buffered
MAX_BLOCK_F = 128


def expert_block_f(d_ff: int) -> int:
    """Expert-width columns per grid step: the widest multiple of 128 up to
    ``MAX_BLOCK_F`` that divides ``d_ff``, else the whole width."""
    for b in range(MAX_BLOCK_F, 127, -128):
        if d_ff % b == 0:
            return b
    return d_ff


def fetch_list(expert_ids: jnp.ndarray, live: jnp.ndarray, n_experts: int
               ) -> tuple:
    """All ``n_experts`` ids, the experts live slots picked first and in
    ascending order, and how many they are.

    ``expert_ids (B, k)`` are each slot's picks; ``live (B,)`` masks the
    slots whose picks count."""
    B = expert_ids.shape[0]
    picked = jnp.zeros((B, n_experts), bool).at[
        jnp.arange(B)[:, None], expert_ids].set(True)
    used = jnp.any(picked & live[:, None], axis=0)
    order = jnp.argsort(~used, stable=True).astype(jnp.int32)
    return order, jnp.sum(used, dtype=jnp.int32)


def combine_matrix(expert_ids: jnp.ndarray, weights: jnp.ndarray,
                   live: jnp.ndarray, n_experts: int) -> jnp.ndarray:
    """Dense ``(B, E)`` combine weights: each live slot's renormalised
    top-k weights at its picked experts, 0 elsewhere and on dead slots."""
    B = expert_ids.shape[0]
    w = jnp.where(live[:, None], weights, 0.0).astype(jnp.float32)
    return jnp.zeros((B, n_experts), jnp.float32).at[
        jnp.arange(B)[:, None], expert_ids].add(w)


def _moe_decode_kernel(layer_ref, ids_ref, n_ref, x_ref, c_ref, wg_ref,
                       wu_ref, wd_ref, o_ref):
    del layer_ref                                # used by the index_maps
    i = pl.program_id(0)
    f = pl.program_id(1)

    @pl.when((i == 0) & (f == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(i < n_ref[0])
    def _expert():
        x = x_ref[...].astype(jnp.float32)                   # (B, D)
        g = jax.lax.dot_general(x, wg_ref[...].astype(jnp.float32),
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        u = jax.lax.dot_general(x, wu_ref[...].astype(jnp.float32),
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        # this expert's column of the combine matrix, as (B, 1)
        c = c_ref[...]
        col = jax.lax.broadcasted_iota(jnp.int32, c.shape, 1)
        w = jnp.sum(jnp.where(col == ids_ref[i], c, 0.0), axis=1,
                    keepdims=True)
        a = g * (1.0 / (1.0 + jnp.exp(-g))) * u * w          # (B, tf)
        o_ref[...] += jax.lax.dot_general(
            a, wd_ref[...].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def moe_decode(x: jnp.ndarray, expert_ids: jnp.ndarray,
               weights: jnp.ndarray, live: jnp.ndarray, w_gate: jnp.ndarray,
               w_up: jnp.ndarray, w_down: jnp.ndarray, layer, *,
               interpret: bool = False) -> tuple:
    """x (B, D); expert_ids/weights (B, k), the renormalised top-k; live
    (B,) bool; w_gate/w_up (L, E, D, F) and w_down (L, E, F, D), read at
    ``layer`` -> (y (B, D) f32, experts fetched () int32)."""
    B, D = x.shape
    _, E, _, F = w_gate.shape
    bf = expert_block_f(F)
    nf = F // bf
    ids, n = fetch_list(expert_ids, live, E)
    comb = combine_matrix(expert_ids, weights, live, E)

    def tile(i, f, n_):
        # with no expert picked, the one row of steps stays on one tile
        return jnp.where(i < n_[0], f, 0)

    def w_in_map(i, f, lay, ids_, n_):
        return (lay[0], ids_[i], 0, tile(i, f, n_))

    def w_out_map(i, f, lay, ids_, n_):
        return (lay[0], ids_[i], tile(i, f, n_), 0)

    def whole(i, f, lay, ids_, n_):
        return (0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        # one row of steps even with no expert picked, so that the output
        # is zeroed; ``pl.when`` skips its compute
        grid=(jnp.maximum(n, 1), nf),
        in_specs=[pl.BlockSpec((B, D), whole),
                  pl.BlockSpec((B, E), whole),
                  pl.BlockSpec((None, None, D, bf), w_in_map),
                  pl.BlockSpec((None, None, D, bf), w_in_map),
                  pl.BlockSpec((None, None, bf, D), w_out_map)],
        out_specs=pl.BlockSpec((B, D), whole),
    )
    y = pl.pallas_call(
        _moe_decode_kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret, name="moe_decode",
    )(jnp.asarray(layer, jnp.int32).reshape(1), ids, n.reshape(1), x, comb,
      w_gate, w_up, w_down)
    return y, n
