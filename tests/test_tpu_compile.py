"""Compile rehearsals for one TPU v5e chip, with no chip attached.

The TPU compiler is installed beside JAX, so the main path's kernels and
jitted serving steps are compiled here, at the widths ``chip_smoke.py``
runs on the chip, for a described (not attached) ``v5e:2x2`` topology.
That refuses what interpret mode cannot see — misaligned tiles, too much
VMEM, a program larger than the device — at no chip time.  A compile that
passes is not a chip run: nothing executes, so nothing here is a time.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and a module that touched it while
being collected would give pytest-xdist workers different test sets.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from testkit import load_chip_smoke
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.serving import kv

#: bytes one v5e chip holds
DEVICE_BYTES = 16e9
SPEC = load_chip_smoke().QWEN3_1_7B
SLOTS, PROMPT = 8, 128


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compile cache
    off: entries compiled for a described chip cannot be read back here."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _sds(chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def _param_shapes(chip, spec):
    """``kv.init_params``'s layout as shapes only (no host arrays)."""
    V, D, L = spec.vocab, spec.d_model, spec.n_layers
    Q, KV = spec.n_heads * spec.head_dim, spec.n_kv_heads * spec.head_dim
    F = spec.ffn_mult * D
    shapes = {"embed": (V, D), "head": (V, D), "wq": (L, D, Q),
              "wk": (L, D, KV), "wv": (L, D, KV), "wo": (L, Q, D),
              "w1": (L, D, F), "w2": (L, F, D), "ln1": (L, D),
              "ln2": (L, D), "ln_f": (D,)}
    return {k: _sds(chip, s) for k, s in shapes.items()}


def _flash(chip):
    q = _sds(chip, (SLOTS, PROMPT, SPEC.n_heads, SPEC.head_dim))
    k = _sds(chip, (SLOTS, PROMPT, SPEC.n_kv_heads, SPEC.head_dim))
    return ops.flash_attention_op.lower(q, k, k, causal=True,
                                        interpret=False)


def _decode_attention(chip):
    q = _sds(chip, (SLOTS, SPEC.n_heads, SPEC.head_dim))
    cache = _sds(chip, (SLOTS, SPEC.max_len, SPEC.n_kv_heads,
                        SPEC.head_dim))
    lengths = _sds(chip, (SLOTS,), jnp.int32)
    return ops.decode_attention_op.lower(q, cache, cache, lengths,
                                         interpret=False)


def _cluster_distance(chip):
    return ops.cluster_distance_op.lower(_sds(chip, (4096, 384)),
                                         _sds(chip, (256, 384)),
                                         interpret=False)


def _prefill(chip):
    return kv.prefill.lower(_param_shapes(chip, SPEC),
                            _sds(chip, (SLOTS, PROMPT), jnp.int32),
                            _sds(chip, (SLOTS,), jnp.int32),
                            spec=SPEC, interpret=False)


def _decode_step(chip):
    cache = _sds(chip, (SPEC.n_layers, SLOTS, SPEC.max_len,
                        SPEC.n_kv_heads, SPEC.head_dim))
    slots = _sds(chip, (SLOTS,), jnp.int32)
    return kv.decode_step.lower(_param_shapes(chip, SPEC), cache, cache,
                                slots, slots, spec=SPEC, interpret=False)


#: the Pallas kernel each program calls, by the ``name=`` of its
#: ``pallas_call``: the device trace names the kernel's operation so
KERNEL_OF = {_flash: "flash_attention", _decode_attention: "decode_attention",
             _cluster_distance: "cluster_distance",
             _prefill: "flash_attention", _decode_step: "decode_attention"}


@pytest.mark.timeout(240)
@pytest.mark.parametrize("lower", [_flash, _decode_attention,
                                   _cluster_distance, _prefill,
                                   _decode_step],
                         ids=lambda f: f.__name__.lstrip("_"))
def test_compiles_for_one_v5e_chip(one_chip, lower):
    compiled = lower(one_chip).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    kernel = KERNEL_OF[lower]
    assert re.search(rf"%{kernel}(\.\d+)? = [^\n]*custom-call\(", text), \
        f"no custom call named {kernel!r}"
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < DEVICE_BYTES, f"{total} bytes do not fit one chip"
