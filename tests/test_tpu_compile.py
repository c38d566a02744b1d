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
#: the benchmark's expert model: one 4-layer stage of Mellum2-12B-A2.5B at
#: its published widths, prompts to 4,096 positions
MOE = kv.LMSpec(vocab=98304, n_heads=32, n_kv_heads=4, head_dim=128,
                n_layers=4, max_len=4224, d_model=2304,
                windows=(1024, 1024, 1024, None), n_experts=64, top_k=8,
                expert_width=896)
MOE_PROMPT = 4096


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
    return {k: _sds(chip, s) for k, s in kv.param_shapes(spec).items()}


def _flash(chip):
    q = _sds(chip, (SLOTS, PROMPT, SPEC.n_heads, SPEC.head_dim))
    k = _sds(chip, (SLOTS, PROMPT, SPEC.n_kv_heads, SPEC.head_dim))
    return ops.flash_attention_op.lower(q, k, k, causal=True,
                                        interpret=False)


def _flash_window(chip):
    """One windowed layer of the expert model's prefill, at 4,096
    positions."""
    q = _sds(chip, (1, MOE_PROMPT, MOE.n_heads, MOE.head_dim))
    k = _sds(chip, (1, MOE_PROMPT, MOE.n_kv_heads, MOE.head_dim))
    return ops.flash_attention_op.lower(q, k, k, causal=True,
                                        window=MOE.windows[0],
                                        interpret=False)


def _cache(chip, spec=SPEC):
    """The decode pellet's stacked ``(L, n_slots, max_len, Hkv, hd)``
    cache.  The lowerings pass this one shape for both caches: ``.lower()``
    takes shapes, not buffers, so donating it twice is no fault here, as
    it would be for one array passed as both."""
    return _sds(chip, (spec.n_layers, SLOTS, spec.max_len,
                       spec.n_kv_heads, spec.head_dim))


def _decode_attention(chip):
    q = _sds(chip, (SLOTS, SPEC.n_heads, SPEC.head_dim))
    cache = _cache(chip)
    lengths = _sds(chip, (SLOTS,), jnp.int32)
    layer = _sds(chip, (), jnp.int32)
    return ops.decode_attention_op.lower(q, cache, cache, lengths, layer,
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
    cache = _cache(chip)
    slots = _sds(chip, (SLOTS,), jnp.int32)
    return kv.decode_step.lower(_param_shapes(chip, SPEC), cache, cache,
                                slots, slots, spec=SPEC, interpret=False)


def _moe_decode(chip):
    L, E, D, F = MOE.n_layers, MOE.n_experts, MOE.d_model, MOE.expert_width
    return ops.moe_decode_op.lower(
        _sds(chip, (SLOTS, D)), _sds(chip, (SLOTS, MOE.top_k), jnp.int32),
        _sds(chip, (SLOTS, MOE.top_k)), _sds(chip, (SLOTS,), jnp.bool_),
        _sds(chip, (L, E, D, F)), _sds(chip, (L, E, D, F)),
        _sds(chip, (L, E, F, D)), _sds(chip, (), jnp.int32),
        interpret=False)


def _moe_prefill(chip):
    return kv.prefill.lower(_param_shapes(chip, MOE),
                            _sds(chip, (SLOTS, MOE_PROMPT), jnp.int32),
                            _sds(chip, (SLOTS,), jnp.int32),
                            spec=MOE, interpret=False)


def _moe_decode_step(chip):
    cache = _cache(chip, MOE)
    slots = _sds(chip, (SLOTS,), jnp.int32)
    return kv.decode_step.lower(_param_shapes(chip, MOE), cache, cache,
                                slots, slots, _sds(chip, (SLOTS,), jnp.bool_),
                                spec=MOE, interpret=False)


def _splice(chip, rows=3):
    """The decode pellet's splice of a carrier of ``rows`` prefill rows."""
    cache = _cache(chip)
    carrier = _sds(chip, (rows, SPEC.n_layers, SPEC.max_len,
                          SPEC.n_kv_heads, SPEC.head_dim))
    return kv.splice.lower(cache, cache, _sds(chip, (rows,), jnp.int32),
                           carrier, carrier)


#: the Pallas kernel each program calls, by the ``name=`` of its
#: ``pallas_call``: the device trace names the kernel's operation so
KERNEL_OF = {_flash: "flash_attention", _flash_window: "flash_attention",
             _decode_attention: "decode_attention",
             _cluster_distance: "cluster_distance",
             _prefill: "flash_attention", _decode_step: "decode_attention",
             _moe_decode: "moe_decode", _moe_prefill: "flash_attention",
             _moe_decode_step: "moe_decode"}


@pytest.mark.timeout(240)
@pytest.mark.parametrize("lower", [_flash, _decode_attention,
                                   _cluster_distance, _prefill,
                                   _decode_step, _moe_decode, _moe_prefill,
                                   _moe_decode_step, _flash_window],
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


def _results(text, shape):
    """Instructions of compiled HLO ``text`` whose result is ``shape``
    (layout aside), as ``(name, opcode)``."""
    pat = rf"^\s*(?:ROOT )?%(\S+) = {re.escape(shape)}(?:\{{[^}}]*\}})? " \
          rf"([\w-]+)\("
    return re.findall(pat, text, re.M)


def _whole(spec):
    """The stacked cache's result shape in compiled HLO text."""
    return f"f32[{spec.n_layers},{SLOTS},{spec.max_len}," \
           f"{spec.n_kv_heads},{spec.head_dim}]"


@pytest.mark.timeout(240)
@pytest.mark.parametrize("lower, spec", [(_decode_step, SPEC),
                                         (_moe_decode_step, MOE)],
                         ids=["dense", "expert"])
def test_decode_step_reads_the_cache_in_place(one_chip, lower, spec):
    """The flash-decode kernel reads the stacked caches where they lie:
    no per-layer relayout of one layer's cache to ``(B, Hkv, S, hd)``;
    the step writes its new rows into the donated caches, with no
    whole-cache copy; nor does its q operand cost a cast of ``wq``."""
    text = lower(one_chip).compile().as_text()
    relayout = f"f32[{SLOTS},{spec.n_kv_heads},{spec.max_len}," \
               f"{spec.head_dim}]"
    assert _results(text, relayout) == []
    whole = _results(text, _whole(spec))
    assert whole, "the pattern matched no cache at all"
    assert [name for name, op in whole if op == "copy"] == []
    # the q projection stays one fusion on the f32 weight: handed queries
    # as (B, H, hd), XLA writes a bf16 copy of every layer's wq first
    entry = text[text.index("\nENTRY "):]
    wq_bf16 = f"bf16[{spec.d_model},{spec.n_heads * spec.head_dim}]"
    assert _results(entry, wq_bf16) == []


@pytest.mark.timeout(240)
@pytest.mark.parametrize("rows", [1, SLOTS])
def test_splice_writes_the_cache_in_place(one_chip, rows):
    """Admitting a carrier writes its rows into the donated caches: no
    whole-cache copy, for one row or every slot."""
    whole = _results(_splice(one_chip, rows).compile().as_text(),
                     _whole(SPEC))
    assert whole, "the pattern matched no cache at all"
    assert [name for name, op in whole if op == "copy"] == []


@pytest.mark.timeout(240)
def test_expert_decode_step_reads_experts_in_place(one_chip):
    """The decode expert kernel reads the stacked ``(L, E, D, F)`` experts
    where they lie: no layer's experts are sliced or copied out, and each
    layer calls the kernel once."""
    text = _moe_decode_step(one_chip).compile().as_text()
    E, D, F = MOE.n_experts, MOE.d_model, MOE.expert_width
    for shape in (f"f32[{E},{D},{F}]", f"f32[{E},{F},{D}]"):
        assert _results(text, shape) == [], shape
    calls = re.findall(r"%moe_decode(?:\.\d+)? = [^\n]*custom-call\(", text)
    assert len(calls) == MOE.n_layers
