"""The serving LM (``serving/kv.py``) spec by property, with no engine.

Every spec in ``testkit.SPECS`` is held to five properties, on the CPU with
interpreted kernels:

1. prefill, then greedy decode steps, gives the logits a one-shot prefill
   of the grown sequence gives;
2. a batch of slots at ragged lengths decodes as each slot does alone
   (the other slots dead, as the decode stage keeps them);
3. the kernel path's prefill matches its ``kernels.ref`` twin;
4. the kernel path's decode step matches its ``kernels.ref`` twin;
5. a prefilled row spliced into a live cache decodes as it does in a fresh
   cache, and the live slots decode on undisturbed.

Three prompts at 20, 6 and 13 tokens run past the 8-position windows, or
cross them while they decode.  Everything runs in float32 at highest
precision, so two paths differ by rounding, about 1e-6 of the values'
scale; a wrong mask, window, cache position or expert moves a logit by
its own scale.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from testkit import SPECS, spec_id

from repro.serving import kv

pytestmark = pytest.mark.parametrize("spec", SPECS, ids=spec_id)

#: float32 rounding on the CPU at highest precision, with room
TOL = 1e-4
LENGTHS = np.asarray([20, 6, 13], np.int32)
STEPS = 4
#: the prompts' padded width: the longest prompt and its STEPS tokens
WIDTH = 24
ALL = (True, True, True)
#: the ref twins, each one program as the kernel path's are
PREFILL_REF = jax.jit(kv.prefill_ref, static_argnames="spec")
DECODE_REF = jax.jit(kv.decode_step_ref, static_argnames="spec")


def _close(got, want):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOL,
                               rtol=TOL)


def _live_arg(spec, live):
    """An expert step's live mask; a dense step takes none."""
    return (jnp.asarray(live),) if spec.n_experts else ()


@functools.lru_cache(maxsize=None)
def _setup(spec):
    """The spec's weights and the three prompts, padded to WIDTH."""
    rng = np.random.default_rng(3)
    tokens = np.zeros((3, WIDTH), np.int32)
    for i, n in enumerate(LENGTHS):
        tokens[i, :n] = rng.integers(1, spec.vocab, n)
    return kv.init_params(spec, 1), tokens


def _prefill(spec, live, path=kv.prefill):
    """The live rows' prompts prefilled; a dead row holds one pad token,
    as a dead decode slot does.  Returns (logits, k, v, lengths)."""
    params, tokens = _setup(spec)
    live = np.asarray(live)
    lens = np.where(live, LENGTHS, 1).astype(np.int32)
    toks = np.where(live[:, None], tokens, 0)
    kwargs = {} if path is PREFILL_REF else {"interpret": True}
    with jax.default_matmul_precision("highest"):
        logits, kc, vc = path(params, jnp.asarray(toks), jnp.asarray(lens),
                              spec=spec, **kwargs)
    return logits, kc, vc, lens


def _decode(spec, kc, vc, lens, tok, live, steps):
    """``steps`` greedy kernel-path decode steps of the live rows from the
    given caches (which the steps consume).  Returns the logits of each
    step ``(steps, B, V)``, the tokens fed at each step ``(steps, B)``, the
    token each row feeds next and the caches."""
    params, _ = _setup(spec)
    live = np.asarray(live)
    lens, logits, fed = lens.copy(), [], []
    for _ in range(steps):
        fed.append(tok)
        with jax.default_matmul_precision("highest"):
            lg, kc, vc, *_ = kv.decode_step(
                params, kc, vc, jnp.asarray(lens), jnp.asarray(tok),
                *_live_arg(spec, live), spec=spec, interpret=True)
        logits.append(np.asarray(lg))
        lens = lens + live
        tok = np.where(live, np.asarray(kv.greedy(lg)), 0).astype(np.int32)
    return np.stack(logits), np.stack(fed), tok, kc, vc


@functools.lru_cache(maxsize=None)
def _generate(spec, live, steps=STEPS):
    """Prefill the live rows, then ``steps`` greedy steps: the logits and
    fed tokens of ``_decode``, on the host."""
    logits, kc, vc, lens = _prefill(spec, live)
    tok = np.where(live, np.asarray(kv.greedy(logits)), 0).astype(np.int32)
    out, fed, *_ = _decode(spec, kc, vc, lens, tok, live, steps)
    return out, fed


def test_decode_continues_the_prefill(spec):
    """The last step's logits are those of a one-shot prefill of the
    prompts and every token the steps were fed."""
    params, tokens = _setup(spec)
    logits, fed = _generate(spec, ALL)
    grown = tokens.copy()
    for j in range(STEPS):
        grown[np.arange(3), LENGTHS + j] = fed[j]
    with jax.default_matmul_precision("highest"):
        want, _, _ = kv.prefill(params, jnp.asarray(grown),
                                jnp.asarray(LENGTHS + STEPS), spec=spec,
                                interpret=True)
    _close(logits[-1], want)


def test_ragged_batch_decodes_as_each_slot_alone(spec):
    batch, batch_fed = _generate(spec, ALL)
    for i in range(3):
        live = tuple(s == i for s in range(3))
        alone, alone_fed = _generate(spec, live)
        assert list(alone_fed[:, i]) == list(batch_fed[:, i])
        _close(batch[:, i], alone[:, i])


def test_prefill_kernels_match_the_reference(spec):
    params, _ = _setup(spec)
    assert {n: p.shape for n, p in params.items()} == kv.param_shapes(spec)
    got = _prefill(spec, ALL)
    want = _prefill(spec, ALL, PREFILL_REF)
    for g, w in zip(got[:3], want[:3]):       # logits, k, v
        _close(g, w)


def test_decode_step_kernels_match_the_reference(spec):
    """Three steps with a dead slot between two live ones: the live
    slots' logits, and an expert step's count of experts fetched, agree;
    a dense step returns three outputs, an expert step four."""
    params, _ = _setup(spec)
    live = np.asarray([True, False, True])
    logits, kr, vr, lens = _prefill(spec, live, PREFILL_REF)
    kc, vc = jnp.array(kr), jnp.array(vr)
    tok = np.where(live, np.asarray(kv.greedy(logits)), 0).astype(np.int32)
    for _ in range(3):
        args = (jnp.asarray(lens), jnp.asarray(tok), *_live_arg(spec, live))
        with jax.default_matmul_precision("highest"):
            got = kv.decode_step(params, kc, vc, *args, spec=spec,
                                 interpret=True)
            want = DECODE_REF(params, kr, vr, *args, spec=spec)
        assert len(got) == len(want) == 3 + bool(spec.n_experts)
        (lg, kc, vc, *n), (lr, kr, vr, *nr) = got, want
        _close(np.asarray(lg)[live], np.asarray(lr)[live])
        if spec.n_experts:
            assert list(n[0]) == list(nr[0])
            # two live slots pick at most 2 * top_k experts a layer
            assert all(1 <= int(c) <= 2 * spec.top_k for c in n[0])
        lens = lens + live
        tok = np.where(live, np.asarray(kv.greedy(lr)), 0).astype(np.int32)


def test_spliced_row_decodes_as_in_a_fresh_cache(spec):
    """Slots 0 and 2 decode two steps; then slot 1's prefilled row is
    spliced in (the caches donated) and all three decode on.  Slot 1's
    logits are those it has alone, and slots 0 and 2 go on as they would
    have without it."""
    live = np.asarray([True, False, True])
    logits, kc, vc, lens = _prefill(spec, live)
    tok = np.where(live, np.asarray(kv.greedy(logits)), 0).astype(np.int32)
    _, _, tok, kc, vc = _decode(spec, kc, vc, lens, tok, live, 2)
    lens = lens + 2 * live

    row_logits, kr, vr, _ = _prefill(spec, (False, True, False))
    spliced = kc
    kc, vc = kv.splice(kc, vc, np.asarray([1], np.int32),
                       jnp.moveaxis(kr, 0, 1)[1:2],
                       jnp.moveaxis(vr, 0, 1)[1:2])
    assert spliced.is_deleted()
    lens[1] = LENGTHS[1]
    tok[1] = int(kv.greedy(row_logits)[1])
    got, got_fed, *_ = _decode(spec, kc, vc, lens, tok, ALL, STEPS)

    alone, alone_fed = _generate(spec, (False, True, False))
    before, before_fed = _generate(spec, (True, False, True), 2 + STEPS)
    assert list(got_fed[:, 1]) == list(alone_fed[:, 1])
    _close(got[:, 1], alone[:, 1])
    for s in (0, 2):
        assert list(got_fed[:, s]) == list(before_fed[2:, s])
        _close(got[:, s], before[2:, s])
