"""The decode step and the slot splice write into the caches they are
handed (donated buffers), and donating changes no value: the same inputs
give bitwise the same logits and caches as the same program without
donation, at a tiny size, dense and expert, on the CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serving import LMSpec, kv
from repro.serving.dataflow import DecodePellet, PrefillPellet

DENSE = LMSpec(vocab=16, n_heads=2, n_kv_heads=1, head_dim=4, n_layers=2,
               max_len=16)
EXPERT = LMSpec(vocab=32, n_heads=4, n_kv_heads=2, head_dim=8, n_layers=2,
                max_len=24, d_model=40, windows=(8, None), n_experts=8,
                top_k=2, expert_width=16)
SPECS = pytest.mark.parametrize("spec", [DENSE, EXPERT],
                                ids=["dense", "expert"])
#: the decode step as one program that donates nothing
UNDONATED = jax.jit(kv.decode_step.__wrapped__,
                    static_argnames=("spec", "interpret"))


def _prefilled(spec, lengths=(3, 5, 1)):
    """Caches of ``len(lengths)`` prompts, their lengths and first tokens."""
    params = kv.init_params(spec, 4)
    B = len(lengths)
    rng = np.random.default_rng(9)
    tokens = jnp.asarray(rng.integers(1, spec.vocab, (B, 8)), jnp.int32)
    lens = jnp.asarray(lengths, jnp.int32)
    logits, kc, vc = kv.prefill(params, tokens, lens, spec=spec,
                                interpret=True)
    return params, kc, vc, lens, kv.greedy(logits)


def _live(spec, B):
    return (jnp.asarray([True, False, True][:B]),) if spec.n_experts else ()


@SPECS
def test_decode_step_consumes_its_caches_and_changes_no_value(spec):
    params, kc, vc, lens, tok = _prefilled(spec)
    live = _live(spec, 3)
    want = UNDONATED(params, jnp.copy(kc), jnp.copy(vc), lens, tok, *live,
                     spec=spec, interpret=True)
    got = kv.decode_step(params, kc, vc, lens, tok, *live, spec=spec,
                         interpret=True)
    assert kc.is_deleted() and vc.is_deleted()
    assert not lens.is_deleted() and not tok.is_deleted()
    assert all(not x.is_deleted() for x in live)
    assert len(got) == len(want) == (4 if spec.n_experts else 3)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_decode_step_refuses_one_buffer_as_both_caches():
    params, kc, _, lens, tok = _prefilled(DENSE)
    with pytest.raises(ValueError, match="donate"):
        kv.decode_step(params, kc, kc, lens, tok, spec=DENSE, interpret=True)


@pytest.mark.parametrize("slots", [[2], [3, 0, 1]], ids=["one", "several"])
def test_splice_equals_a_column_write_and_consumes_the_caches(slots):
    rng = np.random.default_rng(len(slots))
    L, S, Hkv, hd = (DENSE.n_layers, DENSE.max_len, DENSE.n_kv_heads,
                     DENSE.head_dim)
    k, v = (jnp.asarray(rng.normal(size=(L, 4, S, Hkv, hd)), jnp.float32)
            for _ in range(2))
    rk, rv = (jnp.asarray(rng.normal(size=(len(slots), L, S, Hkv, hd)),
                          jnp.float32) for _ in range(2))
    idx = np.asarray(slots, np.int32)
    want_k = k.at[:, idx].set(jnp.moveaxis(rk, 0, 1))
    want_v = v.at[:, idx].set(jnp.moveaxis(rv, 0, 1))
    got_k, got_v = kv.splice(k, v, idx, rk, rv)
    assert k.is_deleted() and v.is_deleted()
    assert not rk.is_deleted() and not rv.is_deleted()
    np.testing.assert_array_equal(np.asarray(got_k), np.asarray(want_k))
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))


def _carrier(spec, params):
    """A prefill carrier of three requests for slots 2, 0 and 1."""
    cols = {"rid": np.arange(3), "slot": np.asarray([2, 0, 1]),
            "length": np.asarray([3, 5, 2]), "budget": np.full(3, 6),
            "t_sub": np.zeros(3),
            "tokens": np.random.default_rng(1).integers(
                1, spec.vocab, (3, 8))}
    return PrefillPellet(params, spec).compute_array(cols)


@SPECS
def test_a_stepped_pellet_snapshots_its_current_caches(spec):
    """``get_state`` after splices and steps reads the caches the pellet
    holds now, not a buffer a step consumed, and a pellet restored from it
    steps on as the original does."""
    params = kv.init_params(spec, 4)
    carrier = _carrier(spec, params)
    a = DecodePellet(params, spec, n_slots=3)
    # the row-wise splice lands as the carrier's column write does
    b = DecodePellet(params, spec, n_slots=3)
    first = a.k
    a.compute_array(carrier)
    for i in range(3):
        b.compute({name: col[i] for name, col in carrier.items()})
    assert first.is_deleted()
    np.testing.assert_array_equal(np.asarray(a.k), np.asarray(b.k))
    for _ in range(2):
        spliced = a.k
        a._step([])
        assert spliced.is_deleted()
    snap = a.get_state()
    np.testing.assert_array_equal(snap["k"], np.asarray(a.k))
    np.testing.assert_array_equal(snap["v"], np.asarray(a.v))
    c = DecodePellet(params, spec, n_slots=3)
    c.set_state(snap)
    out_a, out_c = [], []
    while a.live.any():
        a._step(out_a)
        c._step(out_c)
    assert [e.payload["tokens"] for e in out_a if e.port == "out"] == \
        [e.payload["tokens"] for e in out_c if e.port == "out"]
    np.testing.assert_array_equal(np.asarray(a.k), np.asarray(c.k))
