"""Property-based tests (hypothesis) on the system's invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis; "
    "install the [test] extra")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.adaptation.simulator import SimPellet, simulate
from repro.adaptation.strategies import (DynamicAdaptation, Observation,
                                         PelletHints, static_allocation)
from repro.core import Message
from repro.core.patterns import HashSplit, stable_hash

SETTINGS = dict(max_examples=25, deadline=None)


# ---------------------------------------------------------------------------
# dynamic port mapping invariants
# ---------------------------------------------------------------------------

@settings(**SETTINGS)
@given(st.lists(st.one_of(st.text(max_size=8), st.integers(), st.tuples(
    st.integers(), st.text(max_size=4))), min_size=1, max_size=60),
    st.integers(min_value=1, max_value=12))
def test_hash_split_is_a_function_of_key(keys, n_edges):
    """Same key -> same edge, for any key type and edge count (§II.A)."""
    s = HashSplit()
    for key in keys:
        m1 = Message(payload="a", key=key)
        m2 = Message(payload="b", key=key)
        assert s.choose(m1, n_edges, [0] * n_edges) == \
            s.choose(m2, n_edges, [0] * n_edges)
        (e,) = s.choose(m1, n_edges, [0] * n_edges)
        assert 0 <= e < n_edges


@settings(**SETTINGS)
@given(st.integers(min_value=2, max_value=64))
def test_stable_hash_spreads(n_keys):
    edges = [stable_hash(("key", i)) % 8 for i in range(n_keys * 8)]
    counts = np.bincount(edges, minlength=8)
    assert counts.max() <= 3.5 * counts.mean()  # no catastrophic skew


# ---------------------------------------------------------------------------
# adaptation invariants (§III)
# ---------------------------------------------------------------------------

@settings(**SETTINGS)
@given(st.floats(min_value=0.0, max_value=500.0),
       st.integers(min_value=0, max_value=10000),
       st.floats(min_value=0.01, max_value=5.0),
       st.integers(min_value=0, max_value=32))
def test_dynamic_bounds_and_quiesce(rate, queue, latency, cores):
    d = DynamicAdaptation(max_cores=16)
    out = d.decide(Observation(0.0, queue, rate, latency, cores))
    assert 0 <= out <= 16
    if rate == 0 and queue == 0:
        assert out == 0                       # idle & drained -> quiesce


@settings(**SETTINGS)
@given(st.floats(min_value=1.0, max_value=100.0),
       st.floats(min_value=0.01, max_value=2.0))
def test_dynamic_reaches_fixed_point(rate, latency):
    """At a constant rate the controller settles (no flapping)."""
    d = DynamicAdaptation(max_cores=64)
    cores = 0
    history = []
    for _ in range(50):
        cores = d.decide(Observation(0.0, 0, rate, latency, cores))
        history.append(cores)
    assert len(set(history[-5:])) == 1        # fixed point reached
    # and the fixed point sustains the load
    cap = history[-1] * 4 / latency
    assert cap >= rate * 0.8 or history[-1] == 64


@settings(**SETTINGS)
@given(st.floats(min_value=1.0, max_value=1000.0),
       st.floats(min_value=0.001, max_value=2.0),
       st.floats(min_value=1.0, max_value=600.0))
def test_static_allocation_sustains_window(m1, latency, window):
    hints = [PelletHints(latency=latency)]
    (c,) = static_allocation(hints, m1, window, epsilon=0.0)
    # C cores = 4C instances must clear m1 messages within the window
    assert c * 4 * window / latency >= m1 * 0.999


@settings(max_examples=10, deadline=None)
@given(st.integers(min_value=0, max_value=10_000))
def test_simulator_conserves_messages(seed):
    rng = np.random.default_rng(seed)
    rate = float(rng.uniform(1, 30))
    p = SimPellet("p", latency=0.5)
    res = simulate([p], {"p": DynamicAdaptation(max_cores=32)},
                   lambda t: rate, horizon=120.0)
    offered = rate * 120.0
    assert p.processed_total <= offered + 1e-6
    assert abs((p.processed_total + p.queue) - offered) < rate + 1e-6


# ---------------------------------------------------------------------------
# numerics invariants
# ---------------------------------------------------------------------------

@settings(max_examples=8, deadline=None)
@given(st.integers(min_value=1, max_value=30),
       st.integers(min_value=0, max_value=50))
def test_ssm_scan_split_invariance(split, seed):
    """Scanning [0:split] then [split:] with the carried state equals the
    full scan — the state object is a faithful stream summary (the paper's
    stateful-pellet semantics)."""
    from repro.kernels import ref
    B, S, di, N = 1, 32, 8, 4
    split = min(split, S - 1)
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (B, S, di))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, di)))
    A = -jnp.exp(jax.random.normal(ks[2], (di, N)) * 0.1)
    B_ = jax.random.normal(ks[3], (B, S, N))
    C_ = jax.random.normal(ks[4], (B, S, N))
    y_full, h_full = ref.ssm_scan(x, dt, A, B_, C_)
    y1, h1 = ref.ssm_scan(x[:, :split], dt[:, :split], A, B_[:, :split],
                          C_[:, :split])
    y2, h2 = ref.ssm_scan(x[:, split:], dt[:, split:], A, B_[:, split:],
                          C_[:, split:], h0=h1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h_full),
                               rtol=2e-4, atol=2e-4)
