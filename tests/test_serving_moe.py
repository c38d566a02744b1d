"""The serving plane's expert model: the dropless decode expert kernel, the
grouped-matmul prefill experts, windowed and full attention layers, and
the flow serving such a spec, at a small size in interpret mode.

The geometry keeps what the published model makes the program handle: a
residual width that is not ``n_heads * head_dim``, routed experts in every
layer (8, top-2, renormalised), and the layer pattern sliding, sliding,
sliding, full with a window shorter than the prompts.  Every comparison
runs in float32 at highest precision on the CPU, so the only difference
from the reference is rounding, about 1e-6 of the values' scale; a wrong
expert, weight, window or dead slot moves a value by its own scale.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.moe_decode import fetch_list
from repro.serving import LMSpec, build_serving_flow, kv, make_request
from repro.serving.dataflow import DecodePellet, PrefillPellet
from repro.telemetry import MetricsRegistry

SPEC = LMSpec(vocab=64, n_heads=4, n_kv_heads=2, head_dim=8, n_layers=4,
              max_len=48, d_model=40, windows=(8, 8, 8, None), n_experts=8,
              top_k=2, expert_width=16)
#: float32 rounding on the CPU at highest precision, with room
TOL = 1e-4
B, D, E, F, L = 4, 40, 8, 16, 3


@pytest.fixture(scope="module")
def experts():
    rng = np.random.default_rng(7)
    wg = jnp.asarray(rng.normal(size=(L, E, D, F)) / np.sqrt(D), jnp.float32)
    wu = jnp.asarray(rng.normal(size=(L, E, D, F)) / np.sqrt(D), jnp.float32)
    wd = jnp.asarray(rng.normal(size=(L, E, F, D)) / np.sqrt(F), jnp.float32)
    x = jnp.asarray(rng.normal(size=(B, D)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.1, 1.0, size=(B, 2)), jnp.float32)
    return x, w, wg, wu, wd


#: (picks per slot, live slots, experts the live slots pick)
CASES = {
    "repeated": ([[1, 3], [3, 1], [1, 3], [3, 5]], [1, 1, 1, 1], 3),
    "one_expert": ([[6, 6], [6, 6], [6, 6], [6, 6]], [1, 1, 1, 1], 1),
    "dead_slots": ([[0, 2], [4, 7], [2, 0], [5, 1]], [1, 0, 1, 0], 2),
    "every_expert": ([[0, 1], [2, 3], [4, 5], [6, 7]], [1, 1, 1, 1], 8),
    "all_dead": ([[0, 1], [2, 3], [4, 5], [6, 7]], [0, 0, 0, 0], 0),
}


@pytest.mark.parametrize("case", list(CASES))
def test_moe_decode_kernel_against_dense(experts, case):
    """The kernel computes every live pick once, weighted, and nothing
    for dead slots; it fetches each expert a live slot picked once."""
    x, w, wg, wu, wd = experts
    picks, live, distinct = CASES[case]
    ids = jnp.asarray(picks, jnp.int32)
    live = jnp.asarray(live, bool)
    with jax.default_matmul_precision("highest"):
        y, n = ops.moe_decode_op(x, ids, w, live, wg, wu, wd, 2,
                                 interpret=True)
        want = ref.moe_ffn(x, ids, jnp.where(live[:, None], w, 0.0), wg[2],
                           wu[2], wd[2])
    assert int(n) == distinct
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=TOL,
                               rtol=TOL)
    assert np.all(np.asarray(y)[~np.asarray(live)] == 0.0)
    order, n = fetch_list(ids, live, E)
    used = sorted({e for row, lv in zip(picks, live) if lv for e in row})
    assert list(np.asarray(order)[:int(n)]) == used


@pytest.mark.parametrize("tokens", [5, 37])
def test_grouped_experts_against_dense(experts, tokens):
    """Prefill's grouped matmuls over expert-sorted tokens compute every
    pick, however many tokens share an expert and whatever the row
    padding."""
    _, _, wg, wu, wd = experts
    rng = np.random.default_rng(tokens)
    x = jnp.asarray(rng.normal(size=(tokens, D)), jnp.float32)
    ids = jnp.asarray(np.stack([rng.permutation(E)[:2]
                                for _ in range(tokens)]), jnp.int32)
    w = jnp.asarray(rng.uniform(0.1, 1.0, size=(tokens, 2)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        y = ops.moe_grouped_op(x, ids, w, wg, wu, wd, 1, interpret=True)
        want = ref.moe_ffn(x, ids, w, wg[1], wu[1], wd[1])
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=TOL,
                               rtol=TOL)


def _prompts():
    rng = np.random.default_rng(3)
    lens = [20, 6, 13]                   # past the window, inside it
    tokens = np.zeros((len(lens), 24), np.int32)
    for i, n in enumerate(lens):
        tokens[i, :n] = rng.integers(1, SPEC.vocab, n)
    return jnp.asarray(tokens), jnp.asarray(lens, jnp.int32)


def test_flow_serves_the_expert_spec_exactly_once():
    """The expert spec runs through the same scheduler, pellets, cache and
    sink as the dense model: every request answered once with its budget,
    the kernel path's tokens equal to the ref twin's."""
    prompts = [[1 + i] * (3 + 4 * i) for i in range(5)]   # 3 .. 19 tokens
    budgets = [3, 5, 2, 4, 6]
    got = {}
    for ref_path in (False, True):
        flow = build_serving_flow(spec=SPEC, n_slots=2, max_prompt=24,
                                  default_budget=4, seed=2, ref_path=ref_path)
        with flow.session() as s:
            s.inject_many("sched", [make_request(i, p, max_new=b)
                                    for i, (p, b) in
                                    enumerate(zip(prompts, budgets))])
            out = [r for r in s.results(timeout=120)
                   if isinstance(r, dict) and "rid" in r]
            text = s.telemetry.prometheus()
        assert sorted(r["rid"] for r in out) == list(range(5))
        assert [r["n_new"] for r in sorted(out, key=lambda r: r["rid"])] \
            == budgets
        assert "floe_moe_expert_fetches_total" in text
        got[ref_path] = {r["rid"]: r["tokens"] for r in out}
    assert got[False] == got[True]


class _Tele:
    def __init__(self):
        self.registry = MetricsRegistry()


def test_kv_tiles_count_each_layer_with_its_window():
    """``floe_decode_kv_tiles_read_total`` counts a windowed layer's tiles
    from the window's first tile, a full layer's from the first tile."""
    from repro.kernels.decode_attention import kv_block_k, kv_tiles_read
    spec = LMSpec(vocab=32, n_heads=2, n_kv_heads=1, head_dim=128,
                  n_layers=4, max_len=2048, windows=(256, 256, 256, None))
    pellet = DecodePellet(kv.init_params(spec, 0), spec, n_slots=2)
    tele = _Tele()
    pellet.bind_telemetry(tele, "decode")
    pellet.live[:] = True
    pellet.lengths[:] = [1500, 100]
    pellet.meta = {0: {"rid": 0, "tokens": [1], "budget": 9, "t_sub": 0.0,
                       "t_first": 0.0},
                   1: {"rid": 1, "tokens": [1], "budget": 9, "t_sub": 0.0,
                       "t_first": 0.0}}
    lens = pellet.lengths + 1
    pellet._step([])
    bk = kv_block_k(2048, 1, 128)
    want = 2 * (3 * kv_tiles_read(lens, bk, 256) + kv_tiles_read(lens, bk))
    fam = tele.registry.counter("floe_decode_kv_tiles_read_total", "",
                                ("stage",))
    assert fam.labels(stage="decode").value == want
    # the windowed layers read fewer tiles than a full layer would
    assert want < 2 * 4 * kv_tiles_read(lens, bk)


def test_dense_spec_is_unchanged():
    """A dense spec built without the new fields is the dense model of
    before: the fields' defaults are the dense values, and the parameters
    are drawn in the same order (the sum below is what
    ``init_params(LMSpec(), 3)`` gave before the expert fields existed).
    Every spec's prefill and decode programs are ``test_lm_step.py``'s."""
    spec = LMSpec(vocab=64, n_heads=4, n_kv_heads=2, head_dim=8, n_layers=2,
                  max_len=32)
    explicit = LMSpec(vocab=64, n_heads=4, n_kv_heads=2, head_dim=8,
                      n_layers=2, max_len=32, d_model=32,
                      windows=(None, None), n_experts=0)
    assert spec == explicit and hash(spec) == hash(explicit)
    assert spec.d_model == 32 and spec.window(1) is None
    p = kv.init_params(LMSpec(), 3)
    assert sorted(p) == ["embed", "head", "ln1", "ln2", "ln_f", "w1", "w2",
                         "wk", "wo", "wq", "wv"]
    assert float(sum(jnp.sum(v) for v in p.values())) == \
        pytest.approx(173.4449920654297, abs=1e-4)


def test_spec_refuses_a_broken_expert_layer():
    with pytest.raises(ValueError, match="windows"):
        LMSpec(n_layers=2, windows=(8,))
    with pytest.raises(ValueError, match="top_k"):
        LMSpec(n_experts=4, top_k=5, expert_width=8)


def test_prefill_pellet_and_decode_pellet_take_the_expert_spec():
    """The pellets carry an expert spec's caches like a dense one's."""
    params = kv.init_params(SPEC, 1)
    pre = PrefillPellet(params, SPEC)
    tokens, lens = _prompts()
    cols = {"rid": np.arange(3), "slot": np.arange(3),
            "length": np.asarray(lens), "budget": np.full(3, 3),
            "t_sub": np.zeros(3), "tokens": np.asarray(tokens)}
    out = pre.compute_array(cols)
    assert out["k"].shape == (3, 4, 48, 2, 8)
    dec = DecodePellet(params, SPEC, n_slots=3)
    dec.compute_array(out)
    emits = []
    while dec.live.any():
        dec._step(emits)
    done = [e.payload for e in emits if e.port == "out"]
    assert sorted(d["n_new"] for d in done) == [3, 3, 3]
