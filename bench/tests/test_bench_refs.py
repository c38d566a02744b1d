"""The plain references agree with the program at tiny sizes, and the
comparison catches a program whose causal mask or cache position is
wrong."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from yard import ref_lm, work

DIMS = work.LMDims(vocab=64, d_model=32, n_heads=4, n_kv_heads=2,
                   head_dim=8, n_layers=2, d_ff=64)
MAX_LEN, PROMPT = 64, 24
#: float32 on the CPU at highest precision: rounding only
SOUND = 1e-3
#: a wrong mask or cache position moves the logits by their own scale
BROKEN = 5e-2


def spec():
    from repro.serving import LMSpec
    return LMSpec(vocab=DIMS.vocab, n_heads=DIMS.n_heads,
                  n_kv_heads=DIMS.n_kv_heads, head_dim=DIMS.head_dim,
                  n_layers=DIMS.n_layers, max_len=MAX_LEN,
                  ffn_mult=DIMS.d_ff // DIMS.d_model)


def serve(params, prompts, n_new, *, causal=True, shift=0):
    """Greedy generation through the program's own prefill and decode
    step, optionally broken: a non-causal prefill, or the decode step
    writing and reading the cache ``shift`` positions off."""
    from repro.kernels import ops
    from repro.serving import kv
    B = len(prompts)
    tokens = np.zeros((B, PROMPT), np.int32)
    for i, p in enumerate(prompts):
        tokens[i, :len(p)] = p
    lengths = jnp.asarray([len(p) for p in prompts], jnp.int32)
    attn = ops.flash_attention_op
    if not causal:
        kv.kops.flash_attention_op = \
            lambda q, k, v, causal=True, interpret=False: attn(
                q, k, v, causal=False, interpret=interpret)
    try:
        with jax.default_matmul_precision("highest"):
            logits, kc, vc = kv.prefill.__wrapped__(
                params, jnp.asarray(tokens), lengths, spec=spec(),
                interpret=True)
            out = [[int(t)] for t in kv.greedy(logits)]
            lens = lengths
            for _ in range(n_new - 1):
                last = jnp.asarray([o[-1] for o in out], jnp.int32)
                logits, kc, vc = kv.decode_step.__wrapped__(
                    params, kc, vc, lens + shift, last, spec=spec(),
                    interpret=True)
                for o, t in zip(out, kv.greedy(logits)):
                    o.append(int(t))
                lens = lens + 1
    finally:
        kv.kops.flash_attention_op = attn
    return out


def widest_gap(params, prompts, served):
    seqs = [list(p) + s[:-1] for p, s in zip(prompts, served)]
    probes = [{len(p) - 1 + j: t for j, t in enumerate(s)}
              for p, s in zip(prompts, served)]
    gaps, _ = ref_lm.gaps(params, seqs, probes, dims=DIMS, eps=1e-6,
                          length=MAX_LEN, batch=2)
    return max(float(np.max(g)) for g in gaps)


@pytest.fixture(scope="module")
def case():
    params = dict(ref_lm.init(DIMS, seed=2**31 + 11))
    # at this width the residual stream of the current token drowns what
    # attention brings; louder attention makes a wrong key or mask show
    for name in ("wq", "wk", "wv", "wo"):
        params[name] = params[name] * 3.0
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, DIMS.vocab, size=n).tolist()
               for n in (5, 17, 24, 9)]
    return params, prompts


def test_reference_agrees_with_the_program(case):
    params, prompts = case
    served = serve(params, prompts, 12)
    assert widest_gap(params, prompts, served) < SOUND


def test_wrong_causal_mask_fails(case):
    params, prompts = case
    served = serve(params, prompts, 12, causal=False)
    assert widest_gap(params, prompts, served) > BROKEN


def test_wrong_cache_position_fails(case):
    params, prompts = case
    served = serve(params, prompts, 12, shift=-1)
    assert widest_gap(params, prompts, served) > BROKEN


def test_weights_follow_the_seed():
    a = ref_lm.init(DIMS, seed=7)
    b = ref_lm.init(DIMS, seed=7)
    c = ref_lm.init(DIMS, seed=7 + 2**32)
    assert all(bool(jnp.array_equal(a[k], b[k])) for k in a)
    assert not bool(jnp.array_equal(a["wq"], c["wq"]))
    assert a["w1"].shape == (2, 32, 64) and a["embed"].dtype == jnp.float32
