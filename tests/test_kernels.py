"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import decode_attention as dec
from repro.kernels import flash_attention as fa
from repro.kernels import ops, ref


def rand(key, shape, dtype):
    x = jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)
    return x.astype(dtype)


def maxerr(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32) -
                                 b.astype(jnp.float32))))


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 3e-2}


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,Hkv,hd", [
    (1, 64, 2, 2, 64),      # MHA
    (2, 128, 4, 2, 64),     # GQA 2:1
    (1, 96, 6, 2, 32),      # ragged seq (pad path), GQA 3:1
    (2, 64, 5, 5, 24),      # odd heads + unaligned hd (pad path)
    (1, 256, 8, 1, 64),     # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, S, H, Hkv, hd, dtype):
    q = rand(0, (B, S, H, hd), dtype)
    k = rand(1, (B, S, Hkv, hd), dtype)
    v = rand(2, (B, S, Hkv, hd), dtype)
    got = ops.flash_attention_op(q, k, v, causal=True, block_q=32,
                                 block_k=32, interpret=True)
    want = ref.attention(q, k, v, causal=True)
    assert maxerr(got, want) < TOL[dtype]


@pytest.mark.parametrize("window", [8, 32])
def test_flash_attention_sliding_window(window):
    q = rand(0, (2, 128, 4, 64), jnp.bfloat16)
    k = rand(1, (2, 128, 2, 64), jnp.bfloat16)
    v = rand(2, (2, 128, 2, 64), jnp.bfloat16)
    got = ops.flash_attention_op(q, k, v, causal=True, window=window,
                                 block_q=32, block_k=32, interpret=True)
    want = ref.attention(q, k, v, causal=True, window=window)
    assert maxerr(got, want) < TOL[jnp.bfloat16]


def test_flash_attention_non_causal():
    q = rand(0, (1, 64, 4, 64), jnp.float32)
    k = rand(1, (1, 64, 4, 64), jnp.float32)
    v = rand(2, (1, 64, 4, 64), jnp.float32)
    got = ops.flash_attention_op(q, k, v, causal=False, block_q=32,
                                 block_k=32, interpret=True)
    want = ref.attention(q, k, v, causal=False)
    assert maxerr(got, want) < TOL[jnp.float32]


def _open_blocks(Sq, Skv, bq, bk, causal, window):
    """Brute force over the kernel's padded grid: for each (q block, kv
    block) pair, how many of its (query, key) pairs the masks leave
    open, as an (nq, nk) array."""
    nq, nk = -(-Sq // bq), -(-Skv // bk)
    q = np.arange(nq * bq)[:, None]
    k = np.arange(nk * bk)[None, :]
    open_ = (k < Skv) & (q >= 0)
    if causal:
        open_ &= k <= q
    if window is not None:
        open_ &= k > q - window
    return open_.reshape(nq, bq, nk, bk).sum(axis=(1, 3))


def _open_pairs(Sq, Skv, bq, bk, causal, window):
    return int((_open_blocks(Sq, Skv, bq, bk, causal, window) > 0).sum())


@pytest.mark.parametrize("causal", [True, False])
def test_band_matches_brute_force(causal):
    """Over many shapes: the band is the pairs holding an open (query,
    key) pair, query block by query block in kv order; its first and last
    flags mark each query block's ends, and its edge flag each pair with a
    masked (query, key)."""
    for S, bq, bk in [(64, 8, 8), (70, 16, 8), (96, 8, 32), (40, 16, 16),
                      (45, 5, 3)]:
        for window in (None, 1, 5, 8, 9, 16, 23, 33, 100):
            opened = _open_blocks(S, S, bq, bk, causal, window)
            pairs = fa.band(S, S, bq, bk, causal, window)
            qi, ki, flags = pairs.T
            assert [(a, b) for a, b in zip(qi, ki)] == \
                sorted(zip(*np.nonzero(opened))), (S, bq, bk, window)
            first = np.r_[True, qi[1:] != qi[:-1]]
            last = np.r_[qi[1:] != qi[:-1], True]
            assert ((flags & fa.FIRST) != 0).tolist() == first.tolist()
            assert ((flags & fa.LAST) != 0).tolist() == last.tolist()
            assert ((flags & fa.EDGE) != 0).tolist() == \
                (opened[qi, ki] < bq * bk).tolist()


@pytest.mark.parametrize("B,S,H,Hkv,hd,window,bq,bk", [
    (1, 128, 4, 2, 64, 40, 32, 32),     # window not a multiple of the block
    (1, 128, 4, 2, 64, 34, 32, 32),     # a band edge pair with one open key
    (1, 128, 4, 2, 64, 63, 32, 32),     # a band edge pair with one masked
    (2, 128, 4, 4, 64, 8, 32, 32),      # window smaller than a block, MHA
    (1, 128, 8, 1, 64, 200, 32, 32),    # window longer than the sequence
    (1, 100, 6, 2, 32, 24, 32, 32),     # ragged seq (pad path), GQA 3:1
    (1, 128, 4, 2, 64, None, 32, 64),   # full causal, wider kv blocks
    (1, 160, 4, 1, 64, 48, 64, 32),     # wider q blocks, MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_band(B, S, H, Hkv, hd, window, bq, bk, dtype):
    """The kernel visits only the band, and the band is exactly the block
    pairs holding an open (query, key) pair: the answer is the
    reference's."""
    assert fa.band_blocks(S, S, bq, bk, True, window)[0] == \
        _open_pairs(S, S, bq, bk, True, window)
    q = rand(0, (B, S, H, hd), dtype)
    k = rand(1, (B, S, Hkv, hd), dtype)
    v = rand(2, (B, S, Hkv, hd), dtype)
    got = ops.flash_attention_op(q, k, v, causal=True, window=window,
                                 block_q=bq, block_k=bk, interpret=True)
    want = ref.attention(q, k, v, causal=True, window=window)
    assert maxerr(got, want) < TOL[dtype]


def _grid(B, S, H, Hkv, causal, window, bq, bk):
    """The grid of the one ``pallas_call`` the kernel traces to."""
    q = jax.ShapeDtypeStruct((B, S, H, 128), jnp.float32)
    kv_ = jax.ShapeDtypeStruct((B, S, Hkv, 128), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=causal, window=window, block_q=bq, block_k=bk,
        interpret=True))(q, kv_, kv_)
    calls = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    return tuple(calls[0].params["grid_mapping"].grid)


@pytest.mark.parametrize("causal,window,visited", [
    (True, None, 528),          # Mellum2's full layer at 4,096 positions
    (True, 1024, 252),          # and a windowed one: 8 blocks + diagonal
    (False, None, 1024),        # not causal: the full grid
])
def test_flash_attention_grid_is_the_band(causal, window, visited):
    """The kernel's grid has ``band_blocks(...)[0]`` steps per batch row
    and head, not the full grid, except where nothing is masked."""
    got = fa.band_blocks(4096, 4096, 128, 128, causal, window)
    assert got == (visited, 32 * 32)
    assert _grid(1, 4096, 4, 2, causal, window, 128, 128) == (4, visited)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

def _nan_past(cache, lengths):
    """``cache`` (B,S,Hkv,hd) with every position at or past its slot's
    length set to NaN: a tile the kernel should skip, or a masked
    position it should not use, then poisons the output."""
    pos = jnp.arange(cache.shape[1])[None, :, None, None]
    return jnp.where(pos < lengths[:, None, None, None], cache, jnp.nan)


@pytest.mark.parametrize("B,S,H,Hkv,hd", [
    (2, 128, 4, 2, 64),
    (3, 96, 5, 5, 24),
    (1, 256, 8, 1, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(B, S, H, Hkv, hd, dtype):
    q = rand(0, (B, H, hd), dtype)
    k = rand(1, (B, S, Hkv, hd), dtype)
    v = rand(2, (B, S, Hkv, hd), dtype)
    lengths = jnp.asarray([(7 * (i + 3)) % S + 1 for i in range(B)],
                          jnp.int32)
    got = ops.decode_attention_op(q, _nan_past(k, lengths),
                                  _nan_past(v, lengths), lengths,
                                  block_k=32, interpret=True)
    want = ref.decode_attention(q, k, v, lengths)
    assert maxerr(got, want) < TOL[dtype]


@pytest.mark.parametrize("window,lengths", [
    (16, [100, 64]),
    (16, [105, 97]),        # windows from tile 2 into tile 3
    (48, [100, 40]),        # tiles 1-3; tile 0 only
    (200, [128, 33]),       # wider than the cache
])
def test_decode_attention_window(window, lengths):
    B, S = 2, 128
    q = rand(0, (B, 4, 64), jnp.float32)
    k = rand(1, (B, S, 2, 64), jnp.float32)
    v = rand(2, (B, S, 2, 64), jnp.float32)
    lengths = jnp.array(lengths, jnp.int32)
    got = ops.decode_attention_op(q, _nan_past(k, lengths),
                                  _nan_past(v, lengths), lengths,
                                  window=window, block_k=32, interpret=True)
    want = ref.decode_attention(q, k, v, lengths, window=window)
    assert maxerr(got, want) < TOL[jnp.float32]


@pytest.mark.parametrize("layer", [0, 1, 2])
@pytest.mark.parametrize("n", [1, 31, 32, 33, 128])     # 1, bk +- 1, S
def test_decode_attention_stacked_tile_edges(n, layer):
    """Stacked (L=3) caches read in place at ``layer``: each slot's length
    on or beside a tile edge, NaN past every length and in every other
    layer, against the reference on the clean layer."""
    L, B, S, H, Hkv, hd = 3, 3, 128, 4, 2, 64
    q = rand(0, (B, H, hd), jnp.float32)
    k = rand(1, (B, S, Hkv, hd), jnp.float32)
    v = rand(2, (B, S, Hkv, hd), jnp.float32)
    lengths = jnp.array([n, S + 1 - n, 1 + n // 2], jnp.int32)
    poison = jnp.full((L, B, S, Hkv, hd), jnp.nan, jnp.float32)
    kc = poison.at[layer].set(_nan_past(k, lengths))
    vc = poison.at[layer].set(_nan_past(v, lengths))
    got = ops.decode_attention_op(q, kc, vc, lengths, layer, block_k=32,
                                  interpret=True)
    want = ref.decode_attention(q, k, v, lengths)
    assert maxerr(got, want) < TOL[jnp.float32]


@pytest.mark.parametrize("window", [None, 40])
def test_decode_attention_tile_span(window):
    """The tiles the kernel reads: each slot's live ones, and all of them
    when every slot is full."""
    lengths = np.array([1, 32, 33, 100, 128])
    first, last = dec.kv_tile_span(lengths, 32, window, np)
    if window is None:
        assert first == 0 and last.tolist() == [0, 0, 1, 3, 3]
        assert dec.kv_tiles_read(lengths, 32) == 1 + 1 + 2 + 4 + 4
        assert dec.kv_tiles_read(np.full(8, 512), 128) == 8 * 4
    else:
        assert first.tolist() == [0, 0, 0, 1, 2]
        assert dec.kv_tiles_read(lengths, 32, window) == 1 + 1 + 2 + 3 + 2


# ---------------------------------------------------------------------------
# selective scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,di,N", [
    (1, 16, 32, 8),
    (2, 64, 128, 16),
    (2, 33, 64, 4),     # odd seq length
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssm_scan_sweep(B, S, di, N, dtype):
    x = rand(0, (B, S, di), dtype)
    dt = jax.nn.softplus(rand(1, (B, S, di), jnp.float32)).astype(dtype)
    A = -jnp.exp(rand(2, (di, N), jnp.float32) * 0.1)
    B_ = rand(3, (B, S, N), dtype)
    C_ = rand(4, (B, S, N), dtype)
    y, h = ops.ssm_scan_op(x, dt, A, B_, C_, block_d=32, interpret=True)
    yr, hr = ref.ssm_scan(x, dt, A, B_, C_)
    assert maxerr(y, yr) < TOL[dtype] * 4   # recurrence accumulates error
    assert maxerr(h, hr) < TOL[dtype] * 4


def test_ssm_scan_with_initial_state():
    B, S, di, N = 2, 16, 32, 8
    x = rand(0, (B, S, di), jnp.float32)
    dt = jax.nn.softplus(rand(1, (B, S, di), jnp.float32))
    A = -jnp.exp(rand(2, (di, N), jnp.float32) * 0.1)
    B_ = rand(3, (B, S, N), jnp.float32)
    C_ = rand(4, (B, S, N), jnp.float32)
    h0 = rand(5, (B, di, N), jnp.float32)
    y, h = ops.ssm_scan_op(x, dt, A, B_, C_, h0, block_d=32, interpret=True)
    yr, hr = ref.ssm_scan(x, dt, A, B_, C_, h0)
    assert maxerr(y, yr) < 1e-4
    # continuation property: scanning halves sequentially == full scan
    y1, h1 = ops.ssm_scan_op(x[:, :8], dt[:, :8], A, B_[:, :8], C_[:, :8],
                             h0, block_d=32, interpret=True)
    y2, h2 = ops.ssm_scan_op(x[:, 8:], dt[:, 8:], A, B_[:, 8:], C_[:, 8:],
                             h1, block_d=32, interpret=True)
    assert maxerr(jnp.concatenate([y1, y2], axis=1), yr) < 1e-4
    assert maxerr(h2, hr) < 1e-4


# ---------------------------------------------------------------------------
# cluster distance (array fast-path distance stage)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,D,K", [
    (8, 16, 4),       # tiny, aligned-ish
    (37, 19, 5),      # every dim unaligned (pad paths)
    (256, 32, 12),    # multi-tile batch
])
def test_cluster_distance_sweep(B, D, K):
    x = rand(0, (B, D), jnp.float32)
    c = rand(1, (K, D), jnp.float32)
    got = ops.cluster_distance_op(x, c, block_b=64, interpret=True)
    want = jnp.sum((x[:, None, :] - c[None, :, :]) ** 2, axis=-1)
    assert got.shape == (B, K)
    assert maxerr(got, want) < 1e-3


def test_cluster_distance_nearest_assignment_exact():
    """argmin over the kernel's distances == brute-force nearest centroid."""
    import numpy as np
    rng = np.random.default_rng(3)
    c = rng.normal(size=(6, 24)).astype(np.float32) * 2
    x = c[rng.integers(6, size=100)] + \
        rng.normal(size=(100, 24)).astype(np.float32) * 0.05
    got = jnp.argmin(ops.cluster_distance_op(x, c, interpret=True), axis=1)
    want = jnp.argmin(jnp.sum(
        (jnp.asarray(x)[:, None, :] - jnp.asarray(c)[None, :, :]) ** 2,
        axis=-1), axis=1)
    assert (np.asarray(got) == np.asarray(want)).all()
