"""The expert LM's cell (kind ``serve_moe_lm``) at a size the CPU runs in
seconds: its runner from new files only, its reference against the
program, its bfloat16 control, the program's count of expert fetches, and
the work counted for windowed layers."""
import copy
import json
import shutil

import numpy as np
import pytest
from conftest import BENCH, ROOT, runner

import jax
import jax.numpy as jnp

from yard import common, ref_moe_lm, work_moe

CELL = "serve-mellum2-12b-code-poisson"
#: float32 at highest precision on the CPU: the program and the reference
#: differ by rounding alone, about 1e-6 of the logits' scale
SOUND = 1e-4


def tiny_moe(vocab: int = 64) -> common.Cell:
    """The expert cell with every width shrunk and its shapes kept:
    d_model (40) is not n_heads x head_dim (32), 8 experts top-2, the
    layer pattern sliding, sliding, sliding, full with a window of 8
    under prompts of 4-24 tokens."""
    cell = common.load_cell(CELL)
    cfg = copy.deepcopy(cell.config)
    cfg.update(vocab_size=vocab, hidden_size=40, num_attention_heads=4,
               num_key_value_heads=2, head_dim=8, num_experts=8,
               num_experts_per_tok=2, moe_intermediate_size=16,
               sliding_window=8)
    cfg["serve"].update(slots=4, max_len=64, max_prompt=24)
    cfg["check"].update(sample_tokens=40, batch=2)
    traffic = copy.deepcopy(cell.traffic)
    traffic["arrivals"]["rate_per_s"] = 10.0
    traffic["prompt"].update(median=12, min=4, max=24)
    traffic["budget"].update(median=4, min=2, max=8)
    cell.config, cell.traffic = cfg, traffic
    return cell


def _spec(dims, max_len):
    from repro.serving import LMSpec
    return LMSpec(vocab=dims.vocab, n_heads=dims.n_heads,
                  n_kv_heads=dims.n_kv_heads, head_dim=dims.head_dim,
                  n_layers=dims.n_layers, max_len=max_len,
                  d_model=dims.d_model, windows=dims.windows,
                  n_experts=dims.n_experts, top_k=dims.top_k,
                  expert_width=dims.expert_width)


def test_published_widths_and_the_cut():
    cell = common.load_cell(CELL)
    d = work_moe.MoEDims.from_config(cell.config)
    assert (d.d_model, d.n_heads, d.n_kv_heads, d.head_dim, d.n_layers,
            d.n_experts, d.top_k, d.expert_width, d.vocab) == \
        (2304, 32, 4, 128, 4, 64, 8, 896, 98304)
    assert d.windows == (1024, 1024, 1024, None)
    n = {k: int(np.prod(s)) for k, s in ref_moe_lm.shapes(d).items()}
    layer = sum(v for k, v in n.items() if k not in ("embed", "head",
                                                     "ln_f")) // 4
    # attention 21.23 M, router 0.15 M, experts 64 x 6.19 M a layer
    assert layer == pytest.approx(417.8e6, rel=1e-3)
    assert 4 * sum(n.values()) == pytest.approx(8.50e9, rel=1e-2)
    assert work_moe.expert_bytes(d) == 3 * 2304 * 896 * 4


def test_a_moe_cell_from_new_files_only(tmp_path, peak):
    """A configuration and a traffic mix of kind ``serve_moe_lm``, each a
    new file, with new entries in ``BENCHMARK.json``, make a runnable
    cell; its run is correct, and its per-layer counters read."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    tiny = tiny_moe()
    (tmp_path / "bench/configs/tiny-moe.json").write_text(
        json.dumps(tiny.config))
    (tmp_path / "bench/traffic/tiny-code.json").write_text(
        json.dumps(tiny.traffic))
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    bm["configs"].append({"name": "tiny-moe", "source": "https://example.org",
                          "file": "bench/configs/tiny-moe.json",
                          "reduced": [], "why": "a CPU-sized expert LM"})
    bm["workloads"].append({"name": "tiny-moe.code", "config": "tiny-moe",
                            "traffic": "tiny-code", "chips": 1,
                            "why": "requests at 10 a second"})
    for m in bm["end_to_end"] + bm["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("tiny-moe.code")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))

    cell = common.load_cell("tiny-moe.code", root=tmp_path)
    assert "decode_tick_ms" in {m["name"] for m in cell.per_layer}
    run_mod = common.load_module(tmp_path / "bench/run.py", "tmp_run_moe")
    res = runner(cell).run(cell, seed=2**31 + 41, seconds=1.0, trace=True,
                           peak=peak, t_start=0.0)
    line = run_mod.result_line(cell, res, {"platform": "cpu"}, True)
    assert line["correct"] and line["failed"] == 0
    assert line["attempted"] == 10
    assert line["metrics"]["decode_tick_ms"]["value"] > 0
    w = res["window"]
    # 4 slots x top-2 pick at most all 8 experts of a layer, at least 2
    steps = w.counters["decode_steps"]
    fetched = w.counters["moe_expert_fetches"]
    assert 2 * 4 * steps <= fetched <= 8 * 4 * steps
    assert w.counters["moe_layer_experts"] == 4 * 8
    assert w.work["moe_decode"]["bytes"] == \
        w.counters["moe_expert_fetches"] * 3 * 40 * 16 * 4


def test_prefill_then_decode_match_the_reference():
    """Prefill, then decoding through the cache, give the logits of the
    reference's full forward pass at every position compared, past the
    window and inside it; a cache row past each length set to NaN is
    never read."""
    from repro.serving import kv
    cell = tiny_moe()
    dims = work_moe.MoEDims.from_config(cell.config)
    spec = _spec(dims, 48)
    params = ref_moe_lm.init(dims, 2**31 + 3)
    rng = np.random.default_rng(1)
    lens = [21, 5]
    tokens = np.zeros((2, 24), np.int32)
    for i, n in enumerate(lens):
        tokens[i, :n] = rng.integers(1, dims.vocab, n)
    steps = 6
    with jax.default_matmul_precision("highest"):
        logits, kc, vc = kv.prefill(params, jnp.asarray(tokens),
                                    jnp.asarray(lens, jnp.int32), spec=spec,
                                    interpret=True)
        pos = np.arange(48)[None, None, :, None, None]
        past = pos >= np.asarray(lens)[None, :, None, None, None]
        kc, vc = jnp.where(past, jnp.nan, kc), jnp.where(past, jnp.nan, vc)
        got = [np.asarray(logits)]
        seqs = [list(tokens[i, :n]) for i, n in enumerate(lens)]
        cur = jnp.asarray(lens, jnp.int32)
        live = jnp.ones(2, bool)
        for _ in range(steps):
            nxt = kv.greedy(got[-1])
            for s, t in zip(seqs, np.asarray(nxt)):
                s.append(int(t))
            logits, kc, vc, _ = kv.decode_step(params, kc, vc, cur, nxt, live,
                                               spec=spec, interpret=True)
            got.append(np.asarray(logits))
            cur = cur + 1
        full = np.zeros((2, 48), np.int32)
        for i, s in enumerate(seqs):
            full[i, :len(s)] = s
        want, _, _ = ref_moe_lm.forward(params, jnp.asarray(full), dims=dims,
                                        eps=1e-6)
    want = np.asarray(want)
    for j, lg in enumerate(got):
        for i, n in enumerate(lens):
            np.testing.assert_allclose(lg[i], want[i, n - 1 + j],
                                       atol=SOUND, rtol=SOUND)


def test_fetch_counter_is_the_reference_routing():
    """``floe_moe_expert_fetches_total`` grows each decode step by the
    distinct experts that the live slots' tokens route to in the
    reference, summed over layers."""
    from repro.serving.dataflow import DecodePellet, PrefillPellet
    from repro.telemetry import MetricsRegistry
    cell = tiny_moe()
    dims = work_moe.MoEDims.from_config(cell.config)
    spec = _spec(dims, 48)
    params = ref_moe_lm.init(dims, 2**31 + 9)
    rng = np.random.default_rng(2)
    lens, budgets = [17, 6, 11], [5, 3, 7]
    tokens = np.zeros((3, 24), np.int32)
    for i, n in enumerate(lens):
        tokens[i, :n] = rng.integers(1, dims.vocab, n)
    cols = {"rid": np.arange(3), "slot": np.arange(3),
            "length": np.asarray(lens), "budget": np.asarray(budgets),
            "t_sub": np.zeros(3), "tokens": tokens}

    class Tele:
        registry = MetricsRegistry()

    with jax.default_matmul_precision("highest"):
        carrier = PrefillPellet(params, spec).compute_array(cols)
        decode = DecodePellet(params, spec, n_slots=4)
        decode.bind_telemetry(Tele, "decode")
        decode.compute_array(carrier)
        counter = Tele.registry.counter("floe_moe_expert_fetches_total", "",
                                        ("stage",)).labels(stage="decode")
        seqs = {s: list(tokens[s, :n]) for s, n in enumerate(lens)}
        while decode.live.any():
            live = [int(s) for s in np.nonzero(decode.live)[0]]
            for s in live:
                seqs[s].append(int(decode.last_tok[s]))
            full = np.zeros((4, 48), np.int32)
            for s in live:
                full[s, :len(seqs[s])] = seqs[s]
            _, routes, _ = ref_moe_lm.forward(params, jnp.asarray(full),
                                              dims=dims, eps=1e-6)
            routes = np.asarray(routes)            # (L, B, S, k)
            want = sum(len({int(e) for s in live
                            for e in routes[l, s, len(seqs[s]) - 1]})
                       for l in range(dims.n_layers))
            before = counter.value
            decode._step([])
            assert counter.value - before == want
    assert decode.n_steps == max(budgets) - 1


def test_bfloat16_control_reads_wider_than_the_program(peak):
    """Float32 on the CPU reads no gap; the reference in bfloat16 puts
    other tokens first among 4,096 and reads one, above the limit."""
    cell = tiny_moe(vocab=4096)
    cell.config["check"].update(sample_tokens=200)
    cell.traffic["arrivals"]["rate_per_s"] = 20.0
    seed = 2**31 + 29
    mod = runner(cell)
    res = mod.run(cell, seed=seed, seconds=2.0, trace=False, peak=peak,
                  t_start=0.0)
    program = {name: v for name, v, _ in res["checks"]}["logit_gap_mean"]
    gaps = mod.control_gaps(cell, res, seed)
    control = float(np.mean(np.concatenate(gaps)))
    assert program == 0.0 < control


@pytest.mark.parametrize("window", [None, 1, 3, 8, 40])
def test_windowed_work_by_brute_force(window):
    """Attention counted for a windowed layer, in prefill and in decode,
    equals a count of the (query, key) pairs its mask keeps."""
    dims = work_moe.MoEDims(vocab=10, d_model=4, n_heads=2, n_kv_heads=1,
                            head_dim=2, n_layers=1, n_experts=4, top_k=2,
                            expert_width=3, windows=(window,))
    hq, hkv = 4, 2
    for length in (1, 2, 7, 8, 9, 33):
        pairs = sum(1 for q in range(length) for k in range(length)
                    if k <= q and (window is None or k > q - window))
        assert work_moe.causal_pairs(window, length) == pairs
        fl = work_moe.flash_attention_work(dims, length)
        assert fl["flops"] == 4 * hq * pairs
        keys = sum(1 for k in range(length)
                   if window is None or k > length - 1 - window)
        dec = work_moe.decode_attention_work(dims, length)
        assert dec == {"flops": 4 * hq * keys,
                       "bytes": 4 * (2 * keys * hkv + 2 * hq)}
        matmul = 2 * (4 * 4 + 2 * 4 * 2 + 4 * 4 + 4 * 4 + 2 * 3 * 4 * 3)
        assert work_moe.decode_token_flops(dims, length) == \
            matmul + 4 * hq * keys + 2 * 4 * 10
        assert work_moe.prefill_flops(dims, length) == \
            length * matmul + 4 * hq * pairs + 2 * 4 * 10
