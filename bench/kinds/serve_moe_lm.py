"""Runner of configurations of kind ``serve_moe_lm``: the serving plane's
LM with routed experts and windowed and full attention layers
(``repro.serving.build_serving_flow`` with an expert ``LMSpec``: sched ->
flash-attention prefill with grouped-matmul experts -> decode tick loop
through the flash-decode and dropless ``moe_decode`` kernels ->
exactly-once sink) under an open loop of requests.

It runs as ``serve_lm`` runs, and shares its warm-up, its sampling of the
requests to compare and its checks.  What differs: the spec and the
weights (``ref_moe_lm.init``), the work counted from shapes
(``work_moe``: windows and experts), the decode steps' expert fetches read
from the program's ``floe_moe_expert_fetches_total``, and the reference
(``ref_moe_lm``, every expert dense).  ``control_gaps`` computes the
bfloat16 control that the limit of ``logit_gap_mean`` is set against.
"""
from __future__ import annotations

import collections
import gc
import time
from typing import Any, Dict, List
from unittest import mock

import numpy as np

from kinds.serve_lm import (STAGES, _request, decode_progress, sample,
                            sequences, warm)
from yard import gen, readers, ref_moe_lm, work_moe
from yard.common import CompileClock, Window, peak_bytes_in_use, percentile
from yard.harness import Injector, Monitor, Profile, hist_delta, hist_state

FETCHES = "floe_moe_expert_fetches_total"
#: the decode expert kernel's events in the device trace: its
#: ``pallas_call``'s name, and the kernel function's
MOE_KERNEL = ("moe_decode", "_moe_decode_kernel")


def build(cfg: Dict[str, Any], seed: int):
    """The flow under test, around weights drawn from ``seed``; returns
    ``(flow, params, dims)``."""
    from repro.serving import dataflow
    serve = cfg["serve"]
    dims = work_moe.MoEDims.from_config(cfg)
    spec = dataflow.LMSpec(
        vocab=dims.vocab, n_heads=dims.n_heads, n_kv_heads=dims.n_kv_heads,
        head_dim=dims.head_dim, n_layers=dims.n_layers,
        max_len=int(serve["max_len"]), d_model=dims.d_model,
        windows=dims.windows, n_experts=dims.n_experts, top_k=dims.top_k,
        expert_width=dims.expert_width)
    params = ref_moe_lm.init(dims, seed)
    # the flow takes the benchmark's weights in place of drawing its own
    with mock.patch.object(dataflow, "init_params",
                           lambda spec_, seed_=0: params):
        flow = dataflow.build_serving_flow(
            spec=spec, n_slots=int(serve["slots"]),
            max_prompt=int(serve["max_prompt"]),
            default_budget=int(serve["warm_budget"]), seed=seed)
    return flow, params, dims


def _fetches(tele) -> int:
    """The decode stage's expert fetches so far."""
    fam = tele.registry.counter(FETCHES, "", ("stage",))
    return int(fam.labels(stage="decode").value)


def run(cell, seed: int, seconds: float, trace: bool, peak: Dict[str, float],
        t_start: float) -> Dict[str, Any]:
    cfg, traffic = cell.config, cell.traffic
    flow, params, dims = build(cfg, seed)
    reqs = gen.requests(traffic, seconds, seed=seed, vocab=dims.vocab)
    by_rid = {r["rid"]: r for r in reqs}
    profile = Profile(trace)
    session = flow.session(drain_timeout=600)
    session.open()
    monitor = Monitor(session.coordinator)
    monitor.start()
    try:
        warm(session, monitor, cfg)
        decode = session.coordinator.flakes["decode"]._proto
        tele = session.telemetry
        t0 = time.time() + 0.05
        close = t0 + seconds
        due_wall: Dict[int, float] = {}

        def send(item, due):
            due_wall[item["rid"]] = due
            session.inject("sched", _request(item["rid"], item["prompt"],
                                             item["budget"], due))

        injector = Injector(reqs, send, t0, close + 1.0)
        h0 = hist_state(tele, STAGES)
        steps0, fetch0 = decode.n_steps, _fetches(tele)
        setup_s = t0 - t_start
        with CompileClock() as clock:
            with profile.window():
                while time.time() < t0:
                    time.sleep(0.001)
                prog0 = decode_progress(decode)
                injector.start()
                time.sleep(max(0.0, close - time.time()))
                prog1 = decode_progress(decode)
                h1 = hist_state(tele, STAGES)
                steps1, fetch1 = decode.n_steps, _fetches(tele)
        injector.join()
        monitor.wait_for(len(reqs), close + float(traffic["drain_s"]))
        memory_peak = peak_bytes_in_use(cell.chips)
        errors = list(session.errors)
    finally:
        monitor.stop()
        session.close()
    items, stamps = monitor.take()
    reduced = profile.reduce(cell.chips)
    late, sent = injector.late_s, injector.sent
    # free the program's state (caches, carriers, pellets) before the
    # reference runs: the injector's and monitor's threads hold the session
    del session, flow, decode, tele, monitor, injector, send
    gc.collect()

    # -- what came back ------------------------------------------------------
    answers: Dict[int, List] = collections.defaultdict(list)
    for payload, t in zip(items, stamps):
        if isinstance(payload, dict) and "rid" in payload:
            answers[int(payload["rid"])].append((payload, t))
    ttft, tpot, ok = [], [], {}
    dup = missing = short = order = 0
    for r in reqs:
        got = answers.get(r["rid"], [])
        if len(got) > 1:
            dup += 1
        if not got:
            missing += 1
        elif got[0][0]["n_new"] != r["budget"]:
            short += 1
        good = len(got) == 1 and got[0][0]["n_new"] == r["budget"]
        if not good:
            ttft.append(np.inf)
            tpot.append(np.inf)
            continue
        p, t_done = got[0]
        due = due_wall.get(r["rid"], np.inf)
        if not (due <= p["t_first"] <= t_done):
            order += 1
        ok[r["rid"]] = (p, t_done)
        ttft.append((p["t_first"] - due) * 1e3)
        tpot.append((t_done - p["t_first"]) / (p["n_new"] - 1) * 1e3)
    extra = sum(1 for rid in answers if rid not in by_rid)
    failed = len(reqs) - len(ok)

    # -- the window's work, from shapes ---------------------------------------
    def done_by(rid, t):
        hit = ok.get(rid)
        return hit is not None and hit[1] <= t

    decode_flops = decoded = 0
    dec_attn = {"flops": 0, "bytes": 0}
    for rid, (p, t_done) in ok.items():
        plen = len(by_rid[rid]["prompt"])
        n_dec = p["n_new"] - 1
        a = n_dec if done_by(rid, t0) else max(0, prog0.get(rid, 1) - 1)
        b = n_dec if done_by(rid, close) else max(0, prog1.get(rid, 1) - 1)
        decoded += max(0, b - a)
        for j in range(a + 1, b + 1):
            decode_flops += work_moe.decode_token_flops(dims, plen + j)
            one = work_moe.decode_attention_work(dims, plen + j)
            dec_attn["flops"] += one["flops"]
            dec_attn["bytes"] += one["bytes"]
    prefill_flops = 0
    flash = {"flops": 0, "bytes": 0}
    for rid, (p, _) in ok.items():
        if t0 <= p["t_first"] <= close:
            plen = len(by_rid[rid]["prompt"])
            prefill_flops += work_moe.prefill_flops(dims, plen)
            one = work_moe.flash_attention_work(dims, plen)
            flash["flops"] += one["flops"]
            flash["bytes"] += one["bytes"]
    window = Window(seconds=seconds, peak=peak,
                    stages=hist_delta(h0, h1),
                    work={"decode_flops": decode_flops,
                          "prefill_flops": prefill_flops,
                          "decode_attention": dec_attn,
                          "flash_attention": flash,
                          "moe_decode": work_moe.moe_decode_work(
                              dims, fetch1 - fetch0, decoded)},
                    counters={"decode_steps": steps1 - steps0,
                              "moe_expert_fetches": fetch1 - fetch0,
                              "moe_layer_experts":
                                  dims.n_layers * dims.n_experts},
                    trace=reduced)

    # -- the reference -----------------------------------------------------
    limits = cfg["limits"]
    chosen = sample(reqs, ok, seed, int(cfg["check"]["sample_tokens"]))
    seqs, probes = sequences(reqs, ok, chosen)
    gaps, _ = ref_moe_lm.gaps(params, seqs, probes, **_ref_args(cfg, dims))
    flat = np.concatenate(gaps) if gaps else np.full(1, np.inf)
    served = sum(len(p) for p in probes)
    del params
    checks = [
        ("missing", missing, 0), ("duplicates", dup + extra, 0),
        ("short_budget", short, 0), ("ttft_stamp_order", order, 0),
        ("engine_errors", len(errors), 0),
        ("logit_gap_mean", float(np.mean(flat)),
         float(limits["logit_gap_mean"])),
    ]
    steps = max(1, steps1 - steps0)
    notes = [
        f"requests {len(reqs)} sent {sent}, answered {len(ok)}; "
        f"ttft p95 {percentile(ttft, 95)} ms; "
        f"generator lateness p50 {percentile(late, 50) * 1e3:.3f} ms "
        f"p99 {percentile(late, 99) * 1e3:.3f} ms",
        "stage busy shares: " + ", ".join(
            f"{st} {100 * window.stages[st]['service_sum'] / seconds:.2f}%"
            for st in STAGES),
        f"compiles inside the window: {clock.compiles} "
        f"({dict(clock.by_fun)}), {clock.seconds:.3f} s",
        f"decode steps {steps1 - steps0}, tokens {decoded}, experts "
        f"fetched {(fetch1 - fetch0) / steps:.2f} a step of "
        f"{dims.n_layers * dims.n_experts} (fetch share "
        f"{100 * (fetch1 - fetch0) / (steps * dims.n_layers * dims.n_experts)}"
        f"%)",
        f"reference: {len(seqs)} requests, {served} served tokens compared, "
        f"widest logit gap {float(np.max(flat))}",
    ]
    if reduced is not None:
        secs, calls = reduced.kernel(MOE_KERNEL)
        moe = window.work["moe_decode"]
        notes.append(
            f"moe_decode: {calls} calls, {secs:.4f} s on the device for "
            f"{moe['bytes']} bytes of experts and {moe['flops']} "
            f"operations; roofline "
            f"{readers.roofline_pct(window, MOE_KERNEL, moe)}%")
    if errors:
        notes.append(f"engine errors: {errors[:3]}")
    return {
        "attempted": len(reqs), "failed": failed,
        "e2e": {"ttft_p95_ms": percentile(ttft, 95),
                "tpot_p95_ms": percentile(tpot, 95),
                "setup_s": setup_s},
        "window": window, "checks": checks, "memory_peak": memory_peak,
        "trace": reduced, "notes": notes,
        "compiles_in_window": clock.compiles,
        "compared": {"seqs": seqs, "probes": probes, "dims": dims,
                     "gaps": gaps},
    }


def _ref_args(cfg: Dict[str, Any], dims) -> Dict[str, Any]:
    return dict(dims=dims, eps=float(cfg["rms_norm_eps"]),
                length=int(cfg["serve"]["max_len"]),
                batch=int(cfg["check"]["batch"]))


def control_gaps(cell, res, seed: int):
    """The bfloat16 control's gaps at the positions the program's run
    compared: the reference with the same draws rounded to bfloat16, at
    default precision, puts its own first token at each position, and the
    float32 reference reads that token's gap.  (``bench/calibrate.py``
    computes this for ``serve_lm``.)"""
    import jax.numpy as jnp
    cmp = res["compared"]
    dims, seqs, probes = cmp["dims"], cmp["seqs"], cmp["probes"]
    kw = _ref_args(cell.config, dims)
    low = ref_moe_lm.init(dims, seed, jnp.bfloat16)
    _, tops = ref_moe_lm.gaps(low, seqs, probes, precision="default", **kw)
    del low
    ctrl = [{pos: int(top[pos]) for pos in pr}
            for top, pr in zip(tops, probes)]
    params = ref_moe_lm.init(dims, seed)
    gaps, _ = ref_moe_lm.gaps(params, seqs, ctrl, precision="highest", **kw)
    return gaps
