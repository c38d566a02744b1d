"""Runner of configurations of kind ``serve_lm``: the serving plane's LM
(``repro.serving.build_serving_flow``: sched -> flash-attention prefill ->
flash-decode tick loop -> exactly-once sink) under an open loop of
requests.

Set-up draws the weights on the device from the seed (``ref_lm.init``),
builds the flow around them, opens one session and warms every admission
batch size and the decode step through the flow itself.  The window sends
the traffic file's requests at their due times; the monitor stamps each
response as it leaves.  Afterwards every request must have been answered
exactly once with its full budget, and a sample drawn from the seed, the
longest request in it, is held to the plain reference (``ref_lm``): the
mean, over the served tokens compared, of the gap by which a served
token's logit lies below the reference's best at that position.  The
widest such gap is printed beside it; it is not compared, because the
bfloat16 control reads less than three times the program's largest.
"""
from __future__ import annotations

import collections
import gc
import time
from typing import Any, Dict, List
from unittest import mock

import numpy as np

from yard import gen, ref_lm, work
from yard.common import CompileClock, Window, peak_bytes_in_use, percentile
from yard.harness import Injector, Monitor, Profile, hist_delta, hist_state

STAGES = ("sched", "prefill", "decode", "respond")
#: rids of the warm-up requests lie above every rid of the window
WARM_RID0 = 1 << 30


def build(cfg: Dict[str, Any], seed: int):
    """The flow under test, around weights drawn from ``seed``; returns
    ``(flow, params, dims)``."""
    from repro.serving import dataflow
    serve = cfg["serve"]
    dims = work.LMDims.from_config(cfg)
    spec = dataflow.LMSpec(vocab=dims.vocab, n_heads=dims.n_heads,
                           n_kv_heads=dims.n_kv_heads,
                           head_dim=dims.head_dim, n_layers=dims.n_layers,
                           max_len=int(serve["max_len"]),
                           ffn_mult=dims.d_ff // dims.d_model)
    if spec.ffn_mult * spec.d_model != dims.d_ff:
        raise ValueError(f"d_ff {dims.d_ff} is not a multiple of d_model")
    params = ref_lm.init(dims, seed)
    # the flow takes the benchmark's weights in place of drawing its own
    with mock.patch.object(dataflow, "init_params",
                           lambda spec_, seed_=0: params):
        flow = dataflow.build_serving_flow(
            spec=spec, n_slots=int(serve["slots"]),
            max_prompt=int(serve["max_prompt"]),
            default_budget=int(serve["warm_budget"]), seed=seed)
    return flow, params, dims


def _request(rid: int, prompt, budget: int, t_sub: float):
    from repro.serving import make_request
    return make_request(rid, prompt, max_new=budget, t_sub=t_sub)


def warm(session, monitor: Monitor, cfg: Dict[str, Any]) -> None:
    """Every admission batch size, largest first, then the decode step:
    ``b`` requests sent together reach prefill as one carrier."""
    serve = cfg["serve"]
    rid = WARM_RID0
    for b in range(int(serve["slots"]), 0, -1):
        reqs = [_request(rid + i, [1 + i] * 16, int(serve["warm_budget"]),
                         time.time()) for i in range(b)]
        rid += b
        session.inject_many("sched", reqs)
        if not monitor.wait_for(b, time.time() + 600):
            raise RuntimeError(f"warm-up wave of {b} was not answered; "
                               f"engine errors {session.errors[:3]}")
        monitor.take()


def decode_progress(pellet) -> Dict[int, int]:
    """Tokens each live request holds in the decode stage right now."""
    for _ in range(100):
        try:
            return {int(m["rid"]): len(m["tokens"])
                    for m in list(pellet.meta.values())}
        except RuntimeError:          # the slot table changed under us
            continue
    return {}


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
        steps0 = decode.n_steps
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
                steps1 = decode.n_steps
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
    w0, w1 = t0, close

    def done_by(rid, t):
        hit = ok.get(rid)
        return hit is not None and hit[1] <= t

    decode_flops = 0
    dec_attn = {"flops": 0, "bytes": 0}
    for rid, (p, t_done) in ok.items():
        plen = len(by_rid[rid]["prompt"])
        n_dec = p["n_new"] - 1
        a = n_dec if done_by(rid, w0) else max(0, prog0.get(rid, 1) - 1)
        b = n_dec if done_by(rid, w1) else max(0, prog1.get(rid, 1) - 1)
        for j in range(a + 1, b + 1):
            decode_flops += work.decode_token_flops(dims, plen + j)
            one = work.decode_attention_work(dims, plen + j)
            dec_attn["flops"] += dims.n_layers * one["flops"]
            dec_attn["bytes"] += dims.n_layers * one["bytes"]
    prefill_flops = 0
    flash = {"flops": 0, "bytes": 0}
    for rid, (p, _) in ok.items():
        if w0 <= p["t_first"] <= w1:
            plen = len(by_rid[rid]["prompt"])
            prefill_flops += work.prefill_flops(dims, plen)
            one = work.flash_attention_work(dims, plen)
            flash["flops"] += dims.n_layers * one["flops"]
            flash["bytes"] += dims.n_layers * one["bytes"]
    window = Window(seconds=seconds, peak=peak,
                    stages=hist_delta(h0, h1),
                    work={"decode_flops": decode_flops,
                          "prefill_flops": prefill_flops,
                          "decode_attention": dec_attn,
                          "flash_attention": flash},
                    counters={"decode_steps": steps1 - steps0},
                    trace=reduced)

    # -- the reference -----------------------------------------------------
    limits = cfg["limits"]
    chosen = sample(reqs, ok, seed, int(cfg["check"]["sample_tokens"]))
    seqs, probes = sequences(reqs, ok, chosen)
    gaps = reference_gaps(params, dims, cfg, seqs, probes)
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
        f"reference: {len(seqs)} requests, {served} served tokens compared, "
        f"widest logit gap {float(np.max(flat))}",
    ]
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


def sample(reqs, ok, seed: int, tokens: int) -> List[int]:
    """Requests to compare, drawn from the seed: the longest answered
    request, then others in a seeded order until ``tokens`` served tokens
    are in."""
    rids = sorted(ok)
    if not rids:
        return []
    size = {rid: ok[rid][0]["n_new"] for rid in rids}
    longest = max(rids, key=lambda rid: (len(reqs[rid]["prompt"])
                                         + size[rid], -rid))
    chosen, total = [longest], size[longest]
    for rid in gen.rng(seed, 7).permutation(rids):
        if total >= tokens:
            break
        if int(rid) != longest:
            chosen.append(int(rid))
            total += size[int(rid)]
    return chosen


def reference_gaps(params, dims, cfg, seqs, probes) -> List[np.ndarray]:
    """Per sequence, the gaps by which each probed token's logit lies below
    the float32 reference's best, at highest matmul precision."""
    gaps, _ = ref_lm.gaps(params, seqs, probes, dims=dims,
                          eps=float(cfg["rms_norm_eps"]),
                          length=int(cfg["serve"]["max_len"]),
                          batch=int(cfg["check"]["batch"]),
                          precision="highest")
    return gaps


def sequences(reqs, ok, chosen):
    """Each chosen request as one token sequence (its prompt, then every
    served token but the last) and the served token to read at each
    position that predicts one."""
    seqs, probes = [], []
    for rid in chosen:
        prompt = [int(t) for t in reqs[rid]["prompt"]]
        toks = [int(t) for t in ok[rid][0]["tokens"]]
        seqs.append(prompt + toks[:-1])
        probes.append({len(prompt) - 1 + j: t for j, t in enumerate(toks)})
    return seqs, probes
