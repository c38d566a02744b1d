"""Serving-plane benchmark: LM inference SLOs on the Floe dataflow.

Drives the PR 8 serving plane (``repro.serving.build_serving_flow``) —
admission → flash-attention prefill → continuously-batched flash-decode
with a tick self-loop — under the bursty traffic model shared with
``bench_adaptation`` and records the serving SLO signals:

* **TTFT** (time to first token: prefill emit − submission) and **TPOT**
  (time per output token during decode), p50/p95 each;
* sustained decode throughput (total generated tokens / decode wall);
* elastic decode scale-out/in events from the tail-latency SLO strategy
  (``.elastic(strategy="slo", ...)`` keyed on the PR 6 queue-wait p95);
* a live weight hot-swap applied mid-stream — requests lost across the
  swap (must be 0) and the response count per model version.

Appends one trajectory record to ``BENCH_serving.json`` via ``record``
(wired into ``benchmarks/run.py``).

  PYTHONPATH=src python -m benchmarks.bench_serving \
      [--smoke] [--profile bursty] [--n 4] [--periods 3] [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Dict, List, Tuple

import numpy as np

try:
    from .bench_adaptation import _burst_sizes
except ImportError:                      # direct script invocation
    from bench_adaptation import _burst_sizes

_JSON_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          os.pardir, "BENCH_serving.json")

#: compact geometry so interpret-mode Pallas kernels keep the bench fast;
#: the serving plane is shape-generic (tests cover other geometries).
_SPEC = dict(vocab=32, n_heads=2, n_kv_heads=1, head_dim=4, n_layers=2,
             max_len=32)


def _pct(xs: List[float], q: float) -> float:
    return float(np.percentile(np.asarray(xs, dtype=np.float64), q))


def _warm_jit(spec, n_slots: int, max_prompt: int = 8) -> None:
    """Pre-compile the prefill/decode jit entries the flow will hit so
    TTFT/TPOT measure serving, not XLA compilation (same process-global
    jit cache; prefill recompiles per batch size, decode is fixed-shape)."""
    import jax.numpy as jnp

    from repro.kernels.ops import pallas_interpret
    from repro.serving import kv

    params = kv.init_params(spec, seed=0)
    interpret = pallas_interpret()
    L, Hkv, hd = spec.n_layers, spec.n_kv_heads, spec.head_dim
    for b in range(1, max(2, n_slots) + 1):
        kv.prefill(params, jnp.zeros((b, max_prompt), jnp.int32),
                   jnp.ones((b,), jnp.int32), spec=spec,
                   interpret=interpret)
    shape = (L, n_slots, spec.max_len, Hkv, hd)
    # two buffers: the step consumes (is donated) each cache it is handed
    kv.decode_step(params, jnp.zeros(shape, jnp.float32),
                   jnp.zeros(shape, jnp.float32),
                   jnp.ones((n_slots,), jnp.int32),
                   jnp.zeros((n_slots,), jnp.int32), spec=spec,
                   interpret=interpret)


def run_serving(*, profile: str = "bursty", n_per_burst: int = 4,
                periods: int = 3, budget: int = 12, n_slots: int = 4,
                gap_s: float = 0.3, swap_gap_s: float = 30.0,
                settle_s: float = 0.8, warm: bool = True) -> dict:
    """One traffic profile through the serving flow, with a hot-swap in
    the middle burst and the SLO elasticity controller on decode."""
    from repro.serving import LMSpec, build_serving_flow, make_request, \
        swapped_flow

    spec = LMSpec(**_SPEC)
    if warm:
        _warm_jit(spec, n_slots)
    flow = build_serving_flow(
        spec=spec, n_slots=n_slots, default_budget=budget, seed=0,
        version=0,
        elastic={"strategy": "slo", "queue_slo": 0.002, "max_cores": 4,
                 "drain_horizon": 0.2})
    sizes = _burst_sizes(profile, n_per_burst, periods)
    swap_at = len(sizes) // 2           # apply new weights mid-stream
    rid = 0
    pre_swap_rids: set = set()
    swap_summary: dict = {}
    t0 = time.time()
    with flow.session(sample_interval=0.05) as s:
        for p, n in enumerate(sizes):
            if p == swap_at:
                # let the earlier bursts finish on v0 (bounded wait), then
                # swap live — anything still in flight is carried across
                # by __floe_state__ and finishes tagged with the new
                # version, so the record shows a genuine v0/v1 mix
                deadline = time.time() + swap_gap_s
                while (len(s.coordinator.outputs) < len(pre_swap_rids)
                       and time.time() < deadline):
                    time.sleep(0.02)
                swap_summary = s.apply(swapped_flow(flow, seed=1,
                                                    version=1))
            for _ in range(n):
                prompt = [1 + (rid + j) % (spec.vocab - 1)
                          for j in range(1 + rid % 4)]
                s.inject("sched", make_request(rid, prompt, max_new=budget,
                                               t_sub=time.time()))
                if p < swap_at:
                    pre_swap_rids.add(rid)
                rid += 1
            time.sleep(gap_s)
        msgs = s.drain(timeout=300)
        # let the controller observe the drained queue and quiesce decode
        # to 0 cores — the deterministic scale-in event
        time.sleep(settle_s)
        responses = [m.payload for m in msgs
                     if isinstance(m.payload, dict) and "rid" in m.payload]
        elastic = [e for e in s.events("elasticity")
                   if e.get("flake") == "decode"]
        sink_state = s.coordinator.flakes["respond"].state
    wall = time.time() - t0

    by_rid: Dict[int, dict] = {}
    for r in responses:
        by_rid.setdefault(int(r["rid"]), r)
    lost = rid - len(by_rid)
    versions: Dict[int, int] = {}
    for r in by_rid.values():
        versions[int(r["version"])] = versions.get(int(r["version"]), 0) + 1
    post_swap_wrong = sum(1 for i, r in by_rid.items()
                          if i not in pre_swap_rids and int(r["version"]) != 1)

    ttft = [r["t_first"] - r["t_sub"] for r in by_rid.values()]
    tpot = [(r["t_done"] - r["t_first"]) / max(int(r["n_new"]) - 1, 1)
            for r in by_rid.values()]
    tokens = sum(int(r["n_new"]) for r in by_rid.values())
    decode_wall = (max(r["t_done"] for r in by_rid.values())
                   - min(r["t_first"] for r in by_rid.values()))
    scale_out = sum(1 for e in elastic
                    if e["cores_after"] > e["cores_before"])
    scale_in = sum(1 for e in elastic
                   if e["cores_after"] < e["cores_before"])

    return {
        "profile": profile,
        "bursts": sizes,
        "requests": rid,
        "responses": len(by_rid),
        "lost": lost,
        "duplicates": int(sink_state.get("duplicates", 0)),
        "versions": {str(k): v for k, v in sorted(versions.items())},
        "post_swap_wrong_version": post_swap_wrong,
        "swapped_stages": sorted(swap_summary.get("swapped", [])),
        "tokens": tokens,
        "decode_tok_per_s": round(tokens / max(decode_wall, 1e-9), 1),
        "ttft_p50_ms": round(_pct(ttft, 50) * 1e3, 2),
        "ttft_p95_ms": round(_pct(ttft, 95) * 1e3, 2),
        "tpot_p50_ms": round(_pct(tpot, 50) * 1e3, 2),
        "tpot_p95_ms": round(_pct(tpot, 95) * 1e3, 2),
        "elastic_scale_out": scale_out,
        "elastic_scale_in": scale_in,
        "peak_decode_cores": max((e["cores_after"] for e in elastic),
                                 default=1),
        "wall_s": round(wall, 3),
    }


def run(*, smoke: bool = False, profile: str = "bursty",
        n_per_burst: int = 4, periods: int = 3
        ) -> Tuple[List[Tuple[str, float, str]], dict]:
    if smoke:
        n_per_burst, periods, budget = 2, 2, 4
    else:
        budget = 12
    r = run_serving(profile=profile, n_per_burst=n_per_burst,
                    periods=periods, budget=budget, warm=not smoke)
    assert r["lost"] == 0, f"serving: lost {r['lost']} requests"
    assert r["post_swap_wrong_version"] == 0, \
        f"serving: {r['post_swap_wrong_version']} post-swap responses " \
        f"missing the new model version"
    us = r["wall_s"] * 1e6 / max(r["requests"], 1)
    rows = [
        (f"serving_{profile}", us,
         f"{r['requests']} reqs {r['tokens']} toks "
         f"{r['decode_tok_per_s']} tok/s "
         f"ttft_p95={r['ttft_p95_ms']}ms tpot_p95={r['tpot_p95_ms']}ms"),
        ("serving_hot_swap", 0.0,
         f"lost={r['lost']} dup={r['duplicates']} "
         f"versions={r['versions']} swapped={r['swapped_stages']}"),
        ("serving_elastic_slo", 0.0,
         f"scale_out={r['elastic_scale_out']} "
         f"scale_in={r['elastic_scale_in']} "
         f"peak_cores={r['peak_decode_cores']}"),
    ]
    return rows, r


def record(results: dict, path: str = _JSON_PATH) -> None:
    """Append one trajectory record to BENCH_serving.json."""
    history: List[dict] = []
    if os.path.exists(path):
        try:
            with open(path) as f:
                history = json.load(f)
            if not isinstance(history, list):
                history = [history]
        except (OSError, ValueError):
            history = []
    history.append({"ts": time.time(),
                    "ts_iso": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
                    "suite": "serving", **results})
    with open(path, "w") as f:
        json.dump(history, f, indent=2)
        f.write("\n")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small fast run for CI (still swaps + scales)")
    ap.add_argument("--profile", default="bursty",
                    choices=("bursty", "periodic", "random"))
    ap.add_argument("--n", type=int, default=4,
                    help="requests per burst")
    ap.add_argument("--periods", type=int, default=3,
                    help="bursts in the run")
    ap.add_argument("--out", default=_JSON_PATH,
                    help="trajectory JSON path ('' disables the record)")
    args = ap.parse_args()
    rows, extras = run(smoke=args.smoke, profile=args.profile,
                       n_per_burst=args.n, periods=args.periods)
    for name, us, derived in rows:
        print(f"{name},{us:.0f},{derived}")
    if args.out:
        record(extras, args.out)


if __name__ == "__main__":
    main()
