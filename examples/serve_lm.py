"""Continuous LM serving as a Floe dataflow, end to end.

The "always-on" half of the paper on the Session API (the PR 8 serving
plane): a bursty request stream is injected into a flow whose stages are
admission/scheduling → flash-attention prefill → continuously-batched
flash-decode (a tick self-loop keeps generation inside the dataflow) →
exactly-once response sink.  Mid-stream the model weights are hot-swapped
via ``session.apply`` (§II.B dynamic task update) without dropping a
request — the KV/slot tables ride across on ``__floe_state__`` and every
response records which model version produced it — while a §III
tail-latency SLO strategy elastically scales the decode stage.

Run:  PYTHONPATH=src python examples/serve_lm.py
"""
import time

import numpy as np

from repro.serving import (LMSpec, build_serving_flow, make_request,
                           swapped_flow)


def main():
    spec = LMSpec(vocab=32, n_heads=2, n_kv_heads=1, head_dim=4,
                  n_layers=2, max_len=32)
    flow = build_serving_flow(
        spec=spec, n_slots=4, default_budget=8, seed=0, version=0,
        elastic={"strategy": "slo", "queue_slo": 0.002, "max_cores": 4,
                 "drain_horizon": 0.2})

    rng = np.random.default_rng(0)
    rid = 0
    t0 = time.time()
    with flow.session(sample_interval=0.05) as s:
        for burst in range(4):
            n = 6 if burst % 2 == 0 else 2          # bursty arrivals
            for _ in range(n):
                prompt = rng.integers(1, spec.vocab, size=4).tolist()
                s.inject("sched", make_request(rid, prompt, max_new=8,
                                               t_sub=time.time()))
                rid += 1
            time.sleep(0.25)
            if burst == 1:
                # let the first bursts answer on v0, then update live:
                # any generation still in flight carries over on
                # __floe_state__ and is tagged with the new version
                deadline = time.time() + 60
                while (len(s.coordinator.outputs) < rid
                       and time.time() < deadline):
                    time.sleep(0.02)
                summary = s.apply(swapped_flow(flow, seed=1, version=1))
                print(f"[burst={burst}] live model update -> swapped "
                      f"{sorted(summary['swapped'])} (zero requests lost)")
        responses = [m.payload for m in s.drain(timeout=120)
                     if isinstance(m.payload, dict) and "rid" in m.payload]
        decode_events = [e for e in s.events("elasticity")
                         if e.get("flake") == "decode"]

    v0 = sum(1 for r in responses if r["version"] == 0)
    v1 = sum(1 for r in responses if r["version"] >= 1)
    ttft = [r["t_first"] - r["t_sub"] for r in responses]
    print(f"served {len(responses)}/{rid} requests in "
          f"{time.time() - t0:.1f}s: {v0} on v0, {v1} on v1; "
          f"p50 TTFT {np.percentile(ttft, 50):.3f}s; "
          f"{len(decode_events)} decode scaling events")
    assert len(responses) == rid, "lost requests across the hot-swap"
    assert v0 > 0 and v1 > 0
    assert all(len(r["tokens"]) == int(r["n_new"]) for r in responses)


if __name__ == "__main__":
    main()
