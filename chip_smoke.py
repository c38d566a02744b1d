#!/usr/bin/env python3
"""Drive Floe's serving and stream paths once on one TPU chip.

    python3 chip_smoke.py [--seed N]

Run from the root of a checkout.  One process holds the chip throughout.

0. Device gate: anything but a TPU backend exits non-zero, naming the
   platform found.  There is no CPU fallback.
1. Serving: seeded requests through the LM serving flow
   (``repro.serving.build_serving_flow``: sched -> flash-attention prefill
   -> flash-decode tick loop -> exactly-once sink) at qwen3-1.7b's
   attention and FFN widths, with random weights from ``--seed``.  Every
   request must be answered exactly once with its full token budget, and
   the compiled Pallas kernels must agree with the ``kernels/ref.py``
   twins on the chip within ``LOGIT_TOL`` / ``AGREE_MIN``.
2. Stream: the array fast-path refinement pass of the stream-clustering
   example (paper section IV.B, ``examples/stream_clustering.py``) scores
   seeded windows of 384-wide posts against 256 centroids with the
   compiled ``cluster_distance`` kernel; every assignment must match a
   float64 NumPy argmin outside near-ties (``TIE_GAP``).

Each phase prints what it measured; the last line of standard output is
one JSON object naming the device.  Any failed check exits non-zero
before that line is printed.
"""
from __future__ import annotations

import argparse
import collections
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT / "examples")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.kernels import ops  # noqa: E402
from repro.launch import use_compile_cache  # noqa: E402
from repro.serving import LMSpec, build_serving_flow, make_request  # noqa: E402
from repro.serving import kv  # noqa: E402

#: qwen3-1.7b's attention and FFN widths (configs/archs.py) in the serving
#: plane's own LM; kv.py has no RoPE or qk-norm
QWEN3_1_7B = LMSpec(vocab=151936, n_heads=16, n_kv_heads=8, head_dim=128,
                    n_layers=28, ffn_mult=3, max_len=512)
#: kernel-vs-reference logits: max |kernel - ref| over max |ref|.  Both
#: paths run the same XLA projections; they differ only in attention, where
#: the reference's einsums at default TPU precision round their inputs to
#: bf16 (relative error 2**-9 each) and the Mosaic kernel does not round
#: the same way.  Per layer that is a few parts in 1e3 of the attention
#: output; 28 residual layers add such errors in quadrature to about 1e-2.
#: A wrong mask, scale or cache position moves logits by O(1) of their
#: scale, so 5e-2 separates rounding from wrong math.
LOGIT_TOL = 5e-2
#: share of rows whose greedy token agrees; a disagreement needs a top-2
#: gap in the logits smaller than the rounding above, which is rare but
#: not impossible over 151,936 candidates
AGREE_MIN = 0.75
#: stream assignments may differ from the float64 argmin only where the two
#: best reference distances lie within this relative gap: the kernel's cross
#: term x.c runs on the MXU, whose default precision rounds inputs to bf16
#: (2**-9 relative), and |x| = |c| = 1 here, so distances carry absolute
#: errors of a few 1e-3 against values near 1
TIE_GAP = 1e-2


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileClock:
    """Sum JAX's compile events (trace, lowering, backend compile) while
    open, over every thread, so the sum can exceed the wall time;
    ``by_fun`` counts backend compiles per jitted function."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0
        self.by_fun: collections.Counter = collections.Counter()

    def _on_event(self, event: str, duration: float, **meta) -> None:
        if event in self.EVENTS:
            self.seconds += duration
        if event == self.EVENTS[-1]:
            self.by_fun[meta.get("fun_name", "?")] += 1

    def __enter__(self) -> "CompileClock":
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_event)


def _device_bytes(key: str) -> int | None:
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else int(stats[key])


def _nbytes(tree) -> int:
    return sum(int(a.nbytes) for a in jax.tree_util.tree_leaves(tree))


# -- phase 1: serving ---------------------------------------------------------

def _requests(rng, n: int, *, rid0: int, vocab: int, prompt_lo: int,
              prompt_hi: int, budget: int):
    lengths = rng.integers(prompt_lo, prompt_hi + 1, size=n)
    return [make_request(rid0 + i,
                         rng.integers(1, vocab, size=int(ln)).tolist(),
                         max_new=budget, t_sub=time.time())
            for i, ln in enumerate(lengths)]


def _kernel_vs_ref(params, spec: LMSpec, prompts, *, max_prompt: int,
                   interpret: bool) -> dict:
    """One admission batch through ``kv.prefill`` and its reference twin,
    then one ``kv.decode_step`` against ``kv.decode_step_ref`` on the
    kernel's caches."""
    B = len(prompts)
    tokens = np.zeros((B, max_prompt), np.int32)
    for i, p in enumerate(prompts):
        tokens[i, :len(p)] = p
    tokens = jnp.asarray(tokens)
    lengths = jnp.asarray([len(p) for p in prompts], jnp.int32)

    def compare(got, want):
        got, want = np.asarray(got), np.asarray(want)
        check(bool(np.isfinite(got).all()), "kernel logits not finite")
        err = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
        agree = float(np.mean(got.argmax(-1) == want.argmax(-1)))
        return err, agree

    logits, kc, vc = kv.prefill(params, tokens, lengths, spec=spec,
                                interpret=interpret)
    ref_logits, _, _ = kv.prefill_ref(params, tokens, lengths, spec=spec)
    check(logits.shape == (B, spec.vocab), f"prefill logits {logits.shape}")
    prefill_err, prefill_agree = compare(logits, ref_logits)
    del ref_logits
    tok0 = kv.greedy(logits)
    # the reference first: the kernel's step consumes the caches it is
    # handed (donated)
    ref_step, _, _ = kv.decode_step_ref(params, kc, vc, lengths, tok0,
                                        spec=spec)
    step, _, _ = kv.decode_step(params, kc, vc, lengths, tok0, spec=spec,
                                interpret=interpret)
    decode_err, decode_agree = compare(step, ref_step)
    return {"prefill_err": prefill_err, "prefill_agree": prefill_agree,
            "decode_err": decode_err, "decode_agree": decode_agree}


def serving_phase(spec: LMSpec = QWEN3_1_7B, *, n_slots: int = 8,
                  max_prompt: int = 128, n_requests: int = 16,
                  prompt_lo: int = 16, budget: int = 32, seed: int = 0,
                  interpret: bool = False) -> dict:
    """Serve two rounds of ``n_requests`` seeded requests through one
    session: the first pays the compiles, the second shows the warm path.
    Then check the kernels against the reference."""
    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    flow = build_serving_flow(spec=spec, n_slots=n_slots,
                              max_prompt=max_prompt, default_budget=budget,
                              seed=seed)
    build_s = time.perf_counter() - t0
    params = flow.stages["prefill"].proto.params
    weight_bytes = _nbytes(params)
    print(f"serving: spec {spec}")
    print(f"serving: weights {weight_bytes} bytes, built and placed in "
          f"{build_s:.3f} host s")
    out = {"build_s": build_s, "weight_bytes": weight_bytes, "passes": []}
    prefill_sizes_before = kv.prefill._cache_size()
    first_prompts = None
    with flow.session(drain_timeout=600) as s:
        flakes = s.coordinator.flakes
        prefill, decode = flakes["prefill"]._proto, flakes["decode"]._proto
        check(prefill.interpret is interpret and decode.interpret is interpret,
              f"pellets run with interpret={prefill.interpret}/"
              f"{decode.interpret}, expected {interpret}")
        kv_pair = _nbytes((decode.k, decode.v))
        held = _device_bytes("bytes_in_use")
        out["held_after_open"] = held
        print(f"serving: device bytes in use after session open: {held} "
              f"(weights {weight_bytes}; one decode KV pair is {kv_pair} "
              f"bytes and two are alive: the flow's validated prototype "
              f"pellet and the session's decode pellet)")
        for p in range(2):
            reqs = _requests(rng, n_requests, rid0=p * n_requests,
                             vocab=spec.vocab, prompt_lo=prompt_lo,
                             prompt_hi=max_prompt, budget=budget)
            if first_prompts is None:
                first_prompts = [r["prompt"] for r in reqs[:n_slots]]
            with CompileClock() as clock:
                t0 = time.perf_counter()
                s.inject_many("sched", reqs)
                results = s.results()
                wall = time.perf_counter() - t0
            check(not s.errors, f"engine errors: {s.errors[:3]}")
            resp = [r for r in results if isinstance(r, dict) and "rid" in r]
            counts = collections.Counter(r["rid"] for r in resp)
            want = {r["rid"] for r in reqs}
            check(set(counts) == want and all(c == 1 for c in
                                              counts.values()),
                  f"pass {p}: answered {sorted(counts.items())}, "
                  f"want each of {sorted(want)} once")
            short = [r["rid"] for r in resp if r["n_new"] != budget]
            check(not short, f"pass {p}: n_new != {budget} for rids {short}")
            tokens = n_requests * budget
            print(f"serving pass {p}: {n_requests} requests, {tokens} tokens "
                  f"in {wall:.3f} s wall, of which compile "
                  f"{clock.seconds:.3f} s ({sum(clock.by_fun.values())} "
                  f"backend compiles: {dict(clock.by_fun)}); "
                  f"decode steps so far {decode.n_steps}")
            out["passes"].append({"wall_s": wall,
                                  "compile_s": clock.seconds,
                                  "compiles": dict(clock.by_fun)})
    del prefill, decode, flakes, s
    sizes = kv.prefill._cache_size() - prefill_sizes_before
    out["prefill_batch_sizes_compiled"] = sizes
    print(f"serving: prefill batch sizes compiled: {sizes}")
    with CompileClock() as clock:
        parity = _kernel_vs_ref(params, spec, first_prompts,
                                max_prompt=max_prompt, interpret=interpret)
    out.update(parity)
    print(f"serving: kernel vs reference: prefill logits err "
          f"{parity['prefill_err']:.6g}, greedy agreement "
          f"{parity['prefill_agree']:.4f}; decode logits err "
          f"{parity['decode_err']:.6g}, greedy agreement "
          f"{parity['decode_agree']:.4f} (tolerance err <= {LOGIT_TOL}, "
          f"agreement >= {AGREE_MIN}; compile {clock.seconds:.3f} s)")
    for name in ("prefill", "decode"):
        check(parity[f"{name}_err"] <= LOGIT_TOL,
              f"{name} logits err {parity[f'{name}_err']} > {LOGIT_TOL}")
        check(parity[f"{name}_agree"] >= AGREE_MIN,
              f"{name} greedy agreement {parity[f'{name}_agree']} "
              f"< {AGREE_MIN}")
    peak = _device_bytes("peak_bytes_in_use")
    out["peak_bytes"] = peak
    print(f"serving: device peak_bytes_in_use {peak}")
    return out


# -- phase 2: stream clustering -----------------------------------------------

def _posts(rng, centroids: np.ndarray, n: int) -> np.ndarray:
    """Unit-norm posts scattered around seeded unit-norm centroids, the way
    sentence embeddings sit around topic centres."""
    k, dim = centroids.shape
    x = centroids[rng.integers(0, k, size=n)] \
        + rng.normal(0.0, 1.0 / np.sqrt(dim), size=(n, dim))
    return (x / np.linalg.norm(x, axis=1, keepdims=True)).astype(np.float32)


def stream_phase(*, k: int = 256, dim: int = 384, windows: int = 4,
                 rows: int = 4096, seed: int = 0,
                 interpret: bool = False) -> dict:
    """Feed ``windows`` windows of ``rows`` posts through the refinement
    flow and hold every assignment to a float64 argmin."""
    from stream_clustering import refine_flow

    check(ops.pallas_interpret() is interpret,
          f"cluster_distance would run with interpret="
          f"{ops.pallas_interpret()}, expected {interpret}")
    rng = np.random.default_rng(seed + 1)
    c = rng.normal(size=(k, dim))
    centroids = (c / np.linalg.norm(c, axis=1, keepdims=True)).astype(
        np.float32)
    if not interpret:
        hlo = ops.cluster_distance_op.lower(
            jnp.zeros((8, dim), jnp.float32), centroids,
            interpret=False).as_text()
        check("tpu_custom_call" in hlo,
              "cluster_distance lowered without a Mosaic custom call")
    c64 = centroids.astype(np.float64)
    out = {"windows": []}
    with refine_flow(centroids).session(drain_timeout=600) as s:
        for w in range(windows):
            posts = _posts(rng, centroids, rows)
            with CompileClock() as clock:
                t0 = time.perf_counter()
                s.inject_many("dist", list(posts))
                got = np.asarray(jax.device_get(s.results()), np.int64)
                wall = time.perf_counter() - t0
            check(not s.errors, f"engine errors: {s.errors[:3]}")
            check(got.shape == (rows,),
                  f"window {w}: census {got.shape[0]} of {rows}")
            x64 = posts.astype(np.float64)
            d = ((x64 ** 2).sum(1)[:, None] + (c64 ** 2).sum(1)[None, :]
                 - 2.0 * x64 @ c64.T)
            best2 = np.sort(d, axis=1)[:, :2]
            tie = (best2[:, 1] - best2[:, 0]) <= TIE_GAP * np.abs(best2[:, 1])
            wrong = (got != d.argmin(1)) & ~tie
            check(not wrong.any(),
                  f"window {w}: {int(wrong.sum())} assignments differ from "
                  f"the float64 argmin outside near-ties")
            print(f"stream window {w}: {rows} posts x {k} centroids x {dim} "
                  f"in {wall:.3f} s wall = {rows / wall:.1f} rows/s; compile "
                  f"{clock.seconds:.3f} s ({dict(clock.by_fun)}); census "
                  f"{got.shape[0]}/{rows}, near-ties {int(tie.sum())}, "
                  f"mismatches inside near-ties "
                  f"{int(((got != d.argmin(1)) & tie).sum())}")
            out["windows"].append({"wall_s": wall, "rows_per_s": rows / wall,
                                   "compile_s": clock.seconds,
                                   "ties": int(tie.sum())})
    return out


# -- entry point ---------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights, requests, centroids and posts")
    args = ap.parse_args(argv)
    print(f"jax {jax.__version__}, devices {jax.devices()}")
    platform = jax.default_backend()
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's default backend is "
              f"{platform!r}; there is no CPU fallback", file=sys.stderr)
        return 2
    print(f"compile cache: {use_compile_cache()}")
    serving_phase(seed=args.seed)
    stream_phase(seed=args.seed)
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
