"""Benchmark harness entry point — one suite per paper table/figure.

Prints ``name,us_per_call,derived`` CSV per the harness contract, and
exits non-zero when any suite raised (its row reads ``<suite>_FAILED``).

  PYTHONPATH=src python -m benchmarks.run [suite ...]

Suites: adaptation (Fig. 4), pipeline (§IV.A), clustering (§IV.B),
engine (runtime micro), kernels, recovery, serving (LM SLOs + hot-swap),
train (100M driver sanity), roofline (needs
results/dryrun_roofline.json from the dry-run sweep).
"""
from __future__ import annotations

import sys
import time
import traceback

SUITES = ("adaptation", "pipeline", "clustering", "engine", "kernels",
          "recovery", "serving", "train", "roofline")


def _train_suite():
    sys.path.insert(0, "examples")
    from train_lm import FLOE_100M  # registers the config
    from repro.launch.train import train
    t0 = time.time()
    out = train("floe-100m", steps=12, global_batch=2, seq_len=64,
                log_every=0)
    us = (time.time() - t0) * 1e6 / 12
    return [("train_step_floe100m", us,
             f"loss {out['losses'][0]:.3f}->{out['final_loss']:.3f} "
             f"over 12 steps (full run: examples/train_lm.py)")], {}


def main() -> int:
    from repro.launch import use_compile_cache
    use_compile_cache()
    want = sys.argv[1:] or list(SUITES)
    rows = []
    failed = []
    for suite in want:
        try:
            if suite == "adaptation":
                from . import bench_adaptation as m
                r, extras = m.run()
                m.record(extras)   # append to BENCH_adaptation.json
            elif suite == "pipeline":
                from . import bench_pipeline as m
                r, _ = m.run()
            elif suite == "clustering":
                from . import bench_clustering as m
                r, _ = m.run()
            elif suite == "engine":
                from . import bench_engine as m
                r, extras = m.run()
                m.record(extras)   # append to the BENCH_engine.json trajectory
            elif suite == "kernels":
                from . import bench_kernels as m
                r, _ = m.run()
            elif suite == "recovery":
                from . import bench_recovery as m
                r, extras = m.run()
                m.record(extras)   # append to BENCH_recovery.json
            elif suite == "serving":
                from . import bench_serving as m
                r, extras = m.run()
                m.record(extras)   # append to BENCH_serving.json
            elif suite == "train":
                r, _ = _train_suite()
            elif suite == "roofline":
                from . import roofline as m
                r, _ = m.run()
            else:
                print(f"# unknown suite {suite!r}", file=sys.stderr)
                failed.append(suite)
                continue
            rows.extend(r)
        except Exception:
            print(f"# suite {suite} FAILED:", file=sys.stderr)
            traceback.print_exc()
            rows.append((f"{suite}_FAILED", 0.0, "see stderr"))
            failed.append(suite)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    if failed:
        print(f"# failed suites: {', '.join(failed)}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
