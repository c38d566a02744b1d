#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \
        --control-seeds 1,2,3 --seconds <s>

In one process, for each of ``--seeds``, one run of the cell at its own
load, and the numbers it compares (the program's readings; the lower
reading of each is the largest).  For each of ``--control-seeds`` (runs
of the program first where the seed is not among ``--seeds``), the
control's reading (the upper reading is the smallest): the plain
reference computed in bfloat16, the precision below the configuration's
float32, put in the program's place.  At every position of the same
prompts and served tokens the token it puts first is read by the float32
reference.

Beside the number compared, each reading gives the other statistics of
the same gaps (``gap_stats``), so that a number that separates the two
sides can be chosen from one call.  The benchmark's own runs never run
this.  One JSON line per reading goes to standard output, and a summary
last."""
import time

T_START = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

from yard import common  # noqa: E402


def gap_stats(gaps) -> dict:
    """The widest gap, its 99th percentile and mean, and the share of
    positions whose token is not the reference's first."""
    import numpy as np
    g = np.concatenate([np.asarray(x, np.float64) for x in gaps])
    return {"max": float(g.max()), "p99": float(np.quantile(g, 0.99)),
            "mean": float(g.mean()), "off_top_share": float(np.mean(g > 0)),
            "positions": int(g.size)}


def control_gaps(cell, res, seed: int):
    """The control's gaps at the positions the program's run compared."""
    import jax.numpy as jnp
    from yard import ref_lm
    cmp = res["compared"]
    dims, seqs, probes = cmp["dims"], cmp["seqs"], cmp["probes"]
    cfg = cell.config
    kw = dict(dims=dims, eps=float(cfg["rms_norm_eps"]),
              length=int(cfg["serve"]["max_len"]),
              batch=int(cfg["check"]["batch"]))
    # the same draws as the float32 weights, rounded to bfloat16
    low = ref_lm.init(dims, seed, jnp.bfloat16)
    _, tops = ref_lm.gaps(low, seqs, probes, precision="default", **kw)
    del low
    ctrl = [{pos: int(top[pos]) for pos in pr}
            for top, pr in zip(tops, probes)]
    params = ref_lm.init(dims, seed)
    gaps, _ = ref_lm.gaps(params, seqs, ctrl, precision="highest", **kw)
    return gaps


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = common.load_cell(args.workload, ROOT)
    common.use_compile_cache(ROOT)
    device = common.device_gate(cell.chips)
    peak = common.peaks_of(device["kind"])
    kind = cell.config["kind"]
    runner = common.load_module(BENCH / "kinds" / f"{kind}.py",
                                f"bench_kind_{kind}")
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    program, control = {}, {}
    for seed in seeds + sorted(controls - set(seeds)):
        res = runner.run(cell, seed=seed, seconds=args.seconds, trace=False,
                         peak=peak, t_start=time.time())
        gc.collect()
        if seed in seeds:
            for name, v, lim in res["checks"]:
                program.setdefault(name, []).append(v)
            print(json.dumps({"seed": seed, "side": "program",
                              "checks": res["checks"], "e2e": res["e2e"],
                              "gap_stats": gap_stats(res["compared"]["gaps"]),
                              "attempted": res["attempted"],
                              "failed": res["failed"],
                              "memory_peak": res["memory_peak"],
                              "notes": res["notes"]}),
                  flush=True)
        if seed not in controls:
            continue
        stats = gap_stats(control_gaps(cell, res, seed))
        control.setdefault("logit_gap_mean", []).append(stats["mean"])
        print(json.dumps({"seed": seed, "side": "control",
                          "gap_stats": stats}), flush=True)
        gc.collect()
    summary = {name: {"lower": max(vs), "program": vs,
                      "upper": min(control[name]) if name in control
                      else None, "control": control.get(name)}
               for name, vs in program.items()}
    print(json.dumps({"workload": args.workload, "device": device,
                      "summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
