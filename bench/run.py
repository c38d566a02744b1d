#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell names a configuration
(``bench/configs/``), whose ``kind`` picks the runner
(``bench/kinds/<kind>.py``), and a traffic mix
(``bench/traffic/<mix>.json``).  The runner sets the cell up from the
seed, warms every shape the window uses, measures for ``--seconds``, then
compares what the timed path produced with the plain reference.

Standard error ends with each number compared beside its limit.  The last
line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics, each read by
``bench/metrics/<name>.py``), ``device``, with ``--trace 1`` a
``breakdown`` of the device's top operations and longest idle gaps, and
last ``checks``.  Off a TPU, or with fewer chips than the cell asks for,
it exits 2 and prints no result.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

from yard import common  # noqa: E402


def _num(x):
    """A metric's value as JSON takes it: a non-finite reading (an item
    that never came) has no number."""
    x = float(x)
    return x if math.isfinite(x) else None


def result_line(cell, res, device, trace: bool) -> dict:
    metrics = {}
    if trace:
        for m in cell.per_layer:
            v = common.read_metric(m["name"], res["window"], cell.root)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": _num(res["e2e"][m["name"]]),
                                  "unit": m["unit"]}
    device = dict(device, memory_peak_bytes=res["memory_peak"])
    line = {"correct": all(v <= lim for _, v, lim in res["checks"]),
            "attempted": int(res["attempted"]), "failed": int(res["failed"]),
            "metrics": metrics, "device": device}
    reduced = res.get("trace")
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        line["breakdown"] = {"device_ops": reduced.top_ops(10),
                             "idle_gaps": [[n, s] for n, s in reduced.gaps]}
    line["checks"] = {name: {"value": _num(v), "limit": lim}
                      for name, v, lim in res["checks"]}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = common.load_cell(args.workload, ROOT)
        common.use_compile_cache(ROOT)
        device = common.device_gate(cell.chips)
        peak = common.peaks_of(device["kind"])
        runner = common.load_module(
            BENCH / "kinds" / f"{cell.config['kind']}.py",
            f"bench_kind_{cell.config['kind']}")
    except (common.BenchError, OSError, KeyError, ValueError) as e:
        print(f"bench: {type(e).__name__}: {e}", file=sys.stderr)
        return 2
    try:
        res = runner.run(cell, seed=args.seed, seconds=args.seconds,
                         trace=bool(args.trace), peak=peak, t_start=T_START)
        line = result_line(cell, res, device, bool(args.trace))
    except Exception:
        traceback.print_exc()
        print("bench: the run failed; no result", file=sys.stderr)
        return 1
    notes = list(res["notes"])
    reduced = res.get("trace")
    if reduced is not None:
        notes.append(f"trace: {reduced.file_bytes} bytes, "
                     f"{sum(int(c) for _, c in reduced.ops.values())} device "
                     f"operations in the window, read in "
                     f"{reduced.reduce_s:.1f} s")
    for note in notes:
        print(f"bench: {note}", file=sys.stderr)
    for name, v, lim in res["checks"]:
        print(f"check {name}: {v} (limit {lim})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
