"""The device's idle time under the program's ``floe.*`` spans, on
synthetic traces and on a trace the profiler recorded on the CPU."""
import contextlib
import json
import random
import time

import pytest

from yard import common, spans, trace
from yard.common import Window

from conftest import PEAK, ROOT

DEV = "/device:TPU:0"


def _sweep_by_hand(device_events, group, window):
    """Idle time under a union of spans, nanosecond by nanosecond."""
    lo, hi = window
    busy, covered = set(), set()
    for _, s, d in device_events:
        busy.update(range(max(int(s), lo), min(int(s + d), hi)))
    for _, s, d, _ in group:
        covered.update(range(max(int(s), lo), min(int(s + d), hi)))
    return sum(1 for t in range(lo, hi) if t not in busy and t in covered)


def test_nested_spans_each_count_their_idle():
    # device busy [0, 10) and [30, 40); idle [10, 30) and [40, 50)
    dev = {DEV: [("op", 0, 10), ("op", 30, 10)]}
    sp = [("floe.dispatch", 5, 40, "decode"),          # [5, 45)
          ("floe.decode.sync", 12, 8, "decode"),       # [12, 20) inside
          ("floe.decode.bookkeep", 20, 4, "decode")]   # [20, 24) inside
    got = spans.idle_by_span(dev, sp, (0, 50))
    by = {(n, st): s for n, st, s in got["spans"]}
    assert by[("floe.dispatch", "decode")] == pytest.approx(25e-9)
    assert by[("floe.decode.sync", "decode")] == pytest.approx(8e-9)
    assert by[("floe.decode.bookkeep", "decode")] == pytest.approx(4e-9)
    assert got["any"] == pytest.approx(25e-9)
    assert got["none"] == pytest.approx(5e-9)          # [45, 50)
    assert got["idle"] == pytest.approx(30e-9)
    assert [n for n, _, _ in got["spans"]][0] == "floe.dispatch"


def test_overlap_across_threads_counts_once_per_name():
    # two threads' dispatches overlap on [20, 30): the union counts it once
    dev = {DEV: [("op", 0, 10)]}
    sp = [("floe.dispatch", 10, 20, "prefill"),        # [10, 30)
          ("floe.dispatch", 20, 20, "prefill"),        # [20, 40)
          ("floe.route", 35, 10, "decode")]            # [35, 45)
    got = spans.idle_by_span(dev, sp, (0, 50))
    by = {(n, st): s for n, st, s in got["spans"]}
    assert by[("floe.dispatch", "prefill")] == pytest.approx(30e-9)
    assert by[("floe.route", "decode")] == pytest.approx(10e-9)
    # 'any' is the union of every span: [10, 45)
    assert got["any"] == pytest.approx(35e-9)
    assert got["none"] == pytest.approx(5e-9)


def test_any_and_none_sum_to_the_reduced_idle():
    rng = random.Random(7)
    events, t = [], 0
    for _ in range(400):
        t += rng.randint(0, 30)
        d = rng.randint(1, 40)
        events.append(("op", t, d))
        t += rng.randint(-20, d)           # operations may overlap
    hi = t + 50
    sp = []
    for _ in range(120):
        s = rng.randint(-10, hi)
        sp.append((rng.choice(["floe.dispatch", "floe.route",
                               "floe.decode.sync", "floe.inject"]),
                   s, rng.randint(0, 60), rng.choice(["decode", "sched"])))
    window = (5, hi)
    red = trace.reduce({DEV: events}, [], window)
    got = spans.idle_by_span({DEV: events}, sp, window)
    idle_s = red.window_s - red.busy_s
    assert got["idle"] == pytest.approx(idle_s, rel=1e-9)
    assert got["any"] + got["none"] == pytest.approx(idle_s, rel=1e-9)
    assert got["any"] * 1e9 == pytest.approx(
        _sweep_by_hand(events, sp, window))
    for name, stage, secs in got["spans"]:
        group = [x for x in sp if x[0] == name and x[3] == stage]
        assert secs * 1e9 == pytest.approx(
            _sweep_by_hand(events, group, window))
    host = [x for x in sp if spans.is_decode_host(x)]
    assert spans.decode_host_idle_s({DEV: events}, sp, window) * 1e9 == \
        pytest.approx(_sweep_by_hand(events, host, window))


def test_decode_host_union_takes_only_the_decode_loop():
    dev = {DEV: [("op", 0, 10), ("op", 20, 10)]}       # idle [10,20) [30,40)
    sp = [("floe.dispatch", 8, 6, "decode"),           # [8, 14)
          ("floe.decode.bookkeep", 12, 6, "decode"),   # [12, 18)
          ("floe.route", 31, 2, "decode"),             # [31, 33)
          ("floe.dispatch", 34, 6, "prefill"),         # not decode
          ("floe.inject", 10, 30, "decode"),           # not the loop's
          ("floe.prefill.sync", 10, 30, "decode")]     # not the loop's
    got = spans.decode_host_idle_s(dev, sp, (0, 40))
    assert got == pytest.approx(10e-9)                 # [10,18) + [31,33)


def test_idle_is_averaged_over_devices():
    dev = {"/device:TPU:0": [("op", 0, 10)], "/device:TPU:1": []}
    got = spans.idle_by_span(dev, [("floe.route", 0, 10, "decode")],
                             (0, 10))
    assert got["idle"] == pytest.approx(5e-9)
    assert got["any"] == pytest.approx(5e-9)


def test_no_spans_leaves_all_idle_to_none():
    dev = {DEV: [("op", 2, 3)]}
    got = spans.idle_by_span(dev, [], (0, 10))
    assert got["spans"] == [] and got["any"] == 0.0
    assert got["none"] == pytest.approx(7e-9)
    with pytest.raises(ValueError):
        spans.idle_by_span(dev, [], (5, 5))


def test_a_million_gaps_reduce_quickly():
    # 10^6 operations of 3 ns, one every 5 ns: 10^6 gaps; 50,000 spans
    n = 1_000_000
    events = [("op", 5 * i, 3) for i in range(n)]
    sp = [(("floe.dispatch", "floe.decode.sync")[i % 2], 100 * i, 60,
           "decode") for i in range(50_000)]
    window = (0, 5 * n)
    t0 = time.perf_counter()
    got = spans.idle_by_span({DEV: events}, sp, window)
    host = spans.decode_host_idle_s({DEV: events}, sp, window)
    took = time.perf_counter() - t0
    assert took < 20.0, f"{took:.1f} s"
    assert got["idle"] == pytest.approx(2 * n * 1e-9)
    # each span of 60 ns covers 12 gaps of 2 ns
    assert got["any"] == pytest.approx(50_000 * 24e-9)
    assert host == pytest.approx(got["any"])


def _recorded(tmp_path, with_program_spans: bool):
    import jax
    import jax.numpy as jnp
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("bench.inject"):
            if with_program_spans:
                with jax.profiler.TraceAnnotation("floe.inject",
                                                  stage="sched", rows=2):
                    x = jnp.ones((32, 32))
            else:
                x = jnp.ones((32, 32))
        with (jax.profiler.TraceAnnotation("floe.decode.sync",
                                           stage="decode")
              if with_program_spans else contextlib.nullcontext()):
            (x @ x).block_until_ready()
    jax.profiler.stop_trace()
    return trace.find_xplane(str(tmp_path))


def test_program_spans_are_read_apart_from_the_benchmarks(tmp_path):
    path = _recorded(tmp_path / "with", True)
    plain = _recorded(tmp_path / "without", False)
    _, bench_spans = trace.load(path)
    _, bench_plain = trace.load(plain)
    assert sorted(n for n, _, _ in bench_spans) == \
        sorted(n for n, _, _ in bench_plain) == ["bench.inject",
                                                 "bench.window"]
    program = spans.load(path)
    assert sorted((n, st) for n, _, _, st in program) == [
        ("floe.decode.sync", "decode"), ("floe.inject", "sched")]
    assert spans.load(plain) == []


def _synthetic_device():
    ns = 1e9
    return {DEV: [
        ("jit_decode_step/decode_attention f32[128,1,128]", 1.0 * ns,
         0.2 * ns),
        ("jit_prefill/flash_attention f32[64,256,128]", 1.5 * ns, 0.1 * ns),
        ("jit_decode_step/fusion f32[8,2048]", 2.0 * ns, 0.5 * ns)]}


def test_existing_readers_read_the_same_with_program_spans():
    """The accepted readers and ``idle_gaps`` see the same trace whether or
    not the program's spans are in it: ``trace.load`` keeps only the
    benchmark's spans, and the new reduction changes nothing it reads."""
    ns = 1e9
    dev = _synthetic_device()
    bench_spans = [("bench.window", 1.0 * ns, 2.0 * ns),
                   ("bench.inject", 1.2 * ns, 0.2 * ns)]
    program = [("floe.dispatch", 1.0 * ns, 1.0 * ns, "decode"),
               ("floe.decode.sync", 1.2 * ns, 0.25 * ns, "decode"),
               ("floe.inject", 1.6 * ns, 0.3 * ns, "sched")]
    window = trace.window_of(bench_spans)
    bm = common.load_json(ROOT / "BENCHMARK.json")
    accepted = [m["name"] for m in bm["per_layer"]]
    work = {"decode_attention": {"flops": 1e9, "bytes": 1e9},
            "flash_attention": {"flops": 1e9, "bytes": 1e8},
            "decode_flops": 1e12, "prefill_flops": 1e11}

    def readings(red):
        w = Window(seconds=2.0, peak=dict(PEAK), work=work,
                   stages={"decode": {"service_sum": 1.5}},
                   counters={"decode_steps": 60}, trace=red)
        return ({m: common.read_metric(m, w, ROOT) for m in accepted},
                json.dumps([[n, s] for n, s in red.gaps]))

    before = readings(trace.reduce(dev, bench_spans, window))
    spans.idle_by_span(dev, program, window)
    spans.decode_host_idle_s(dev, program, window)
    after = readings(trace.reduce(dev, bench_spans, window))
    assert before == after
    assert all(v is not None for v in before[0].values())
