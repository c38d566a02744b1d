"""The trace reduction on synthetic traces, and on a trace the profiler
recorded on the CPU."""
import pytest

from yard import trace


def test_union_merges_overlaps():
    dev = {"/device:TPU:0": [("a", 0, 2), ("b", 1, 2), ("c", 5, 1),
                             ("d", 6, 1)]}
    red = trace.reduce(dev, [], (0, 7))
    assert red.busy_s == pytest.approx(5e-9)
    assert red.gaps == [("idle", pytest.approx(2e-9))]


def test_only_the_longest_gaps_are_kept():
    # 1,000 ops with gaps of 1..999 ns between them
    events, t = [], 0
    for i in range(1000):
        events.append(("op", t, 1))
        t += 1 + i
    red = trace.reduce({"/device:TPU:0": events},
                       [("bench.monitor", t - 1000, 999)], (0, t),
                       top_gaps=3)
    assert [g for g, _ in red.gaps] == ["monitor", "idle", "idle"]
    assert [s for _, s in red.gaps] == pytest.approx([999e-9, 998e-9,
                                                      997e-9])


def test_busy_idle_and_ops_inside_the_window():
    ns = 1e9
    dev = {"/device:TPU:0": [("fusion.1", 0.5 * ns, 1.0 * ns),   # half out
                             ("fusion.1", 2.0 * ns, 0.5 * ns),
                             ("_flash_kernel", 2.25 * ns, 0.5 * ns),
                             ("late", 9.0 * ns, 1.0 * ns)]}   # out
    spans = [("bench.window", 1.0 * ns, 3.0 * ns),
             ("bench.inject", 1.5 * ns, 0.4 * ns)]
    red = trace.reduce(dev, spans, trace.window_of(spans))
    assert red.window_s == pytest.approx(3.0)
    # [1.0, 1.5] + [2.0, 2.75]
    assert red.busy_s == pytest.approx(1.25)
    assert red.idle_share == pytest.approx(1 - 1.25 / 3.0)
    assert red.ops["fusion.1"] == pytest.approx([1.0, 2])
    assert red.kernel(("flash",)) == (pytest.approx(0.5), 1)
    assert red.kernel(("nothing",)) == (0.0, 0)
    assert red.top_ops(1) == [["fusion.1", pytest.approx(1.0)]]
    # gaps: [1.5, 2.0] under the inject span, [2.75, 4.0] idle
    labels = dict((round(s, 6), name) for name, s in red.gaps)
    assert labels == {0.5: "inject", 1.25: "idle"}
    assert [round(s, 6) for _, s in red.gaps] == [1.25, 0.5]


def test_busy_is_averaged_over_devices():
    dev = {"/device:TPU:0": [("a", 0, 10)], "/device:TPU:1": []}
    red = trace.reduce(dev, [], (0, 10))
    assert red.busy_s == pytest.approx(5e-9)
    assert red.devices == 2


def test_empty_window_is_refused():
    with pytest.raises(ValueError):
        trace.reduce({}, [], (5, 5))


def test_reads_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("bench.inject"):
            x = jnp.ones((64, 64))
            (x @ x).block_until_ready()
    jax.profiler.stop_trace()
    device_events, spans = trace.load(trace.find_xplane(str(tmp_path)))
    names = {n for n, _, _ in spans}
    assert {"bench.window", "bench.inject"} <= names
    lo, hi = trace.window_of(spans)
    assert hi > lo
    # the CPU has no device plane: nothing ran "on the device"
    red = trace.reduce(device_events, spans, (lo, hi))
    assert red.busy_s == 0.0 and red.devices == 0


def test_op_labels_group_layers_and_find_kernels():
    a = trace.op_label("%decode_attention_op.29 = f32[128,1,128]{2,1,0:T(1,"
                       "128)} custom-call(s32[8]{0} %x, f32[1] %fusion.3)",
                       "jit_decode_step")
    b = trace.op_label("%decode_attention_op.30 = f32[128,1,128]{2,1,0} "
                       "custom-call(s32[8]{0} %y)", "jit_decode_step")
    assert a == b == "jit_decode_step/decode_attention_op f32[128,1,128]"
    assert trace.instruction(a) == "decode_attention_op"
    # an operation that reads a kernel's output is not the kernel
    c = trace.op_label("%bitcast.4 = f32[8,2048]{1,0} bitcast(f32[128,1,128]"
                       " %decode_attention_op.29)", "jit_decode_step")
    assert trace.instruction(c) == "bitcast"
    assert trace.op_label("%f.1 = (f32[2]{0}, f32[3]{0}) fusion(%a)") == \
        "f tuple"
    red = trace.Reduced(window_s=1.0, busy_s=0.5, devices=1, gaps=[],
                        ops={a: [0.25, 28], c: [0.1, 28]})
    assert red.kernel(("decode_attention",)) == (0.25, 28)
