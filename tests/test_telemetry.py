"""Telemetry plane: metrics registry, tracing, events, export surface.

Covers the tentpole guarantees — histogram percentile math, exact census
reconciliation between the metrics plane and an injected-message count on a
live multi-host flow, Prometheus text that parses cleanly, trace contexts
surviving ArrayBatch stacking / live migration / checkpoint-restore, and a
totally ordered event bus under concurrent transactions.  Plus the
satellites: ``inject_many(stacked=True)``, the migration EWMA/histogram
reset regression, and a loose in-process overhead guard (no benchmark
cell measures telemetry's cost yet).
"""
import json
import threading
import time

import numpy as np
import pytest

from testkit import wait_until
from repro import ClusterSpec
from repro.api import Flow
from repro.core import (ArrayBatch, Coordinator, FloeGraph, FnPellet,
                        Message)
from repro.telemetry import (LATENCY_BUCKETS, EventBus, MetricsRegistry,
                             Telemetry, Tracer, TRACE_KEY, make_context,
                             parse_prometheus, render_prometheus, trace_of)


def chain_flow(n=3, fn=None, sequential=True):
    flow = Flow("chain")
    stages = []
    for i in range(n):
        f = fn or (lambda x: x)
        stages.append(flow.pellet(f"p{i}", (lambda f=f: FnPellet(
            f, sequential=sequential))))
        if i:
            stages[i - 1] >> stages[i]
    return flow, stages


# ---------------------------------------------------------------------------
# registry: histogram math, labels, prometheus round-trip
# ---------------------------------------------------------------------------

def test_histogram_percentiles_uniform():
    r = MetricsRegistry()
    fam = r.histogram("lat", "latency", ())
    h = fam.labels()
    # uniform samples across [0, 0.1): percentiles land in the right bucket
    for i in range(1000):
        h.observe(i / 10000.0)
    snap = h.snapshot()
    assert snap["count"] == 1000
    assert snap["sum"] == pytest.approx(sum(i / 10000.0 for i in range(1000)))
    # bucket-interpolated estimates: within one bucket width of the truth
    assert h.percentile(0.50) == pytest.approx(0.05, abs=0.026)
    assert h.percentile(0.95) == pytest.approx(0.095, abs=0.026)
    assert h.percentile(0.99) == pytest.approx(0.099, abs=0.026)


def test_histogram_weighted_observe_equals_repeated():
    r = MetricsRegistry()
    a = r.histogram("a", "h", ()).labels()
    b = r.histogram("b", "h", ()).labels()
    for v in (0.001, 0.02, 0.3):
        for _ in range(7):
            a.observe(v)
        b.observe(v, n=7)                 # one weighted call per dispatch
    sa, sb = a.snapshot(), b.snapshot()
    assert sa["count"] == sb["count"] == 21
    assert sa["buckets"] == sb["buckets"]
    assert sa["sum"] == pytest.approx(sb["sum"])
    assert a.percentile(0.5) == b.percentile(0.5)


def test_histogram_reset_and_empty_percentile():
    h = MetricsRegistry().histogram("x", "h", ()).labels()
    assert h.percentile(0.99) == 0.0      # empty: defined, not NaN
    h.observe(0.5, n=10)
    assert h.percentile(0.5) > 0.0
    h.reset()
    assert h.snapshot()["count"] == 0 and h.percentile(0.5) == 0.0


def test_histogram_windowed_percentile_since():
    """Frame differencing: the delta percentile reflects only samples
    observed after the snapshot, while the cumulative view stays
    polluted by history — the whole point of the windowed tail signal."""
    r = MetricsRegistry()
    h = r.histogram("lat", "latency", ()).labels()
    for _ in range(100):
        h.observe(0.001)                    # fast era
    base = h.window_state()
    for _ in range(100):
        h.observe(0.08)                     # slow era after the snapshot
    assert h.percentile_since(base, 0.95) == pytest.approx(0.08, abs=0.03)
    assert h.percentile(0.95) < h.percentile_since(base, 0.95)
    # empty delta is defined (0.0), a reset since the baseline is the
    # rebase sentinel (-1.0), never a bogus percentile
    assert h.percentile_since(h.window_state(), 0.95) == 0.0
    h.reset()
    assert h.percentile_since(base, 0.95) == -1.0


def test_windowed_queue_wait_unbreaches_after_burst():
    """A burst breaches the cumulative p95 forever; the windowed view
    decays once the recent tail recovers (what TailLatencySLO keys on)."""
    tele = Telemetry(tail_window_s=0.01)
    qw = tele.queue_wait.labels(stage="s")
    for _ in range(50):
        qw.observe(0.5)                     # the burst
    first = tele.windowed_queue_wait_p95("s")
    assert first > 0.1                      # startup: cumulative view
    time.sleep(0.02)
    tele.windowed_queue_wait_p95("s")       # rotate a frame past the burst
    time.sleep(0.02)
    for _ in range(200):
        qw.observe(0.001)                   # recovered tail
    w = tele.windowed_queue_wait_p95("s")
    assert w < 0.1                          # windowed signal un-breached
    assert qw.percentile(0.95) > 0.1        # cumulative never does
    assert tele.stage_percentiles("s")["queue_wait_p95_window"] == \
        pytest.approx(w, rel=0.5)


def test_windowed_queue_wait_rebases_on_histogram_reset():
    """A reset under the frames (migration/replace without reset_stage)
    must rebase, not emit the -1.0 sentinel to strategies."""
    tele = Telemetry(tail_window_s=0.01)
    qw = tele.queue_wait.labels(stage="s")
    for _ in range(10):
        qw.observe(0.2)
    tele.windowed_queue_wait_p95("s")
    qw.reset()                              # frames now ahead of the counts
    time.sleep(0.02)
    for _ in range(10):
        qw.observe(0.001)
    assert tele.windowed_queue_wait_p95("s") >= 0.0
    # reset_stage drops the frames with the counts
    tele.reset_stage("s")
    assert tele.windowed_queue_wait_p95("s") == 0.0


def test_percentile_overflow_bucket_floors_to_last_bound():
    h = MetricsRegistry().histogram("x", "h", ()).labels()
    h.observe(99.0, n=4)                  # beyond every finite bucket
    assert h.percentile(0.5) == LATENCY_BUCKETS[-1]


def test_counter_gauge_labels_and_snapshot():
    r = MetricsRegistry()
    c = r.counter("hits", "h", ("stage",))
    c.labels(stage="a").inc()
    c.labels(stage="a").inc(4)
    c.labels(stage="b").inc()
    g = r.gauge("depth", "d", ("stage",))
    g.labels(stage="a").set(17)
    snap = r.snapshot()
    by_stage = {s["labels"]["stage"]: s["value"]
                for s in snap["hits"]["samples"]}
    assert by_stage == {"a": 5, "b": 1}
    assert snap["depth"]["samples"][0]["value"] == 17


def test_prometheus_render_parse_round_trip():
    r = MetricsRegistry()
    r.counter("floe_rows_total", "Rows.", ("stage",)).labels(
        stage='we"ird\\x').inc(3)
    r.gauge("floe_depth", "Depth.", ()).labels().set(2.5)
    h = r.histogram("floe_lat_seconds", "Latency.", ("stage",)).labels(
        stage="a")
    h.observe(0.003, n=5)
    h.observe(2.0)
    text = render_prometheus(r)
    assert "# HELP floe_rows_total Rows." in text
    assert "# TYPE floe_lat_seconds histogram" in text
    series = parse_prometheus(text)
    assert series["floe_rows_total"][0] == ({"stage": 'we"ird\\x'}, 3.0)
    assert series["floe_depth"][0][1] == 2.5
    count = dict((tuple(sorted(l.items())), v)
                 for l, v in series["floe_lat_seconds_count"])
    assert count[(("stage", "a"),)] == 6.0
    # cumulative buckets: the +Inf bucket equals the count
    inf = [v for l, v in series["floe_lat_seconds_bucket"]
           if l.get("le") == "+Inf"]
    assert inf == [6.0]


def test_collector_failures_are_contained():
    r = MetricsRegistry()
    r.counter("ok_total", "ok", ()).labels().inc()
    r.register_collector(lambda: (_ for _ in ()).throw(RuntimeError("boom")))
    text = render_prometheus(r)          # a broken collector never breaks
    assert "ok_total 1" in text          # the scrape


# ---------------------------------------------------------------------------
# event bus
# ---------------------------------------------------------------------------

def test_event_bus_total_order_under_concurrency():
    bus = EventBus()
    n_threads, per = 8, 200

    def worker(i):
        for j in range(per):
            bus.emit("tick", thread=i, j=j)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    recs = bus.records()
    seqs = [r["seq"] for r in recs]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    assert bus.last_seq == n_threads * per
    # per-thread FIFO survives the interleave
    for i in range(n_threads):
        js = [r["j"] for r in recs if r["thread"] == i]
        assert js == list(range(per))


def test_event_bus_subscribe_filter_jsonl():
    bus = EventBus()
    seen = []
    unsub = bus.subscribe(seen.append)
    bus.emit("a", x=1)
    bus.emit("b", x=2)
    unsub()
    bus.emit("a", x=3)
    assert [r["kind"] for r in seen] == ["a", "b"]
    assert [r["x"] for r in bus.records("a")] == [1, 3]
    assert [r for r in bus.records(since_seq=2)][0]["x"] == 3
    for line in bus.to_jsonl().splitlines():
        rec = json.loads(line)            # every line is valid JSON
        assert {"seq", "ts", "kind"} <= set(rec)


# ---------------------------------------------------------------------------
# live engine: census reconciliation, stats surface, events
# ---------------------------------------------------------------------------

def test_metrics_census_reconciles_on_multihost_flow():
    """Acceptance criterion: on a live multi-host flow, per-stage service
    and queue-wait histogram counts equal the injected-message census
    exactly — no samples lost, none double-counted through batching."""
    n = 500
    flow, (p0, p1, p2) = chain_flow(3)
    with flow.session(cluster=ClusterSpec(hosts=2, cores_per_host=8)) as s:
        s.inject_many(p0, list(range(n)))
        assert len(s.results()) == n and not s.errors
        tele = s.telemetry
        assert tele.injected.labels().value == n
        for stage in ("p0", "p1", "p2"):
            svc = tele.service_time.labels(stage=stage).snapshot()
            qw = tele.queue_wait.labels(stage=stage).snapshot()
            assert svc["count"] == n, (stage, svc["count"])
            assert qw["count"] == n, (stage, qw["count"])
        # the scrape agrees and parses cleanly
        series = parse_prometheus(s.prometheus())
        counts = {l["stage"]: v
                  for l, v in series["floe_stage_service_seconds_count"]}
        assert counts == {"p0": float(n), "p1": float(n), "p2": float(n)}
        processed = {l["stage"]: v
                     for l, v in series["floe_stage_processed_total"]}
        assert processed == counts
        hosts = {l["host"] for l, v in series["floe_host_cores_total"]}
        assert hosts == {"h0", "h1"}


def test_stats_surface_has_percentiles_and_legacy_keys():
    flow, (p0, p1) = chain_flow(2)
    with flow.session() as s:
        s.inject_many(p0, list(range(50)))
        s.results()
        st = s.describe()["stages"]["p0"]
        for k in ("queue", "arrived", "processed", "emitted", "avg_latency",
                  "cores", "batch_max", "host", "version",
                  "service_p50", "service_p95", "service_p99",
                  "queue_wait_p95"):
            assert k in st, k
        assert st["arrived"] == 50
        assert st["service_p95"] >= st["service_p50"] > 0.0
        # session.metrics() mirrors the same counts
        m = s.metrics()
        svc = [x for x in m["floe_stage_service_seconds"]["samples"]
               if x["labels"]["stage"] == "p0"]
        assert svc[0]["hist"]["count"] == 50


def test_telemetry_disabled_keeps_legacy_stats_shape():
    flow, (p0, p1) = chain_flow(2)
    with flow.session(telemetry=False) as s:
        s.inject_many(p0, list(range(20)))
        s.results()
        st = s.describe()["stages"]["p0"]
        assert st["arrived"] == 20
        assert "service_p95" not in st    # percentiles need the plane on
        assert s.telemetry.enabled is False
        assert parse_prometheus(s.prometheus()) == {}


def test_error_counter_and_event():
    flow = Flow("err")
    bad = flow.pellet("bad", lambda: FnPellet(
        lambda x: 1 / 0 if x == 3 else x, sequential=True))
    with flow.session() as s:
        s.inject_many(bad, list(range(6)))
        s.results()
        assert wait_until(
            lambda: s.telemetry.errors.labels(stage="bad").value == 1)
        evs = s.events("error")
        assert len(evs) == 1 and evs[0]["flake"] == "bad"
        assert "ZeroDivisionError" in evs[0]["error"]


def test_recomposition_and_elasticity_events_on_bus():
    flow, (p0, p1) = chain_flow(2)
    with flow.session() as s:
        s.inject_many(p0, list(range(10)))
        s.results()
        with s.recompose() as tx:
            tx.scale("p1", cores=3)
        evs = s.events("transaction")
        assert len(evs) == 1 and evs[0]["scaled"] == {"p1": 3}
        # seq ordering spans kinds: the bus is one totally ordered stream
        all_seqs = [r["seq"] for r in s.events()]
        assert all_seqs == sorted(all_seqs)


def test_cluster_ledger_mirrors_onto_bus():
    flow, (p0, p1) = chain_flow(2)
    with flow.session(cluster=ClusterSpec(hosts=2, cores_per_host=8)) as s:
        s.inject_many(p0, list(range(10)))
        s.results()
        src = s.cluster.host_of("p1").name
        s.migrate(p1, "h1" if src == "h0" else "h0")
        migs = s.events("migration")
        assert len(migs) == 1 and migs[0]["flake"] == "p1"
        assert {migs[0]["src"], migs[0]["dst"]} == {"h0", "h1"}
        assert any(e["cluster_event"] == "migrate"
                   for e in s.events("cluster"))


# ---------------------------------------------------------------------------
# migration resets stale latency state (satellite bugfix regression)
# ---------------------------------------------------------------------------

def test_migration_resets_ewma_and_histograms():
    """Regression: migrated flakes kept the old host's EWMA avg_latency and
    histogram samples, poisoning the adaptation controller's view (and the
    cold-start batch guard) on the new core budget."""
    flow, (p0, p1) = chain_flow(2, fn=lambda x: (time.sleep(0.001), x)[1])
    with flow.session(cluster=ClusterSpec(hosts=2, cores_per_host=8)) as s:
        s.inject_many(p0, list(range(50)))
        s.results()
        flake = s.coordinator.flakes["p1"]
        assert flake.stats.avg_latency > 0.0
        assert s.telemetry.service_time.labels(
            stage="p1").snapshot()["count"] == 50
        src = s.cluster.host_of("p1").name
        s.migrate(p1, "h1" if src == "h0" else "h0")
        flake = s.coordinator.flakes["p1"]
        assert flake.stats.avg_latency == 0.0           # EWMA reset
        assert s.telemetry.service_time.labels(
            stage="p1").snapshot()["count"] == 0        # histogram reset
        # counters survive: the census is cumulative across the move
        assert flake.stats.processed == 50
        s.inject_many(p0, list(range(10)))
        s.results()
        assert flake.stats.avg_latency > 0.0            # re-learns fresh


# ---------------------------------------------------------------------------
# tracing: ArrayBatch stacking, migration, checkpoint/restore
# ---------------------------------------------------------------------------

def test_traces_span_every_hop():
    flow, (p0, p1, p2) = chain_flow(3)
    with flow.session(trace_sample=1.0) as s:
        s.inject_many(p0, list(range(20)))
        s.results()
        tids = s.trace()
        assert len(tids) == 20
        for tid in tids:
            spans = s.trace(tid)
            assert [sp["stage"] for sp in spans] == ["p0", "p1", "p2"]
            assert all(sp["t_end"] >= sp["t_start"] for sp in spans)
            # hops are causally ordered
            assert all(a["t_start"] <= b["t_end"]
                       for a, b in zip(spans, spans[1:]))
            assert all(sp["queue_wait"] >= 0.0 for sp in spans)
    # behind a slow stage rows queue, and its spans report the wait the
    # dispatch observed (the same value the queue-wait histogram takes)
    slow = Flow("slow")
    a = slow.pellet("a", lambda: FnPellet(lambda x: x, sequential=True))
    b = slow.pellet("b", lambda: FnPellet(
        lambda x: (time.sleep(0.003), x)[1], sequential=True)).batch(1)
    a >> b
    with slow.session(trace_sample=1.0) as s:
        s.inject_many("a", list(range(20)))
        s.results()
        waits = [sp["queue_wait"] for tid in s.trace()
                 for sp in s.trace(tid) if sp["stage"] == "b"]
        hist = s.telemetry.queue_wait.labels(stage="b").snapshot()
    assert len(waits) == 20 and hist["count"] == 20
    # the last row waited behind the 19 before it
    assert max(waits) >= 0.003 * 10
    assert sum(waits) == pytest.approx(hist["sum"], rel=1e-6, abs=1e-6)


def test_trace_sampling_fraction():
    flow, (p0,) = chain_flow(1)
    with flow.session(trace_sample=0.25) as s:
        s.inject_many(p0, list(range(400)))
        s.results()
        assert 40 <= len(s.trace()) <= 180   # ~100 expected, seeded RNG


def test_traces_survive_arraybatch_stacking_and_slicing():
    """Trace contexts ride the carrier's sidecar: stacked at the source,
    sliced on hash-split, restored on unstack — every hop still spans."""
    n = 64
    g = FloeGraph("tr")
    g.add("a", lambda: FnPellet(lambda X: np.asarray(X) + 1.0,
                                vectorized=True, sequential=True),
          batch_max=32, batch_array=True)
    g.add("b", lambda: FnPellet(lambda X: np.asarray(X) * 2.0,
                                vectorized=True, sequential=True),
          batch_max=32, batch_array=True)
    g.connect("a", "b")
    coord = Coordinator(g, trace_sample=1.0).start()
    try:
        coord.flakes["a"].pause()
        coord.inject_many("a", [float(i) for i in range(n)], stacked=True)
        coord.flakes["a"].resume()
        assert coord.run_until_quiescent(timeout=60)
        out = sorted(float(m.payload) for m in coord.drain_outputs()
                     if m.is_data())
        assert out == [(i + 1.0) * 2.0 for i in range(n)]
        tracer = coord.telemetry.tracer
        tids = tracer.trace_ids()
        assert len(tids) == n
        rows = {"a": 0, "b": 0}
        for tid in tids:
            spans = tracer.spans(tid)
            assert [sp["stage"] for sp in spans] == ["a", "b"]
            for sp in spans:
                rows[sp["stage"]] += sp["rows"]
        assert rows == {"a": n, "b": n}  # row-weighted spans: exact census
        # the carriers really were shared: far fewer spans' dispatches
        # than messages is already asserted by the array-path suite; here
        # we check the sidecar survived a real stack/unstack cycle
        assert coord.telemetry.stacked_injections.labels().value == 1
    finally:
        coord.stop()


def test_traces_survive_migration_across_hosts():
    flow, (p0, p1, p2) = chain_flow(3)
    with flow.session(cluster=ClusterSpec(hosts=2, cores_per_host=8),
                      trace_sample=1.0) as s:
        s.coordinator.flakes["p1"].pause()
        s.inject_many(p0, list(range(30)))
        assert wait_until(
            lambda: s.coordinator.flakes["p1"].queue_length() == 30)
        dst = "h1" if s.cluster.host_of("p1").name == "h0" else "h0"
        s.migrate(p1, dst)                # traced backlog moves with it
        s.coordinator.flakes["p1"].resume()
        assert len(s.results()) == 30
        tids = s.trace()
        assert len(tids) == 30
        for tid in tids:
            spans = s.trace(tid)
            assert [sp["stage"] for sp in spans] == ["p0", "p1", "p2"]
            p1_span = spans[1]
            assert p1_span["host"] == dst   # span names the post-move host


def test_traces_survive_checkpoint_restore(tmp_path):
    path = str(tmp_path / "floe.ckpt")
    flow, (p0, p1) = chain_flow(2)
    with flow.session(trace_sample=1.0) as s:
        s.coordinator.flakes["p1"].pause()
        s.inject_many(p0, list(range(12)))
        assert wait_until(
            lambda: s.coordinator.flakes["p1"].queue_length() == 12)
        parked = [trace_of(m.meta) for m in
                  s.coordinator.flakes["p1"].inputs["in"]._q]
        old_ids = {c["id"] for c in parked if c}
        assert len(old_ids) == 12
        s.checkpoint(path)
    flow2, _ = chain_flow(2)
    with flow2.session(trace_sample=1.0).open() as s2:
        from repro.checkpoint import restore_floe_graph
        restore_floe_graph(s2.coordinator, path)
        assert len(s2.results()) == 12
        # the restored flow finishes the ORIGINAL traces: p1 spans carry
        # the checkpointed ids, not freshly minted ones
        recorded = set(s2.trace())
        assert old_ids <= recorded
        for tid in old_ids:
            assert [sp["stage"] for sp in s2.trace(tid)] == ["p1"]


def test_trace_context_helpers():
    assert trace_of(None) is None and trace_of({}) is None
    ctx = make_context()
    assert trace_of({TRACE_KEY: ctx}) is ctx
    t = Tracer(sample=0.0)
    assert not t.active and t.maybe_trace() is None
    t = Tracer(sample=1.0, max_traces=4)
    for _ in range(8):
        ctx = t.maybe_trace()
        t.record_span(ctx, stage="s", t_start=0.0, t_end=1.0)
    assert len(t.trace_ids()) == 4        # LRU-bounded


# ---------------------------------------------------------------------------
# stacked injection (satellite)
# ---------------------------------------------------------------------------

def test_inject_many_stacked_builds_one_carrier():
    got = []
    g = FloeGraph("stk")
    g.add("v", lambda: FnPellet(
        lambda X: (got.append(np.asarray(X).shape), np.asarray(X))[1],
        vectorized=True, sequential=True),
        batch_max=128, batch_array=True)
    coord = Coordinator(g).start()
    try:
        coord.flakes["v"].pause()
        coord.inject_many("v", [float(i) for i in range(64)], stacked=True)
        assert coord.flakes["v"].queue_length() == 64   # rows accounted
        # ONE entry in the channel: the carrier was built at the source
        assert len(coord.flakes["v"].inputs["in"]._q) == 1
        coord.flakes["v"].resume()
        assert coord.run_until_quiescent(timeout=60)
        out = [m for m in coord.drain_outputs() if m.is_data()]
        assert len(out) == 64
        assert got == [(64,)]             # one vectorized call, all rows
        assert coord.telemetry.stacked_injections.labels().value == 1
        assert coord.telemetry.injected.labels().value == 64
    finally:
        coord.stop()


def test_inject_many_stacked_ragged_falls_back():
    flow, (p0,) = chain_flow(1)
    with flow.session() as s:
        payloads = [np.zeros((2,)), np.zeros((3,)), "x"]   # unstackable
        s.inject_many(p0, payloads, stacked=True)
        assert len(s.results()) == 3
        assert s.telemetry.stacked_injections.labels().value == 0
        assert s.telemetry.injected.labels().value == 3


def test_inject_many_stacked_respects_keys():
    g = FloeGraph("stkk")
    g.add("v", lambda: FnPellet(lambda X: np.asarray(X), vectorized=True,
                                sequential=True),
          batch_max=128, batch_array=True)
    coord = Coordinator(g).start()
    try:
        coord.inject_many("v", [float(i) for i in range(8)],
                          keys=[i % 2 for i in range(8)], stacked=True)
        assert coord.run_until_quiescent(timeout=60)
        out = [m for m in coord.drain_outputs() if m.is_data()]
        assert sorted(m.key for m in out) == [0] * 4 + [1] * 4
    finally:
        coord.stop()


# ---------------------------------------------------------------------------
# array-path + backpressure observability
# ---------------------------------------------------------------------------

def test_array_hit_and_degrade_counters():
    n = 96
    g = FloeGraph("deg")
    g.add("v", lambda: FnPellet(lambda X: np.asarray(X) + 1.0,
                                vectorized=True, sequential=True),
          batch_max=32, batch_array=True)
    g.add("scalar", lambda: FnPellet(lambda x: x, sequential=True))
    g.connect("v", "scalar")
    coord = Coordinator(g).start()
    try:
        coord.flakes["v"].pause()
        coord.inject_many("v", [float(i) for i in range(n)], stacked=True)
        coord.flakes["v"].resume()
        assert coord.run_until_quiescent(timeout=60)
        assert len([m for m in coord.drain_outputs() if m.is_data()]) == n
        tele = coord.telemetry
        assert tele.array_hits.labels(stage="v").value == n
        # scalar consumer forced carrier unstack: degradations recorded
        assert tele.degradations.labels(stage="scalar").value >= 1
    finally:
        coord.stop()


def test_backpressure_stall_counter():
    g = FloeGraph("bp")
    g.add("slow", lambda: FnPellet(
        lambda x: (time.sleep(0.01), x)[1], sequential=True))
    coord = Coordinator(g, channel_capacity=4).start()
    try:
        for i in range(40):
            coord.inject("slow", i)
        assert coord.run_until_quiescent(timeout=60)
        assert coord.telemetry.stalls.labels(
            stage="slow").value > 0
    finally:
        coord.stop()


# ---------------------------------------------------------------------------
# overhead guard (loose in-process check; strict 5% lives in bench_engine)
# ---------------------------------------------------------------------------

def test_telemetry_overhead_is_bounded():
    def run(telemetry):
        flow, stages = chain_flow(4)
        with flow.session(telemetry=telemetry) as s:
            t0 = time.perf_counter()
            s.inject_many(stages[0], list(range(2000)))
            assert len(s.results()) == 2000
            return time.perf_counter() - t0

    run(True), run(False)                 # warm both paths
    on = min(run(True) for _ in range(3))
    off = min(run(False) for _ in range(3))
    # generous in-process bound to stay CI-stable; no benchmark cell
    # measures telemetry's cost yet
    assert on < off * 1.5 + 0.05, (on, off)
