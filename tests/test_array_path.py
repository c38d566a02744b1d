"""Array-payload fast path: ArrayBatch carriers end-to-end.

Covers the tentpole guarantees: a drained batch of stackable payloads
travels between vectorized stages as ONE stacked array (no per-message
unstack), while every engine invariant holds — zero-loss/zero-dup census,
landmark boundaries, per-key FIFO under hash splits, BatchItemError
row-wise degradation, ragged-payload fallback, row-accurate credits/stats,
checkpoint capture, and live migration of in-flight carriers.  Plus the
``Channel.put_many`` shared-deadline regression (satellite bugfix).
"""
import threading
import time

import numpy as np
import pytest

from testkit import wait_until
from repro.api import Flow
from repro.core import (ArrayBatch, Coordinator, FloeGraph, FnPellet,
                        Message, PushPellet, WindowPellet, stable_hash)
from repro.core.engine import Channel


def _vec(X):
    return np.asarray(X) * 2.0


# -- Channel.put_many shared deadline (satellite bugfix) -----------------------

def test_put_many_timeout_is_one_shared_deadline():
    """A multi-chunk admit against a slow consumer must fail within ONE
    timeout wall-clock, not N x timeout (the old per-chunk allowance let a
    trickle-draining consumer stretch a 0.3s timeout to seconds)."""
    ch = Channel(capacity=1)

    def slow_consumer():
        while not stop.is_set():
            ch.pop_up_to(1)
            time.sleep(0.05)

    stop = threading.Event()
    t = threading.Thread(target=slow_consumer, daemon=True)
    t.start()
    try:
        t0 = time.time()
        with pytest.raises(TimeoutError) as exc:
            ch.put_many([Message(payload=i) for i in range(100)],
                        timeout=0.3)
        elapsed = time.time() - t0
        # the consumer keeps freeing one slot per 50ms, so the old code
        # would grind through all 100 chunks (~5s) without ever raising
        assert elapsed < 2.0, f"deadline not shared: {elapsed:.2f}s"
        assert 0 < exc.value.appended < 100   # rollback contract intact
    finally:
        stop.set()
        t.join(timeout=5)


def test_put_many_counts_carrier_rows_against_capacity():
    ch = Channel(capacity=10)
    ab = ArrayBatch(np.zeros((8, 4), np.float32))
    ch.put(Message(payload=ab))
    assert len(ch) == 8                      # rows, not entries
    ch.put_many([Message(payload=i) for i in range(2)])
    with pytest.raises(TimeoutError):        # 8 + 2 rows = full
        ch.put(Message(payload="x"), timeout=0.05)
    got = ch.pop_up_to(1)
    assert isinstance(got[0].payload, ArrayBatch)
    assert len(ch) == 2


# -- census + amortization -----------------------------------------------------

def test_array_chain_one_call_per_hop_census_exact():
    calls = {"a": [], "b": []}

    def stage(tag):
        def fn(X):
            calls[tag].append(np.asarray(X).shape)
            return np.asarray(X) + 1.0
        return fn

    n = 300
    g = FloeGraph("chain")
    g.add("a", lambda: FnPellet(stage("a"), vectorized=True,
                                sequential=True),
          batch_max=64, batch_array=True)
    g.add("b", lambda: FnPellet(stage("b"), vectorized=True,
                                sequential=True),
          batch_max=64, batch_array=True)
    g.connect("a", "b")
    coord = Coordinator(g).start()
    try:
        coord.flakes["a"].pause()
        coord.inject_many("a", [float(i) for i in range(n)])
        coord.flakes["a"].resume()
        assert coord.run_until_quiescent(timeout=60)
        out = sorted(float(m.payload) for m in coord.drain_outputs()
                     if m.is_data())
        assert out == [i + 2.0 for i in range(n)]        # 0 lost / 0 dup
        # stage b consumed stacked arrays directly: one call per carrier,
        # far fewer calls than messages, never a length-1 unstack storm
        assert len(calls["b"]) < n / 4
        assert all(len(s) == 1 and s[0] > 1 for s in calls["b"])
        for name in ("a", "b"):
            st = coord.flakes[name].stats
            assert st.arrived == st.processed == n       # rows, exact
            assert st.emitted == n
        assert not coord.errors, coord.errors[:3]
    finally:
        coord.stop()


def test_array_batches_never_span_a_landmark():
    n = 120
    g = FloeGraph("lm")
    g.add("p", lambda: FnPellet(_vec, vectorized=True, sequential=True),
          batch_max=64, batch_array=True)
    coord = Coordinator(g).start()
    try:
        coord.flakes["p"].pause()
        for i in range(n):
            coord.inject("p", float(i))
        coord.inject_landmark("p", tag="flush")
        for i in range(n, 2 * n):
            coord.inject("p", float(i))
        coord.flakes["p"].resume()
        assert coord.run_until_quiescent(timeout=60)
        kinds = [("lm" if m.landmark else float(m.payload))
                 for m in coord.drain_outputs()]
        assert kinds == [i * 2.0 for i in range(n)] + ["lm"] + \
            [i * 2.0 for i in range(n, 2 * n)]
    finally:
        coord.stop()


# -- routing -------------------------------------------------------------------

def test_array_hash_split_is_per_key_deterministic_and_fifo():
    """Carrier rows hash-split by the key sidecar: placement must equal the
    per-message HashSplit choice, and each key's values must arrive at its
    sink in injection order (per-key FIFO through array slicing)."""
    n, n_sinks = 400, 4
    g = FloeGraph("hash")
    g.add("src", lambda: FnPellet(lambda X: np.asarray(X), vectorized=True,
                                  sequential=True),
          batch_max=64, batch_array=True)
    for i in range(n_sinks):
        g.add(f"s{i}", lambda i=i: FnPellet(lambda x, i=i: (i, float(x)),
                                            sequential=True))
        g.connect("src", f"s{i}", split="hash")
    coord = Coordinator(g).start()
    try:
        coord.flakes["src"].pause()
        coord.inject_many("src", [float(i) for i in range(n)],
                          keys=[i % 8 for i in range(n)])
        coord.flakes["src"].resume()
        assert coord.run_until_quiescent(timeout=60)
        out = [m.payload for m in coord.drain_outputs() if m.is_data()]
        assert len(out) == n
        seen_per_key = {}
        for sink_idx, value in out:
            key = int(value) % 8
            assert sink_idx == stable_hash(key) % n_sinks
            seen_per_key.setdefault(key, []).append(value)
        for key, values in seen_per_key.items():
            assert values == sorted(values), f"key {key} out of order"
        assert not coord.errors, coord.errors[:3]
    finally:
        coord.stop()


def test_array_round_robin_matches_row_count():
    n = 128
    g = FloeGraph("rr")
    g.add("src", lambda: FnPellet(lambda X: np.asarray(X), vectorized=True,
                                  sequential=True),
          batch_max=32, batch_array=True)
    for i in range(2):
        g.add(f"s{i}", lambda i=i: FnPellet(lambda x, i=i: (i, float(x)),
                                            sequential=True))
        g.connect("src", f"s{i}", split="round_robin")
    coord = Coordinator(g).start()
    try:
        coord.flakes["src"].pause()
        coord.inject_many("src", [float(i) for i in range(n)])
        coord.flakes["src"].resume()
        assert coord.run_until_quiescent(timeout=60)
        out = [m.payload for m in coord.drain_outputs() if m.is_data()]
        assert len(out) == n
        per_sink = {0: 0, 1: 0}
        for sink_idx, _ in out:
            per_sink[sink_idx] += 1
        assert per_sink[0] == per_sink[1] == n // 2   # row-level RR
    finally:
        coord.stop()


def test_custom_split_sees_unstacked_rows():
    """A custom policy without a choose_rows path must observe every row
    as an ordinary Message (exact legacy semantics, no silent misroute)."""
    from repro.core import Split
    from repro.core.patterns import SPLITS

    class EvenOnly(Split):
        def choose(self, msg, n_edges, queue_depths):
            return [0] if int(msg.payload) % 2 == 0 else []

    SPLITS["even_only2"] = EvenOnly
    try:
        g = FloeGraph("csp")
        g.add("src", lambda: FnPellet(lambda X: np.asarray(X),
                                      vectorized=True, sequential=True),
              batch_max=32, batch_array=True)
        g.add("dst", lambda: FnPellet(lambda x: float(x), sequential=True))
        g.add("dst2", lambda: FnPellet(lambda x: float(x), sequential=True))
        g.connect("src", "dst", split="even_only2")
        g.connect("src", "dst2", split="even_only2")
        coord = Coordinator(g).start()
        try:
            coord.flakes["src"].pause()
            coord.inject_many("src", [float(i) for i in range(60)])
            coord.flakes["src"].resume()
            assert coord.run_until_quiescent(timeout=60)
            out = sorted(float(m.payload) for m in coord.drain_outputs()
                         if m.is_data())
            assert out == [float(i) for i in range(60) if i % 2 == 0]
        finally:
            coord.stop()
    finally:
        SPLITS.pop("even_only2", None)


# -- degradation ---------------------------------------------------------------

def test_array_failure_degrades_rowwise_zero_loss_zero_dup():
    """A raising compute_array degrades THAT batch to per-row compute:
    only the raising row drops (recorded), everything else delivers
    exactly once — the BatchItemError census."""
    def frag(X):
        arr = np.asarray(X)
        if arr.size > 1 and np.any(arr == 13):
            raise RuntimeError("vectorized boom")
        if np.any(arr == 13):
            raise RuntimeError("boom")
        return arr * 10.0

    n = 60
    g = FloeGraph("frag")
    g.add("p", lambda: FnPellet(frag, vectorized=True, sequential=True),
          batch_max=64, batch_array=True)
    coord = Coordinator(g).start()
    try:
        coord.flakes["p"].pause()
        coord.inject_many("p", [float(i) for i in range(n)])
        coord.flakes["p"].resume()
        assert coord.run_until_quiescent(timeout=60)
        out = sorted(float(m.payload) for m in coord.drain_outputs()
                     if m.is_data())
        assert out == [i * 10.0 for i in range(n) if i != 13]
        assert any(isinstance(e, RuntimeError) for _, e in coord.errors)
        st = coord.flakes["p"].stats
        assert st.arrived == st.processed == n   # credits exact, in rows
        assert st.emitted == n - 1
    finally:
        coord.stop()


def test_ragged_payloads_fall_back_to_rowwise_path():
    """Non-stackable payloads must silently take the row-wise batched
    path — correct results, no errors, no carriers."""
    n = 80
    calls = []

    def fn(xs):   # list contract: ragged batches arrive as lists
        calls.append(len(xs))
        return [sum(x) for x in xs]

    g = FloeGraph("rag")
    g.add("p", lambda: FnPellet(fn, vectorized=True, sequential=True),
          batch_max=32, batch_array=True)
    coord = Coordinator(g).start()
    try:
        coord.flakes["p"].pause()
        payloads = [[1] * (i % 5 + 1) for i in range(n)]   # ragged lists
        coord.inject_many("p", payloads)
        coord.flakes["p"].resume()
        assert coord.run_until_quiescent(timeout=60)
        out = sorted(int(m.payload) for m in coord.drain_outputs()
                     if m.is_data())
        assert out == sorted(i % 5 + 1 for i in range(n))
        assert not coord.errors, coord.errors[:3]
        assert sum(calls) == n      # still batched, just not columnar
    finally:
        coord.stop()


def test_carrier_unstacks_for_non_array_consumer():
    """An array stage feeding a window pellet: the carrier must degrade
    to per-row messages at the window's enqueue, keeping count-window
    semantics exact."""
    class SumWin(WindowPellet):
        window = 4

        def compute(self, payloads):
            return float(np.sum(np.asarray(payloads, dtype=np.float64)))

    n = 64
    g = FloeGraph("win")
    g.add("v", lambda: FnPellet(lambda X: np.asarray(X), vectorized=True,
                                sequential=True),
          batch_max=32, batch_array=True)
    g.add("w", SumWin)
    g.connect("v", "w")
    coord = Coordinator(g).start()
    try:
        coord.flakes["v"].pause()
        coord.inject_many("v", [float(i) for i in range(n)])
        coord.flakes["v"].resume()
        assert coord.run_until_quiescent(timeout=60)
        out = [float(m.payload) for m in coord.drain_outputs()
               if m.is_data()]
        assert len(out) == n // 4
        assert sum(out) == float(sum(range(n)))
        # windows gathered in row order: each is 4 consecutive values
        assert out[0] == 0.0 + 1 + 2 + 3
        assert not coord.errors, coord.errors[:3]
    finally:
        coord.stop()


def test_classic_list_result_ends_columnar_handoff_correctly():
    """An array=True stage whose callable returns a per-row LIST (the
    classic vectorized contract) still delivers exactly one result per
    row — the hand-off just stops being columnar at that stage."""
    n = 50
    g = FloeGraph("lst")
    g.add("p", lambda: FnPellet(lambda X: [float(x) * 3 for x in X],
                                vectorized=True, sequential=True),
          batch_max=32, batch_array=True)
    coord = Coordinator(g).start()
    try:
        coord.flakes["p"].pause()
        coord.inject_many("p", [float(i) for i in range(n)])
        coord.flakes["p"].resume()
        assert coord.run_until_quiescent(timeout=60)
        out = sorted(float(m.payload) for m in coord.drain_outputs()
                     if m.is_data())
        assert out == [i * 3.0 for i in range(n)]
        assert not coord.errors, coord.errors[:3]
    finally:
        coord.stop()


# -- Session API knob ----------------------------------------------------------

def test_flow_array_annotation_and_runtime_toggle():
    flow = Flow("knob")
    stage = flow.pellet("p", lambda: FnPellet(_vec, vectorized=True))
    stage.batch(32, array=True)
    with flow.session() as s:
        flake = s.coordinator.flakes["p"]
        assert flake.batch_array and flake.accepts_arrays
        assert s.stats()["p"]["batch_array"] is True
        s.set_batch("p", max_size=32, array=False)   # runtime opt-out
        assert not flake.batch_array
        s.set_batch("p", max_size=32, array=True)
        s.inject_many("p", [1.0, 2.0, 3.0])
        assert sorted(float(x) for x in s.results()) == [2.0, 4.0, 6.0]


# -- checkpoint / migration ----------------------------------------------------

def test_checkpoint_round_trips_parked_carrier(tmp_path):
    """A checkpoint taken with an ArrayBatch parked in a channel must
    restore and replay every row (carriers pickle via host arrays)."""
    flow = Flow("ck")
    flow.pellet("p", lambda: FnPellet(_vec, vectorized=True)) \
        .batch(64, array=True)
    path = str(tmp_path / "floe.ckpt")
    n = 40
    with flow.session() as s:
        flake = s.coordinator.flakes["p"]
        flake.pause()
        s.inject_many("p", [float(i) for i in range(n)])
        # force the backlog into carrier form: what an upstream array
        # stage would have parked here
        ch = flake.inputs["in"]
        msgs = ch.pop_up_to(None)
        ab = ArrayBatch.try_stack([m.payload for m in msgs],
                                  seqs=[m.seq for m in msgs])
        ch.put(Message(payload=ab))
        assert any(isinstance(m.payload, ArrayBatch) for m in ch._q)
        s.checkpoint(path)
    from repro.api.session import Session
    with Session.restore(path, flow) as s2:
        out = sorted(float(x) for x in s2.results())
        assert out == [i * 2.0 for i in range(n)]
        assert not s2.errors, s2.errors[:3]


def test_migration_carries_inflight_arraybatch():
    """Live flake migration with carriers parked in the channel: the
    columnar backlog moves host whole, zero loss / zero dup."""
    from repro.cluster import ClusterManager, ClusterSpec
    n = 256
    g = FloeGraph("mig")
    g.add("p0", lambda: FnPellet(lambda X: np.asarray(X), vectorized=True),
          cores=2, batch_max=64, batch_array=True)
    g.add("p1", lambda: FnPellet(_vec, vectorized=True),
          cores=2, batch_max=64, batch_array=True)
    g.connect("p0", "p1")
    cluster = ClusterManager(ClusterSpec(hosts=2, cores_per_host=8))
    coord = Coordinator(g, cluster=cluster).start()
    try:
        coord.flakes["p1"].pause()
        coord.flakes["p0"].pause()
        coord.inject_many("p0", [float(i) for i in range(n)])
        coord.flakes["p0"].resume()
        # wait until p0 pushed (stacked) batches into p1's channel
        assert wait_until(
            lambda: coord.flakes["p1"].queue_length() == n, timeout=30)
        assert any(isinstance(m.payload, ArrayBatch)
                   for m in coord.flakes["p1"].inputs["in"]._q)
        src = cluster.host_of("p1").name
        dst = "h1" if src == "h0" else "h0"
        cluster.migrate("p1", dst)
        assert cluster.host_of("p1").name == dst
        assert coord.flakes["p1"].batch_array    # knob survives the move
        assert coord.run_until_quiescent(timeout=60)
        out = [float(m.payload) for m in coord.drain_outputs()
               if m.is_data()]
        assert sorted(out) == [i * 2.0 for i in range(n)]
        assert len(out) == len(set(out)) == n    # 0 lost / 0 dup
        assert not coord.errors, coord.errors[:3]
    finally:
        coord.stop()
