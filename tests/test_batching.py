"""Adaptive micro-batched data path: semantics preserved, accounting exact.

Covers the engine's batched dispatch (`Channel.pop_up_to`/`put_many`,
the 'batch' work kind, amortized routing), the `compute_batch` pellet
contract (including `FnPellet(vectorized=True)`), the `.batch(...)`
Session API knob, and the guarantees the tentpole must not bend: per-channel
FIFO, per-key routing determinism, landmark ordering, exact FlakeStats, and
B adapting back to 1 when queues drain.
"""
import threading

import pytest

from testkit import wait_until
from repro.api import Flow
from repro.api.errors import CompositionError
from repro.core import (Coordinator, Drop, FloeGraph, FnMapper, FnPellet,
                        FnReducer, Message, PushPellet, add_mapreduce,
                        stable_hash)
from repro.core.engine import Channel
from repro.core.message import landmark


def _is_special(m):
    return not m.is_data()


# -- Channel batch primitives --------------------------------------------------

def test_pop_up_to_respects_limit_and_order():
    ch = Channel()
    for i in range(10):
        ch.put(Message(payload=i))
    got = ch.pop_up_to(4)
    assert [m.payload for m in got] == [0, 1, 2, 3]
    assert [m.payload for m in ch.pop_up_to()] == [4, 5, 6, 7, 8, 9]
    assert ch.pop_up_to() == []


def test_pop_up_to_never_spans_a_boundary():
    ch = Channel()
    ch.put(Message(payload="d1"))
    ch.put(Message(payload="d2"))
    ch.put(landmark("L"))
    ch.put(Message(payload="d3"))
    batch = ch.pop_up_to(10, stop=_is_special)
    assert [m.payload for m in batch] == ["d1", "d2"]
    # a boundary message at the head pops ALONE
    batch = ch.pop_up_to(10, stop=_is_special)
    assert len(batch) == 1 and batch[0].landmark
    assert [m.payload for m in ch.pop_up_to(10, stop=_is_special)] == ["d3"]


def test_unpop_restores_head():
    ch = Channel()
    ch.put(Message(payload=1))
    ch.put(Message(payload=2))
    m = ch.try_pop()
    ch.unpop(m)
    assert [x.payload for x in ch.pop_up_to()] == [1, 2]


def test_put_many_preserves_capacity_and_order():
    ch = Channel(capacity=5)
    ch.put_many([Message(payload=i) for i in range(5)])
    done = threading.Event()

    def overflow():
        ch.put_many([Message(payload=i) for i in range(5, 8)], timeout=10)
        done.set()

    t = threading.Thread(target=overflow, daemon=True)
    t.start()
    assert not done.wait(0.05)          # blocked: channel full (backpressure)
    assert len(ch.pop_up_to(3)) == 3    # make room
    assert done.wait(5)
    t.join()
    assert [m.payload for m in ch.pop_up_to()] == [3, 4, 5, 6, 7]


def test_put_many_timeout_reports_partial_admission():
    ch = Channel(capacity=3)
    with pytest.raises(TimeoutError) as exc:
        ch.put_many([Message(payload=i) for i in range(5)], timeout=0.05)
    assert exc.value.appended == 3   # callers roll back the remainder
    assert [m.payload for m in ch.pop_up_to()] == [0, 1, 2]


def test_put_many_notifies_consumer_per_chunk():
    ch = Channel(capacity=2)
    wakes = []
    ch._on_put = lambda: wakes.append(len(ch))
    consumed = []

    def consume():
        while len(consumed) < 6:
            got = ch.pop_up_to()
            consumed.extend(got)
            if not got:
                threading.Event().wait(0.002)

    t = threading.Thread(target=consume, daemon=True)
    t.start()
    ch.put_many([Message(payload=i) for i in range(6)], timeout=10)
    t.join(timeout=10)
    assert [m.payload for m in consumed] == list(range(6))
    assert len(wakes) >= 2   # chunked admission notified along the way


# -- FIFO + determinism under batching ----------------------------------------

def test_batched_dispatch_preserves_fifo_per_channel():
    n = 400
    g = FloeGraph("fifo")
    g.add("p", lambda: FnPellet(lambda x: x * 2, sequential=True))
    coord = Coordinator(g).start()
    try:
        coord.flakes["p"].pause()
        for i in range(n):
            coord.inject("p", i)
        coord.flakes["p"].resume()
        assert coord.run_until_quiescent(timeout=60)
        out = [m.payload for m in coord.drain_outputs() if m.is_data()]
        assert out == [i * 2 for i in range(n)]   # exact order, no loss
        assert coord.flakes["p"].stats.max_batch > 1   # really batched
    finally:
        coord.stop()


def test_batched_hash_routing_is_per_key_deterministic():
    n, n_sinks = 300, 4
    g = FloeGraph("hash")
    g.add("m", lambda: FnMapper(lambda x: [(x % 8, x)]))
    for i in range(n_sinks):
        g.add(f"s{i}", lambda i=i: FnPellet(lambda x, i=i: (i, x),
                                            sequential=True))
        g.connect("m", f"s{i}", split="hash")
    coord = Coordinator(g).start()
    try:
        coord.flakes["m"].pause()
        for i in range(n):
            coord.inject("m", i)
        coord.flakes["m"].resume()
        assert coord.run_until_quiescent(timeout=60)
        out = [m.payload for m in coord.drain_outputs() if m.is_data()]
        assert len(out) == n
        for sink_idx, value in out:
            # batched split evaluation must place each key exactly where
            # the per-message HashSplit would
            assert sink_idx == stable_hash(value % 8) % n_sinks
    finally:
        coord.stop()


def test_landmark_never_overtakes_data_across_batches():
    n = 250
    g = FloeGraph("lm")
    g.add("p", lambda: FnPellet(lambda x: x, sequential=True))
    coord = Coordinator(g).start()
    try:
        coord.flakes["p"].pause()
        for i in range(n):
            coord.inject("p", i)
        coord.inject_landmark("p", tag="flush")
        for i in range(n, 2 * n):
            coord.inject("p", i)
        coord.flakes["p"].resume()
        assert coord.run_until_quiescent(timeout=60)
        out = coord.drain_outputs()
        kinds = [("lm" if m.landmark else m.payload) for m in out]
        assert kinds == list(range(n)) + ["lm"] + list(range(n, 2 * n))
    finally:
        coord.stop()


def test_batched_shuffle_reduce_counts_are_exact():
    """Flood a 2x4 MapReduce; every (key, count) must be exact despite
    batched mappers, amortized hash routing, and fan-in landmark alignment."""
    n = 640   # divisible by 16 so every key's exact count is n // 16
    g = FloeGraph("wc")
    g.add("src", lambda: FnPellet(lambda x: x, sequential=True))
    add_mapreduce(g, prefix="b",
                  mapper_factory=lambda: FnMapper(lambda x: [(x % 16, 1)]),
                  reducer_factory=lambda: FnReducer(lambda: 0,
                                                    lambda a, v: a + v),
                  n_mappers=2, n_reducers=4, source="src")
    coord = Coordinator(g).start()
    try:
        for i in range(n):
            coord.inject("src", i)
        coord.inject_landmark("src")
        assert coord.run_until_quiescent(timeout=60)
        counts = dict(m.payload for m in coord.drain_outputs()
                      if m.is_data())
        assert sum(counts.values()) == n
        assert counts == {k: n // 16 for k in range(16)}
    finally:
        coord.stop()


# -- accounting ----------------------------------------------------------------

def test_flakestats_exact_under_batched_accounting():
    n = 500
    g = FloeGraph("stats")
    g.add("p", lambda: FnPellet(
        lambda x: Drop if x % 2 else x, sequential=True))
    coord = Coordinator(g).start()
    try:
        coord.flakes["p"].pause()
        for i in range(n):
            coord.inject("p", i)
        coord.flakes["p"].resume()
        assert coord.run_until_quiescent(timeout=60)
        st = coord.flakes["p"].stats
        assert st.arrived == n
        assert st.processed == n
        assert st.emitted == n // 2
        assert st.selectivity == pytest.approx(0.5)
        assert st.max_batch > 1
    finally:
        coord.stop()


def test_adaptive_batch_shrinks_back_to_one():
    g = FloeGraph("adapt")
    g.add("p", lambda: FnPellet(lambda x: x, sequential=True))
    coord = Coordinator(g).start()
    try:
        flake = coord.flakes["p"]
        flake.pause()
        for i in range(300):
            coord.inject("p", i)
        flake.resume()
        assert coord.run_until_quiescent(timeout=60)
        assert flake.stats.max_batch > 1          # grew under backlog
        for i in range(5):                         # trickle: B must be 1
            coord.inject("p", i)
            assert coord.run_until_quiescent(timeout=60)
            assert flake.stats.last_batch == 1
    finally:
        coord.stop()


def test_compute_batch_length_mismatch_recovers_per_message():
    """A batch-level bug (broken override) is surfaced as an engine error
    but the data is recovered through per-message compute — no loss."""
    class Bad(PushPellet):
        sequential = True

        def compute(self, payload):
            return payload

        def compute_batch(self, payloads):
            return payloads[:-1]   # one result short

    g = FloeGraph("bad")
    g.add("p", Bad)
    coord = Coordinator(g).start()
    try:
        coord.flakes["p"].pause()
        for i in range(10):
            coord.inject("p", i)
        coord.flakes["p"].resume()
        assert coord.run_until_quiescent(timeout=60)
        assert coord.errors and isinstance(coord.errors[0][1], ValueError)
        st = coord.flakes["p"].stats
        assert st.arrived == st.processed == 10   # credits never leak
        out = [m.payload for m in coord.drain_outputs() if m.is_data()]
        assert out == list(range(10))             # recovered, in order
    finally:
        coord.stop()


def test_raising_message_does_not_drop_its_batchmates():
    """Error semantics stay exactly per-message under batching: only the
    raising message is dropped (and recorded), the rest of its micro-batch
    is still delivered, and every message's side effects run EXACTLY once
    (no re-execution of batchmates on failure)."""
    calls = []

    def fragile(x):
        calls.append(x)
        if x == 13:
            raise RuntimeError("boom")
        return x

    g = FloeGraph("frag")
    g.add("p", lambda: FnPellet(fragile, sequential=True))
    coord = Coordinator(g).start()
    try:
        coord.flakes["p"].pause()
        for i in range(40):
            coord.inject("p", i)
        coord.flakes["p"].resume()
        assert coord.run_until_quiescent(timeout=60)
        assert coord.flakes["p"].stats.max_batch > 1   # really batched
        out = sorted(m.payload for m in coord.drain_outputs() if m.is_data())
        assert out == [i for i in range(40) if i != 13]
        errs = [e for _, e in coord.errors]
        assert len(errs) == 1 and isinstance(errs[0], RuntimeError)
        assert sorted(calls) == list(range(40))        # exactly once each
        st = coord.flakes["p"].stats
        assert st.arrived == st.processed == 40
        assert st.emitted == 39
    finally:
        coord.stop()


def test_failing_vectorized_batch_recovers_per_message():
    """A raising vectorized override is recovered by re-running the batch
    per message: only the bad message is dropped and recorded."""
    def vec(xs):
        if any(x == 7 for x in xs) and len(xs) > 1:
            raise RuntimeError("vectorized boom")
        return [x * 10 if x != 7 else (_ for _ in ()).throw(
            RuntimeError("boom")) for x in xs]

    g = FloeGraph("vfrag")
    g.add("p", lambda: FnPellet(vec, vectorized=True, sequential=True))
    coord = Coordinator(g).start()
    try:
        coord.flakes["p"].pause()
        for i in range(20):
            coord.inject("p", i)
        coord.flakes["p"].resume()
        assert coord.run_until_quiescent(timeout=60)
        out = sorted(m.payload for m in coord.drain_outputs() if m.is_data())
        assert out == [i * 10 for i in range(20) if i != 7]
        assert any(isinstance(e, RuntimeError) for _, e in coord.errors)
    finally:
        coord.stop()


def test_custom_split_policy_honored_under_batching():
    """Split policies are a public extension point; a custom choose() must
    see every message whether B is 1 or 100 — even with a single target."""
    from repro.core import Split
    from repro.core.patterns import SPLITS

    class EvenOnly(Split):
        def choose(self, msg, n_edges, queue_depths):
            return [0] if msg.payload % 2 == 0 else []

    SPLITS["even_only"] = EvenOnly
    try:
        g = FloeGraph("csp")
        g.add("src", lambda: FnPellet(lambda x: x, sequential=True))
        g.add("dst", lambda: FnPellet(lambda x: x, sequential=True))
        g.connect("src", "dst", split="even_only")
        coord = Coordinator(g).start()
        try:
            coord.flakes["src"].pause()
            for i in range(100):
                coord.inject("src", i)
            coord.flakes["src"].resume()
            assert coord.run_until_quiescent(timeout=60)
            assert coord.flakes["src"].stats.max_batch > 1  # really batched
            out = sorted(m.payload for m in coord.drain_outputs()
                         if m.is_data())
            assert out == [i for i in range(100) if i % 2 == 0]
        finally:
            coord.stop()
    finally:
        SPLITS.pop("even_only", None)


def test_routing_failure_releases_inflight_credits():
    """A split policy that raises mid-routing must not wedge quiescence:
    the consumed credits are released and the error is recorded."""
    from repro.core import Split
    from repro.core.patterns import SPLITS

    class Exploding(Split):
        def choose(self, msg, n_edges, queue_depths):
            raise RuntimeError("router down")

    SPLITS["exploding"] = Exploding
    try:
        g = FloeGraph("rf")
        g.add("src", lambda: FnPellet(lambda x: x, sequential=True))
        g.add("dst", lambda: FnPellet(lambda x: x))
        g.connect("src", "dst", split="exploding")
        coord = Coordinator(g).start()
        try:
            coord.flakes["src"].pause()
            for i in range(20):
                coord.inject("src", i)
            coord.flakes["src"].resume()
            # quiescence must still be reachable despite every route failing
            assert coord.run_until_quiescent(timeout=30)
            assert any(isinstance(e, RuntimeError) for _, e in coord.errors)
        finally:
            coord.stop()
    finally:
        SPLITS.pop("exploding", None)


# -- vectorized pellets --------------------------------------------------------

def test_vectorized_fnpellet_runs_once_per_batch():
    n = 200
    calls = []

    def batched_double(xs):
        calls.append(len(xs))
        return [x * 2 for x in xs]

    g = FloeGraph("vec")
    g.add("p", lambda: FnPellet(batched_double, vectorized=True,
                                sequential=True))
    coord = Coordinator(g).start()
    try:
        coord.flakes["p"].pause()
        for i in range(n):
            coord.inject("p", i)
        coord.flakes["p"].resume()
        assert coord.run_until_quiescent(timeout=60)
        out = [m.payload for m in coord.drain_outputs() if m.is_data()]
        assert out == [i * 2 for i in range(n)]
        assert sum(calls) == n
        assert len(calls) < n          # genuinely amortized
        assert max(calls) > 1
    finally:
        coord.stop()


def test_vectorized_single_message_semantics():
    p = FnPellet(lambda xs: [x + 1 for x in xs], vectorized=True)
    assert p.compute(41) == 42
    assert p.compute_batch([1, 2, 3]) == [2, 3, 4]


# -- Session API knob ----------------------------------------------------------

def test_flow_batch_annotation_compiles_onto_flake():
    flow = Flow("b")
    stage = flow.pellet("p", lambda: FnPellet(lambda x: x))
    stage.batch(32, max_wait_ms=5.0)
    with flow.session() as s:
        flake = s.coordinator.flakes["p"]
        assert flake.batch_max == 32
        assert flake.batch_wait == pytest.approx(0.005)
        s.set_batch("p", max_size=1)          # runtime disable
        assert flake.batch_max == 1
        s.inject("p", 7)
        assert s.results() == [7]


def test_flow_batch_annotation_validates_eagerly():
    flow = Flow("bad")
    stage = flow.pellet("p", lambda: FnPellet(lambda x: x))
    with pytest.raises(CompositionError, match="max_size"):
        stage.batch(0)
    with pytest.raises(CompositionError, match="max_wait_ms"):
        stage.batch(8, max_wait_ms=-1)


def test_batch_rejected_for_non_push_stages():
    """The knob is a no-op for pull/window/tuple pellets, so accepting it
    would silently do nothing — eager validation rejects it instead."""
    from repro.core import FnReducer, WindowPellet

    class Win(WindowPellet):
        window = 4

        def compute(self, payloads):
            return sum(payloads)

    flow = Flow("nonpush")
    red = flow.pellet("red", lambda: FnReducer(lambda: 0, lambda a, v: a + v))
    win = flow.pellet("win", Win)
    for stage in (red, win):
        with pytest.raises(CompositionError, match="push pellets only"):
            stage.batch(32)
    with flow.session() as s:
        from repro.api.errors import SessionStateError
        with pytest.raises(SessionStateError, match="push pellets only"):
            s.set_batch("red", max_size=32)


def test_set_batch_validates_at_runtime():
    from repro.api.errors import SessionStateError
    flow = Flow("rt")
    flow.pellet("p", lambda: FnPellet(lambda x: x))
    with flow.session() as s:
        with pytest.raises(SessionStateError, match="max_size"):
            s.set_batch("p", max_size=0)
        with pytest.raises(SessionStateError, match="max_wait_ms"):
            s.set_batch("p", max_size=8, max_wait_ms=-5)


@pytest.mark.parametrize("sequential", [True, False])
def test_batch_wait_coalesces_a_partial_batch(sequential):
    """The linger must engage for pooled (non-sequential) stages too —
    that is the README's recommended vectorized configuration."""
    flow = Flow("wait")
    flow.pellet("p", lambda: FnPellet(lambda x: x, sequential=sequential)) \
        .batch(64, max_wait_ms=25.0)
    with flow.session() as s:
        flake = s.coordinator.flakes["p"]
        flake.pause()
        for i in range(10):
            s.inject("p", i)
        flake.resume()
        assert sorted(s.results()) == list(range(10))
        # all 10 queued messages (< max_size) coalesced into ONE dispatch
        # after the bounded linger
        assert flake.stats.batches == 1
        assert flake.stats.last_batch == 10


def test_batch_wait_does_not_delay_landmarks():
    """Specials can never be part of a batch, so a lingering stage must
    dispatch them immediately instead of burning the full wait."""
    import time as _time
    flow = Flow("lmwait")
    flow.pellet("p", lambda: FnPellet(lambda x: x, sequential=True)) \
        .batch(256, max_wait_ms=10_000.0)   # pathological 10s linger
    with flow.session() as s:
        t0 = _time.time()
        s.inject_landmark("p", tag="flush")
        out = s.drain(timeout=5)
        assert _time.time() - t0 < 5
        assert any(m.landmark for m in out)


def test_set_batch_clears_pending_linger():
    flow = Flow("clear")
    flow.pellet("p", lambda: FnPellet(lambda x: x, sequential=True)) \
        .batch(64, max_wait_ms=5_000.0)
    with flow.session() as s:
        flake = s.coordinator.flakes["p"]
        s.inject("p", 1)          # starts a 5s linger (1 < 64)
        assert wait_until(lambda: flake._batch_deadline is not None)
        s.set_batch("p", max_size=64, max_wait_ms=0.0)
        # the dropped linger must not strand the queued message
        assert s.results(timeout=5) == [1]
        assert flake._batch_deadline is None


def test_batched_sink_collection_preserves_cross_port_emit_order():
    """Sink-collected emissions from different out-ports share one output
    list; batching must not regroup them by port."""
    class TwoPort(PushPellet):
        sequential = True
        out_ports = ("a", "b")

        def compute(self, x):
            return {"a": ("a", x), "b": ("b", x)}

    g = FloeGraph("ports")
    g.add("p", TwoPort)
    coord = Coordinator(g).start()
    try:
        coord.flakes["p"].pause()
        for i in range(60):
            coord.inject("p", i)
        coord.flakes["p"].resume()
        assert coord.run_until_quiescent(timeout=60)
        assert coord.flakes["p"].stats.max_batch > 1   # really batched
        out = [m.payload for m in coord.drain_outputs() if m.is_data()]
        expected = []
        for i in range(60):
            expected += [("a", i), ("b", i)]   # interleaved emit order
        assert out == expected
    finally:
        coord.stop()


def test_batched_routing_preserves_cross_port_order_to_shared_destination():
    """Two out-ports wired to the SAME downstream flake: the downstream
    channel must observe the exact emit interleaving, not port bursts."""
    class TwoPort(PushPellet):
        sequential = True
        out_ports = ("a", "b")

        def compute(self, x):
            return {"a": ("a", x), "b": ("b", x)}

    g = FloeGraph("xport")
    g.add("p", TwoPort)
    g.add("q", lambda: FnPellet(lambda x: x, sequential=True))
    g.connect("p", "q", src_port="a")
    g.connect("p", "q", src_port="b")
    coord = Coordinator(g).start()
    try:
        coord.flakes["p"].pause()
        for i in range(50):
            coord.inject("p", i)
        coord.flakes["p"].resume()
        assert coord.run_until_quiescent(timeout=60)
        assert coord.flakes["p"].stats.max_batch > 1   # really batched
        out = [m.payload for m in coord.drain_outputs() if m.is_data()]
        expected = []
        for i in range(50):
            expected += [("a", i), ("b", i)]
        assert out == expected
    finally:
        coord.stop()


# -- speculative execution keeps its per-message path --------------------------

def test_speculation_forces_per_message_dispatch():
    g = FloeGraph("spec")
    g.add("p", lambda: FnPellet(lambda x: x))
    coord = Coordinator(g, speculative_timeout=5.0).start()
    try:
        flake = coord.flakes["p"]
        assert flake._batch_limit() == 1
        flake.pause()
        for i in range(50):
            coord.inject("p", i)
        flake.resume()
        assert coord.run_until_quiescent(timeout=60)
        assert flake.stats.max_batch == 1
        out = sorted(m.payload for m in coord.drain_outputs() if m.is_data())
        assert out == list(range(50))
    finally:
        coord.stop()


def test_speculative_backup_does_not_leak_semaphore_slots():
    """Backup tasks bypass the instance pool; they must not release slots
    they never acquired (the admission cap would loosen by one per backup)."""
    import time as _time

    def slow_once(x):
        if x == 0:
            _time.sleep(0.2)
        return x

    g = FloeGraph("slots")
    g.add("p", lambda: FnPellet(slow_once), cores=2)
    coord = Coordinator(g, speculative_timeout=0.05).start()
    try:
        for i in range(5):
            coord.inject("p", i)
        assert coord.run_until_quiescent(timeout=60)
        assert wait_until(
            lambda: coord.flakes["p"]._sem._in_use == 0, timeout=10)
        assert coord.flakes["p"]._sem._in_use == 0   # never negative
    finally:
        coord.stop()


# -- message seq block allocation ---------------------------------------------

def test_seq_ids_unique_across_threads():
    seqs, lock = [], threading.Lock()

    def mint(k=800):
        local = [Message(payload=None).seq for _ in range(k)]
        with lock:
            seqs.extend(local)

    threads = [threading.Thread(target=mint) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(set(seqs)) == len(seqs) == 8 * 800


def test_seq_monotonic_per_thread():
    a = Message(payload=1).seq
    b = Message(payload=2).seq
    assert b > a
