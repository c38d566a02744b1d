"""Dynamic-topology Session API (ISSUE 4): graph-diff recomposition —
vertex add/remove under live load, declarative ``session.apply(flow)``,
checkpoint-integrated sessions, topology versioning, split rebuild."""
import threading
import time

import pytest

from testkit import wait_until
from repro import (ClusterManager, ClusterSpec, Flow, FnPellet, PullPellet,
                   PushPellet, RecompositionError, Session, WindowPellet)
from repro.checkpoint import read_floe_meta


class Tag(PushPellet):
    """Pass-through that labels payloads so the census can see the route."""

    def __init__(self, tag):
        self.tag = tag

    def compute(self, x):
        return (self.tag, x)


class SumWindow(WindowPellet):
    def compute(self, payloads):
        return sum(payloads)


class FlushWindow(WindowPellet):
    """Large window: only a landmark flush ever emits."""
    window = 100

    def compute(self, payloads):
        return ("flush", sorted(payloads))


class Summer(PullPellet):
    def initial_state(self):
        return 0

    def compute(self, messages, emit, state):
        for m in messages:
            if m.is_data():
                state += m.payload
                emit(state)
        return state


def _linear_flow():
    flow = Flow("lin")
    src = flow.pellet("src", lambda: FnPellet(lambda x: x))
    work = flow.pellet("work", lambda: FnPellet(lambda x: x))
    src >> work
    return flow


# ---------------------------------------------------------------------------
# recompose: vertex addition / removal
# ---------------------------------------------------------------------------

def test_recompose_add_stage_and_connect():
    flow = _linear_flow()
    with flow.session() as s:
        with s.recompose() as tx:
            tx.add("tag", lambda: Tag("grafted"))
            tx.connect("work", "tag")
        assert tx.result["added"] == ["tag"]
        assert "tag" in s.coordinator.flakes
        s.inject("src", 7)
        assert s.results() == [("grafted", 7)]


def test_recompose_add_from_stage_handle_carries_annotations():
    flow = _linear_flow()
    scratch = Flow("scratch")
    handle = scratch.pellet("tag", lambda: Tag("h"), cores=3).batch(16)
    with flow.session() as s:
        with s.recompose() as tx:
            tx.add(handle)
            tx.connect("work", "tag")
        flake = s.coordinator.flakes["tag"]
        assert flake.cores == 3
        assert flake.batch_max == 16
        s.inject("src", 1)
        assert s.results() == [("h", 1)]


def test_recompose_remove_stage_releases_cores_and_routes():
    flow = _linear_flow()
    tag = flow.pellet("tag", lambda: Tag("t"), cores=2)
    flow.stages["work"] >> tag
    with flow.session() as s:
        coord = s.coordinator
        container = coord._container_of["tag"]
        held = container.allocated.get("tag", 0)
        assert held == 2
        with s.recompose() as tx:
            tx.remove("tag")
        assert "tag" not in coord.flakes
        assert container.allocated.get("tag", 0) == 0
        assert "tag" not in coord.graph.vertices
        # the dataflow keeps running: work is a sink again
        s.inject("src", 5)
        assert s.results() == [5]


def test_remove_backlog_collect_surfaces_messages_and_credits():
    flow = _linear_flow()
    slow = flow.pellet("slow", lambda: FnPellet(lambda x: x))
    flow.stages["work"] >> slow
    with flow.session() as s:
        s.coordinator.flakes["slow"].pause()   # park backlog in 'slow'
        s.inject_many("src", list(range(20)))

        def parked():
            return s.coordinator.flakes["slow"].queue_length() == 20
        deadline = time.time() + 10
        while not parked() and time.time() < deadline:
            time.sleep(0.01)
        assert parked()
        with s.recompose() as tx:
            tx.remove("slow", backlog="collect")
        backlog = tx.result["backlog"]["slow"]
        assert sorted(m.payload for m in backlog) == list(range(20))
        assert tx.result["removed_backlog"]["slow"] == 20
        # credits released: the engine must go quiescent, not wedge
        assert s.quiesce(10)


def test_remove_backlog_reroute_preserves_messages():
    flow = _linear_flow()
    old = flow.pellet("old", lambda: Tag("old"))
    new = flow.pellet("new", lambda: Tag("new"))
    flow.stages["work"] >> old
    with flow.session() as s:
        s.coordinator.flakes["old"].pause()
        s.inject_many("src", list(range(10)))
        deadline = time.time() + 10
        while s.coordinator.flakes["old"].queue_length() < 10 and \
                time.time() < deadline:
            time.sleep(0.01)
        with s.recompose() as tx:
            tx.remove("old", backlog=("new", "in"))
            tx.connect("work", "new")
        out = s.results()
        assert sorted(x for (_, x) in out) == list(range(10))
        assert all(t == "new" for (t, _) in out)


def test_recompose_add_remove_under_live_load_census():
    """Graft a stage onto a running pipeline, then retire it, while a
    producer thread keeps injecting: every message arrives exactly once
    (zero loss, zero duplication) and per-key FIFO order holds."""
    N, KEYS = 3000, 8

    class KeyedRelay(PushPellet):
        """Pass-through that PRESERVES the routing key on emit, so the
        downstream hash split keeps pinning each key to one worker."""
        sequential = True

        def compute(self, x):
            from repro import KeyedEmit
            return KeyedEmit(x, key=x[0])

    # sequential pellets: per-key FIFO is only contractual without the
    # data-parallel instance pool (same setup as the migration census)
    flow = Flow("live")
    src = flow.pellet("src", KeyedRelay)
    w0 = flow.pellet("w0", lambda: FnPellet(lambda x: x, sequential=True))
    w1 = flow.pellet("w1", lambda: FnPellet(lambda x: x, sequential=True))
    gather = flow.pellet("gather",
                         lambda: FnPellet(lambda x: x, sequential=True))
    src.split("hash") >> w0
    src >> w1
    w0 >> gather
    w1 >> gather
    with flow.session() as s:
        def producer():
            for i in range(N):
                key = i % KEYS
                s.inject("src", (key, i), key=key)
                if i % 400 == 0:
                    time.sleep(0.01)

        t = threading.Thread(target=producer)
        t.start()
        time.sleep(0.02)
        # graft an audit branch mid-stream...
        with s.recompose() as tx:
            tx.add("audit", lambda: Tag("audit"))
            tx.connect("gather", "audit")
        time.sleep(0.05)
        # ...and retire it again; its parked backlog is surfaced, not lost
        with s.recompose() as tx2:
            tx2.remove("audit", backlog="collect")
        t.join()
        out = s.results(timeout=60)
        collected = tx2.result.get("backlog", {}).get("audit", [])
        # normalize: out entries are in sink-collection order; entries that
        # passed through the grafted branch carry the "audit" tag
        seen = [o[1] if isinstance(o, tuple) and o[0] == "audit" else o
                for o in out]
        ids = sorted([x[1] for x in seen]
                     + [m.payload[1] for m in collected])
        assert ids == list(range(N)), (
            f"census mismatch: {len(ids)} messages, "
            f"lost={set(range(N)) - set(ids)}, "
            f"dups={[i for i in ids if ids.count(i) > 1][:5]}")
        # per-key FIFO over the sink order: hash split pins a key to one
        # worker and the grafted/retired branch extends the path without
        # reordering it.  (The collected backlog was pulled out of the
        # stream at removal — it fills id gaps in the census above but has
        # no position in the sink timeline.)
        dropped = {m.payload[1] for m in collected}
        order = {}
        for key, i in seen:
            assert i not in dropped, "collected message also delivered"
            assert order.get(key, -1) < i, f"key {key} reordered at {i}"
            order[key] = i


def test_remove_stage_with_half_gathered_window():
    flow = Flow("win")
    src = flow.pellet("src", lambda: FnPellet(lambda x: x))
    win = flow.pellet("win", lambda: SumWindow(10))
    src >> win
    with flow.session() as s:
        s.inject_many("src", [1, 2, 3])     # half-gathered window
        deadline = time.time() + 10
        while not s.coordinator.flakes["win"]._window_buf and \
                time.time() < deadline:
            time.sleep(0.01)
        assert s.coordinator.flakes["win"]._window_buf
        with s.recompose() as tx:
            tx.remove("win", backlog="collect")
        # the half-gathered messages are surfaced, their credits released
        assert sorted(m.payload for m in tx.result["backlog"]["win"]) == \
            [1, 2, 3]
        assert s.quiesce(10)


def test_remove_upstream_completes_pending_landmark_round():
    """Retiring one of a reducer's feeders (fan-in 2 -> 1) completes a
    half-counted landmark alignment round instead of losing it."""
    flow = Flow("lm")
    a = flow.pellet("a", lambda: FnPellet(lambda x: x))
    b = flow.pellet("b", lambda: FnPellet(lambda x: x))
    win = flow.pellet("win", FlushWindow)
    a >> win
    b >> win
    with flow.session() as s:
        s.inject("a", 1)
        s.inject("b", 2)
        # window-buffered messages hold their credits until a flush, so
        # poll the buffer instead of engine-wide quiescence
        deadline = time.time() + 10
        while len(s.coordinator.flakes["win"]._window_buf) < 2 and \
                time.time() < deadline:
            time.sleep(0.01)
        assert len(s.coordinator.flakes["win"]._window_buf) == 2
        s.inject_landmark("a")              # 1 of 2 copies: swallowed
        time.sleep(0.2)
        assert s.coordinator.flakes["win"]._lm_pending is not None
        with s.recompose() as tx:
            tx.remove("b")
        out = s.results(timeout=20)
        assert ("flush", [1, 2]) in out     # the round completed


def test_recompose_add_invalid_wiring_rolls_back_everything():
    flow = _linear_flow()
    with flow.session() as s:
        v0 = s.coordinator.topology_version
        with pytest.raises(RecompositionError, match="no INPUT port"):
            with s.recompose() as tx:
                tx.add("tag", lambda: Tag("t"))
                tx.connect("work", "tag", dst_port="nope")
        assert s.coordinator.topology_version == v0
        assert "tag" not in s.coordinator.flakes
        assert s.coordinator.core_audit() == {
            c.name: dict(c.allocated)
            for c in s.coordinator.containers if c.allocated}
        s.inject("src", 3)
        assert s.results() == [3]


def test_add_then_remove_same_name_in_one_tx_rejected():
    flow = _linear_flow()
    with flow.session() as s:
        with pytest.raises(RecompositionError, match="both added and"):
            with s.recompose() as tx:
                tx.add("x", lambda: Tag("x"))
                tx.remove("x")


def test_remove_unknown_and_swap_removed_rejected():
    flow = _linear_flow()
    with flow.session() as s:
        with pytest.raises(RecompositionError, match="unknown stage"):
            with s.recompose() as tx:
                tx.remove("ghost")
        with pytest.raises(RecompositionError, match="cannot also be"):
            with s.recompose() as tx:
                tx.remove("work")
                tx.swap("work", lambda: FnPellet(lambda x: x))


def test_grafted_stage_with_elastic_policy_joins_controller():
    flow = _linear_flow()
    scratch = Flow("scratch")
    handle = scratch.pellet("burst", lambda: FnPellet(lambda x: x)).elastic(
        max_cores=4, strategy="dynamic")
    with flow.session() as s:
        assert s.controller is None
        with s.recompose() as tx:
            tx.add(handle)
            tx.connect("work", "burst")
        assert s.controller is not None
        assert "burst" in s.controller.strategies
        with s.recompose() as tx:
            tx.remove("burst")
        assert "burst" not in s.controller.strategies


# ---------------------------------------------------------------------------
# topology version + diff summary
# ---------------------------------------------------------------------------

def test_topology_version_monotonic_and_diff_in_describe():
    flow = _linear_flow()
    with flow.session() as s:
        d = s.describe()
        assert d["topology_version"] == 0
        assert d["last_recomposition"] is None
        with s.recompose() as tx:
            tx.add("tag", lambda: Tag("t"))
            tx.connect("work", "tag")
        d1 = s.describe()
        assert d1["topology_version"] == 1
        assert d1["last_recomposition"]["added"] == ["tag"]
        assert d1["last_recomposition"]["edges_added"] == [
            {"src": "work", "src_port": "out", "dst": "tag",
             "dst_port": "in", "split": "round_robin",
             "transport": "push"}]
        with s.recompose() as tx:
            tx.scale("work", cores=2)
        d2 = s.describe()
        assert d2["topology_version"] == 2
        assert d2["last_recomposition"]["scaled"] == {"work": 2}
        # an aborted transaction must NOT bump the version
        with pytest.raises(RecompositionError):
            with s.recompose() as tx:
                tx.remove("ghost")
        assert s.describe()["topology_version"] == 2


# ---------------------------------------------------------------------------
# declarative session.apply(flow)
# ---------------------------------------------------------------------------

def test_apply_commits_add_remove_rewire_delta_atomically():
    flow = Flow("pipe")
    src = flow.pellet("src", lambda: FnPellet(lambda x: x))
    old = flow.pellet("old", lambda: Tag("old"))
    src >> old
    with flow.session() as s:
        s.inject("src", 1)
        assert s.results() == [("old", 1)]
        nf = s.flow.derive()
        nf.remove("old")
        fresh = nf.pellet("fresh", lambda: Tag("fresh"))
        nf.stages["src"] >> fresh
        summary = s.apply(nf)
        assert summary["added"] == ["fresh"]
        assert summary["removed"] == ["old"]
        assert s.describe()["topology_version"] == 1
        assert s.flow is nf
        s.inject("src", 2)
        assert s.results() == [("fresh", 2)]


def test_apply_under_live_load_census():
    """The acceptance-criteria scenario: one apply() commits an
    add+remove+rewire delta on a running session with zero message loss
    or duplication."""
    N = 2000
    flow = Flow("pipe")
    src = flow.pellet("src", lambda: FnPellet(lambda x: x))
    old = flow.pellet("old", lambda: Tag("old"))
    src >> old
    with flow.session() as s:
        def producer():
            for i in range(N):
                s.inject("src", i)
                if i % 250 == 0:
                    time.sleep(0.01)

        t = threading.Thread(target=producer)
        t.start()
        time.sleep(0.02)
        nf = s.flow.derive()
        nf.remove("old")                      # remove
        fresh = nf.pellet("fresh", lambda: Tag("fresh"))
        nf.stages["src"] >> fresh             # add + rewire
        summary = s.apply(nf, backlog="collect")
        t.join()
        out = s.results(timeout=60)
        ids = [x for (_, x) in out]
        for m in summary.get("backlog", {}).get("old", []):
            ids.append(m.payload)
        assert sorted(ids) == list(range(N)), (
            f"{len(ids)} messages, lost={set(range(N)) - set(ids)}")


def test_apply_noop_commits_nothing():
    flow = _linear_flow()
    with flow.session() as s:
        v0 = s.describe()["topology_version"]
        summary = s.apply(s.flow.derive())
        assert summary == {"changed": False, "noop": True, "version": v0}
        assert s.describe()["topology_version"] == v0
        assert s.describe()["last_recomposition"] is None


def test_apply_invalid_diff_rolls_back_before_any_change():
    flow = _linear_flow()
    with flow.session() as s:
        v0 = s.describe()["topology_version"]
        nf = s.flow.derive()
        # bypass .replace() validation: a factory producing a non-Pellet
        # must be caught by apply itself, before any change
        nf.stages["work"].factory = lambda: 42
        with pytest.raises(RecompositionError, match="expected a Pellet"):
            s.apply(nf)
        assert s.describe()["topology_version"] == v0
        assert s.flow is not nf
        s.inject("src", 9)
        assert s.results() == [9]


def test_apply_same_name_replacement_with_changed_ports():
    """ROADMAP follow-up: a same-name stage whose factory changes the port
    signature is committed as a replacement in ONE transaction — new
    wiring validated against the fresh proto's ports, backlog on the
    surviving input port carried over FIFO."""
    class TwoOut(PushPellet):
        out_ports = ("hi", "lo")

        def compute(self, x):
            return {"hi" if x >= 10 else "lo": x}

    flow = Flow("rep")
    src = flow.pellet("src", lambda: FnPellet(lambda x: x))
    work = flow.pellet("work", lambda: Tag("v1"))
    sink = flow.pellet("sink", lambda: FnPellet(lambda x: ("sunk", x)))
    src >> work
    work >> sink
    with flow.session() as s:
        s.inject("src", 1)
        assert s.results() == [("sunk", ("v1", 1))]
        v0 = s.describe()["topology_version"]
        # park backlog in the stage being replaced: it must survive the
        # swap and be processed by the NEW logic
        s.coordinator.flakes["work"].pause()
        s.inject("src", 3)
        s.inject("src", 42)
        assert wait_until(
            lambda: s.coordinator.flakes["work"].queue_length() == 2)
        nf = s.flow.derive()
        nf.disconnect("src", "work")
        nf.disconnect("work", "sink")
        nf.stages["work"].replace(TwoOut)      # in=(in,), out=(hi, lo)
        nf.stages["src"] >> nf.stages["work"]
        nf.stages["work"]["hi"] >> nf.stages["sink"]
        nf.stages["work"]["lo"] >> nf.stages["sink"]
        summary = s.apply(nf)
        assert summary["replaced"] == ["work"]
        assert summary["swapped"] == []
        assert s.describe()["topology_version"] == v0 + 1
        out = s.results()
        assert sorted(out) == [("sunk", 3), ("sunk", 42)]   # carried FIFO
        s.inject("src", 7)
        s.inject("src", 70)
        assert sorted(s.results()) == [("sunk", 7), ("sunk", 70)]
        assert not s.errors, s.errors[:3]


def test_apply_replacement_preserves_landmark_alignment():
    """A fan-in-2 stage replaced mid-alignment (one landmark copy already
    swallowed) must complete the round when the second copy arrives —
    alignment progress moves to the replacement like it does in
    migration."""
    class TwoOut(PushPellet):
        out_ports = ("x", "y")

        def compute(self, v):
            return {"x": v}

    flow = Flow("lmrep")
    s1 = flow.pellet("s1", lambda: FnPellet(lambda x: x))
    s2 = flow.pellet("s2", lambda: FnPellet(lambda x: x))
    mid = flow.pellet("mid", lambda: FnPellet(lambda x: x))
    sink = flow.pellet("sink", lambda: FnPellet(lambda x: x))
    s1 >> mid
    s2 >> mid
    mid >> sink
    with flow.session() as s:
        s.inject_landmark("s1", tag="w0")   # copy 1 of 2: swallowed at mid
        assert s.quiesce()
        assert s.coordinator.flakes["mid"]._lm_count == 1
        nf = s.flow.derive()
        nf.disconnect("mid", "sink")
        nf.stages["mid"].replace(TwoOut)
        nf.stages["mid"]["x"] >> nf.stages["sink"]
        nf.stages["mid"]["y"] >> nf.stages["sink"]
        assert s.apply(nf)["replaced"] == ["mid"]
        s.inject_landmark("s2", tag="w0")   # copy 2 completes the round
        out = s.drain()
        assert sum(1 for m in out if m.landmark) == 1
        assert not s.errors, s.errors[:3]


def test_apply_replacement_rejects_stale_wiring():
    """Edges still naming a port the replacement proto lacks abort the
    whole transaction before any change."""
    class TwoOut(PushPellet):
        out_ports = ("hi", "lo")

        def compute(self, x):
            return {"hi": x}

    flow = Flow("stale")
    src = flow.pellet("src", lambda: FnPellet(lambda x: x))
    work = flow.pellet("work", lambda: Tag("v1"))
    sink = flow.pellet("sink", lambda: FnPellet(lambda x: x))
    src >> work
    work >> sink
    with flow.session() as s:
        v0 = s.describe()["topology_version"]
        nf = s.flow.derive()
        nf.stages["work"].replace(TwoOut)
        # old edge work["out"] -> sink left in place: invalid for TwoOut
        with pytest.raises(RecompositionError, match="OUTPUT port"):
            s.apply(nf)
        assert s.describe()["topology_version"] == v0
        s.inject("src", 5)
        assert s.results() == [("v1", 5)]   # old logic untouched


def test_apply_swaps_pellet_and_retunes_batch():
    flow = Flow("sw")
    src = flow.pellet("src", lambda: FnPellet(lambda x: x))
    work = flow.pellet("work", lambda: Tag("v1"))
    src >> work
    with flow.session() as s:
        s.inject("src", 1)
        assert s.results() == [("v1", 1)]
        nf = s.flow.derive()
        nf.stages["work"].replace(lambda: Tag("v2"))
        nf.stages["work"].batch(32)
        summary = s.apply(nf)
        assert summary["swapped"] == ["work"]
        assert summary["batch_updated"] == ["work"]
        assert s.coordinator.flakes["work"].batch_max == 32
        s.inject("src", 2)
        assert s.results() == [("v2", 2)]


def test_apply_batch_annotation_removal_reverts_to_default():
    from repro.core.engine import DEFAULT_BATCH_MAX
    flow = Flow("ba")
    src = flow.pellet("src", lambda: FnPellet(lambda x: x))
    work = flow.pellet("work", lambda: FnPellet(lambda x: x)).batch(7)
    src >> work
    with flow.session() as s:
        assert s.coordinator.flakes["work"].batch_max == 7
        nf = s.flow.derive()
        del nf.stages["work"].annotations["batch_max"]
        del nf.stages["work"].annotations["batch_wait_ms"]
        summary = s.apply(nf)
        assert summary["batch_updated"] == ["work"]
        flake = s.coordinator.flakes["work"]
        assert flake.batch_max == DEFAULT_BATCH_MAX
        assert not flake._batch_explicit


def test_last_transaction_does_not_retain_collected_backlog():
    """describe()/the coordinator must not pin collected Messages."""
    flow = _linear_flow()
    tail = flow.pellet("tail", lambda: FnPellet(lambda x: x))
    flow.stages["work"] >> tail
    with flow.session() as s:
        s.coordinator.flakes["tail"].pause()
        s.inject("src", 1)
        deadline = time.time() + 10
        while s.coordinator.flakes["tail"].queue_length() < 1 and \
                time.time() < deadline:
            time.sleep(0.01)
        with s.recompose() as tx:
            tx.remove("tail", backlog="collect")
        assert len(tx.result["backlog"]["tail"]) == 1   # caller gets them
        assert "backlog" not in s.coordinator.last_transaction
        assert s.coordinator.last_transaction["removed_backlog"] == \
            {"tail": 1}


def test_apply_elastic_policy_change_syncs_controller():
    flow = _linear_flow()
    with flow.session() as s:
        assert s.controller is None
        nf = s.flow.derive()
        nf.stages["work"].elastic(max_cores=4)
        summary = s.apply(nf)
        assert summary["elastic_updated"] == ["work"]
        assert s.controller is not None and \
            "work" in s.controller.strategies
        nf2 = s.flow.derive()
        nf2.stages["work"].policy = None
        s.apply(nf2)
        assert "work" not in s.controller.strategies


# ---------------------------------------------------------------------------
# checkpoint-integrated sessions
# ---------------------------------------------------------------------------

def test_checkpoint_kill_restore_roundtrip(tmp_path):
    def build():
        flow = Flow("ck")
        src = flow.pellet("src", lambda: FnPellet(lambda x: x))
        summer = flow.pellet("sum", Summer)
        src >> summer
        return flow

    path = str(tmp_path / "sess.ckpt")
    with build().session() as s:
        s.inject_many("src", [10, 5])
        assert s.quiesce(20)
        s.drain()
        # park two messages mid-pipeline, then snapshot the live session
        s.coordinator.flakes["sum"].pause()
        s.inject("src", 7)
        s.inject("src", 3)
        deadline = time.time() + 10
        while s.coordinator.flakes["sum"].queue_length() < 2 and \
                time.time() < deadline:
            time.sleep(0.01)
        meta = s.checkpoint(path)
        assert meta["flow"] == "ck" and meta["topology_version"] == 0
    # "kill": the with-block tore the session down.  Restore into a fresh
    # session over the same composition: state + parked backlog replay.
    assert read_floe_meta(path)["flow"] == "ck"
    with Session.restore(path, build()) as s2:
        assert s2.quiesce(20)
        assert s2.coordinator.flakes["sum"].state == 25   # 15 + 7 + 3
        assert sorted(m.payload for m in s2.drain() if m.is_data()) == \
            [22, 25]


def test_checkpoint_preserves_half_gathered_window(tmp_path):
    def build():
        flow = Flow("wck")
        src = flow.pellet("src", lambda: FnPellet(lambda x: x))
        win = flow.pellet("win", lambda: SumWindow(4))
        src >> win
        return flow

    path = str(tmp_path / "w.ckpt")
    with build().session() as s:
        s.inject_many("src", [1, 2, 3])
        deadline = time.time() + 10
        while len(s.coordinator.flakes["win"]._window_buf) < 3 and \
                time.time() < deadline:
            time.sleep(0.01)
        s.checkpoint(path)
    with Session.restore(path, build()) as s2:
        s2.inject("src", 4)                  # completes the window
        assert s2.results(timeout=20) == [10]


def test_checkpoint_after_recomposition_restores_on_derived_flow(tmp_path):
    """A recomposition gone wrong can be rolled back: checkpoint before,
    mutate, restore the pre-change state on the matching blueprint."""
    flow = _linear_flow()
    path = str(tmp_path / "pre.ckpt")
    with flow.session() as s:
        s.inject("src", 1)
        assert s.quiesce(10)
        s.drain()
        s.coordinator.flakes["work"].pause()
        s.inject("src", 41)
        s.checkpoint(path)
        # the "bad" change: retire 'work' entirely (backlog dropped!)
        with s.recompose() as tx:
            tx.remove("work", backlog="drop")
        assert "work" not in s.coordinator.flakes
    # roll back to the checkpoint on the original blueprint
    with Session.restore(path, _linear_flow()) as s2:
        assert s2.results(timeout=20) == [41]


# ---------------------------------------------------------------------------
# split rebuild on fan-out-changing rewires (PR-3 satellite fix)
# ---------------------------------------------------------------------------

def test_split_rebuilt_when_fanout_changes():
    flow = Flow("fan")
    src = flow.pellet("src", lambda: FnPellet(lambda x: x))
    a = flow.pellet("a", lambda: Tag("a"))
    b = flow.pellet("b", lambda: Tag("b"))
    src >> a
    src >> b
    with flow.session() as s:
        flake = s.coordinator.flakes["src"]
        split_before = flake.routes["out"][0]
        with s.recompose() as tx:
            tx.unwire("src", "b")
        assert flake.routes["out"][0] is not split_before
        assert len(flake.routes["out"][1]) == 1
        s.inject_many("src", [1, 2, 3])
        assert sorted(s.results()) == [("a", 1), ("a", 2), ("a", 3)]


def test_split_reused_when_group_unchanged():
    """Stateful split policies (round-robin counters) must survive
    rewires that do not touch their fan-out group."""
    flow = Flow("fan2")
    src = flow.pellet("src", lambda: FnPellet(lambda x: x))
    a = flow.pellet("a", lambda: Tag("a"))
    b = flow.pellet("b", lambda: Tag("b"))
    other = flow.pellet("other", lambda: FnPellet(lambda x: x))
    src >> a
    src >> b
    with flow.session() as s:
        flake = s.coordinator.flakes["src"]
        split_before = flake.routes["out"][0]
        with s.recompose() as tx:       # unrelated rewire
            tx.add("tail", lambda: Tag("tail"))
            tx.connect("other", "tail")
        assert flake.routes["out"][0] is split_before


# ---------------------------------------------------------------------------
# cluster sessions
# ---------------------------------------------------------------------------

def test_cluster_add_remove_places_and_releases():
    flow = Flow("cl")
    src = flow.pellet("src", lambda: FnPellet(lambda x: x))
    work = flow.pellet("work", lambda: FnPellet(lambda x: x), cores=2)
    src >> work
    cluster = ClusterManager(ClusterSpec(hosts=2, cores_per_host=8,
                                         placement="spread"))
    with flow.session(cluster=cluster) as s:
        scratch = Flow("scratch")
        handle = scratch.pellet("tag", lambda: Tag("t"), cores=3)
        handle.place(host="h1")
        with s.recompose() as tx:
            tx.add(handle)
            tx.connect("work", "tag")
        assert cluster._placement["tag"] == "h1"
        assert cluster.hosts["h1"].container.allocated.get("tag") == 3
        s.inject("src", 1)
        assert s.results(timeout=30) == [("t", 1)]
        with s.recompose() as tx:
            tx.remove("tag")
        assert "tag" not in cluster._placement
        assert cluster.hosts["h1"].container.allocated.get("tag", 0) == 0
        events = [e["event"] for e in cluster.events]
        assert "unplace" in events
        s.inject("src", 2)
        assert s.results(timeout=30) == [2]


def test_cluster_add_placement_failure_rolls_back():
    flow = Flow("cl2")
    src = flow.pellet("src", lambda: FnPellet(lambda x: x))
    work = flow.pellet("work", lambda: FnPellet(lambda x: x))
    src >> work
    cluster = ClusterManager(ClusterSpec(hosts=1, cores_per_host=8))
    with flow.session(cluster=cluster) as s:
        v0 = s.coordinator.topology_version
        scratch = Flow("scratch")
        bad = scratch.pellet("tag", lambda: Tag("t")).place(host="h9")
        with pytest.raises(Exception, match="unknown host"):
            with s.recompose() as tx:
                tx.add(bad)
                tx.connect("work", "tag")
        assert "tag" not in s.coordinator.flakes
        assert "tag" not in cluster._placement
        assert s.coordinator.topology_version == v0
        s.inject("src", 1)
        assert s.results(timeout=30) == [1]


# ---------------------------------------------------------------------------
# Flow.derive / remove / disconnect (builder support)
# ---------------------------------------------------------------------------

def test_derive_is_independent_copy():
    flow = _linear_flow()
    d = flow.derive()
    d.pellet("extra", lambda: Tag("x"))
    d.stages["work"] >> d.stages["extra"]
    d.remove("extra")
    assert "extra" not in flow.stages
    assert len(flow.edges) == 1
    assert d.stages["work"].factory is flow.stages["work"].factory
    d.stages["work"].batch(8)
    assert "batch_max" not in flow.stages["work"].annotations


def test_flow_disconnect_and_split_claim_release():
    from repro import CompositionError
    flow = Flow("d")
    a = flow.pellet("a", lambda: FnPellet(lambda x: x))
    b = flow.pellet("b", lambda: FnPellet(lambda x: x))
    a.split("hash") >> b
    flow.disconnect("a", "b")
    assert flow.edges == []
    # the group's split claim is released: a different policy is legal now
    a.split("round_robin") >> b
    with pytest.raises(CompositionError, match="no edge"):
        flow.disconnect("a", "b", src_port="nope")
