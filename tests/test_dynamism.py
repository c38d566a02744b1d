"""Application dynamism (paper §II.B): dynamic task + dataflow updates."""
import threading
import time

import pytest

from testkit import wait_until
from repro.core import (Coordinator, FloeGraph, FnPellet, Message, PullPellet,
                        PushPellet)


class V1(PushPellet):
    def compute(self, x):
        return ("v1", x)


class V2(PushPellet):
    def compute(self, x):
        return ("v2", x)


def test_sync_task_update_swaps_logic():
    g = FloeGraph("upd")
    g.add("p", V1)
    coord = Coordinator(g).start()
    try:
        coord.inject("p", 1)
        assert coord.run_until_quiescent(timeout=30)
        coord.update_pellet("p", V2, mode="sync")
        coord.inject("p", 2)
        assert coord.run_until_quiescent(timeout=30)
        out = [m.payload for m in coord.drain_outputs() if m.is_data()]
        assert out == [("v1", 1), ("v2", 2)]
        assert coord.flakes["p"].version == 1
    finally:
        coord.stop()


def test_sync_update_drains_inflight_first():
    """Synchronous update: messages being processed finish to completion and
    their outputs are delivered before the new pellet is instantiated."""
    release = threading.Event()

    class Slow(PushPellet):
        def compute(self, x):
            release.wait(timeout=10)
            return ("old", x)

    g = FloeGraph("upd2")
    g.add("p", Slow, cores=2)
    coord = Coordinator(g).start()
    try:
        for i in range(4):
            coord.inject("p", i)
        # let all 4 instances pick up their message and block on the gate
        assert wait_until(lambda: coord.flakes["p"]._inflight == 4)

        done = threading.Event()

        def do_update():
            coord.update_pellet("p", V2, mode="sync")
            done.set()

        t = threading.Thread(target=do_update, daemon=True)
        t.start()
        time.sleep(0.1)
        assert not done.is_set()  # update is blocked on the drain
        release.set()
        t.join(timeout=20)
        assert done.is_set()
        assert coord.run_until_quiescent(timeout=30)
        out = [m.payload for m in coord.drain_outputs() if m.is_data()]
        # every message processed exactly once; old before the swap
        assert sorted(out) == [("old", i) for i in range(4)]
    finally:
        release.set()
        coord.stop()


def test_async_update_zero_downtime_interleaves():
    """Asynchronous update: old in-flight instances run to completion while
    the new logic processes new messages — outputs may interleave."""
    gate = threading.Event()

    class SlowV1(PushPellet):
        def compute(self, x):
            gate.wait(timeout=10)
            return ("v1", x)

    g = FloeGraph("upd3")
    g.add("p", SlowV1, cores=2)
    coord = Coordinator(g).start()
    try:
        coord.inject("p", 0)
        # old instance now in flight, blocked on the gate
        assert wait_until(lambda: coord.flakes["p"]._inflight == 1)
        coord.update_pellet("p", V2, mode="async")  # returns immediately
        coord.inject("p", 1)
        # new logic processes msg 1 while the old instance is still blocked
        assert wait_until(lambda: any(m.payload == ("v2", 1)
                                      for m in coord.outputs))
        gate.set()
        assert coord.run_until_quiescent(timeout=30)
        out = {m.payload for m in coord.drain_outputs() if m.is_data()}
        assert out == {("v1", 0), ("v2", 1)}
    finally:
        gate.set()
        coord.stop()


def test_update_emits_update_landmark():
    g = FloeGraph("upd4")
    g.add("p", V1)
    g.add("sink", lambda: FnPellet(lambda x: x))
    g.connect("p", "sink")
    coord = Coordinator(g).start()
    try:
        coord.update_pellet("p", V2, mode="sync", emit_update_landmark=True)
        assert coord.run_until_quiescent(timeout=30)
        lms = [m for m in coord.drain_outputs() if m.update_landmark]
        assert lms and lms[0].payload["version"] == 1
    finally:
        coord.stop()


def test_update_rejects_port_mismatch():
    class TwoPort(PushPellet):
        out_ports = ("a", "b")

        def compute(self, x):
            return {"a": x}

    g = FloeGraph("upd5")
    g.add("p", V1)
    coord = Coordinator(g).start()
    try:
        with pytest.raises(ValueError, match="identical ports"):
            coord.update_pellet("p", TwoPort)
    finally:
        coord.stop()


def test_stateful_pellet_state_survives_update():
    """Internal state held by a stateful pellet survives the update (§II.B)."""
    class CounterA(PullPellet):
        def initial_state(self):
            return 0

        def compute(self, messages, emit, state):
            for m in messages:
                if m.is_data():
                    state += m.payload
                    emit(("a", state))
            return state

    class CounterB(CounterA):
        def compute(self, messages, emit, state):
            for m in messages:
                if m.is_data():
                    state += m.payload
                    emit(("b", state))
            return state

    g = FloeGraph("upd6")
    g.add("p", CounterA)
    coord = Coordinator(g).start()
    try:
        coord.inject("p", 5)
        assert coord.run_until_quiescent(timeout=30)
        coord.update_pellet("p", CounterB, mode="sync")
        coord.inject("p", 3)
        assert coord.run_until_quiescent(timeout=30)
        out = [m.payload for m in coord.drain_outputs() if m.is_data()]
        assert out == [("a", 5), ("b", 8)]  # 8 = state 5 survived + 3
    finally:
        coord.stop()


def test_pending_messages_survive_update():
    """Messages pending in input ports are retained for the new pellet."""
    g = FloeGraph("upd7")
    g.add("gate", lambda: FnPellet(lambda x: x, sequential=True))
    g.add("p", V1)
    g.connect("gate", "p")
    coord = Coordinator(g).start()
    try:
        coord.flakes["p"].pause()
        coord.inject("gate", 1)
        coord.inject("gate", 2)
        # messages flow through the gate and park in p's input queue
        assert wait_until(lambda: coord.flakes["p"].queue_length() == 2)
        coord.update_pellet("p", V2, mode="async")
        coord.flakes["p"].resume()
        assert coord.run_until_quiescent(timeout=30)
        out = [m.payload for m in coord.drain_outputs() if m.is_data()]
        assert sorted(out) == [("v2", 1), ("v2", 2)]
    finally:
        coord.stop()


def test_dynamic_dataflow_subgraph_update():
    """Coordinated multi-pellet swap (§II.B dynamic dataflow update)."""
    g = FloeGraph("sub")
    g.add("a", V1)
    g.add("b", V1)
    g.add("join", lambda: FnPellet(lambda x: x))
    g.connect("a", "join")
    g.connect("b", "join")
    coord = Coordinator(g).start()
    try:
        coord.inject("a", 1)
        coord.inject("b", 2)
        assert coord.run_until_quiescent(timeout=30)
        coord.update_subgraph({"a": V2, "b": V2}, mode="sync")
        coord.inject("a", 3)
        coord.inject("b", 4)
        assert coord.run_until_quiescent(timeout=30)
        out = [m.payload for m in coord.drain_outputs() if m.is_data()]
        assert sorted(out) == [("v1", 1), ("v1", 2), ("v2", 3), ("v2", 4)]
        assert coord.flakes["a"].version == 1
        assert coord.flakes["b"].version == 1
    finally:
        coord.stop()


def test_set_cores_runtime_resource_control():
    g = FloeGraph("cores")
    g.add("p", lambda: FnPellet(lambda x: x), cores=1)
    coord = Coordinator(g).start()
    try:
        assert coord.flakes["p"].cores == 1
        coord.set_cores("p", 4)
        assert coord.flakes["p"].cores == 4
        assert coord.flakes["p"]._sem.capacity == 16  # alpha = 4
        coord.inject("p", 1)
        assert coord.run_until_quiescent(timeout=30)
        assert [m.payload for m in coord.drain_outputs()] == [1]
    finally:
        coord.stop()


def test_speculative_execution_dedups():
    """Straggler mitigation: backup task fires; output delivered exactly once."""
    calls = []
    lock = threading.Lock()

    class Straggler(PushPellet):
        def compute(self, x):
            with lock:
                calls.append(x)
                first = calls.count(x) == 1
            if first and x == 0:
                time.sleep(0.25)  # straggle on the first attempt only
            return ("ok", x)

    g = FloeGraph("spec")
    g.add("p", Straggler, cores=2)
    coord = Coordinator(g, speculative_timeout=0.05).start()
    try:
        coord.inject("p", 0)
        coord.inject("p", 1)
        # the backup task fires after the speculative timeout
        assert wait_until(lambda: calls.count(0) >= 2, timeout=10)
        assert coord.run_until_quiescent(timeout=30)
        out = [m.payload for m in coord.drain_outputs() if m.is_data()]
        assert sorted(out) == [("ok", 0), ("ok", 1)]  # exactly once each
        assert calls.count(0) >= 2  # the backup task really ran
    finally:
        coord.stop()
