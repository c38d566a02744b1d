"""Checkpoint/restart fault tolerance: train state, async snapshots, Floe
graph state + pending-message replay, elastic resume."""
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (AsyncCheckpointer, checkpoint_floe_graph,
                              restore, restore_floe_graph, save)
from repro.configs import registry
from repro.data import TokenPipeline
from repro.launch.steps import make_train_step
from repro.models import Model
from repro.optim import init_state
from testkit import wait_until


@pytest.fixture(scope="module")
def setup():
    cfg = registry.get("smollm-360m").scaled_down()
    step, model = make_train_step(cfg)
    jstep = jax.jit(step)
    pipe = TokenPipeline(cfg, global_batch=4, seq_len=16, seed=3)
    return cfg, model, jstep, pipe


def tree_equal(a, b):
    fa, fb = jax.tree.leaves(a), jax.tree.leaves(b)
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(fa, fb))


def test_save_restore_roundtrip(tmp_path, setup):
    cfg, model, jstep, pipe = setup
    state = init_state(model.init(jax.random.PRNGKey(0)))
    state, _ = jstep(state, pipe.batch_at(0))
    path = str(tmp_path / "ckpt")
    save(path, state, step=1)
    back = restore(path, like=state)
    assert tree_equal(state, back)


def test_restart_resumes_identical_training(tmp_path, setup):
    """Kill-and-restart equivalence: train 4 steps straight vs train 2,
    checkpoint, 'crash', restore, train 2 more — identical final state
    (deterministic data pipeline + saved optimizer state)."""
    cfg, model, jstep, pipe = setup
    s = init_state(model.init(jax.random.PRNGKey(0)))
    for i in range(4):
        s, _ = jstep(s, pipe.batch_at(i))
    straight = s

    s2 = init_state(model.init(jax.random.PRNGKey(0)))
    for i in range(2):
        s2, _ = jstep(s2, pipe.batch_at(i))
    save(str(tmp_path / "c2"), s2, step=2)
    del s2                                            # "crash"
    s3 = restore(str(tmp_path / "c2"), like=straight)
    for i in range(2, 4):
        s3, _ = jstep(s3, pipe.batch_at(i))
    assert tree_equal(straight, s3)


def test_async_checkpointer_retention(tmp_path, setup):
    cfg, model, jstep, pipe = setup
    state = init_state(model.init(jax.random.PRNGKey(0)))
    ck = AsyncCheckpointer(str(tmp_path / "root"), keep=2)
    for i in (1, 2, 3):
        ck.save_async(i, state)
    ck.wait()
    names = sorted(os.listdir(str(tmp_path / "root")))
    assert names == ["step_2", "step_3"]              # retention
    step, back = ck.restore_latest(like=state)
    assert step == 3 and tree_equal(state, back)


def test_floe_graph_checkpoint_replays_pending(tmp_path):
    from repro.core import Coordinator, FloeGraph, FnPellet, PullPellet

    class Summer(PullPellet):
        def initial_state(self):
            return 0

        def compute(self, messages, emit, state):
            for m in messages:
                if m.is_data():
                    state += m.payload
                    emit(state)
            return state

    g = FloeGraph("ck")
    g.add("sum", Summer)
    coord = Coordinator(g).start()
    try:
        coord.inject("sum", 10)
        coord.inject("sum", 5)
        assert coord.run_until_quiescent(timeout=30)
        # park two messages (pause = simulate failure with queued input)
        coord.flakes["sum"].pause()
        coord.inject("sum", 7)
        coord.inject("sum", 3)
        time.sleep(0.1)
        path = str(tmp_path / "floe.pkl")
        checkpoint_floe_graph(coord, path)
    finally:
        coord.stop()
    # "restart": a fresh engine restores state + replays pending messages
    g2 = FloeGraph("ck")
    g2.add("sum", Summer)
    coord2 = Coordinator(g2).start()
    try:
        restore_floe_graph(coord2, path)
        assert coord2.run_until_quiescent(timeout=30)
        assert coord2.flakes["sum"].state == 25       # 15 restored + 7 + 3
        out = [m.payload for m in coord2.drain_outputs()]
        assert sorted(out) == [22, 25]                # replayed execution
    finally:
        coord2.stop()


def test_elastic_resume_smaller_mesh(tmp_path, setup):
    """Node-failure handling: restore the same checkpoint into a training
    run configured for fewer replicas (divisor resize) — state restores and
    training proceeds (single-device stand-in for the re-mesh)."""
    cfg, model, jstep, pipe = setup
    s = init_state(model.init(jax.random.PRNGKey(0)))
    s, _ = jstep(s, pipe.batch_at(0))
    save(str(tmp_path / "c"), s, step=1)
    restored = restore(str(tmp_path / "c"), like=s)
    # half the replicas -> half the global batch, same step function
    small_pipe = TokenPipeline(cfg, global_batch=2, seq_len=16, seed=3)
    s2, metrics = jstep(restored, small_pipe.batch_at(1))
    assert np.isfinite(float(metrics["loss"]))


def test_checkpoint_captures_push_pellet_instance_state(tmp_path):
    """ROADMAP follow-up: mutable state a push pellet keeps on ``self``
    (outside the explicit state object) survives checkpoint/restore via
    the ``__floe_state__``/get_state hook."""
    from repro.api import Flow, Session
    from repro.core import PushPellet

    class Dedup(PushPellet):
        """Drops repeats — the seen-set is instance state."""
        __floe_state__ = ("seen",)
        sequential = True

        def __init__(self):
            self.seen = set()

        def compute(self, x):
            if x in self.seen:
                from repro.core import Drop
                return Drop
            self.seen.add(x)
            return x

    flow = Flow("ps")
    flow.pellet("d", Dedup)
    path = str(tmp_path / "floe.ckpt")
    with flow.session() as s:
        s.inject_many("d", [1, 2, 3, 2])
        assert sorted(s.results()) == [1, 2, 3]
        s.checkpoint(path)
    # restart: the fresh pellet instance must remember what it has seen
    with Session.restore(path, flow) as s2:
        proto = s2.coordinator.flakes["d"]._proto
        assert proto.seen == {1, 2, 3}
        s2.inject_many("d", [3, 4])
        assert s2.results() == [4]          # 3 still deduped post-restore


def test_frozen_cut_waits_for_a_popped_message(monkeypatch):
    """A consistent cut holds each message once: in a channel, or in the
    state of the pellet that ran it.  The dispatcher here lingers after
    each pop, so a drain that counted only running tasks would cut between
    the pop and the run and lose the popped messages."""
    from repro.api import Flow
    from repro.core import PushPellet
    from repro.core.engine import Flake

    class Count(PushPellet):
        __floe_state__ = ("n",)
        sequential = True

        def __init__(self):
            self.n = 0

        def compute(self, x):
            self.n += 1
            return x

    collect = Flake._collect

    def lingering(flake):
        work = collect(flake)
        if work is not None and flake.name == "count":
            time.sleep(0.02)
        return work

    monkeypatch.setattr(Flake, "_collect", lingering)
    flow = Flow("cut")
    flow.pellet("count", Count).batch(1)     # one message a pop
    with flow.session() as s:
        s.inject_many("count", list(range(30)))
        flake = s.coordinator.flakes["count"]
        cuts = []
        for _ in range(5):
            time.sleep(0.03)
            with s.coordinator.frozen():
                cuts.append(flake._proto.n + sum(
                    len(c) for c in flake.inputs.values()))
        assert sorted(s.results(timeout=30)) == list(range(30))
    assert cuts == [30] * 5


def test_frozen_cut_of_a_flake_scaled_to_no_cores():
    """A dispatcher waiting for an instance slot holds a popped message
    that the cut must include.  Scaled to no cores, the flake has no slot
    to wait for: the message runs inline at once, and the cut does not
    wait out the slot's timeout."""
    from repro.api import Flow
    from repro.core import FnPellet

    def slow(x):
        time.sleep(0.2)
        return x

    flow = Flow("scaled")
    flow.pellet("work", lambda: FnPellet(slow), cores=1).batch(1)
    with flow.session() as s:
        s.inject_many("work", list(range(12)))
        flake = s.coordinator.flakes["work"]
        # every slot busy: the dispatcher waits on the next popped message
        assert wait_until(lambda: flake._sem.free == 0, timeout=10)
        t0 = time.time()
        s.coordinator.set_cores("work", 0)
        with s.coordinator.frozen(timeout=10):
            held = len(flake.inputs["in"])
        assert time.time() - t0 < 5
        s.coordinator.set_cores("work", 1)
        out = s.results(timeout=30)
    assert sorted(out) == list(range(12)) and held < 12


def test_checkpoint_custom_get_state_override(tmp_path):
    """Pellets can override get_state/set_state directly (no attr list)."""
    from repro.api import Flow, Session
    from repro.core import PushPellet

    class Counter(PushPellet):
        sequential = True

        def __init__(self):
            self.count = 0

        def compute(self, x):
            self.count += 1
            return (self.count, x)

        def get_state(self):
            return self.count

        def set_state(self, snapshot):
            self.count = snapshot

    flow = Flow("cnt")
    flow.pellet("c", Counter)
    path = str(tmp_path / "floe.ckpt")
    with flow.session() as s:
        s.inject_many("c", ["a", "b"])
        assert sorted(s.results()) == [(1, "a"), (2, "b")]
        s.checkpoint(path)
    with Session.restore(path, flow) as s2:
        s2.inject("c", "c")
        assert s2.results() == [(3, "c")]   # numbering continues


# -- atomic write + corruption detection --------------------------------------

def _simple_flow():
    from repro.api import Flow
    from repro.core import FnPellet
    flow = Flow("atomic")
    flow.pellet("id", lambda: FnPellet(lambda x: x))
    return flow


def test_checkpoint_write_is_atomic_no_tmp_left(tmp_path):
    flow = _simple_flow()
    path = str(tmp_path / "cut.floe")
    with flow.session() as s:
        s.inject("id", 1)
        s.results()
        s.checkpoint(path)
    assert os.path.exists(path)
    # the temp file used for the atomic rename must not survive
    assert [n for n in os.listdir(tmp_path) if n.endswith(".tmp")] == []


def test_restore_truncated_checkpoint_raises(tmp_path):
    """Regression: a checkpoint truncated mid-write (crash during save
    before atomic rename existed) must fail loudly, not unpickle garbage
    or silently restore a partial graph."""
    from repro.api import Session
    from repro.checkpoint import CheckpointCorruptError

    flow = _simple_flow()
    path = str(tmp_path / "cut.floe")
    with flow.session() as s:
        s.inject_many("id", list(range(100)))
        s.results()
        s.checkpoint(path)
    data = open(path, "rb").read()
    for cut in (len(data) // 2, 10, 3):     # payload, header, magic
        open(path, "wb").write(data[:cut])
        with pytest.raises(CheckpointCorruptError):
            Session.restore(path, _simple_flow())


def test_restore_corrupted_byte_fails_checksum(tmp_path):
    from repro.api import Session
    from repro.checkpoint import CheckpointCorruptError

    flow = _simple_flow()
    path = str(tmp_path / "cut.floe")
    with flow.session() as s:
        s.checkpoint(path)
    data = bytearray(open(path, "rb").read())
    data[-1] ^= 0xFF                        # flip one payload byte
    open(path, "wb").write(bytes(data))
    with pytest.raises(CheckpointCorruptError):
        Session.restore(path, _simple_flow())


def test_restore_reads_legacy_raw_pickle(tmp_path):
    """Pre-manifest checkpoints (raw pickle, no FLOECKPT header) still
    restore."""
    import pickle

    from repro.checkpoint import read_floe_meta
    from repro.checkpoint.checkpointer import _read_floe_state

    flow = _simple_flow()
    path = str(tmp_path / "cut.floe")
    with flow.session() as s:
        s.checkpoint(path)
    state = _read_floe_state(path)
    legacy = str(tmp_path / "legacy.pkl")
    with open(legacy, "wb") as f:
        pickle.dump(state, f)
    assert read_floe_meta(legacy)["flow"] == read_floe_meta(path)["flow"]
