"""Cluster runtime: hosts, placement, transports, live migration, VM-level
elasticity (simulated-VM deployment of the §III container model).

The load-bearing scenarios: migration correctness under load (per-key FIFO,
zero loss/duplication by payload census, landmark/window alignment
surviving a mid-window move) and the paper's scale-out arc — one host,
injected backlog, strategy-driven acquire + migrate to a second host,
drain, consolidate home, release the idle VM.
"""
import pickle
import time

import pytest

from repro import (ClusterError, ClusterManager, ClusterSpec, CompositionError,
                   Coordinator, FloeGraph, Flow, FnPellet, PullPellet,
                   SessionStateError, WindowPellet)
from repro.adaptation import AdaptationController, DynamicAdaptation
from repro.cluster import LoopbackTransport, SerializingTransport

from testkit import wait_until


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def chain_flow(n=3, fn=None, sequential=False):
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
# spec + fleet basics
# ---------------------------------------------------------------------------

def test_spec_validation():
    with pytest.raises(ClusterError):
        ClusterSpec(hosts=0)
    with pytest.raises(ClusterError):
        ClusterSpec(hosts=2, max_hosts=1)
    with pytest.raises(ClusterError):
        ClusterSpec(placement="nope")
    with pytest.raises(ClusterError):
        ClusterSpec(transport="udp")
    with pytest.raises(ClusterError):
        ClusterSpec(spinup_s=-1)


def test_quota_and_release_rules():
    cm = ClusterManager(ClusterSpec(hosts=1, cores_per_host=4, max_hosts=2))
    h1 = cm.acquire_host()
    assert h1.elastic
    with pytest.raises(ClusterError):
        cm.acquire_host()                    # quota: 2 active
    cm.release_host(h1)
    assert h1.state == "released"
    cm.release_host(h1)                      # idempotent
    h2 = cm.acquire_host()                   # slot freed
    assert h2.name == "h2"


def test_spinup_latency_is_respected():
    cm = ClusterManager(ClusterSpec(hosts=1, cores_per_host=2, spinup_s=0.3))
    assert cm.hosts["h0"].is_ready           # initial fleet: ready at once
    t0 = time.time()
    h = cm.acquire_host()                    # elastic: pays spin-up
    assert not h.is_ready and h.state == "provisioning"
    h.wait_ready()
    assert time.time() - t0 >= 0.29 and h.is_ready
    with pytest.raises(TimeoutError):
        cm.acquire_host().wait_ready(timeout=0.01)


def test_release_refuses_occupied_host():
    flow, (a, b, c) = chain_flow()
    with flow.session(cluster=ClusterSpec(hosts=2, cores_per_host=8)) as s:
        host = s.cluster.host_of("p0")
        with pytest.raises(ClusterError):
            s.cluster.release_host(host)


# ---------------------------------------------------------------------------
# placement: policies + annotations
# ---------------------------------------------------------------------------

def test_bin_pack_vs_spread():
    g = FloeGraph("g")
    for i in range(4):
        g.add(f"p{i}", lambda: FnPellet(lambda x: x), cores=2)
    packed = ClusterManager(ClusterSpec(hosts=2, cores_per_host=8))
    packed.place_all(g, list(g.vertices))
    assert set(packed._placement.values()) == {"h0"}   # best fit packs
    spread = ClusterManager(ClusterSpec(hosts=2, cores_per_host=8,
                                        placement="spread"))
    spread.place_all(g, list(g.vertices))
    by_host = {}
    for f, h in spread._placement.items():
        by_host.setdefault(h, []).append(f)
    assert len(by_host) == 2 and all(len(v) == 2 for v in by_host.values())


def test_place_and_colocate_annotations():
    flow = Flow("placed")
    a = flow.pellet("a", lambda: FnPellet(lambda x: x)).place(host="h1")
    b = flow.pellet("b", lambda: FnPellet(lambda x: x)).place(
        colocate_with=a)
    c = flow.pellet("c", lambda: FnPellet(lambda x: x)).place(
        colocate_with="b")                   # chain resolves through b -> a
    a >> b >> c
    with flow.session(cluster=ClusterSpec(hosts=2, cores_per_host=8)) as s:
        assert s.describe()["cluster"]["placement"] == {
            "a": "h1", "b": "h1", "c": "h1"}


def test_place_validation_errors():
    flow = Flow("bad")
    a = flow.pellet("a", lambda: FnPellet(lambda x: x))
    with pytest.raises(CompositionError):
        a.place()                            # neither
    with pytest.raises(CompositionError):
        a.place(host="h0", colocate_with="a")  # both
    with pytest.raises(CompositionError):
        a.place(colocate_with="missing")
    with pytest.raises(CompositionError):
        a.place(colocate_with=a)
    other = Flow("other").pellet("x", lambda: FnPellet(lambda x: x))
    with pytest.raises(CompositionError):
        a.place(colocate_with=other)


def test_oversubscribe_fallback_recorded():
    g = FloeGraph("g")
    g.add("big", lambda: FnPellet(lambda x: x), cores=8)
    cm = ClusterManager(ClusterSpec(hosts=1, cores_per_host=2))
    cm.place_all(g, ["big"])
    assert any(e["event"] == "oversubscribe" for e in cm.events)
    assert cm.hosts["h0"].free_cores < 0     # honest accounting


# ---------------------------------------------------------------------------
# transports
# ---------------------------------------------------------------------------

def test_loopback_counts_cross_host_traffic_only():
    flow = Flow("x")
    a = flow.pellet("a", lambda: FnPellet(lambda x: x)).place(host="h0")
    b = flow.pellet("b", lambda: FnPellet(lambda x: x)).place(host="h1")
    c = flow.pellet("c", lambda: FnPellet(lambda x: x)).place(host="h1")
    a >> b >> c                              # a->b crosses, b->c is local
    with flow.session(cluster=ClusterSpec(hosts=2, cores_per_host=4)) as s:
        s.inject_many(a, list(range(50)))
        assert len(s.results()) == 50
        t = s.cluster.transport.stats
        assert t.messages == 50 and t.bytes == 0


def test_serializing_transport_roundtrips_payloads():
    flow = Flow("ser")
    a = flow.pellet("a", lambda: FnPellet(lambda x: x)).place(host="h0")
    b = flow.pellet("b", lambda: FnPellet(lambda x: x)).place(host="h1")
    a >> b
    spec = ClusterSpec(hosts=2, cores_per_host=4, transport="serializing")
    with flow.session(cluster=spec) as s:
        payload = {"k": [1, 2]}
        s.inject(a, payload)
        out = s.drain()
        got = [m.payload for m in out if m.is_data()][0]
        # equal but never the same object: no sharing across hosts
        assert got == payload and got is not payload
        assert got["k"] is not payload["k"]
        assert s.cluster.transport.stats.bytes > 0


def test_serializing_transport_enforces_picklability():
    flow = Flow("ser2")
    a = flow.pellet("a", lambda: FnPellet(lambda x: x)).place(host="h0")
    b = flow.pellet("b", lambda: FnPellet(lambda x: x)).place(host="h1")
    a >> b
    spec = ClusterSpec(hosts=2, cores_per_host=4, transport="serializing")
    with flow.session(cluster=spec) as s:
        s.inject(a, lambda: None)            # not picklable
        assert s.quiesce(10)                 # credits released, no wedge
        assert s.errors and s.errors[-1][0] == "a"
        assert isinstance(s.errors[-1][1], (pickle.PicklingError,
                                            AttributeError, TypeError))


def test_serializing_transport_models_delay():
    t = SerializingTransport(per_msg_delay_s=0.01, per_byte_delay_s=0.0)

    class Sink:
        def enqueue_many(self, port, msgs):
            self.got = msgs

    from repro.core.message import Message
    sink = Sink()
    t0 = time.time()
    t.deliver(sink, "in", [Message(payload=i) for i in range(3)])
    assert time.time() - t0 >= 0.03
    assert t.stats.modeled_delay_s >= 0.03 and t.stats.messages == 3


# ---------------------------------------------------------------------------
# live migration
# ---------------------------------------------------------------------------

def test_migrate_mid_stream_zero_loss_zero_dup():
    flow, (p0, p1, p2) = chain_flow(3, fn=lambda x: x)
    with flow.session(cluster=ClusterSpec(hosts=2, cores_per_host=8)) as s:
        n = 2000
        s.inject_many(p0, list(range(n)))
        src = s.cluster.host_of("p1").name
        s.migrate(p1, "h1" if src == "h0" else "h0")
        out = s.results()
        assert len(out) == n and len(set(out)) == n    # census: exact
        assert not s.errors


def test_migrate_under_load_preserves_per_key_fifo():
    seen = []
    flow = Flow("fifo")
    src = flow.pellet("src", lambda: FnPellet(lambda x: x, sequential=True))
    mid = flow.pellet("mid", lambda: FnPellet(lambda x: x, sequential=True))
    snk = flow.pellet("snk", lambda: FnPellet(
        lambda kv: (seen.append(kv), kv)[1], sequential=True))
    src >> mid >> snk
    keys, per_key = 4, 250
    with flow.session(cluster=ClusterSpec(hosts=2, cores_per_host=8)) as s:
        payloads = [(i % keys, i // keys) for i in range(keys * per_key)]
        s.inject_many(src, payloads, keys=[p[0] for p in payloads])
        s.migrate(mid, "h1" if s.cluster.host_of("mid").name == "h0"
                  else "h0")
        out = s.results()
        assert len(out) == keys * per_key and len(set(seen)) == len(seen)
        for k in range(keys):                # FIFO per key across the move
            ordered = [i for kk, i in seen if kk == k]
            assert ordered == sorted(ordered)


def test_migrate_carries_pull_pellet_state():
    class Counter(PullPellet):
        def compute(self, messages, emit, state):
            state = state or 0
            for m in messages:
                state += 1
                emit(state)
            return state

    flow = Flow("state")
    src = flow.pellet("src", lambda: FnPellet(lambda x: x, sequential=True))
    cnt = flow.pellet("cnt", Counter)
    src >> cnt
    with flow.session(cluster=ClusterSpec(hosts=2, cores_per_host=8)) as s:
        s.inject_many(src, list(range(10)))
        assert sorted(s.results()) == list(range(1, 11))
        s.migrate(cnt, "h1" if s.cluster.host_of("cnt").name == "h0"
                  else "h0")
        s.inject_many(src, list(range(5)))
        # the running count survives the move: 11..15, not 1..5
        assert sorted(s.results()) == list(range(11, 16))


def test_migrate_mid_window_keeps_partial_window():
    class SumWindow(WindowPellet):
        window = 5

        def compute(self, payloads):
            return sum(payloads)

    flow = Flow("win")
    src = flow.pellet("src", lambda: FnPellet(lambda x: x, sequential=True))
    win = flow.pellet("win", SumWindow)
    src >> win
    with flow.session(cluster=ClusterSpec(hosts=2, cores_per_host=8)) as s:
        for x in (1, 2, 3):
            s.inject(src, x)
        flake = s.coordinator.flakes["win"]
        assert wait_until(lambda: len(flake._window_buf) == 3
                          and flake.queue_length() == 0)
        s.migrate(win, "h1")
        s.inject(src, 4)
        s.inject(src, 5)                     # completes the window post-move
        assert s.results() == [15]
        # landmark flushes a partial window on the migrated flake
        s.inject(src, 7)
        s.inject_landmark(src)
        out = s.drain()
        assert [m.payload for m in out if m.is_data()] == [7]


def test_migrate_preserves_landmark_alignment_round():
    flow = Flow("align")
    s1 = flow.pellet("s1", lambda: FnPellet(lambda x: x, sequential=True))
    s2 = flow.pellet("s2", lambda: FnPellet(lambda x: x, sequential=True))
    mid = flow.pellet("mid", lambda: FnPellet(lambda x: x))
    s1 >> mid
    s2 >> mid                                # fan-in 2: landmarks align
    with flow.session(cluster=ClusterSpec(hosts=2, cores_per_host=8)) as s:
        s.inject_landmark(s1)                # first copy: swallowed
        assert s.quiesce(10)
        assert s.coordinator.flakes["mid"]._lm_count == 1
        s.migrate(mid, "h1")
        s.inject_landmark(s2)                # second copy completes the round
        out = s.drain()
        assert sum(1 for m in out if m.landmark) == 1


@pytest.mark.timeout(110)
def test_inject_racing_migration_loses_nothing():
    """Injection concurrent with repeated migrations: exact census, and
    the session still quiesces (no stranded inflight credits)."""
    import threading

    flow, (p0, p1, p2) = chain_flow(3, fn=lambda x: x)
    with flow.session(cluster=ClusterSpec(hosts=2, cores_per_host=8)) as s:
        n, chunks = 20_000, 200
        stop = threading.Event()

        def injector():
            for i in range(0, n, chunks):
                s.inject_many(p0, list(range(i, i + chunks)))

        t = threading.Thread(target=injector)
        t.start()
        for i in range(12):
            s.migrate(p1, "h1" if s.cluster.host_of("p1").name == "h0"
                      else "h0")
        t.join()
        stop.set()
        out = s.results(timeout=60)
        assert len(out) == n and len(set(out)) == n
        assert not s.errors


def test_prebuilt_manager_survives_session_close():
    """A prebuilt ClusterManager is reusable: placements clear on close,
    the fleet and its ledger survive."""
    cm = ClusterManager(ClusterSpec(hosts=2, cores_per_host=8))
    for round_ in range(2):
        flow, (p0, p1, p2) = chain_flow(3, fn=lambda x: x + 1)
        with flow.session(cluster=cm) as s:
            s.inject_many(p0, list(range(10)))
            assert sorted(s.results()) == [i + 3 for i in range(10)]
        assert cm._placement == {} and cm._coord is None
    assert len(cm.hosts) == 2                # same fleet both rounds
    # while a session is live, a second bind is refused
    flow2, _ = chain_flow(2)
    with flow2.session(cluster=cm):
        flow3, _ = chain_flow(2)
        with pytest.raises(ClusterError):
            flow3.session(cluster=cm).open()


def test_release_host_refuses_pending_scaleout_target():
    cm = ClusterManager(ClusterSpec(hosts=1, cores_per_host=4, max_hosts=2))
    h = cm.acquire_host()
    cm._pending["work"] = h.name             # scale-out awaiting spin-up
    with pytest.raises(ClusterError):
        cm.release_host(h)
    cm._pending.clear()
    cm.release_host(h)                       # releasable once cancelled


def test_migrate_requires_cluster_and_known_host():
    flow, (p0, p1, p2) = chain_flow()
    with flow.session() as s:
        with pytest.raises(SessionStateError):
            s.migrate(p1, "h1")
    g = Flow("g")
    a = g.pellet("a", lambda: FnPellet(lambda x: x))
    with g.session(cluster=ClusterSpec(hosts=1, cores_per_host=4)) as s:
        with pytest.raises(ClusterError):
            s.migrate(a, "h9")


# ---------------------------------------------------------------------------
# core accounting: release-on-deactivate / release-on-migrate audit
# ---------------------------------------------------------------------------

def test_cores_released_on_session_close_legacy_and_cluster():
    flow, _ = chain_flow()
    s = flow.session()
    s.open()
    coord = s.coordinator
    assert coord.core_audit()                # allocations live while running
    s.close()
    assert coord.core_audit() == {}          # all returned on deactivate

    flow2, _ = chain_flow()
    s2 = flow2.session(cluster=ClusterSpec(hosts=2, cores_per_host=8))
    s2.open()
    coord2 = s2.coordinator
    s2.close()
    assert coord2.core_audit() == {}


def test_migrate_moves_core_accounting():
    flow, (p0, p1, p2) = chain_flow()
    with flow.session(cluster=ClusterSpec(hosts=2, cores_per_host=8)) as s:
        src = s.cluster.host_of("p1")
        dst = s.cluster.hosts["h1" if src.name == "h0" else "h0"]
        s.migrate(p1, dst.name, cores=3)
        assert "p1" not in src.container.allocated
        assert dst.container.allocated["p1"] == 3
        assert s.cores(p1) == 3
        assert not s.errors                  # no accounting-drift error


def test_cluster_scale_is_bounded_by_host():
    flow = Flow("bounded")
    a = flow.pellet("a", lambda: FnPellet(lambda x: x))
    with flow.session(cluster=ClusterSpec(hosts=1, cores_per_host=4)) as s:
        s.scale(a, cores=16)                 # intra-VM resize: capped
        assert s.cores(a) == 4
        assert s.cluster.hosts["h0"].free_cores == 0
        s.scale(a, cores=1)
        assert s.cluster.hosts["h0"].free_cores == 3


# ---------------------------------------------------------------------------
# observation plumbing (batch occupancy -> adaptation layer)
# ---------------------------------------------------------------------------

def test_observation_carries_batch_occupancy():
    flow = Flow("obs")
    src = flow.pellet("src", lambda: FnPellet(lambda x: x, sequential=True))
    work = flow.pellet("work", lambda: FnPellet(lambda x: x)).batch(64)
    src >> work
    with flow.session() as s:
        ctrl = AdaptationController(s.coordinator,
                                    {"work": DynamicAdaptation()})
        s.inject_many(src, list(range(2000)))
        assert len(s.results()) == 2000
        ctrl.step_once()
        obs = ctrl.history[-1][2]
        assert obs.last_batch >= 1
        assert obs.avg_batch > 0.0
        st = s.stats()["work"]
        assert st["avg_batch"] > 0.0 and st["last_batch"] >= 1


def test_inject_many_validates_keys():
    flow, (p0, p1, p2) = chain_flow()
    with flow.session() as s:
        with pytest.raises(ValueError):
            s.inject_many(p0, [1, 2, 3], keys=[1])


# ---------------------------------------------------------------------------
# the scripted scale-out scenario (acceptance)
# ---------------------------------------------------------------------------

@pytest.mark.timeout(110)
def test_scaleout_scenario_end_to_end():
    """1 host -> backlog -> strategy acquires + migrates to a 2nd host ->
    drain with exact census -> consolidate home -> idle host released."""
    def busy(x):
        time.sleep(0.001)
        return x

    flow = Flow("scenario")
    gen = flow.pellet("gen", lambda: FnPellet(lambda x: x, sequential=True))
    work = flow.pellet("work", lambda: FnPellet(busy), cores=1)
    snk = flow.pellet("snk", lambda: FnPellet(lambda x: x))
    gen >> work >> snk
    work.elastic(max_cores=8, drain_horizon=0.3)
    spec = ClusterSpec(hosts=1, cores_per_host=3, max_hosts=2,
                       spinup_s=0.05, idle_grace_s=0.1)
    n = 2000
    with flow.session(cluster=spec, sample_interval=0.02) as s:
        s.inject_many(gen, list(range(n)))
        # strategy-driven scale-out: a second VM is acquired and the hot
        # stage live-migrates onto it while traffic flows
        assert wait_until(
            lambda: s.cluster._placement.get("work") == "h1", timeout=60)
        assert s.cluster.hosts["h1"].elastic
        out = s.results(timeout=90)
        assert len(out) == n and len(set(out)) == n    # zero loss, zero dup
        assert not s.errors
        # burst over: consolidate home, release the idle VM
        assert wait_until(
            lambda: s.cluster.hosts["h1"].state == "released", timeout=30)
        assert s.cluster._placement["work"] == "h0"
        kinds = [e["event"] for e in s.cluster.events]
        assert kinds.count("acquire") >= 2 and "migrate" in kinds \
            and "release" in kinds
        assert s.cluster.host_seconds() > 0
        assert s.cluster.transport.stats.messages > 0  # edges crossed hosts
    # post-close: nothing leaked
    assert s._coord is None
