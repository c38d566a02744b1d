"""Process-backed worker hosts: real OS processes behind the cluster's
``Host`` objects, a pickle-5 control channel, and zero-copy ArrayBatch
transfer through shared-memory rings.

The load-bearing assertions:

* remote execution is REAL — a pellet observing ``os.getpid()`` sees the
  worker's pid, not the parent's;
* an ArrayBatch crossing a process-host edge pickles no array bytes
  (transport ledger: ``bytes == 0``, ``control_bytes > 0``,
  ``shm_bytes > 0``);
* ``backend="sim"`` (the default) is byte-for-byte unchanged — no worker,
  no remote runner;
* a killed worker process fails real liveness pings, so the fault plane's
  detection → ``host_failed`` → recovery arc works unmodified.

Every pellet function here is module-level: spawn workers re-import this
module to unpickle the shipped factories.
"""
import functools
import os
import time

import numpy as np
import pytest

from repro import ClusterError, ClusterSpec, Flow, FnPellet, RecoveryPolicy
from repro.cluster.backends import SimBackend, make_backend
from repro.cluster.manager import ClusterManager
from repro.cluster.workers import ProcessBackend, ShmRing
from repro.faults import CheckpointPolicy

from testkit import wait_until


# -- module-level pellet functions (spawn workers unpickle by reference) ----

def _double(x):
    return x * 2


def _plus_tag(x):
    return x + 1000


def _pid_of(x):
    return float(os.getpid())


def _vec(X):
    return X * 2.0 + 1.0


def _make_double():
    return FnPellet(_double)


def _make_plus():
    return FnPellet(_plus_tag)


def _make_pid():
    return FnPellet(_pid_of)


def _make_vec():
    return FnPellet(_vec, vectorized=True)


def _proc_spec(hosts=2, **kw):
    kw.setdefault("cores_per_host", 4)
    kw.setdefault("placement", "spread")
    return ClusterSpec(hosts=hosts, backend="process", **kw)


# -- spec / backend plumbing -------------------------------------------------

def test_spec_backend_validation():
    with pytest.raises(ClusterError):
        ClusterSpec(backend="nope")
    with pytest.raises(ClusterError):
        ClusterSpec(backend="process", shm_ring_bytes=16)
    # a process backend on the loopback default upgrades the transport so
    # cross-host edges get real (counted) serialization semantics
    assert ClusterSpec(backend="process").transport == "process"
    # the process wire needs a process on the other end
    with pytest.raises(ClusterError):
        ClusterSpec(backend="sim", transport="process")
    # explicit serializing transport is allowed with process hosts
    assert ClusterSpec(backend="process",
                       transport="serializing").backend == "process"


def test_make_backend_dispatch():
    assert isinstance(make_backend(ClusterSpec()), SimBackend)
    spec = ClusterSpec(backend="process")
    b = make_backend(spec)
    assert isinstance(b, ProcessBackend) and b.blocking_spinup
    b.shutdown()


def test_sim_default_unchanged():
    """No backend= → SimBackend: no workers, no remote runners."""
    flow = Flow("sim")
    a = flow.pellet("a", _make_double)
    with flow.session(cluster=ClusterSpec(hosts=2)) as s:
        mgr = s.coordinator.cluster
        assert isinstance(mgr.backend, SimBackend)
        assert all(h.worker is None for h in mgr.hosts.values())
        s.inject_many("a", [1, 2, 3])
        assert sorted(s.results(10)) == [2, 4, 6]
        assert all(f.remote is None
                   for f in s.coordinator.flakes.values())


# -- shm ring mechanics ------------------------------------------------------

def test_shm_ring_pack_and_map():
    ring = ShmRing(1 << 16)
    try:
        a = np.arange(12.0).reshape(3, 4)
        b = np.arange(5, dtype=np.int64)
        specs = ring.write([a, b])
        assert [s[2] for s in specs] == [0, a.nbytes]
        va = ring.view(specs[0])
        assert not va.flags.writeable            # zero-copy view
        np.testing.assert_array_equal(va, a)
        owned = ring.read(specs[1])
        np.testing.assert_array_equal(owned, b)
        assert owned.flags.writeable             # result copies are owned
        assert not ring.fits([np.zeros(1 << 14)])
        with pytest.raises(ValueError):
            ring.write([np.zeros(1 << 14)])
    finally:
        ring.close()


# -- end-to-end process compute ---------------------------------------------

@pytest.mark.timeout(120)
def test_process_chain_remote_execution():
    """Results are correct AND provably computed in the worker process."""
    flow = Flow("proc")
    a = flow.pellet("a", _make_double)
    b = flow.pellet("b", _make_double)
    a >> b
    with flow.session(cluster=_proc_spec(hosts=2)) as s:
        mgr = s.coordinator.cluster
        assert isinstance(mgr.backend, ProcessBackend)
        for h in mgr.hosts.values():
            assert h.worker is not None and h.worker.alive()
            assert h.worker.pid != os.getpid()
        s.inject_many("a", list(range(20)))
        assert sorted(s.results(30)) == [i * 4 for i in range(20)]
        d = mgr.describe()
        assert d["backend"]["backend"] == "process"
        assert d["transport"]["kind"] == "process"
        assert d["transport"]["messages"] > 0

    flow2 = Flow("pid")
    p = flow2.pellet("p", _make_pid)
    with flow2.session(cluster=_proc_spec(hosts=1)) as s:
        s.inject_many("p", [0, 1, 2])
        pids = {int(x) for x in s.results(30)}
        assert pids and all(pid != os.getpid() for pid in pids)


@pytest.mark.timeout(120)
def test_zero_copy_array_ledger():
    """The acceptance property: a vectorized chain on process hosts moves
    every array through the shm rings — the pickled-payload ledger stays
    at zero while control traffic and shm traffic are both nonzero."""
    flow = Flow("zc")
    a = flow.pellet("a", _make_vec).batch(64, array=True)
    b = flow.pellet("b", _make_vec).batch(64, array=True)
    a >> b
    with flow.session(cluster=_proc_spec(hosts=2)) as s:
        s.inject_many("a", [np.full(256, float(i)) for i in range(64)],
                      stacked=True)
        out = s.results(30)
        assert len(out) == 64
        got = sorted(float(np.asarray(r)[0]) for r in out)
        want = sorted(float(i) * 4.0 + 3.0 for i in range(64))
        np.testing.assert_allclose(got, want)
        st = s.coordinator.cluster.transport.stats
        assert st.bytes == 0, \
            f"array bytes were pickled: {st.describe()}"
        assert st.shm_bytes > 0 and st.control_bytes > 0


@pytest.mark.timeout(120)
def test_non_picklable_factory_falls_back_local():
    """A lambda factory can't cross the process boundary: the flake
    silently degrades to parent-local compute (counted), results exact."""
    flow = Flow("fb")
    a = flow.pellet("a", lambda: FnPellet(lambda x: x * 3))
    with flow.session(cluster=_proc_spec(hosts=1)) as s:
        s.inject_many("a", [1, 2, 3, 4])
        assert sorted(s.results(20)) == [3, 6, 9, 12]
        host = next(iter(s.coordinator.cluster.hosts.values()))
        assert host.worker.fallbacks >= 1
        assert host.worker.describe()["fallbacks"] >= 1


@pytest.mark.timeout(120)
def test_stateful_pellet_computes_in_parent():
    """Stateful pellets keep state where checkpoints live (the parent),
    regardless of process placement — offload eligibility excludes them."""
    flow = Flow("st")
    a = flow.pellet("a", _make_pid)
    with flow.session(cluster=_proc_spec(hosts=1)) as s:
        flake = s.coordinator.flakes["a"]
        assert flake.remote is not None

        class _Stateful:
            stateful = True
        assert not flake._remote_eligible(_Stateful())


@pytest.mark.timeout(180)
def test_worker_kill_is_host_failure_and_recovers():
    """SIGKILL the worker behind h1: Host.ping() now reports real process
    liveness, so the unmodified fault plane detects it, emits
    ``host_failed``, and recovery re-places the flake on the survivor —
    where it keeps computing (remotely, on the survivor's live worker)."""
    flow = Flow("rec")
    src = flow.pellet("src", _make_double).place(host="h0")
    mid = flow.pellet("mid", _make_plus).place(host="h1")
    src >> mid
    pol = RecoveryPolicy(
        checkpoint=CheckpointPolicy(interval_s=0.25, freeze_timeout_s=10.0),
        heartbeat_interval_s=0.05, suspicion_timeout_s=0.2,
        max_row_retries=4, restart_backoff_s=0.01)
    with flow.session(cluster=_proc_spec(hosts=2), recovery=pol) as s:
        s.inject_many("src", list(range(50)))
        s.results(timeout=30)

        victim = s.cluster.hosts["h1"].worker
        victim.kill()                      # real SIGKILL, no bookkeeping
        assert wait_until(lambda: not victim.alive(), timeout=10)
        assert wait_until(lambda: s.faults.recoveries, timeout=30), \
            "worker death was never detected/recovered"
        rec = s.faults.last_recovery
        assert rec["host"] == "h1" and "mid" in rec["flakes"]
        assert rec["placed"]["mid"] != "h1"
        assert any(e["kind"] == "host_failed" for e in s.events())

        # post-recovery wave: flows end-to-end on the surviving host
        wave2 = list(range(1000, 1040))
        s.inject_many("src", wave2)
        expect = {i * 2 + 1000 for i in wave2}
        got = set()

        def _drain():
            got.update(s.results(timeout=2))
            return expect <= got
        assert wait_until(_drain, timeout=60), \
            f"missing {sorted(expect - got)[:5]}"
        surv = s.cluster.hosts["h0"].worker
        assert surv is not None and surv.alive()


@pytest.mark.timeout(120)
def test_backend_shutdown_reaps_workers():
    mgr = ClusterManager(_proc_spec(hosts=2))
    workers = [h.worker for h in mgr.hosts.values()]
    assert all(w is not None for w in workers)
    for w in workers:
        w.wait_ready(60)
    pids = [w.pid for w in workers]
    mgr.shutdown()
    deadline = time.time() + 10
    while time.time() < deadline and any(w.proc.is_alive()
                                         for w in workers):
        time.sleep(0.05)
    assert all(not w.proc.is_alive() for w in workers), pids
    # idempotent
    mgr.shutdown()


def test_partial_factories_are_spawn_picklable():
    """The documented pattern for process hosts: module-level functions
    (optionally via functools.partial) ship; closures do not."""
    import pickle
    fac = functools.partial(FnPellet, _double)
    rebuilt = pickle.loads(pickle.dumps(fac, protocol=5))
    assert rebuilt().compute(21) == 42
    with pytest.raises(Exception):
        pickle.dumps(lambda: FnPellet(_double), protocol=5)
