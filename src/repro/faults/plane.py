"""The fault-tolerance plane: detection, recovery, and row-level safety.

One :class:`FaultPlane` rides inside a Coordinator started with
``recovery=RecoveryPolicy(...)``.  It runs two daemon threads:

* a **supervisor** — collects per-flake heartbeats (one timestamp store
  per dispatch-loop iteration) and per-host liveness pings, emits
  ``flake_suspected`` / ``flake_failed`` / ``host_failed`` events,
  restarts crashed pellets (exponential backoff, max-restarts
  quarantine), revives dead dispatch threads, and drives full host
  recovery;
* an **auto-checkpointer** — a periodic consistent cut
  (``Coordinator.frozen`` + atomic ``checkpoint_floe_graph``) with
  retention, paired with a **source journal** of every row injected
  since the last cut.

Host recovery is a *global rollback*: respawn the lost flakes on
surviving (or newly-acquired) hosts, restore the WHOLE graph from the
latest cut, then replay the journal suffix.  Restoring only the dead
flakes would silently lose rows that crossed a surviving stage after
the cut and were parked in a dead channel at crash time; rolling the
survivors back too converts that loss into duplicates, which
at-least-once delivery permits and :func:`repro.faults.census` counts.

Row-level safety is independent of checkpoints: a row whose compute
raises is redelivered up to ``max_row_retries`` times, then moved to
the dead-letter queue; a stage that crashes (:class:`PelletCrashError`)
past its restart budget is quarantined — it keeps running, but its
failing rows go straight to the DLQ, so one poison pill cannot take the
healthy part of the stream down with it.
"""
from __future__ import annotations

import os
import shutil
import tempfile
import threading
import time
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional

from ..checkpoint.checkpointer import (CheckpointCorruptError,
                                       checkpoint_floe_graph,
                                       restore_floe_graph)
from ..cluster.host import ClusterError
from ..core.engine import Flake, _rows_of
from ..core.message import Message, landmark
from .policy import (DeadLetter, DeadLetterQueue, PelletCrashError,
                     RecoveryPolicy)


class FaultPlane:
    """Failure detection + automatic recovery for one Coordinator."""

    def __init__(self, coord, policy: RecoveryPolicy):
        self.coord = coord
        self.policy = policy
        self.dead_letters = DeadLetterQueue(policy.dead_letter_capacity)
        #: rows injected since the last checkpoint cut, appended under
        #: ``coord._inject_lock`` (the same lock ``frozen()`` holds while
        #: the cut is taken, so cut and truncation are atomic)
        self._journal: List[tuple] = []
        self.journal_overflow = False
        #: per-stage crash/restart bookkeeping
        self._restarts: Dict[str, int] = {}
        self.quarantined: set = set()
        self._restart_pending: set = set()
        self._suspected: set = set()
        #: per-row retry attempts keyed by message seq (bounded LRU)
        self._attempts: "OrderedDict[int, int]" = OrderedDict()
        self._alock = threading.Lock()
        #: restart work queued from pool threads, executed by the
        #: supervisor (a synchronous restart from inside a pool task
        #: would deadlock on its own pool's shutdown)
        self._actions: deque = deque()
        self._kick = threading.Event()
        self._stop_evt = threading.Event()
        self._threads: List[threading.Thread] = []
        #: checkpoint state
        self._ckpt_epoch = 0
        self.checkpoint_path: Optional[str] = None
        self._ckpt_dir: Optional[str] = None
        self._own_ckpt_dir = False
        #: host liveness
        self._host_last_ok: Dict[str, float] = {}
        self._host_declared: set = set()
        self.recoveries: List[Dict[str, Any]] = []
        self.last_recovery: Optional[Dict[str, Any]] = None
        tele = coord.telemetry
        if tele.enabled:
            r = tele.registry
            self._m_failures = r.counter(
                "floe_failures_total",
                "Detected failures by kind (host/flake/pellet).", ("kind",))
            self._m_recoveries = r.counter(
                "floe_recoveries_total", "Completed host recoveries.")
            self._m_recovery_s = r.histogram(
                "floe_recovery_seconds",
                "Failure-declaration-to-recovered wall time.")
            self._m_restarts = r.counter(
                "floe_stage_restarts_total",
                "Crash restarts per stage.", ("stage",))
            self._m_retries = r.counter(
                "floe_row_retries_total",
                "Row redeliveries after compute errors.", ("stage",))
            self._m_dead = r.counter(
                "floe_dead_letters_total", "Rows dead-lettered.", ("stage",))
            self._m_ckpts = r.counter(
                "floe_checkpoints_total",
                "Background checkpoints written.")
        else:
            self._m_failures = self._m_recoveries = self._m_recovery_s = None
            self._m_restarts = self._m_retries = self._m_dead = None
            self._m_ckpts = None

    def _emit(self, kind: str, **detail: Any) -> None:
        tele = self.coord.telemetry
        if tele.enabled:
            tele.events.emit(kind, **detail)

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> "FaultPlane":
        cp = self.policy.checkpoint
        if cp is not None:
            self._ckpt_dir = cp.dir
            if self._ckpt_dir is None:
                self._ckpt_dir = tempfile.mkdtemp(prefix="floe-ckpt-")
                self._own_ckpt_dir = True
            else:
                os.makedirs(self._ckpt_dir, exist_ok=True)
            t = threading.Thread(target=self._ckpt_loop,
                                 name="floe-ckpt", daemon=True)
            self._threads.append(t)
            t.start()
        t = threading.Thread(target=self._supervise,
                             name="floe-supervisor", daemon=True)
        self._threads.append(t)
        t.start()
        return self

    def stop(self) -> None:
        self._stop_evt.set()
        self._kick.set()
        for t in self._threads:
            t.join(timeout=10)
        self._threads = []
        if self._own_ckpt_dir and self._ckpt_dir is not None:
            shutil.rmtree(self._ckpt_dir, ignore_errors=True)
            self._ckpt_dir = None
            self.checkpoint_path = None

    # -- source journal -----------------------------------------------------
    def journal_rows(self, flake_name: str, port: str,
                     payloads, keys=None) -> None:
        """Record injected rows for post-cut replay.  The caller holds
        ``coord._inject_lock`` (all Coordinator.inject* paths do)."""
        if not self.policy.journal:
            return
        j = self._journal
        if keys is None:
            for p in payloads:
                j.append(("data", flake_name, port, p, None))
        else:
            for p, k in zip(payloads, keys):
                j.append(("data", flake_name, port, p, k))
        limit = self.policy.journal_limit
        if len(j) > limit:
            del j[:len(j) - limit]
            if not self.journal_overflow:
                self.journal_overflow = True
                self._emit("journal_overflow", limit=limit)

    def journal_landmark(self, flake_name: str, port: str, tag) -> None:
        if self.policy.journal:
            self._journal.append(("lm", flake_name, port, tag))

    def _replay_journal(self) -> int:
        """Re-enqueue the journal suffix (caller holds the inject lock).
        Replayed rows bypass injection telemetry — they are not new."""
        coord = self.coord
        n = 0
        for entry in self._journal:
            flake = coord.flakes.get(entry[1])
            if flake is None:
                continue
            try:
                if entry[0] == "data":
                    flake.enqueue(entry[2],
                                  Message(payload=entry[3], key=entry[4]))
                else:
                    flake.enqueue(entry[2], landmark(entry[3]))
                n += 1
            except Exception as e:
                coord._record_error(entry[1], e)
        return n

    # -- periodic checkpoints -----------------------------------------------
    def _ckpt_loop(self) -> None:
        cp = self.policy.checkpoint
        while not self._stop_evt.wait(cp.interval_s):
            try:
                self.checkpoint_now()
            except Exception as e:
                self.coord._record_error("__faults__", e)

    def checkpoint_now(self) -> Optional[str]:
        """Take one consistent cut now (also truncates the journal —
        everything injected so far is in the cut).  Returns the written
        path, or None when the graph could not freeze in time (skipped,
        not fatal: the next interval retries)."""
        cp = self.policy.checkpoint
        if cp is None or self._ckpt_dir is None:
            raise RuntimeError("recovery policy has no CheckpointPolicy")
        coord = self.coord
        path = os.path.join(self._ckpt_dir,
                            f"cut_{self._ckpt_epoch + 1:06d}.floe")
        try:
            with coord.frozen(timeout=cp.freeze_timeout_s):
                checkpoint_floe_graph(
                    coord, path,
                    extra={"epoch": self._ckpt_epoch + 1, "reason": "auto"})
                if self.policy.journal:
                    del self._journal[:]
        except TimeoutError:
            self._emit("checkpoint_skipped", reason="freeze-timeout")
            return None
        self._ckpt_epoch += 1
        self.checkpoint_path = path
        if self._m_ckpts is not None:
            self._m_ckpts.inc()
        self._emit("checkpoint", path=path, epoch=self._ckpt_epoch)
        self._prune_checkpoints()
        return path

    def _prune_checkpoints(self) -> None:
        cp = self.policy.checkpoint
        try:
            cuts = sorted(n for n in os.listdir(self._ckpt_dir)
                          if n.startswith("cut_") and n.endswith(".floe"))
        except OSError:
            return
        for name in cuts[:-cp.keep]:
            try:
                os.remove(os.path.join(self._ckpt_dir, name))
            except OSError:
                pass

    # -- row-level error handling (engine hooks) ----------------------------
    def on_row_error(self, flake, msg: Message, exc: Exception,
                     port: Optional[str] = None) -> bool:
        """One failed row (BatchItemError path).  Returns True when the
        plane took ownership (retry or dead-letter); the engine then
        skips its drop-and-record default."""
        stage = flake.name
        if isinstance(exc, PelletCrashError):
            self._note_crash(stage, exc)
        with self._alock:
            n = self._attempts.get(msg.seq, 0) + 1
            self._attempts[msg.seq] = n
            while len(self._attempts) > 8192:
                self._attempts.popitem(last=False)
        if (stage not in self.quarantined and flake.inputs
                and n <= self.policy.max_row_retries):
            if port is None or port not in flake.inputs:
                port = next(iter(flake.inputs))
            try:
                # the SAME message object goes back: seq-keyed attempt
                # counting stays coherent across redeliveries
                flake.enqueue(port, msg)
            except Exception:
                self._dead_letter(stage, port, msg, exc, n)
                return True
            if self._m_retries is not None:
                self._m_retries.labels(stage=stage).inc()
            return True
        self._dead_letter(stage, port, msg, exc, n)
        return True

    def on_task_error(self, flake, kind: str, item, exc: Exception) -> bool:
        """A whole dispatched unit raised out of compute.  Decompose it
        into rows and run each through the retry/DLQ ladder."""
        if kind == "msg":
            return self.on_row_error(flake, item, exc)
        if kind in ("batch", "window"):
            for m in item:
                self.on_row_error(flake, m, exc)
            return True
        if kind == "abatch":
            for m in item.payload.to_messages(port=item.port):
                self.on_row_error(flake, m, exc)
            return True
        if kind == "tuple":
            for port, m in item.items():
                self.on_row_error(flake, m, exc, port=port)
            return True
        if kind == "pull":
            # pull consumption is destructive (source-side state already
            # advanced); redelivery would re-run source logic — dead-letter
            for m in item:
                self._dead_letter(flake.name, None, m, exc, 1)
            return True
        return False

    def _dead_letter(self, stage: str, port: Optional[str],
                     msg: Message, exc: Exception, attempts: int) -> None:
        self.dead_letters.append(DeadLetter(
            stage=stage, port=port, payload=msg.payload, key=msg.key,
            seq=msg.seq, error=repr(exc), attempts=attempts,
            t=time.time()))
        with self._alock:
            self._attempts.pop(msg.seq, None)
        if self._m_dead is not None:
            self._m_dead.labels(stage=stage).inc()
        self._emit("dead_letter", stage=stage, seq=msg.seq,
                   error=repr(exc), attempts=attempts)

    # -- pellet crash restarts ----------------------------------------------
    def _note_crash(self, stage: str, exc: Exception) -> None:
        if self._m_failures is not None:
            self._m_failures.labels(kind="pellet").inc()
        with self._alock:
            if stage in self.quarantined:
                return
            self._restarts[stage] = n = self._restarts.get(stage, 0) + 1
            if n > self.policy.max_restarts:
                self.quarantined.add(stage)
                quarantined = True
            else:
                quarantined = False
                if stage in self._restart_pending:
                    return
                self._restart_pending.add(stage)
        if quarantined:
            # circuit-breaker, not a kill: the stage keeps running so
            # healthy rows still flow; failing rows shortcut to the DLQ
            self._emit("flake_quarantined", stage=stage,
                       restarts=self.policy.max_restarts)
            return
        self._emit("flake_failed", stage=stage, cause="pellet_crash",
                   error=repr(exc), restart=n)
        self._actions.append(("restart", stage, n))
        self._kick.set()

    def _do_restart(self, stage: str, count: int) -> None:
        coord = self.coord
        flake = coord.flakes.get(stage)
        backoff = self.policy.restart_backoff_s * (2 ** (count - 1))
        try:
            if flake is None or self._stop_evt.is_set():
                return
            flake.pause()
            try:
                if backoff > 0:
                    self._stop_evt.wait(backoff)
                with flake._pellet_lock:
                    old = flake._proto
                    # crash semantics: a FRESH pellet instance (in-memory
                    # instance state is what the crash destroyed; durable
                    # state comes back from the checkpoint plane)
                    flake._proto = flake._bind(flake.factory())
                    flake.version += 1
                try:
                    old.teardown()
                except Exception:
                    pass
            finally:
                flake.resume()
        finally:
            with self._alock:
                self._restart_pending.discard(stage)
        if self._m_restarts is not None:
            self._m_restarts.labels(stage=stage).inc()
        self._emit("flake_restarted", stage=stage, restarts=count,
                   backoff_s=round(backoff, 6))

    # -- supervisor ----------------------------------------------------------
    def _supervise(self) -> None:
        p = self.policy
        while not self._stop_evt.is_set():
            self._kick.wait(timeout=p.heartbeat_interval_s)
            self._kick.clear()
            if self._stop_evt.is_set():
                return
            try:
                while self._actions:
                    action = self._actions.popleft()
                    if action[0] == "restart":
                        self._do_restart(action[1], action[2])
                self._scan_flakes()
                self._scan_hosts()
            except Exception as e:
                self.coord._record_error("__faults__", e)

    def _scan_flakes(self) -> None:
        now = time.time()
        timeout = self.policy.suspicion_timeout_s
        for flake in list(self.coord.flakes.values()):
            if flake._stop.is_set():
                continue
            thread = flake._thread
            if thread is None:
                continue
            if not thread.is_alive():
                # the dispatch thread died (a bug escaped the loop):
                # that is a positive failure, not a suspicion — revive it
                self._emit("flake_failed", stage=flake.name,
                           cause="dispatch_thread")
                if self._m_failures is not None:
                    self._m_failures.labels(kind="flake").inc()
                flake.heartbeat = time.time()
                t = threading.Thread(target=flake._dispatch_loop,
                                     name=f"dispatch-{flake.name}",
                                     daemon=True)
                flake._thread = t
                t.start()
                if self._m_restarts is not None:
                    self._m_restarts.labels(stage=flake.name).inc()
                self._emit("flake_restarted", stage=flake.name,
                           cause="dispatch_thread")
                continue
            hb = flake.heartbeat
            if hb and now - hb > timeout:
                # alive but not looping — likely stuck in a long inline
                # compute.  Suspicion only (killing a live thread on a
                # timer would be the false-positive failure mode).
                if flake.name not in self._suspected:
                    self._suspected.add(flake.name)
                    self._emit("flake_suspected", stage=flake.name,
                               stale_s=round(now - hb, 3))
            else:
                self._suspected.discard(flake.name)

    def _scan_hosts(self) -> None:
        cluster = self.coord.cluster
        if cluster is None:
            return
        now = time.time()
        for host in list(cluster.hosts.values()):
            if host.released_at is not None:
                self._host_last_ok.pop(host.name, None)
                continue
            if host.ping():
                self._host_last_ok[host.name] = now
                continue
            if host.name in self._host_declared:
                continue
            last_ok = self._host_last_ok.setdefault(host.name, now)
            if now - last_ok >= self.policy.suspicion_timeout_s:
                self._host_declared.add(host.name)
                if self._m_failures is not None:
                    self._m_failures.labels(kind="host").inc()
                self._emit("host_failed", host=host.name)
                try:
                    self._recover_host(host, t_detect=now)
                except Exception as e:
                    self.coord._record_error("__faults__", e)
                    self._emit("recovery_failed", host=host.name,
                               error=repr(e))

    # -- host recovery --------------------------------------------------------
    def _pick_host(self, cluster, cores: int):
        """Respawn target: best-fit surviving host, else acquire a fresh
        VM (paying spin-up), else oversubscribe the least-loaded."""
        ready = [h for h in cluster.active_hosts() if h.is_ready]
        fitting = [h for h in ready if h.free_cores >= cores]
        if fitting:
            return min(fitting, key=lambda h: h.free_cores)
        try:
            host = cluster.acquire_host()
            host.wait_ready()
            return host
        except ClusterError:
            if ready:
                return max(ready, key=lambda h: h.free_cores)
            raise

    def _recover_host(self, host, t_detect: float) -> None:
        coord = self.coord
        cluster = coord.cluster
        p = self.policy
        full_rollback = p.journal and not self.journal_overflow
        with coord._wiring_lock:
            placement = cluster.placement()
            dead = sorted(n for n, h in placement.items()
                          if h == host.name and n in coord.flakes)
            if not dead:
                for f, h in list(placement.items()):
                    if h == host.name:
                        cluster.unplace(f, release_cores=True)
                try:
                    cluster.release_host(host)
                except ClusterError:
                    pass
                return
            dead_flakes = [coord.flakes[n] for n in dead]
            live = [f for n, f in coord.flakes.items() if n not in dead]
            # 1. the dead VM's flakes stop now (no drain: process death)
            for f in dead_flakes:
                f._stop.set()
                f._notify()
            # 2. pause survivors; their in-flight work runs to completion
            for f in live:
                f._drain_acquire()
            try:
                deadline = time.time() + p.recovery_quiesce_timeout_s
                for f in live:
                    f._wait_quiescent(
                        timeout=max(0.0, deadline - time.time()))
                # 3. join the dead flakes' pools — after this, nothing
                #    delivers from the dead VM anymore
                for f in dead_flakes:
                    try:
                        f.deactivate()
                    except Exception:
                        pass
                replaced: Dict[str, str] = {}
                discarded = 0
                with coord._inject_lock:
                    # 4. discard parked rows and release their quiescence
                    #    credits (the rollback regenerates the rows; the
                    #    credits would otherwise wedge run_until_quiescent
                    #    forever).  With a journaled rollback the
                    #    survivors' backlogs are discarded too — the cut +
                    #    journal regenerate them, with fewer duplicates
                    #    than replaying on top of the live backlog.
                    discard_from = (dead_flakes + live if full_rollback
                                    else dead_flakes)
                    for f in discard_from:
                        for ch in f.inputs.values():
                            discarded += sum(_rows_of(m)
                                             for m in ch.pop_up_to(None))
                        discarded += len(f._window_buf)
                        f._window_buf = []
                    if discarded:
                        coord._inflight_dec(discarded)
                    # 5. respawn each lost flake on a surviving/new host
                    for n in dead:
                        cluster.unplace(n, release_cores=True)
                    for n in dead:
                        v = coord.graph.vertices[n]
                        target = self._pick_host(cluster, v.cores)
                        cluster.place(n, v.cores, host=target)
                        old = coord.flakes[n]
                        new = Flake(
                            n, v.factory, cores=v.cores, engine=coord,
                            channel_capacity=coord._channel_capacity,
                            speculative_timeout=coord._speculative_timeout,
                            batch_max=v.annotations.get("batch_max"),
                            batch_wait_ms=v.annotations.get(
                                "batch_wait_ms", 0.0),
                            batch_array=v.annotations.get(
                                "batch_array", False))
                        new._chaos = old._chaos  # chaos targets the stage
                        coord.flakes[n] = new
                        coord._container_of[n] = target.container
                        replaced[n] = target.name
                # 6. the carcass is empty now — release the VM
                try:
                    cluster.release_host(host)
                except ClusterError:
                    pass
                # 7. rewire (fresh RemoteFlake proxies resolve the new
                #    placement) and start the respawns
                coord.apply_wiring(coord.graph)
                for n in dead:
                    coord.flakes[n].activate()
                # 8. global rollback: latest cut + journal suffix replay
                with coord._inject_lock:
                    cores_now = {n: f.cores
                                 for n, f in coord.flakes.items()}
                    restored = None
                    if self.checkpoint_path is not None:
                        try:
                            restore_floe_graph(coord, self.checkpoint_path)
                            restored = self.checkpoint_path
                        except (CheckpointCorruptError, OSError) as e:
                            coord._record_error("__faults__", e)
                    for n, f in coord.flakes.items():
                        # core allocation is a resource property, not
                        # dataflow state — don't roll it back
                        f.set_cores(cores_now[n])
                    replayed = self._replay_journal()
            finally:
                for f in live:
                    f._drain_release()
        dt = time.time() - t_detect
        record = {
            "host": host.name, "flakes": dead, "placed": replaced,
            "checkpoint": restored, "replayed_rows": replayed,
            "discarded_rows": discarded,
            "journal_overflow": self.journal_overflow,
            "duration_s": round(dt, 6), "t": time.time(),
        }
        self.recoveries.append(record)
        self.last_recovery = record
        if self._m_recoveries is not None:
            self._m_recoveries.inc()
            self._m_recovery_s.observe(dt)
        self._emit("recovery", **record)

    # -- introspection --------------------------------------------------------
    def describe(self) -> Dict[str, Any]:
        with self._alock:
            restarts = dict(self._restarts)
            quarantined = sorted(self.quarantined)
        return {
            "restarts": restarts,
            "quarantined": quarantined,
            "suspected": sorted(self._suspected),
            "dead_letters": len(self.dead_letters),
            "dead_letters_total": self.dead_letters.total,
            "checkpoints": self._ckpt_epoch,
            "checkpoint_path": self.checkpoint_path,
            "journal_rows": len(self._journal),
            "journal_overflow": self.journal_overflow,
            "hosts_failed": sorted(self._host_declared),
            "recoveries": len(self.recoveries),
            "last_recovery": self.last_recovery,
        }
