"""The Floe continuous execution engine (paper §III, Fig. 2).

Component model (no centralized dataflow orchestrator in the data path):

* ``Flake``       — executes a single pellet: holds per-port input channels,
  de/serialization-free message buffers, an instance pool for data-parallel
  pellet instances, split-policy routing to neighbour flakes, and the
  monitoring instrumentation (queue length, message latency) used by the
  adaptation strategies.
* ``Container``   — VM-level resource runtime: accounts CPU cores and hands
  them to flakes; pellet-instance count = cores × α (α = 4, §III).
* ``Coordinator`` — parses the FloeGraph, acquires cores from containers,
  instantiates and wires flakes bottom-up (sinks first), activates them, and
  drives dynamic task / dataflow updates (§II.B).

Threading: one dispatcher thread per flake; data-parallel push pellets fan
out to a shared worker pool bounded by an adjustable semaphore whose capacity
tracks the flake's core allocation (so ``set_cores`` takes effect without
restarting threads — the mechanism behind the dynamic adaptation strategy).

Straggler mitigation: optional speculative re-execution of push-pellet tasks
that exceed a timeout; first completion wins, duplicates are suppressed by
message seq id (engine-level analogue of backup tasks).  A single shared
watchdog thread per flake arms the backup tasks.

Data path: adaptively micro-batched.  Each dispatch drains up to
min(queue_depth, ``batch_max``) messages from one channel in a single lock
round-trip, runs them through the pellet's ``compute_batch`` (default: loop
over ``compute``; vectorizable), and routes the emitted outputs grouped by
destination ``(flake, port)`` so split evaluation, stats, inflight
accounting, and the downstream channel append are each paid once per batch.
B self-tunes: near-empty queues dispatch single messages (latency path),
backlog grows batches up to the cap (throughput path).  Batches never span
a landmark, so window/flush ordering is exactly the per-message semantics.
"""
from __future__ import annotations

import threading
import time
from collections import Counter, deque
from concurrent.futures import ThreadPoolExecutor
from contextlib import contextmanager
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

from .arraybatch import ArrayBatch
from .graph import FloeGraph
from .message import Message, _next_seq
from .patterns import SPLITS, Split, make_split
from .pellet import (BatchItemError, Drop, FnPellet, KeyedEmit, Pellet,
                     PullPellet, PushPellet, TuplePellet, WindowPellet)
from ..telemetry import NO_SPAN, TRACE_KEY, Telemetry, span, trace_of

ALPHA = 4  # pellet instances per core (§III)

#: default cap for the adaptive micro-batch: a dispatch drains
#: min(queue_depth, batch_max) messages per wake, so B self-tunes to 1 at
#: low occupancy (single-message latency path) and grows with backlog.
DEFAULT_BATCH_MAX = 128
#: the default policy targets ~this much compute per batch: pellets whose
#: per-message latency is large keep B small (batching would only hide
#: backlog from the adaptation strategies without amortizing anything),
#: pellets with micro-second compute — where dispatch overhead dominates —
#: batch up to DEFAULT_BATCH_MAX.  Explicit ``.batch(...)`` annotations
#: bypass this heuristic.
TARGET_BATCH_SECONDS = 0.005
#: cap before the first latency measurement lands (cold-start guard)
BOOTSTRAP_BATCH_MAX = 32


def _is_special(msg: Message) -> bool:
    """Batch boundary predicate: landmarks/control never share a batch."""
    return not msg.is_data()


def _is_carrier(msg: Message) -> bool:
    """Is this message an ArrayBatch carrier (one entry, many rows)?"""
    return msg.is_data() and isinstance(msg.payload, ArrayBatch)


def _batch_boundary(msg: Message) -> bool:
    """Push-path pop boundary: specials never share a batch, and a carrier
    is already a whole batch — it dispatches alone (as one columnar unit)
    rather than being mixed with scalar messages."""
    return not msg.is_data() or isinstance(msg.payload, ArrayBatch)


def _rows_of(msg: Message) -> int:
    """Logical row count of one channel entry.  All credit, backpressure
    and stats accounting is in rows, so an ArrayBatch carrier weighs
    exactly what its unstacked messages would."""
    p = msg.payload
    return len(p) if isinstance(p, ArrayBatch) else 1


def _rows_total(msgs) -> int:
    return sum(_rows_of(m) for m in msgs)


def _degrade_carriers(msgs: List[Message]) -> List[Message]:
    """Unstack any ArrayBatch carriers into per-row messages (in place,
    order preserved).  Used by raw channel hand-offs (backlog reroute /
    replacement re-admit) whose target cannot consume carriers — going
    through ``enqueue`` would do this automatically, but those paths
    deliberately bypass it to keep credits moving with the messages."""
    if not any(_is_carrier(m) for m in msgs):
        return msgs
    out: List[Message] = []
    for m in msgs:
        out.extend(m.payload.to_messages(port=m.port)
                   if _is_carrier(m) else (m,))
    return out


def _edge_key(e) -> Tuple[str, str, str, str, str, str]:
    """Edge identity for structural diffs (every routed-on field)."""
    return (e.src, e.src_port, e.dst, e.dst_port, e.split, e.transport)


def _edge_delta(old: FloeGraph, new: FloeGraph
                ) -> Tuple[List[Dict[str, str]], List[Dict[str, str]]]:
    """Multiset edge diff old -> new as (added, removed) summary dicts."""
    fields = ("src", "src_port", "dst", "dst_port", "split", "transport")
    oc = Counter(_edge_key(e) for e in old.edges)
    nc = Counter(_edge_key(e) for e in new.edges)
    added = [dict(zip(fields, k)) for k in sorted((nc - oc).elements())]
    removed = [dict(zip(fields, k)) for k in sorted((oc - nc).elements())]
    return added, removed


class AdjustableSemaphore:
    """Counting semaphore whose capacity can change at runtime."""

    def __init__(self, capacity: int):
        self._capacity = capacity
        self._in_use = 0
        self._cond = threading.Condition()

    def acquire(self, timeout: Optional[float] = None) -> bool:
        """Take a slot; False on timeout, and at once while the capacity
        is 0: a pool scaled to no cores has no slot to wait for."""
        with self._cond:
            ok = self._cond.wait_for(
                lambda: self._in_use < self._capacity or self._capacity == 0,
                timeout=timeout)
            if not ok or self._capacity == 0:
                return False
            self._in_use += 1
            return True

    def release(self) -> None:
        with self._cond:
            self._in_use -= 1
            self._cond.notify_all()

    def set_capacity(self, capacity: int) -> None:
        with self._cond:
            self._capacity = max(0, int(capacity))
            self._cond.notify_all()

    @property
    def capacity(self) -> int:
        return self._capacity

    @property
    def free(self) -> int:
        # unlocked heuristic read (GIL-atomic ints): used only to shape
        # adaptive batch sizes, never for admission control
        return self._capacity - self._in_use


class Channel:
    """Bounded FIFO edge buffer with backpressure.

    The batch operations (``put_many`` / ``pop_up_to``) move a whole
    micro-batch per lock round-trip — the primitive underneath the engine's
    adaptive micro-batched data path.

    Capacity, queue length (``len``), and backpressure are all accounted in
    **rows**: an ArrayBatch carrier is one deque entry but weighs its row
    count, so batching never loosens the buffer bound and queue-depth
    readers (adaptive B, balanced splits, adaptation strategies) see the
    real backlog.
    """

    def __init__(self, capacity: int = 100_000,
                 on_put: Optional[Callable[[], None]] = None,
                 on_stall: Optional[Callable[[], None]] = None):
        self._q: deque = deque()       # guarded-by: _lock
        self._capacity = capacity
        self._rows = 0                 # guarded-by: _lock
        self._lock = threading.Lock()
        self._not_full = threading.Condition(self._lock)
        self._on_put = on_put
        #: telemetry hook: called once per producer block on a full
        #: channel (backpressure-stall counter), never on the fast path
        self._on_stall = on_stall

    def put(self, msg: Message, timeout: Optional[float] = 30.0) -> None:
        with self._not_full:
            if self._rows >= self._capacity:
                if self._on_stall:
                    self._on_stall()
                if not self._not_full.wait_for(
                        lambda: self._rows < self._capacity,
                        timeout=timeout):
                    raise TimeoutError("channel full: backpressure timeout")
            self._q.append(msg)
            self._rows += _rows_of(msg)
        if self._on_put:
            self._on_put()

    def put_many(self, msgs: List[Message],
                 timeout: Optional[float] = 30.0) -> None:
        """Append a batch under one lock acquisition, backpressure preserved.

        A batch larger than the remaining capacity is admitted in chunks as
        space frees up (waiting for room for the *whole* batch could
        deadlock a graph cycle); each chunk still respects the capacity
        bound, so downstream backpressure semantics are unchanged.
        ``timeout`` is ONE shared deadline for the whole call, not a
        per-chunk allowance — a multi-chunk admit against a slow consumer
        fails within ``timeout`` wall-clock, never N×timeout.
        """
        if not msgs:
            return
        deadline = (None if timeout is None
                    else time.monotonic() + timeout)
        i, n = 0, len(msgs)
        while i < n:
            with self._not_full:
                if self._rows >= self._capacity and self._on_stall:
                    self._on_stall()
                remaining = (None if deadline is None
                             else deadline - time.monotonic())
                if not self._not_full.wait_for(
                        lambda: self._rows < self._capacity,
                        timeout=remaining):
                    err = TimeoutError(
                        "channel full: backpressure timeout")
                    err.appended = i   # callers roll back the remainder
                    raise err
                space = self._capacity - self._rows
                take, rows = 0, 0
                while i + take < n:
                    r = _rows_of(msgs[i + take])
                    if take > 0 and rows + r > space:
                        break   # always admit >= 1 entry per chunk
                    rows += r
                    take += 1
                    if rows >= space:
                        break
                self._q.extend(msgs[i:i + take])
                self._rows += rows
                i += take
            if self._on_put:   # per chunk, so the consumer makes progress
                self._on_put()

    def try_pop(self) -> Optional[Message]:
        with self._not_full:
            if self._q:
                msg = self._q.popleft()
                self._rows -= _rows_of(msg)
                self._not_full.notify_all()
                return msg
            return None

    def pop_up_to(self, n: Optional[int] = None,
                  stop: Optional[Callable[[Message], bool]] = None
                  ) -> List[Message]:
        """Pop up to ``n`` messages (all, if None) in one lock round-trip.

        ``stop`` marks batch boundaries (e.g. landmarks): popping halts
        *before* a message for which ``stop(msg)`` is true, except that a
        boundary message at the head is popped alone — so a returned batch
        is either entirely non-boundary messages or a single boundary one,
        and a batch never spans a landmark.
        """
        out: List[Message] = []
        with self._not_full:
            q = self._q
            while q and (n is None or len(out) < n):
                if stop is not None and stop(q[0]):
                    if not out:
                        out.append(q.popleft())
                    break
                out.append(q.popleft())
            if out:
                self._rows -= _rows_total(out)
                self._not_full.notify_all()
        return out

    def unpop(self, msg: Message) -> None:
        """Push a popped message back to the head (locked restore path)."""
        with self._lock:
            self._q.appendleft(msg)
            self._rows += _rows_of(msg)

    def peek(self) -> Optional[Message]:
        with self._lock:
            return self._q[0] if self._q else None

    def snapshot(self) -> List[Message]:
        """Locked copy of the pending messages (checkpoint capture) —
        iterating ``_q`` unlocked races producers (deque mutation)."""
        with self._lock:
            return list(self._q)

    def __len__(self) -> int:
        """Pending ROWS (not deque entries) — the logical queue depth."""
        return self._rows


class FlakeStats:
    """Monitoring instrumentation inside flakes (§III).

    Tracks arrival/processing counts and EWMA per-message latency; the
    adaptation strategies read ``input_rate``, ``service_rate`` and
    ``queue_length`` at sampling intervals.
    """

    def __init__(self, ewma: float = 0.2):
        self._lock = threading.Lock()
        self.arrived = 0
        self.processed = 0
        self.emitted = 0
        self.ewma = ewma
        self.avg_latency = 0.0    # seconds per message, single instance
        self.batches = 0          # data dispatches on the push path
        self.last_batch = 0       # size of the most recent dispatch
        self.avg_batch = 0.0      # EWMA dispatch size (batch occupancy)
        self.max_batch = 0
        self._win_arrived = 0
        self._win_processed = 0
        self._win_start = time.time()

    def on_arrive(self, n: int = 1) -> None:
        with self._lock:
            self.arrived += n
            self._win_arrived += n

    def on_dispatch(self, n: int) -> None:
        """Record one push-path data dispatch of ``n`` messages (B)."""
        with self._lock:
            self.batches += 1
            self.last_batch = n
            if self.avg_batch == 0.0:
                self.avg_batch = float(n)
            else:
                self.avg_batch += self.ewma * (n - self.avg_batch)
            if n > self.max_batch:
                self.max_batch = n

    def on_process(self, latency: float, n: int = 1) -> None:
        with self._lock:
            self.processed += n
            self._win_processed += n
            per_msg = latency / max(n, 1)
            if self.avg_latency == 0.0:
                self.avg_latency = per_msg
            else:
                self.avg_latency += self.ewma * (per_msg - self.avg_latency)

    def on_emit(self, n: int = 1) -> None:
        with self._lock:
            self.emitted += n

    def reset_latency(self) -> None:
        """Forget the latency EWMA (and batch-size EWMA) — used when a
        flake moves to a different core budget (migration / replacement):
        samples measured on the old host would poison post-move decisions
        (a stale-fast EWMA over-batches a now-slow stage; a stale-slow one
        keeps a now-fast stage trickling).  Zeroing also re-arms the
        BOOTSTRAP_BATCH_MAX cold-start guard until fresh samples land.
        Counters (arrived/processed/emitted) are cumulative facts about
        the stage and deliberately survive."""
        with self._lock:
            self.avg_latency = 0.0
            self.avg_batch = 0.0
            self.last_batch = 0

    def sample_rates(self) -> Tuple[float, float]:
        """Return (input_rate, processed_rate) msgs/sec since last sample."""
        with self._lock:
            now = time.time()
            dt = max(now - self._win_start, 1e-9)
            rates = (self._win_arrived / dt, self._win_processed / dt)
            self._win_arrived = 0
            self._win_processed = 0
            self._win_start = now
            return rates

    @property
    def selectivity(self) -> float:
        return self.emitted / max(self.processed, 1)


class Flake:
    """Executes one pellet; coordinates dataflow with neighbour flakes."""

    def __init__(self, name: str, factory: Callable[[], Pellet], *,
                 cores: int = 1, engine: "Coordinator" = None,
                 channel_capacity: int = 100_000,
                 speculative_timeout: Optional[float] = None,
                 batch_max: Optional[int] = None,
                 batch_wait_ms: float = 0.0,
                 batch_array: bool = False,
                 proto: Optional[Pellet] = None):
        self.name = name
        self.factory = factory
        self.engine = engine
        self.cores = cores
        #: prototype for port/semantic info; callers that already built and
        #: validated one (transactional vertex addition) pass it in so the
        #: factory runs once per spawn
        self._proto = proto if proto is not None else factory()
        self.stats = FlakeStats()
        #: telemetry handles, cached once so the hot path pays one method
        #: call per dispatch (all None when telemetry is off — every
        #: instrumentation site gates on a single attribute check)
        tele = engine.telemetry if engine is not None else None
        if tele is not None and tele.enabled:
            self._tele: Optional[Telemetry] = tele
            self._tele_service = tele.service_time.labels(stage=name)
            self._tele_wait = tele.queue_wait.labels(stage=name)
            self._tele_array = tele.array_hits.labels(stage=name)
            self._tele_degrade = tele.degradations.labels(stage=name)
            _stall = tele.stalls.labels(stage=name).inc
        else:
            self._tele = None
            self._tele_service = None
            self._tele_wait = None
            self._tele_array = None
            self._tele_degrade = None
            _stall = None
        self._bind(self._proto)
        self._channel_capacity = channel_capacity
        self._wake = threading.Condition()
        self.inputs: Dict[str, Channel] = {
            p: Channel(channel_capacity, on_put=self._notify,
                       on_stall=_stall)
            for p in self._proto.in_ports}
        #: routing: src_port -> (split, [(flake, dst_port)])
        self.routes: Dict[str, Tuple[Split, List[Tuple["Flake", str]]]] = {}
        #: ordered edge-group signature per out-port as last installed by
        #: ``apply_wiring`` — the ground truth for split-object reuse.  A
        #: split (and its counters) survives a rewire only when the group it
        #: was built for is byte-identical, membership AND order; anything
        #: else rebuilds it, so a rewire that alters fan-out can never
        #: consult a split whose state was accumulated against the old
        #: destination set.
        self._route_sigs: Dict[str, List[Tuple[str, str, str]]] = {}
        self.state: Any = self._proto.initial_state()
        self._state_lock = threading.Lock()
        self._pellet_lock = threading.RLock()  # guards factory swap
        self._paused = threading.Event()
        self._stop = threading.Event()
        #: sync update: block dispatch.  Refcounted (``_drain_acquire`` /
        #: ``_drain_release``) so concurrent drainers (a sync task update
        #: racing a recompose transaction) cannot cancel each other's drain.
        self._drain = threading.Event()
        self._drain_depth = 0          # guarded-by: _drain_lock
        self._drain_lock = threading.Lock()
        self._sem = AdjustableSemaphore(max(1, cores * ALPHA))
        self._pool: Optional[ThreadPoolExecutor] = None
        self._thread: Optional[threading.Thread] = None
        self._window_buf: List[Any] = []
        self._inflight = 0             # guarded-by: _inflight_cond
        self._popping = False          # guarded-by: _inflight_cond
        self._inflight_cond = threading.Condition()
        self._done_seqs: set = set()           # speculative dedup
        self.speculative_timeout = speculative_timeout
        #: one shared watchdog thread per flake arms speculative backup
        #: tasks (a per-message threading.Timer — one OS thread per message
        #: — was itself a throughput bug at any sustained rate)
        self._spec_q: deque = deque()  # guarded-by: _spec_cond
        self._spec_cond = threading.Condition()
        self._spec_thread: Optional[threading.Thread] = None
        #: adaptive micro-batch knobs: a dispatch drains up to
        #: min(queue_depth, batch_max) messages; batch_wait lets a
        #: latency-insensitive stage linger up to that long for a fuller
        #: batch (0 = dispatch whatever is available immediately).
        #: ``batch_max=None`` selects the default policy (DEFAULT_BATCH_MAX
        #: further capped by the measured-latency heuristic); an explicit
        #: value — composition annotation or ``set_batch`` — is authoritative.
        self._batch_explicit = batch_max is not None
        self.batch_max = (DEFAULT_BATCH_MAX if batch_max is None
                          else max(1, int(batch_max)))
        self.batch_wait = max(0.0, float(batch_wait_ms)) / 1000.0
        #: array fast path opt-in (``stage.batch(..., array=True)``): a
        #: drained batch of stackable payloads is kept as ONE ArrayBatch
        #: carrier — computed via ``compute_array``, routed columnar.
        self.batch_array = bool(batch_array)
        self._batch_deadline: Optional[float] = None
        self.version = 0                       # bumps on dynamic task update
        #: landmark alignment (watermark semantics): a flush landmark is
        #: delivered to the pellet only once a copy has arrived from every
        #: inbound edge (set by the coordinator during wiring).  Without this,
        #: a reducer fed by m mappers would flush m times per logical window.
        #: The last swallowed copy is retained so a dynamic fan-in change can
        #: complete a half-counted round instead of losing it.
        #: NOTE: do not send flush landmarks around cycles — back-edges count
        #: toward the in-degree and the round would never complete.
        self.in_degree = 1
        self._lm_count = 0             # guarded-by: _lm_lock
        self._lm_pending: Optional[Message] = None   # guarded-by: _lm_lock
        self._lm_lock = threading.Lock()
        #: failure-detection heartbeat: one float store per dispatch-loop
        #: iteration, read by the fault plane's supervisor
        self.heartbeat = 0.0
        #: armed chaos CrashRule (fault-injection harness), None in production
        self._chaos = None
        #: remote compute seam (``cluster.workers.FlakeRunner``) bound by
        #: ``Coordinator.apply_wiring`` when this flake's host runs on a
        #: process backend; None = compute locally (the sim default)
        self.remote = None

    # -- lifecycle -----------------------------------------------------------
    def activate(self) -> None:
        self.heartbeat = time.time()
        self._pool = ThreadPoolExecutor(
            max_workers=64, thread_name_prefix=f"flake-{self.name}")
        self._thread = threading.Thread(
            target=self._dispatch_loop, name=f"dispatch-{self.name}", daemon=True)
        self._thread.start()
        if self.speculative_timeout is not None:
            self._spec_thread = threading.Thread(
                target=self._spec_loop, name=f"spec-{self.name}", daemon=True)
            self._spec_thread.start()

    def deactivate(self) -> None:
        self._stop.set()
        self._notify()
        with self._spec_cond:
            self._spec_cond.notify_all()
        if self._thread:
            self._thread.join(timeout=10)
        if self._spec_thread:
            self._spec_thread.join(timeout=10)
        if self._pool:
            self._pool.shutdown(wait=True, cancel_futures=True)

    def pause(self) -> None:
        self._paused.set()

    def resume(self) -> None:
        self._paused.clear()
        self._notify()

    def set_cores(self, cores: int) -> None:
        """Fine-grained runtime resource control (§III): resize instance pool."""
        self.cores = max(0, int(cores))
        self._sem.set_capacity(max(1, self.cores * ALPHA) if self.cores else 0)

    def set_batch(self, max_size: int,
                  max_wait_ms: Optional[float] = None,
                  array: Optional[bool] = None) -> None:
        """Runtime micro-batch tuning (max_size=1 disables batching).

        An explicit size is authoritative: it replaces the default
        latency-targeting policy for this flake.  ``array`` toggles the
        ArrayBatch fast path (None = leave unchanged).
        """
        self.batch_max = max(1, int(max_size))
        self._batch_explicit = True
        if max_wait_ms is not None:
            self.batch_wait = max(0.0, float(max_wait_ms)) / 1000.0
        if array is not None:
            self.batch_array = bool(array)
        self._batch_deadline = None   # drop any in-progress linger
        self._notify()

    def clear_batch(self) -> None:
        """Revert to the default adaptive batching policy (the state of a
        flake whose stage never carried a ``.batch(...)`` annotation)."""
        self.batch_max = DEFAULT_BATCH_MAX
        self.batch_wait = 0.0
        self._batch_explicit = False
        self.batch_array = False
        self._batch_deadline = None
        self._notify()

    @property
    def accepts_arrays(self) -> bool:
        """Can this flake consume an ArrayBatch carrier whole?  Anything
        else (window/tuple/pull pellets, speculation, no opt-in) gets the
        carrier unstacked into per-row messages at enqueue — the clean
        fallback to the row-wise data path."""
        return (self.batch_array and self.speculative_timeout is None
                and isinstance(self._proto, PushPellet))

    def _drain_acquire(self) -> None:
        with self._drain_lock:
            self._drain_depth += 1
            self._drain.set()

    def _drain_release(self) -> None:
        with self._drain_lock:
            self._drain_depth = max(0, self._drain_depth - 1)
            if self._drain_depth == 0:
                self._drain.clear()
        self._notify()

    # -- dynamic task update (§II.B) ------------------------------------------
    def swap_pellet(self, factory: Callable[[], Pellet], *,
                    mode: str = "sync", emit_update_landmark: bool = True,
                    new_proto: Optional[Pellet] = None) -> None:
        """In-place task update without halting other pellets.

        sync  — stop dispatching, let in-flight messages finish to completion
                and deliver their outputs, then swap; optionally emit an
                "update landmark" downstream before resuming.
        async — swap the factory immediately: new messages are processed by
                the new logic while old in-flight instances run to completion
                (outputs may interleave). Zero downtime.

        ``new_proto`` lets callers that already instantiated/validated the
        new pellet (``Coordinator.transact``) pass it in instead of paying
        a second ``factory()`` call.
        """
        if mode not in ("sync", "async"):
            raise ValueError("mode must be 'sync' or 'async'")
        if new_proto is None:
            new_proto = factory()
        self._bind(new_proto)
        if tuple(new_proto.in_ports) != tuple(self._proto.in_ports) or \
           tuple(new_proto.out_ports) != tuple(self._proto.out_ports):
            raise ValueError(
                "in-place task update requires identical ports; use a "
                "dynamic dataflow update instead (§II.B)")
        if mode == "sync":
            self._drain_acquire()      # stop pulling new messages
            # in-flight finish to completion; outputs delivered
            if not self._wait_quiescent():
                self._drain_release()
                raise TimeoutError(
                    f"flake {self.name!r} did not quiesce within 30s; "
                    "task update aborted, nothing applied")
        with self._pellet_lock:
            old = self._proto
            self.factory = factory
            self._proto = new_proto
            self.version += 1
            self._batch_deadline = None   # new logic: drop any linger
            # internal state survives the update if stateful (§II.B)
            if not new_proto.stateful:
                self.state = new_proto.initial_state()
            # mutable *instance* state declared via ``__floe_state__``
            # also survives, when the replacement declares the same
            # attributes: a task update swaps *logic*, not in-flight
            # state (e.g. a decode stage's KV/slot tables across a live
            # weight hot-swap).  Replacements that declare different
            # (or no) state attributes start fresh, as before.
            carry = tuple(type(old).__floe_state__)
            if carry and tuple(type(new_proto).__floe_state__) == carry:
                try:
                    new_proto.set_state(old.get_state())
                except Exception as e:
                    if self.engine is not None:
                        self.engine._record_error(self.name, e)
        try:
            old.teardown()
        except Exception:
            pass
        if emit_update_landmark:
            from .message import update_landmark
            self._route(update_landmark(tag={"flake": self.name,
                                             "version": self.version}))
        if mode == "sync":
            self._drain_release()

    # -- input side ------------------------------------------------------------
    def enqueue(self, port: str, msg: Message) -> None:
        if port not in self.inputs:
            raise KeyError(f"{self.name}: no input port {port!r}")
        if _is_carrier(msg) and not self.accepts_arrays:
            # columnar fast path ends here: this flake cannot consume a
            # stacked batch (window/tuple/pull semantics, no opt-in, or
            # speculation) — degrade to the exact row-wise data path
            if self._tele_degrade is not None:
                self._tele_degrade.inc()
            self.enqueue_many(port, msg.payload.to_messages(port=msg.port))
            return
        if msg.landmark and self.in_degree > 1:
            with self._lm_lock:
                self._lm_count += 1
                if self._lm_count < self.in_degree:
                    self._lm_pending = msg
                    return  # swallow: wait for copies from remaining edges
                self._lm_count = 0
                self._lm_pending = None
        n = _rows_of(msg)
        if self.engine is not None:
            self.engine._inflight_inc(n)
        self.stats.on_arrive(n)
        try:
            self.inputs[port].put(msg)
        except Exception:
            # never-admitted message: release its credit or engine-wide
            # quiescence would wedge for the life of the session
            if self.engine is not None:
                self.engine._inflight_dec(n)
            raise

    def enqueue_many(self, port: str, msgs: List[Message]) -> None:
        """Batched enqueue: inflight accounting, arrival stats, and the
        channel append each run once per batch instead of once per message.

        Only data messages take the batched fast path — specials
        (landmarks/control) fall back to ``enqueue`` so fan-in landmark
        alignment semantics are byte-for-byte identical.
        """
        if not msgs:
            return
        if port not in self.inputs:
            raise KeyError(f"{self.name}: no input port {port!r}")
        if len(msgs) == 1:
            self.enqueue(port, msgs[0])
            return
        if any(not m.is_data() for m in msgs):
            for m in msgs:
                self.enqueue(port, m)
            return
        if not self.accepts_arrays:
            degraded = _degrade_carriers(msgs)
            if degraded is not msgs and self._tele_degrade is not None:
                self._tele_degrade.inc(sum(1 for m in msgs
                                           if _is_carrier(m)))
            msgs = degraded
        rows = _rows_total(msgs)
        if self.engine is not None:
            self.engine._inflight_inc(rows)
        self.stats.on_arrive(rows)
        try:
            self.inputs[port].put_many(msgs)
        except Exception as e:
            # release credits for the never-admitted remainder (put_many
            # reports how many entries it appended before timing out)
            lost = _rows_total(msgs[getattr(e, "appended", 0):])
            if self.engine is not None and lost > 0:
                self.engine._inflight_dec(lost)
            raise

    def queue_length(self) -> int:
        return sum(len(c) for c in self.inputs.values())

    def _notify(self) -> None:
        with self._wake:
            self._wake.notify_all()

    # -- telemetry ----------------------------------------------------------------
    def _bind(self, proto: Pellet) -> Pellet:
        """Hand a freshly built pellet this stage's telemetry: pellets that
        count their own work define ``bind_telemetry(telemetry, stage)``."""
        bind = getattr(proto, "bind_telemetry", None)
        if bind is not None and self._tele is not None:
            bind(self._tele, self.name)
        return proto

    def _span(self, name: str, **meta: Any):
        """A ``floe.*`` profiler span on this stage; the shared no-op when
        telemetry is off (no span object is built)."""
        if self._tele is None:
            return NO_SPAN
        return span(name, stage=self.name, **meta)

    # -- dispatch ---------------------------------------------------------------
    def _dispatch_loop(self) -> None:
        while not self._stop.is_set():
            self.heartbeat = time.time()
            if self._paused.is_set() or self._drain.is_set() or self.cores == 0:
                with self._wake:
                    self._wake.wait(timeout=0.05)
                continue
            work = self._pop()
            if work is not None:
                self._dispatch(work)
            else:
                with self._wake:
                    hold = self._batch_deadline
                    remaining = (hold - time.time()) if hold is not None \
                        else 0.0
                    if remaining > 0.0 and not self._stop.is_set():
                        # batch_wait hold: messages are queued but below
                        # batch_max — linger (bounded) for a fuller batch.
                        # A stale/expired deadline falls through to the
                        # normal wait (no busy-spin).
                        self._wake.wait(timeout=min(0.05, remaining))
                    elif (self.queue_length() == 0 and not self._stop.is_set()
                            and not self._ready()):
                        self._wake.wait(timeout=0.05)

    def _pop(self):
        """``_collect`` in sight of a drain: a drain that finds this flake
        quiescent must not miss a message popped from its channel but not
        yet run (a cut would then hold neither the message nor its
        effect).  ``_popping`` holds the drain's wait from before the
        drain check until popped work is counted in flight; the work
        releases that count when it is done (``_dispatch``)."""
        with self._inflight_cond:
            self._popping = True
        work = None
        try:
            if not self._drain.is_set():
                work = self._collect()
        finally:
            with self._inflight_cond:
                if work is not None:
                    self._inflight += 1
                self._popping = False
                self._inflight_cond.notify_all()
        return work

    def _dispatch(self, work) -> None:
        """Run or submit one unit of ``_pop``'s work and release its
        in-flight count when it is done: a pooled instance takes the count
        over, anything else runs here in the dispatch thread, visible to
        ``_wait_quiescent`` (a sequential/pull pellet mid-compute must not
        look quiescent to a concurrent sync update or recompose commit)."""
        kind, item, credits, wait = work
        with self._pellet_lock:
            proto = self._proto
        if (kind != "landmark" and not proto.sequential
                and not isinstance(proto, PullPellet)
                and self._submit(kind, item, credits, wait)):
            return
        try:
            if kind == "landmark":
                # a landmark must not overtake data: wait for in-flight
                # data-parallel instances to complete and deliver outputs
                # before forwarding the flush marker downstream
                self._wait_quiescent(own=1)
                self._finish(item, credits, forward=True)
            else:
                self._run_task(kind, item, credits, wait)
        finally:
            self._inflight_dec_local()

    def _observe_wait(self, head_ts: float, rows: int) -> float:
        """Queue-wait histogram: time from enqueue to dispatch, observed
        once per dispatch with the batch-head's wait weighted by row count
        (``derive()`` stamps a fresh ``ts`` per hop, so ``msg.ts`` is the
        enqueue time at this stage to within routing latency).  Returns
        the wait, which the dispatch's sampled trace spans report."""
        w = self._tele_wait
        if w is None or rows <= 0:
            return 0.0
        wait = max(time.time() - head_ts, 0.0)
        w.observe(wait, n=rows)
        return wait

    def _ready(self) -> bool:
        """Is a unit of work available right now?"""
        proto = self._proto
        if isinstance(proto, TuplePellet):
            return all(len(c) > 0 for c in self.inputs.values())
        return any(len(c) > 0 for c in self.inputs.values())

    def _collect(self):
        """Pop one unit of work: ('msg', Message, credits, wait) |
        ('batch', [Message], credits, wait) |
        ('tuple', {port: Message}, credits, wait) |
        ('window', [Message], credits, wait) |
        ('pull', [Message], credits, wait) | ('landmark', Message, 1, 0.0) |
        None, where ``wait`` is the head's queue wait (``_observe_wait``).

        The push path drains an adaptive micro-batch per wake: up to
        min(queue_depth, batch_max) messages in one channel lock round-trip,
        so B self-tunes to 1 when queues are near-empty (latency path) and
        grows with backlog (throughput path).  Batches never span a landmark
        (``pop_up_to`` stops at specials), so flush ordering is preserved.
        """
        proto = self._proto
        if isinstance(proto, TuplePellet):
            # synchronous merge: align one message per port (Fig. 1, P5);
            # landmarks bypass alignment and are forwarded immediately.
            for c in self.inputs.values():
                head = c.peek()
                if head is not None and not head.is_data():
                    return ("landmark", c.try_pop(), 1, 0.0)
            if all(len(c) > 0 for c in self.inputs.values()):
                tup = {p: c.try_pop() for p, c in self.inputs.items()}
                if any(m is None for m in tup.values()):   # lost a race
                    for p, m in tup.items():
                        if m is not None:
                            self.inputs[p].unpop(m)  # locked restore
                    return None
                wait = self._observe_wait(
                    min(m.ts for m in tup.values()), len(tup))
                return ("tuple", tup, len(tup), wait)
            return None
        if isinstance(proto, PullPellet):
            msgs: List[Message] = []
            for c in self.inputs.values():
                msgs.extend(c.pop_up_to())   # drain all, one lock round-trip
            if msgs:
                wait = self._observe_wait(msgs[0].ts, len(msgs))
                return ("pull", msgs, len(msgs), wait)
            return None
        if isinstance(proto, WindowPellet):
            # count window (Fig. 1, P3): gather up to `window` data messages;
            # a landmark flushes a partial window.
            for c in self.inputs.values():
                while True:
                    need = proto.window - len(self._window_buf)
                    got = c.pop_up_to(max(need, 1), stop=_is_special)
                    if not got:
                        break
                    if not got[0].is_data():
                        m = got[0]
                        buf, self._window_buf = self._window_buf, []
                        if buf:
                            # flush partial window, then forward the landmark
                            # (credits include the landmark message itself)
                            self._requeue_landmark_after = m
                            wait = self._observe_wait(buf[0].ts, len(buf))
                            return ("window", buf, len(buf) + 1, wait)
                        return ("landmark", m, 1, 0.0)
                    self._window_buf.extend(got)
                    if len(self._window_buf) >= proto.window:
                        buf, self._window_buf = self._window_buf, []
                        wait = self._observe_wait(buf[0].ts, len(buf))
                        return ("window", buf, len(buf), wait)
            return None
        # plain push pellet (interleaved merge across ports, Fig. 1, P6):
        # adaptive micro-batch
        linger = (self.batch_wait > 0.0 and self.batch_max > 1
                  and self.speculative_timeout is None)
        if linger:
            # an explicit linger says "prefer fuller batches over per-slot
            # parallelism": gate on the depth of the channel that will be
            # drained vs the configured cap and, once elapsed, take the
            # coalesced batch whole (no free-slot shaping).  Specials at
            # the head dispatch immediately — a batch can never include
            # them, so lingering would only delay the flush.  One deadline
            # per batch bounds the added latency at ``batch_wait`` per
            # non-empty input port.
            limit = self.batch_max
            target = next((c for c in self.inputs.values() if len(c)), None)
            if target is None:
                self._batch_deadline = None
                return None
            head = target.peek()
            if head is not None and head.is_data() \
                    and not isinstance(head.payload, ArrayBatch) \
                    and len(target) < limit:
                now = time.time()
                if self._batch_deadline is None:
                    self._batch_deadline = now + self.batch_wait
                    return None
                if now < self._batch_deadline:
                    return None
            self._batch_deadline = None
            channels = (target,)
        else:
            limit = self._batch_limit()
            channels = self.inputs.values()
        for c in channels:
            batch = c.pop_up_to(limit, stop=_batch_boundary)
            if not batch:
                continue
            head = batch[0]
            if not head.is_data():
                return ("landmark", head, 1, 0.0)
            if isinstance(head.payload, ArrayBatch):
                # an upstream stage already stacked this batch: dispatch
                # the carrier whole — credits/stats counted in rows
                rows = len(head.payload)
                self.stats.on_dispatch(rows)
                wait = self._observe_wait(head.ts, rows)
                return ("abatch", head, rows, wait)
            self.stats.on_dispatch(len(batch))
            wait = self._observe_wait(head.ts, len(batch))
            if len(batch) == 1:
                return ("msg", batch[0], 1, wait)
            return ("batch", batch, len(batch), wait)
        return None

    def _batch_limit(self) -> int:
        """Adaptive micro-batch cap for the next dispatch.

        Three concerns shape B, all of which decay it to 1 on the
        latency-sensitive single-message path:

        * latency target (default policy only): B is capped so one batch
          holds ~TARGET_BATCH_SECONDS of measured compute.  Slow pellets
          stay per-message — batching them would amortize nothing and hide
          backlog from queue-length-driven adaptation strategies.
        * data-parallelism: while instance slots are free, the backlog is
          split across them (B = ceil(queue/free)) instead of serialized
          into one batch; only a saturated pool — where dispatch overhead,
          not compute, is the bottleneck — grows B to the cap.
        * speculation: strictly per-message (seq-id dedup semantics).
        """
        if self.speculative_timeout is not None:
            return 1
        bmax = self.batch_max
        if bmax <= 1:
            return 1
        if not self._batch_explicit:
            avg = self.stats.avg_latency      # unlocked heuristic read
            if avg <= 0.0:
                bmax = min(bmax, BOOTSTRAP_BATCH_MAX)
            else:
                bmax = min(bmax, max(1, int(TARGET_BATCH_SECONDS / avg)))
            if bmax <= 1:
                return 1
        if self._proto.sequential:
            return bmax
        free = self._sem.free
        if free > 1:
            return min(bmax, max(1, -(-self.queue_length() // free)))
        return bmax

    # -- execution ---------------------------------------------------------------
    def _submit(self, kind: str, item, credits: int,
                wait: float = 0.0) -> bool:
        """Hand work and its in-flight count to a pooled instance; False
        when no instance slot frees within 30 s, or at once when cores are
        0 (a drain waits on this work): the caller then runs it inline."""
        if not self._sem.acquire(timeout=30):
            return False
        fut = self._pool.submit(self._run_pooled, kind, item, credits,
                                wait=wait)
        if self.speculative_timeout is not None and kind == "msg":
            with self._spec_cond:
                self._spec_q.append(
                    (time.time() + self.speculative_timeout,
                     fut, item, credits))
                self._spec_cond.notify_all()
        return True

    def _spec_loop(self) -> None:
        """Shared straggler watchdog: ONE thread arms every backup task.

        The timeout is constant per flake, so ``_spec_q`` is naturally
        deadline-ordered and a FIFO scan suffices (no heap needed).
        """
        while not self._stop.is_set():
            with self._spec_cond:
                while not self._spec_q and not self._stop.is_set():
                    self._spec_cond.wait(timeout=0.5)
                if self._stop.is_set():
                    return
                deadline, fut, item, credits = self._spec_q[0]
                wait = deadline - time.time()
                if wait > 0:
                    self._spec_cond.wait(timeout=wait)
                    continue           # re-check head (stop may have been set)
                self._spec_q.popleft()
            self._speculate(fut, item, credits)

    def _speculate(self, fut, item: Message, credits: int) -> None:
        """Backup-task execution for stragglers (first-done-wins).

        Backups deliberately bypass the instance-pool semaphore (they must
        run even when stragglers hold every slot), so they must not release
        a slot they never acquired — that would permanently loosen the
        cores×ALPHA admission cap by one per backup.
        """
        if fut.done() or self._stop.is_set():
            return
        self._inflight_inc_local()
        self._pool.submit(self._run_pooled, "msg", item, credits, False)

    def _run_pooled(self, kind: str, item, credits: int,
                    release_slot: bool = True, wait: float = 0.0) -> None:
        try:
            self._run_task(kind, item, credits, wait)
        finally:
            if release_slot:
                self._sem.release()
            self._inflight_dec_local()

    def _run_task(self, kind: str, item, credits: int,
                  wait: float = 0.0) -> None:
        with self._pellet_lock:
            proto = self._proto
            version = self.version
        outputs: List[Message] = []
        seq_for_dedup = item.seq if isinstance(item, Message) else None
        err: Optional[Exception] = None
        # the span covers exactly the service time the histogram observes
        with self._span("floe.dispatch", kind=kind, rows=credits):
            t0 = time.time()
            try:
                handled = False
                remote = self.remote
                if remote is not None and kind in ("msg", "batch", "abatch") \
                        and self._remote_eligible(proto):
                    res = self._remote_task(remote, proto, kind, item)
                    if res is not None:
                        outputs = res
                        handled = True
                if handled:
                    pass
                elif kind == "msg":
                    if seq_for_dedup is not None and self.speculative_timeout is not None:
                        with self._inflight_cond:
                            if seq_for_dedup in self._done_seqs:
                                return  # duplicate speculative task lost the race
                    if self._chaos is not None:
                        self._chaos.check_one(item.payload)
                    result = proto.compute(item.payload)
                    outputs = self._wrap(result, item)
                elif kind == "batch":
                    # micro-batch of data messages from ONE channel: one
                    # compute_batch call, per-message lineage/wrap preserved.
                    # With the array opt-in, stackable payloads take the
                    # columnar fast path instead (one ArrayBatch carrier out).
                    # An armed chaos rule forces the row-wise path so a
                    # poison row fails alone instead of sinking the batch.
                    outputs = None
                    if self.batch_array and self._chaos is None:
                        outputs = self._array_outputs(proto, msgs=item)
                    if outputs is None:
                        outputs = self._batch_outputs(proto, item)
                elif kind == "abatch":
                    # an ArrayBatch carrier: one compute_array call over the
                    # stacked array, no unstack between vectorized stages.  If
                    # the pellet declines the array path, degrade the carrier
                    # to the exact row-wise batched semantics.
                    ab = item.payload
                    outputs = None
                    if self._chaos is None:
                        outputs = self._array_outputs(proto, ab=ab)
                    if outputs is None:
                        outputs = self._batch_outputs(
                            proto, ab.to_messages(port=item.port))
                elif kind == "tuple":
                    payloads = {p: m.payload for p, m in item.items()}
                    anchor = next(iter(item.values()))
                    result = proto.compute(payloads)
                    outputs = self._wrap(result, anchor)
                elif kind == "window":
                    payloads = [m.payload for m in item]
                    result = proto.compute(payloads)
                    outputs = self._wrap(result, item[0])
                elif kind == "pull":
                    emitted: List[Message] = []
                    anchor = item[0]

                    def emit(payload, *, port: str = None, key: Any = None,
                             landmark: bool = False):
                        m = anchor.derive(payload, key=key,
                                          port=port or proto.out_ports[0])
                        m.landmark = landmark
                        emitted.append(m)

                    with self._state_lock:
                        st = self.state
                    new_state = proto.compute(iter(item), emit, st)
                    with self._state_lock:
                        self.state = new_state
                    outputs = emitted
            except Exception as e:  # pellet error: count and drop (log upstream)
                err = e
            t1 = time.time()
        if err is not None:
            lat = t1 - t0
            self.stats.on_process(lat, n=credits)
            if self._tele_service is not None:
                self._tele_service.observe(lat / max(credits, 1), n=credits)
            if self.engine is not None:
                # fault plane first: it may retry the rows or dead-letter
                # them (returns True = handled); default is drop-and-log
                faults = self.engine._faults
                if faults is None or not faults.on_task_error(
                        self, kind, item, err):
                    self.engine._record_error(self.name, err)
                self.engine._inflight_dec(credits)
            return
        if seq_for_dedup is not None and self.speculative_timeout is not None:
            with self._inflight_cond:
                if seq_for_dedup in self._done_seqs:
                    return  # another speculative copy already delivered
                self._done_seqs.add(seq_for_dedup)
        self.stats.on_process(t1 - t0, n=credits)
        if self._tele_service is not None:
            self._tele_service.observe((t1 - t0) / max(credits, 1),
                                       n=credits)
            self._record_spans(kind, item, t0, t1, wait)
        try:
            with self._span("floe.route"):
                self._route_many(outputs)
            self.stats.on_emit(_rows_total(outputs))
            # forward a landmark that flushed a partial window
            lm = getattr(self, "_requeue_landmark_after", None)
            if lm is not None:
                self._requeue_landmark_after = None
                self._route(lm)
        except Exception as e:
            # routing failure (e.g. sustained-backpressure timeout): the
            # undelivered outputs are dropped and logged, but the consumed
            # input credits MUST still be released below — leaking them
            # would wedge quiescence for the life of the session
            if self.engine is not None:
                self.engine._record_error(self.name, e)
        finally:
            if self.engine is not None:
                self.engine._inflight_dec(credits)

    def _record_spans(self, kind: str, item, t0: float, t1: float,
                      wait: float) -> None:
        """One span per distinct traced context in the dispatched work
        (rows sharing a trace aggregate into a single span), each with the
        dispatch's queue wait.  Only runs when the tracer is sampling."""
        tele = self._tele
        if tele is None or not tele.tracer.active:
            return
        ctxs: Dict[int, Tuple[dict, int]] = {}

        def add(ctx) -> None:
            if isinstance(ctx, dict):
                tid = ctx.get("id")
                if tid is not None:
                    cur = ctxs.get(tid)
                    ctxs[tid] = (ctx, cur[1] + 1 if cur else 1)

        if kind == "msg":
            add(item.meta.get(TRACE_KEY) if item.meta else None)
        elif kind in ("batch", "pull", "window"):
            for m in item:
                add(m.meta.get(TRACE_KEY) if m.meta else None)
        elif kind == "abatch":
            if item.payload.traces:
                for ctx in item.payload.traces:
                    add(ctx)
        elif kind == "tuple":
            for m in item.values():
                add(m.meta.get(TRACE_KEY) if m.meta else None)
        if not ctxs:
            return
        host = (self.engine._host_label(self.name)
                if self.engine is not None else "local")
        for ctx, rows in ctxs.values():
            tele.tracer.record_span(ctx, stage=self.name, host=host,
                                    rows=rows, t_start=t0, t_end=t1,
                                    queue_wait=wait)

    # -- remote compute offload (process-backed hosts) ------------------------
    def _remote_eligible(self, proto: Pellet) -> bool:
        """Only side-effect-contained dispatches offload to the host's
        worker process: stateless push compute with no chaos arming and no
        speculative re-execution.  Stateful pellets (``proto.stateful`` or
        a ``__floe_state__`` carrier) keep their state in the parent where
        checkpoints/migration capture it, so they compute locally
        regardless of placement."""
        return (self._chaos is None
                and self.speculative_timeout is None
                and not getattr(proto, "stateful", False)
                and not getattr(proto, "__floe_state__", ()))

    def _remote_task(self, remote, proto: Pellet, kind: str, item
                     ) -> Optional[List[Message]]:
        """Execute one dispatch in the flake's host worker process.

        Returns None when the runner declines (e.g. a non-picklable
        factory → permanent local fallback, semantics preserved).  Raises
        on a dead worker, which lands in the task-error path exactly like
        a pellet exception — the fault plane retries/dead-letters the
        rows while failure detection reaps the host.
        """
        if kind == "msg":
            reply = remote.compute_rows(self, [item.payload])
            if reply is None:
                return None
            return self._wrap_remote_rows([item], *reply)
        if kind == "batch":
            if self.batch_array:
                # the zero-copy columnar offload: stack once, ship the
                # block through the worker's shared-memory ring
                traces = None
                if self._tele is not None and self._tele.tracer.active:
                    traces = [m.meta.get(TRACE_KEY) if m.meta else None
                              for m in item]
                    if not any(t is not None for t in traces):
                        traces = None
                ab = ArrayBatch.try_stack([m.payload for m in item],
                                          seqs=[m.seq for m in item],
                                          keys=[m.key for m in item],
                                          traces=traces)
                if ab is not None:
                    rep = remote.compute_array(self, ab)
                    if rep is not None:
                        return self._remote_array_outputs(
                            proto, ab, rep, msgs=item)
            reply = remote.compute_rows(self, [m.payload for m in item])
            if reply is None:
                return None
            return self._wrap_remote_rows(item, *reply)
        # kind == "abatch": an ArrayBatch carrier
        ab = item.payload
        rep = remote.compute_array(self, ab)
        if rep is None:
            return None
        return self._remote_array_outputs(proto, ab, rep, port=item.port)

    def _wrap_remote_rows(self, msgs: List[Message], wire: List[tuple],
                          note: Optional[str]) -> List[Message]:
        """Map the worker's ``("ok", v)`` / ``("err", repr)`` rows back
        onto the engine's per-row error semantics — failed rows go through
        ``faults.on_row_error`` (retry/dead-letter) like any
        BatchItemError."""
        if note is not None and self.engine is not None:
            self.engine._record_error(
                self.name, RuntimeError(f"remote batch error: {note}"))
        results = [BatchItemError(RuntimeError(r[1])) if r[0] == "err"
                   else r[1] for r in wire]
        return self._wrap_results(msgs, results)

    def _remote_array_outputs(self, proto: Pellet, ab: ArrayBatch,
                              rep: dict, *,
                              msgs: Optional[List[Message]] = None,
                              port: str = "out") -> List[Message]:
        """Normalize a worker's columnar reply into output messages."""
        rows = len(ab)
        if rep["kind"] == "array":
            out = ArrayBatch(
                rep["array"],
                seqs=rep["seqs"] if rep["seqs"] is not None else ab.seqs,
                keys=rep["keys"] if rep["keys"] is not None else ab.keys,
                traces=ab.traces)
            if len(out) != rows:
                raise RuntimeError(
                    f"remote compute_array returned {len(out)} rows "
                    f"for {rows}")
            if self._tele_array is not None:
                self._tele_array.inc(rows)
            return [Message(payload=out, port=proto.out_ports[0])]
        if msgs is None:
            msgs = ab.to_messages(port=port)
        return self._wrap_remote_rows(msgs, rep["results"], rep["note"])

    def _batch_outputs(self, proto: Pellet,
                       item: List[Message]) -> List[Message]:
        """Row-wise batched compute: one compute_batch call, per-message
        lineage/wrap preserved.  The default compute_batch executes each
        payload exactly once and marks failures as BatchItemError entries,
        so error semantics stay message-granular with no double-execution
        of side effects."""
        payloads = [m.payload for m in item]
        chaos = self._chaos
        if chaos is not None:
            # chaos-armed stage: only the rows the rule selects crash
            # (BatchItemError), innocent batch-mates compute normally
            hits = chaos.scan(payloads)
            if hits:
                results: List[Any] = []
                for i, m in enumerate(item):
                    if i in hits:
                        results.append(BatchItemError(chaos.crash_exc()))
                        continue
                    try:
                        results.append(proto.compute(m.payload))
                    except Exception as e:
                        results.append(BatchItemError(e))
                return self._wrap_results(item, results)
        fn = getattr(proto, "compute_batch", None)
        try:
            if fn is not None:
                results = fn(payloads)
            else:
                results = PushPellet.compute_batch(proto, payloads)
            if len(results) != len(item):
                raise ValueError(
                    f"compute_batch returned {len(results)} results "
                    f"for {len(item)} payloads")
        except Exception as batch_exc:
            # a vectorized override failed as a unit; such overrides
            # must be side-effect free (documented, and the same
            # statelessness contract speculative re-execution relies
            # on), so recover by re-running per message — only
            # raising messages are dropped, the rest delivered
            results = []
            for m in item:
                try:
                    results.append(proto.compute(m.payload))
                except Exception as e:
                    results.append(BatchItemError(e))
            if not any(isinstance(r, BatchItemError)
                       for r in results) and self.engine is not None:
                # batch-level bug (e.g. wrong result count) that
                # per-message compute recovered from: deliver the
                # data, surface the bug
                self.engine._record_error(self.name, batch_exc)
        return self._wrap_results(item, results)

    def _wrap_results(self, item: List[Message],
                      results: List[Any]) -> List[Message]:
        outputs: List[Message] = []
        for m, r in zip(item, results):
            if isinstance(r, BatchItemError):
                if self.engine is not None:
                    faults = self.engine._faults
                    if faults is not None and faults.on_row_error(
                            self, m, r.exc):
                        continue
                    self.engine._record_error(self.name, r.exc)
                continue
            outputs.extend(self._wrap(r, m))
        return outputs

    def _array_outputs(self, proto: Pellet, *,
                       msgs: Optional[List[Message]] = None,
                       ab: Optional[ArrayBatch] = None
                       ) -> Optional[List[Message]]:
        """The columnar fast path: ONE compute_array call over a stacked
        batch, ONE carrier message out.

        Returns ``None`` when the fast path does not apply — ragged or
        non-stackable payloads, or a pellet whose ``compute_array``
        declines — and the caller falls back to the row-wise batched
        machinery.  A raising/misbehaving ``compute_array`` degrades to
        per-row ``compute`` with exactly the BatchItemError semantics of
        the row-wise path (only the raising row drops).
        """
        fn = getattr(proto, "compute_array", None)
        if fn is None:
            return None
        # decline BEFORE paying the stack: a pellet that never overrides
        # the hook (or a non-vectorized FnPellet) would only return
        # NotImplemented after an O(B) copy, every dispatch
        if type(proto).compute_array is PushPellet.compute_array:
            return None
        if isinstance(proto, FnPellet) and not proto.vectorized:
            return None
        if ab is None:
            traces = None
            if self._tele is not None and self._tele.tracer.active:
                traces = [m.meta.get(TRACE_KEY) if m.meta else None
                          for m in msgs]
                if not any(t is not None for t in traces):
                    traces = None
            ab = ArrayBatch.try_stack([m.payload for m in msgs],
                                      seqs=[m.seq for m in msgs],
                                      keys=[m.key for m in msgs],
                                      traces=traces)
            if ab is None:
                return None    # ragged / non-array payloads: fall back
        try:
            res = fn(ab.array)
        except Exception as exc:
            return self._degrade_rowwise(proto, ab, exc)
        if res is NotImplemented:
            return None
        rows = len(ab)
        if isinstance(res, ArrayBatch):
            if len(res) != rows:
                return self._degrade_rowwise(proto, ab, ValueError(
                    f"compute_array returned {len(res)} rows for {rows}"))
            if res.seqs is None:
                res.seqs = ab.seqs
            if res.keys is None:
                res.keys = ab.keys
            if res.traces is None:
                res.traces = ab.traces   # trace contexts ride the carrier
            if self._tele_array is not None:
                self._tele_array.inc(rows)
            return [Message(payload=res, port=proto.out_ports[0])]
        if hasattr(res, "ndim") and getattr(res, "ndim", 0) >= 1 \
                and res.shape[0] == rows \
                and getattr(res, "dtype", None) != object:
            out = ArrayBatch(res, seqs=ab.seqs, keys=ab.keys,
                             traces=ab.traces)
            if self._tele_array is not None:
                self._tele_array.inc(rows)
            return [Message(payload=out, port=proto.out_ports[0])]
        if isinstance(res, dict) and res and all(
                getattr(c, "ndim", 0) >= 1
                and c.shape[0] == rows
                and getattr(c, "dtype", None) != object
                for c in res.values()):
            # dict-of-arrays result: a multi-column carrier (every column
            # row-aligned with the input) — the serving plane's decode rows
            # carry token + slot id this way without ragged fallback
            out = ArrayBatch(res, seqs=ab.seqs, keys=ab.keys,
                             traces=ab.traces)
            if self._tele_array is not None:
                self._tele_array.inc(rows)
            return [Message(payload=out, port=proto.out_ports[0])]
        if isinstance(res, (list, tuple)) and len(res) == rows:
            # classic per-row vectorized contract (KeyedEmit / Drop /
            # multi-port dicts): correct, but the columnar hand-off ends
            # here — rows are wrapped individually
            if self._tele_array is not None:
                self._tele_array.inc(rows)
            return self._wrap_results(ab.to_messages(), list(res))
        return self._degrade_rowwise(proto, ab, ValueError(
            f"compute_array returned {type(res).__name__}, expected an "
            f"array with leading dim {rows} (or a {rows}-item sequence)"))

    def _degrade_rowwise(self, proto: Pellet, ab: ArrayBatch,
                         batch_exc: Exception) -> List[Message]:
        """Recover a failed array-batch by re-running per row — exactly
        the row-wise recovery contract: only raising rows are dropped
        (recorded), everything else is delivered."""
        msgs = ab.to_messages()
        results: List[Any] = []
        for m in msgs:
            try:
                results.append(proto.compute(m.payload))
            except Exception as e:
                results.append(BatchItemError(e))
        if not any(isinstance(r, BatchItemError) for r in results) \
                and self.engine is not None:
            # batch-level bug the per-row pass recovered from: deliver
            # the data, surface the bug
            self.engine._record_error(self.name, batch_exc)
        return self._wrap_results(msgs, results)

    def _wrap(self, result: Any, anchor: Message) -> List[Message]:
        """Normalize a compute() return value into output Messages."""
        if result is Drop or isinstance(result, Drop):
            return []
        default_port = self._proto.out_ports[0]
        outs: List[Message] = []

        def one(r):
            if r is Drop or isinstance(r, Drop) or r is None:
                return
            if isinstance(r, KeyedEmit):
                outs.append(anchor.derive(r.payload, key=r.key,
                                          port=r.port or default_port))
            elif isinstance(r, dict) and set(r) <= set(self._proto.out_ports):
                # multi-port emission: switch / if-then-else control flow
                for port, payload in r.items():
                    if payload is not Drop and payload is not None:
                        outs.append(anchor.derive(payload, port=port))
            else:
                outs.append(anchor.derive(r, port=default_port))

        if isinstance(result, list):
            for r in result:
                one(r)
        else:
            one(result)
        return outs

    def _finish(self, msg: Message, credits: int, forward: bool) -> None:
        """Forward landmarks/control messages downstream on all routes."""
        try:
            if forward:
                self._route(msg, broadcast=True)
        except Exception as e:
            if self.engine is not None:
                self.engine._record_error(self.name, e)
        finally:
            if self.engine is not None:
                self.engine._inflight_dec(credits)

    # -- output side -----------------------------------------------------------
    def _route(self, msg: Message, broadcast: bool = False) -> None:
        if _is_carrier(msg):
            self._route_carrier(msg)
            return
        route = self.routes.get(msg.port)
        if route is None:
            if broadcast and self.routes:  # landmark: fan out on every route
                for split, targets in self.routes.values():
                    for flake, dst_port in targets:
                        flake.enqueue(dst_port, msg)
                return
            if self.engine is not None:  # sink: collect (landmarks included)
                self.engine._collect_output(self.name, msg)
            return
        split, targets = route
        if not msg.is_data() and split.broadcast_specials():
            idxs = range(len(targets))
        else:
            depths = [t[0].queue_length() for t in targets]
            idxs = split.choose(msg, len(targets), depths)
        for i in idxs:
            flake, dst_port = targets[i]
            flake.enqueue(dst_port, msg)

    def _route_carrier(self, msg: Message) -> None:
        """Route an ArrayBatch carrier WITHOUT unstacking.

        Per-row destinations come from the split's ``choose_rows`` (key
        sidecar only) and the array is sliced once per destination group —
        one enqueue per downstream flake, rows in emit order so
        per-destination (and per-key, under hash) FIFO is preserved.
        Policies without a row path fall back to unstacked per-message
        routing, which owns the exact legacy semantics.
        """
        ab: ArrayBatch = msg.payload
        route = self.routes.get(msg.port)
        if route is None:
            if self.engine is not None:  # sink: rows surface as messages
                self.engine._collect_output(self.name, msg)
            return
        split, targets = route
        n = len(targets)
        if n == 1:
            targets[0][0].enqueue(targets[0][1], msg)
            return
        if split.broadcast_rows():
            for flake, dst_port in targets:   # shared, read-only carrier
                flake.enqueue(dst_port, msg)
            return
        depths = [t[0].queue_length() for t in targets]
        dests = split.choose_rows(len(ab), ab.keys, n, depths)
        if dests is None:
            # no vectorized row path (custom policy, keyless hash):
            # unstack and route rows through the per-message machinery
            for m in ab.to_messages(port=msg.port):
                self._route(m)
            return
        groups: Dict[int, List[int]] = {}
        for i, d in enumerate(dests):
            groups.setdefault(int(d), []).append(i)
        for d, rows in groups.items():
            flake, dst_port = targets[d]
            sub = ab if len(rows) == len(ab) else ab.take(rows)
            flake.enqueue(dst_port, Message(payload=sub, port=msg.port))

    def _route_many(self, msgs: List[Message]) -> None:
        """Amortized routing for a batch of emitted messages.

        Split evaluation runs once per (port, batch) via ``choose_many``
        (queue depths sampled once) and deliveries are grouped by
        destination ``(flake, dst_port)`` so downstream enqueue accounting
        is paid per group, not per message.  Per-destination FIFO order is
        preserved (groups are filled in emit order).  Any special message
        in the batch falls back to the per-message path, which owns the
        broadcast/alignment semantics; ArrayBatch carriers route whole
        via ``_route_carrier``.
        """
        if not msgs:
            return
        if len(msgs) == 1 or any(not m.is_data()
                                 or isinstance(m.payload, ArrayBatch)
                                 for m in msgs):
            for m in msgs:
                self._route(m)
            return
        by_port: Dict[str, List[Message]] = {}
        sink: List[Message] = []
        for m in msgs:
            if m.port in self.routes:
                by_port.setdefault(m.port, []).append(m)
            else:
                # unrouted ports all land on the coordinator's shared
                # output list: collect them in one pass so cross-port emit
                # order is preserved (grouping by port would reorder it)
                sink.append(m)
        if sink and self.engine is not None:
            self.engine._collect_outputs(self.name, sink)
        # split evaluation amortized per out-port ...
        targets_of: Dict[str, List[Tuple["Flake", str]]] = {}
        choice_of: Dict[int, List[int]] = {}
        for port, ms in by_port.items():
            split, targets = self.routes[port]
            depths = [t[0].queue_length() for t in targets]
            targets_of[port] = targets
            for m, idxs in zip(ms, split.choose_many(ms, len(targets),
                                                     depths)):
                choice_of[id(m)] = idxs
        # ... but destination buckets fill in GLOBAL emit order, so a
        # destination fed from several out-ports sees the exact
        # per-message interleaving, not port-grouped bursts
        buckets: Dict[Tuple["Flake", str], List[Message]] = {}
        for m in msgs:
            idxs = choice_of.get(id(m))
            if idxs is None:
                continue   # sink message, already collected
            targets = targets_of[m.port]
            for i in idxs:
                buckets.setdefault(targets[i], []).append(m)
        for (flake, dst_port), bucket in buckets.items():
            flake.enqueue_many(dst_port, bucket)

    # -- quiescence bookkeeping --------------------------------------------------
    def _inflight_inc_local(self) -> None:
        with self._inflight_cond:
            self._inflight += 1

    def _inflight_dec_local(self) -> None:
        with self._inflight_cond:
            self._inflight -= 1
            self._inflight_cond.notify_all()

    def _wait_quiescent(self, timeout: float = 30.0, own: int = 0) -> bool:
        """Wait until no work but the caller's ``own`` counts is in
        flight, and no pop is under way."""
        deadline = time.time() + timeout
        with self._inflight_cond:
            return self._inflight_cond.wait_for(
                lambda: self._inflight <= own and not self._popping,
                timeout=max(0.0, deadline - time.time()))


class Container:
    """Resource runtime at VM granularity (§III): core accounting for flakes."""

    def __init__(self, name: str, cores: int = 8):
        self.name = name
        self.total_cores = cores
        self.allocated: Dict[str, int] = {}
        self._lock = threading.Lock()

    @property
    def free_cores(self) -> int:
        return self.total_cores - sum(self.allocated.values())

    def allocate(self, flake_name: str, cores: int,
                 force: bool = False) -> bool:
        """Reserve cores.  ``force`` oversubscribes past the budget — used
        only by cluster placement fallback, and always ledger-recorded."""
        with self._lock:
            if cores > self.free_cores and not force:
                return False
            self.allocated[flake_name] = self.allocated.get(flake_name, 0) + cores
            return True

    def release(self, flake_name: str, cores: Optional[int] = None) -> int:
        """Return cores to the budget; reports how many were actually freed.

        The return value is the release-on-deactivate audit: callers that
        tear down or migrate a flake away compare it against the cores the
        flake was believed to hold, so a long-running session cannot leak
        capacity silently.
        """
        with self._lock:
            held = self.allocated.get(flake_name, 0)
            if held == 0:
                return 0
            if cores is None or cores >= held:
                self.allocated.pop(flake_name)
                return held
            self.allocated[flake_name] = held - cores
            return cores


class Coordinator:
    """Application runtime at graph granularity (§III).

    Parses the FloeGraph, acquires cores on containers via best-fit,
    instantiates flakes, wires them bottom-up (sinks before sources), and
    exposes management operations: inject inputs, pause/resume, dynamic task
    and dataflow updates, and graceful shutdown.  Outputs of sink pellets are
    collected into ``self.outputs``.
    """

    def __init__(self, graph: FloeGraph, *,
                 containers: Optional[List[Container]] = None,
                 cluster=None,
                 channel_capacity: int = 100_000,
                 speculative_timeout: Optional[float] = None,
                 telemetry: Union[bool, Telemetry] = True,
                 trace_sample: float = 0.0,
                 recovery=None):
        graph.validate()
        self.graph = graph
        #: the ops plane: metrics registry + event bus + tracer.  Always
        #: present as an object (so call sites never branch on None), but
        #: with ``telemetry=False`` every hot-path hook is inert — the
        #: configuration the overhead guard benches against.
        if isinstance(telemetry, Telemetry):
            self.telemetry = telemetry
        else:
            self.telemetry = Telemetry(enabled=bool(telemetry),
                                       trace_sample=trace_sample)
        if self.telemetry.enabled:
            self.telemetry.bind_engine_collector(self)
        #: cluster mode (``repro.cluster.ClusterManager``): hosts own the
        #: containers, placement/migration/transports are cluster-managed
        self.cluster = cluster
        if cluster is not None:
            if containers is not None:
                raise ValueError(
                    "pass either containers (single-process mode) or "
                    "cluster, not both")
            cluster.bind(self)
            self.containers = [h.container for h in cluster.hosts.values()]
        else:
            self.containers = containers or [Container("c0", cores=64)]
        #: which container each flake's cores are accounted to (release-on-
        #: deactivate audit; in cluster mode kept in step by migration)
        self._container_of: Dict[str, Container] = {}
        self.flakes: Dict[str, Flake] = {}
        self.outputs: List[Message] = []   # guarded-by: _out_lock
        self._out_lock = threading.Lock()
        self.errors: List[Tuple[str, Exception]] = []
        self._inflight = 0             # guarded-by: _iq
        self._iq = threading.Condition()
        #: injection vs migration handoff: resolving a flake name and
        #: enqueuing into it must be atomic against the backlog transfer,
        #: or a message injected mid-migration strands in the retired
        #: flake (lost payload + a leaked inflight credit that wedges
        #: quiescence for the life of the session)
        self._inject_lock = threading.Lock()
        #: serializes structural mutations (transact / task updates /
        #: migrations) — e.g. a controller-driven scale-out migrating the
        #: same flake a user migrate is moving would split the backlog
        self._wiring_lock = threading.RLock()
        self._active = False
        self._channel_capacity = channel_capacity
        self._speculative_timeout = speculative_timeout
        #: monotonically increasing structural version: bumped once per
        #: committed ``transact`` that changed anything (swap / rewire /
        #: scale / vertex add / vertex remove), never on aborts
        self.topology_version = 0
        #: structural diff summary of the last committed transaction
        self.last_transaction: Optional[Dict[str, Any]] = None
        self._stopped = False
        self._stop_lock = threading.Lock()
        #: fault-tolerance plane (``recovery=RecoveryPolicy(...)``):
        #: heartbeat failure detection, auto-checkpointing + source
        #: journal, host recovery, row retry/dead-letter.  None (one
        #: attribute check on cold error paths) when not configured.
        self._faults = None
        if recovery is not None:
            from ..faults.plane import FaultPlane
            self._faults = FaultPlane(self, recovery)

    # -- engine-wide quiescence ---------------------------------------------
    def _inflight_inc(self, n: int = 1) -> None:
        with self._iq:
            self._inflight += n

    def _inflight_dec(self, n: int = 1) -> None:
        with self._iq:
            self._inflight -= n
            if self._inflight <= 0:
                self._iq.notify_all()

    def _record_error(self, flake: str, exc: Exception) -> None:
        self.errors.append((flake, exc))
        if self.telemetry.enabled:
            self.telemetry.errors.labels(stage=flake).inc()
            self.telemetry.events.emit(
                "error", flake=flake, error=repr(exc))

    def _host_label(self, name: str) -> str:
        """Host a flake currently runs on ('local' in single-process mode)."""
        if self.cluster is not None:
            return self.cluster.host_label(name)
        return "local"

    def _collect_output(self, flake: str, msg: Message) -> None:
        if _is_carrier(msg):
            # a columnar batch leaving the dataflow surfaces as ordinary
            # per-row messages, so drain_outputs/census tooling is
            # payload-container agnostic
            msgs = msg.payload.to_messages(port=msg.port)
            with self._out_lock:
                self.outputs.extend(msgs)
            return
        with self._out_lock:
            self.outputs.append(msg)

    def _collect_outputs(self, flake: str, msgs: List[Message]) -> None:
        msgs = _degrade_carriers(msgs)
        with self._out_lock:
            self.outputs.extend(msgs)

    # -- lifecycle -------------------------------------------------------------
    def start(self) -> "Coordinator":
        order = self.graph.wiring_order()  # bottom-up BFS, loops ignored (§III)
        if self.cluster is not None:
            # host-aware placement: policy + place/colocate annotations
            placement = self.cluster.place_all(self.graph, order)
        for name in order:
            v = self.graph.vertices[name]
            if self.cluster is not None:
                self._container_of[name] = placement[name].container
            else:
                placed = False
                # best-fit container selection (§III)
                for c in sorted(self.containers, key=lambda c: c.free_cores):
                    if c.allocate(name, v.cores):
                        placed = True
                        break
                if not placed:
                    # elastic acquisition: the resource manager would request
                    # a new VM from the Cloud fabric; locally we add a
                    # container.
                    c = Container(f"c{len(self.containers)}",
                                  cores=max(8, v.cores))
                    c.allocate(name, v.cores)
                    self.containers.append(c)
                self._container_of[name] = c
            self.flakes[name] = Flake(
                name, v.factory, cores=v.cores, engine=self,
                channel_capacity=self._channel_capacity,
                speculative_timeout=self._speculative_timeout,
                batch_max=v.annotations.get("batch_max"),
                batch_wait_ms=v.annotations.get("batch_wait_ms", 0.0),
                batch_array=v.annotations.get("batch_array", False))
        # wire routes + landmark in-degrees (same derivation as a dynamic
        # dataflow update, so started and recomposed sessions never drift)
        self.apply_wiring(self.graph)
        # activate in wiring order: downstream pellets first (§III)
        for name in order:
            self.flakes[name].activate()
        self._active = True
        if self._faults is not None:
            self._faults.start()
        return self

    def stop(self) -> None:
        """Idempotent, exception-safe shutdown: a second call is a no-op,
        and a failure in one flake's teardown never skips the others or
        leaks container cores / cluster bindings.  The first exception is
        re-raised once cleanup has run to completion."""
        with self._stop_lock:
            if self._stopped:
                return
            self._stopped = True
        first_exc: Optional[BaseException] = None
        if self._faults is not None:
            try:
                self._faults.stop()
            except BaseException as e:
                first_exc = e
        for name, f in self.flakes.items():
            try:
                f.deactivate()
            except BaseException as e:
                if first_exc is None:
                    first_exc = e
            # release-on-deactivate: return the flake's cores to its
            # container so capacity cannot leak across session lifetimes
            c = self._container_of.pop(name, None)
            if c is not None:
                try:
                    c.release(name)
                except BaseException as e:
                    if first_exc is None:
                        first_exc = e
        if self.cluster is not None:
            # forget this graph's placements (the fleet survives, so a
            # prebuilt ClusterManager can host the next session)
            try:
                self.cluster.unbind(self)
            except BaseException as e:
                if first_exc is None:
                    first_exc = e
        self._active = False
        if first_exc is not None:
            raise first_exc

    def core_audit(self) -> Dict[str, Dict[str, int]]:
        """Outstanding per-container allocations (empty after ``stop``)."""
        containers = ([h.container for h in self.cluster.hosts.values()]
                      if self.cluster is not None else self.containers)
        return {c.name: dict(c.allocated) for c in containers if c.allocated}

    # -- I/O ---------------------------------------------------------------------
    def inject(self, flake_name: str, payload: Any, *, port: str = "in",
               key: Any = None) -> None:
        """Pass inputs to the dataflow via the input port endpoint (§III)."""
        msg = Message(payload=payload, key=key)
        tele = self.telemetry
        if tele.enabled:
            tele.injected.inc()
            if tele.tracer.active:
                ctx = tele.tracer.maybe_trace()
                if ctx is not None:
                    msg.meta[TRACE_KEY] = ctx
        with (span("floe.inject", stage=flake_name, rows=1)
              if tele.enabled else NO_SPAN):
            with self._inject_lock:
                self.flakes[flake_name].enqueue(port, msg)
                if self._faults is not None:
                    self._faults.journal_rows(
                        flake_name, port, (payload,),
                        None if key is None else (key,))

    def inject_many(self, flake_name: str, payloads: List[Any], *,
                    port: str = "in",
                    keys: Optional[List[Any]] = None,
                    stacked: bool = False) -> None:
        """Source-side amortized injection: one batched enqueue for a whole
        payload list (inflight accounting, arrival stats and the channel
        append via ``Channel.put_many`` are each paid once per batch, not
        once per message).  ``keys`` optionally aligns a routing key per
        payload (for hash splits / dynamic port mapping).

        With ``stacked=True`` the payloads are stacked into ONE ArrayBatch
        carrier at the source — the columnar fast path starts at injection
        instead of at the first array stage, so a vectorized head stage
        gets a single ``compute_array`` call with no per-message wrapping
        at all.  Ragged / non-stackable payloads fall back to the
        per-message path transparently; a target that cannot consume
        carriers degrades on enqueue as usual.  Rows are telemetry-counted
        from birth either way.
        """
        if keys is not None and len(keys) != len(payloads):
            raise ValueError(
                f"inject_many: {len(keys)} keys for {len(payloads)} payloads")
        tele = self.telemetry
        with (span("floe.inject", stage=flake_name, rows=len(payloads))
              if tele.enabled else NO_SPAN):
            tracing = tele.enabled and tele.tracer.active
            if tele.enabled:
                tele.injected.inc(len(payloads))
            if stacked and payloads:
                traces = None
                if tracing:
                    traces = [tele.tracer.maybe_trace() for _ in payloads]
                    if not any(t is not None for t in traces):
                        traces = None
                ab = ArrayBatch.try_stack(
                    payloads, seqs=[_next_seq() for _ in payloads],
                    keys=keys, traces=traces)
                if ab is not None:
                    if tele.enabled:
                        tele.stacked_injections.inc()
                    with self._inject_lock:
                        self.flakes[flake_name].enqueue(
                            port, Message(payload=ab))
                        if self._faults is not None:
                            self._faults.journal_rows(
                                flake_name, port, payloads, keys)
                    return
                # ragged payloads: fall through to the per-message path (any
                # contexts handed out above are reused row-aligned below)
                if traces is not None:
                    msgs = [Message(payload=p,
                                    key=keys[i] if keys is not None else None)
                            for i, p in enumerate(payloads)]
                    for m, ctx in zip(msgs, traces):
                        if ctx is not None:
                            m.meta[TRACE_KEY] = ctx
                    with self._inject_lock:
                        self.flakes[flake_name].enqueue_many(port, msgs)
                        if self._faults is not None:
                            self._faults.journal_rows(
                                flake_name, port, payloads, keys)
                    return
            msgs = [Message(payload=p, key=keys[i] if keys is not None else None)
                    for i, p in enumerate(payloads)]
            if tracing:
                for m in msgs:
                    ctx = tele.tracer.maybe_trace()
                    if ctx is not None:
                        m.meta[TRACE_KEY] = ctx
            with self._inject_lock:
                self.flakes[flake_name].enqueue_many(port, msgs)
                if self._faults is not None:
                    self._faults.journal_rows(flake_name, port, payloads, keys)

    def inject_landmark(self, flake_name: str, tag: Any = None,
                        port: str = "in") -> None:
        from .message import landmark
        with self._inject_lock:
            self.flakes[flake_name].enqueue(port, landmark(tag))
            if self._faults is not None:
                self._faults.journal_landmark(flake_name, port, tag)

    def run_until_quiescent(self, timeout: float = 60.0) -> bool:
        """Block until no message is in flight anywhere in the graph."""
        deadline = time.time() + timeout
        with self._iq:
            return self._iq.wait_for(
                lambda: self._inflight <= 0,
                timeout=max(0.0, deadline - time.time()))

    def drain_outputs(self) -> List[Message]:
        with self._out_lock:
            out, self.outputs = self.outputs, []
            return out

    @contextmanager
    def frozen(self, timeout: float = 30.0):
        """Freeze the dataflow for a consistent cut (checkpointing).

        Every flake stops dispatching, in-flight tasks run to completion
        and deliver their outputs, structural mutations and injection are
        blocked — so pellet state, half-gathered windows, and channel
        backlogs are a single consistent snapshot.  Unlike
        ``run_until_quiescent`` this does NOT require empty queues: parked
        backlog is exactly what a checkpoint wants to capture.  Raises
        ``TimeoutError`` (and unfreezes) if in-flight work cannot finish
        within ``timeout``.
        """
        with self._wiring_lock:
            flakes = list(self.flakes.values())
            for f in flakes:
                f._drain_acquire()
            try:
                deadline = time.time() + timeout
                for f in flakes:
                    if not f._wait_quiescent(
                            timeout=max(0.0, deadline - time.time())):
                        raise TimeoutError(
                            f"flake {f.name!r} did not quiesce within "
                            f"{timeout}s; snapshot aborted")
                with self._inject_lock:
                    yield self
            finally:
                for f in flakes:
                    f._drain_release()

    # -- dynamism (§II.B) ----------------------------------------------------------
    def update_pellet(self, name: str, factory: Callable[[], Pellet], *,
                      mode: str = "sync", emit_update_landmark: bool = True) -> None:
        """Dynamic task update: in-place swap of one pellet's logic."""
        with self._wiring_lock:   # vs a concurrent migration of the flake
            self.flakes[name].swap_pellet(
                factory, mode=mode, emit_update_landmark=emit_update_landmark)

    def update_subgraph(self, factories: Dict[str, Callable[[], Pellet]], *,
                        mode: str = "sync") -> None:
        """Dynamic dataflow update: coordinated multi-pellet swap (§II.B).

        All named pellets are drained together (slowest pellet bounds the
        synchronization cost, as the paper notes), then swapped
        simultaneously, then resumed together.  In sync mode a pellet that
        cannot quiesce within 30s raises ``TimeoutError`` and NOTHING is
        applied (abort-before-change; previously the swap proceeded after a
        silent best-effort wait).
        """
        if mode == "sync":
            self.transact(swaps=factories)
            return
        with self._wiring_lock:
            for n, factory in factories.items():
                self.flakes[n].swap_pellet(factory, mode="async",
                                           emit_update_landmark=False)
            from .message import update_landmark
            for n in factories:
                self.flakes[n]._route(
                    update_landmark(tag={"subgraph": list(factories)}),
                    broadcast=True)

    def transact(self, *, swaps: Optional[Dict[str, Callable[[], Pellet]]] = None,
                 graph: Optional[FloeGraph] = None,
                 cores: Optional[Dict[str, int]] = None,
                 extra_drain: Tuple[str, ...] = (),
                 quiesce_timeout: float = 30.0,
                 swap_protos: Optional[Dict[str, Pellet]] = None,
                 remove_backlog: Optional[Dict[str, Any]] = None,
                 add_protos: Optional[Dict[str, Pellet]] = None,
                 replace: Optional[Dict[str, Callable[[], Pellet]]] = None,
                 replace_protos: Optional[Dict[str, Pellet]] = None
                 ) -> Dict[str, Any]:
        """Coordinated §II.B change set applied as one atomic step.

        Drains the union of swapped pellets and ``extra_drain`` together,
        aborts with ``TimeoutError`` (before any change) if a flake cannot
        quiesce within ``quiesce_timeout``, then swaps pellet logic, adopts
        ``graph``'s wiring (if given), applies core changes, emits one
        coordinated update landmark per swapped pellet, and resumes.  This
        is the engine primitive behind ``update_subgraph`` (sync mode) and
        the Session API's transactional ``recompose`` / ``apply``.

        ``graph`` may name a *different vertex set* than the running one —
        the structural diff is committed in the same atomic step:

        * vertices present only in ``graph`` are **added**: fresh flakes
          are spawned (cluster placement annotations honored when a
          ``ClusterManager`` is bound, best-fit containers otherwise),
          wired, and activated downstream-first.  A placement failure
          rolls back every allocation made so far and aborts the whole
          transaction.
        * vertices absent from ``graph`` are **removed**: the flake and
          every upstream neighbour drain together with the rest of the
          affected set, then the flake retires — its cores audited back
          to its container.  Whatever is still queued in its channels
          (plus a half-gathered window buffer) is disposed per
          ``remove_backlog[name]``: ``"drop"`` (default — discarded,
          credits released, count surfaced in the summary),
          ``"collect"`` (surfaced to the caller in the summary's
          ``backlog`` map), or ``(stage, port)`` (rerouted: raw FIFO
          hand-off into another stage's input, migration-style, credits
          moving with the messages).

        ``replace`` stages a **same-name replacement with a changed port
        signature**: the named flake retires and a fresh one (built from
        the new factory) takes its name in the same atomic step.  Unlike a
        ``swap``, ports may differ — the new wiring in ``graph`` is
        validated against the replacement proto's ports up front.  Channel
        backlog carries over FIFO for input ports the new signature keeps;
        rows on retired ports are dropped (credits released, counts
        surfaced in the summary).  Pellet/window state does NOT transfer —
        a replacement is new logic, not a task update.

        Returns the structural diff summary of the commit (also stored as
        ``self.last_transaction``); ``topology_version`` bumps once per
        committed transaction that changed anything.
        """
        with self._wiring_lock:   # vs concurrent migrations / task updates
            return self._transact_locked(swaps, graph, cores, extra_drain,
                                         quiesce_timeout, swap_protos,
                                         remove_backlog, add_protos,
                                         replace, replace_protos)

    def _transact_locked(self, swaps, graph, cores, extra_drain,
                         quiesce_timeout, swap_protos,
                         remove_backlog=None, add_protos=None,
                         replace=None, replace_protos=None
                         ) -> Dict[str, Any]:
        swaps = dict(swaps or {})
        cores = dict(cores or {})
        remove_backlog = dict(remove_backlog or {})
        replace = dict(replace or {})
        # validate EVERYTHING up front so a bad input aborts before any
        # change is applied (the atomicity contract above)
        protos = dict(swap_protos or {})
        added: List[str] = []
        removed: List[str] = []
        if graph is not None:
            graph.validate()
            added = [n for n in graph.vertices if n not in self.flakes]
            removed = [n for n in self.flakes if n not in graph.vertices]
            for e in graph.edges:
                if e.split not in SPLITS:
                    raise ValueError(f"transact: unknown split {e.split!r}")
        elif remove_backlog:
            raise ValueError("transact: remove_backlog requires a graph "
                             "naming the post-removal vertex set")
        for n in {*swaps, *cores, *extra_drain}:
            if n not in self.flakes:
                raise ValueError(f"transact: unknown flake {n!r}")
            if n in removed and n in set(swaps) | set(cores):
                raise ValueError(
                    f"transact: {n!r} is being removed; it cannot also be "
                    "swapped or scaled in the same transaction")
        for n, factory in swaps.items():
            new_proto = protos.get(n) or factory()
            protos[n] = new_proto
            old = self.flakes[n]._proto
            if tuple(new_proto.in_ports) != tuple(old.in_ports) or \
               tuple(new_proto.out_ports) != tuple(old.out_ports):
                raise ValueError(
                    f"transact: swap of {n!r} requires identical ports "
                    "(use a dynamic dataflow update instead, §II.B)")
        cores = {n: int(c) for n, c in cores.items()}
        # prebuilt/validated protos (the API layer's, so each added
        # factory runs once per commit); missing entries are built here
        added_protos: Dict[str, Pellet] = {}
        for n in added:
            p = (add_protos or {}).get(n) or graph.vertices[n].factory()
            if not isinstance(p, Pellet):
                raise ValueError(
                    f"transact: added stage {n!r} factory produced "
                    f"{type(p).__name__}, expected a Pellet")
            added_protos[n] = p
        for n, policy in remove_backlog.items():
            if n not in removed:
                raise ValueError(
                    f"transact: remove_backlog names {n!r}, which is not "
                    "being removed")
            if isinstance(policy, tuple):
                dst, dport = policy
                if dst not in graph.vertices:
                    raise ValueError(
                        f"transact: backlog of {n!r} rerouted to {dst!r}, "
                        "which is not in the post-change graph")
                dproto = added_protos.get(dst) or self.flakes[dst]._proto
                if dport not in dproto.in_ports:
                    raise ValueError(
                        f"transact: backlog reroute target {dst!r} has no "
                        f"input port {dport!r}; in={list(dproto.in_ports)}")
            elif policy not in ("drop", "collect"):
                raise ValueError(
                    f"transact: remove_backlog[{n!r}] must be 'drop', "
                    f"'collect' or (stage, port); got {policy!r}")
        # same-name replacements: the fresh proto's ports are the ground
        # truth the new wiring must satisfy (validated BEFORE any change)
        rprotos: Dict[str, Pellet] = dict(replace_protos or {})
        if replace and graph is None:
            raise ValueError("transact: replace requires a graph naming "
                             "the post-change topology")
        for n, factory in replace.items():
            if n not in self.flakes:
                raise ValueError(f"transact: replace names unknown "
                                 f"flake {n!r}")
            if n not in graph.vertices:
                raise ValueError(f"transact: replaced stage {n!r} is "
                                 "missing from the new graph")
            if n in set(swaps) | set(cores):
                raise ValueError(
                    f"transact: {n!r} is being replaced; it cannot also "
                    "be swapped or scaled in the same transaction")
            p = rprotos.get(n) or factory()
            if not isinstance(p, Pellet):
                raise ValueError(
                    f"transact: replacement of {n!r} produced "
                    f"{type(p).__name__}, expected a Pellet")
            rprotos[n] = p
            for e in graph.edges:
                if e.src == n and e.src_port not in p.out_ports:
                    raise ValueError(
                        f"transact: replacement {n!r} has no OUTPUT port "
                        f"{e.src_port!r}; out={list(p.out_ports)}")
                if e.dst == n and e.dst_port not in p.in_ports:
                    raise ValueError(
                        f"transact: replacement {n!r} has no INPUT port "
                        f"{e.dst_port!r}; in={list(p.in_ports)}")
        # the removed/replaced flakes' upstreams must be part of the drain
        # set, or a neighbour could be mid-send while the backlog is popped
        upstream_removed = {e.src for n in removed
                            for e in self.graph.in_edges(n)} - set(removed)
        upstream_replaced = {e.src for n in replace
                             for e in self.graph.in_edges(n)} - set(replace)
        affected = set(swaps) | set(extra_drain) | set(removed) \
            | upstream_removed | set(replace) | upstream_replaced
        flakes = [self.flakes[n] for n in sorted(affected)]
        for f in flakes:
            f._drain_acquire()
        retired: Dict[str, Flake] = {}
        summary: Dict[str, Any] = {}
        try:
            # ONE shared deadline across all flakes, so an abort happens
            # within quiesce_timeout wall-clock, not N x quiesce_timeout
            deadline = time.time() + quiesce_timeout
            for f in flakes:
                if not f._wait_quiescent(
                        timeout=max(0.0, deadline - time.time())):
                    # abort BEFORE any change: atomicity over progress —
                    # committing with messages still in flight would let
                    # old outputs route along the new topology
                    raise TimeoutError(
                        f"flake {f.name!r} did not quiesce within "
                        f"{quiesce_timeout}s")
            # spawn the added flakes first (they are invisible until wired,
            # so a placement failure can still roll back to a zero-change
            # state: release the cores, abort, nothing else moved)
            add_order = [n for n in graph.wiring_order() if n in added] \
                if added else []
            spawned = self._spawn_added(graph, add_order, added_protos)
            try:
                replaced_new = self._spawn_replacements(graph, replace,
                                                        rprotos)
            except Exception:
                # the added flakes were built but never wired: unwind
                # their allocations too, or an aborted transaction leaks
                # cores/placements on every retry
                self._rollback_spawn(add_order)
                raise
            for n, factory in swaps.items():
                self.flakes[n].swap_pellet(factory, mode="async",
                                           emit_update_landmark=False,
                                           new_proto=protos[n])
            old_graph = self.graph
            retired_replaced: Dict[str, Flake] = {}
            if graph is not None:
                # retire/adopt the vertex-set delta atomically vs injection:
                # a racing inject must either land before the pop (and be
                # disposed with the backlog) or fail to resolve the removed
                # stage — never strand in a dead flake's channels
                backlogs: Dict[str, List[Message]] = {}
                carried: Dict[str, Dict[str, List[Message]]] = {}
                with self._inject_lock:
                    for n in removed:
                        retired[n] = self.flakes.pop(n)
                        backlogs[n] = self._pop_backlog(retired[n])
                    for n, f in replaced_new.items():
                        old_f = self.flakes[n]
                        retired_replaced[n] = old_f
                        # FIFO backlog hand-off, migration-style: credits
                        # move with the messages; ports the new signature
                        # dropped are disposed below
                        carried[n] = {p: ch.pop_up_to(None)
                                      for p, ch in old_f.inputs.items()}
                        # landmark-alignment progress is an input-side
                        # property, independent of pellet logic: move it
                        # (as migration does) so a half-counted flush
                        # round is completed by apply_wiring below, not
                        # silently lost
                        with old_f._lm_lock:
                            f.in_degree = old_f.in_degree
                            f._lm_count = old_f._lm_count
                            f._lm_pending = old_f._lm_pending
                        self.flakes[n] = f
                    self.flakes.update(spawned)
                self.apply_wiring(graph)
                for n, msgs in backlogs.items():
                    self._dispose_backlog(
                        n, msgs, remove_backlog.get(n, "drop"), summary)
                for n, by_port in carried.items():
                    self._readmit_replaced_backlog(
                        n, retired_replaced[n], by_port, summary)
                # activate downstream-first, same discipline as start()
                for n in add_order:
                    spawned[n].activate()
                for n in replaced_new:
                    replaced_new[n].activate()
            for n, c in cores.items():
                self.set_cores(n, c)
            # one coordinated update landmark from each swapped pellet
            if swaps:
                from .message import update_landmark
                for n in swaps:
                    self.flakes[n]._route(
                        update_landmark(tag={"subgraph": sorted(swaps),
                                             "flake": n}),
                        broadcast=True)
            e_added, e_removed = _edge_delta(old_graph, self.graph) \
                if graph is not None else ([], [])
            changed = bool(swaps or cores or added or removed or replace
                           or e_added or e_removed)
            if changed:
                self.topology_version += 1
            summary.update({
                "version": self.topology_version,
                "changed": changed,
                "swapped": sorted(swaps),
                "scaled": dict(cores),
                "added": sorted(added),
                "removed": sorted(removed),
                "replaced": sorted(replace),
                "edges_added": e_added,
                "edges_removed": e_removed,
                "removed_backlog": {n: _rows_total(b) for n, b in
                                    (backlogs.items() if removed else ())},
            })
            if changed and self.telemetry.enabled:
                # a replaced stage spawns with fresh FlakeStats but its
                # label-keyed histograms persist by name: reset them so
                # post-replacement percentiles reflect the new logic only
                for n in replace:
                    self.telemetry.reset_stage(n)
                self.telemetry.events.emit(
                    "transaction",
                    version=self.topology_version,
                    swapped=sorted(swaps), scaled=dict(cores),
                    added=sorted(added), removed=sorted(removed),
                    replaced=sorted(replace),
                    edges_added=e_added, edges_removed=e_removed)
        finally:
            for f in flakes:
                f._drain_release()
        # retire outside the drain window (deactivate joins the dispatch
        # thread, which needs the drain released to observe _stop quickly)
        for n, f in retired.items():
            f.deactivate()
            c = self._container_of.pop(n, None)
            if c is not None:
                freed = c.release(n)
                if freed != f.cores:
                    self._record_error(n, RuntimeError(
                        f"core-accounting drift on removal: container held "
                        f"{freed}, flake had {f.cores}"))
            if self.cluster is not None:
                self.cluster.unplace(n, release_cores=False)
            # belt-and-braces for callers that held a direct reference to
            # the retired flake across the swap: dispose anything they
            # enqueued into its (now dead) channels under the same policy
            leftovers = self._pop_backlog(f)
            if leftovers:
                self._dispose_backlog(n, leftovers,
                                      remove_backlog.get(n, "drop"), summary)
                summary["removed_backlog"][n] = \
                    summary["removed_backlog"].get(n, 0) \
                    + _rows_total(leftovers)
        for n, f in retired_replaced.items():
            f.deactivate()
            try:
                f._proto.teardown()   # old logic retired for good
            except Exception:
                pass
            # belt-and-braces sweep, like migration: anything a stale
            # reference enqueued into the dead flake moves to the
            # replacement (surviving ports) or is disposed
            leftovers = {p: ch.pop_up_to(None)
                         for p, ch in f.inputs.items()}
            if any(leftovers.values()):
                self._readmit_replaced_backlog(n, f, leftovers, summary)
        if summary.get("changed"):
            # the stored copy drops the raw collected Messages: they belong
            # to the caller of THIS commit, and pinning a whole backlog on
            # the coordinator until the next transaction would be an
            # unbounded retention
            self.last_transaction = {k: v for k, v in summary.items()
                                     if k != "backlog"}
        return summary

    def _spawn_added(self, graph: Optional[FloeGraph], add_order: List[str],
                     added_protos: Dict[str, Pellet]) -> Dict[str, "Flake"]:
        """Allocate cores and build (but not wire/activate) added flakes.

        All-or-nothing: any placement/allocation failure releases every
        core and placement taken so far and re-raises, leaving the running
        graph untouched.
        """
        spawned: Dict[str, Flake] = {}
        try:
            placement = (self.cluster.place_all(graph, add_order)
                         if self.cluster is not None and add_order else {})
            for n in add_order:
                v = graph.vertices[n]
                if self.cluster is not None:
                    self._container_of[n] = placement[n].container
                else:
                    placed = None
                    for c in sorted(self.containers,
                                    key=lambda c: c.free_cores):
                        if c.allocate(n, v.cores):
                            placed = c
                            break
                    if placed is None:
                        placed = Container(f"c{len(self.containers)}",
                                           cores=max(8, v.cores))
                        placed.allocate(n, v.cores)
                        self.containers.append(placed)
                    self._container_of[n] = placed
                spawned[n] = Flake(
                    n, v.factory, cores=v.cores, engine=self,
                    channel_capacity=self._channel_capacity,
                    speculative_timeout=self._speculative_timeout,
                    batch_max=v.annotations.get("batch_max"),
                    batch_wait_ms=v.annotations.get("batch_wait_ms", 0.0),
                    batch_array=v.annotations.get("batch_array", False),
                    proto=added_protos[n])
        except Exception:
            self._rollback_spawn(add_order)
            raise
        return spawned

    def _rollback_spawn(self, add_order: List[str]) -> None:
        """Release every core/placement taken for not-yet-wired added
        flakes (all-or-nothing abort of a spawning transaction)."""
        for n in add_order:
            c = self._container_of.pop(n, None)
            if c is not None and self.cluster is None:
                c.release(n)
            if self.cluster is not None:
                # releases the host container's cores and forgets the
                # placement/home bookkeeping in one step
                self.cluster.unplace(n)

    def _spawn_replacements(self, graph: Optional[FloeGraph],
                            replace: Dict[str, Callable[[], Pellet]],
                            rprotos: Dict[str, Pellet]
                            ) -> Dict[str, "Flake"]:
        """Build (not wire/activate) same-name replacement flakes.

        The replacement stays on the old flake's container; only the core
        *delta* against the new blueprint is allocated/released.  All-or-
        nothing: a failed grant rolls back every adjustment made so far
        and re-raises, leaving the running graph untouched.
        """
        out: Dict[str, Flake] = {}
        adjusted: List[Tuple[Container, str, int]] = []
        try:
            for n, factory in replace.items():
                old = self.flakes[n]
                c = self._container_of[n]
                v = graph.vertices[n]
                delta = v.cores - old.cores
                if delta > 0:
                    if not c.allocate(n, delta):
                        raise RuntimeError(
                            f"transact: container {c.name!r} cannot grant "
                            f"{delta} extra cores to replace {n!r} "
                            f"(free={c.free_cores})")
                    adjusted.append((c, n, delta))
                elif delta < 0:
                    c.release(n, -delta)
                    adjusted.append((c, n, delta))
                out[n] = Flake(
                    n, factory, cores=v.cores, engine=self,
                    channel_capacity=self._channel_capacity,
                    speculative_timeout=self._speculative_timeout,
                    batch_max=v.annotations.get("batch_max"),
                    batch_wait_ms=v.annotations.get("batch_wait_ms", 0.0),
                    batch_array=v.annotations.get("batch_array", False),
                    proto=rprotos[n])
        except Exception:
            for c, n, delta in adjusted:
                if delta > 0:
                    c.release(n, delta)
                else:
                    c.allocate(n, -delta, force=True)
            raise
        return out

    def _readmit_replaced_backlog(self, name: str, old_flake: "Flake",
                                  by_port: Dict[str, List[Message]],
                                  summary: Dict[str, Any]) -> None:
        """Re-admit a replaced flake's backlog into the replacement.

        Ports the new signature keeps get their messages back in FIFO
        order (credits move with them); rows on retired ports — plus the
        old logic's half-gathered window buffer — leave the dataflow:
        credits released, counts surfaced in the summary.
        """
        new = self.flakes.get(name)
        dropped = 0

        def admit(port: str, msgs: List[Message]) -> None:
            nonlocal dropped
            if not new.accepts_arrays:
                msgs = _degrade_carriers(msgs)
            # bounded put: this runs under the wiring lock (and the
            # replacement may not be consuming yet), so a backlog that
            # cannot fit must degrade to dropped-with-credits-released
            # rather than wedge the engine (same hazard and remedy as
            # the _dispose_backlog reroute)
            try:
                new.inputs[port].put_many(msgs, timeout=30.0)
                new.stats.on_arrive(_rows_total(msgs))
                new._notify()
            except TimeoutError as e:
                admitted = getattr(e, "appended", 0)
                if admitted:
                    new.stats.on_arrive(_rows_total(msgs[:admitted]))
                    new._notify()
                rest = msgs[admitted:]
                dropped += _rows_total(rest)
                self._record_error(name, RuntimeError(
                    f"replacement backlog re-admit into {name!r} "
                    f"port {port!r} timed out with "
                    f"{_rows_total(rest)} rows unadmitted (channel "
                    "full); they were dropped, credits released"))

        # the half-gathered window buffer holds INPUT data (popped but
        # never processed — the oldest messages): re-admit it ahead of
        # the channel backlog, like checkpoint restore does
        wbuf, old_flake._window_buf = old_flake._window_buf, []
        if wbuf:
            if new is not None and new.inputs:
                admit(next(iter(new.inputs)), list(wbuf))
            else:
                dropped += _rows_total(wbuf)
        for port, msgs in by_port.items():
            if not msgs:
                continue
            if new is not None and port in new.inputs:
                admit(port, msgs)
            else:
                dropped += _rows_total(msgs)
        if dropped:
            self._inflight_dec(dropped)
            d = summary.setdefault("replaced_backlog_dropped", {})
            d[name] = d.get(name, 0) + dropped

    def _pop_backlog(self, flake: "Flake") -> List[Message]:
        """Drain a retiring flake's undelivered input: the half-gathered
        window buffer first (those messages are older — they were popped
        from the channel before the window filled), then each channel in
        FIFO order.  Every returned message still holds one engine
        inflight credit."""
        msgs: List[Message] = list(flake._window_buf)
        flake._window_buf = []
        for ch in flake.inputs.values():
            msgs.extend(ch.pop_up_to(None))
        return msgs

    def _dispose_backlog(self, name: str, msgs: List[Message],
                         policy: Union[str, Tuple[str, str]],
                         summary: Dict[str, Any]) -> None:
        """Apply one removed flake's backlog policy (see ``transact``)."""
        if not msgs:
            return
        if isinstance(policy, tuple):
            dst, dport = policy
            target = self.flakes[dst]
            # raw migration-style FIFO hand-off: inflight credits and
            # arrival stats move with the messages, not recounted.  Specials
            # bypass the target's landmark alignment, exactly like a
            # migrated backlog — best-effort, like all §II.B changes racing
            # in-flight control messages.  The target may itself be
            # drain-paused for this transaction (it cannot consume), so the
            # put must NOT wait forever on a full channel — that would
            # wedge the engine under the wiring lock.  On timeout the
            # unadmitted remainder degrades to 'collect' (surfaced, not
            # lost) and the condition is recorded as an engine error.
            if not target.accepts_arrays:
                msgs = _degrade_carriers(msgs)
            try:
                target.inputs[dport].put_many(msgs, timeout=30.0)
                target.stats.on_arrive(_rows_total(msgs))
                target._notify()
                return
            except TimeoutError as e:
                admitted = getattr(e, "appended", 0)
                if admitted:
                    target.stats.on_arrive(_rows_total(msgs[:admitted]))
                    target._notify()
                msgs = msgs[admitted:]
                self._record_error(name, RuntimeError(
                    f"backlog reroute to {dst!r} timed out with "
                    f"{len(msgs)} messages unadmitted (target channel "
                    "full); they were collected into the transaction "
                    "summary instead"))
                policy = "collect"
        # drop/collect: the messages leave the dataflow — release their
        # credits (rows, for ArrayBatch carriers) or engine-wide
        # quiescence would wedge forever.  Collected carriers surface as
        # per-row messages, like sink collection, so the caller's census/
        # replay code stays payload-container agnostic
        self._inflight_dec(_rows_total(msgs))
        if policy == "collect":
            summary.setdefault("backlog", {}).setdefault(name, []).extend(
                _degrade_carriers(msgs))

    def set_cores(self, name: str, cores: int) -> None:
        if self.cluster is not None:
            # container-accounted intra-VM resize (grant bounded by the
            # flake's host); VM-level scale-out is the adaptation tier's
            # call (``ClusterManager.actuate``), never an implicit side
            # effect of a plain set_cores
            self.cluster.resize(name, cores)
        else:
            self.flakes[name].set_cores(cores)

    def apply_wiring(self, graph: FloeGraph) -> None:
        """Dynamic dataflow update of the edge set (§II.B).

        Re-derives every flake's routes and landmark in-degree from
        ``graph`` (which must name the same vertices) and adopts it as the
        coordinator's graph.  Callers are responsible for quiescing the
        affected flakes first — ``Session.recompose`` drains them, swaps
        wiring, then resumes, so no in-flight message observes a half
        rewired graph.
        """
        graph.validate()
        if set(graph.vertices) != set(self.flakes):
            raise ValueError(
                "apply_wiring requires the same vertex set; "
                f"got {sorted(graph.vertices)} vs {sorted(self.flakes)}")

        def in_sig(g: FloeGraph, name: str) -> List[Tuple[str, str, str]]:
            return sorted((e.src, e.src_port, e.dst_port)
                          for e in g.in_edges(name))

        old_in = {n: in_sig(self.graph, n) for n in self.flakes}
        for name, flake in self.flakes.items():
            by_port: Dict[str, List] = {}
            for e in graph.out_edges(name):
                by_port.setdefault(e.src_port, []).append(e)
            routes: Dict[str, Tuple[Split, List[Tuple[Flake, str]]]] = {}
            sigs: Dict[str, List[Tuple[str, str, str]]] = {}
            for port, edges in by_port.items():
                # reuse the existing split object ONLY when this port's
                # edge group is identical — membership and order — to the
                # group the split was installed against (the signature the
                # flake itself recorded, not a graph-derived guess), so
                # stateful split policies (round-robin counters) survive
                # unrelated rewires but a rewire that alters the fan-out
                # group in any way gets a fresh split: a stale one could
                # consult counters accumulated against the old destination
                # set.  The target list is always rebuilt: a migration
                # replaces flake objects and moves them across hosts, so
                # cached references (and their transport proxies) go stale
                sig = [(e.dst, e.dst_port, e.split) for e in edges]
                if port in flake.routes and \
                        flake._route_sigs.get(port) == sig:
                    split = flake.routes[port][0]
                else:
                    split = make_split(edges[0].split)
                targets = [(self._route_target(name, e.dst), e.dst_port)
                           for e in edges]
                routes[port] = (split, targets)
                sigs[port] = sig
            flake.routes = routes
            flake._route_sigs = sigs
        for name, flake in self.flakes.items():
            n_in = max(1, len(graph.in_edges(name)))
            if in_sig(graph, name) == old_in[name]:
                flake.in_degree = n_in
                continue
            # inbound edges changed (even at equal fan-in): complete any
            # partially-counted landmark round now — already-swallowed
            # copies belong to the old topology, and copies still to come
            # may never arrive under the new one.  Flushing early beats
            # losing the round (a reducer window that never flushes).
            # Copies of that round still in flight from old edges can cause
            # at most one extra early flush — best-effort, like all §II.B
            # changes racing in-flight control messages.
            with flake._lm_lock:
                flake.in_degree = n_in
                pending, flake._lm_pending = flake._lm_pending, None
                flake._lm_count = 0
            if pending is not None and flake.inputs:
                self._inflight_inc()
                flake.stats.on_arrive()
                next(iter(flake.inputs.values())).put(pending)
        self.graph = graph
        # every placement-changing path (start, transact, migrate, fault
        # recovery) funnels through here: rebind each flake's remote
        # compute seam to its (possibly new) host's execution backend
        cluster = self.cluster
        if cluster is not None:
            binder = getattr(cluster, "bind_runners", None)
            if binder is not None:
                binder(self.flakes)

    def _route_target(self, src: str, dst: str):
        """Destination for edge src->dst: the flake itself within one host,
        a transport proxy (``RemoteFlake``) across hosts."""
        flake = self.flakes[dst]
        if self.cluster is not None:
            return self.cluster.route_target(src, dst, flake)
        return flake

    # -- live flake migration (cluster mode) -----------------------------------
    def migrate_flake(self, name: str, host, *, cores: Optional[int] = None,
                      quiesce_timeout: float = 30.0) -> None:
        """Move one flake to another host without losing a message.

        Mechanics (the §II.B quiescence machinery, reused):

        1. drain the flake *and every upstream neighbour* together (shared
           deadline; abort-before-change on timeout, like ``transact``);
        2. once quiescent, hand off identity and state to a fresh flake on
           the target host — the live pellet prototype (the swap_pellet
           state-transfer path), pull-pellet state, a half-gathered window
           buffer, landmark-alignment progress, batch knobs, stats and the
           speculative dedup set all move;
        3. transfer the channel backlog port-by-port in FIFO order (raw
           channel hand-off: inflight credits and arrival stats moved with
           the messages, not recounted);
        4. re-derive every route from the graph (upstream edges now point
           at the new flake, through a transport if the edge went
           cross-host), activate the replacement, resume the upstreams,
           and retire the old flake — its cores audited back to the source
           host's container.

        Per-key FIFO order survives because upstreams are quiescent while
        the backlog moves: everything already sent sits in the transferred
        channels, ahead of anything sent after resume.
        """
        if self.cluster is None:
            raise RuntimeError("migrate_flake requires cluster mode "
                               "(Coordinator(..., cluster=ClusterManager))")
        if name not in self.flakes:
            raise ValueError(f"migrate_flake: unknown flake {name!r}")
        with self._wiring_lock:
            self._migrate_locked(name, host, cores, quiesce_timeout)

    def _migrate_locked(self, name: str, host, cores: Optional[int],
                        quiesce_timeout: float) -> None:
        src_host = self.cluster.host_of(name)
        if host is src_host:
            return
        old = self.flakes[name]
        cores = old.cores if cores is None else max(0, int(cores))
        # acquisition latency respected: a still-provisioning VM blocks here
        host.wait_ready()
        upstream = {e.src for e in self.graph.in_edges(name)}
        drained = [self.flakes[n] for n in sorted({name} | upstream)]
        for f in drained:
            f._drain_acquire()
        try:
            deadline = time.time() + quiesce_timeout
            for f in drained:
                if not f._wait_quiescent(
                        timeout=max(0.0, deadline - time.time())):
                    raise TimeoutError(
                        f"flake {f.name!r} did not quiesce within "
                        f"{quiesce_timeout}s; migration aborted, "
                        "nothing moved")
            if not host.container.allocate(name, cores):
                raise RuntimeError(
                    f"host {host.name!r} cannot grant {cores} cores for "
                    f"{name!r} (free={host.container.free_cores})")
            # release-on-migrate audit: the source container must hold
            # exactly the cores the flake believes it has
            released = src_host.container.release(name)
            if released != old.cores:
                self._record_error(name, RuntimeError(
                    f"core-accounting drift on migration: container "
                    f"{src_host.name!r} held {released}, flake had "
                    f"{old.cores}"))
            new = Flake(name, old.factory, cores=cores, engine=self,
                        channel_capacity=self._channel_capacity,
                        speculative_timeout=self._speculative_timeout)
            # -- identity & state hand-off ---------------------------------
            with old._pellet_lock:
                new._proto = old._proto        # live pellet state moves
                new.version = old.version
            new.state = old.state              # pull-pellet explicit state
            new._window_buf = old._window_buf  # half-gathered count window
            new.stats = old.stats              # monitoring continuity
            # ... but NOT latency continuity: the EWMA (and the latency
            # histograms, keyed by stage name) were measured against the
            # old host's core budget — carrying them poisons post-move
            # batch sizing and elasticity decisions until enough fresh
            # samples dilute them.  Counters survive; latency restarts.
            new.stats.reset_latency()
            if self.telemetry.enabled:
                self.telemetry.reset_stage(name)
            new._done_seqs = old._done_seqs    # speculative dedup history
            new.batch_max = old.batch_max
            new._batch_explicit = old._batch_explicit
            new.batch_wait = old.batch_wait
            new.batch_array = old.batch_array  # array fast path survives
            with old._lm_lock:                 # landmark-alignment progress
                new.in_degree = old.in_degree
                new._lm_count = old._lm_count
                new._lm_pending = old._lm_pending
            new.routes = old.routes            # split counters survive
            new._route_sigs = dict(old._route_sigs)  # (group unchanged)
            new.set_cores(cores)               # targets rebuilt below
            # -- channel backlog hand-off (FIFO, credits move untouched).
            # Atomic against injection: a concurrent inject must either
            # land before this pop (and be transferred) or resolve the
            # replacement flake after the dict swap — never strand in the
            # retired flake's channels.
            with self._inject_lock:
                for port, ch in old.inputs.items():
                    backlog = ch.pop_up_to(None)
                    if backlog:
                        new.inputs[port].put_many(backlog, timeout=None)
                self.flakes[name] = new
                self._container_of[name] = host.container
                self.cluster._record_migration(name, host)
            if self.telemetry.enabled:
                self.telemetry.events.emit(
                    "migration", flake=name, src=src_host.name,
                    dst=host.name, cores=cores)
            # upstream routes re-point at the replacement (through the
            # transport where the edge is now cross-host)
            self.apply_wiring(self.graph)
            new.activate()
        finally:
            for f in drained:
                f._drain_release()
        old.deactivate()
        # belt-and-braces for callers that held a direct reference to the
        # retired flake across the swap: sweep anything they enqueued into
        # its (now dead) channels over to the replacement
        for port, ch in old.inputs.items():
            leftovers = ch.pop_up_to(None)
            if leftovers:
                new.inputs[port].put_many(leftovers, timeout=None)

    # -- introspection ---------------------------------------------------------------
    def stats(self) -> Dict[str, Dict[str, Any]]:
        """Per-stage runtime stats — one snapshot through the telemetry
        plane (the single source of truth for observation surfaces:
        ``session.stats()``, ``session.describe()``, the Prometheus
        collector, and percentile-aware strategies all read the same
        numbers).  With telemetry enabled each stage additionally carries
        ``service_p50/p95/p99`` and ``queue_wait_p95``."""
        return self.telemetry.stage_snapshot(self)
