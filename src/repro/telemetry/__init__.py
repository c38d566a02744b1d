"""Telemetry plane — metrics, events, tracing, and export for the engine.

One ``Telemetry`` object per :class:`~repro.core.engine.Coordinator` owns
the three observability surfaces:

* :class:`~repro.telemetry.registry.MetricsRegistry` — counters, gauges,
  per-stage service-time / queue-wait histograms (p50/p95/p99);
* :class:`~repro.telemetry.events.EventBus` — one ordered, subscribable
  stream unifying engine transactions, migrations, elasticity actuations,
  errors, and cluster ledger events (JSONL-exportable);
* :class:`~repro.telemetry.tracing.Tracer` — sampled per-message dataflow
  traces (a span per flake hop, surviving ArrayBatch stacking, cross-host
  transport, migration, checkpoint/restore);
* :func:`~repro.telemetry.tracing.span` — ``floe.*`` spans on the
  profiler's clock around dispatch, routing, injection and the serving
  plane's device launches and waits.

The facade also pre-declares the engine metric families so instrumentation
sites grab label children once (at flake construction) and pay a single
method call per dispatch.  A disabled Telemetry (``enabled=False``) keeps
the same object shape with every hot-path hook short-circuited, which is
what the overhead guard benches against.
"""
from __future__ import annotations

import threading as _threading
import time as _time
from collections import deque as _deque
from typing import Any, Dict, List, Optional, Tuple

from .events import EventBus
from .export import parse_prometheus, render_prometheus
from .registry import LATENCY_BUCKETS, Counter, Family, Gauge, Histogram, \
    MetricsRegistry
from .tracing import NO_SPAN, TRACE_KEY, Tracer, make_context, span, \
    trace_of

__all__ = [
    "Telemetry", "MetricsRegistry", "EventBus", "Tracer",
    "Counter", "Gauge", "Histogram", "Family", "LATENCY_BUCKETS",
    "render_prometheus", "parse_prometheus",
    "TRACE_KEY", "make_context", "trace_of", "span", "NO_SPAN",
]

#: queue-wait histograms see longer tails than service times (a message can
#: sit behind a stalled stage for seconds) — same buckets work for both.
_STAGE_LABELS = ("stage",)


class Telemetry:
    """Per-coordinator observability facade.

    Parameters
    ----------
    enabled:
        master switch; when False every family handle is still valid but
        the engine skips its observe/inc calls entirely (the handles the
        flakes cache are ``None``).
    trace_sample:
        fraction of injected messages to trace (0.0 = tracing off).
    """

    def __init__(self, *, enabled: bool = True, trace_sample: float = 0.0,
                 event_buffer: int = 4096, max_traces: int = 256,
                 tail_window_s: float = 5.0):
        self.enabled = bool(enabled)
        self.registry = MetricsRegistry()
        self.events = EventBus(maxlen=event_buffer)
        self.tracer = Tracer(sample=trace_sample if self.enabled else 0.0,
                             max_traces=max_traces)
        #: sliding-window length for the windowed tail percentiles
        #: (``queue_wait_p95_window``): the per-stage histograms are
        #: cumulative over a stage's lifetime, so SLO strategies gating on
        #: the plain percentile see a breach that never un-breaches;
        #: the windowed view covers roughly the last 1–2 windows
        self.tail_window_s = float(tail_window_s)
        self._qw_frames: Dict[str, Any] = {}
        self._qw_lock = _threading.Lock()

        # -- pre-declared engine families (labels grabbed per flake) -------
        r = self.registry
        self.service_time = r.histogram(
            "floe_stage_service_seconds",
            "Per-message service time by stage (observed per dispatch).",
            _STAGE_LABELS)
        self.queue_wait = r.histogram(
            "floe_stage_queue_wait_seconds",
            "Time from enqueue to dispatch by stage.",
            _STAGE_LABELS)
        self.stalls = r.counter(
            "floe_channel_backpressure_stalls_total",
            "Producer blocks on a full input channel, by receiving stage.",
            _STAGE_LABELS)
        self.array_hits = r.counter(
            "floe_stage_array_path_rows_total",
            "Rows that took the columnar ArrayBatch fast path, by stage.",
            _STAGE_LABELS)
        self.degradations = r.counter(
            "floe_stage_array_degrade_total",
            "ArrayBatch carriers unstacked for a non-array consumer.",
            _STAGE_LABELS)
        self.errors = r.counter(
            "floe_stage_errors_total",
            "Pellet compute errors by stage.",
            _STAGE_LABELS)
        self.injected = r.counter(
            "floe_injected_rows_total",
            "Rows injected into the dataflow at the source.")
        self.stacked_injections = r.counter(
            "floe_stacked_injections_total",
            "inject_many(stacked=True) calls that built one carrier.")

    # -- scrape-time live state --------------------------------------------
    def bind_engine_collector(self, coordinator: Any) -> None:
        """Register a collector exposing live engine state (queue depths,
        cores, FlakeStats counters, cluster fleet) at scrape time."""
        def _collect() -> List[Tuple]:
            out: List[Tuple] = []
            for name, f in list(getattr(coordinator, "flakes", {}).items()):
                lk = (("stage", name),)
                st = f.stats
                out.append(("floe_stage_queue_depth", "Queued rows by stage.",
                            "gauge", lk, f.queue_length()))
                out.append(("floe_stage_cores", "Worker cores by stage.",
                            "gauge", lk, f.cores))
                out.append(("floe_stage_arrived_total",
                            "Rows arrived by stage.", "counter", lk,
                            st.arrived))
                out.append(("floe_stage_processed_total",
                            "Rows processed by stage.", "counter", lk,
                            st.processed))
                out.append(("floe_stage_emitted_total",
                            "Messages emitted by stage.", "counter", lk,
                            st.emitted))
                out.append(("floe_stage_avg_latency_seconds",
                            "EWMA per-message service latency by stage.",
                            "gauge", lk, st.avg_latency))
                out.append(("floe_stage_batch_max", "Current batch cap.",
                            "gauge", lk, f.batch_max))
            cluster = getattr(coordinator, "cluster", None)
            if cluster is not None:
                hosts = getattr(cluster, "hosts", {})
                out.append(("floe_cluster_hosts", "Provisioned hosts.",
                            "gauge", (), len(hosts)))
                for hname, host in list(hosts.items()):
                    hk = (("host", hname),)
                    out.append(("floe_host_cores_used",
                                "Cores in use on host.", "gauge", hk,
                                host.cores - host.free_cores))
                    out.append(("floe_host_cores_total",
                                "Core capacity of host.", "gauge", hk,
                                host.cores))
            return out
        self.registry.register_collector(_collect)

    # -- export surface -----------------------------------------------------
    def prometheus(self) -> str:
        return render_prometheus(self.registry)

    def metrics(self) -> Dict[str, Dict[str, Any]]:
        return self.registry.snapshot()

    def stage_snapshot(self, coordinator: Any) -> Dict[str, Dict[str, Any]]:
        """Per-stage stats dict — the single source of truth behind
        ``Coordinator.stats()`` / ``session.describe()``.  Keeps the
        legacy key set (queue/arrived/processed/emitted/avg_latency/
        cores/batch knobs/host/version) and, when enabled, adds the
        service-time and queue-wait percentiles strategies consume."""
        cluster = getattr(coordinator, "cluster", None)
        placement = cluster.placement() if cluster is not None else {}
        out: Dict[str, Dict[str, Any]] = {}
        for n, f in coordinator.flakes.items():
            st = f.stats
            entry: Dict[str, Any] = {
                "queue": f.queue_length(),
                "arrived": st.arrived,
                "processed": st.processed,
                "emitted": st.emitted,
                "avg_latency": st.avg_latency,
                "cores": f.cores,
                "batch_max": f.batch_max,
                "batch_array": f.batch_array,
                "last_batch": st.last_batch,
                "avg_batch": st.avg_batch,
                "host": placement.get(n),
                "version": f.version,
            }
            if self.enabled:
                entry.update(self.stage_percentiles(n))
            out[n] = entry
        return out

    def stage_percentiles(self, stage: str) -> Dict[str, float]:
        """p50/p95/p99 service time + queue wait for one stage — the view
        the adaptation controller feeds to percentile-aware strategies.
        ``queue_wait_p95`` is cumulative over the stage's lifetime;
        ``queue_wait_p95_window`` covers only the recent sliding window
        (what ``TailLatencySLO`` keys on, so a past breach un-breaches
        once the tail recovers)."""
        svc = self.service_time.labels(stage=stage)
        qw = self.queue_wait.labels(stage=stage)
        return {"service_p50": svc.percentile(0.50),
                "service_p95": svc.percentile(0.95),
                "service_p99": svc.percentile(0.99),
                "queue_wait_p95": qw.percentile(0.95),
                "queue_wait_p95_window":
                    self.windowed_queue_wait_p95(stage)}

    def windowed_queue_wait_p95(self, stage: str,
                                now: Optional[float] = None) -> float:
        """p95 queue wait over (roughly) the last 1–2 ``tail_window_s``.

        Implemented as frame differencing on the cumulative histogram:
        a two-frame deque of bucket snapshots is rotated every window, and
        the percentile is computed over the count deltas since the older
        frame.  Until the first frame ages past one window the cumulative
        view is returned (best available signal at startup); a histogram
        reset (migration/replace) rebases the frames."""
        hist = self.queue_wait.labels(stage=stage)
        if now is None:
            now = _time.time()
        with self._qw_lock:
            frames = self._qw_frames.get(stage)
            if frames is None:
                frames = _deque(maxlen=2)
                frames.append((now, hist.window_state()))
                self._qw_frames[stage] = frames
                return hist.percentile(0.95)
            if now - frames[-1][0] >= self.tail_window_s:
                frames.append((now, hist.window_state()))
            base = frames[0][1]
        p = hist.percentile_since(base, 0.95)
        if p < 0.0:   # histogram reset since the baseline: rebase
            with self._qw_lock:
                self._qw_frames.pop(stage, None)
            return self.windowed_queue_wait_p95(stage, now)
        return p

    def reset_stage(self, stage: str) -> None:
        """Zero a stage's latency histograms (migration / replace: samples
        measured on the old core budget must not poison post-move views)."""
        self.service_time.labels(stage=stage).reset()
        self.queue_wait.labels(stage=stage).reset()
        with self._qw_lock:
            self._qw_frames.pop(stage, None)
