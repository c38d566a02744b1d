"""The load loop around a session: an open-loop injector that sends each
item at its due time, a monitor that stamps each output as it leaves the
session, the telemetry histograms read at the window's edges, and the
profiler around the window of a traced run.

The benchmark's own spans (``bench.inject``, ``bench.monitor``,
``bench.window``) go into the profiler's trace, so the trace reduction can
say what the harness was doing in each idle gap of the device."""
from __future__ import annotations

import contextlib
import os
import shutil
import tempfile
import threading
import time
from typing import Any, Callable, Dict, Iterable, List, Optional

from . import trace as trace_mod

#: how often the monitor looks for outputs when it found none
POLL_S = 0.0005


class Monitor(threading.Thread):
    """Pull the session's outputs as they leave it, and stamp each with the
    host clock at the moment it was seen."""

    def __init__(self, coordinator):
        super().__init__(name="bench-monitor", daemon=True)
        self.coord = coordinator
        self.items: List[Any] = []       # payloads, in order of leaving
        self.stamps: List[float] = []
        self._halt = threading.Event()
        self._lock = threading.Lock()

    def run(self) -> None:
        import jax
        while not self._halt.is_set():
            msgs = self.coord.drain_outputs()
            if not msgs:
                time.sleep(POLL_S)
                continue
            t = time.time()
            with jax.profiler.TraceAnnotation("bench.monitor"):
                data = [m.payload for m in msgs if m.is_data()]
                with self._lock:
                    self.items.extend(data)
                    self.stamps.extend([t] * len(data))

    def count(self) -> int:
        return len(self.stamps)

    def wait_for(self, n: int, deadline: float) -> bool:
        """Block until ``n`` outputs were seen, or the host clock passes
        ``deadline``."""
        while self.count() < n:
            if time.time() > deadline:
                return False
            time.sleep(POLL_S * 4)
        return True

    def take(self) -> tuple:
        """Every output so far with its stamp; forget them."""
        with self._lock:
            items, stamps = self.items, self.stamps
            self.items, self.stamps = [], []
        return items, stamps

    def stop(self) -> None:
        self._halt.set()
        self.join()


class Injector(threading.Thread):
    """Send ``items`` at ``t0 + due`` on the host clock (open loop: a slow
    system does not slow the schedule) until ``close``."""

    def __init__(self, items: List[Dict[str, Any]], send: Callable, t0: float,
                 close: float):
        super().__init__(name="bench-injector", daemon=True)
        self.items, self.send, self.t0, self.close = items, send, t0, close
        self.sent = 0
        self.late_s: List[float] = []

    def run(self) -> None:
        import jax
        for item in self.items:
            due = self.t0 + item["due"]
            now = time.time()
            if due > now:
                time.sleep(due - now)
            now = time.time()
            if now >= self.close:
                break
            with jax.profiler.TraceAnnotation("bench.inject"):
                self.send(item, due)
            self.late_s.append(now - due)
            self.sent += 1


def hist_state(telemetry, stages: Iterable[str]) -> Dict[str, Dict]:
    """The per-stage service and queue-wait histograms, as they stand."""
    out = {}
    for st in stages:
        svc = telemetry.service_time.labels(stage=st).snapshot()
        qw = telemetry.queue_wait.labels(stage=st).snapshot()
        out[st] = {"service": svc, "wait": qw}
    return out


def hist_delta(before: Dict[str, Dict], after: Dict[str, Dict]
               ) -> Dict[str, Dict[str, Any]]:
    """Differences over the window: counts, sums, and the queue wait's
    bucket counts, per stage."""
    out = {}
    for st, a in after.items():
        b = before[st]
        out[st] = {
            "service_count": a["service"]["count"] - b["service"]["count"],
            "service_sum": a["service"]["sum"] - b["service"]["sum"],
            "wait_count": a["wait"]["count"] - b["wait"]["count"],
            "wait_sum": a["wait"]["sum"] - b["wait"]["sum"],
            "wait_buckets": [x - y for x, y in zip(a["wait"]["buckets"],
                                                   b["wait"]["buckets"])],
            "wait_bounds": list(a["wait"]["bounds"]),
        }
    return out


class Profile:
    """The profiler over the measured window of a ``--trace 1`` run, with
    Python tracing off; the trace goes to a directory under ``TMPDIR`` and
    is deleted once reduced."""

    def __init__(self, on: bool):
        self.on = on
        self.dir: Optional[str] = None

    @contextlib.contextmanager
    def window(self):
        import jax
        if not self.on:
            yield
            return
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
                yield
        finally:
            jax.profiler.stop_trace()

    def reduce(self, chips: int) -> Optional[trace_mod.Reduced]:
        if not self.on:
            return None
        try:
            t0 = time.time()
            path = trace_mod.find_xplane(self.dir)
            size = os.path.getsize(path)
            device_events, spans = trace_mod.load(path, devices=chips)
            reduced = trace_mod.reduce(device_events, spans,
                                       trace_mod.window_of(spans))
            reduced.file_bytes = size
            reduced.reduce_s = time.time() - t0
            return reduced
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
