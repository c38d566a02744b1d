"""Dataflow tracing — "where did message X spend its time" across hosts.

A *trace* follows one injected message through every flake hop it (or any
derivative) takes.  The context is just a small dict riding ``Message.meta``
under the ``"trace"`` key — ``derive()`` already copies meta downstream, so
propagation through ordinary pellet emission is free; the engine threads the
same dict through ``ArrayBatch`` sidecars (per-row, surviving slicing),
``SerializingTransport`` (meta pickles with the message), migration parking,
and checkpoint snapshots.

Sampling is the cost knob: with ``sample=0.0`` (default) the tracer is
completely inert — injection does not allocate a context and the engine's
span-recording branches short-circuit on ``tracer.active``.  At
``sample=1.0`` every injected message is traced.

The second kind of span lives on the profiler's clock: ``span`` wraps a
``jax.profiler.TraceAnnotation`` named ``floe.*`` whose keyword metadata
(``stage=``, ``rows=`` ...) lands as event stats, so a device trace can say
which host code was running while the device sat idle.  Host thread names
do not reach the profiler's file, so the stage always rides the metadata.
Sites build one only through a bound telemetry handle; with telemetry off
they use ``NO_SPAN`` and build nothing.
"""
from __future__ import annotations

import contextlib
import itertools
import random
import threading
from collections import OrderedDict
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

#: meta key under which the trace context rides a Message
TRACE_KEY = "trace"

_trace_ids = itertools.count(1)


#: the shared no-op context a site enters when telemetry is off
NO_SPAN = contextlib.nullcontext()


def span(name: str, **meta: Any) -> TraceAnnotation:
    """A profiler span: free of effect unless a trace is being recorded,
    ~1 us to build and enter on a host CPU when not."""
    return TraceAnnotation(name, **meta)


def make_context(tid: Optional[int] = None) -> Dict[str, Any]:
    """A fresh trace context (the dict stored at ``meta['trace']``)."""
    return {"id": tid if tid is not None else next(_trace_ids)}


class Tracer:
    """Span store + sampling decision.

    ``maybe_trace()`` is called once per *injection* (not per hop): it
    rolls the sampling dice and returns a context dict or ``None``.
    ``record_span`` is called by the engine after each compute dispatch
    for each distinct traced context in the batch — spans land in a
    bounded per-trace store (oldest traces evicted beyond ``max_traces``).
    """

    def __init__(self, sample: float = 0.0, max_traces: int = 256,
                 max_spans: int = 512):
        if not 0.0 <= sample <= 1.0:
            raise ValueError(f"trace sample must be in [0, 1], got {sample}")
        self.sample = float(sample)
        self.max_traces = max_traces
        self.max_spans = max_spans
        self._traces: "OrderedDict[int, List[Dict[str, Any]]]" = OrderedDict()
        self._lock = threading.Lock()
        self._rng = random.Random(0xF10E)

    @property
    def active(self) -> bool:
        """Cheap hot-path guard: anything span-related gates on this."""
        return self.sample > 0.0

    def maybe_trace(self) -> Optional[Dict[str, Any]]:
        """Sampling decision at injection time; returns a context or None."""
        if self.sample <= 0.0:
            return None
        if self.sample < 1.0 and self._rng.random() >= self.sample:
            return None
        return make_context()

    def record_span(self, ctx: Dict[str, Any], *, stage: str,
                    host: str = "local", rows: int = 1,
                    t_start: float = 0.0, t_end: float = 0.0,
                    queue_wait: float = 0.0) -> None:
        tid = ctx.get("id")
        if tid is None:
            return
        span = {"stage": stage, "host": host, "rows": rows,
                "t_start": t_start, "t_end": t_end,
                "service": max(t_end - t_start, 0.0),
                "queue_wait": queue_wait}
        with self._lock:
            spans = self._traces.get(tid)
            if spans is None:
                spans = []
                self._traces[tid] = spans
                while len(self._traces) > self.max_traces:
                    self._traces.popitem(last=False)
            if len(spans) < self.max_spans:
                spans.append(span)

    # -- query surface ------------------------------------------------------
    def trace_ids(self) -> List[int]:
        with self._lock:
            return list(self._traces.keys())

    def spans(self, tid: int) -> List[Dict[str, Any]]:
        """Spans for one trace, ordered by start time (hop order)."""
        with self._lock:
            spans = list(self._traces.get(tid, ()))
        return sorted(spans, key=lambda s: s["t_start"])

    def clear(self) -> None:
        with self._lock:
            self._traces.clear()


def trace_of(meta: Optional[Dict[str, Any]]) -> Optional[Dict[str, Any]]:
    """The trace context riding a message's meta dict, if any."""
    if not meta:
        return None
    ctx = meta.get(TRACE_KEY)
    return ctx if isinstance(ctx, dict) else None
