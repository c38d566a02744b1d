"""The program's own spans in a profiler trace, and the device's idle time
under them.

The serving path opens ``floe.*`` spans (``jax.profiler.TraceAnnotation``,
``repro.telemetry.tracing.span``) around each flake dispatch, its routing,
each injection and the decode and prefill steps' device launches, host
waits and bookkeeping; the stage rides each span's ``stage`` stat.  This
module reads them apart from ``trace.load``'s ``bench.*`` spans, so every
reader of ``trace.Reduced`` reads what it read before, and says how much of
the device's idle time fell while each kind of span was open:

- ``idle_by_span``: per span name and stage, the idle seconds that fell
  while at least one such span was open on any thread (nested or
  concurrent spans may each count the same idle time), with ``any`` (idle
  under at least one ``floe.*`` span) and ``none`` (under none), which sum
  to the window's idle seconds;
- ``decode_host_idle_s``: the idle seconds under the union of the decode
  stage's dispatch, routing and ``floe.decode.*`` spans, the host code of
  the decode tick loop.

Both are pure and work on plain tuples on the trace's one clock, in
nanoseconds.  Idle time is measured with sorted sweeps (``searchsorted``
over the merged idle intervals and each merged set of spans), never a
product of gaps and spans: a window holds millions of gaps.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Sequence, Tuple

import numpy as np

from .trace import Event

#: (name, start_ns, duration_ns, stage)
Span = Tuple[str, float, float, str]

PREFIX = "floe."
#: the decode stage's host code around its device work
DECODE_STAGE = "decode"
DECODE_HOST_NAMES = ("floe.dispatch", "floe.route")
DECODE_HOST_PREFIX = "floe.decode."


def load(path: str) -> List[Span]:
    """Every ``floe.*`` span on the host planes of a ``.xplane.pb``, with
    its ``stage`` stat (empty where it has none)."""
    from jax.profiler import ProfileData
    out: List[Span] = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    stage = dict(e.stats).get("stage", "")
                    out.append((e.name, e.start_ns, e.duration_ns,
                                str(stage)))
    return out


def _merged(starts: np.ndarray, ends: np.ndarray
            ) -> Tuple[np.ndarray, np.ndarray]:
    """The union of intervals as sorted disjoint intervals; intervals that
    touch are joined, as ``trace.reduce`` joins them."""
    keep = ends > starts
    starts, ends = starts[keep], ends[keep]
    if starts.size == 0:
        return starts, ends
    order = np.argsort(starts, kind="stable")
    starts, ends = starts[order], ends[order]
    reach = np.maximum.accumulate(ends)
    first = np.ones(starts.size, dtype=bool)
    first[1:] = starts[1:] > reach[:-1]
    heads = np.flatnonzero(first)
    lasts = np.append(heads[1:] - 1, starts.size - 1)
    return starts[heads], reach[lasts]


def idle_intervals(events: Sequence[Event], window: Tuple[float, float]
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """One device's idle intervals inside ``window``: the complement of
    the union of its operations."""
    lo, hi = window
    s = np.fromiter((e[1] for e in events), dtype=np.float64,
                    count=len(events))
    e = s + np.fromiter((ev[2] for ev in events), dtype=np.float64,
                        count=len(events))
    bs, be = _merged(np.clip(s, lo, hi), np.clip(e, lo, hi))
    starts = np.append(lo, be)
    ends = np.append(bs, hi)
    keep = ends > starts
    return starts[keep], ends[keep]


class _Idle:
    """Idle intervals of one device with prefix sums: the idle time inside
    any interval in two binary searches."""

    def __init__(self, starts: np.ndarray, ends: np.ndarray):
        self.starts, self.ends = starts, ends
        self.before = np.concatenate(([0.0], np.cumsum(ends - starts)))

    @property
    def total(self) -> float:
        return float(self.before[-1])

    def _upto(self, t: np.ndarray) -> np.ndarray:
        """Idle time before each ``t``."""
        k = np.searchsorted(self.starts, t, side="right") - 1
        kk = np.maximum(k, 0)
        part = np.clip(np.minimum(t, self.ends[kk]) - self.starts[kk],
                       0.0, None)
        return np.where(k >= 0, self.before[kk] + part, 0.0)

    def under(self, starts: np.ndarray, ends: np.ndarray) -> float:
        """Idle time inside the union of the intervals."""
        a, b = _merged(starts, ends)
        if a.size == 0 or self.starts.size == 0:
            return 0.0
        return float(np.sum(self._upto(b) - self._upto(a)))


def _span_arrays(spans: Iterable[Span], lo: float, hi: float
                 ) -> Tuple[np.ndarray, np.ndarray]:
    pairs = [(s, s + d) for _, s, d, _ in spans]
    if not pairs:
        return np.empty(0), np.empty(0)
    arr = np.asarray(pairs, dtype=np.float64)
    return np.clip(arr[:, 0], lo, hi), np.clip(arr[:, 1], lo, hi)


def _idle_under(device_events: Dict[str, List[Event]],
                window: Tuple[float, float],
                groups: Dict[object, List[Span]]) -> Tuple[float, Dict]:
    """Window idle seconds, and per group the idle seconds under the union
    of its spans; both averaged over the devices, as busy time is."""
    lo, hi = window
    if hi <= lo:
        raise ValueError(f"empty window {window}")
    arrays = {key: _span_arrays(group, lo, hi)
              for key, group in groups.items()}
    n = max(1, len(device_events))
    total = 0.0
    out = {key: 0.0 for key in groups}
    for _, events in sorted(device_events.items()):
        idle = _Idle(*idle_intervals(events, window))
        total += idle.total
        for key, (a, b) in arrays.items():
            out[key] += idle.under(a, b)
    return total * 1e-9 / n, {k: v * 1e-9 / n for k, v in out.items()}


def idle_by_span(device_events: Dict[str, List[Event]], spans: List[Span],
                 window: Tuple[float, float]) -> Dict[str, object]:
    """Device-idle seconds in ``window`` under each span name and stage,
    longest first, with ``any`` and ``none``, and the window's ``idle``."""
    groups: Dict[object, List[Span]] = {}
    for sp in spans:
        groups.setdefault((sp[0], sp[3]), []).append(sp)
    groups["any"] = list(spans)
    idle, under = _idle_under(device_events, window, groups)
    anyone = under.pop("any")
    ranked = sorted(under.items(), key=lambda kv: (-kv[1], kv[0]))
    return {"spans": [[name, stage, s] for (name, stage), s in ranked],
            "any": anyone, "none": idle - anyone, "idle": idle}


def is_decode_host(span: Span) -> bool:
    name, _, _, stage = span
    return stage == DECODE_STAGE and (
        name in DECODE_HOST_NAMES or name.startswith(DECODE_HOST_PREFIX))


def decode_host_idle_s(device_events: Dict[str, List[Event]],
                       spans: List[Span], window: Tuple[float, float]
                       ) -> float:
    """Device-idle seconds in ``window`` under the union of the decode
    tick loop's host spans."""
    _, under = _idle_under(device_events, window,
                           {"host": [sp for sp in spans
                                     if is_decode_host(sp)]})
    return under["host"]
