"""Reduce a profiler trace of the measured window to what the metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
nothing but JAX: the device planes' operations, and the benchmark's own
host spans (``jax.profiler.TraceAnnotation`` names starting ``bench.``),
all on the trace's one clock.  ``reduce`` is pure and works on plain
tuples, so it is tested on synthetic traces:

- busy seconds: the union of the intervals in which an operation ran on a
  device, inside the window, averaged over the devices used;
- operations by name: summed device seconds and the number of events;
- idle gaps: the intervals inside the window in which no operation ran,
  each labelled by the benchmark span that overlaps it most (``idle``
  where none does).
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import glob
import heapq
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

#: (name, start_ns, duration_ns)
Event = Tuple[str, float, float]

#: the line of a device plane that holds one event per operation run, and
#: the line that holds one event per program run
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    ops: Dict[str, List[float]]          # name -> [seconds, count]
    gaps: List[Tuple[str, float]]        # (label, seconds), longest first
    devices: int
    file_bytes: int = 0                  # size of the profiler's file
    reduce_s: float = 0.0                # host seconds to read and reduce it

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel(self, patterns: Sequence[str]) -> Tuple[float, int]:
        """Seconds and events of every operation whose instruction name
        holds one of ``patterns`` (lower case), summed over the devices."""
        secs, n = 0.0, 0
        for name, (s, c) in self.ops.items():
            low = instruction(name).lower()
            if any(p in low for p in patterns):
                secs += s
                n += int(c)
        return secs, n

    def top_ops(self, k: int = 10) -> List[List]:
        ranked = sorted(self.ops.items(), key=lambda kv: -kv[1][0])
        return [[name, s] for name, (s, _) in ranked[:k]]


def _clip(events: Iterable[Event], lo: float, hi: float
          ) -> List[Tuple[float, float, str]]:
    out = []
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append((s, e, name))
    return out


def reduce(device_events: Dict[str, List[Event]], spans: List[Event],
           window: Tuple[float, float], top_gaps: int = 10) -> Reduced:
    """``device_events`` per device, ``spans`` of the host, ``window`` as
    ``(start_ns, end_ns)``; all on one clock in nanoseconds."""
    lo, hi = window
    if hi <= lo:
        raise ValueError(f"empty window {window}")
    ops: Dict[str, List[float]] = {}
    busy = 0.0
    idle: List[Tuple[float, float, float]] = []      # (length, start, end)
    for dev, events in sorted(device_events.items()):
        clipped = _clip(events, lo, hi)
        for s, e, name in clipped:
            rec = ops.setdefault(name, [0.0, 0])
            rec[0] += (e - s) * 1e-9
            rec[1] += 1
        cursor = lo
        for s, e in _merged((s, e) for s, e, _ in clipped):
            busy += (e - s) * 1e-9
            if s > cursor:
                idle.append((s - cursor, cursor, s))
            cursor = e
        if hi > cursor:
            idle.append((hi - cursor, cursor, hi))
    # only the longest gaps are labelled: a window holds millions
    clipped_spans = _clip([sp for sp in spans if sp[0] != WINDOW_SPAN],
                          lo, hi)
    gaps = [(_label(s, e, clipped_spans), length * 1e-9)
            for length, s, e in heapq.nlargest(top_gaps, idle)]
    n = max(1, len(device_events))
    return Reduced(window_s=(hi - lo) * 1e-9, busy_s=busy / n, ops=ops,
                   gaps=gaps, devices=len(device_events))


def _merged(intervals: Iterable[Tuple[float, float]]
            ) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _label(lo: float, hi: float, spans: List[Tuple[float, float, str]]
           ) -> str:
    best, best_overlap = "idle", 0.0
    for s, e, name in spans:
        overlap = min(e, hi) - max(s, lo)
        if overlap > best_overlap:
            best, best_overlap = name[len(SPAN_PREFIX):], overlap
    return best


# -- reading the profiler's file ----------------------------------------------

@functools.lru_cache(maxsize=None)
def op_label(hlo: str, module: str = "") -> str:
    """A stable label for one device operation from its HLO text: the
    program, the instruction's name without its numeric suffix, and its
    result type without layout, e.g. ``jit_decode_step/copy
    f32[28,8,512,8,128]``.  The same instruction of every layer shares a
    label."""
    name, _, rest = hlo.partition(" = ")
    name = re.sub(r"\.\d+$", "", name.strip().lstrip("%"))
    if rest.startswith("("):
        out = "tuple"
    else:
        out = re.sub(r"\{[^}]*\}", "", rest.split(" ", 1)[0])
    label = f"{name} {out}".strip()
    return f"{module}/{label}" if module else label


def instruction(label: str) -> str:
    """The instruction name inside an ``op_label``."""
    return label.rsplit("/", 1)[-1].split(" ", 1)[0]


def _modules(events) -> Tuple[List[float], List[float], List[str]]:
    evs = sorted((e.start_ns, e.start_ns + e.duration_ns,
                  re.sub(r"\(\d+\)$", "", e.name)) for e in events)
    return [s for s, _, _ in evs], [e for _, e, _ in evs], \
        [n for _, _, n in evs]


def _module_at(mods, t: float) -> str:
    starts, ends, names = mods
    i = bisect.bisect_right(starts, t) - 1
    return names[i] if i >= 0 and t <= ends[i] else ""


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def load(path: str, devices: Optional[int] = None
         ) -> Tuple[Dict[str, List[Event]], List[Event]]:
    """The device operations per device plane (``/device:TPU:<n>``, the
    ``XLA Ops`` line) and the benchmark's host spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device_events: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            evs = device_events.setdefault(plane.name, [])
            lines = {line.name: line for line in plane.lines}
            mods = _modules(lines[MODULES_LINE].events
                            if MODULES_LINE in lines else [])
            if OPS_LINE in lines:
                evs.extend((op_label(e.name, _module_at(mods, e.start_ns)),
                            e.start_ns, e.duration_ns)
                           for e in lines[OPS_LINE].events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, e.start_ns, e.duration_ns))
    if devices is not None:
        keep = sorted(device_events)[:devices]
        device_events = {k: device_events[k] for k in keep}
    return device_events, spans


def window_of(spans: List[Event], name: str = WINDOW_SPAN
              ) -> Tuple[float, float]:
    for n, s, d in spans:
        if n == name:
            return s, s + d
    raise ValueError(f"the trace holds no {name!r} span")
