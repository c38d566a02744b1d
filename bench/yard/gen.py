"""Seeded traffic: arrival times, request lengths and token ids.

Every traffic file under ``bench/traffic/`` is read by these functions
alone.  The arrival times and the lengths are drawn from the file's own
``shape_seed``, so every run seed offers the same schedule of work: near
the knee, the order of the same gaps and lengths alone moves a tail
latency by half its value, which would read as noise between seeds.  The
run seed draws what the work is made of: the token ids (and the weights,
elsewhere).  The same seed gives the same inputs.

Arrivals (``arrivals.kind``) are ``poisson``: exponential gaps at
``rate_per_s``, scaled so that the window holds exactly
``round(rate_per_s * seconds)`` arrivals.
"""
from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

_MASK = (1 << 64) - 1


def rng(seed: int, stream: int) -> np.random.Generator:
    """An independent generator for one use (``stream``) of one seed."""
    return np.random.default_rng([int(seed) & _MASK, int(stream)])


def arrivals(spec: Dict[str, Any], seconds: float, *, shape_seed: int
             ) -> np.ndarray:
    """Offsets in seconds from the window's start, sorted, all below
    ``seconds``."""
    rate = float(spec["rate_per_s"])
    n = int(round(rate * seconds))
    if n <= 0:
        return np.zeros(0)
    if spec["kind"] != "poisson":
        raise ValueError(f"unknown arrival kind {spec['kind']!r}")
    gaps = rng(shape_seed, 1).exponential(1.0 / rate, n + 1)
    return np.cumsum(gaps[:n] * (seconds / gaps.sum()))


def lengths(spec: Dict[str, Any], n: int, *, shape_seed: int,
            stream: int) -> np.ndarray:
    """``n`` integer lengths, log-normal with ``median`` and ``sigma``,
    clipped to ``[min, max]``."""
    draw = rng(shape_seed, stream)
    x = np.exp(draw.normal(np.log(float(spec["median"])),
                           float(spec["sigma"]), n))
    return np.clip(np.rint(x), int(spec["min"]),
                   int(spec["max"])).astype(np.int64)


def requests(traffic: Dict[str, Any], seconds: float, *, seed: int,
             vocab: int) -> List[Dict[str, Any]]:
    """The serving schedule: ``{"rid", "due", "prompt", "budget"}`` per
    request, in order of ``due`` (seconds from the window's start).  Token
    ids are uniform over ``[1, vocab)``; no two prompts share a prefix but
    by chance."""
    shape_seed = int(traffic["shape_seed"])
    due = arrivals(traffic["arrivals"], seconds, shape_seed=shape_seed)
    n = len(due)
    plen = lengths(traffic["prompt"], n, shape_seed=shape_seed, stream=2)
    budget = lengths(traffic["budget"], n, shape_seed=shape_seed, stream=3)
    ids = rng(seed, 4).integers(1, vocab, size=int(plen.sum()),
                                dtype=np.int64)
    cuts = np.concatenate([[0], np.cumsum(plen)])
    return [{"rid": i, "due": float(due[i]),
             "prompt": ids[cuts[i]:cuts[i + 1]].astype(np.int32),
             "budget": int(budget[i])} for i in range(n)]

