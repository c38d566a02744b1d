"""Channel patterns: splits, merges, and dynamic port mapping (paper §II.A).

A *split* governs how messages leaving one logical output port are routed to
multiple sink edges:

* ``DuplicateSplit``  — every outgoing edge receives a copy (Fig. 1, P7).
* ``RoundRobinSplit`` — load balance across edges (Fig. 1, P8, the default).
* ``HashSplit``       — **dynamic port mapping**: hash the message key to pick
  the edge, so all messages with the same key reach the same sink pellet —
  the streaming MapReduce shuffle (Fig. 1, P9).  This is the pattern the
  paper singles out as missing from generic dataflow frameworks; at the
  SPMD layer it becomes the MoE ``all_to_all`` dispatch.
* ``BalancedSplit``   — the paper's "more sophisticated strategy ... e.g.
  depending on the numbers of messages pending in the input queue": route to
  the sink with the shortest pending queue (join-the-shortest-queue).

A *merge* governs how multiple inbound edges feed a pellet's input side:

* interleaved merge (Fig. 1, P6) — edges share one port; messages interleave
  by arrival. This is the default when several edges target the same port.
* synchronous merge (Fig. 1, P5) — edges target distinct ports; the flake
  aligns one message per port into a tuple (dict) before triggering.

Both merge flavours are implemented inside ``core.engine.Flake``; this module
provides the split policies and the stable key hash.
"""
from __future__ import annotations

import hashlib
import itertools
from typing import Any, List, Optional, Sequence

from .message import Message


def stable_hash(key: Any) -> int:
    """Deterministic cross-process hash of a routing key.

    ``hash()`` is salted per-process for strings; the shuffle contract
    (same key -> same reducer, even across restarts/checkpoint resume)
    needs a stable hash, so we use blake2b over the repr.
    """
    h = hashlib.blake2b(repr(key).encode(), digest_size=8).digest()
    return int.from_bytes(h, "big")


class Split:
    """Base split policy: choose target edge indices for a message."""

    def choose(self, msg: Message, n_edges: int, queue_depths: Sequence[int]) -> List[int]:
        raise NotImplementedError

    def choose_many(self, msgs: Sequence[Message], n_edges: int,
                    queue_depths: Sequence[int]) -> List[List[int]]:
        """Route a whole micro-batch in one call (amortized routing).

        Returns one index list per message, in order.  ``queue_depths`` is
        sampled once per batch.  The default delegates to ``choose`` per
        message, so every policy keeps its exact per-message determinism
        (hash placement, round-robin counter advancement) under batching.
        """
        choose = self.choose
        return [choose(m, n_edges, queue_depths) for m in msgs]

    def broadcast_specials(self) -> bool:
        """Landmarks/control messages go to *all* edges regardless of policy."""
        return True

    def broadcast_rows(self) -> bool:
        """Array fast path: does every edge receive the whole carrier?"""
        return False

    def choose_rows(self, n_rows: int, keys: Optional[Sequence],
                    n_edges: int, queue_depths: Sequence[int]
                    ) -> Optional[List[int]]:
        """Array fast path: one destination edge index *per row* of an
        ``ArrayBatch`` carrier, computed from the per-row key sidecar
        alone (no payload unstacking).  Returning ``None`` (the default —
        and the right answer for any policy that needs the full Message,
        like a custom content-based split) makes the engine unstack the
        carrier and route the rows through ``choose`` one by one, so
        custom policies keep exact per-message semantics.  Policies that
        override this MUST place each row exactly where ``choose`` would
        have placed the equivalent message.
        """
        return None


class DuplicateSplit(Split):
    def choose(self, msg: Message, n_edges: int, queue_depths: Sequence[int]) -> List[int]:
        return list(range(n_edges))

    def broadcast_rows(self) -> bool:
        return True


class RoundRobinSplit(Split):
    def __init__(self):
        self._counter = itertools.count()

    def choose(self, msg: Message, n_edges: int, queue_depths: Sequence[int]) -> List[int]:
        return [next(self._counter) % n_edges]

    def choose_rows(self, n_rows, keys, n_edges, queue_depths):
        c = self._counter
        return [next(c) % n_edges for _ in range(n_rows)]


class HashSplit(Split):
    """Dynamic port mapping: same key -> same edge, Hadoop-style."""

    def choose(self, msg: Message, n_edges: int, queue_depths: Sequence[int]) -> List[int]:
        key = msg.key if msg.key is not None else msg.payload
        return [stable_hash(key) % n_edges]

    def choose_rows(self, n_rows, keys, n_edges, queue_depths):
        # a keyless row would hash its payload — that needs the unstacked
        # message, so fall back rather than silently misplace the key
        if keys is None or any(k is None for k in keys):
            return None
        return [stable_hash(k) % n_edges for k in keys]


class DirectSplit(Split):
    """Addressed delivery: the integer key *is* the target edge index.

    Used by the BSP pattern (Fig. 1, P10) where a worker emits a message to a
    specific peer; a degenerate (identity) case of dynamic port mapping.
    """

    def choose(self, msg: Message, n_edges: int, queue_depths: Sequence[int]) -> List[int]:
        key = msg.key if msg.key is not None else 0
        return [int(key) % n_edges]

    def choose_rows(self, n_rows, keys, n_edges, queue_depths):
        if keys is None:
            return [0] * n_rows
        return [int(k) % n_edges if k is not None else 0 for k in keys]


class BalancedSplit(Split):
    """Join-the-shortest-queue (paper's suggested future refinement of P8)."""

    def __init__(self):
        self._tie = itertools.count()

    def choose(self, msg: Message, n_edges: int, queue_depths: Sequence[int]) -> List[int]:
        if not queue_depths or len(queue_depths) != n_edges:
            return [next(self._tie) % n_edges]
        m = min(queue_depths)
        candidates = [i for i, d in enumerate(queue_depths) if d == m]
        return [candidates[next(self._tie) % len(candidates)]]

    def choose_many(self, msgs: Sequence[Message], n_edges: int,
                    queue_depths: Sequence[int]) -> List[List[int]]:
        # account for the batch's own placements so a burst does not pile
        # onto whichever queue happened to be shortest at batch start
        depths = (list(queue_depths) if len(queue_depths) == n_edges
                  else [0] * n_edges)
        out: List[List[int]] = []
        for m in msgs:
            idxs = self.choose(m, n_edges, depths)
            for i in idxs:
                depths[i] += 1
            out.append(idxs)
        return out

    def choose_rows(self, n_rows, keys, n_edges, queue_depths):
        # key-independent: same in-batch placement simulation as
        # choose_many, one int per row
        depths = (list(queue_depths) if len(queue_depths) == n_edges
                  else [0] * n_edges)
        out: List[int] = []
        for _ in range(n_rows):
            m = min(depths)
            candidates = [i for i, d in enumerate(depths) if d == m]
            i = candidates[next(self._tie) % len(candidates)]
            depths[i] += 1
            out.append(i)
        return out


SPLITS = {
    "duplicate": DuplicateSplit,
    "round_robin": RoundRobinSplit,
    "hash": HashSplit,
    "direct": DirectSplit,
    "balanced": BalancedSplit,
}


def make_split(name: str) -> Split:
    try:
        return SPLITS[name]()
    except KeyError:
        raise ValueError(f"unknown split policy {name!r}; one of {sorted(SPLITS)}")
