"""Bulk Synchronous Parallel pattern (paper §II.A, Fig. 1 P10).

BSP is composed from basic Floe patterns: ``n`` worker pellets whose "peers"
output ports are fully connected to each others' "data" input ports
(addressed delivery via ``DirectSplit``), plus a **manager pellet** acting as
the synchronization point.  Data messages on worker input ports are *gated*
by a control "tick" message from the manager: peer messages are buffered in
the worker's state and only consumed when the tick for their superstep
arrives, giving the superstep barrier semantics (messages sent in superstep
``k`` become visible in superstep ``k+1``).  The number of supersteps is
decided at runtime — workers vote to halt, Pregel-style.

The same pattern at the SPMD layer is a ``shard_map`` step with an
``all_to_all``/``all_gather`` at the superstep boundary (see
``examples/stream_clustering.py`` for the distributed-LSH instantiation).

``add_bsp``/``start_bsp`` are the legacy graph-level helpers; new code
should use the Session API combinator ``Flow.bsp(...)`` plus
``Session.start_bsp(...)`` (``repro.api``).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

from .graph import FloeGraph
from .message import Message
from .pellet import PullPellet, WindowPellet

#: user worker logic:
#:   fn(worker_id, superstep, user_state, inbox_payloads)
#:     -> (new_user_state, outbox=[(dst_worker, payload)], halt_vote: bool)
WorkerLogic = Callable[[int, int, Any, List[Any]],
                       Tuple[Any, List[Tuple[int, Any]], bool]]


class BSPWorker(PullPellet):
    in_ports = ("data", "ctrl")
    out_ports = ("peers", "done")

    def __init__(self, worker_id: int, logic: WorkerLogic,
                 init_state: Any = None):
        self.worker_id = worker_id
        self.logic = logic
        self._init = init_state

    def initial_state(self) -> Dict[str, Any]:
        return {"user": self._init, "inbox": [], "step": 0, "halted": False}

    def compute(self, messages: Iterable[Message], emit, state: Dict) -> Dict:
        state = dict(state)
        inbox: List[Tuple[int, Any]] = list(state["inbox"])
        ticks: List[int] = []
        for msg in messages:
            if msg.port == "tick":
                ticks.append(int(msg.payload))
            elif msg.is_data():
                # peer payloads are (target_superstep, value): buffering makes
                # messages visible only once their superstep starts, which is
                # the manager-gated barrier of the paper.
                inbox.append(msg.payload)
        for step in sorted(ticks):
            now = [v for (s, v) in inbox if s <= step]
            inbox = [(s, v) for (s, v) in inbox if s > step]
            if state["halted"] and not now:
                # Pregel semantics: a halted worker stays halted unless
                # messages arrive, but still acknowledges the barrier so the
                # manager's vote window completes.
                emit({"worker": self.worker_id, "step": step, "halt": True},
                     port="done")
                state["step"] = step + 1
                continue
            state["halted"] = False  # reactivated by incoming messages
            new_user, outbox, halt = self.logic(
                self.worker_id, step, state["user"], now)
            state["user"] = new_user
            for dst, payload in outbox:
                emit((step + 1, payload), key=int(dst), port="peers")
            emit({"worker": self.worker_id, "step": step, "halt": bool(halt)},
                 port="done")
            state["step"] = step + 1
            state["halted"] = bool(halt)
        state["inbox"] = inbox
        return state


class BSPManager(WindowPellet):
    """Synchronization point: a count-window over per-worker 'done' votes.

    When all ``n`` workers report a superstep done, either broadcast the next
    tick (some worker wants to continue) or emit the final result message.
    A ``max_supersteps`` cap bounds runaway iteration.
    """

    in_ports = ("in",)
    out_ports = ("tick", "result")
    sequential = True

    def __init__(self, n_workers: int, max_supersteps: int = 1000):
        super().__init__(window=n_workers)
        self.n_workers = n_workers
        self.max_supersteps = max_supersteps

    def compute(self, votes: List[Dict[str, Any]]):
        step = max(v["step"] for v in votes)
        all_halt = all(v["halt"] for v in votes)
        if all_halt or step + 1 >= self.max_supersteps:
            return {"result": {"supersteps": step + 1, "halted": all_halt}}
        return {"tick": step + 1}


def add_bsp(graph: FloeGraph, *, prefix: str, n_workers: int,
            logic: WorkerLogic, init_states: Optional[List[Any]] = None,
            max_supersteps: int = 1000,
            sink: Optional[str] = None) -> Tuple[List[str], str]:
    """Wire a BSP stage: n fully-connected workers + a manager pellet."""
    workers = [f"{prefix}_w{i}" for i in range(n_workers)]
    manager = f"{prefix}_mgr"
    inits = init_states or [None] * n_workers
    for i, name in enumerate(workers):
        wid, st = i, inits[i]
        graph.add(name, (lambda wid=wid, st=st: BSPWorker(wid, logic, st)))
    graph.add(manager,
              lambda: BSPManager(n_workers, max_supersteps=max_supersteps))
    for i, src in enumerate(workers):
        # fully-connected peers: DirectSplit addresses edge index == worker id
        for dst in workers:
            graph.connect(src, dst, src_port="peers", dst_port="data",
                          split="direct")
        graph.connect(src, manager, src_port="done", dst_port="in")
    for dst in workers:
        graph.connect(manager, dst, src_port="tick", dst_port="ctrl",
                      split="duplicate")
    if sink is not None:
        graph.connect(manager, sink, src_port="result", dst_port="in")
    return workers, manager


def start_bsp(coordinator, workers: List[str], *,
              seeds: Optional[Dict[int, List[Any]]] = None) -> None:
    """Kick off a wired BSP stage: seed worker inboxes (superstep 0 data) and
    inject tick 0 to every worker."""
    seeds = seeds or {}
    for i, name in enumerate(workers):
        for payload in seeds.get(i, []):
            coordinator.flakes[name].enqueue(
                "data", Message(payload=(0, payload), port="peers"))
    for name in workers:
        coordinator.flakes[name].enqueue(
            "ctrl", Message(payload=0, port="tick"))
