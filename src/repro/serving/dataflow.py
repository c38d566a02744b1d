"""LM inference as a Floe dataflow (the serving *plane*).

Topology (continuous batching as a dataflow cycle)::

    inject ──> sched ──> prefill ══▷ decode ──> respond (exactly-once sink)
                 ▲                    │  │ ▲
                 └──────── free ──────┘  └─┘ tick (self-loop)

* ``sched``    — admission + slot pool (``serving.scheduler.Scheduler``)
* ``prefill``  — vectorized full-prompt pass driven by the seed
  ``flash_attention`` Pallas kernel; admissions arrive stacked as ONE
  multi-column ``ArrayBatch`` carrier and leave as one carrier whose
  columns include each request's KV cache rows and first token
* ``decode``   — continuously-batched generation driven by the
  ``decode_attention`` (flash-decode) kernel.  The KV cache + slot table
  live in ``__floe_state__`` instance state, so checkpoints capture
  in-flight generations and a live weight hot-swap
  (``session.apply`` of a new factory) carries them across the update —
  generations keep streaming under the new weights, zero requests lost.
* ``respond``  — journal-aware exactly-once sink: replayed duplicates
  after a fault-plane recovery are deduped by rid before delivery.

The decode self-loop ("tick") keeps generation *inside* the dataflow: a
step is work-in-flight like any other message, so ``session.drain()``
naturally waits for all generations, backpressure applies, and a
checkpoint's consistent cut always contains either the pending tick or no
live slots.  At most one tick is in flight (``tick_pending``).

Bound to a running stage (the engine calls ``bind_telemetry``), prefill
and decode count their work in the coordinator's ``MetricsRegistry``
(``floe_prefill_tokens_total``/``floe_prefill_positions_total``,
``floe_decode_steps_total``/``floe_decode_tokens_total``, and the
``floe_decode_tick_wait_seconds`` histogram) and open ``floe.prefill.*`` /
``floe.decode.*`` profiler spans around their device launches, host waits
and bookkeeping.  Unbound (telemetry off), both are no-ops.

Every response dict carries ``version`` — the model version of the decode
weights at completion time (the paper's update-landmark made visible to
clients), plus ``t_sub``/``t_first``/``t_done`` for TTFT/TPOT accounting.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from ..api.builder import Flow
from ..core.pellet import Drop, KeyedEmit, PushPellet
from ..kernels.ops import pallas_interpret
from ..telemetry.tracing import NO_SPAN, span
from . import kv
from .kv import LMSpec, init_params
from .scheduler import Scheduler, make_request

__all__ = ["LMSpec", "init_params", "make_request", "PrefillPellet",
           "DecodePellet", "build_serving_flow", "swapped_flow", "TICK"]

#: decode self-loop sentinel payload
TICK = "__floe_tick__"


def _np32(x) -> np.ndarray:
    return np.asarray(x, dtype=np.int32)


class _StagePellet(PushPellet):
    """A serving pellet that counts and spans its work once the engine
    binds it to a stage; unbound, every instrumentation site is a no-op."""

    #: the stage this instance serves, set by ``bind_telemetry``
    _stage: Optional[str] = None

    def bind_telemetry(self, telemetry: Any, stage: str) -> None:
        self._stage = stage

    def _span(self, name: str, **meta: Any):
        if self._stage is None:
            return NO_SPAN
        return span(name, stage=self._stage, **meta)


def _counter(registry: Any, name: str, help_: str, stage: str):
    return registry.counter(name, help_, ("stage",)).labels(stage=stage)


class PrefillPellet(_StagePellet):
    """Vectorized prompt pass: admission columns in, KV + first token out.

    Stateless (weights are construction-time constants), so the engine is
    free to run prefill data-parallel and ``.elastic(...)`` can scale it.
    ``ref_path=True`` routes the same math through ``kernels/ref.py`` —
    the twin used to assert kernel parity *through the dataflow*.
    """

    in_ports = ("in",)
    out_ports = ("out",)
    _tokens = _positions = None      # counters, once bound

    def __init__(self, params: Dict[str, Any], spec: LMSpec, *,
                 version: int = 0, ref_path: bool = False):
        self.params = params
        self.spec = spec
        self.model_version = int(version)
        self.ref_path = bool(ref_path)
        self.interpret = pallas_interpret()

    def bind_telemetry(self, telemetry: Any, stage: str) -> None:
        super().bind_telemetry(telemetry, stage)
        registry = telemetry.registry
        self._tokens = _counter(
            registry, "floe_prefill_tokens_total",
            "Real prompt tokens prefilled, by stage.", stage)
        self._positions = _counter(
            registry, "floe_prefill_positions_total",
            "Prompt positions prefill computed (batch x padded prompt "
            "length), by stage.", stage)

    def compute_array(self, cols: Any) -> Any:
        if not isinstance(cols, dict) or "tokens" not in cols:
            return NotImplemented
        host_tokens = _np32(cols["tokens"])                  # (B, max_prompt)
        host_lengths = _np32(cols["length"])                 # (B,)
        B = int(host_tokens.shape[0])
        with self._span("floe.prefill.launch", rows=B):
            tokens = jnp.asarray(host_tokens)
            lengths = jnp.asarray(host_lengths)
            if self.ref_path:
                logits, kc, vc = kv.prefill_ref(
                    self.params, tokens, lengths, spec=self.spec)
            else:
                logits, kc, vc = kv.prefill(
                    self.params, tokens, lengths, spec=self.spec,
                    interpret=self.interpret)
        with self._span("floe.prefill.sync", rows=B):
            tok0 = _np32(kv.greedy(logits))                  # (B,)
        if self._tokens is not None:
            self._tokens.inc(int(host_lengths.sum()))
            self._positions.inc(host_tokens.size)
        return {
            "rid": _np32(cols["rid"]), "slot": _np32(cols["slot"]),
            "length": _np32(cols["length"]), "budget": _np32(cols["budget"]),
            "t_sub": np.asarray(cols["t_sub"], dtype=np.float64),
            "t_first": np.full(B, time.time(), dtype=np.float64),
            "tok0": tok0,
            # per-request cache rows (B, L, max_len, Hkv, hd): stay jnp so
            # the carrier hop to decode keeps device residency
            "k": jnp.moveaxis(kc, 0, 1), "v": jnp.moveaxis(vc, 0, 1),
        }

    def compute(self, payload: Any) -> Any:
        """Row-wise fallback (degraded batches): same math, batch of one."""
        if not isinstance(payload, dict) or "tokens" not in payload:
            return Drop
        cols = {k_: np.asarray(v_)[None] for k_, v_ in payload.items()}
        out = self.compute_array(cols)
        return {k_: v_[0] for k_, v_ in out.items()}


class DecodePellet(_StagePellet):
    """Continuously-batched decode: splice carriers in, responses out.

    Holds the whole decode-tier working set as ``__floe_state__`` instance
    state — KV caches ``(L, n_slots, max_len, Hkv, hd)``, per-slot
    lengths/last-token/liveness, and request metadata — which buys three
    guarantees at once: ``session.checkpoint`` captures in-flight
    generations, ``Session.restore`` resumes them mid-token, and a live
    weight hot-swap (``swap_pellet`` via ``session.apply``) carries them
    onto the new weights.  ``sequential=True``: the slot table is one
    shared accumulator, steps must serialize.  ``compute_array`` mutates
    that state by design; the splice is idempotent per (rid, slot), so the
    engine's per-row recovery re-running a failed batch cannot corrupt it.
    """

    in_ports = ("in",)
    out_ports = ("out", "free", "tick")
    sequential = True
    __floe_state__ = ("k", "v", "lengths", "last_tok", "live", "meta",
                      "tick_pending", "n_steps", "n_spliced")
    _steps = _tokens = _tick_wait = None      # counters, once bound
    #: ``perf_counter`` when the pending tick was emitted; instance-only
    #: (not checkpointed), so the first tick after a restore or a swap is
    #: not observed
    _tick_t: Optional[float] = None

    def __init__(self, params: Dict[str, Any], spec: LMSpec, *,
                 n_slots: int = 4, version: int = 0, ref_path: bool = False):
        self.params = params
        self.spec = spec
        self.n_slots = int(n_slots)
        self.model_version = int(version)
        self.ref_path = bool(ref_path)
        self.interpret = pallas_interpret()
        L, S = spec.n_layers, spec.max_len
        shape = (L, self.n_slots, S, spec.n_kv_heads, spec.head_dim)
        self.k = jnp.zeros(shape, dtype=jnp.float32)
        self.v = jnp.zeros(shape, dtype=jnp.float32)
        # dead slots are pinned at length 1 / token 0: the kernel attends
        # one zeroed cache position instead of a fully-masked (NaN) row
        self.lengths = np.ones(self.n_slots, dtype=np.int32)
        self.last_tok = np.zeros(self.n_slots, dtype=np.int32)
        self.live = np.zeros(self.n_slots, dtype=bool)
        self.meta: Dict[int, Dict[str, Any]] = {}
        self.tick_pending = False
        self.n_steps = 0
        self.n_spliced = 0

    def bind_telemetry(self, telemetry: Any, stage: str) -> None:
        super().bind_telemetry(telemetry, stage)
        registry = telemetry.registry
        self._steps = _counter(
            registry, "floe_decode_steps_total",
            "Decode steps taken over the slot batch, by stage.", stage)
        self._tokens = _counter(
            registry, "floe_decode_tokens_total",
            "Live slots advanced by decode steps (tokens generated), by "
            "stage.", stage)
        self._tick_wait = registry.histogram(
            "floe_decode_tick_wait_seconds",
            "Time from a decode tick's emission to the start of the step "
            "it drives, by stage.", ("stage",)).labels(stage=stage)

    # -- checkpoint / hot-swap state -----------------------------------------
    def get_state(self) -> Dict[str, Any]:
        # host-materialized + deep-copied: the snapshot must not alias
        # arrays/lists the running pellet keeps mutating after the cut
        return {"k": np.asarray(self.k), "v": np.asarray(self.v),
                "lengths": self.lengths.copy(),
                "last_tok": self.last_tok.copy(), "live": self.live.copy(),
                "meta": {s: dict(m, tokens=list(m["tokens"]))
                         for s, m in self.meta.items()},
                "tick_pending": self.tick_pending,
                "n_steps": self.n_steps, "n_spliced": self.n_spliced}

    def set_state(self, snapshot: Any) -> None:
        if not snapshot:
            return
        self.k = jnp.asarray(snapshot["k"])
        self.v = jnp.asarray(snapshot["v"])
        self.lengths = _np32(snapshot["lengths"])
        self.last_tok = _np32(snapshot["last_tok"])
        self.live = np.asarray(snapshot["live"], dtype=bool)
        self.meta = {int(s): dict(m, tokens=list(m["tokens"]))
                     for s, m in snapshot["meta"].items()}
        self.tick_pending = bool(snapshot["tick_pending"])
        self.n_steps = int(snapshot["n_steps"])
        self.n_spliced = int(snapshot["n_spliced"])

    # -- compute --------------------------------------------------------------
    def compute_array(self, cols: Any) -> Any:
        """Splice a prefill carrier: all rows land in their slots in ONE
        column-wise write per cache."""
        if not isinstance(cols, dict) or "slot" not in cols:
            return NotImplemented
        rows = int(np.asarray(cols["slot"]).shape[0])
        emits: List[List[Any]] = [[] for _ in range(rows)]
        slots = np.asarray(cols["slot"], dtype=np.int64)
        with self._span("floe.decode.splice", rows=rows):
            self.k = self.k.at[:, slots].set(
                jnp.moveaxis(jnp.asarray(cols["k"]), 0, 1))
            self.v = self.v.at[:, slots].set(
                jnp.moveaxis(jnp.asarray(cols["v"]), 0, 1))
            for i in range(rows):
                self._admit_row({name: col[i] for name, col in cols.items()},
                                emits[i], spliced=True)
            self._maybe_tick(emits[-1])
        return emits

    def compute(self, payload: Any) -> Any:
        emits: List[Any] = []
        if payload == TICK:
            self.tick_pending = False
            self._step(emits)
            self._maybe_tick(emits)
        elif isinstance(payload, dict) and "slot" in payload:
            # degraded single-row splice (row-wise fallback path)
            s = int(payload["slot"])
            self.k = kv.splice(self.k, payload["k"], s)
            self.v = kv.splice(self.v, payload["v"], s)
            self._admit_row(payload, emits, spliced=True)
            self._maybe_tick(emits)
        return emits or Drop

    # -- slot lifecycle --------------------------------------------------------
    def _admit_row(self, row: Dict[str, Any], emits: List[Any],
                   *, spliced: bool) -> None:
        s = int(row["slot"])
        rid = int(row["rid"])
        prior = self.meta.get(s)
        if prior is not None and prior["rid"] == rid:
            return          # replayed splice for an in-flight rid: idempotent
        self.n_spliced += 1
        tok0 = int(row["tok0"])
        self.lengths[s] = int(row["length"])
        self.last_tok[s] = tok0
        self.meta[s] = {"rid": rid, "tokens": [tok0],
                        "budget": int(row["budget"]),
                        "t_sub": float(row["t_sub"]),
                        "t_first": float(row["t_first"])}
        if int(row["budget"]) <= 1:    # prefill's token already filled it
            self._finish(s, emits)
        else:
            self.live[s] = True

    def _step(self, emits: List[Any]) -> None:
        """One decode_attention step over the full slot batch."""
        if not self.live.any():
            return
        if self._tick_t is not None:
            self._tick_wait.observe(time.perf_counter() - self._tick_t)
            self._tick_t = None
        step = kv.decode_step_ref if self.ref_path else kv.decode_step
        kwargs = {} if self.ref_path else {"interpret": self.interpret}
        with self._span("floe.decode.launch"):
            logits, self.k, self.v = step(
                self.params, self.k, self.v, jnp.asarray(self.lengths),
                jnp.asarray(self.last_tok), spec=self.spec, **kwargs)
        with self._span("floe.decode.sync"):
            nxt = _np32(kv.greedy(logits))
        self.n_steps += 1
        live = np.nonzero(self.live)[0]
        if self._steps is not None:
            self._steps.inc()
            self._tokens.inc(len(live))
        with self._span("floe.decode.bookkeep"):
            for s in live:
                s = int(s)
                self.lengths[s] += 1
                tok = int(nxt[s])
                m = self.meta[s]
                m["tokens"].append(tok)
                self.last_tok[s] = tok
                if len(m["tokens"]) >= m["budget"]:
                    self._finish(s, emits)

    def _finish(self, s: int, emits: List[Any]) -> None:
        m = self.meta.pop(s)
        emits.append(KeyedEmit({
            "rid": m["rid"], "tokens": list(m["tokens"]),
            "n_new": len(m["tokens"]), "version": self.model_version,
            "t_sub": m["t_sub"], "t_first": m["t_first"],
            "t_done": time.time()}, port="out"))
        emits.append(KeyedEmit({"free_slot": s}, port="free"))
        self.live[s] = False
        self.lengths[s] = 1           # dead-slot pin (see __init__)
        self.last_tok[s] = 0

    def _maybe_tick(self, emits: List[Any]) -> None:
        if self.live.any() and not self.tick_pending:
            self.tick_pending = True
            if self._tick_wait is not None:
                self._tick_t = time.perf_counter()
            emits.append(KeyedEmit(TICK, port="tick"))


# -- flow composition --------------------------------------------------------

def build_serving_flow(*, spec: Optional[LMSpec] = None, n_slots: int = 4,
                       max_prompt: Optional[int] = None,
                       default_budget: int = 8, seed: int = 0,
                       version: int = 0, ref_path: bool = False,
                       prefill_cores: int = 2,
                       elastic: Optional[Dict[str, Any]] = None,
                       exactly_once: bool = True,
                       name: str = "serving") -> Flow:
    """Compose the serving plane as a :class:`Flow`.

    ``seed``/``version`` pin the weights and their client-visible version
    tag; ``swapped_flow`` derives the hot-swap blueprint.  ``elastic`` (a
    dict of ``.elastic(...)`` kwargs, e.g. ``{"strategy": "dynamic",
    "max_cores": 4}``) scales the decode tier on the PR 6 tail
    percentiles.  ``ref_path=True`` builds the kernel-free twin.
    """
    spec = spec or LMSpec()
    if max_prompt is None:
        max_prompt = max(1, min(8, spec.max_len - default_budget - 1))
    params = init_params(spec, seed)
    flow = Flow(name)
    sched = flow.pellet("sched", lambda: Scheduler(
        n_slots=n_slots, max_prompt=max_prompt, max_len=spec.max_len,
        default_budget=default_budget))
    prefill = flow.pellet("prefill", lambda: PrefillPellet(
        params, spec, version=version, ref_path=ref_path),
        cores=prefill_cores).batch(max(2, n_slots), 2.0, array=True)
    decode = flow.pellet("decode", lambda: DecodePellet(
        params, spec, n_slots=n_slots, version=version, ref_path=ref_path),
        cores=1).batch(max(2, n_slots), 0.0, array=True)
    respond = flow.sink(
        "respond",
        exactly_once=exactly_once,
        key=lambda p: p["rid"] if isinstance(p, dict) else p)
    sched >> prefill
    prefill >> decode
    decode["tick"] >> decode          # generation stays in-dataflow
    decode["free"] >> sched["free"]   # slot recycling feedback
    decode >> respond
    if elastic:
        decode.elastic(**elastic)
    return flow


def swapped_flow(flow: Flow, *, seed: int, version: int) -> Flow:
    """Derive the live weight hot-swap blueprint: same topology, new
    weights + version on prefill/decode only (scheduler and sink keep
    factory identity, so ``session.apply`` stages exactly two task
    updates; ``__floe_state__`` carries the KV/slot tables across)."""
    old = flow.stages["decode"].proto
    spec, n_slots = old.spec, old.n_slots
    ref_path = old.ref_path
    params = init_params(spec, seed)
    new = flow.derive()
    new.stages["prefill"].replace(lambda: PrefillPellet(
        params, spec, version=version, ref_path=ref_path))
    new.stages["decode"].replace(lambda: DecodePellet(
        params, spec, n_slots=n_slots, version=version, ref_path=ref_path))
    return new
