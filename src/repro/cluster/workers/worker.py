"""The worker process entry point (child side of a process-backed Host).

Spawned via ``multiprocessing.get_context("spawn")`` — a fresh interpreter
whose import + handshake time is the host's *real* spin-up latency.  The
loop is strictly request/response over the control pipe (pickle protocol
5); array blocks ride the shared-memory rings and are mapped, never
pickled.

Compute semantics mirror the engine's row-wise and columnar contracts
exactly (`Flake._batch_outputs` / `_array_outputs`): ``compute_batch``
with per-row ``BatchItemError`` isolation, ``compute_array`` with decline
(`NotImplemented`) and degrade-to-row-wise recovery — so a pellet behaves
identically whether its host is simulated or a real process.  Errors are
shipped back as reprs, not exceptions, to keep the reply channel free of
unpicklable tracebacks.
"""
from __future__ import annotations

import os
import pickle
from typing import Any, List, Optional, Tuple

import numpy as np

PROTO = 5  # pickle protocol: out-of-band-capable, required by the design


def _result_rows(pellet, payloads: List[Any]) -> Tuple[list, Optional[str]]:
    """compute_batch with the engine's exactly-once per-row recovery.

    Returns ``(wire_rows, note)`` where each wire row is ``("ok", value)``
    or ``("err", repr)`` and ``note`` surfaces a batch-level bug the
    per-row pass recovered from (the parent records it, like
    ``_batch_outputs`` does).
    """
    from repro.core.pellet import BatchItemError, PushPellet
    note = None
    fn = getattr(pellet, "compute_batch", None)
    try:
        if fn is not None:
            results = fn(payloads)
        else:
            results = PushPellet.compute_batch(pellet, payloads)
        if len(results) != len(payloads):
            raise ValueError(
                f"compute_batch returned {len(results)} results "
                f"for {len(payloads)} payloads")
    except Exception as batch_exc:
        results = []
        for p in payloads:
            try:
                results.append(pellet.compute(p))
            except Exception as e:
                results.append(BatchItemError(e))
        if not any(isinstance(r, BatchItemError) for r in results):
            note = repr(batch_exc)
    wire = [("err", repr(r.exc)) if isinstance(r, BatchItemError)
            else ("ok", r) for r in results]
    return wire, note


def _unstack(arr) -> List[Any]:
    """Rows of a single- or multi-column array block (for degrade paths)."""
    if isinstance(arr, dict):
        names = list(arr)
        n = arr[names[0]].shape[0]
        return [{k: arr[k][i] for k in names} for i in range(n)]
    return [arr[i] for i in range(arr.shape[0])]


def _compute_array(pellet, arr, rows: int):
    """Run the columnar hook with the engine's decline/degrade contract.

    Returns one of:
      ("cols", names_or_None, [np.ndarray ...], extra) — columnar result
      ("rows", wire_rows, note, True)                  — per-row result
    ``extra`` is a (seqs, keys) pair when the pellet returned an
    ``ArrayBatch`` carrying its own sidecars.
    """
    from repro.core.arraybatch import ArrayBatch
    from repro.core.pellet import FnPellet, PushPellet

    def degrade(exc: Exception):
        wire, note = _result_rows_perrow(pellet, _unstack(arr))
        if note is None and not any(tag == "err" for tag, _ in wire):
            note = repr(exc)
        return ("rows", wire, note, True)

    fn = getattr(pellet, "compute_array", None)
    declined = (
        fn is None
        or type(pellet).compute_array is PushPellet.compute_array
        or (isinstance(pellet, FnPellet) and not pellet.vectorized))
    if declined:
        wire, note = _result_rows(pellet, _unstack(arr))
        return ("rows", wire, note, True)
    try:
        res = fn(arr)
    except Exception as exc:
        return degrade(exc)
    if res is NotImplemented:
        wire, note = _result_rows(pellet, _unstack(arr))
        return ("rows", wire, note, True)
    extra = None
    if isinstance(res, ArrayBatch):
        if len(res) != rows:
            return degrade(ValueError(
                f"compute_array returned {len(res)} rows for {rows}"))
        if res.seqs is not None or res.keys is not None:
            extra = (res.seqs, res.keys)
        res = res.array
    if hasattr(res, "ndim") and getattr(res, "ndim", 0) >= 1 \
            and res.shape[0] == rows \
            and getattr(res, "dtype", None) != object:
        return ("cols", None, [np.ascontiguousarray(res)], extra)
    if isinstance(res, dict) and res and all(
            getattr(c, "ndim", 0) >= 1 and c.shape[0] == rows
            and getattr(c, "dtype", None) != object for c in res.values()):
        names = list(res)
        return ("cols", names,
                [np.ascontiguousarray(res[k]) for k in names], extra)
    if isinstance(res, (list, tuple)) and len(res) == rows:
        return ("rows", [("ok", r) for r in res], None, True)
    return degrade(ValueError(
        f"compute_array returned {type(res).__name__}, expected an "
        f"array with leading dim {rows} (or a {rows}-item sequence)"))


def _result_rows_perrow(pellet, payloads: List[Any]):
    """Per-row compute only (the degrade path — no compute_batch retry)."""
    wire = []
    for p in payloads:
        try:
            wire.append(("ok", pellet.compute(p)))
        except Exception as e:
            wire.append(("err", repr(e)))
    return wire, None


def _pin_jax_to_cpu() -> None:
    """Keep this worker off the accelerator.

    The chip belongs to the parent process: a second process that opens
    it fails or hangs.  Called before the worker's first JAX call, so
    offloaded pellets that compute with JAX run on the host CPU (and any
    process the worker starts inherits the setting).
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")


def worker_main(conn, tx_name: str, rx_name: str, ring_bytes: int,
                host_name: str) -> None:
    _pin_jax_to_cpu()
    from .shm import ShmRing
    tx = ShmRing.attach(tx_name, ring_bytes)   # parent → worker
    rx = ShmRing.attach(rx_name, ring_bytes)   # worker → parent
    pellets = {}  # flake name -> pellet instance

    conn.send_bytes(pickle.dumps(("hello", os.getpid()), protocol=PROTO))
    while True:
        try:
            blob = conn.recv_bytes()
        except (EOFError, OSError):
            break
        try:
            req = pickle.loads(blob)
            op = req[0]
            if op == "shutdown":
                rep = ("ok",)
                conn.send_bytes(pickle.dumps(rep, protocol=PROTO))
                break
            elif op == "ping":
                rep = ("pong", os.getpid())
            elif op == "register":
                _, name, factory = req
                pellets[name] = factory()
                rep = ("ok",)
            elif op == "rows":
                _, name, payloads = req
                pellet = pellets.get(name)
                if pellet is None:
                    rep = ("nak", f"flake {name!r} not registered")
                else:
                    wire, note = _result_rows(pellet, payloads)
                    rep = ("rows", wire, note, False)
            elif op == "array":
                _, name, names, specs, blobs = req
                pellet = pellets.get(name)
                if pellet is None:
                    rep = ("nak", f"flake {name!r} not registered")
                else:
                    if specs is not None:
                        cols = [tx.view(s) for s in specs]  # zero-copy map
                    else:
                        cols = [pickle.loads(b) for b in blobs]  # spilled
                    arr = cols[0] if names is None else dict(zip(names, cols))
                    rows = cols[0].shape[0]
                    out = _compute_array(pellet, arr, rows)
                    if out[0] == "cols":
                        _, onames, arrays, extra = out
                        if rx.fits(arrays):
                            ospecs = rx.write(arrays)
                            rep = ("array", onames, ospecs, None, extra)
                        else:  # result larger than the ring: spill
                            obl = [pickle.dumps(a, protocol=PROTO)
                                   for a in arrays]
                            rep = ("array", onames, None, obl, extra)
                    else:
                        rep = out
            else:
                rep = ("nak", f"unknown op {op!r}")
        except Exception as e:
            rep = ("nak", repr(e))
        try:
            out_blob = pickle.dumps(rep, protocol=PROTO)
        except Exception as e:
            out_blob = pickle.dumps(
                ("nak", f"unpicklable result: {e!r}"), protocol=PROTO)
        try:
            conn.send_bytes(out_blob)
        except (BrokenPipeError, OSError):
            break
    tx.close()
    rx.close()
