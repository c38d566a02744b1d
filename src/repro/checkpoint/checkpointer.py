"""Checkpointing of a Floe graph: pellet state objects + pending messages.

The paper (§II.A) makes pellet state an *explicit* object precisely so the
framework can "offer resilience through transparent checkpointing of the
state object and resuming from the last saved state and the input messages
available then" — listed as future work there; implemented here:
``checkpoint_floe_graph`` writes every stateful flake's state object plus
its pending input messages (at-least-once replay on restore), and
``restore_floe_graph`` reads them back.
"""
from __future__ import annotations

import hashlib
import json
import os
import pickle
import time
from typing import Any, Dict, Optional


class CheckpointCorruptError(RuntimeError):
    """A floe checkpoint failed verification (truncated file, checksum
    mismatch, or unreadable payload).  Raised instead of unpickling
    garbage so a recovery path can fall back to an older checkpoint."""


# ---------------------------------------------------------------------------
# Floe-engine checkpointing (pellet state objects + pending messages)
# ---------------------------------------------------------------------------

#: engine-checkpoint container format: MAGIC | 4-byte big-endian header
#: length | JSON header {format, sha256, n_bytes, time} | pickle blob.
#: The header checksum turns a torn/truncated write into a loud
#: CheckpointCorruptError instead of an unpickling crash (or worse,
#: silently restoring half a graph).
_FLOE_MAGIC = b"FLOECKPT"
_FLOE_FORMAT = "floe-ckpt-v1"


def _write_floe_state(path: str, state: Dict[str, Any]) -> None:
    """Atomic checkpoint write: temp file + fsync + ``os.replace``, with
    a sha256 manifest over the payload.  A reader never observes a
    partially-written file at ``path``."""
    blob = pickle.dumps(state, protocol=pickle.HIGHEST_PROTOCOL)
    header = json.dumps({
        "format": _FLOE_FORMAT,
        "sha256": hashlib.sha256(blob).hexdigest(),
        "n_bytes": len(blob),
        "time": time.time(),
    }).encode("utf-8")
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(_FLOE_MAGIC)
        f.write(len(header).to_bytes(4, "big"))
        f.write(header)
        f.write(blob)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def _read_floe_state(path: str) -> Dict[str, Any]:
    """Read + verify an engine checkpoint; raises CheckpointCorruptError
    on any damage.  Pre-manifest checkpoints (raw pickle) still load."""
    with open(path, "rb") as f:
        magic = f.read(len(_FLOE_MAGIC))
        if magic != _FLOE_MAGIC:
            # legacy raw-pickle checkpoint from before the manifest format
            f.seek(0)
            try:
                state = pickle.load(f)
            except Exception as e:
                raise CheckpointCorruptError(
                    f"checkpoint {path!r}: not a floe checkpoint and "
                    f"not a readable legacy pickle ({e!r})") from e
            if not isinstance(state, dict):
                raise CheckpointCorruptError(
                    f"checkpoint {path!r}: legacy payload is "
                    f"{type(state).__name__}, expected dict")
            return state
        raw_len = f.read(4)
        if len(raw_len) != 4:
            raise CheckpointCorruptError(
                f"checkpoint {path!r}: truncated before header length")
        hlen = int.from_bytes(raw_len, "big")
        raw_header = f.read(hlen)
        if len(raw_header) != hlen:
            raise CheckpointCorruptError(
                f"checkpoint {path!r}: truncated inside header")
        try:
            header = json.loads(raw_header.decode("utf-8"))
        except Exception as e:
            raise CheckpointCorruptError(
                f"checkpoint {path!r}: unreadable header ({e!r})") from e
        blob = f.read()
    n_expected = header.get("n_bytes")
    if len(blob) != n_expected:
        raise CheckpointCorruptError(
            f"checkpoint {path!r}: truncated payload "
            f"({len(blob)} of {n_expected} bytes)")
    if hashlib.sha256(blob).hexdigest() != header.get("sha256"):
        raise CheckpointCorruptError(
            f"checkpoint {path!r}: payload checksum mismatch")
    try:
        state = pickle.loads(blob)
    except Exception as e:
        raise CheckpointCorruptError(
            f"checkpoint {path!r}: payload failed to unpickle "
            f"({e!r})") from e
    if not isinstance(state, dict):
        raise CheckpointCorruptError(
            f"checkpoint {path!r}: payload is {type(state).__name__}, "
            f"expected dict")
    return state


def checkpoint_floe_graph(coordinator, path: str, *,
                          extra: Optional[Dict[str, Any]] = None) -> None:
    """Persist every flake's state object and pending input messages.

    Also captures each flake's half-gathered count-window buffer (those
    messages were already popped from the channel, so pending alone would
    silently lose them) and, under the reserved ``"__meta__"`` key,
    arbitrary session metadata — ``restore_floe_graph`` skips keys that
    name no flake, so old checkpoints and old readers stay compatible.
    For a consistent cut of a live graph take the snapshot inside
    ``Coordinator.frozen()`` (what ``Session.checkpoint`` does).
    """
    def snap_msg(m):
        # the 4th field keeps landmark/control/update flags across the
        # round-trip (a checkpointed flush marker must not replay as data);
        # the 5th carries message meta — lineage and trace contexts parked
        # in a channel survive the restore.  restore accepts the
        # historical 3- and 4-tuples too.
        return (m.payload, m.key, m.seq,
                (m.landmark, m.update_landmark, m.control),
                dict(m.meta) if m.meta else None)

    state: Dict[str, Any] = {}
    for name, flake in coordinator.flakes.items():
        pending = {port: [snap_msg(m) for m in ch.snapshot()]
                   for port, ch in flake.inputs.items()}
        window = [snap_msg(m) for m in flake._window_buf]
        # mutable instance attributes of the live pellet (push pellets
        # that accumulate on ``self`` — outside the explicit state
        # object): captured via the Pellet.get_state hook / __floe_state__
        with flake._pellet_lock:
            try:
                pellet_state = flake._proto.get_state()
            except Exception as e:
                # a broken snapshot hook must not kill the checkpoint,
                # but silent state loss on the recovery path needs a
                # diagnostic
                pellet_state = None
                coordinator._record_error(name, e)
        state[name] = {"state": flake.state, "pending": pending,
                       "window": window, "pellet": pellet_state,
                       "version": flake.version, "cores": flake.cores}
    if extra:
        state["__meta__"] = dict(extra)
    _write_floe_state(path, state)


def read_floe_meta(path: str) -> Dict[str, Any]:
    """Session metadata embedded in a checkpoint ({} for old files)."""
    state = _read_floe_state(path)
    meta = state.get("__meta__", {})
    return meta if isinstance(meta, dict) else {}


def restore_floe_graph(coordinator, path: str) -> None:
    """Restore state objects and replay pending messages (at-least-once).

    Snapshot keys that name no flake of ``coordinator`` are skipped (the
    ``"__meta__"`` sidecar, or stages retired since the checkpoint).  A
    checkpointed half-gathered window buffer replays *before* the channel
    backlog — those messages were older — so window contents regather in
    the original order.
    """
    from ..core.message import Message

    def revive(rec) -> Message:
        payload, key = rec[0], rec[1]
        m = Message(payload=payload, key=key)
        if len(rec) > 3:
            m.landmark, m.update_landmark, m.control = rec[3]
        if len(rec) > 4 and rec[4]:
            m.meta = dict(rec[4])
        return m

    state = _read_floe_state(path)
    for name, snap in state.items():
        flake = coordinator.flakes.get(name)
        if flake is None or not isinstance(snap, dict) \
                or "pending" not in snap:
            continue
        flake.state = snap["state"]
        flake.set_cores(snap["cores"])
        if snap.get("pellet") is not None:
            # restore mutable instance attributes onto the fresh pellet
            # (the Pellet.set_state half of the checkpoint hook)
            with flake._pellet_lock:
                flake._proto.set_state(snap["pellet"])
        if snap.get("window") and flake.inputs:
            port0 = next(iter(flake.inputs))
            for rec in snap["window"]:
                flake.enqueue(port0, revive(rec))
        for port, msgs in snap["pending"].items():
            for rec in msgs:
                flake.enqueue(port, revive(rec))
