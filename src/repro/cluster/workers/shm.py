"""Single-slot shared-memory rings for zero-copy array transfer.

Each worker gets two rings: ``tx`` (parent → worker) and ``rx`` (worker →
parent).  Because every request on a worker's control pipe is synchronous
and lock-serialized by :class:`~repro.cluster.workers.handle.WorkerHandle`,
at most one transfer is in flight per ring at any time — so a "ring" is a
single slot at offset 0 and slot reclamation is implicit in the reply.
That keeps the protocol free of allocation/credit machinery while still
giving the property that matters: the sender writes the array block once,
the receiver maps it (``np.ndarray`` over the shared buffer), and the
array bytes are never pickled.

Transfers larger than the ring spill to inline pickle blobs on the control
channel (counted against the transport's pickled-bytes ledger, so spills
are visible); size the ring via ``ClusterSpec(shm_ring_bytes=...)``.
"""
from __future__ import annotations

from multiprocessing import shared_memory
from typing import List, Optional, Sequence, Tuple

import numpy as np


class ShmRing:
    """One shared-memory slot with numpy pack/map helpers."""

    def __init__(self, size: int, *, name: Optional[str] = None):
        if name is None:
            self.shm = shared_memory.SharedMemory(create=True, size=size)
            self.owner = True
        else:
            # worker-side attach.  NOTE: on Python 3.12 attaching also
            # registers the segment with the resource tracker — which mp
            # spawn children INHERIT from the parent, so the registry is a
            # shared set and the double-register is harmless; the parent's
            # single unlink on close() retires it.  Do not "fix" this with
            # resource_tracker.unregister here: that would remove the
            # parent's registration from the shared tracker.
            self.shm = shared_memory.SharedMemory(name=name)
            self.owner = False
        self.size = int(size)

    @classmethod
    def attach(cls, name: str, size: int) -> "ShmRing":
        return cls(size, name=name)

    @property
    def name(self) -> str:
        return self.shm.name

    # -- packing ---------------------------------------------------------
    def fits(self, arrays: Sequence[np.ndarray]) -> bool:
        return sum(int(a.nbytes) for a in arrays) <= self.size

    def write(self, arrays: Sequence[np.ndarray]) -> List[Tuple[str, tuple, int]]:
        """Copy arrays into the slot; returns (dtype, shape, offset) specs.

        The single memcpy on the send side — receivers map, they don't copy.
        """
        specs: List[Tuple[str, tuple, int]] = []
        off = 0
        for a in arrays:
            a = np.ascontiguousarray(a)
            if off + a.nbytes > self.size:
                raise ValueError(
                    f"array block of {a.nbytes}B at offset {off} exceeds "
                    f"ring size {self.size}B")
            dst = np.ndarray(a.shape, dtype=a.dtype, buffer=self.shm.buf,
                             offset=off)
            np.copyto(dst, a)
            specs.append((a.dtype.str, tuple(a.shape), off))
            off += int(a.nbytes)
        return specs

    # -- mapping ---------------------------------------------------------
    def view(self, spec: Tuple[str, tuple, int]) -> np.ndarray:
        """Zero-copy read-only view of one packed array."""
        dtype, shape, off = spec
        arr = np.ndarray(tuple(shape), dtype=np.dtype(dtype),
                         buffer=self.shm.buf, offset=int(off))
        arr.flags.writeable = False
        return arr

    def read(self, spec: Tuple[str, tuple, int]) -> np.ndarray:
        """Materialized (owned) copy of one packed array.

        Used on the parent side for worker *results*: the slot is reused by
        the next request, so results that outlive the reply must own their
        memory.  One memcpy — still no pickling of array bytes.
        """
        return self.view(spec).copy()

    def close(self) -> None:
        try:
            self.shm.close()
        except Exception:
            pass
        if self.owner:
            try:
                self.shm.unlink()
            except FileNotFoundError:
                pass
            except Exception:
                pass
