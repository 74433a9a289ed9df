"""Master data + per-device versioned copies (coherency substrate).

Rebuild of ``parsec/data.c`` / ``data_internal.h:28-73``: a master
``parsec_data_t`` {key, owner_device, preferred_device, device_copies[]} with
per-device ``parsec_data_copy_t`` {device_index, coherency state, readers,
version, device_private pointer, datatype}.

TPU mapping: a copy's payload is a host ``numpy.ndarray`` (device 0 = CPU) or
an HBM-resident ``jax.Array`` (TPU devices).  Coherency follows the reference's
MOESI-like protocol: INVALID / OWNED / EXCLUSIVE / SHARED; version numbers
decide staleness at stage-in time (``parsec_device_data_stage_in``,
``device_gpu.c:1269``).
"""

from __future__ import annotations

import itertools
import threading
from typing import Any

from .datatype import TileType

# Coherency states (cf. data_internal.h / data.h in the reference).
COHERENCY_INVALID = 0
COHERENCY_OWNED = 1
COHERENCY_EXCLUSIVE = 2
COHERENCY_SHARED = 3

# Flow access modes, shared across the tree (cf. parsec FLOW_ACCESS_*).
ACCESS_NONE = 0x0
ACCESS_READ = 0x1
ACCESS_WRITE = 0x2
ACCESS_RW = ACCESS_READ | ACCESS_WRITE

_data_keys = itertools.count()


class DataCopy:
    """One device's copy of a datum (cf. ``parsec_data_copy_t``).

    **Who owns ``value``.**  A device copy's array belongs to its device
    module.  Everyone else (tasks, repo entries, the LRU's eviction queue,
    failure recovery, the comm engine) keeps the *copy* and reads ``.value``
    when it uses it: the graph orders every reader of a version before the
    task that writes the next one, and the writer replaces ``value``.  The
    module may hand the array it replaces to the program that supersedes it
    (``device/tpu.py:_run_vmapped`` donates a written tile so that the
    result takes its buffer), after which that array is deleted.  It does
    so only while nobody else refers to the array (one reading of its
    reference count a lane: ``TPUDevice._sole_holder``): who does keep the
    array itself (the datum's host copy after a memory edge,
    ``scheduling.apply_writeback_to_home``; a send registered with the comm
    engine, ``comm/remote_dep.py``; a caller's variable) keeps it valid, at
    the price of the allocation the donation would have saved.  A weak
    reference does not count: ``pushed`` is asked by itself."""

    __slots__ = ("original", "device_index", "coherency", "readers", "version",
                 "value", "dtt", "flags", "arena_chunk", "reshaped",
                 "wb_mark", "pushed")

    def __init__(self, original: "Data", device_index: int,
                 value: Any = None, dtt: TileType | None = None) -> None:
        self.original = original
        self.device_index = device_index
        self.coherency = COHERENCY_INVALID if value is None else COHERENCY_SHARED
        self.readers = 0
        self.version = 0
        self.value = value
        self.dtt = dtt
        self.flags = 0
        self.arena_chunk = None  # owning arena, for recycling
        self.reshaped = None     # dtt-key -> shared repack future (reshape.py)
        # weakref to the device array whose D2H a push-out started
        # (device/tpu.py:pushout); a later writer's new array is not it
        self.pushed = None

    def __repr__(self) -> str:
        return (f"<DataCopy key={self.original.key} dev={self.device_index} "
                f"v{self.version} coh={self.coherency}>")


class Data:
    """Master record for one datum (cf. ``parsec_data_t``)."""

    def __init__(self, key: Any = None, dc: Any = None,
                 nb_elts: int = 0) -> None:
        self.key = key if key is not None else next(_data_keys)
        self.dc = dc                      # owning data collection, if any
        self.nb_elts = nb_elts
        self.owner_device = 0
        self.preferred_device = -1
        self.device_copies: dict[int, DataCopy] = {}
        self._lock = threading.RLock()

    # -- copy management (cf. parsec_data_copy_attach/detach/get_copy) ------
    def get_copy(self, device_index: int = 0) -> DataCopy | None:
        with self._lock:
            return self.device_copies.get(device_index)

    def attach_copy(self, copy: DataCopy) -> DataCopy:
        with self._lock:
            self.device_copies[copy.device_index] = copy
            return copy

    def detach_copy(self, device_index: int,
                    copy: DataCopy | None = None) -> DataCopy | None:
        """Drop the device's copy; given ``copy``, only if that is the one
        the datum holds there (a copy made invalid and staged anew since is
        another object)."""
        with self._lock:
            if copy is not None \
                    and self.device_copies.get(device_index) is not copy:
                return None
            return self.device_copies.pop(device_index, None)

    def newest_copy(self) -> DataCopy | None:
        """The highest-version valid copy on any device."""
        with self._lock:
            best = None
            for c in self.device_copies.values():
                if c.coherency == COHERENCY_INVALID:
                    continue
                if best is None or c.version > best.version:
                    best = c
            return best

    # -- coherency transitions ----------------------------------------------
    def start_write(self, device_index: int) -> DataCopy:
        """Make ``device_index``'s copy the exclusive owner; invalidate
        others (write-invalidate, cf. transfer_ownership in data.c)."""
        with self._lock:
            w = self.device_copies.get(device_index)
            if w is None:
                raise KeyError(f"no copy on device {device_index}")
            for idx, c in self.device_copies.items():
                if idx != device_index:
                    c.coherency = COHERENCY_INVALID
            w.coherency = COHERENCY_EXCLUSIVE
            w.version += 1
            self.owner_device = device_index
            return w

    def start_read(self, device_index: int) -> DataCopy:
        with self._lock:
            c = self.device_copies.get(device_index)
            if c is None or c.coherency == COHERENCY_INVALID:
                raise KeyError(f"no valid copy on device {device_index}")
            if c.coherency == COHERENCY_EXCLUSIVE:
                c.coherency = COHERENCY_OWNED
            c.readers += 1
            return c

    def end_read(self, device_index: int) -> None:
        with self._lock:
            c = self.device_copies[device_index]
            c.readers -= 1


def data_create(value: Any, device_index: int = 0, key: Any = None,
                dtt: TileType | None = None, dc: Any = None) -> Data:
    """Create a master datum with an initial copy (``parsec_data_create``)."""
    d = Data(key=key, dc=dc,
             nb_elts=getattr(value, "nbytes", 0) if value is not None else 0)
    if value is not None:
        c = DataCopy(d, device_index, value=value, dtt=dtt)
        c.coherency = COHERENCY_EXCLUSIVE
        c.version = 1
        d.attach_copy(c)
        d.owner_device = device_index
    return d


def scratch_copy(dtt: TileType) -> DataCopy:
    """A fresh zeroed tile of the declared type — the scratch allocation
    policy of ``prepare_input`` (WRITE-only/NEW flows), which the lowering
    mirrors."""
    import numpy as np
    d = data_create(np.zeros(dtt.shape, dtype=dtt.dtype), dtt=dtt)
    return d.get_copy(0)
