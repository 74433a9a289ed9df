"""PINS: instrumentation callback chains on runtime events.

Rebuild of ``parsec/mca/pins/pins.h:26-120``: modules register begin/end
callbacks on runtime events (SELECT, PREPARE_INPUT, EXEC, COMPLETE_EXEC,
SCHEDULE, RELEASE_DEPS, ...); the runtime fires them from fixed points in the
scheduling loop.

Dispatch is a table of **precompiled per-event slots** (:data:`hooks`): slot
``i`` is either ``None`` (nothing attached to event ``i``) or a closure that
delivers ``(es, payload)`` to the recorder and/or the registered chains.  A
hot-loop fire site is therefore::

    h = _hooks[_EXEC_BEGIN]          # _hooks = pins.hooks, bound at import
    if h is not None:
        h(es, task)

— one index load plus a falsy branch with ZERO allocation when the site is
disabled (the macro-compiled-out analog), and exactly one call when enabled.
The :data:`hooks` list object never changes identity; slots are swapped in
place by :func:`_rebuild`, so call sites may bind the list once at import.

:func:`fire` remains the compatible slow-path entry (used by warm sites and
tests); ``pins.recorder`` remains assignable exactly as in the flight-recorder
contract — the module intercepts the assignment (module-class property) and
retargets every slot, so a recorder installed by direct attribute write is
seen by the precompiled sites immediately.
"""

from __future__ import annotations

import sys
import threading
import types
from enum import IntEnum
from typing import Any, Callable


class PinsEvent(IntEnum):
    SELECT_BEGIN = 0
    SELECT_END = 1
    PREPARE_INPUT_BEGIN = 2
    PREPARE_INPUT_END = 3
    EXEC_BEGIN = 4
    EXEC_END = 5
    COMPLETE_EXEC_BEGIN = 6
    COMPLETE_EXEC_END = 7
    SCHEDULE_BEGIN = 8
    SCHEDULE_END = 9
    RELEASE_DEPS_BEGIN = 10
    RELEASE_DEPS_END = 11
    ACTIVATE_CB_BEGIN = 12
    ACTIVATE_CB_END = 13
    DATA_FLUSH_BEGIN = 14
    DATA_FLUSH_END = 15
    TASKPOOL_INIT = 16
    TASKPOOL_FINI = 17
    # 18-21 are unused: numbers are never reused, so a recorded trace
    # keeps reading the same events
    # a select that pulled work from beyond the stream's own queue
    # (payload: (task, distance)) — feeds the print_steals module
    SELECT_STEAL = 22
    # device-module sites (device/tpu.py) — primarily flight-recorder feed
    DEVICE_ENQUEUE = 23            # payload: task handed to the manager
    DEVICE_BATCH_BEGIN = 24        # payload: batch size
    DEVICE_BATCH_END = 25          # payload: batch size
    DEVICE_STAGE_IN = 26           # payload: H2D bytes of one batched put
    DEVICE_EVICT = 27              # payload: victims written back in a drain
    DEVICE_STAGE_MIXED_VERSIONS = 28   # payload: (key, kept_ver, other_ver)
    # comm sites (comm/remote_dep.py)
    COMM_ACTIVATE_SEND = 29        # payload: (dst_rank, seq)
    COMM_ACK_RECV = 30             # payload: seq
    # serving-layer lifecycle sites (serve/server.py) — payload:
    # (tenant, taskpool_name).  Every submission walks SUBMIT → {ADMIT →
    # START → COMPLETE | REJECT}; DRAIN fires once per server drain, so
    # the flight recorder covers the serving path out of the box
    SERVE_SUBMIT = 31
    SERVE_ADMIT = 32
    SERVE_REJECT = 33
    SERVE_START = 34
    SERVE_COMPLETE = 35
    SERVE_DRAIN = 36
    # zero-copy wire data path (comm/engine.py fragmented rendezvous) —
    # integer payloads are byte counts, so the flight recorder's per-event
    # vsums double as traffic counters in runtime_report's comm block
    COMM_GET_FRAG_SENT = 37        # payload: fragment bytes served
    COMM_GET_FRAG_RECV = 38        # payload: fragment bytes landed
    COMM_GET_DONE = 39             # payload: total bytes of a finished GET
    COMM_GET_PREFETCH = 40         # payload: owner rank of a lookahead GET


Callback = Callable[[Any, Any], None]   # (execution_stream_or_none, payload)

N_EVENTS = max(int(e) for e in PinsEvent) + 1

_lock = threading.Lock()
_chains: dict[int, list[Callback]] = {}
enabled = False

# the flight-recorder hook (prof/flight_recorder.py): a callable
# ``(event, payload) -> None`` or None.  Kept separate from the callback
# chains so the always-on recorder costs one slot call per site without
# flipping ``enabled`` (which would tax the per-task instrumentation
# branches).  Exposed as the assignable ``pins.recorder``
# attribute through the module-class property below; a hook with a
# ``site`` method compiles its own part of each slot (:func:`_recorder_site`).
_recorder: Callable[[Any, Any], None] | None = None

# the per-event dispatch table.  IDENTITY-STABLE: hot call sites bind this
# list object once at import; _rebuild() swaps slots in place.
hooks: list[Callable[[Any, Any], None] | None] = [None] * N_EVENTS


def _recorder_site(event: int) -> Callable[[Any, Any], None] | None:
    """The recorder's part of one event's slot, an ``(es, payload)``
    callable or None.  A recorder with a ``site(event)`` method compiles
    its own (the flight recorder: per event, what a note costs and whether
    the event reaches it at all); any other callable is called with the
    event."""
    rec = _recorder
    if rec is None:
        return None
    ev = PinsEvent(event)
    site = getattr(rec, "site", None)
    if site is not None:
        return site(ev)

    def h(es: Any, payload: Any, _r=rec, _e=ev) -> None:
        _r(_e, payload)
    return h


def _slot(event: int) -> Callable[[Any, Any], None] | None:
    """Compile one event's dispatch slot from the current recorder/chains."""
    rec = _recorder_site(event)
    chain = _chains.get(event)
    if not chain:
        return rec
    if rec is None:
        def h(es: Any, payload: Any, _c=chain) -> None:
            for cb in _c:               # snapshot-free: append-only lists
                cb(es, payload)
        return h

    def h(es: Any, payload: Any, _r=rec, _c=chain) -> None:
        _r(es, payload)
        for cb in _c:
            cb(es, payload)
    return h


def _rebuild() -> None:
    """Recompile every slot (caller holds ``_lock``, or is single-threaded
    module init).  In-place assignment keeps the table identity stable;
    an unused number's slot stays None."""
    for e in PinsEvent:
        hooks[e] = _slot(int(e))


def set_recorder(value: Callable[[Any, Any], None] | None) -> None:
    """Install/clear the flight-recorder hook and retarget every slot.
    ``pins.recorder = fn`` routes here through the module-class setter."""
    global _recorder
    with _lock:
        _recorder = value
        _rebuild()


def register(event: PinsEvent, cb: Callback) -> None:
    global enabled
    with _lock:
        _chains.setdefault(int(event), []).append(cb)
        enabled = True
        _rebuild()


def unregister(event: PinsEvent, cb: Callback) -> None:
    global enabled
    with _lock:
        lst = _chains.get(int(event), [])
        if cb in lst:
            # copy-on-write: slots iterate these lists unlocked
            _chains[int(event)] = [c for c in lst if c is not cb]
        enabled = any(_chains.values())
        _rebuild()


def fire(event: PinsEvent, es: Any = None, payload: Any = None) -> None:
    h = hooks[event]
    if h is not None:
        h(es, payload)


class _PinsModule(types.ModuleType):
    """Intercepts ``pins.recorder`` assignment: the flight recorder (and
    its tests) install by plain attribute write, which must retarget the
    precompiled slots — a raw module global could be rebound behind the
    dispatch table's back."""

    @property
    def recorder(self) -> Callable[[Any, Any], None] | None:
        return _recorder

    @recorder.setter
    def recorder(self, value: Callable[[Any, Any], None] | None) -> None:
        set_recorder(value)


sys.modules[__name__].__class__ = _PinsModule
