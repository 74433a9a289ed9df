"""Request-scoped distributed tracing: trace contexts + the span recorder.

The flight recorder (PR 1) answers "what was the runtime doing"; this
layer answers the question a production serving stack lives on: *where
did THIS request's latency go*.  A :class:`TraceContext` — a 64-bit
``trace_id`` plus a span sequence — is minted at
``RuntimeServer.submit`` / ``submit_stream`` and attached to tickets,
streams, and taskpools (``tp._trace``); when the recorder is installed,
every request then decomposes into spans:

==================  =========================================================
span                covers
==================  =========================================================
serve.admission     submit() -> admission grant (backpressure wait)
queue_wait          pool enqueue -> its first task entering execution
schedule            scheduler hand-off batches (SCHEDULE_BEGIN/END)
exec                one task body (EXEC_BEGIN/END) — *body-execute*
release             dep release + termdet accounting (RELEASE_DEPS_*)
comm.activate       one activation hop leaving / landing on a rank
comm.get            a rendezvous GET, request -> payload landed
comm.get_serve      the producer serving that GET (fragment window)
wire.ctrl           one binary CTRL frame landing (socket fabric)
serve.request       the whole submission, submit -> ticket resolution
==================  =========================================================

Cost model (the acceptance budget, gated by ``perf_smoke``):

- **disabled** (the default): the task-grain spans ride the existing
  PINS dispatch slots, so a hot site costs exactly what it costs today —
  one index load + falsy branch; the comm/serve sites compile the same
  one-branch pattern against :data:`recorder` (``r = spans.recorder; if
  r is not None: ...``), pinned allocation-free the same way as the
  flight recorder's disabled path.
- **enabled**: one thread-local stack op at begin, one list append at
  end — the ring-write shape of the flight recorder, no locks on the
  record path (the bound is enforced amortized, half-drop like the
  metrics snapshotter).

Cross-rank: the 8-byte ``trace_id`` rides the PR-4 binary wire protocol
(activation tuples via :func:`~parsec_tpu.comm.remote_dep
.pack_activation`, CTRL frame header word ``u2``, and the first DATA
fragment's meta — docs/OBSERVABILITY.md has the byte layout), and comm
spans carry ``flow``/``flow_side`` args (``act:<src>:<seq>``,
``get:<requester>:<get_id>``) that :mod:`~parsec_tpu.prof.tracemerge`
stitches into Chrome flow arrows across rank boundaries.

The **phase plane** further down (ISSUE 27) is the one part of this module
that reaches the profiler's clock: coarse ``TraceAnnotation`` spans inside
``Context`` and the device module, with exact self times per name
(:func:`phase`, :func:`phase_add`, :func:`phase_totals`).
"""

from __future__ import annotations

import contextlib
import itertools
import json
import os
import threading
import time
from typing import Any

from ..core.params import params as _params
from . import pins
from .pins import PinsEvent

_params.register("prof_spans", False,
                 "install the request-scoped span recorder at Context "
                 "init (trace-context spans for every traced taskpool; "
                 "off = the hot paths keep their existing one-branch "
                 "disabled cost)")
_params.register("prof_spans_max", 65536,
                 "finished spans kept in memory before the oldest half "
                 "is dropped (the snapshotter's bounding discipline)")

_now = time.perf_counter_ns


class TraceContext:
    """One request's trace identity: a process-unique 64-bit trace id
    plus a span-sequence counter for ids minted under it.  The wire
    carries the 8-byte ``trace_id``; the span id stays rank-local."""

    __slots__ = ("trace_id", "span_id")

    def __init__(self, trace_id: int, span_id: int = 1) -> None:
        self.trace_id = int(trace_id) & 0xFFFFFFFFFFFFFFFF
        self.span_id = span_id

    def __repr__(self) -> str:
        return f"TraceContext({self.trace_id:#x})"


_trace_seq = itertools.count(1)


def new_trace() -> TraceContext:
    """Mint a trace context unique across ranks/processes: the pid in
    the high bits de-collides concurrently minting processes, the
    monotonic sequence de-collides within one."""
    tid = ((os.getpid() & 0xFFFFFF) << 40) | (next(_trace_seq)
                                             & 0xFFFFFFFFFF)
    return TraceContext(tid)


class SpanRecorder:
    """Bounded store of finished spans.  ``record`` is one tuple build +
    one list append (GIL-atomic), the flight recorder's ring-write
    shape; the capacity bound drops the oldest half under a lock taken
    only at overflow."""

    __slots__ = ("max", "spans", "dropped", "_lock")

    def __init__(self, max_spans: int | None = None) -> None:
        self.max = max_spans if max_spans is not None \
            else int(_params.get("prof_spans_max"))
        self.spans: list[tuple] = []
        self.dropped = 0
        self._lock = threading.Lock()

    def record(self, name: str, trace_id: int, t0: int, t1: int,
               tenant: str | None = None,
               args: "dict | str | None" = None) -> None:
        """``args`` may be a plain string as the cheap form — the hot
        task-span path passes the task-class name without building a
        dict; export maps it to ``{"task": <str>}``."""
        self.spans.append((name, trace_id, t0, t1, tenant, args,
                           threading.get_ident()))
        if len(self.spans) > self.max:
            with self._lock:
                if len(self.spans) > self.max:
                    drop = self.max // 2
                    del self.spans[:drop]
                    self.dropped += drop

    def by_trace(self, trace_id: int) -> list[tuple]:
        return [s for s in list(self.spans) if s[1] == trace_id]


# the module-global recorder slot the hot sites branch on: None = the
# one-branch disabled path (pinned allocation-free in tests/test_tracing)
recorder: SpanRecorder | None = None


class _TaskSpans:
    """The PINS-driven task-grain spans: registered as ordinary PINS
    chains, so the DISABLED cost is the dispatch table's existing
    ``hooks[i] is None`` branch — no new hot-path site anywhere.  Only
    tasks of a TRACED pool (``tp._trace`` set) record; everything else
    pays one getattr at the end hook."""

    def __init__(self, rec: SpanRecorder) -> None:
        self.rec = rec
        self._tls = threading.local()
        self._pairs = [
            (PinsEvent.EXEC_BEGIN, self._exec_begin),
            (PinsEvent.EXEC_END, self._exec_end),
            (PinsEvent.RELEASE_DEPS_BEGIN, self._rel_begin),
            (PinsEvent.RELEASE_DEPS_END, self._rel_end),
            (PinsEvent.SCHEDULE_BEGIN, self._sched_begin),
            (PinsEvent.SCHEDULE_END, self._sched_end),
        ]

    def install(self) -> None:
        for ev, cb in self._pairs:
            pins.register(ev, cb)

    def uninstall(self) -> None:
        for ev, cb in self._pairs:
            pins.unregister(ev, cb)

    # every callback body is tuned for the enabled-cost budget (≤1µs/
    # task target): default-arg bindings for
    # the clock and the record method, try/except thread-local fast
    # paths, and string args instead of per-span dicts

    # -- exec: one task body -> "exec" (+ the pool's first exec closes
    # its "queue_wait" span, enqueue -> first body entering execution)
    def _exec_begin(self, es: Any, task: Any, _now=_now) -> None:
        tls = self._tls
        try:
            stk = tls.x
        except AttributeError:
            stk = tls.x = []
        stk.append((getattr(task.taskpool, "_trace", None), _now()))

    def _exec_end(self, es: Any, task: Any, _now=_now) -> None:
        try:
            tr, t0 = self._tls.x.pop()
        except (AttributeError, IndexError):
            return
        if tr is None:
            return
        tp = task.taskpool
        if getattr(tp, "_trace_first_ns", None) is None:
            tp._trace_first_ns = t0
            enq = getattr(tp, "_trace_enq_ns", None)
            if enq is not None:
                self.rec.record("queue_wait", tr.trace_id, enq, t0)
        self.rec.record("exec", tr.trace_id, t0, _now(), None,
                        task.task_class.name)

    # -- release_deps: successor release + termdet accounting
    def _rel_begin(self, es: Any, task: Any, _now=_now) -> None:
        tls = self._tls
        try:
            stk = tls.r
        except AttributeError:
            stk = tls.r = []
        stk.append((getattr(task.taskpool, "_trace", None), _now()))

    def _rel_end(self, es: Any, task: Any, _now=_now) -> None:
        try:
            tr, t0 = self._tls.r.pop()
        except (AttributeError, IndexError):
            return
        if tr is not None:
            self.rec.record("release", tr.trace_id, t0, _now())

    # -- schedule: one scheduler hand-off batch (trace of the first
    # task's pool; captured at BEGIN — the END payload may be emptied
    # by the keep-hot pop)
    def _sched_begin(self, es: Any, tasks: Any, _now=_now) -> None:
        tr = None
        if type(tasks) is list and tasks:
            tr = getattr(tasks[0].taskpool, "_trace", None)
        tls = self._tls
        try:
            stk = tls.s
        except AttributeError:
            stk = tls.s = []
        stk.append((tr, _now()))

    def _sched_end(self, es: Any, tasks: Any, _now=_now) -> None:
        try:
            tr, t0 = self._tls.s.pop()
        except (AttributeError, IndexError):
            return
        if tr is not None:
            self.rec.record("schedule", tr.trace_id, t0, _now())


_task_spans: _TaskSpans | None = None


def install(max_spans: int | None = None,
            recorder_obj: SpanRecorder | None = None) -> SpanRecorder:
    """Install the span recorder + the PINS task-span chains.
    ``recorder_obj`` re-installs an EXISTING recorder (spans and
    capacity preserved) — how a measurement that needs the recorder off
    hands a user-installed one back afterwards."""
    global recorder, _task_spans
    if recorder is not None:
        return recorder
    recorder = recorder_obj if recorder_obj is not None \
        else SpanRecorder(max_spans)
    _task_spans = _TaskSpans(recorder)
    _task_spans.install()
    return recorder


def uninstall() -> None:
    global recorder, _task_spans
    if _task_spans is not None:
        _task_spans.uninstall()
        _task_spans = None
    recorder = None


def ensure_installed() -> SpanRecorder | None:
    """Idempotent Context-init entry point: installs when the
    ``prof_spans`` MCA param asks for it (default off)."""
    if recorder is None and _params.get("prof_spans"):
        install()
    return recorder


# ---------------------------------------------------------------------------
# The phase plane: who owns each host second of a solve
# ---------------------------------------------------------------------------
#
# Coarse spans (one per batch or coarser) inside Context and the device
# module, written as ``jax.profiler.TraceAnnotation``s so they land on the
# profiler's clock beside the device planes, and accumulated as exact self
# times in one process-wide table.  docs/OBSERVABILITY.md lists the names.
#
# On while a profiler session is active or ``prof_spans`` is set; the flag
# is refreshed at a few coarse sites (Context init / add_taskpool / fini,
# once per device batch, sync, flush_cache), never per task.  Off, a site
# is ``phase()``'s load of the flag and a branch: no object is made, no
# clock is read.

phase_on = False

_phase_table: dict[str, list[int]] = {}   # name -> [self, inclusive, count]
_phase_lock = threading.Lock()
_phase_tls = threading.local()
_phase_off = contextlib.nullcontext()
_jax_profiler: Any = None       # jax.profiler, bound when the plane comes on
_session_active: Any = None     # TraceMe.is_enabled, bound at first refresh


def phase_refresh() -> None:
    """Re-read whether the plane is on (~1 us: one call into the profiler,
    one parameter read)."""
    global phase_on, _session_active, _jax_profiler
    if _session_active is None:
        from jaxlib._profiler import TraceMe
        _session_active = TraceMe.is_enabled
    on = _session_active() or bool(_params.get("prof_spans"))
    if on and _jax_profiler is None:
        import jax.profiler
        _jax_profiler = jax.profiler
    phase_on = on


def _phase_account(name: str, self_ns: int, incl_ns: int) -> None:
    with _phase_lock:
        row = _phase_table.get(name)
        if row is None:
            row = _phase_table[name] = [0, 0, 0]
        row[0] += self_ns
        row[1] += incl_ns
        row[2] += 1


class _Phase:
    """One open span: a TraceAnnotation on the calling thread's host line
    (``args`` become the event's arguments in the profiler's viewer) and a
    frame on the thread's stack, so that what its children cover comes off
    its self time."""

    __slots__ = ("name", "note", "t0", "child")

    def __init__(self, name: str, args: dict) -> None:
        self.name = name
        self.note = _jax_profiler.TraceAnnotation(name, **args)
        self.child = 0

    def __enter__(self) -> "_Phase":
        try:
            stack = _phase_tls.stack
        except AttributeError:
            stack = _phase_tls.stack = []
        stack.append(self)
        self.note.__enter__()
        self.t0 = _now()
        return self

    def __exit__(self, *exc) -> None:
        self.close(_now() - self.t0, *exc)

    def close(self, dt: int, *exc) -> None:
        """End the span at a duration its caller measured from ``t0``: a
        site that keeps a wall of its own reads one clock for both."""
        self.note.__exit__(*exc)
        stack = _phase_tls.stack
        stack.pop()
        if stack:
            stack[-1].child += dt
        _phase_account(self.name, dt - self.child, dt)


def phase(name: str, **args: Any) -> Any:
    """``with spans.phase("devmod.dispatch"): ...`` -- a span of the phase
    plane while it is on, the shared no-op otherwise.  ``args`` go to the
    annotation alone (one span looked at in the viewer); the table is keyed
    by ``name``."""
    return _Phase(name, args) if phase_on else _phase_off


def phase_add(name: str, ns: int, covered: int = 0) -> None:
    """Counter-only form for per-task work: ``ns`` of ``name`` inside the
    open span, with no annotation.  The caller tests ``phase_on`` before it
    reads a clock.  ``covered``: the part of ``ns`` that spans and counters
    inside this one already own, as the difference of two
    :func:`phase_covered` readings; it comes off this row's self time."""
    stack = getattr(_phase_tls, "stack", None)
    if stack:
        stack[-1].child += ns - covered
    _phase_account(name, ns - covered, ns)


def phase_covered() -> int:
    """Nanoseconds of the calling thread's open span that its children own
    so far (0 outside any span).  A counter that encloses other counters
    reads it before and after, and hands the difference to
    :func:`phase_add`."""
    stack = getattr(_phase_tls, "stack", None)
    return stack[-1].child if stack else 0


def phase_totals() -> dict[str, tuple[int, int, int]]:
    """A copy of the table: name -> (self ns, inclusive ns, count)."""
    with _phase_lock:
        return {k: tuple(v) for k, v in _phase_table.items()}


def phase_reset() -> None:
    with _phase_lock:
        _phase_table.clear()


# ---------------------------------------------------------------------------
# Chrome export
# ---------------------------------------------------------------------------

def to_chrome_events(pid: int = 3) -> list[dict]:
    """Finished spans as Chrome ``ph:"X"`` events (one tid per recording
    thread); comm spans keep their ``flow``/``flow_side`` args so
    :mod:`tracemerge` can stitch arrows."""
    r = recorder
    if r is None:
        return []
    tids: dict[int, int] = {}
    events: list[dict] = []
    for name, trace_id, t0, t1, tenant, args, ident in list(r.spans):
        tid = tids.setdefault(ident, len(tids))
        a: dict[str, Any] = {"trace": format(trace_id, "x")}
        if tenant:
            a["tenant"] = tenant
        if args:
            if type(args) is str:       # the cheap hot-path form
                a["task"] = args
            else:
                a.update(args)
        events.append({"name": name, "cat": "span", "ph": "X",
                       "ts": t0 / 1e3,
                       "dur": max((t1 - t0) / 1e3, 0.001),
                       "pid": pid, "tid": tid, "args": a})
    meta = [{"name": "thread_name", "ph": "M", "pid": pid, "tid": t,
             "args": {"name": f"spans:{ident}"}}
            for ident, t in sorted(tids.items(), key=lambda kv: kv[1])]
    return meta + events


def export_spans(path: str, rank: int = 0) -> dict:
    """Write THIS rank's spans RAW (the recorder tuples, json-listed) —
    the lossless input :mod:`critpath` replays; Chrome export rounds
    sub-µs spans up, this keeps the ns clocks."""
    r = recorder
    doc = {"rank": rank,
           "spans": [list(s) for s in (list(r.spans) if r else [])],
           "dropped": r.dropped if r else 0}
    with open(path, "w") as f:
        json.dump(doc, f)
    return {"path": path, "spans": len(doc["spans"]), "rank": rank}


def export_chrome(path: str, rank: int = 0) -> dict:
    """Write THIS rank's spans as a standalone Chrome trace, anchored by
    a wall-clock sync event — ``perf_counter_ns`` clocks are per-process,
    so :mod:`tracemerge` aligns ranks through the ``parsec_clock_sync``
    anchor (``unix_ns`` - ``perf_ns`` offset) before stitching."""
    events: list[dict] = [
        {"name": "parsec_clock_sync", "ph": "i", "s": "g",
         "ts": _now() / 1e3, "pid": rank, "tid": 0,
         "args": {"unix_ns": time.time_ns(), "perf_ns": _now()}},
        {"name": "process_name", "ph": "M", "pid": rank,
         "args": {"name": f"rank{rank}"}},
    ]
    events += to_chrome_events(pid=rank)
    trace = {"traceEvents": events}
    with open(path, "w") as f:
        json.dump(trace, f)
    return {"path": path, "events": len(events), "rank": rank}
