"""Remote dependency activation: release_deps across ranks.

Rebuild of ``remote_dep.c`` / ``remote_dep_mpi.c`` (SURVEY §3.4):

- the producer's ``release_deps`` accumulates per-output **rank bitmaps**
  into a :class:`RemoteDeps` record (``parsec_remote_deps_t``,
  ``remote_dep.h:132-153``) instead of releasing locally;
- :meth:`RemoteDepEngine.activate` packs a wire activation
  {taskpool comm-id, task-class id, locals, output mask, payload
  descriptors} (``remote_dep_wire_activate_t``, ``remote_dep.h:42-50``),
  **inlines short payloads** (``remote_dep_mpi_pack_dep:1270``), registers
  larger ones for rendezvous GET, and sends it down a **propagation tree**
  (binomial / chain / star, ``remote_dep.c:320-358``) re-derived
  deterministically at each hop from the sorted participant list;
- the receiver reconstructs the *ghost predecessor task* and re-runs its
  successor iterator restricted to this rank to learn where each payload
  lands (``remote_dep_get_datatypes:820``), pulls non-inline payloads
  (``remote_dep_mpi_get_start:2042``), then releases local successors into
  the scheduler (``remote_dep_release_incoming:955``) and forwards to its
  tree children (``parsec_remote_dep_propagate:409``);
- every in-flight activation holds a **pending action** on the producing
  taskpool's termination detector, dropped when the consumer acknowledges
  (``remote_dep_dec_flying_messages``, ``remote_dep.h:367-372``).

Writeback edges (``-> A(k)`` arrows whose home tile lives on another rank)
ride the same activation with an ownerless descriptor; the home rank applies
them to its local master copy.

TPU-first note: on hardware the payload move is an ICI device-to-device
transfer between HBM-resident tiles; the tree propagation maps onto neighbor
chains of the ICI torus.  The in-process fabric exercises the identical
protocol (SURVEY §5.8).
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import Any

import numpy as np

from ..core.backoff import Backoff
from ..core.params import params as _params
from ..data.data import data_create
from ..data.datatype import wire_slice_key
from ..prof import pins, spans as _spans
from ..prof.pins import PinsEvent

_now_ns = time.perf_counter_ns
from ..runtime.scheduling import (ExecutionStream, _find_input_dep,
                                  apply_writeback_to_home, schedule_tasks)
from ..runtime.task import Task
from .engine import (AM_TAG_ACTIVATE, AM_TAG_DTD, AM_TAG_GET_ACK,
                     AM_TAG_TERMDET, CommEngine)

_params.register("comm_short_limit", 4096,
                 "payloads at most this many bytes ride inside the "
                 "activation message (short-message inlining)")
_params.register("comm_thread", False,
                 "run a dedicated comm-progress thread per rank "
                 "(remote_dep_dequeue_main analog)")
_params.register("comm_coalesce", True,
                 "stage outgoing activations and flush one "
                 "priority-ordered AM per peer per progress "
                 "(remote_dep_mpi.c:1066-1194 aggregation)")
_params.register("comm_wire_datatypes", True,
                 "honor partial-tile wire datatypes ([type_remote/"
                 "displ_remote]) on remote edges; off ships full tiles")
_params.register("comm_bcast_tree", "binomial",
                 "multi-peer activation propagation: binomial|chain|star, "
                 "or auto (per-payload: resolve_tree_kind)")
_params.declare_knob("comm_bcast_tree",
                     values=("binomial", "chain", "star", "auto"))


def _wire_value(value: Any) -> Any:
    """Normalize a payload for the wire: JAX arrays stay device-resident
    (immutable — the device transport moves them D2D); everything else
    becomes a host ndarray."""
    from .device_fabric import is_device_array
    if is_device_array(value):
        return value
    return np.asarray(value)


def _slice_view(value: Any, view_key: tuple) -> Any:
    """Cut the wire view out of a tile (host or device array).  The copy
    is unconditional for host arrays (``ascontiguousarray`` would alias
    when the slice happens to be contiguous — e.g. 1-row tiles): the
    wire must not alias the live tile a local successor may be mutating.
    An out-of-range view is an error, not a silent clamp — numpy would
    ship a SMALLER region and the consumer's shape branch would
    misclassify it."""
    sl = []
    for axis, s in enumerate(view_key):
        s = slice(*s) if isinstance(s, (tuple, list)) else s
        if isinstance(s, slice) and s.stop is not None \
                and s.stop > value.shape[axis]:
            raise ValueError(
                f"wire view {view_key} exceeds tile shape {value.shape} "
                f"on axis {axis} (bad displ_remote?)")
        sl.append(s)
    out = value[tuple(sl)]
    if isinstance(out, np.ndarray):
        out = np.array(out, copy=True)
    return out


# ---------------------------------------------------------------------------
# compact activation wire form: coalesced batches used to ship as nested
# dicts (str keys repeated per message, per output, per batch entry); the
# positional tuples below cut the meta the codec has to walk and emit to a
# few dozen bytes per activation.  Inline ndarray payloads ride as raw
# codec segments either way — this trims the *structure*, the codec already
# removed the pickling of the *bytes*.
# ---------------------------------------------------------------------------

_OPT_DESC_KEYS = ("version", "inline", "wire", "shape", "dtype", "wire_view")


def _pack_desc(d: dict) -> tuple:
    flags = 0
    vals = []
    for i, k in enumerate(_OPT_DESC_KEYS):
        if k in d:
            flags |= 1 << i
            vals.append(d[k])
    return (d["flow_index"], 1 if d.get("writeback") else 0, flags, *vals)


def _unpack_desc(t: tuple) -> dict:
    d = {"flow_index": t[0], "writeback": bool(t[1])}
    flags, j = t[2], 3
    for i, k in enumerate(_OPT_DESC_KEYS):
        if flags & (1 << i):
            d[k] = t[j]
            j += 1
    return d


def pack_activation(msg: dict) -> tuple:
    """dict activation → positional wire tuple (tag "A").  The trailing
    element is the request's 8-byte trace context (prof/spans.py; 0 =
    untraced) — the cross-rank propagation of request-scoped tracing."""
    return ("A", msg["tp"], msg["tc"], msg["locals"],
            [_pack_desc(d) for d in msg["outputs"]], msg["ranks"],
            msg["tree"], msg["priority"], msg["seq"], msg["pos"],
            msg.get("trace") or 0)


def unpack_activation(t: tuple) -> dict:
    return {"tp": t[1], "tc": t[2], "locals": t[3],
            "outputs": [_unpack_desc(x) for x in t[4]], "ranks": t[5],
            "tree": t[6], "priority": t[7], "seq": t[8], "pos": t[9],
            # mixed-version peers may still ship the 10-element form
            "trace": t[10] if len(t) > 10 else 0}


# ---------------------------------------------------------------------------
# propagation trees (cf. remote_dep.c:320-358) — positions are indices into
# the sorted participant list, position 0 = root; children are re-derived
# identically at every hop, so no child list rides the wire
# ---------------------------------------------------------------------------

def _packed_trace(m: Any) -> int:
    """The trace id of one staged activation (packed tuple element 10;
    0 for legacy/test payloads that never carried one)."""
    if type(m) is tuple and len(m) > 10 and type(m[10]) is int:
        return m[10]
    return 0


TREE_KINDS = ("binomial", "chain", "star")


def _check_tree_kind(kind: str) -> None:
    if kind not in TREE_KINDS:
        from ..core.params import MCAParamValueError
        raise MCAParamValueError("comm_bcast_tree", kind, TREE_KINDS)


def resolve_tree_kind(kind: str | None = None, *,
                      nbytes: int | None = None,
                      n: int | None = None) -> str:
    """Resolve a tree-shape request (the ``comm_bcast_tree`` param when
    ``kind`` is None) to a concrete member of :data:`TREE_KINDS`.

    ``auto`` picks per payload class: payloads at or under
    ``comm_short_limit`` on small meshes (≤8 participants) take the
    latency-minimal star — they ride inline in the activation frame, so
    root egress is one frame per peer either way; everything else takes
    the egress-bounding binomial (the root re-serves at most ⌈log2 n⌉
    copies).  ``analysis/commcheck.recommend_tree`` derives its
    per-edge-class shapes through this same rule, so static advice and
    runtime resolution cannot drift.

    The wire never carries ``auto``: activation staging resolves once
    per message and ships the concrete kind, since every hop re-derives
    its children from ``msg["tree"]``."""
    if kind is None:
        kind = _params.get("comm_bcast_tree")
    if kind == "auto":
        if nbytes is not None and \
                0 < nbytes <= _params.get("comm_short_limit") \
                and (n if n is not None else 2) <= 8:
            return "star"
        return "binomial"
    _check_tree_kind(kind)
    return kind


def tree_children(kind: str, position: int, n: int) -> list[int]:
    _check_tree_kind(kind)
    if n <= 1:
        return []
    if kind == "star":
        return list(range(1, n)) if position == 0 else []
    if kind == "chain":
        return [position + 1] if position + 1 < n else []
    # binomial: children of p are p + 2^j for 2^j > p
    out = []
    j = 1
    while j <= position:
        j <<= 1
    while position + j < n:
        out.append(position + j)
        j <<= 1
    return out


def tree_parent(kind: str, position: int, n: int) -> int | None:
    """The inverse of :func:`tree_children`: the position that re-serves
    payloads to ``position`` (``None`` for the root).  Binomial parent =
    the position with its most-significant set bit cleared."""
    _check_tree_kind(kind)
    if position <= 0 or n <= 1:
        return None
    if kind == "star":
        return 0
    if kind == "chain":
        return position - 1
    return position & ~(1 << (position.bit_length() - 1))


# ---------------------------------------------------------------------------
# producer-side accumulation
# ---------------------------------------------------------------------------

class _RemoteOutput:
    __slots__ = ("flow_index", "copy", "ranks", "writeback_ranks", "views")

    def __init__(self, flow_index: int) -> None:
        self.flow_index = flow_index
        self.copy = None              # producing DataCopy (None for CTL)
        self.ranks: set[int] = set()  # ranks with consumer successors
        self.writeback_ranks: set[int] = set()  # remote home-tile writebacks
        # rank -> wire view key (slice triples) | None (full tile): the
        # partial-tile wire datatypes of the edges that reached that rank
        # ([type_remote/displ_remote]); a rank touched by several edges
        # with DIFFERENT views degrades to the full tile (the superset is
        # always correct; the reference picks one dep's datatype per rank)
        self.views: dict[int, tuple | None] = {}


class RemoteDeps:
    """Per-completed-task record of which peers need which outputs."""

    __slots__ = ("task", "outputs")

    def __init__(self, task: Task) -> None:
        self.task = task
        self.outputs: dict[int, _RemoteOutput] = {}

    def output(self, flow_index: int) -> _RemoteOutput:
        o = self.outputs.get(flow_index)
        if o is None:
            o = self.outputs[flow_index] = _RemoteOutput(flow_index)
        return o


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

class RemoteDepEngine:
    """Owns one rank's comm engine and implements the activation protocol.

    Installed as ``context.comm_engine``; the context delegates
    ``remote_dep_accumulate`` / ``remote_dep_activate`` here and calls
    :meth:`progress` from idle workers (the reference funnels the same work
    through its comm thread, ``remote_dep_mpi.c:426-484``).
    """

    def __init__(self, context: Any, ce: CommEngine) -> None:
        self.ctx = context
        self.ce = ce
        context.comm_engine = self
        self.my_rank = ce.rank
        self.nranks = ce.nranks
        self._es = ExecutionStream(-2, context.virtual_processes[0], context)
        self._seq = itertools.count(1)
        # outgoing activation stage: per-peer pending lists flushed by
        # progress (or the dedicated comm thread) as ONE coalesced AM per
        # peer, priority-ordered — the dep_cmd_queue aggregation of
        # remote_dep_mpi.c:1066-1194
        self._outq: dict[int, list] = {}
        self._outq_lock = threading.Lock()
        # serializes whole drains of the outgoing stage: concurrent callers
        # (worker via _flush_if_unthreaded, comm thread, engine flush hook)
        # would otherwise interleave their per-peer sends and break the
        # highest-priority-first ordering across snapshots
        self._flush_serial = threading.Lock()
        self._outseq = itertools.count()
        self._comm_thread: threading.Thread | None = None
        self._comm_stop: threading.Event | None = None
        # activation seq -> (taskpool, parent_rank or None)
        self._inflight: dict[int, Any] = {}
        self._iflock = threading.Lock()
        self.dup_acks = 0      # duplicate/unknown acks tolerated (faults)
        # activation payload bytes staged by THIS rank as a bcast root
        # (post wire-view slicing; counted once per receiving peer) — the
        # counter that proves partial-tile wire datatypes cut halo
        # traffic (~NB/R for the stencil's LR edges)
        self.payload_bytes_staged = 0
        # activations/DTD messages whose taskpool comm-id is not registered
        # yet (cf. DEP_NEW_TASKPOOL delays, remote_dep_mpi.c); guarded by a
        # lock: appended from worker progress, replayed from the enqueuing
        # thread; entries are (handler, src, msg)
        self._pending_unknown_tp: list[tuple[Any, int, dict]] = []
        self._pending_lock = threading.Lock()
        # distributed termdet monitors by taskpool comm-id, + stashed tokens
        self._termdet: dict[int, Any] = {}
        self._pending_termdet: list[dict] = []
        # received activation payload bytes (the inbound counterpart of
        # payload_bytes_staged; both are snapshotter-sampled gauges)
        self.payload_bytes_received = 0
        ce.tag_register(AM_TAG_ACTIVATE, self._on_activate)
        ce.tag_register(AM_TAG_GET_ACK, self._on_ack)
        ce.tag_register(AM_TAG_TERMDET, self._on_termdet)
        ce.tag_register(AM_TAG_DTD, self._on_dtd)
        # every engine progress drives the outgoing stage too — loops that
        # spin on raw ce.progress() (sync, quiesce) must flush forwards
        # their own AM handlers stage mid-wait
        ce.flush_hook = self.flush_outgoing
        from ..prof.counters import properties, sde
        sde.register_gauge(f"comm::rank{self.my_rank}::inflight",
                           self.inflight)
        sde.register_gauge(f"comm::rank{self.my_rank}::bytes_out",
                           lambda: self.payload_bytes_staged)
        sde.register_gauge(f"comm::rank{self.my_rank}::bytes_in",
                           lambda: self.payload_bytes_received)
        # wire-level twins of the payload counters: total framed bytes the
        # fabric moved each way, plus the fragment pipeline's own counters
        fabric = getattr(ce, "fabric", None)
        sde.register_gauge(f"comm::rank{self.my_rank}::wire_bytes_out",
                           lambda: getattr(fabric, "bytes_sent", 0))
        sde.register_gauge(f"comm::rank{self.my_rank}::wire_bytes_in",
                           lambda: getattr(fabric, "bytes_recv", 0))
        sde.register_gauge(f"comm::rank{self.my_rank}::frags_in",
                           lambda: getattr(ce, "frags_in", 0))
        sde.register_gauge(f"comm::rank{self.my_rank}::frag_bytes_in",
                           lambda: getattr(ce, "frag_bytes_in", 0))
        # per-peer bytes/frames/frags ledgers (socket tier) + fragment
        # pipeline state, as one live property the snapshotter samples
        properties.register("comm", f"rank{self.my_rank}",
                            self._comm_property)

    # ------------------------------------------------------------ lifecycle
    def enable(self) -> None:
        self.ce.enable()
        if _params.get("comm_thread") and self._comm_thread is None:
            # the dedicated progress thread of remote_dep_mpi.c's
            # remote_dep_dequeue_main: owns flushing + draining so workers
            # never stall on comm (they may still opportunistically
            # progress; the engine's internal lock keeps it single-driver)
            self._comm_stop = threading.Event()
            self._comm_thread = threading.Thread(
                target=self._comm_main, daemon=True,
                name=f"parsec-comm-r{self.my_rank}")
            self._comm_thread.start()

    def _comm_main(self) -> None:
        from ..core.backoff import Backoff
        backoff = Backoff()
        while not self._comm_stop.is_set():
            try:
                n = self.flush_outgoing() + self.ce.progress()
            except BaseException as e:   # surface like a worker failure:
                # a silent dead comm thread is a hang, not a crash
                self.ctx.record_failure(e)
                return
            if n:
                backoff.reset()
            else:
                backoff.wait()

    def fini(self) -> None:
        if self._comm_thread is not None:
            self._comm_stop.set()
            self._comm_thread.join(timeout=5)
            self._comm_thread = None
        self.flush_outgoing()
        self.ce.fini()
        from ..prof.counters import properties, sde
        for g in ("inflight", "bytes_out", "bytes_in", "wire_bytes_out",
                  "wire_bytes_in", "frags_in", "frag_bytes_in"):
            sde.unregister_gauge(f"comm::rank{self.my_rank}::{g}")
        properties.unregister("comm", f"rank{self.my_rank}")

    def _comm_property(self) -> dict:
        """The ``comm`` block of the live properties dictionary: fragment
        pipeline state plus per-peer wire ledgers when the fabric keeps
        them (docs/COMM.md)."""
        out: dict = {}
        fs = getattr(self.ce, "frag_state", None)
        if fs is not None:
            out.update(fs())
        ps = getattr(getattr(self.ce, "fabric", None), "peer_stats", None)
        if ps is not None:
            out["peers"] = ps()
        return out

    def debug_state(self) -> dict:
        """In-flight comm operations for the flight-recorder stall dump."""
        with self._outq_lock:
            staged = {dst: len(items) for dst, items in self._outq.items()}
        with self._iflock:
            inflight = len(self._inflight)
        with self._pending_lock:
            unknown = len(self._pending_unknown_tp)
            pending_td = len(self._pending_termdet)
        return {"rank": self.my_rank, "inflight_activations": inflight,
                "staged_sends": staged, "pending_unknown_taskpool": unknown,
                "pending_termdet_tokens": pending_td,
                "dup_acks": self.dup_acks,
                "payload_bytes_staged": self.payload_bytes_staged,
                "payload_bytes_received": self.payload_bytes_received,
                "engine_pending": self.ce.pending(),
                "comm_thread": self._comm_thread is not None,
                **self._comm_property()}

    def progress(self, es: Any = None) -> int:
        # the engine's progress drives flush_outgoing through flush_hook,
        # so one call covers both halves (no double drain)
        return self.ce.progress()

    # -------------------------------------------- outgoing stage (coalescing)
    def _post_activate(self, dst: int, msg: dict) -> None:
        # well-formed activations ride the compact positional form; other
        # dicts (tests driving the staging queue directly) pass through
        packed = pack_activation(msg) if "tp" in msg else msg
        if not _params.get("comm_coalesce"):
            self.ce.send_am(AM_TAG_ACTIVATE, dst, packed,
                            trace_id=_packed_trace(packed))
            return
        with self._outq_lock:
            self._outq.setdefault(dst, []).append(
                (-msg.get("priority", 0), next(self._outseq), packed))

    def _flush_if_unthreaded(self) -> None:
        """The staging queue is the comm thread's mailbox; without one,
        flush at the end of each send batch so busy workers never starve
        outgoing sends (coalescing still aggregates within the batch)."""
        if self._comm_thread is None:
            self.flush_outgoing()

    def flush_outgoing(self) -> int:
        """Drain the outgoing stage: one AM per peer, messages inside
        ordered highest-priority-first (the same-peer aggregation +
        priority ordering of remote_dep_mpi.c:1066-1194).  Whole drains are
        serialized so the priority contract holds globally, not merely
        per-snapshot, when multiple progress paths flush at once."""
        if not self._outq:
            return 0
        with self._flush_serial:
            with self._outq_lock:
                batches, self._outq = self._outq, {}
            n = 0
            for dst, items in batches.items():
                items.sort(key=lambda it: it[:2])
                msgs = [m for _, _, m in items]
                if len(msgs) == 1:
                    # a lone activation's trace context rides the frame
                    # header too (CTRL u2); coalesced aggregates mix
                    # requests, so their header word stays 0 and the
                    # per-message trace fields carry it instead
                    self.ce.send_am(AM_TAG_ACTIVATE, dst, msgs[0],
                                    trace_id=_packed_trace(msgs[0]))
                else:
                    # coalesced same-peer aggregate: a flat positional
                    # batch, no nested per-message dicts on the wire
                    self.ce.send_am(AM_TAG_ACTIVATE, dst, ("B", msgs))
                n += len(msgs)
        return n

    def inflight(self) -> int:
        with self._iflock:
            return len(self._inflight)

    def quiesce(self, timeout: float = 60.0) -> None:
        """Progress until this rank has no in-flight activations and an
        all-ranks barrier passes twice with silence in between (context-level
        drain; taskpool-level termination is the termdet's job)."""
        deadline = time.monotonic() + timeout
        backoff = Backoff()
        for _round in range(2):
            while self.inflight() or self.ce.pending() or self._outq:
                if self.progress():
                    backoff.reset()
                else:
                    backoff.wait()   # a spin here starves the peers' threads
                if time.monotonic() > deadline:
                    raise TimeoutError(f"rank {self.my_rank} quiesce timeout")
            self.ce.sync()

    # ------------------------------------------------- producer (sender) side
    def accumulate(self, remote: RemoteDeps | None, task: Task, flow, dep,
                   succ_tc, succ_locals, rank: int) -> RemoteDeps:
        """One remote successor edge found by release_deps (the remote branch
        of ``parsec_release_dep_fct``, ``parsec.c:1808-1874``)."""
        if remote is None:
            remote = RemoteDeps(task)
        out = remote.output(flow.flow_index)
        if not flow.is_ctl:
            out.copy = task.data[flow.flow_index]
        if succ_tc is None:
            # home-tile writeback must carry the whole tile
            out.writeback_ranks.add(rank)
            out.views[rank] = None
        else:
            out.ranks.add(rank)
            vk = (wire_slice_key(dep.wire_slices(task.locals))
                  if _params.get("comm_wire_datatypes") else None)
            if rank in out.views and out.views[rank] != vk:
                out.views[rank] = None     # conflicting views: full tile
            else:
                out.views.setdefault(rank, vk)
        return remote

    def activate(self, es: Any, task: Task, remote: RemoteDeps) -> None:
        """Kick the sends (``parsec_remote_dep_activate``, ``remote_dep.c:441``).

        Peers are grouped by identical output masks so true broadcasts share
        one propagation tree; odd one-off masks fall back to direct sends.
        """
        tp = task.taskpool
        # group peers by (flow set + per-flow wire view): ranks receiving
        # identical bytes share one propagation tree; a partial-tile view
        # ([type_remote]) forms its own group so the sliced payload is cut
        # once and broadcast, never re-sliced per peer
        by_mask: dict[tuple, list[int]] = {}
        all_ranks: dict[int, set[int]] = {}
        for fi, out in remote.outputs.items():
            for r in out.ranks | out.writeback_ranks:
                all_ranks.setdefault(r, set()).add(fi)
        for r, flows in all_ranks.items():
            key = tuple((fi, remote.outputs[fi].views.get(r))
                        for fi in sorted(flows))
            by_mask.setdefault(key, []).append(r)

        for flows, ranks in by_mask.items():
            ranks.sort()
            # resolve the tree shape ONCE per activation message (the
            # wire never carries "auto" — every hop re-derives children
            # from msg["tree"]); the hint is the largest staged payload
            hint = max((int(getattr(remote.outputs[fi].copy.value,
                                    "nbytes", 0))
                        for fi, _v in flows
                        if remote.outputs[fi].copy is not None),
                       default=0)
            tree_kind = resolve_tree_kind(nbytes=hint, n=len(ranks) + 1)
            outputs = []
            for fi, view in flows:
                out = remote.outputs[fi]
                desc = {"flow_index": fi,
                        "writeback": bool(out.writeback_ranks)}
                if out.copy is not None:
                    value = _wire_value(out.copy.value)
                    owned = False
                    if view is not None:
                        # partial-tile wire datatype: ship only the
                        # declared sub-block (the LR ghost columns, not
                        # the whole tile); the consumer receives it as a
                        # standalone region buffer
                        value = _slice_view(value, view)   # owned copy
                        desc["wire_view"] = view
                        owned = True
                    self.payload_bytes_staged += int(
                        getattr(value, "nbytes", 0)) * len(ranks)
                    desc["version"] = out.copy.version
                    if value.nbytes <= _params.get("comm_short_limit"):
                        # receiver must own its bytes even in-process
                        # (immutable device arrays ride as-is; a sliced
                        # view was already cut to an owned buffer)
                        desc["inline"] = (value.copy()
                                          if isinstance(value, np.ndarray)
                                          and not owned else value)
                    else:
                        all_ranks = [self.my_rank] + ranks
                        child_ranks = [
                            all_ranks[p] for p in tree_children(
                                tree_kind, 0, len(all_ranks))]
                        # snapshot at registration: a local successor may
                        # mutate the live host tile in place before the
                        # remote GET is served (the reference retains a
                        # refcounted data copy for the whole send); the
                        # engine copies mutable buffers at the boundary.
                        # peers= lets a dead child's share be reclaimed.
                        h = self.ce.mem_register(value,
                                                 refcount=len(child_ranks),
                                                 peers=set(child_ranks))
                        desc["wire"] = h.wire()
                        desc["shape"] = value.shape
                        desc["dtype"] = str(value.dtype)
                outputs.append(desc)
            tr = getattr(tp, "_trace", None)
            msg = {
                "tp": tp.comm_id,
                "tc": task.task_class.task_class_id,
                "locals": dict(task.locals),
                "outputs": outputs,
                # participants: producer at position 0, consumers after —
                # every hop re-derives its children from this list
                "ranks": [self.my_rank] + ranks,
                "tree": tree_kind,
                "priority": task.priority,
                # the request's 8-byte trace context rides every hop of
                # the propagation tree (prof/spans.py; 0 = untraced)
                "trace": tr.trace_id if tr is not None else 0,
            }
            self._send_to_children(tp, msg, my_pos=0)
        self._flush_if_unthreaded()

    def _send_to_children(self, tp: Any, msg: dict, my_pos: int) -> None:
        ranks = msg["ranks"]
        for child_pos in tree_children(msg["tree"], my_pos, len(ranks)):
            seq = next(self._seq)
            with self._iflock:
                self._inflight[seq] = tp
            # in-flight activation == pending action on the termdet
            # (remote_dep.h:360-372); fourcounter also counts raw messages
            tp.tdm.taskpool_addto_nb_pa(+1)
            tp.tdm.on_comm_sent()
            child_msg = dict(msg)
            child_msg["seq"] = seq
            child_msg["pos"] = child_pos
            pins.fire(PinsEvent.COMM_ACTIVATE_SEND, None,
                      (ranks[child_pos], seq))
            r = _spans.recorder
            if r is not None and msg.get("trace"):
                # the emit half of one activation hop: tracemerge
                # stitches it to the child rank's recv span by flow id
                t = _now_ns()
                r.record("comm.activate", msg["trace"], t, t,
                         args={"flow": f"act:{self.my_rank}:{seq}",
                               "flow_side": "emit",
                               "dst": ranks[child_pos]})
            self._post_activate(ranks[child_pos], child_msg)

    def _on_ack(self, eng, src: int, msg: dict) -> None:
        pins.fire(PinsEvent.COMM_ACK_RECV, None, int(msg["seq"]))
        with self._iflock:
            tp = self._inflight.pop(msg["seq"], None)
        if tp is None:
            # duplicate or unknown ack (transport replay after a reconnect,
            # or a peer acking twice): the first landing already settled the
            # pending-action count — tolerate, count, move on
            self.dup_acks += 1
            return
        tp.tdm.taskpool_addto_nb_pa(-1)

    # ------------------------------------------------- consumer (receiver) side
    # --------------------------------------------------- distributed termdet
    def send_termdet(self, dst: int, token: dict) -> None:
        """Ship a termination-detection token (reserved tag, §2.4/§2.6)."""
        self.ce.send_am(AM_TAG_TERMDET, dst, token)

    def _on_termdet(self, eng, src: int, token: dict) -> None:
        mon = self._termdet.get(token["tp"])
        if mon is None:
            tp = self.ctx._tp_by_comm_id.get(token["tp"])
            if tp is not None:
                raise RuntimeError(
                    f"rank {self.my_rank}: termdet wave token for taskpool "
                    f"{tp.name} whose detector ({tp.tdm.name}) is not "
                    f"distributed — termdet selection differs across ranks")
            with self._pending_lock:
                mon = self._termdet.get(token["tp"])
                if mon is None:
                    self._pending_termdet.append(token)
                    return
        mon.on_token(token)

    def taskpool_registered(self, tp: Any) -> None:
        """Replay activations/tokens that raced ahead of the enqueue."""
        distributed = hasattr(tp.tdm, "on_token")
        with self._pending_lock:
            if distributed:
                self._termdet[tp.comm_id] = tp.tdm
            replay_td = [t for t in self._pending_termdet
                         if t["tp"] == tp.comm_id]
            self._pending_termdet = [
                t for t in self._pending_termdet if t["tp"] != tp.comm_id]
            replay = [m for m in self._pending_unknown_tp
                      if m[2]["tp"] == tp.comm_id]
            self._pending_unknown_tp = [
                m for m in self._pending_unknown_tp
                if m[2]["tp"] != tp.comm_id]
        if replay_td and not distributed:
            raise RuntimeError(
                f"rank {self.my_rank}: received termdet wave tokens for "
                f"taskpool {tp.name} whose detector ({tp.tdm.name}) is not "
                f"distributed — termdet selection differs across ranks")
        for token in replay_td:
            tp.tdm.on_token(token)
        for handler, src, msg in replay:
            handler(self.ce, src, msg)

    def _lookup_or_pend(self, handler, src: int, msg: dict):
        tp = self.ctx._tp_by_comm_id.get(msg["tp"])
        if tp is None:
            with self._pending_lock:
                # re-check under the lock: registration may have just landed
                tp = self.ctx._tp_by_comm_id.get(msg["tp"])
                if tp is None:
                    self._pending_unknown_tp.append((handler, src, msg))
        return tp

    # ------------------------------------------------ DTD cross-rank channel
    def dtd_send(self, tp: Any, dst: int, msg: dict) -> None:
        """Ship a DTD protocol message (tile push / flush) to ``dst``,
        holding a termdet pending action until the ack lands (the
        DEP_DTD_DELAYED_RELEASE-era accounting, ``remote_dep_mpi.c:2022``)."""
        seq = next(self._seq)
        with self._iflock:
            self._inflight[seq] = tp
        tp.tdm.taskpool_addto_nb_pa(+1)
        tp.tdm.on_comm_sent()
        self.ce.send_am(AM_TAG_DTD, dst, dict(msg, tp=tp.comm_id, seq=seq))

    def _on_dtd(self, eng, src: int, msg: dict) -> None:
        tp = self._lookup_or_pend(self._on_dtd, src, msg)
        if tp is None:
            return
        tp.tdm.on_comm_recv()
        tp._on_dtd_message(self, src, msg)
        self.ce.send_am(AM_TAG_GET_ACK, src, {"seq": msg["seq"]})

    def _on_activate(self, eng, src: int, msg: Any) -> None:
        if type(msg) is tuple:
            if msg[0] == "B":
                # a coalesced aggregate: unpack in (priority) order
                for m in msg[1]:
                    self._on_activate(eng, src, m)
                return
            msg = unpack_activation(msg)
        elif "batch" in msg:
            # legacy dict aggregate (tests / mixed-version peers)
            for m in msg["batch"]:
                self._on_activate(eng, src, m)
            return
        tp = self._lookup_or_pend(self._on_activate, src, msg)
        if tp is None:
            return
        pins.fire(PinsEvent.ACTIVATE_CB_BEGIN, None, (src, msg["seq"]))
        want = [d for d in msg["outputs"] if "wire" in d]
        # every receiver owns its bytes: an inline payload forwarded down the
        # tree would otherwise alias across ranks
        landed: dict[int, Any] = {
            d["flow_index"]: (d["inline"].copy()
                              if isinstance(d["inline"], np.ndarray)
                              else d["inline"])
            for d in msg["outputs"] if "inline" in d}
        if not want:
            self._complete_incoming(tp, src, msg, landed)
            return
        remaining = [len(want)]

        def make_cb(d):
            def cb(value):
                landed[d["flow_index"]] = value
                remaining[0] -= 1
                if remaining[0] == 0:
                    self._complete_incoming(tp, src, msg, landed)
            return cb

        for d in want:
            # the GET inherits the activation's trace context, so both
            # ends of the rendezvous span-record under the request
            self.ce.get(tuple(d["wire"]), make_cb(d),
                        trace=msg.get("trace") or None)

    def _complete_incoming(self, tp: Any, src: int, msg: dict,
                           landed: dict[int, Any]) -> None:
        """All payloads present: release local successors, apply writebacks,
        forward down the tree, ack the parent."""
        t0 = _now_ns() if _spans.recorder is not None else 0
        for v in landed.values():
            self.payload_bytes_received += int(getattr(v, "nbytes", 0))
        tp.tdm.on_comm_recv()
        tc = tp.task_classes[msg["tc"]]
        ghost = Task(tp, tc, dict(msg["locals"]),
                     priority=msg.get("priority", 0))
        copies = {}
        for d in msg["outputs"]:
            fi = d["flow_index"]
            if fi in landed:
                datum = data_create(
                    landed[fi], key=("remote", tp.comm_id, tc.name,
                                     tuple(sorted(msg["locals"].items())), fi))
                copy = datum.get_copy(0)
                copy.version = d.get("version", 1)
                copies[fi] = copy
                ghost.data[fi] = copy

        ready: list[Task] = []
        out_mask = {d["flow_index"] for d in msg["outputs"]}
        wb = {d["flow_index"]: d.get("writeback", False)
              for d in msg["outputs"]}

        from ..data.reshape import reshape_for_edge, reshape_for_writeback

        def visitor(t: Task, flow, dep) -> None:
            if flow.flow_index not in out_mask:
                return
            if dep.target_class is None:
                # apply only on the tile's home rank: other ranks sharing
                # this activation's mask must not fabricate master copies
                if wb.get(flow.flow_index) and dep.data_ref is not None:
                    copy = copies.get(flow.flow_index)
                    dc, key = dep.data_ref(t.locals)
                    if copy is not None and dc.rank_of(*key) == self.my_rank:
                        copy = reshape_for_writeback(copy, dep, dc, key)
                        apply_writeback_to_home(dc, key, copy,
                                                owner=tp.taskpool_id)
                return
            succ_tc = tp.task_class(dep.target_class)
            for succ_locals in dep.each_target(t.locals):
                if succ_tc.in_space is not None \
                        and not succ_tc.in_space(succ_locals):
                    continue   # generated bounds check, receiver side
                rank = self._succ_rank(succ_tc, succ_locals)
                if rank != self.my_rank:
                    continue
                fi, di = _find_input_dep(succ_tc, dep.target_flow, tc.name,
                                         succ_locals, t.locals)
                # the wire carries the producer's type; a typed edge
                # repacks on the read side (remote_dep.h:102-113 dtt_dst
                # over dtt_src), lazily and shared per (copy, type)
                send = reshape_for_edge(copies.get(flow.flow_index), dep,
                                        succ_tc.flows[fi].deps_in[di])
                rt = self.ctx.deps.release_dep(tp, succ_tc, succ_locals, fi,
                                               di, send, None)
                if rt is not None:
                    ready.append(rt)

        tc.iterate_successors(ghost, visitor)

        # interior tree node: re-register landed buffers and forward
        # (parsec_remote_dep_propagate, remote_dep.c:409-436)
        my_pos = msg["pos"]
        children = tree_children(msg["tree"], my_pos, len(msg["ranks"]))
        if children:
            fwd = dict(msg)
            fwd["outputs"] = [dict(d) for d in msg["outputs"]]
            for d in fwd["outputs"]:
                if "wire" in d:
                    # snapshot: the landed host buffer is simultaneously
                    # handed to local successors, which may mutate it in
                    # place (the engine copies mutable buffers; device
                    # arrays are immutable and alias)
                    value = _wire_value(landed[d["flow_index"]])
                    h = self.ce.mem_register(
                        value, refcount=len(children),
                        peers={msg["ranks"][p] for p in children})
                    d["wire"] = h.wire()
            self._send_to_children(tp, fwd, my_pos=my_pos)
            self._flush_if_unthreaded()

        self.ce.send_am(AM_TAG_GET_ACK, src, {"seq": msg["seq"]})
        pins.fire(PinsEvent.ACTIVATE_CB_END, None, (src, msg["seq"]))
        r = _spans.recorder
        if r is not None and msg.get("trace"):
            # the recv half of the activation hop: flow-keyed by the
            # SENDING rank + seq, matching the emitter's span
            r.record("comm.activate", msg["trace"], t0 or _now_ns(),
                     _now_ns(),
                     args={"flow": f"act:{src}:{msg['seq']}",
                           "flow_side": "recv",
                           "released": len(ready)})
        if ready:
            schedule_tasks(self._es, ready, 0)

    def _succ_rank(self, tc, locals_) -> int:
        if tc.affinity is None:
            return self.my_rank
        dc, key = tc.affinity(locals_)
        if not isinstance(key, tuple):
            key = (key,)
        return dc.rank_of(*key)
