"""The comm-engine abstraction and the in-process fabric backend.

Rebuild of ``parsec_comm_engine.h`` (SURVEY §2.6): a transport exposes

- **active messages** — ``tag_register(tag, cb)`` + ``send_am(tag, dst,
  payload)``: small fixed-role control messages delivered by invoking the
  registered callback on the receiver during its ``progress()``
  (``parsec_comm_engine.h:60-93``);
- **registered memory + one-sided get** — ``mem_register`` publishes a local
  buffer under a :class:`MemHandle`; a peer pulls it with :meth:`get`
  (rendezvous protocol, ``parsec_comm_engine.h:95-113``), completion invoking
  a local callback and an optional remote-completion AM;
- **progress** — drains incoming traffic; never called concurrently for one
  engine (the funnelled discipline of ``parsec_mpi_funnelled.c``).

Reserved AM tags mirror ``parsec_comm_engine.h:24-40``.

Backends:

- :class:`InprocCommEngine` over :class:`InprocFabric` — N ranks inside one
  process with per-rank message queues.  This is the rebuild's analog of the
  reference's oversubscribed-MPI test runs (SURVEY §4): the *protocol* layer
  (remote_dep) is exercised unchanged; only the byte transport is local.
  ``get`` copies the source buffer (the stand-in for an ICI DMA read).
- :class:`~parsec_tpu.comm.device_fabric.DeviceCommEngine` over
  :class:`~parsec_tpu.comm.device_fabric.DeviceFabric` — the device-backed
  transport: each rank owns one JAX device, ``mem_register`` pins payloads
  device-resident, ``get`` is a device-to-device ``jax.device_put`` (ICI DMA
  on hardware), AMs stay host-side; see §5.8 of SURVEY.md for the mapping.
- :class:`~parsec_tpu.comm.socket_fabric.SocketCommEngine` over
  :class:`~parsec_tpu.comm.socket_fabric.SocketFabric` — ranks as separate
  OS processes over TCP (the DCN tier; launched by
  :func:`parsec_tpu.comm.multiproc.run_multiproc`, the mpiexec analog).
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import Any, Callable

import numpy as np

from ..core.backoff import Backoff
from ..core.params import params as _params
from ..prof import pins, spans as _spans
from ..prof.pins import PinsEvent

_now_ns = time.perf_counter_ns

# Reserved AM tags (cf. parsec_comm_engine.h:24-40).
AM_TAG_GET_REQ = 1       # internal: rendezvous pull request
AM_TAG_GET_REPLY = 2     # internal: rendezvous payload delivery
AM_TAG_GET_ACK = 3       # remote-completion notification after a get
AM_TAG_ACTIVATE = 4      # remote-dep activation
AM_TAG_TERMDET = 5       # termination-detection waves (fourcounter)
AM_TAG_BARRIER = 6       # context-level sync barrier
AM_TAG_DTD = 7           # DTD cross-rank data pushes / flushes
AM_TAG_GET_FRAG = 8      # internal: one rendezvous payload fragment
AM_TAG_GET_FRAG_ACK = 9  # internal: fragment credit (windowed pipelining)
AM_TAG_USER_BASE = 16    # first tag available to applications/DSLs

_params.register("comm_get_frag_bytes", 4 << 20,
                 "rendezvous GETs above this many bytes are split into "
                 "fragments of this size and pipelined (0 = monolithic "
                 "replies, the pre-fragmentation wire path)")
_params.register("comm_get_window", 4,
                 "max in-flight unacked fragments per GET (the sender-side "
                 "window; each landed fragment returns one credit)")
# the autotuner's declared domains (docs/TUNING.md): fragment sizes move
# in powers of two between 256KiB and 16MiB, the window between 1 and 16
_params.declare_knob("comm_get_frag_bytes", lo=256 << 10, hi=16 << 20,
                     scale="log2")
_params.declare_knob("comm_get_window", lo=1, hi=16, scale="log2")


class Capabilities:
    """What a backend supports (cf. ``parsec_comm_engine_capabilities_t``)."""

    __slots__ = ("sided", "multithreaded", "supports_noncontiguous")

    def __init__(self, sided: int = 1, multithreaded: bool = True,
                 supports_noncontiguous: bool = True) -> None:
        self.sided = sided
        self.multithreaded = multithreaded
        self.supports_noncontiguous = supports_noncontiguous


class MemHandle:
    """A published local buffer (cf. ``mem_register`` handles).

    ``refcount`` counts peers still expected to pull; the publisher drops the
    registration when it reaches zero (the ``mem_unregister`` moment).
    ``peers`` optionally names the consumer ranks — a peer that dies before
    its GET then releases its reference through
    :meth:`CommEngine.on_peer_failed` instead of pinning the buffer forever.
    """

    __slots__ = ("handle_id", "rank", "value", "refcount", "on_drained",
                 "peers")

    _ids = itertools.count(1)

    def __init__(self, rank: int, value: Any, refcount: int = 1,
                 on_drained: Callable[[], None] | None = None,
                 peers: set[int] | None = None) -> None:
        self.handle_id = next(MemHandle._ids)
        self.rank = rank
        self.value = value
        self.refcount = refcount
        self.on_drained = on_drained
        self.peers = set(peers) if peers is not None else None

    def wire(self) -> tuple[int, int]:
        """The on-the-wire form: (owner rank, handle id)."""
        return (self.rank, self.handle_id)


class _FragSend:
    """Sender-side state of one fragmented rendezvous reply: the ordered
    piece list plus the send cursor the credit window advances."""

    __slots__ = ("dst", "get_id", "handle_id", "pieces", "meta", "next",
                 "trace", "t0")

    def __init__(self, dst: int, get_id: int, handle_id: int,
                 pieces: list, meta: dict, trace: int = 0,
                 t0: int = 0) -> None:
        self.dst = dst
        self.get_id = get_id
        self.handle_id = handle_id
        self.pieces = pieces        # [(byte_offset, nbytes, buffer), ...]
        self.meta = meta
        self.next = 0
        self.trace = trace          # 8-byte trace context (prof/spans.py)
        self.t0 = t0                # serve-span open timestamp (ns)


class _LandingZone:
    """Receiver-side state of one fragmented GET: the preallocated final
    destination fragments ``recv_into`` (host tier) or accumulate onto
    (device tier), plus landed-offset dedup for transport replays."""

    __slots__ = ("get_id", "src", "meta", "dest", "flat", "remaining",
                 "landed", "frags")

    def __init__(self, get_id: int, src: int, meta: dict) -> None:
        self.get_id = get_id
        self.src = src
        self.meta = meta
        self.dest = None            # host tier: the final ndarray
        self.flat = None            # its flat uint8 view (recv_into target)
        self.remaining = int(meta["nbytes"])
        self.landed: set[int] = set()
        self.frags: dict[int, Any] | None = None   # device tier pieces


class InprocFabric:
    """Process-global N-rank fabric: per-rank inboxes + engine registry."""

    def __init__(self, nranks: int) -> None:
        self.nranks = nranks
        self._inboxes: list[deque] = [deque() for _ in range(nranks)]
        self._locks = [threading.Lock() for _ in range(nranks)]
        self.engines: list["InprocCommEngine | None"] = [None] * nranks

    def attach(self, rank: int) -> "InprocCommEngine":
        eng = InprocCommEngine(self, rank)
        self.engines[rank] = eng
        return eng

    def deliver(self, dst: int, tag: int, src: int, payload: Any,
                trace_id: int = 0) -> None:
        # trace_id is a wire-header concern (socket_fabric packs it into
        # the CTRL header's u2 word); the in-process fabric has no frame
        # headers, and the payload-level trace fields already carry it
        with self._locks[dst]:
            self._inboxes[dst].append((tag, src, payload))

    def drain(self, rank: int, limit: int = 64) -> list[tuple]:
        out = []
        with self._locks[rank]:
            while self._inboxes[rank] and len(out) < limit:
                out.append(self._inboxes[rank].popleft())
        return out

    def pending(self, rank: int) -> int:
        with self._locks[rank]:
            return len(self._inboxes[rank])


class CommEngine:
    """The abstract vtable (``parsec_comm_engine.h:176-199``)."""

    capabilities = Capabilities()

    def __init__(self, nranks: int, rank: int) -> None:
        self.nranks = nranks
        self.rank = rank
        self._am_callbacks: dict[int, Callable] = {}
        self._mem: dict[int, MemHandle] = {}
        self._mem_lock = threading.Lock()
        self._enabled = False
        self.prefetch_gets = 0     # lookahead GETs issued (prefetch_get)
        # upper-layer flush callback (the remote-dep outgoing stage): every
        # progress() drives it, so loops that spin on raw engine progress
        # (sync, quiesce) can never strand staged sends
        self.flush_hook: Callable[[], int] | None = None

    # -- active messages ----------------------------------------------------
    def tag_register(self, tag: int, cb: Callable[[Any, int, Any], None]) -> None:
        """``cb(engine, src_rank, payload)`` runs during ``progress``."""
        self._am_callbacks[tag] = cb

    def send_am(self, tag: int, dst: int, payload: Any,
                trace_id: int = 0) -> None:
        """``trace_id`` (optional 8-byte trace context, prof/spans.py)
        rides the frame header on binary-framed transports — payload
        semantics are untouched."""
        raise NotImplementedError

    # -- registered memory / one-sided ---------------------------------------
    def mem_register(self, value: Any, refcount: int = 1,
                     on_drained: Callable[[], None] | None = None,
                     owned: bool = False,
                     peers: set[int] | None = None) -> MemHandle:
        """Publish a buffer for one-sided GETs.

        The engine needs a stable snapshot (the last consumer may receive the
        registered buffer itself, not a copy), so mutable host arrays are
        copied here unless the caller asserts ownership with ``owned=True``
        — the invariant lives at the API boundary, not in caller convention.
        Immutable payloads (JAX arrays) alias safely either way.

        ``peers`` names the consumer ranks expected to pull (one reference
        each); :meth:`on_peer_failed` then releases a dead peer's share.
        """
        if not owned and isinstance(value, np.ndarray):
            value = value.copy()
        h = MemHandle(self.rank, value, refcount, on_drained, peers=peers)
        with self._mem_lock:
            self._mem[h.handle_id] = h
        return h

    def mem_retrieve(self, handle_id: int) -> MemHandle | None:
        with self._mem_lock:
            return self._mem.get(handle_id)

    def mem_release(self, handle_id: int, peer: int | None = None) -> None:
        """Drop one reference; unregister when drained."""
        with self._mem_lock:
            h = self._mem.get(handle_id)
            if h is None:
                return
            h.refcount -= 1
            if peer is not None and h.peers is not None:
                h.peers.discard(peer)
            if h.refcount > 0:
                return
            del self._mem[handle_id]
        if h.on_drained is not None:
            h.on_drained()

    def on_peer_failed(self, rank: int) -> int:
        """Release every registration share held for a dead peer — the
        buffer-GC moment the reference performs at communicator teardown
        (``parsec_mpi_funnelled.c:431``), here per-peer so a failed rank
        cannot pin its producers' memory forever.  Returns the number of
        handles fully drained by this."""
        drained = []
        with self._mem_lock:
            for hid in list(self._mem):
                h = self._mem[hid]
                if h.peers is None or rank not in h.peers:
                    continue
                h.peers.discard(rank)
                h.refcount -= 1
                if h.refcount <= 0:
                    del self._mem[hid]
                    drained.append(h)
        for h in drained:
            if h.on_drained is not None:
                h.on_drained()
        return len(drained)

    def get(self, rwire: tuple[int, int],
            on_complete: Callable[[Any], None],
            trace: int | None = None) -> None:
        """One-sided pull of the remote buffer named by ``rwire``;
        ``on_complete(value)`` runs locally when the payload has landed.
        ``trace`` is an optional 8-byte trace id (prof/spans.py): it
        rides the GET request so BOTH ends span-record the transfer
        under the originating request's trace."""
        raise NotImplementedError

    def prefetch_get(self, rwire: tuple[int, int],
                     on_complete: Callable[[Any], None],
                     trace: int | None = None) -> None:
        """A GET issued AHEAD of demand (ISSUE 11): same wire protocol
        — credit-windowed fragmented replies included — but tallied
        separately (``prefetch_gets``, the COMM_GET_PREFETCH PINS
        event → ``runtime_report``'s comm block, and ``frag_state`` in
        stall dumps) so wavefront lookahead (the KV tier map paging a
        cold sequence back one superpool early) is distinguishable
        from on-demand dependency pulls."""
        self.prefetch_gets += 1
        pins.fire(PinsEvent.COMM_GET_PREFETCH, None, rwire[0])
        self.get(rwire, on_complete, trace=trace)

    # -- lifecycle / progress -------------------------------------------------
    def enable(self) -> None:
        self._enabled = True

    def progress(self) -> int:
        """Drain incoming traffic; returns number of events handled."""
        raise NotImplementedError

    def pending(self) -> int:
        """Number of undelivered incoming events (0 if unknowable)."""
        return 0

    def sync(self) -> None:
        """Barrier across ranks (collective; used at context teardown)."""
        raise NotImplementedError

    def fini(self) -> None:
        """Teardown: force-drop every live registration (the reference frees
        registered buffers when the communicator dies)."""
        with self._mem_lock:
            leftovers, self._mem = list(self._mem.values()), {}
        for h in leftovers:
            if h.on_drained is not None:
                h.on_drained()


class InprocCommEngine(CommEngine):
    """N ranks in one process (the oversubscribed-MPI analog, SURVEY §4)."""

    def __init__(self, fabric: InprocFabric, rank: int) -> None:
        super().__init__(fabric.nranks, rank)
        self.fabric = fabric
        self._pending_gets: dict[int, Callable] = {}
        self._get_ids = itertools.count(1)
        self.dup_get_replies = 0
        self._barrier_seen: dict[int, set] = {}
        self._barrier_gen = 0
        self._progress_lock = threading.Lock()
        # fragmented-rendezvous state: receiver landing zones by get_id,
        # sender piece cursors by (dst, get_id).  _frag_active is the
        # lock-free busy-worker gate (a plain int read): nonzero while any
        # zone or send window is open, so workers with plenty of tasks
        # still interleave fragment progress (the T3-style overlap)
        self._landing: dict[int, _LandingZone] = {}
        self._frag_sends: dict[tuple[int, int], _FragSend] = {}
        self._frag_lock = threading.Lock()
        self._frag_active = 0
        # requester-side span state by get_id: (trace_id, t0_ns) —
        # entries exist only while the span recorder is installed, so
        # the disabled path never touches the dict
        self._get_spans: dict[int, tuple[int, int]] = {}
        self.frags_in = 0
        self.frag_bytes_in = 0
        self.frags_out = 0
        self.frag_bytes_out = 0
        self.dup_frags = 0
        self.tag_register(AM_TAG_GET_REQ, self._serve_get)
        self.tag_register(AM_TAG_GET_REPLY, self._finish_get)
        self.tag_register(AM_TAG_GET_FRAG, self._on_frag)
        self.tag_register(AM_TAG_GET_FRAG_ACK, self._on_frag_ack)
        self.tag_register(AM_TAG_BARRIER, self._on_barrier)

    # -- AM -------------------------------------------------------------------
    def send_am(self, tag: int, dst: int, payload: Any,
                trace_id: int = 0) -> None:
        # self-sends also go through the inbox so the callback runs from
        # progress(), never from the sender's stack
        self.fabric.deliver(dst, tag, self.rank, payload,
                            trace_id=trace_id)

    # -- one-sided get: rendezvous through internal AMs ----------------------
    # (the same emulation the reference's MPI backend uses: GET req AM →
    #  source replies with the payload, parsec_mpi_funnelled.c:247,980)
    def get(self, rwire: tuple[int, int],
            on_complete: Callable[[Any], None],
            trace: int | None = None) -> int:
        owner, handle_id = rwire
        get_id = next(self._get_ids)
        self._pending_gets[get_id] = on_complete
        msg = {"handle": handle_id, "get_id": get_id,
               "reply_to": self.rank}
        if _spans.recorder is not None:
            self._get_spans[get_id] = (trace or 0, _now_ns())
        if trace:
            msg["trace"] = trace
        self.send_am(AM_TAG_GET_REQ, owner, msg, trace_id=trace or 0)
        return get_id

    def resume_get(self, rwire: tuple[int, int], get_id: int,
                   trace: int | None = None) -> bool:
        """Re-issue a still-pending GET against a (possibly different)
        owner — the mid-tree fault path: a staging parent died with the
        transfer partially landed, so the requester pulls the REMAINDER
        from a surviving holder (typically the grandparent).  Offsets
        already in the landing zone ride a ``resume`` list on the GET
        request; the new server skips them, and any zombie fragments the
        dead parent managed to emit dedup against ``zone.landed`` exactly
        once.  Returns False when the get already completed (nothing to
        resume)."""
        owner, handle_id = rwire
        if get_id not in self._pending_gets:
            return False
        with self._frag_lock:
            zone = self._landing.get(get_id)
            resume = sorted(zone.landed) if zone is not None else []
            if zone is not None:
                # retarget the zone BEFORE any on_peer_failed(dead parent)
                # sweep: a zone pointing at the dead src would be reaped
                zone.src = owner
        msg = {"handle": handle_id, "get_id": get_id,
               "reply_to": self.rank}
        if resume:
            msg["resume"] = resume
        if trace:
            msg["trace"] = trace
        self.send_am(AM_TAG_GET_REQ, owner, msg, trace_id=trace or 0)
        return True

    def _record_get_span(self, get_id: int, nbytes: int) -> None:
        """Requester-side "comm.get" span: request sent -> payload
        landed, flow-keyed ``get:<requester>:<get_id>`` so tracemerge
        stitches it against the producer's serve span."""
        ent = self._get_spans.pop(get_id, None)
        r = _spans.recorder
        if ent is None or r is None:
            return
        tr, t0 = ent
        r.record("comm.get", tr, t0, _now_ns(),
                 args={"flow": f"get:{self.rank}:{get_id}",
                       "flow_side": "recv", "bytes": nbytes})

    def _serve_get(self, eng: CommEngine, src: int, msg: dict) -> None:
        h = self.mem_retrieve(msg["handle"])
        if h is None:
            raise RuntimeError(
                f"rank {self.rank}: GET for unknown handle {msg['handle']}")
        t0 = _now_ns() if _spans.recorder is not None else 0
        value = self._serve_value(h)
        plan = self._plan_frags(value)
        trace = msg.get("trace") or 0
        landed = set(msg.get("resume") or ())
        if plan is not None and landed:
            # resumed pull: serve only the offsets the requester is still
            # missing (its landing zone keeps what the dead parent shipped)
            pieces, meta = plan
            pieces = [p for p in pieces if p[0] not in landed]
            if not pieces:
                # everything already landed on the requester's side; its
                # zone completes off in-flight fragments — just drop the
                # share this pull would have consumed
                self.mem_release(msg["handle"], peer=msg["reply_to"])
                return
            plan = (pieces, meta)
        if plan is not None:
            # large payload: windowed fragmented reply — the receiver
            # copies fragments into its own preallocated destination, so
            # no sender-side ownership copy is needed here
            self._start_frag_send(msg["reply_to"], msg["get_id"],
                                  msg["handle"], plan, trace=trace, t0=t0)
            return
        # the DMA copy: the receiver must own its bytes (ICI read analog).
        # The registered buffer is already a private snapshot, so the LAST
        # consumer takes ownership of it instead of copying again.
        if isinstance(value, np.ndarray) and h.refcount > 1:
            value = value.copy()
        self.send_am(AM_TAG_GET_REPLY, msg["reply_to"],
                     {"get_id": msg["get_id"], "value": value},
                     trace_id=trace)
        r = _spans.recorder
        if r is not None:
            r.record("comm.get_serve", trace, t0, _now_ns(),
                     args={"flow": f"get:{msg['reply_to']}:"
                                   f"{msg['get_id']}",
                           "flow_side": "emit",
                           "bytes": int(getattr(value, "nbytes", 0))})
        # the puller's share is consumed: clear it from the expected-peer
        # set too, so a LATER death of that rank cannot double-release
        self.mem_release(msg["handle"], peer=msg["reply_to"])

    def _finish_get(self, eng: CommEngine, src: int, msg: dict) -> None:
        with self._frag_lock:
            # a resumed GET answered monolithically (the new owner's frag
            # params differ) supersedes any half-landed zone: retire it or
            # _frag_active would stay pinned forever
            if self._landing.pop(msg["get_id"], None) is not None:
                self._frag_active -= 1
        cb = self._pending_gets.pop(msg["get_id"], None)
        if cb is None:
            # duplicate reply (e.g. a transport-level replay after a
            # reconnect): the first landing completed the get — idempotent
            self.dup_get_replies += 1
            return
        value = self._land_value(msg["value"])
        self._record_get_span(msg["get_id"],
                              int(getattr(value, "nbytes", 0)))
        cb(value)

    # -- fragmentation hooks (overridden by the device tiers) -----------------
    def _serve_value(self, h: MemHandle) -> Any:
        """What a GET of handle ``h`` serves (device tiers stage here)."""
        return h.value

    def _land_value(self, value: Any) -> Any:
        """Final landing transform applied to every completed GET
        (device tiers ``device_put`` here)."""
        return value

    def _plan_frags(self, value: Any) -> tuple[list, dict] | None:
        """Fragmentation plan for a large payload: ``(pieces, meta)`` with
        ``pieces = [(byte_offset, nbytes, buffer), ...]``, or None for the
        monolithic reply path."""
        fb = _params.get("comm_get_frag_bytes")
        if not fb or not isinstance(value, np.ndarray) \
                or value.dtype == object or value.nbytes <= fb:
            return None
        v = value if value.flags.c_contiguous else np.ascontiguousarray(value)
        flat = v.reshape(-1).view(np.uint8)
        pieces = [(off, min(fb, v.nbytes - off), flat[off:off + fb])
                  for off in range(0, v.nbytes, fb)]
        meta = {"shape": tuple(v.shape), "dtype": v.dtype.str,
                "nbytes": v.nbytes, "nfrags": len(pieces), "tier": "host"}
        return pieces, meta

    def _transport_frag(self, dst: int, get_id: int, offset: int,
                        nbytes: int, data: Any, meta: dict | None,
                        last: bool) -> None:
        """Ship one fragment.  In-process: the inbox carries a VIEW of the
        registered buffer; the receiver-side zone copy is the DMA analog.
        The socket tier overrides this with a binary DATA frame whose raw
        bytes ``recv_into`` the destination directly."""
        self.fabric.deliver(dst, AM_TAG_GET_FRAG, self.rank,
                            (get_id, offset, nbytes, meta, data))

    # -- fragmentation: sender side -------------------------------------------
    def _start_frag_send(self, dst: int, get_id: int, handle_id: int,
                         plan: tuple[list, dict], trace: int = 0,
                         t0: int = 0) -> None:
        pieces, meta = plan
        if trace:
            # the first DATA frame's codec meta carries the trace: later
            # fragments resolve through their get_id (docs/OBSERVABILITY)
            meta = dict(meta, trace=trace)
        fs = _FragSend(dst, get_id, handle_id, pieces, meta, trace, t0)
        with self._frag_lock:
            self._frag_sends[(dst, get_id)] = fs
            self._frag_active += 1
        for _ in range(max(int(_params.get("comm_get_window")), 1)):
            if not self._send_next_frag(fs):
                break

    def _send_next_frag(self, fs: _FragSend) -> bool:
        i = fs.next
        if i >= len(fs.pieces):
            return False
        fs.next = i + 1
        off, n, data = fs.pieces[i]
        last = fs.next == len(fs.pieces)
        self._transport_frag(fs.dst, fs.get_id, off, n, data,
                             fs.meta if i == 0 else None, last)
        self.frags_out += 1
        self.frag_bytes_out += n
        pins.fire(PinsEvent.COMM_GET_FRAG_SENT, None, n)
        if last:
            with self._frag_lock:
                self._frag_sends.pop((fs.dst, fs.get_id), None)
                self._frag_active -= 1
            r = _spans.recorder
            if r is not None:
                r.record("comm.get_serve", fs.trace, fs.t0 or _now_ns(),
                         _now_ns(),
                         args={"flow": f"get:{fs.dst}:{fs.get_id}",
                               "flow_side": "emit",
                               "bytes": int(fs.meta.get("nbytes", 0)),
                               "frags": len(fs.pieces)})
            self.mem_release(fs.handle_id, peer=fs.dst)
        return True

    def _on_frag_ack(self, eng: CommEngine, src: int, payload: Any) -> None:
        with self._frag_lock:
            fs = self._frag_sends.get((src, payload[0]))
        if fs is not None:
            self._send_next_frag(fs)

    # -- fragmentation: receiver side -----------------------------------------
    def _zone_alloc(self, get_id: int, src: int, meta: dict) -> _LandingZone:
        zone = _LandingZone(get_id, src, meta)
        if meta.get("tier") == "device":
            zone.frags = {}
        else:
            zone.dest = np.empty(meta["shape"], np.dtype(meta["dtype"]))
            zone.flat = zone.dest.reshape(-1).view(np.uint8)
        return zone

    def landing_view(self, get_id: int, src: int, offset: int, nbytes: int,
                     meta: dict | None) -> memoryview | None:
        """Writable destination slice for a DATA frame's raw bytes — called
        by the socket receive thread so payloads land socket → final buffer
        with no staging hop.  None = duplicate/stale fragment (the caller
        drains the bytes to scratch).

        The offset is NOT marked landed here — only :meth:`landing_commit`
        (after the bytes fully arrived) does that.  A receive that dies
        mid-body therefore leaves no mark, and a concurrent replay on a
        fresh connection may be handed the same slice: both writers carry
        identical bytes, the writes are idempotent, and exactly one commit
        wins."""
        with self._frag_lock:
            zone = self._landing.get(get_id)
            if zone is None:
                if meta is None:
                    return None          # fragment of a completed/stale GET
                zone = self._zone_alloc(get_id, src, meta)
                self._landing[get_id] = zone
                self._frag_active += 1
            if offset in zone.landed:
                return None              # transport replay: already landed
        return memoryview(zone.flat[offset:offset + nbytes]).cast("B")

    def landing_commit(self, get_id: int, offset: int) -> bool:
        """Mark a fully received fragment landed; False = another delivery
        (a replay racing on a second connection) already committed it, or
        the zone is gone — the caller must not double-account it."""
        with self._frag_lock:
            zone = self._landing.get(get_id)
            if zone is None or offset in zone.landed:
                return False
            zone.landed.add(offset)
            return True

    def _zone_write(self, zone: _LandingZone, offset: int, data: Any) -> None:
        n = getattr(data, "nbytes", len(data))
        zone.flat[offset:offset + n] = \
            data if isinstance(data, np.ndarray) \
            else np.frombuffer(data, np.uint8)

    def _zone_finish(self, zone: _LandingZone) -> Any:
        return zone.dest

    def _on_frag(self, eng: CommEngine, src: int, payload: tuple) -> None:
        get_id, offset, nbytes, meta, data = payload
        with self._frag_lock:
            zone = self._landing.get(get_id)
            if zone is None:
                if data is None or meta is None:
                    # socket tier: zone was created by the recv thread and
                    # already retired, or an in-process stale duplicate
                    self.dup_frags += 1
                    return
                zone = self._zone_alloc(get_id, src, meta)
                self._landing[get_id] = zone
                self._frag_active += 1
            if data is not None:
                if offset in zone.landed:
                    self.dup_frags += 1
                    return
                zone.landed.add(offset)
        if data is not None:
            # in-process tiers: the fragment view is copied into the final
            # destination here, interleaved with task execution; on the
            # socket tier the recv thread already landed the bytes
            self._zone_write(zone, offset, data)
        zone.remaining -= nbytes
        self.frags_in += 1
        self.frag_bytes_in += nbytes
        pins.fire(PinsEvent.COMM_GET_FRAG_RECV, None, nbytes)
        self.send_am(AM_TAG_GET_FRAG_ACK, src, (get_id,))
        if zone.remaining > 0:
            return
        with self._frag_lock:
            self._landing.pop(get_id, None)
            self._frag_active -= 1
        value = self._land_value(self._zone_finish(zone))
        pins.fire(PinsEvent.COMM_GET_DONE, None, int(zone.meta["nbytes"]))
        self._record_get_span(get_id, int(zone.meta["nbytes"]))
        cb = self._pending_gets.pop(get_id, None)
        if cb is None:
            self.dup_get_replies += 1
            return
        cb(value)

    def frag_state(self) -> dict:
        """In-flight fragmentation state (flight-recorder stall dumps)."""
        with self._frag_lock:
            return {"landing_zones": len(self._landing),
                    "frag_sends": len(self._frag_sends),
                    "frags_in": self.frags_in,
                    "frag_bytes_in": self.frag_bytes_in,
                    "frags_out": self.frags_out,
                    "frag_bytes_out": self.frag_bytes_out,
                    "dup_frags": self.dup_frags,
                    "prefetch_gets": self.prefetch_gets}

    def on_peer_failed(self, rank: int) -> int:
        # a dead consumer's open send windows are abandoned (its credit
        # acks will never arrive), and a dead OWNER's landing zones are
        # dropped — leaking either would pin _frag_active nonzero and the
        # busy-worker progress gate would fire forever.  (The pending-get
        # callback stays unresolved, exactly like a monolithic GET_REPLY
        # that will never arrive: context failure handling owns that.)
        with self._frag_lock:
            for key in [k for k in self._frag_sends if k[0] == rank]:
                del self._frag_sends[key]
                self._frag_active -= 1
            for gid in [g for g, z in self._landing.items()
                        if z.src == rank]:
                del self._landing[gid]
                self._frag_active -= 1
        return super().on_peer_failed(rank)

    # -- progress -------------------------------------------------------------
    def pending(self) -> int:
        return self.fabric.pending(self.rank)

    def progress(self) -> int:
        # funnelled discipline: idle workers, quiesce, and rank threads may
        # all race here — only one thread drives the engine at a time, the
        # rest skip (non-blocking) so AM callbacks never interleave
        if not self._progress_lock.acquire(blocking=False):
            return 0
        try:
            n = 0
            if self.flush_hook is not None:
                n += self.flush_hook()
            for tag, src, payload in self.fabric.drain(self.rank):
                cb = self._am_callbacks.get(tag)
                if cb is None:
                    raise RuntimeError(f"no callback for AM tag {tag}")
                cb(self, src, payload)
                n += 1
            return n
        finally:
            self._progress_lock.release()

    def _on_barrier(self, eng: CommEngine, src: int, msg: dict) -> None:
        self._barrier_seen.setdefault(msg["gen"], set()).add(src)

    def sync(self) -> None:
        """All-ranks barrier over AMs, progressing while waiting."""
        gen = self._barrier_gen = self._barrier_gen + 1
        seen = self._barrier_seen.setdefault(gen, set())
        for r in range(self.nranks):
            if r != self.rank:
                self.send_am(AM_TAG_BARRIER, r, {"gen": gen})
        deadline = time.monotonic() + 30.0
        backoff = Backoff()
        while len(seen) < self.nranks - 1:
            if self.progress():
                backoff.reset()
            else:
                backoff.wait()   # a spin here starves the peers' threads
            if time.monotonic() > deadline:
                raise TimeoutError(f"rank {self.rank} barrier timeout")
        del self._barrier_seen[gen]
