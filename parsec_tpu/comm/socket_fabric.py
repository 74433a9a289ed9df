"""Socket transport: the multi-PROCESS comm backend (the DCN tier).

SURVEY §5.8 maps the reference's transport tiers onto TPU pods as
ICI (device-to-device, :mod:`device_fabric`) for in-pod payloads and
DCN/host networking across pods.  This module is the DCN tier: each rank
is its own OS process, active messages and rendezvous payloads move over
TCP, and the entire protocol stack above the engine vtable — remote-dep
activation, propagation trees, coalescing, termdet waves, DTD pushes —
runs unchanged (``RemoteDepEngine`` never learns which fabric it rides).

Wire format (``comm_wire_binary``, the default): every frame is a fixed
40-byte struct header ``<BBHIQQQQ`` = (kind, flags, tag, src, seq, u0, u1,
u2) followed by a kind-specific body:

- ``CTRL`` — an active message.  u0 = meta length, u1 = total raw-segment
  bytes, u2 = the 8-byte **trace context** of the request the message
  belongs to (0 = untraced; ``prof/spans.py`` — the receive thread
  span-records traced frames, so a request's wire hops appear in its
  trace).  Body = codec meta blob + raw buffer segments (ndarray bodies),
  sent with ``socket.sendmsg`` scatter-gather straight from the payload's
  own buffers and received with ``recv_into`` straight into freshly
  allocated final buffers (:mod:`parsec_tpu.comm.codec`) — no pickling of
  data, no staging copies on either side.
- ``ACK`` — cumulative receive ack, header only (seq = acked-upto).
- ``DATA`` — one rendezvous GET fragment.  u0 = get id, u1 = byte offset,
  u2 = fragment length; flag bit 0 marks the first fragment (body is then
  prefixed by the codec-encoded shape/dtype meta).  The receive thread
  asks the engine for the fragment's **final destination slice**
  (:meth:`~parsec_tpu.comm.engine.InprocCommEngine.landing_view`) and
  ``recv_into``\\ s it directly — socket → destination tile, zero copies.

``comm_wire_binary=False`` falls back to the legacy length-prefixed-pickle
framing, which pickles every payload byte; both ends of
a fabric must agree.  Topology: rank *i*
listens on ``base_port + i``; outgoing connections are made lazily with
connect-retry (peers boot in any order).  The host list defaults to
localhost (the oversubscribed test form — real multi-host runs set
``PARSEC_TPU_HOSTS=h0,h1,...``).

Fault model: TCP gives in-order reliable delivery *per connection*, but a
broken connection loses whatever was buffered in flight.  Each peer channel
therefore carries a monotonically increasing ``seq``; the sender keeps every
unacked frame in a bounded replay window and, when a send fails, reconnects
and replays the window; the receiver acks cumulatively every few frames and
drops duplicates by sequence — so a connection reset anywhere between two
ranks is invisible above the fabric (exactly-once, in-order per channel).

Use :func:`parsec_tpu.comm.multiproc.run_multiproc` to launch N subprocess
ranks and collect their results — the ``mpiexec -np N`` analog.
"""

from __future__ import annotations

import os
import pickle
import socket
import struct
import threading
import time
from collections import deque
from typing import Any

from ..core.params import params as _params
from ..data.arena import wire_pool
from ..prof import spans as _spans
from . import codec
from .engine import AM_TAG_GET_FRAG, InprocCommEngine

_params.register("comm_wire_binary", True,
                 "binary wire framing on the socket fabric: struct headers "
                 "+ scatter-gather raw segments (sendmsg/recv_into); off "
                 "reverts to length-prefixed pickle frames (both ends of a "
                 "fabric must agree)")
_params.register("comm_socket_base_port", 39100,
                 "first TCP port of the socket fabric (rank i listens on "
                 "base+i)")
_params.register("comm_socket_ack_every", 16,
                 "receiver sends a cumulative ack after this many frames "
                 "(bounds the sender's replay window)")
_params.register("comm_socket_replay_window", 4096,
                 "max unacked frames retained per peer for reconnect "
                 "replay; exceeding it is a visible error (a peer that "
                 "stopped acking)")
_params.register("comm_socket_fault_p", 0.0,
                 "fault injection: probability per outgoing frame of "
                 "breaking the connection first (tests the "
                 "reconnect-and-replay path; 0 disables)")
_params.register("comm_socket_fault_seed", 0,
                 "seed for the fault-injection RNG (per-rank offset added)")
# concurrency contracts, enforced by analysis.runtimelint (docs/ANALYSIS.md):
# receive-side channel state mutates only under _ilock (shared by every
# per-connection receive thread), sender-side peer table and cross-peer
# traffic ledgers only under _plock; per-peer connection entries (ent[0..3])
# are guarded by the entry's own send lock (ent[1]) — anonymous, so outside
# the lint's reach (kept hierarchical by construction).  No site nests the
# two named locks; the declared order documents the intended direction.
_LOCK_PROTECTED = {
    "SocketFabric._inbox": "_ilock",
    "SocketFabric._seen": "_ilock",
    "SocketFabric._unacked_in": "_ilock",
    "SocketFabric.peer_rx": "_ilock",
    "SocketFabric.bytes_recv": "_ilock",
    "SocketFabric.dup_frames": "_ilock",
    "SocketFabric._peers": "_plock",
    "SocketFabric._accepted": "_plock",
    "SocketFabric.bytes_sent": "_plock",
    "SocketFabric.peer_tx": "_plock",
}
_LOCK_ORDER = ("_plock", "_ilock")

_params.register("comm_socket_buf_bytes", 1 << 22,
                 "SO_SNDBUF/SO_RCVBUF hint per connection (0 = OS default); "
                 "large GET fragments stream without stalling on the "
                 "default ~64KiB kernel buffers")


def _tune_socket(s: socket.socket) -> None:
    s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    buf = int(_params.get("comm_socket_buf_bytes"))
    if buf > 0:
        try:
            s.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, buf)
            s.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, buf)
        except OSError:
            pass        # a capped kernel clamps silently anyway

_LEN = struct.Struct("<Q")

# binary frame header: kind, flags, tag, src, seq, u0, u1, u2 (see module
# docstring for the per-kind field meanings)
_HDR = struct.Struct("<BBHIQQQQ")
K_CTRL = 1
K_ACK = 2
K_DATA = 3
F_FIRST = 1       # DATA: first fragment (body carries the shape/dtype meta)
F_LAST = 2        # DATA: last fragment of its GET
_U32 = struct.Struct("<I")


def _hosts(nranks: int) -> list[str]:
    spec = os.environ.get("PARSEC_TPU_HOSTS", "")
    hosts = [h.strip() for h in spec.split(",") if h.strip()]
    if not hosts:
        hosts = ["127.0.0.1"]
    return [hosts[r % len(hosts)] for r in range(nranks)]


def _frame(obj: Any) -> bytes:
    data = pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)
    return _LEN.pack(len(data)) + data


def _recv_exact_into(sock: socket.socket, mv: memoryview) -> bool:
    """Fill ``mv`` from the socket; False on EOF.  ``recv_into`` lands the
    bytes in place — the receive path's one and only copy is kernel→buffer."""
    while mv.nbytes:
        n = sock.recv_into(mv)
        if n == 0:
            return False
        mv = mv[n:]
    return True


def _recv_exact(sock: socket.socket, n: int) -> bytearray | None:
    """Exact-length receive into ONE preallocated buffer (no per-chunk
    ``bytearray +=`` growth copies)."""
    buf = bytearray(n)
    if not _recv_exact_into(sock, memoryview(buf)):
        return None
    return buf


def _drain(sock: socket.socket, n: int) -> bool:
    """Consume and discard ``n`` body bytes (duplicate/stale frames whose
    payload has nowhere to land) through a pooled scratch buffer."""
    mv = wire_pool.acquire(min(n, 1 << 16))
    try:
        while n:
            take = mv[:min(n, mv.nbytes)]
            if not _recv_exact_into(sock, take):
                return False
            n -= take.nbytes
        return True
    finally:
        wire_pool.release(mv)


# Linux caps one sendmsg at UIO_MAXIOV iovecs; stay safely under it (a
# coalesced flush of >1000 inline-payload activations can exceed it)
_IOV_MAX = 512


def _sendmsg_all(sock: socket.socket, bufs: list) -> None:
    """``sendmsg`` the scatter-gather list fully, resuming after short
    writes and chunking to the iovec limit (the vectored-send analog of
    ``sendall``)."""
    views = []
    for b in bufs:
        v = memoryview(b).cast("B")
        if v.nbytes:
            views.append(v)
    while views:
        chunk = views[:_IOV_MAX]
        chunk_total = sum(v.nbytes for v in chunk)
        n = sock.sendmsg(chunk)
        if n >= chunk_total:
            del views[:len(chunk)]
            continue
        while n:
            if n >= views[0].nbytes:
                n -= views[0].nbytes
                views.pop(0)
            else:
                views[0] = views[0][n:]
                n = 0


class SocketFabric:
    """One process's endpoint of the TCP mesh (quacks like InprocFabric
    for the local rank: deliver / drain / pending)."""

    def __init__(self, nranks: int, rank: int,
                 base_port: int | None = None) -> None:
        self.nranks = nranks
        self.rank = rank
        self.base_port = base_port if base_port is not None else \
            _params.get("comm_socket_base_port")
        self.hosts = _hosts(nranks)
        self._inbox: deque = deque()
        self._ilock = threading.Lock()
        # dst -> [sock|None, send-lock, next_seq, unacked deque[(seq, bytes)]]
        self._peers: dict[int, list] = {}
        self._plock = threading.Lock()
        # receiver-side channel state (guarded by _ilock): highest seq seen
        # per src (duplicate suppression) and frames since the last ack
        self._seen: dict[int, int] = {}
        self._unacked_in: dict[int, int] = {}
        self.replays = 0          # reconnect-and-replay events (observable)
        self.dup_frames = 0       # duplicate frames suppressed
        self.bytes_sent = 0       # total framed bytes (traffic accounting)
        self.bytes_recv = 0       # total framed bytes received (gauge twin)
        self.binary = bool(_params.get("comm_wire_binary"))
        # per-peer traffic ledgers: dst -> [bytes, frames, frags] (tx under
        # _plock, rx under _ilock) — the per-peer gauges of docs/COMM.md
        self.peer_tx: dict[int, list] = {}
        self.peer_rx: dict[int, list] = {}
        # engine hook: the socket receive thread lands DATA-frame bytes
        # through this (InprocCommEngine.landing_view); None until an
        # engine attaches — frames arriving earlier drain to scratch
        self.landing_view = None
        # fault injection (tests): break the connection before some sends
        fault_p = float(_params.get("comm_socket_fault_p"))
        self._fault_p = fault_p
        if fault_p > 0.0:
            import random
            self._fault_rng = random.Random(
                _params.get("comm_socket_fault_seed") + rank)
        else:
            self._fault_rng = None
        # engine hook: invoked with a rank when it stays unreachable past
        # the reconnect budget (SocketCommEngine points this at its
        # registered-buffer GC, CommEngine.on_peer_failed)
        self.on_peer_dead = None
        self._accepted: list[socket.socket] = []   # inbound conns, for close
        self._listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._listener.bind(("0.0.0.0", self.base_port + rank))
        self._listener.listen(nranks)
        self._stop = threading.Event()
        self._accept_thread = threading.Thread(
            target=self._accept_main, daemon=True,
            name=f"parsec-sock-accept-r{rank}")
        self._accept_thread.start()

    # ------------------------------------------------------------ receive
    def _accept_main(self) -> None:
        while not self._stop.is_set():
            try:
                self._listener.settimeout(0.2)
                conn, _addr = self._listener.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            with self._plock:
                self._accepted.append(conn)
            if self._stop.is_set():
                # raced with close(): it may have cleared _accepted before
                # our append — clean up here instead of leaking the conn
                # (separate try blocks: shutdown of a dead peer raises
                # ENOTCONN and must not skip the close)
                try:
                    conn.shutdown(socket.SHUT_RDWR)
                except OSError:
                    pass
                try:
                    conn.close()
                except OSError:
                    pass
                return
            threading.Thread(target=self._recv_main, args=(conn,),
                             daemon=True).start()

    def _recv_main(self, conn: socket.socket) -> None:
        _tune_socket(conn)
        if self.binary:
            self._recv_main_binary(conn)
            return
        ack_every = _params.get("comm_socket_ack_every")
        while not self._stop.is_set():
            try:
                head = _recv_exact(conn, _LEN.size)
                if head is None:
                    return
                body = _recv_exact(conn, _LEN.unpack(head)[0])
                if body is None:
                    return
                # network bytes never hit the bare pickle VM: the legacy
                # framing decodes through the control-frame allowlist too
                frame = codec.restricted_loads(bytes(body))
            except OSError:
                return
            except Exception as e:
                # a corrupt/undecodable frame kills only THIS connection —
                # visibly.  The peer's replay window re-sends everything it
                # had in flight on its next send; the seq dedup below keeps
                # delivery exactly-once across the reset.
                from ..core.output import warning
                warning(f"socket fabric rank {self.rank}: dropping "
                        f"connection on undecodable frame: {e!r}")
                try:
                    conn.close()
                except OSError:
                    pass
                return
            if frame[0] == "a":                  # cumulative ack
                _, src, upto = frame
                self._prune_unacked(src, upto)
                continue
            _, seq, body = frame
            tag, src, payload = codec.restricted_loads(bytes(body))
            ack_now = None
            with self._ilock:
                if seq <= self._seen.get(src, 0):
                    self.dup_frames += 1         # replay overlap: suppress
                else:
                    self._seen[src] = seq
                    self._inbox.append((tag, src, payload))
                n = self._unacked_in.get(src, 0) + 1
                if n >= ack_every:
                    self._unacked_in[src] = 0
                    ack_now = self._seen[src]
                else:
                    self._unacked_in[src] = n
            if ack_now is not None:
                self._send_ack(src, ack_now)

    # ------------------------------------------------- binary receive loop
    def _recv_main_binary(self, conn: socket.socket) -> None:
        ack_every = _params.get("comm_socket_ack_every")
        hdr = bytearray(_HDR.size)
        while not self._stop.is_set():
            try:
                if not _recv_exact_into(conn, memoryview(hdr)):
                    return
                kind, flags, tag, src, seq, u0, u1, u2 = _HDR.unpack(hdr)
                if kind == K_ACK:
                    self._prune_unacked(src, seq)
                    continue
                if kind == K_CTRL:
                    self._recv_ctrl(conn, tag, src, seq, u0, u1, ack_every,
                                    trace_id=u2)
                elif kind == K_DATA:
                    self._recv_data(conn, flags, src, seq, u0, u1, u2,
                                    ack_every)
                else:
                    raise ValueError(f"unknown wire frame kind {kind}")
            except OSError:
                return
            except Exception as e:
                # a corrupt/undecodable frame kills only THIS connection —
                # visibly; the peer's replay window recovers the traffic
                from ..core.output import warning
                warning(f"socket fabric rank {self.rank}: dropping "
                        f"connection on undecodable frame: {e!r}")
                try:
                    conn.close()
                except OSError:
                    pass
                return

    def _rx_account(self, src: int, nbytes: int, frag: bool) -> None:
        """Caller holds ``_ilock``."""
        self.bytes_recv += nbytes
        rx = self.peer_rx.get(src)
        if rx is None:
            rx = self.peer_rx[src] = [0, 0, 0]
        rx[0] += nbytes
        rx[1] += 1
        if frag:
            rx[2] += 1

    def _recv_ctrl(self, conn: socket.socket, tag: int, src: int, seq: int,
                   meta_len: int, seg_bytes: int, ack_every: int,
                   trace_id: int = 0) -> None:
        t0 = time.perf_counter_ns() if trace_id \
            and _spans.recorder is not None else 0
        meta = wire_pool.acquire(meta_len)
        try:
            if not _recv_exact_into(conn, meta):
                raise OSError("peer closed mid-frame (meta)")

            def fill(view: memoryview) -> None:
                # the zero-copy landing: segment bytes recv_into the
                # decoded payload's final buffers
                if not _recv_exact_into(conn, view):
                    raise OSError("peer closed mid-frame (segment)")

            payload = codec.decode(meta, fill)
        finally:
            wire_pool.release(meta)
        if t0:
            # a traced CTRL frame landing: the wire-level receive span
            # (header trace word u2), attributed to the request's trace
            r = _spans.recorder
            if r is not None:
                r.record("wire.ctrl", trace_id, t0,
                         time.perf_counter_ns(),
                         args={"src": src,
                               "bytes": _HDR.size + meta_len + seg_bytes})
        ack_now = None
        with self._ilock:
            self._rx_account(src, _HDR.size + meta_len + seg_bytes, False)
            if seq <= self._seen.get(src, 0):
                self.dup_frames += 1         # replay overlap: suppress
            else:
                self._seen[src] = seq
                self._inbox.append((tag, src, payload))
            ack_now = self._ack_bookkeeping(src, ack_every)
        if ack_now is not None:
            self._send_ack(src, ack_now)

    def _recv_data(self, conn: socket.socket, flags: int, src: int,
                   seq: int, get_id: int, offset: int, nbytes: int,
                   ack_every: int) -> None:
        meta = None
        extra = 0
        if flags & F_FIRST:
            mlen_buf = bytearray(4)
            if not _recv_exact_into(conn, memoryview(mlen_buf)):
                raise OSError("peer closed mid-frame (frag meta len)")
            mlen = _U32.unpack(mlen_buf)[0]
            mbuf = wire_pool.acquire(mlen)
            try:
                if not _recv_exact_into(conn, mbuf):
                    raise OSError("peer closed mid-frame (frag meta)")
                meta = codec.decode_with_segments(bytes(mbuf), [])
            finally:
                wire_pool.release(mbuf)
            extra = 4 + mlen
        with self._ilock:
            dup = seq <= self._seen.get(src, 0)
        committed = False
        dups = 0    # counted locally, published under _ilock below (the
        # increment is a read-modify-write racing other receive threads)
        if dup:
            dups += 1
            if not _drain(conn, nbytes):
                raise OSError("peer closed mid-frame (dup frag)")
        else:
            lv = self.landing_view
            mv = lv(get_id, src, offset, nbytes, meta) if lv else None
            if mv is None:
                # stale fragment (its GET already completed, or no engine
                # attached yet): consume and discard
                if not _drain(conn, nbytes):
                    raise OSError("peer closed mid-frame (stale frag)")
            else:
                # a receive that dies here leaves NO landed mark, so the
                # peer's replay (same offset, fresh connection) re-lands
                # it; if that replay raced us and committed first, our
                # identical bytes were idempotent and we stand down
                if not _recv_exact_into(conn, mv):
                    raise OSError("peer closed mid-frame (frag body)")
                eng = getattr(lv, "__self__", None)   # bound engine method
                committed = eng is not None and \
                    eng.landing_commit(get_id, offset)
                if not committed:
                    dups += 1
        ack_now = None
        with self._ilock:
            self.dup_frames += dups
            self._rx_account(src, _HDR.size + extra + nbytes, True)
            if not dup:
                self._seen[src] = max(self._seen.get(src, 0), seq)
                if committed:
                    self._inbox.append((AM_TAG_GET_FRAG, src,
                                        (get_id, offset, nbytes, None,
                                         None)))
            ack_now = self._ack_bookkeeping(src, ack_every)
        if ack_now is not None:
            self._send_ack(src, ack_now)

    def _ack_bookkeeping(self, src: int, ack_every: int) -> int | None:
        """Caller holds ``_ilock``; returns the seq to ack now, if due."""
        n = self._unacked_in.get(src, 0) + 1
        if n >= ack_every:
            self._unacked_in[src] = 0
            return self._seen.get(src, 0)
        self._unacked_in[src] = n
        return None

    def _prune_unacked(self, src: int, upto: int) -> None:
        with self._plock:
            ent = self._peers.get(src)
        if ent is None:
            return
        with ent[1]:
            q = ent[3]
            while q and q[0][0] <= upto:
                q.popleft()

    def _send_ack(self, src: int, upto: int) -> None:
        """Best-effort cumulative ack (idempotent: never replayed; a lost
        ack just leaves the peer's window larger until the next one).
        Runs on a receive thread, so a missing reverse connection gets only
        a SHORT connect budget — stalling reception behind a 30s boot retry
        would freeze frames already queued on this connection.  A failed
        send DROPS the socket (the next ack reconnects) and never declares
        the peer dead — a receive-only rank's ack channel would otherwise
        stay wedged after one reset and starve the sender's window."""
        with self._plock:
            ent = self._peers.get(src)
            if ent is None:
                ent = self._peers[src] = [None, threading.Lock(), 0, deque()]
        ack = (_HDR.pack(K_ACK, 0, 0, self.rank, upto, 0, 0, 0)
               if self.binary else _frame(("a", self.rank, upto)))
        with ent[1]:
            try:
                if ent[0] is None:
                    ent[0] = self._connect(src, retry_s=2.0,
                                           report_dead=False)
                ent[0].sendall(ack)
            except OSError:
                if ent[0] is not None:
                    try:
                        ent[0].close()
                    except OSError:
                        pass
                    ent[0] = None

    # --------------------------------------------------------------- send
    def _connect(self, dst: int, retry_s: float = 30.0,
                 report_dead: bool = True) -> socket.socket:
        """Connect to ``dst``, retrying refusals for up to ``retry_s`` (30s
        default covers peers still booting; reconnect paths pass a short
        budget — a peer dead mid-run should fail fast, not hang callers for
        the boot window).  Bails immediately on fabric teardown.
        ``report_dead=False`` suppresses the peer-death notification —
        best-effort paths (acks) must not declare a live peer dead off a
        short transient budget."""
        deadline = time.monotonic() + retry_s
        while True:
            if self._stop.is_set():
                raise OSError("fabric is shutting down")
            try:
                s = socket.create_connection(
                    (self.hosts[dst], self.base_port + dst), timeout=2.0)
                break
            except OSError:
                if time.monotonic() > deadline:
                    if report_dead:
                        self._peer_dead(dst)
                    raise
                time.sleep(0.05)   # peer still booting
        _tune_socket(s)
        return s

    def _peer_dead(self, dst: int) -> None:
        """A peer is unreachable past its retry budget: tell the engine so
        it can release resources pinned for that rank (registered-buffer
        shares via ``CommEngine.on_peer_failed``)."""
        cb = self.on_peer_dead
        if cb is not None:
            try:
                cb(dst)
            except Exception:       # a GC hook must never mask the OSError
                pass

    def deliver(self, dst: int, tag: int, src: int, payload: Any,
                trace_id: int = 0) -> None:
        if dst == self.rank:
            with self._ilock:
                self._inbox.append((tag, src, payload))
            return
        # the expensive serialization (payload object graph) runs OUTSIDE
        # the send lock; only the tiny seq-stamped header is built inside
        if self.binary:
            meta, segs = codec.encode(payload)
            seg_bytes = sum(memoryview(s).nbytes for s in segs)
            tid = trace_id & 0xFFFFFFFFFFFFFFFF

            def frame(seq: int) -> list:
                return [_HDR.pack(K_CTRL, 0, tag, src, seq,
                                  len(meta), seg_bytes, tid), meta, *segs]
            self._send_frame(dst, frame,
                             _HDR.size + len(meta) + seg_bytes, frag=False,
                             snapshot=True)
            return
        body = pickle.dumps((tag, src, payload),
                            protocol=pickle.HIGHEST_PROTOCOL)
        self._send_frame(dst, lambda seq: [_frame(("d", seq, body))], None,
                         frag=False)

    def deliver_data(self, dst: int, get_id: int, offset: int, nbytes: int,
                     data: Any, meta: dict | None, last: bool) -> None:
        """Ship one rendezvous GET fragment as a binary DATA frame whose
        raw bytes go scatter-gather straight from the registered buffer."""
        flags = (F_FIRST if meta is not None else 0) | (F_LAST if last else 0)
        head: list = []
        if meta is not None:
            mblob, msegs = codec.encode(meta)
            assert not msegs, "fragment meta must be segment-free"
            head = [_U32.pack(len(mblob)), mblob]
        extra = sum(len(b) for b in head)

        def frame(seq: int) -> list:
            return [_HDR.pack(K_DATA, flags, 0, self.rank, seq,
                              get_id, offset, nbytes), *head, data]
        self._send_frame(dst, frame, _HDR.size + extra + nbytes, frag=True)

    def _send_frame(self, dst: int, frame, nbytes: int | None,
                    frag: bool, snapshot: bool = False) -> None:
        """Seq-stamp, window, account, and transmit one frame (binary
        scatter-gather list or legacy pre-framed bytes).

        ``snapshot=True`` stores byte COPIES of the frame's buffers in the
        replay window while still transmitting the zero-copy views: a CTRL
        payload's arrays may be mutated by the caller after ``send_am``
        returns (the legacy pickle framing snapshotted implicitly), and a
        reconnect replay must resend the bytes as they were at send time.
        DATA frames skip it — their source is a registered buffer the
        engine contract keeps immutable until the GET completes."""
        with self._plock:
            ent = self._peers.get(dst)
            if ent is None:
                ent = self._peers[dst] = [None, threading.Lock(), 0, deque()]
        with ent[1]:     # frames must not interleave on one connection
            if len(ent[3]) >= _params.get("comm_socket_replay_window"):
                raise RuntimeError(
                    f"rank {self.rank}: replay window to rank {dst} full "
                    f"({len(ent[3])} unacked frames) — peer stopped acking")
            ent[2] += 1
            seq = ent[2]
            bufs = frame(seq)
            if nbytes is None:
                nbytes = sum(len(b) for b in bufs)
            # bytes_sent/peer_tx are shared across peers; concurrent
            # senders hold different per-peer locks, so the
            # read-modify-write needs the peer-table lock
            with self._plock:
                self.bytes_sent += nbytes
                tx = self.peer_tx.get(dst)
                if tx is None:
                    tx = self.peer_tx[dst] = [0, 0, 0]
                tx[0] += nbytes
                tx[1] += 1
                if frag:
                    tx[2] += 1
            if snapshot:
                ent[3].append((seq, [bytes(memoryview(b).cast("B"))
                                     for b in bufs]))
            else:
                ent[3].append((seq, bufs))
            if ent[0] is None:
                ent[0] = self._connect(dst)
            if (self._fault_rng is not None
                    and self._fault_rng.random() < self._fault_p):
                # injected fault: hard-break the live connection so this
                # send fails and exercises reconnect-and-replay
                try:
                    ent[0].shutdown(socket.SHUT_RDWR)
                except OSError:
                    pass
            try:
                _sendmsg_all(ent[0], bufs)
            except OSError:
                self._reconnect_and_replay(dst, ent)

    def _reconnect_and_replay(self, dst: int, ent: list) -> None:
        """A broken connection loses whatever TCP had buffered: reconnect
        and resend the whole unacked window in order (caller holds the
        send lock).  The receiver's seq dedup drops the overlap."""
        try:
            if ent[0] is not None:
                ent[0].close()
        except OSError:
            pass
        ent[0] = None
        self.replays += 1
        ent[0] = self._connect(dst, retry_s=5.0)
        for _seq, bufs in list(ent[3]):
            _sendmsg_all(ent[0], bufs)   # a second failure here is fatal

    def peer_stats(self) -> dict:
        """Per-peer traffic ledgers: ``{"tx"|"rx": {rank: {bytes, frames,
        frags}}}`` (the per-peer gauges surfaced in the ``comm`` block)."""
        with self._plock:
            tx = {d: {"bytes": v[0], "frames": v[1], "frags": v[2]}
                  for d, v in self.peer_tx.items()}
        with self._ilock:
            rx = {s: {"bytes": v[0], "frames": v[1], "frags": v[2]}
                  for s, v in self.peer_rx.items()}
        return {"tx": tx, "rx": rx}

    # ----------------------------------------------------- drain (local)
    def drain(self, rank: int, limit: int = 64) -> list[tuple]:
        assert rank == self.rank
        out = []
        with self._ilock:
            while self._inbox and len(out) < limit:
                out.append(self._inbox.popleft())
        return out

    def pending(self, rank: int) -> int:
        assert rank == self.rank
        with self._ilock:
            return len(self._inbox)

    def close(self) -> None:
        self._stop.set()
        try:
            self._listener.close()
        except OSError:
            pass
        with self._plock:
            for ent in self._peers.values():
                if ent[0] is not None:
                    try:
                        ent[0].shutdown(socket.SHUT_RDWR)
                    except OSError:
                        pass
                    try:
                        ent[0].close()
                    except OSError:
                        pass
            self._peers.clear()
            # shutdown() (not just close()) unblocks recv threads parked in
            # recv(2) — close alone only drops the fd reference while the
            # syscall keeps blocking — so _recv_main exits and no
            # thread/fd accumulates across fabric create/teardown cycles
            for conn in self._accepted:
                try:
                    conn.shutdown(socket.SHUT_RDWR)
                except OSError:
                    pass
                try:
                    conn.close()
                except OSError:
                    pass
            self._accepted.clear()


class SocketCommEngine(InprocCommEngine):
    """The engine vtable over :class:`SocketFabric`.

    :class:`SocketFabric` exposes the same deliver/drain/pending surface
    the in-process fabric does, so the whole AM + rendezvous-GET + barrier
    protocol is inherited verbatim — the engine cannot tell whether its
    bytes cross a deque or a TCP connection, which is exactly the vtable
    discipline the reference's comm engines follow
    (``parsec_comm_engine.h:176-199``)."""

    def __init__(self, fabric: SocketFabric) -> None:
        super().__init__(fabric, fabric.rank)
        # a rank unreachable past the reconnect budget releases its
        # registered-buffer shares (the peer-death GC)
        fabric.on_peer_dead = self.on_peer_failed
        # DATA-frame bytes land through the engine's zone registry from
        # the fabric's receive threads (recv_into the final destination)
        fabric.landing_view = self.landing_view

    def _plan_frags(self, value: Any) -> tuple | None:
        # fragmented rendezvous needs the binary DATA frames; the legacy
        # pickle framing keeps the monolithic replies it always had
        if not self.fabric.binary:
            return None
        return super()._plan_frags(value)

    def _transport_frag(self, dst: int, get_id: int, offset: int,
                        nbytes: int, data: Any, meta: dict | None,
                        last: bool) -> None:
        if dst == self.rank:
            super()._transport_frag(dst, get_id, offset, nbytes, data,
                                    meta, last)
            return
        self.fabric.deliver_data(dst, get_id, offset, nbytes, data, meta,
                                 last)

    def fini(self) -> None:
        super().fini()          # force-drop leftover registrations first
        self.fabric.close()
