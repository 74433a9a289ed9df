"""Paged KV cache as a :class:`DataCollection` — the LLM serving datum.

The inference-serving analog of the tiled matrices: a transformer KV
cache laid out as fixed-size *pages* (vLLM's PagedAttention block table;
"Ragged Paged Attention", arxiv 2604.15464, is the TPU-kernel treatment
the decode task class mirrors).  Logical keys are ``(seq_id, page_idx)``;
a per-sequence **block table** maps them to physical pages allocated
from a free list, so sequences grow ragged without reallocation,
fork-with-copy-on-write shares prompt pages between sequences, and the
physical page — not the sequence — is the residency unit: each page is
an ordinary :class:`~parsec_tpu.data.data.Data`, so the TPU device
module's HBM LRU (``device/tpu.py``) caches, evicts, and writes back
pages exactly like matrix tiles, and two forked sequences reading one
shared physical page hit the SAME cache entry.

Page layout: one ``(3, page_size, heads, head_dim)`` array per page —
channel 0 the keys, channel 1 the values, channel 2 metadata with
``page[2, 0, 0, 0]`` the page's **fill count** (valid slots).  Carrying
the fill inside the tensor keeps the per-page attention kernel pure
(same shapes across sequences → the PR-2 fused same-class
dispatch can batch every live sequence's decode task into one XLA
call) rather than threading ragged lengths through the task signature.

``has_key`` answers from the block tables, so the key space is CLOSED:
graphcheck's bounds oracle statically rejects a decode pool referencing
a page beyond a sequence's table (``docs/LLM.md``).
"""

from __future__ import annotations

import threading
from typing import Any, Callable

import numpy as np

from ..data.data import (COHERENCY_INVALID, COHERENCY_SHARED, Data,
                         data_create)
from ..data.datatype import TileType
from .collection import DataCollection

K_CH, V_CH, META_CH = 0, 1, 2


class PagedKVCollection(DataCollection):
    """Block-table-backed paged KV cache distribution.

    ``rank_of(seq, page)`` defaults to ``hash(seq) % nodes`` (a whole
    sequence's pages co-locate — decode is a per-sequence chain, so
    page-granular distribution would put every chain hop on the wire);
    ``rank_of_fn`` overrides.
    """

    def __init__(self, name: str = "KV", page_size: int = 16,
                 num_heads: int = 4, head_dim: int = 8,
                 dtype: Any = np.float32, max_pages: int = 4096,
                 nodes: int = 1, myrank: int = 0,
                 rank_of_fn: Callable | None = None) -> None:
        super().__init__(name, nodes, myrank)
        self.page_size = int(page_size)
        self.num_heads = int(num_heads)
        self.head_dim = int(head_dim)
        self.dtype = np.dtype(dtype)
        self.max_pages = int(max_pages)
        self.default_dtt = TileType(
            (3, self.page_size, self.num_heads, self.head_dim), self.dtype)
        self._rank_of_fn = rank_of_fn
        self._lock = threading.RLock()
        self._pages: dict[int, Data] = {}        # phys id -> page Data
        self._refs: dict[int, int] = {}          # phys id -> sharers
        self._free: list[int] = []               # recycled phys ids
        self._next_phys = 0
        self._tables: dict[Any, list[int]] = {}  # phys ids per seq
        self._lens: dict[Any, int] = {}          # seq -> appended tokens
        # tallies (bench/docs surface them)
        self.pages_allocated = 0
        self.pages_recycled = 0
        self.cow_copies = 0
        # the prefix-cache counters (llm/prefix_tree.py bumps them on
        # every trie adoption) and the tier attach point
        # (data_dist/kv_tiers.py sets .tier so stats() can answer
        # host_tier_bytes / prefetch_inflight without a second surface)
        self.prefix_hits = 0
        self.prefix_pages_reused = 0
        # speculative-decode rollback tallies (rollback_tail, ISSUE 12)
        self.tail_rollbacks = 0
        self.slots_rolled_back = 0
        self.tier: Any = None

    # -- the DataCollection vtable --------------------------------------
    def rank_of(self, *key) -> int:
        seq, _page = key
        if self._rank_of_fn is not None:
            return self._rank_of_fn(seq, _page)
        if isinstance(seq, (int, np.integer)):
            return int(seq) % max(self.nodes, 1)
        # deterministic across processes — Python's str hash is salted
        # per interpreter, and ranks must AGREE on an owner
        import zlib
        return zlib.crc32(repr(seq).encode()) % max(self.nodes, 1)

    def data_of(self, *key) -> Data:
        seq, page = key
        with self._lock:
            return self._pages[self._tables[seq][page]]

    def has_key(self, *key) -> bool:
        """Bounds oracle (graphcheck): a ``(seq, page)`` key exists iff
        the sequence is live and the page is inside its block table."""
        if len(key) != 2:
            return False
        seq, page = key
        with self._lock:
            table = self._tables.get(seq)
            return table is not None and isinstance(page, (int, np.integer)) \
                and 0 <= page < len(table)

    # -- page lifecycle --------------------------------------------------
    @staticmethod
    def _scrub_copies(d: Data) -> int:
        """The recycle-detach discipline, stated ONCE (recycle, CoW
        privatize, speculative rollback and seed-time staging all apply
        it): invalidate + detach every accelerator copy of one page —
        a dirty device copy running AHEAD of host (deferred writeback,
        device/tpu.py) must never satisfy a later stage-in version
        check or write back over fresher host bytes — and return the
        highest version ANY copy ever reached, which the caller's new
        host version must jump PAST."""
        with d._lock:
            maxv = max(c.version for c in d.device_copies.values())
            stale = [i for i in d.device_copies if i != 0]
        for idx in stale:
            c = d.get_copy(idx)
            if c is not None:
                c.coherency = COHERENCY_INVALID
            d.detach_copy(idx)
        return maxv

    def _new_page_locked(self) -> int:  # lint: holds(_lock)
        if self._free:
            phys = self._free.pop()
            self.pages_recycled += 1
            # recycle the Data in place: fresh zeros, stale copies
            # scrubbed, host version jumped past every copy
            d = self._pages[phys]
            host = d.get_copy(0)
            maxv = self._scrub_copies(d)
            host.value = np.zeros(self.default_dtt.shape, self.dtype)
            host.version = maxv + 1
            # a device start_write may have left the host INVALID; the
            # zeroed host copy is now the one true version
            host.coherency = COHERENCY_SHARED
            d.owner_device = 0
        else:
            if self._next_phys >= self.max_pages:
                raise MemoryError(
                    f"{self.name}: out of KV pages "
                    f"({self.max_pages} x {self.page_bytes} B)")
            phys = self._next_phys
            self._next_phys += 1
            self._pages[phys] = data_create(
                np.zeros(self.default_dtt.shape, self.dtype),
                key=(self.name, phys), dtt=self.default_dtt, dc=self)
        self._refs[phys] = 1
        self.pages_allocated += 1
        return phys

    def alloc_seq(self, seq: Any) -> None:
        """Register a sequence with an empty block table."""
        with self._lock:
            if seq in self._tables:
                raise KeyError(f"sequence {seq!r} already allocated")
            self._tables[seq] = []
            self._lens[seq] = 0

    def alloc_page(self, seq: Any) -> int:
        """Append one fresh physical page to ``seq``'s table; returns the
        new logical page index."""
        with self._lock:
            table = self._tables[seq]
            table.append(self._new_page_locked())
            return len(table) - 1

    def ensure_tail_slot(self, seq: Any) -> tuple[int, int]:
        """Make the next token's write slot real and writable: allocate a
        tail page when the table is empty or the tail is full, and
        copy-on-write a tail shared with a forked sibling.  Returns
        ``(page_idx, slot)`` — the decode step's write position."""
        with self._lock:
            table = self._tables[seq]
            n = self._lens[seq]
            page, slot = divmod(n, self.page_size)
            if page >= len(table):
                table.append(self._new_page_locked())
            elif self._refs[table[page]] > 1:
                # shared partial tail (post-fork): writes must not leak
                # into the sibling — private copy, refcount handed back
                self._privatize_locked(table, page)
            return page, slot

    def _privatize_locked(self, table: list[int],
                          page: int) -> int:  # lint: holds(_lock)
        """Replace ``table[page]`` with a private copy of its bytes —
        the CoW divergence point.  The copy sources the NEWEST live copy
        of the shared page, not the host copy: with a device tier the
        sibling's on-device writes (or an evicted-but-not-yet-written-
        back victim in the w2r queue) run AHEAD of host, and copying the
        host bytes would silently fork a stale snapshot.  The private
        page's host version also jumps PAST every version the shared
        page ever reached — the recycle-detach discipline of
        ``_new_page_locked`` extended to the fork path, so no later
        version comparison can ever prefer state inherited from the
        shared ancestor."""
        old = table[page]
        old_d = self._pages[old]
        src = old_d.newest_copy()
        if src is None or src.value is None:
            # every copy is gone (e.g. the page sits in the peer tier
            # mid-roundtrip): privatizing would fork garbage — fail THIS
            # stream loudly instead (the batcher contains it per stream)
            raise RuntimeError(
                f"{self.name}: page {old} has no live copy to privatize "
                f"from (spilled beyond the host tier?)")
        self._refs[old] -= 1
        phys = self._new_page_locked()
        with old_d._lock:
            maxv = max((c.version for c in old_d.device_copies.values()),
                       default=0)
        dst = self._pages[phys].get_copy(0)
        dst.value = np.array(np.asarray(src.value), copy=True)
        dst.version = max(dst.version, maxv) + 1
        table[page] = phys
        self.cow_copies += 1
        return phys

    def note_appended(self, seq: Any, n: int = 1) -> None:
        """Advance host-side bookkeeping after ``n`` tokens' K/V landed in
        the pages (the task bodies update the in-tensor fill counts; the
        collection's length ledger is the host-side twin the batcher and
        ``ensure_tail_slot`` plan from)."""
        with self._lock:
            self._lens[seq] += n

    def fork(self, parent: Any, child: Any) -> None:
        """Copy-on-write fork: the child shares every parent page
        (refcount++), so N continuations of one prompt hold ONE physical
        copy of the prompt's KV — the paged-attention prefix-sharing win.
        A shared tail is privatized lazily by :meth:`ensure_tail_slot`."""
        with self._lock:
            if child in self._tables:
                raise KeyError(f"sequence {child!r} already allocated")
            table = list(self._tables[parent])
            for phys in table:
                self._refs[phys] += 1
            self._tables[child] = table
            self._lens[child] = self._lens[parent]

    def fork_prefix(self, parent: Any, child: Any, pages: int) -> None:
        """Prefix fork: the child shares only the parent's first
        ``pages`` pages (refcount++) and its length ledger starts at the
        page boundary ``pages * page_size`` — the trie-adoption seam
        (``llm/prefix_tree.py``): an incoming prompt that matches a
        retained prefix forks exactly the matched FULL pages and
        prefills only its unmatched tail.  Only whole pages are ever
        shared, so a prefix fork never creates a shared partial tail —
        divergence happens in fresh private pages, not through
        :meth:`ensure_tail_slot` CoW."""
        with self._lock:
            if child in self._tables:
                raise KeyError(f"sequence {child!r} already allocated")
            table = self._tables[parent]
            if not 0 <= pages <= len(table):
                raise ValueError(
                    f"prefix fork of {pages} pages from {parent!r} "
                    f"({len(table)} pages)")
            if pages * self.page_size > self._lens[parent]:
                raise ValueError(
                    f"prefix fork of {pages} pages exceeds {parent!r}'s "
                    f"{self._lens[parent]}-token ledger (partial page)")
            shared = table[:pages]
            for phys in shared:
                self._refs[phys] += 1
            self._tables[child] = list(shared)
            self._lens[child] = pages * self.page_size

    def update_page_host(self, seq: Any, page: int, fn: Callable) -> None:
        """Host-side page rewrite under the recycle-detach discipline —
        the speculative seed-time staging path (ISSUE 12): ``fn`` gets
        a private copy of the NEWEST live bytes (the tier or a device
        copy may be ahead of host) and returns the page's new contents;
        every accelerator copy is then invalidated + detached and the
        host version jumps PAST the highest version any copy reached,
        so a deferred device writeback can never clobber the staged
        bytes or regress the host version.  Fails loudly (like
        :meth:`rollback_tail` / ``_privatize_locked``) when no live
        copy exists to stage from."""
        with self._lock:
            phys = self._tables[seq][page]
            d = self._pages[phys]
        src = d.newest_copy()
        if src is None or src.value is None:
            raise RuntimeError(
                f"{self.name}: page {phys} has no live copy to stage "
                f"a host write from (spilled beyond the host tier?)")
        val = fn(np.array(np.asarray(src.value), copy=True))
        host = d.get_copy(0)
        maxv = self._scrub_copies(d)
        host.value = np.asarray(val)
        host.version = maxv + 1
        host.coherency = COHERENCY_SHARED
        d.owner_device = 0

    def rollback_tail(self, seq: Any, new_len: int) -> int:
        """Truncate ``seq``'s speculatively-written tail back to
        ``new_len`` tokens — the speculative-decode rollback primitive
        (ISSUE 12): a rejected draft's K/V appends must never leak into
        the next superpool as stale cache.

        Every slot in ``[new_len, seq_len)`` is scrubbed: K/V zeroed,
        the in-tensor fill count reset to the kept slots, and — the
        recycle-detach discipline of :meth:`_new_page_locked` /
        :meth:`_privatize_locked` — each touched page's accelerator
        copies are invalidated+detached and its host version jumps PAST
        the highest version any copy ever reached, so a dirty device
        copy holding the rejected appends can never satisfy a later
        stage-in version check.  The boundary page's KEPT slots are
        sourced from the newest live copy (on-device writes run ahead
        of host until writeback).  The length ledger lands at
        ``new_len``; trailing preallocated-but-never-written pages stay
        in the table (they are zeroed and the next superpool's schedule
        reuses them).  Returns the number of slots rolled back (0 =
        nothing to do)."""
        with self._lock:
            table = self._tables[seq]
            old_len = self._lens[seq]
            if not 0 <= new_len <= old_len:
                raise ValueError(
                    f"rollback of {seq!r} to {new_len} outside its "
                    f"[0, {old_len}] ledger")
            if new_len == old_len:
                return 0
            P = self.page_size
            for page in range(new_len // P,
                              min((old_len - 1) // P + 1, len(table))):
                phys = table[page]
                if self._refs[phys] > 1:
                    # speculative slots are only ever written through a
                    # privatized tail — a shared page in the rollback
                    # range means the ledger and the block table
                    # disagree; scrubbing it would corrupt the sibling
                    raise RuntimeError(
                        f"{self.name}: rollback range page {phys} of "
                        f"{seq!r} is shared ({self._refs[phys]} refs)")
                keep = max(0, min(new_len - page * P, P))
                d = self._pages[phys]
                host = d.get_copy(0)
                if keep == 0:
                    val = np.zeros(self.default_dtt.shape, self.dtype)
                else:
                    src = d.newest_copy()
                    if src is None or src.value is None:
                        raise RuntimeError(
                            f"{self.name}: page {phys} has no live copy "
                            f"to roll back from (spilled beyond the "
                            f"host tier?)")
                    val = np.array(np.asarray(src.value), copy=True)
                    val[K_CH, keep:] = 0.0
                    val[V_CH, keep:] = 0.0
                    val[META_CH, 0, 0, 0] = keep
                maxv = self._scrub_copies(d)
                host.value = val
                host.version = maxv + 1
                host.coherency = COHERENCY_SHARED
                d.owner_device = 0
            self._lens[seq] = new_len
            self.tail_rollbacks += 1
            self.slots_rolled_back += old_len - new_len
            return old_len - new_len

    def has_seq(self, seq: Any) -> bool:
        with self._lock:
            return seq in self._tables

    def free_seq(self, seq: Any) -> int:
        """Release a sequence; pages drop to the free list when their
        last sharer leaves.  Returns the number of pages recycled."""
        freed = 0
        with self._lock:
            for phys in self._tables.pop(seq, ()):
                self._refs[phys] -= 1
                if self._refs[phys] == 0:
                    del self._refs[phys]
                    self._free.append(phys)
                    freed += 1
            self._lens.pop(seq, None)
        return freed

    # -- geometry / introspection ---------------------------------------
    @property
    def page_bytes(self) -> int:
        return self.default_dtt.nbytes

    def seq_len(self, seq: Any) -> int:
        with self._lock:
            return self._lens[seq]

    def npages(self, seq: Any) -> int:
        with self._lock:
            return len(self._tables[seq])

    def block_table(self, seq: Any) -> list[int]:
        with self._lock:
            return list(self._tables[seq])

    def live_seqs(self) -> list:
        with self._lock:
            return list(self._tables)

    def page_fill(self, seq: Any, page: int) -> int:
        """Valid slots of one logical page, from the length ledger (the
        in-tensor fill count is the kernel-side twin)."""
        with self._lock:
            n = self._lens[seq] - page * self.page_size
            return max(0, min(n, self.page_size))

    def stats(self) -> dict:
        with self._lock:
            in_use = sum(len(t) for t in self._tables.values())
            phys = len(self._refs)
            return {
                "seqs": len(self._tables),
                "tokens": sum(self._lens.values()),
                "logical_pages": in_use,
                "physical_pages": phys,
                "shared_pages": in_use - phys,
                "free_pages": len(self._free),
                "page_bytes": self.page_bytes,
                "bytes_in_use": phys * self.page_bytes,
                "pages_allocated": self.pages_allocated,
                "pages_recycled": self.pages_recycled,
                "cow_copies": self.cow_copies,
                # prefix-cache effectiveness + tier residency: every
                # consumer of stats() (bench llm emit, runtime_report's
                # llm block, the serve soak asserts) reads cache wins
                # and spill pressure off the SAME dict
                "prefix_hits": self.prefix_hits,
                "prefix_pages_reused": self.prefix_pages_reused,
                "tail_rollbacks": self.tail_rollbacks,
                "slots_rolled_back": self.slots_rolled_back,
                "host_tier_bytes": (self.tier.host_tier_bytes
                                    if self.tier is not None else 0),
                "prefetch_inflight": (self.tier.prefetch_inflight
                                      if self.tier is not None else 0),
            }
