"""Dependency tracking: hashed per-task IN-dep bookkeeping.

Rebuild of the reference's dep-resolution core (``parsec.c:1293-1897``):
not-yet-ready tasks are represented only by a *dependency tracker* in a hash
table keyed by (task_class_id, task key) — the hashed variant
(``parsec_hash_find_deps``, ``parsec.c:1501``); the multi-dimensional-array
variant is an optimization the rebuild folds into the same interface.  Each
arriving dep sets a bit in the satisfied mask (``parsec_update_deps_with_mask``
``parsec.c:1577``); when it equals the required mask (computed by evaluating
the class's input-dep guards for those locals), the task is instantiated with
its input data attached and handed to the scheduler
(``parsec_release_local_OUT_dependencies``, ``parsec.c:1670-1756``).
"""

from __future__ import annotations

import threading
from typing import Any

from ..core.hash_table import ConcurrentHashTable
from ..core.params import params as _params
from ..native import ENTRY_MISSING
from .scheduling import resolve_data_inputs
from .task import Task, TaskClass

_params.register(
    "deps_storage", "index-array",
    "dep-tracker storage: 'index-array' (parsec_default_find_deps — "
    "dense per-class arrays over static execution-space boxes, the "
    "default: non-eligible classes fall back to the hashed tier, and "
    "batched release takes one lock per class group) or 'hash' "
    "(parsec_hash_find_deps only)")
_params.declare_knob("deps_storage", values=("index-array", "hash"))
_params.register(
    "deps_index_array_max_slots", 1 << 22,
    "largest static-box volume (slots) the index-array tier will "
    "allocate densely; bigger boxes — e.g. the mostly-empty cube of a "
    "large triangular space — fall back to the hashed tier instead of "
    "materializing gigabytes of empty tracker slots")

# concurrency contracts, enforced by analysis.runtimelint (docs/ANALYSIS.md):
# the index store's array table and purge set mutate only under its _lock
# (per-class slot arrays carry their OWN anonymous locks — one lock per
# (taskpool, class), outside the lint's reach); the native tier's input
# side-dict only under _inputs_lock.
_LOCK_PROTECTED = {
    "_IndexArrayStore._arrays": "_lock",
    "_IndexArrayStore._dead": "_lock",
    "DependencyTracking._inputs": "_inputs_lock",
}

# 64-bit key layout for the native dep table: [tpid:10][tcid:6][params:48].
# Packing is *exact* (injective) or refused — a non-packable key falls back
# to the Python tracker for that task, never to a lossy hash.
_TP_BITS, _TC_BITS, _PARAM_BITS = 10, 6, 48


def _pack_key64(tpid: int, tcid: int, key: tuple) -> int | None:
    if tpid >= (1 << _TP_BITS) or tcid >= (1 << _TC_BITS):
        return None
    v = 0
    p = len(key)
    if p:
        bits = _PARAM_BITS // p
        lim = 1 << bits
        for x in key:
            if type(x) is not int or x < 0 or x >= lim:
                return None
            v = (v << bits) | x
    return (tpid << (_TC_BITS + _PARAM_BITS)) | (tcid << _PARAM_BITS) | v


def _tracker_key(taskpool: Any, tc: "TaskClass", locals_: dict,
                 tkey: tuple) -> tuple:
    """Where a task's dep tracker lives — shared by mask and counted modes.

    A user ``find_deps_fn`` (JDF_PROP_UD_FIND_DEPS_FN_NAME) answers the
    location question itself (any hashable identity); the tracker store/GC
    stays the runtime's (the alloc/free_deps_fn halves are runtime-owned).
    """
    if tc.find_deps_fn is not None:
        return (taskpool.taskpool_id, tc.find_deps_fn(taskpool, locals_))
    return (taskpool.taskpool_id, tc.task_class_id, tkey)


def _check_bit(tc: "TaskClass", where: Any, trk: "_DepTracker",
               bit: int) -> None:
    """The mask protocol's two invariants on an arrival: the dep is one the
    task waits for (a release that names the wrong input dep would leave
    the task unready for ever), and it arrives once."""
    assert trk.required_mask & bit, \
        f"dep {tc.name}{where} bit {bit} is not one the task waits for " \
        f"(mask {trk.required_mask:#x})"
    assert not (trk.satisfied_mask & bit), \
        f"dep {tc.name}{where} bit {bit} satisfied twice"


class _DepTracker:
    __slots__ = ("required_mask", "satisfied_mask", "inputs", "repo_refs",
                 "priority", "goal")

    def __init__(self, required_mask: int, nflows: int) -> None:
        self.required_mask = required_mask
        self.satisfied_mask = 0
        self.inputs: list[Any] = [None] * nflows
        self.repo_refs: list[Any] = [None] * nflows
        self.priority = 0
        self.goal = -1   # >= 0: counted mode (ranged deps), arrivals left


class _IndexArrayStore:
    """Dense per-(taskpool, class) tracker arrays over the static
    execution-space box — the ``parsec_default_find_deps`` variant
    (``parsec.c:1479``; ``-M index-array``, ``ptg-compiler/main.c:49``).
    Slot index = row-major linearization of (param - lo) over the box;
    triangular spaces waste the unused slots exactly like the
    reference's multi-dimensional arrays do.  Each (taskpool, class)
    array carries its own lock — slots of unrelated classes never
    contend (the hashed tier's per-key locking analog)."""

    __slots__ = ("_arrays", "_lock", "_dead", "_fits", "allocated",
                 "releases")

    def __init__(self) -> None:
        self._arrays: dict[tuple, tuple] = {}   # akey -> (lock, list)
        self._lock = threading.Lock()           # guards the dict only
        # purged taskpool ids: a late release racing teardown must NOT
        # resurrect the array (a context-lifetime leak of a dense array
        # plus stashed inputs); ids are per-context monotonically
        # assigned, so the set is bounded by finished pools
        self._dead: set[int] = set()
        # box-volume eligibility memo, keyed by the extents tuple itself
        # (volume is a pure function of it) — the hot release path pays a
        # dict hit, not a product loop
        self._fits: dict[tuple, bool] = {}
        self.allocated = 0    # arrays created (SDE-style engagement proof)
        self.releases = 0     # dep records through the indexed tier

    def fits(self, extents: tuple) -> bool:
        """Whether a static box is small enough to back densely — beyond
        ``deps_index_array_max_slots`` (a large triangular space's mostly
        empty cube) the class takes the hashed tier instead."""
        ok = self._fits.get(extents)
        if ok is None:
            size = 1
            for lo, stop in extents:
                size *= max(stop - lo, 0)
            ok = self._fits[extents] = \
                size <= _params.get("deps_index_array_max_slots")
        return ok

    @staticmethod
    def slot(extents: tuple, tkey: tuple) -> int | None:
        if len(tkey) != len(extents):
            return None
        li = 0
        for (lo, stop), v in zip(extents, tkey):
            if type(v) is not int or v < lo or v >= stop:
                return None
            li = li * (stop - lo) + (v - lo)
        return li

    def array(self, taskpool: Any, tc: TaskClass) -> tuple | None:
        """(lock, slots) for one (taskpool, class), created on first use;
        None for a purged taskpool (a late release must not resurrect)."""
        akey = (taskpool.taskpool_id, tc.task_class_id)
        with self._lock:
            if taskpool.taskpool_id in self._dead:
                return None
            entry = self._arrays.get(akey)
            if entry is None:
                size = 1
                for lo, stop in tc.space_extents:
                    size *= max(stop - lo, 0)
                entry = self._arrays[akey] = (threading.Lock(),
                                              [None] * size)
                self.allocated += 1
        return entry

    def purge(self, taskpool_id: int) -> None:
        with self._lock:
            self._dead.add(taskpool_id)
            for k in [k for k in self._arrays if k[0] == taskpool_id]:
                del self._arrays[k]


class DependencyTracking:
    """One instance per context (cf. per-task-class ``parsec_dependencies_t``).

    Storage tiers sharing one protocol: the **native** C++ dep table
    (mask bookkeeping behind one atomic call, keyed by an exact 64-bit
    packing of the task identity), the **Python** tracker table (any key
    shape), and — under the default ``deps_storage=index-array`` — dense
    per-class arrays over static execution-space boxes.  Data-carrying deps stash
    their input copies in a side dict either way; the pure-CTL hot path
    (the dispatch benchmark's EP DAG) never touches Python locks with
    the native tier on.
    """

    def __init__(self) -> None:
        self._table = ConcurrentHashTable()
        self._native = None
        self._inputs: dict[int, list] = {}    # k64 -> inputs ++ repo_refs
        self._inputs_lock = threading.Lock()
        self._index_store = (_IndexArrayStore()
                             if _params.get("deps_storage") == "index-array"
                             else None)
        try:
            from .. import native            # registers runtime_native
            if _params.get("runtime_native") and native.available():
                self._native = native.NativeDepTable()
        except Exception:
            self._native = None

    def release_dep(self, taskpool: Any, tc: TaskClass, locals_: dict,
                    flow_index: int, dep_index: int,
                    data_copy: Any, repo_ref: Any = None) -> Task | None:
        """Record one satisfied input dep; return the now-ready Task or None.

        ``repo_ref`` is (repo_entry, src_flow_index) for usage accounting at
        completion (``jdf2c.c:7157`` consume-input-repos contract).
        """
        view = tc.view_of(locals_)
        bit = 0 if tc.counted else 1 << tc.dep_bit(flow_index, dep_index)
        return self._release_one(taskpool, tc, locals_, view, flow_index,
                                 bit, data_copy, repo_ref)

    def _release_one(self, taskpool: Any, tc: TaskClass, locals_: dict,
                     view: Any, flow_index: int, bit: int,
                     data_copy: Any, repo_ref: Any) -> Task | None:
        """:meth:`release_dep` for a caller that holds the successor's
        locals view (``tc.locals_view``: what its guards read) and the dep's
        mask bit already.  In every tier the required mask is evaluated
        when the tracker is CREATED, on the task's first arrival."""
        tkey = tc.make_key(locals_)
        if tc.counted:
            # goal-counted mode (ranged input deps): arrivals decrement a
            # per-task counter instead of OR-ing bits — N arrivals may land
            # on ONE declared dep (the dependencies_goal protocol)
            return self._release_counted(taskpool, tc, locals_, view, tkey,
                                         flow_index, data_copy, repo_ref)
        if self._indexed_eligible(tc):
            li = _IndexArrayStore.slot(tc.space_extents, tkey)
            if li is not None:
                return self._release_indexed(taskpool, tc, locals_, view, li,
                                             bit, flow_index, data_copy,
                                             repo_ref)
        if self._native is not None and tc.find_deps_fn is None:
            # UD keys with non-int elements refuse to pack and fall through
            k64 = _pack_key64(taskpool.taskpool_id, tc.task_class_id, tkey)
            if k64 is not None:
                return self._release_native(taskpool, tc, locals_, view, k64,
                                            bit, flow_index, data_copy,
                                            repo_ref)
        key = _tracker_key(taskpool, tc, locals_, tkey)
        with self._table.locked(key):
            trk = self._table.get(key)
            if trk is None:
                trk = _DepTracker(tc.input_dep_mask(view), len(tc.flows))
                self._table.insert(key, trk)
            _check_bit(tc, key, trk, bit)
            trk.satisfied_mask |= bit
            if data_copy is not None:
                trk.inputs[flow_index] = data_copy
                trk.repo_refs[flow_index] = repo_ref
            ready = trk.satisfied_mask == trk.required_mask
            if ready:
                self._table.remove(key)
        if not ready:
            return None
        return self._make_ready(taskpool, tc, locals_, view, trk.inputs,
                                trk.repo_refs)

    def _indexed_eligible(self, tc: TaskClass) -> bool:
        """Whether a class's deps may take the dense index-array tier.
        The ONE predicate both release paths share — a split would route a
        single-record release and a batched release of the same successor
        through different trackers and hang the pool.  make_key_fn is
        excluded because a UD key is injective but not positionally
        aligned with the param-range extents (direct linearization could
        collide distinct tasks); oversized boxes fall to the hashed tier
        (:meth:`_IndexArrayStore.fits`)."""
        memo = tc._indexed_memo     # per class: asked for every record
        if memo is not None and memo[0] is self:
            return memo[1]
        store = self._index_store
        ok = (store is not None and not tc.counted
              and tc.find_deps_fn is None and tc.make_key_fn is None
              and tc.space_extents is not None
              and store.fits(tc.space_extents))
        tc._indexed_memo = (self, ok)
        return ok

    def release_many(self, taskpool: Any,
                     records: list[tuple]) -> list[Task]:
        """Batched release of one completing task's successor deps.

        ``records`` is a list of ``(tc, locals_, view, flow_index, bit,
        data_copy, repo_ref)`` tuples, :meth:`_release_one`'s arguments
        (``view``: the successor's ``tc.locals_view`` of ``locals_``, built
        once by the releaser; ``bit``: the dep's mask bit, 0 for a counted
        class).  Records eligible for the dense
        index-array tier are grouped per task class and released under ONE
        lock acquisition per group (the batched-dep-release half of the
        critical-path fast path); everything else goes record-at-a-time
        through :meth:`_release_one`.  Returns every task that became ready.
        """
        ready: list[Task] = []
        if self._index_store is not None and len(records) > 1:
            by_class: dict[int, list] = {}
            tcs: dict[int, TaskClass] = {}
            rest: list[tuple] = []
            for rec in records:
                tc = rec[0]
                if self._indexed_eligible(tc):
                    li = _IndexArrayStore.slot(tc.space_extents,
                                               tc.make_key(rec[1]))
                    if li is not None:
                        cid = tc.task_class_id
                        by_class.setdefault(cid, []).append((rec, li))
                        tcs[cid] = tc
                        continue
                rest.append(rec)
            for cid, grp in by_class.items():
                ready.extend(self._release_indexed_batch(taskpool, tcs[cid],
                                                         grp))
            records = rest
        for rec in records:
            t = self._release_one(taskpool, *rec)
            if t is not None:
                ready.append(t)
        return ready

    def _release_indexed_batch(self, taskpool: Any, tc: TaskClass,
                               grp: list[tuple]) -> list[Task]:
        """Same mask protocol as :meth:`_release_indexed`, amortizing the
        class-array lock over a whole batch of same-class releases."""
        store = self._index_store
        entry = store.array(taskpool, tc)
        if entry is None:
            return []        # taskpool already purged: late releases dropped
        lock, arr = entry
        done: list[tuple] = []
        with lock:
            cur = store._arrays.get((taskpool.taskpool_id,
                                     tc.task_class_id))
            if cur is None or cur[1] is not arr:
                return []    # purged between lookup and lock (abort race)
            store.releases += len(grp)
            for (_, locals_, view, fi, bit, data_copy, repo_ref), li in grp:
                trk = arr[li]
                if trk is None:
                    trk = arr[li] = _DepTracker(tc.input_dep_mask(view),
                                                len(tc.flows))
                _check_bit(tc, [li], trk, bit)
                trk.satisfied_mask |= bit
                if data_copy is not None:
                    trk.inputs[fi] = data_copy
                    trk.repo_refs[fi] = repo_ref
                if trk.satisfied_mask == trk.required_mask:
                    arr[li] = None
                    done.append((locals_, view, trk))
        return [self._make_ready(taskpool, tc, locals_, view, trk.inputs,
                                 trk.repo_refs)
                for locals_, view, trk in done]

    def _release_indexed(self, taskpool: Any, tc: TaskClass, locals_: dict,
                         view: Any, li: int, bit: int, flow_index: int,
                         data_copy: Any, repo_ref: Any) -> Task | None:
        """The index-array variant's release: same mask protocol as the
        hashed tier, tracker slot found by direct indexing."""
        store = self._index_store
        entry = store.array(taskpool, tc)
        if entry is None:
            return None    # taskpool already purged: late release dropped
        lock, arr = entry
        with lock:
            cur = store._arrays.get((taskpool.taskpool_id,
                                     tc.task_class_id))
            if cur is None or cur[1] is not arr:
                # purged between lookup and lock (abort teardown racing a
                # late release): drop the record — the pool is dying, and
                # splitting bits across an orphaned tracker would hang it
                return None
            store.releases += 1
            trk = arr[li]
            if trk is None:
                trk = arr[li] = _DepTracker(tc.input_dep_mask(view),
                                            len(tc.flows))
            _check_bit(tc, [li], trk, bit)
            trk.satisfied_mask |= bit
            if data_copy is not None:
                trk.inputs[flow_index] = data_copy
                trk.repo_refs[flow_index] = repo_ref
            ready = trk.satisfied_mask == trk.required_mask
            if ready:
                arr[li] = None
        if not ready:
            return None
        return self._make_ready(taskpool, tc, locals_, view, trk.inputs,
                                trk.repo_refs)

    def _release_counted(self, taskpool: Any, tc: TaskClass, locals_: dict,
                         view: Any, tkey: tuple, flow_index: int,
                         data_copy: Any, repo_ref: Any) -> Task | None:
        key = _tracker_key(taskpool, tc, locals_, tkey)
        with self._table.locked(key):
            trk = self._table.get(key)
            if trk is None:
                trk = _DepTracker(0, len(tc.flows))
                trk.goal = tc.input_dep_goal(view)
                self._table.insert(key, trk)
            assert trk.goal > 0, \
                f"dep {tc.name}{tkey}: more arrivals than the goal"
            trk.goal -= 1
            if data_copy is not None:
                trk.inputs[flow_index] = data_copy
                trk.repo_refs[flow_index] = repo_ref
            ready = trk.goal == 0
            if ready:
                self._table.remove(key)
        if not ready:
            return None
        return self._make_ready(taskpool, tc, locals_, view, trk.inputs,
                                trk.repo_refs)

    def _release_native(self, taskpool: Any, tc: TaskClass, locals_: dict,
                        view: Any, k64: int, bit: int, flow_index: int,
                        data_copy: Any, repo_ref: Any) -> Task | None:
        # inputs are written BEFORE the native release: the releaser that
        # observes readiness sees every earlier writer's entry (GIL + the
        # table's internal lock order the accesses)
        nf = len(tc.flows)
        first = False
        if data_copy is not None:
            with self._inputs_lock:
                lst = self._inputs.get(k64)
                if lst is None:
                    lst = self._inputs[k64] = [None] * (2 * nf)
                    first = True
                lst[flow_index] = data_copy
                lst[nf + flow_index] = repo_ref
        # the table keeps the required mask from the arrival that created
        # the entry, so only that arrival evaluates the guards.  The first
        # copy stashed for the task is that arrival (or follows arrivals
        # that carried none: the mask it brings is then ignored); any other
        # release goes without a mask and is answered ENTRY_MISSING where
        # no entry exists yet, instead of creating one (two first arrivals
        # racing each install the same mask: idempotent)
        release = self._native.release
        rc = release(k64, bit, tc.input_dep_mask(view) if first else 0)
        if rc == ENTRY_MISSING:
            rc = release(k64, bit, tc.input_dep_mask(view))
        if not rc:
            return None
        with self._inputs_lock:
            lst = self._inputs.pop(k64, None)
        if lst is None:
            return self._make_ready(taskpool, tc, locals_, view,
                                    [None] * nf, [None] * nf)
        return self._make_ready(taskpool, tc, locals_, view, lst[:nf],
                                lst[nf:])

    def _make_ready(self, taskpool: Any, tc: TaskClass, locals_: dict,
                    view: Any, inputs: list, repo_refs: list) -> Task:
        """The task of a tracker whose last dep arrived; ``inputs`` and
        ``repo_refs`` become the task's own lists (every caller hands over
        a tracker's, which dies here, or fresh ones)."""
        prio = tc.priority(view) if tc.priority is not None else 0
        task = Task(taskpool, tc, dict(locals_), priority=prio)
        task.data = inputs
        task.repo_entries = repo_refs
        task.status = "ready"
        # snapshot collection reads at creation
        resolve_data_inputs(task, view)
        return task

    def purge_taskpool(self, taskpool_id: int) -> None:
        """Reclaim tracker/input entries of a finished (or aborted) taskpool.

        Normally completion consumes every entry; a taskpool that dies with
        unsatisfied deps would otherwise leak its stashed input copies for
        the context lifetime (the k64 space is context-wide)."""
        with self._inputs_lock:
            shift = _TC_BITS + _PARAM_BITS
            for k in [k for k in self._inputs if (k >> shift) == taskpool_id]:
                del self._inputs[k]
        for key, _ in list(self._table.items()):
            if isinstance(key, tuple) and key and key[0] == taskpool_id:
                self._table.remove(key)
        if self._index_store is not None:
            self._index_store.purge(taskpool_id)

    @property
    def native_enabled(self) -> bool:
        return self._native is not None

    def __len__(self) -> int:
        n = len(self._table)
        if self._native is not None:
            n += len(self._native)
        return n
