"""Scheduler implementations.

Rebuild of the reference's scheduler zoo (``parsec/mca/sched/*``, SURVEY
§2.4), all eleven: **lfq** (default) per-stream bounded priority heaps
bucketed by task class, spilling to a per-VP FIFO bucketed the same way,
with sibling stealing; **ap** global absolute-priority list; **spq** global
priority+distance list (the tutorial scheduler, ``sched.h:87-169``); **gd**
global dequeue; **ll/llp** per-stream LIFOs with stealing (± priority);
**rnd** random; **ip** inverse priority;
and the local-hierarchical family — **pbq** priority-based local queues with
proximity-ordered stealing, **ltq** local tree queues whose steals migrate
whole release-subtrees, **lhq** local hierarchical queues with an
intermediate group rung.  Priorities and the distance contract follow
``sched/api.py``.
"""

from __future__ import annotations

import heapq
import itertools
import random
import threading
from collections import deque
from typing import Any, Sequence

from ..core.params import params as _params
from ..core.hbbuffer import HBBuffer, ReadyQueue
from ..core.mca import Component, component
# imported at module load (main thread): the topology affinity snapshot
# must be taken before any worker binds itself to a single core
from ..core import topology as _topology
from .api import SchedulerModule

_params.register("sched_lfq_buffer_size", 256,
                 "per-stream ready-queue capacity for lfq: the window in "
                 "which priorities order the tasks; a release beyond it "
                 "spills to the per-VP system queue and keeps arrival order "
                 "(which is what fills the device module's batches)")


def _task_priority(t: Any) -> int:
    return t.priority


def _stream_queue_depths(context: Any) -> dict[str, int]:
    """Shared per-stream depth map (lfq/pbq family shapes) for the
    flight-recorder stall dump."""
    out: dict[str, int] = {}
    for vp in context.virtual_processes:
        if vp.sched_private is not None and \
                hasattr(vp.sched_private, "system"):
            out[f"vp{vp.vp_id}.system"] = len(vp.sched_private.system)
        for es in vp.execution_streams:
            if es.sched_private is not None:
                try:
                    out[f"es{es.th_id}"] = len(es.sched_private)
                except TypeError:
                    pass
    return out


# ---------------------------------------------------------------------------
# lfq — local flat queues (default; cf. sched/lfq, priority 20)
# ---------------------------------------------------------------------------

class _VPQueues:
    def __init__(self, system: Any) -> None:
        self.system = system
        self.lock = threading.Lock()


class LFQModule(SchedulerModule):
    """Ready queues keyed by task class: each stream owns a
    :class:`ReadyQueue` (core/hbbuffer.py), the primary push target, bounded
    by ``sched_lfq_buffer_size``; each VP a FIFO one for what spills past
    that bound, external submissions and rescheduled tasks.  A select pops
    the stream's own queue (newest first until a task carries a priority,
    then best priority first), steals a sibling's oldest, then takes the
    system queue's oldest; none of them scans, and each queue's lock is
    contended only by thieves and remote pushers."""

    name = "lfq"

    def install(self, context: Any) -> None:
        for vp in context.virtual_processes:
            vp.sched_private = _VPQueues(ReadyQueue(fifo=True))
        self._cap = _params.get("sched_lfq_buffer_size")

    def flow_init(self, es: Any) -> None:
        es.sched_private = ReadyQueue()

    def schedule(self, es: Any, tasks: Sequence[Any], distance: int = 0) -> None:
        sp = es.sched_private
        system = es.virtual_process.sched_private.system
        if sp is None or distance > 0:
            system.push_all(tasks)
            return
        # advisory bound (concurrent pushers may briefly overshoot): the
        # head of a release stays local, the tail spills in arrival order
        room = max(self._cap - len(sp), 0)
        if room < len(tasks):
            system.push_all(tasks[room:])
            tasks = tasks[:room]
        sp.push_all(tasks)

    def select(self, es: Any) -> tuple[Any | None, int]:
        sp = es.sched_private
        if sp is not None:
            t = sp.pop()
            if t is not None:
                return t, 0
        # steal from sibling streams in the same VP (never across VPs)
        for sib in es.virtual_process.execution_streams:
            if sib is es or sib.sched_private is None:
                continue
            t = sib.sched_private.steal()
            if t is not None:
                return t, 1
        t = es.virtual_process.sched_private.system.pop()
        return (t, 99) if t is not None else (None, 0)

    def select_class(self, es: Any, task_class: Any, want: int
                     ) -> tuple[list[tuple[Any, int]], int]:
        """The class's bucket of each queue ``select`` would reach, in its
        order; no task of another class leaves its queue."""
        vp = es.virtual_process
        queues = [(es.sched_private, 0)]
        queues += [(s.sched_private, 1) for s in vp.execution_streams
                   if s is not es]
        queues.append((vp.sched_private.system, 99))
        taken: list[tuple[Any, int]] = []
        for q, distance in queues:
            if q is not None and len(taken) < want:
                taken += [(t, distance) for t in
                          q.pop_class(task_class, want - len(taken))]
        return taken, 0

    def remove(self, context: Any) -> None:
        for vp in context.virtual_processes:
            vp.sched_private = None
            for es in vp.execution_streams:
                es.sched_private = None

    def pending_tasks(self, context: Any) -> int:
        n = 0
        for vp in context.virtual_processes:
            if vp.sched_private is not None:
                n += len(vp.sched_private.system)
            for es in vp.execution_streams:
                if es.sched_private is not None:
                    n += len(es.sched_private)
        return n

    queue_depths = staticmethod(_stream_queue_depths)


# ---------------------------------------------------------------------------
# global single-queue family
# ---------------------------------------------------------------------------

class _GlobalHeapModule(SchedulerModule):
    """Shared helper: one process-global heap ordered by a key fn."""

    def install(self, context: Any) -> None:
        self._heap: list = []
        self._lock = threading.Lock()
        self._tie = itertools.count()

    def _key(self, task: Any, distance: int):
        raise NotImplementedError

    def schedule(self, es: Any, tasks: Sequence[Any], distance: int = 0) -> None:
        with self._lock:
            for t in tasks:
                heapq.heappush(self._heap,
                               (self._key(t, distance), next(self._tie), t))

    def select(self, es: Any) -> tuple[Any | None, int]:
        with self._lock:
            if not self._heap:
                return None, 0
            _, _, t = heapq.heappop(self._heap)
            return t, 0

    def remove(self, context: Any) -> None:
        self._heap = []

    def pending_tasks(self, context: Any) -> int:
        return len(self._heap)


class APModule(_GlobalHeapModule):
    """Absolute priority: highest priority first (cf. sched/ap)."""
    name = "ap"

    def _key(self, task: Any, distance: int):
        return (-task.priority,)


class SPQModule(_GlobalHeapModule):
    """Priority then distance (the documented tutorial scheduler, sched/spq)."""
    name = "spq"

    def _key(self, task: Any, distance: int):
        return (-task.priority, distance)


class IPModule(_GlobalHeapModule):
    """Inverse priority — lowest first (cf. sched/ip; a testing policy)."""
    name = "ip"

    def _key(self, task: Any, distance: int):
        return (task.priority,)


class GDModule(SchedulerModule):
    """Global dequeue (cf. sched/gd): hot tasks to the front."""
    name = "gd"

    def install(self, context: Any) -> None:
        self._dq = deque()
        self._lock = threading.Lock()

    def schedule(self, es: Any, tasks: Sequence[Any], distance: int = 0) -> None:
        with self._lock:
            if distance == 0:
                self._dq.extendleft(reversed(list(tasks)))
            else:
                self._dq.extend(tasks)

    def select(self, es: Any) -> tuple[Any | None, int]:
        with self._lock:
            if self._dq:
                return self._dq.popleft(), 0
        return None, 0

    def remove(self, context: Any) -> None:
        self._dq = deque()

    def pending_tasks(self, context: Any) -> int:
        return len(self._dq)


class RNDModule(SchedulerModule):
    """Random selection (cf. sched/rnd; a fairness fuzzer)."""
    name = "rnd"

    def install(self, context: Any) -> None:
        self._items: list = []
        self._lock = threading.Lock()
        self._rng = random.Random(0x9a53)

    def schedule(self, es: Any, tasks: Sequence[Any], distance: int = 0) -> None:
        with self._lock:
            self._items.extend(tasks)

    def select(self, es: Any) -> tuple[Any | None, int]:
        with self._lock:
            if not self._items:
                return None, 0
            i = self._rng.randrange(len(self._items))
            self._items[i], self._items[-1] = self._items[-1], self._items[i]
            return self._items.pop(), 0

    def remove(self, context: Any) -> None:
        self._items = []

    def pending_tasks(self, context: Any) -> int:
        return len(self._items)


# ---------------------------------------------------------------------------
# ll / llp — per-stream LIFOs with stealing (cf. sched/ll, sched/llp)
# ---------------------------------------------------------------------------

class LLModule(SchedulerModule):
    """Per-stream lock-free LIFOs with stealing.  When the native tier is
    up, the queue IS the C++ ABA-counted LIFO (the reference's ll is exactly
    its ``class/lifo.h``); tasks ride as uid handles through a side map.
    ``llp`` needs priority scans, so it stays on the Python deque.

    Steal order differs between tiers by design: the native LIFO can only
    pop from the top, so steals are LIFO (exactly the reference's ll, which
    steals via ``parsec_lifo_pop`` too); the Python tier steals FIFO from
    the victim's bottom for locality.  Both are valid ll semantics — the
    scheduler contract orders nothing across streams."""

    name = "ll"
    use_priority = False

    def install(self, context: Any) -> None:
        self._tasks: dict[int, Any] = {}
        self._native = None
        if not self.use_priority:
            try:
                from .. import native        # registers runtime_native
                if _params.get("runtime_native") and native.available():
                    self._native = native
            except Exception:
                self._native = None

    def flow_init(self, es: Any) -> None:
        if self._native is not None:
            es.sched_private = self._native.NativeLifo()
        else:
            es.sched_private = (deque(), threading.Lock())

    def schedule(self, es: Any, tasks: Sequence[Any], distance: int = 0) -> None:
        target = es if es.sched_private is not None else \
            es.virtual_process.execution_streams[0]
        if self._native is not None:
            lifo = target.sched_private
            for t in tasks:
                self._tasks[t.uid] = t
                lifo.push(t.uid)
            return
        dq, lock = target.sched_private
        with lock:
            dq.extend(tasks)

    def select(self, es: Any) -> tuple[Any | None, int]:
        streams = es.virtual_process.execution_streams
        order = [es] + [s for s in streams if s is not es]
        for dist, s in enumerate(order):
            if s.sched_private is None:
                continue
            if self._native is not None:
                uid = s.sched_private.pop()
                if uid is None:
                    continue
                t = self._tasks.pop(uid, None)
                if t is None:
                    continue   # remove() raced us during teardown
                return t, min(dist, 1)
            dq, lock = s.sched_private
            with lock:
                if not dq:
                    continue
                if self.use_priority and s is es:
                    best = max(range(len(dq)), key=lambda i: dq[i].priority)
                    t = dq[best]
                    del dq[best]
                    return t, 0
                # own queue: LIFO; victim: FIFO steal
                return (dq.pop() if s is es else dq.popleft()), min(dist, 1)
        return None, 0

    def remove(self, context: Any) -> None:
        for vp in context.virtual_processes:
            for es in vp.execution_streams:
                es.sched_private = None
        self._tasks = {}

    def pending_tasks(self, context: Any) -> int:
        n = 0
        for vp in context.virtual_processes:
            for es in vp.execution_streams:
                if es.sched_private is None:
                    continue
                if self._native is not None:
                    n += len(es.sched_private)
                else:
                    n += len(es.sched_private[0])
        return n


class LLPModule(LLModule):
    name = "llp"
    use_priority = True


# ---------------------------------------------------------------------------
# the local-hierarchical family: pbq / ltq / lhq
# (cf. sched_local_queues_utils.h: per-stream hbbuffer "task_queue", an
#  ordered list of hierarch queues to steal from, and a shared system
#  dequeue.  hwloc proximity becomes th_id ring distance here — the GIL
#  flattens cache hierarchy, the *structure* is what is rebuilt.)
# ---------------------------------------------------------------------------

class PBQModule(SchedulerModule):
    """Priority-based local queues (``mca/sched/pbq``): per-stream bounded
    buffer with best-priority pop, nearest-neighbor steal order, shared
    system dequeue."""

    name = "pbq"

    def install(self, context: Any) -> None:
        self._order: dict[int, list] = {}   # id(es) -> cached steal order
        for vp in context.virtual_processes:
            vp.sched_private = _VPQueues(deque())
            # reference queue_size = 4 * vp->nb_cores — per VP
            vp.sched_private.cap = max(4, 4 * len(vp.execution_streams))

    def flow_init(self, es: Any) -> None:
        vpq = es.virtual_process.sched_private

        def overflow(items: list, distance: int) -> None:
            with vpq.lock:
                vpq.system.extend(items)

        es.sched_private = HBBuffer(vpq.cap, parent_push=overflow)

    def _steal_order(self, es: Any) -> list:
        order = self._order.get(id(es))
        if order is None:
            sibs = es.virtual_process.execution_streams
            n = len(sibs)
            me = sibs.index(es)
            my_core = _topology.core_of_stream(es.th_id)
            idx = {id(s): i for i, s in enumerate(sibs)}
            # topology-near first (same LLC before cross-cache — the
            # hwloc distance matrix), ring distance as the tiebreak;
            # static per stream, so computed once and cached
            order = sorted(
                (s for s in sibs if s is not es),
                key=lambda s: (
                    _topology.distance(my_core,
                                       _topology.core_of_stream(s.th_id)),
                    min((idx[id(s)] - me) % n,
                        (me - idx[id(s)]) % n)))
            self._order[id(es)] = order
        return order

    def schedule(self, es: Any, tasks: Sequence[Any],
                 distance: int = 0) -> None:
        if es.sched_private is None or distance > 0:
            vpq = es.virtual_process.sched_private
            with vpq.lock:
                vpq.system.extend(tasks)
            return
        es.sched_private.push_all(list(tasks), distance)

    def select(self, es: Any) -> tuple[Any | None, int]:
        if es.sched_private is not None:
            t = es.sched_private.try_pop_best(priority=_task_priority)
            if t is not None:
                return t, 0
            for d, sib in enumerate(self._steal_order(es)):
                if sib.sched_private is None:
                    continue
                t = sib.sched_private.steal()
                if t is not None:
                    return t, min(1 + d, 98)   # 99 is the system sentinel
        vpq = es.virtual_process.sched_private
        with vpq.lock:
            if vpq.system:
                return vpq.system.popleft(), 99
        return None, 0

    def remove(self, context: Any) -> None:
        for vp in context.virtual_processes:
            vp.sched_private = None
            for es in vp.execution_streams:
                es.sched_private = None

    def pending_tasks(self, context: Any) -> int:
        n = 0
        for vp in context.virtual_processes:
            if vp.sched_private is not None:
                n += len(vp.sched_private.system)
            for es in vp.execution_streams:
                if es.sched_private is not None:
                    n += len(es.sched_private)
        return n

    queue_depths = staticmethod(_stream_queue_depths)


class _Bundle:
    """A released batch kept together — the maxheap node of ltq: the owner
    pops the best task off the top; a thief migrates the whole remainder
    (subtree stealing)."""

    __slots__ = ("tasks",)

    def __init__(self, tasks: list) -> None:
        self.tasks = sorted(tasks, key=lambda t: t.priority, reverse=True)

    @property
    def priority(self) -> int:
        return self.tasks[0].priority if self.tasks else -1


class LTQModule(PBQModule):
    """Local tree queues (``mca/sched/ltq``): releases travel as heaps —
    one steal migrates a whole subtree of related work, preserving the
    producer-consumer locality the tree encodes."""

    name = "ltq"

    def schedule(self, es: Any, tasks: Sequence[Any],
                 distance: int = 0) -> None:
        if not tasks:
            return
        super().schedule(es, [_Bundle(list(tasks))], distance)

    def select(self, es: Any) -> tuple[Any | None, int]:
        b, d = super().select(es)
        if b is None:
            return None, 0
        t = b.tasks.pop(0)
        if b.tasks and es.sched_private is not None:
            # remainder stays with whoever popped it (subtree migration)
            es.sched_private.push_all([b], 0)
        return t, d

    def pending_tasks(self, context: Any) -> int:
        n = 0
        for vp in context.virtual_processes:
            if vp.sched_private is not None:
                n += sum(len(b.tasks) for b in vp.sched_private.system)
            for es in vp.execution_streams:
                if es.sched_private is not None:
                    n += sum(len(b.tasks) for b in es.sched_private._items)
        return n


class LHQModule(PBQModule):
    """Local hierarchical queues (``mca/sched/lhq``): an intermediate
    *group* buffer between the per-stream buffers and the system queue —
    the hwloc-level ladder with two rungs (stream → group → VP)."""

    name = "lhq"

    def install(self, context: Any) -> None:
        super().install(context)
        self._group: dict[int, Any] = {}   # id(es) -> its group buffer
        for vp in context.virtual_processes:
            # one group buffer per last-level cache represented among this
            # VP's streams (the real hwloc rung; a VP whose streams all
            # share one LLC gets one group — no artificial split)
            vpq = vp.sched_private
            llcs = sorted({_topology.llc_group_of(
                _topology.core_of_stream(s.th_id))
                for s in vp.execution_streams})
            vpq.llc_index = {llc: i for i, llc in enumerate(llcs)}
            vpq.groups = []
            for _g in llcs:
                def spill(items: list, distance: int, vpq=vpq) -> None:
                    with vpq.lock:
                        vpq.system.extend(items)
                vpq.groups.append(HBBuffer(vpq.cap, parent_push=spill))

    def _group_of(self, es: Any):
        grp = self._group.get(id(es))
        if grp is None:
            vpq = es.virtual_process.sched_private
            g = vpq.llc_index[_topology.llc_group_of(
                _topology.core_of_stream(es.th_id))]
            grp = vpq.groups[g]
            self._group[id(es)] = grp
        return grp

    def flow_init(self, es: Any) -> None:
        vpq = es.virtual_process.sched_private

        def overflow(items: list, distance: int) -> None:
            self._group_of(es).push_all(items, distance)

        es.sched_private = HBBuffer(vpq.cap, parent_push=overflow)

    def select(self, es: Any) -> tuple[Any | None, int]:
        if es.sched_private is not None:
            t = es.sched_private.try_pop_best(priority=_task_priority)
            if t is not None:
                return t, 0
            my_grp = self._group_of(es)
            # the stream's OWN hierarchy: its buffer's spill target is not
            # another stream's queue, so this is distance 0 (not a steal)
            t = my_grp.try_pop_best(priority=_task_priority)
            if t is not None:
                return t, 0
            for d, sib in enumerate(self._steal_order(es)):
                if sib.sched_private is None:
                    continue
                t = sib.sched_private.steal()
                if t is not None:
                    return t, min(1 + d, 98)
            vpq = es.virtual_process.sched_private
            for grp in vpq.groups:
                if grp is my_grp:
                    continue    # already drained above; a re-pop is no steal
                t = grp.steal()
                if t is not None:
                    return t, 10
        vpq = es.virtual_process.sched_private
        with vpq.lock:
            if vpq.system:
                return vpq.system.popleft(), 99
        return None, 0

    def pending_tasks(self, context: Any) -> int:
        n = super().pending_tasks(context)
        for vp in context.virtual_processes:
            if getattr(vp.sched_private, "groups", None):
                n += sum(len(g) for g in vp.sched_private.groups)
        return n


# ---------------------------------------------------------------------------
# component registrations (priorities mirror the reference's)
# ---------------------------------------------------------------------------

def _mk_component(mod_cls: type, prio: int) -> None:
    @component
    class _C(Component):
        type_name = "sched"
        name = mod_cls.name
        priority = prio

        def open(self, context: Any = None) -> SchedulerModule:
            return mod_cls()

    _C.__name__ = f"Sched{mod_cls.name.upper()}Component"


@component
class SchedServeFairComponent(Component):
    """``--mca sched serve_fair`` / ``Context(scheduler="serve_fair")``:
    a context built with the serving layer's weighted-fair shim
    (serve/fair.py) pre-installed around whichever module wins the normal
    priority query.  Fairness applies only to tasks of pools carrying a
    serve submission — i.e. this exists to hand a pre-shimmed context to
    ``RuntimeServer(context=...)`` (which then reuses it instead of
    stacking a second shim); pools enqueued outside a server delegate
    straight through to the inner module and are dispatched FIRST.
    Explicit request only: the shim taxes schedule/select with a fairness
    probe, so it must never win a default query."""

    type_name = "sched"
    name = "serve_fair"
    priority = 0

    def query(self, context: Any = None) -> bool:
        return False

    def open(self, context: Any = None) -> SchedulerModule:
        from ..core.mca import repository
        from ..serve.fair import FairScheduler
        # best-priority inner by direct scan (not repository.query: the
        # sched MCA param may name serve_fair itself, which would recurse)
        for c in repository.components_of_type("sched"):
            if c is not self and c.query(context):
                return FairScheduler(c.open(context))
        raise LookupError("serve_fair: no inner sched component accepts "
                          "this context")


_mk_component(LFQModule, 20)
_mk_component(SPQModule, 18 - 6)   # spq=12 in the reference
_mk_component(APModule, 12)
_mk_component(GDModule, 10)
_mk_component(PBQModule, 4)
_mk_component(LTQModule, 3)
_mk_component(LHQModule, 3)
_mk_component(LLModule, 2)
_mk_component(LLPModule, 2)
_mk_component(RNDModule, 1)
_mk_component(IPModule, 0)
