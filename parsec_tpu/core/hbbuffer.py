"""Ready-task stores of the local-queue schedulers.

:class:`ReadyQueue` is the default scheduler's (lfq) store: priority heaps
bucketed by task class, so that a pop never scans and a device batch can take
the tasks of its class alone.  :class:`HBBuffer` is the rebuild of
``parsec/class/hbbuffer.{h,c}``: a small fixed-capacity buffer that *spills to
a parent store* when full, stacked by pbq/ltq/lhq (per-thread buffer → group →
per-VP overflow queue); its pops scan newest-first with an optional
best-priority selection over at most ``capacity`` entries.
"""

from __future__ import annotations

import threading
from collections import deque
from heapq import heappop, heappush
from typing import Any, Callable, Sequence

# concurrency contracts, enforced by analysis.runtimelint (docs/ANALYSIS.md):
# HBBuffer's item list and every field of a ReadyQueue mutate only under
# the instance's _lock (``__len__`` reads one int, GIL-atomic).
_LOCK_PROTECTED = {
    "HBBuffer._items": "_lock",
    "ReadyQueue._buckets": "_lock",
    "ReadyQueue._prio": "_lock",
    "ReadyQueue._seq": "_lock",
    "ReadyQueue._len": "_lock",
}


class ReadyQueue:
    """Ready tasks held per ``task_class``, each bucket in pop order.

    An entry is ``(-priority, seq, task)`` with ``seq`` a running number of
    the queue, so entries order without ever comparing tasks.  Any thread may
    push, pop or steal: every operation is a few C calls under ``_lock``
    (uncontended on the owner's select→release path; thieves and the comm
    thread contend only for the one queue they touch).  No operation walks
    the entries: a pop compares the *heads* of the non-empty buckets (one per
    live task class) and a class pop touches that class's bucket alone.

    The order adapts to what the queue observes in its input:

    - while no pushed task has carried a priority, a bucket is a deque in
      arrival order: the owner pops the **newest** (LIFO locality) and a
      thief steals the oldest;
    - the first task with a nonzero priority flips the queue (one-way) into
      *priority mode*: each bucket becomes a heap (entries in ``seq`` order
      already are one, so the flip is one ``list()`` a bucket and sorts
      nothing) and every pop yields the **best priority, oldest among
      equals**;
    - ``fifo=True`` (the per-VP system queue: external submissions and
      rescheduled tasks) never flips and always yields the oldest.
    """

    __slots__ = ("_fifo", "_lock", "_buckets", "_prio", "_seq", "_len")

    def __init__(self, fifo: bool = False) -> None:
        self._fifo = fifo
        self._lock = threading.Lock()
        self._buckets: dict[Any, Any] = {}   # task_class -> deque | heap
        self._prio = False        # one-way flip: stays sticky once set
        self._seq = 0
        self._len = 0

    def __len__(self) -> int:
        return self._len

    def push_all(self, items: Sequence[Any]) -> None:
        with self._lock:
            if not (self._prio or self._fifo):
                for t in items:
                    if t.priority:
                        self._buckets = {tc: list(b) for tc, b
                                         in self._buckets.items()}
                        self._prio = True
                        break
            buckets = self._buckets
            prio = self._prio
            seq = self._seq
            for t in items:
                seq += 1
                b = buckets.get(t.task_class)
                if b is None:
                    b = buckets[t.task_class] = [] if prio else deque()
                if prio:
                    heappush(b, (-t.priority, seq, t))
                else:
                    b.append((0, seq, t))
            self._seq = seq
            self._len += len(items)

    def _take_locked(self, newest: bool) -> Any | None:
        """Pop over all classes: the newest entry, else the least one (best
        priority and oldest, which is plainly the oldest outside priority
        mode).  Caller holds ``_lock``."""
        buckets = self._buckets
        if not buckets:
            return None
        if len(buckets) == 1:
            tc = next(iter(buckets))
        elif newest:
            tc = max(buckets, key=lambda c: buckets[c][-1])
        else:
            tc = min(buckets, key=lambda c: buckets[c][0])
        b = buckets[tc]
        entry = b.pop() if newest else \
            heappop(b) if self._prio else b.popleft()
        if not b:
            del buckets[tc]
        self._len -= 1
        return entry[2]

    def pop(self) -> Any | None:
        """The owner's pop, in the queue's order (class docstring)."""
        with self._lock:
            return self._take_locked(not (self._prio or self._fifo))

    def steal(self) -> Any | None:
        """Victim-side pop: the oldest (work-stealing fairness), and in
        priority mode the best."""
        with self._lock:
            return self._take_locked(False)

    def pop_class(self, task_class: Any, want: int) -> list[Any]:
        """Up to ``want`` tasks of ``task_class`` in the queue's order; no
        other class's bucket is touched."""
        with self._lock:
            b = self._buckets.get(task_class)
            if b is None or want <= 0:
                return []
            n = min(want, len(b))
            if self._prio:
                out = [heappop(b)[2] for _ in range(n)]
            elif self._fifo:
                out = [b.popleft()[2] for _ in range(n)]
            else:
                out = [b.pop()[2] for _ in range(n)]
            if not b:
                del self._buckets[task_class]
            self._len -= n
            return out


class HBBuffer:
    def __init__(self, capacity: int,
                 parent_push: Callable[[list[Any], int], None]) -> None:
        if capacity <= 0:
            raise ValueError("capacity must be positive")
        self.capacity = capacity
        self._parent_push = parent_push
        self._items: list[Any] = []
        self._lock = threading.Lock()

    def __len__(self) -> int:
        return len(self._items)

    def push_all(self, items: list[Any], distance: int = 0) -> None:
        """Push as many as fit; spill the rest (lowest priority first kept
        local? no — reference keeps the *head* local and spills the tail)."""
        overflow: list[Any] = []
        with self._lock:
            room = self.capacity - len(self._items)
            if room >= len(items):
                self._items.extend(items)
            else:
                if room > 0:
                    self._items.extend(items[:room])
                overflow = items[room:]
        if overflow:
            self._parent_push(overflow, distance + 1)

    def try_pop_best(self, priority: Callable[[Any], float] | None = None
                     ) -> Any | None:
        with self._lock:
            if not self._items:
                return None
            if priority is None:
                return self._items.pop()
            best_i = max(range(len(self._items)),
                         key=lambda i: priority(self._items[i]))
            return self._items.pop(best_i)

    def steal(self) -> Any | None:
        """Victim-side pop from the *oldest* end (work-stealing fairness)."""
        with self._lock:
            if not self._items:
                return None
            return self._items.pop(0)
