"""Scheduler component interface.

Rebuild of ``parsec/mca/sched/sched.h:183-353``: a scheduler module exposes
``install / flow_init / schedule / select / remove``, and ``select_class``
for the device module's batches.  The *distance* contract
(``sched.h:22-170``) is preserved: ``schedule(es, tasks, distance)`` hints how
far from the submitting stream the tasks should land (0 = hot, larger = was
rescheduled / overflowed), and ``select`` returns the distance the task came
from so starvation pushes work outward fairly.
"""

from __future__ import annotations

from typing import Any, Sequence


class SchedulerModule:
    name = "base"

    def install(self, context: Any) -> None:
        """Global structures; called once per context."""

    def flow_init(self, es: Any) -> None:
        """Per-execution-stream structures; called from each worker before
        the barrier opens (cf. ``flow_init`` rendezvous)."""

    def schedule(self, es: Any, tasks: Sequence[Any], distance: int = 0) -> None:
        raise NotImplementedError

    def select(self, es: Any) -> tuple[Any | None, int]:
        """Return (task, distance) or (None, 0).

        Distance contract: 0 = the stream's own queue; 1..98 = pulled from
        another stream's queue, topologically-near first (a *steal* — the
        SELECT_STEAL PINS feed); 99 = the shared system queue (externally
        submitted work; starvation relief, not a steal)."""
        raise NotImplementedError

    def select_class(self, es: Any, task_class: Any, want: int
                     ) -> tuple[list[tuple[Any, int]], int]:
        """Take up to ``want`` ready tasks of ``task_class`` for a device
        batch: ``([(task, distance), ...], put_back)``, where ``put_back``
        counts the tasks of other classes that were popped and handed back.

        The default serves any module through its own contract: ``select``
        until ``want`` are found or the module is empty, then ``schedule``
        every other task again at the distance it came from.  A module whose
        store is keyed by class (lfq) overrides it and puts nothing back."""
        taken: list[tuple[Any, int]] = []
        stash: list[tuple[Any, int]] = []
        while len(taken) < want:
            t, distance = self.select(es)
            if t is None:
                break
            (taken if t.task_class is task_class else stash).append(
                (t, distance))
        for t, distance in stash:
            self.schedule(es, [t], distance)
        return taken, len(stash)

    def remove(self, context: Any) -> None:
        """Tear down; must leave no queued tasks behind."""

    def pending_tasks(self, context: Any) -> int:
        """Approximate queue depth (PAPI-SDE counter analog)."""
        return -1

    def queue_depths(self, context: Any) -> dict[str, int]:
        """Best-effort per-queue depth map for diagnostics (the flight
        recorder's stall dump).  Modules with per-stream queues override
        this; the base reports the shared total only."""
        return {"shared": self.pending_tasks(context)}
