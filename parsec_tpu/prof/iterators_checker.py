"""iterators_checker: validate successor iterators against the dep graph.

Rebuild of ``mca/pins/iterators_checker`` (SURVEY §2.4): after every task
executes, walk its ``iterate_successors`` output and check each claimed
edge is *consistent* — the successor class exists in the taskpool, the
target flow exists, and the successor's input deps contain a matching
active arrow pointing back at this class.  A PTG whose out-arrows and
in-arrows disagree (the classic hand-written-JDF bug) surfaces here as a
hard error at the first executed task instead of a hang at the dep table.

Folded into the analysis subsystem (ISSUE 5): :mod:`parsec_tpu.analysis`
re-exports :func:`check_task` / :class:`IteratorsCheckerError`, and
``analysis.graphcheck``'s forward edge-symmetry walk is this checker's
*static* twin over the whole execution space — run that in CI, keep this
PINS module for per-execution validation of dynamic/UD-keyed pools the
static enumeration cannot cover (``--mca pins iterators_checker``).
"""

from __future__ import annotations

from typing import Any

from ..core.mca import Component, component
from . import pins
from .pins import PinsEvent


class IteratorsCheckerError(AssertionError):
    pass


def check_task(task: Any) -> int:
    """Walk one task's successor iterator; returns edges checked."""
    from ..runtime.scheduling import _find_input_dep
    tc = task.task_class
    tp = task.taskpool
    count = 0

    def visitor(t, flow, dep) -> None:
        nonlocal count
        if dep.target_class is None:
            return
        if dep.target_class not in tp.task_classes_by_name:
            raise IteratorsCheckerError(
                f"{t}: out-arrow names unknown class {dep.target_class!r}")
        succ_tc = tp.task_class(dep.target_class)
        for succ_locals in dep.each_target(t.locals):
            try:
                _find_input_dep(succ_tc, dep.flow_name(t.locals),
                                tc.name, succ_locals, t.locals)
            except (KeyError, LookupError) as e:
                raise IteratorsCheckerError(
                    f"{t}: arrow to {dep.target_class}({succ_locals})."
                    f"{dep.flow_name(t.locals)} has no matching active input dep "
                    f"({e})") from e
            count += 1

    tc.iterate_successors(task, visitor)
    return count


class IteratorsCheckerModule:
    def __init__(self) -> None:
        self._cb = None
        self.checked_edges = 0

    def install(self) -> None:
        def cb(es, task):
            if task is not None and hasattr(task, "task_class"):
                self.checked_edges += check_task(task)
        self._cb = cb
        pins.register(PinsEvent.EXEC_END, cb)

    def uninstall(self) -> None:
        if self._cb is not None:
            pins.unregister(PinsEvent.EXEC_END, self._cb)
            self._cb = None


@component
class IteratorsCheckerComponent(Component):
    type_name = "pins"
    name = "iterators_checker"
    priority = 0

    def open(self, context: Any = None) -> IteratorsCheckerModule:
        mod = IteratorsCheckerModule()
        mod.install()
        return mod

    def close(self, module: IteratorsCheckerModule) -> None:
        module.uninstall()
