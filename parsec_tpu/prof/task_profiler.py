"""task_profiler: the PINS module bridging runtime events into the trace.

Rebuild of ``mca/pins/task_profiler`` (SURVEY §2.4): registers on the PINS
callback chain and writes begin/end trace events for task execution,
prepare-input, scheduling and release phases, with task coordinates as the
per-event info payload (the reference packs task locals into the profiling
info struct, ``parsec_internal.h:534-546``).
"""

from __future__ import annotations

from typing import Any

from ..core.mca import Component, component
from . import pins
from .pins import PinsEvent
from .profiling import profiling


class TaskProfilerModule:
    """Install/uninstall the event bridge (one instance per enable)."""

    PHASES = {
        "exec": (PinsEvent.EXEC_BEGIN, PinsEvent.EXEC_END),
        "prepare_input": (PinsEvent.PREPARE_INPUT_BEGIN,
                          PinsEvent.PREPARE_INPUT_END),
        "release_deps": (PinsEvent.RELEASE_DEPS_BEGIN,
                         PinsEvent.RELEASE_DEPS_END),
        "complete": (PinsEvent.COMPLETE_EXEC_BEGIN,
                     PinsEvent.COMPLETE_EXEC_END),
    }

    def __init__(self) -> None:
        self._keys: dict[str, tuple[int, int]] = {}
        self._cbs: list[tuple[PinsEvent, Any]] = []

    def install(self) -> None:
        colors = {"exec": "#00ff00", "prepare_input": "#8888ff",
                  "release_deps": "#ff8800", "complete": "#888888"}
        for phase, (b, e) in self.PHASES.items():
            self._keys[phase] = profiling.add_dictionary_keyword(
                f"task_{phase}", colors[phase],
                ("task", "key", "taskpool"))

            def mk(phase, start):
                key_pair = self._keys[phase]

                def cb(es, task):
                    if task is None:
                        return
                    t = task[0] if isinstance(task, list) and task else task
                    tc = getattr(t, "task_class", None)
                    info = None
                    if start and tc is not None:
                        info = {"task": tc.name,
                                "key": str(getattr(t, "key", "")),
                                "taskpool": t.taskpool.name}
                    profiling.trace(key_pair[0 if start else 1],
                                    event_id=getattr(t, "uid", 0),
                                    object_id=id(t), info=info)
                return cb

            for start, event in ((True, b), (False, e)):
                cb = mk(phase, start)
                pins.register(event, cb)
                self._cbs.append((event, cb))

    def uninstall(self) -> None:
        for event, cb in self._cbs:
            pins.unregister(event, cb)
        self._cbs.clear()


@component
class TaskProfilerComponent(Component):
    type_name = "pins"
    name = "task_profiler"
    priority = 10

    def query(self, context: Any = None) -> bool:
        return False   # explicit request only (--mca pins task_profiler)

    def open(self, context: Any = None) -> TaskProfilerModule:
        m = TaskProfilerModule()
        m.install()
        return m

    def close(self, module: TaskProfilerModule) -> None:
        module.uninstall()
