"""Device chore hooks: route task bodies to device modules.

The analog of the generated GPU hook (``jdf_generate_code_hook_gpu``,
``jdf2c.c:6566-6925``): a device chore resolves the best device of its type
(``parsec_get_best_device``), wraps the task into a device task descriptor and
hands it to the device's kernel scheduler.  Synchronous fallback: when the
device module has no async manager (or the device is the host), the body runs
inline and the hook returns DONE.
"""

from __future__ import annotations

from types import SimpleNamespace
from typing import Any, Callable

from ..runtime.task import (HOOK_RETURN_DONE, HOOK_RETURN_NEXT)


def make_device_hook(device_type: str, body: Callable | None,
                     dyld: str | None, ptg: Any = None) -> Callable:
    def hook(es: Any, task: Any) -> int:
        dev = es.context.best_device(task, device_type)
        if dev is None:
            return HOOK_RETURN_NEXT  # no such device: fall through to next chore
        submit = body
        if submit is None and dyld is not None:
            from .kernels import find_incarnation
            submit = find_incarnation(dyld, dev)
            if submit is None:
                dev.release_task(task)
                return HOOK_RETURN_NEXT
        sched = getattr(dev, "kernel_scheduler", None)
        if sched is not None:
            return sched(es, task, submit)
        # synchronous fallback path
        if ptg is not None:
            g = SimpleNamespace(**ptg.globals)
            l = SimpleNamespace(**task.locals)
            rc = submit(es, task, g, l)
        else:
            rc = submit(es, task)
        dev.release_task(task)
        dev.note_executed()
        return HOOK_RETURN_DONE if rc is None else rc

    return hook
