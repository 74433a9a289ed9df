"""Of the tasks the accelerators ran, the share of the one that ran most:
the counter ``executed_tasks``, the process's totals (warm-up solves
included: the same graph).  25 is even over four accelerators, 100 is one
accelerator alone.  Counts tasks, not their time (``device.busy_balance``
reads that from the trace).  Nothing where no accelerator ran a task."""


def read(run: dict) -> float | None:
    from parsec_tpu.device import registry
    ran = [d.executed_tasks for d in registry.devices if d.type != "cpu"]
    if not sum(ran):
        return None
    return 100.0 * max(ran) / sum(ran)
