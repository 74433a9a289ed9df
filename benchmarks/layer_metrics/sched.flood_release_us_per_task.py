"""What a task costs the host scheduler: the manager's pull of ready tasks
into a batch (``sched.flood``: select, best_device, prepare_input) and the
release of its dependencies at completion (``sched.release``), self times
from the program's phase table, over the tasks the accelerators ran."""

from phases import self_seconds


def read(run: dict) -> float | None:
    table = self_seconds()
    tasks = run["window"].counters["executed_tasks"]
    if not table or not tasks:
        return None
    return 1e6 * (table.get("sched.flood", 0.0)
                  + table.get("sched.release", 0.0)) / tasks
