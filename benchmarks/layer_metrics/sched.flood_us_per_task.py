"""What pulling a ready task into a device batch costs the host: the self
time of the manager's ``sched.flood`` span (the scheduler's pops of the
batch's class, ``best_device``, ``prepare_input`` and the device task) from
the program's phase table, over the tasks the accelerators ran in the
window.  The one row alone, so that every dynamic cell can report it; nothing
where the program has no phase plane or the window ran no task."""

from phases import self_seconds


def read(run: dict) -> float | None:
    table = self_seconds()
    tasks = run["window"].counters["executed_tasks"]
    if "sched.flood" not in table or not tasks:
        return None
    return 1e6 * table["sched.flood"] / tasks
