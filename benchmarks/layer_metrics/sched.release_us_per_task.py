"""What a task's completion costs the host scheduler: the self time of the
``sched.release`` counter (one ``complete_execution`` a task: the walk of the
class's release plan, the successors' trackers, the tasks made ready and
handed to the scheduler, the consumed repo entries; what ``devmod.pushout``
owns inside it taken off) from the program's phase table, over the tasks the
accelerators ran in the window.  The twin of ``sched.flood_us_per_task``: the
one row alone, on every dynamic cell; nothing where the program has no phase
plane or the window ran no task."""

from phases import self_seconds


def read(run: dict) -> float | None:
    table = self_seconds()
    tasks = run["window"].counters["executed_tasks"]
    if "sched.release" not in table or not tasks:
        return None
    return 1e6 * table["sched.release"] / tasks
