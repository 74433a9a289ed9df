"""What discovering a task costs the DTD front end: the self time of the
``dtd.insert`` counter (one ``insert_task`` a task: the argument specs, the
task class, the ``DTDTask``, one walk of each tile's accessor chain, the
in-flight count, the ready task handed to the scheduler; a window drive that
follows is ``dtd.window``'s and not in it) from the program's phase table,
over the tasks the accelerators ran in the window, which are the tasks
inserted in it.  The DTD twin of ``sched.release_us_per_task``'s share of a
PTG; nothing where the program has no such counter (the parent of PR 34)."""

from phases import self_seconds


def read(run: dict) -> float | None:
    table = self_seconds()
    tasks = run["window"].counters["executed_tasks"]
    if "dtd.insert" not in table or not tasks:
        return None
    return 1e6 * table["dtd.insert"] / tasks
