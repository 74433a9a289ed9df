"""The device module's own Python around the call, per task: the self time of
``devmod.dispatch`` (since PR 38 the gather alone: eligibility, the flat
arguments, the program's lookup, the budget less ``devmod.pressure``) plus
the self time of ``devmod.land`` (results into the copies, versions, counts,
the in-flight ring less ``devmod.inflight_wait``), over the window's
``executed_tasks``.  What the call itself costs is ``devmod.call``'s and not
in it.  Nothing on a program without ``devmod.land`` (the parent of PR 38:
its ``devmod.dispatch`` holds the call too)."""

from phases import self_seconds


def read(run: dict) -> float | None:
    table = self_seconds()
    tasks = run["window"].counters["executed_tasks"]
    if "devmod.land" not in table or "devmod.dispatch" not in table \
            or not tasks:
        return None
    return 1e6 * (table["devmod.dispatch"] + table["devmod.land"]) / tasks
