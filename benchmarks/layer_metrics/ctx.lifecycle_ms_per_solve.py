"""What the ``Context`` a solve builds and tears down costs: self times of
``ctx.init`` (device registration, scheduler, recorders),
``ctx.add_taskpool`` (termdet, the DAG-compile probe, start-up enumeration,
the first schedule) and ``ctx.fini``, from the program's phase table."""

from phases import self_seconds


def read(run: dict) -> float | None:
    table = self_seconds()
    solves = run["window"].solves
    if not table or not solves:
        return None
    return 1e3 * sum(table.get(k, 0.0) for k in
                     ("ctx.init", "ctx.add_taskpool", "ctx.fini")) / solves
