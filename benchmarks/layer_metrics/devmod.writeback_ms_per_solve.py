"""Host time a solve spends writing tiles back: the flush at its end
(``devmod.writeback``) and the eviction drains between batches
(``devmod.drain``), self times from the program's phase table."""

from phases import self_seconds


def read(run: dict) -> float | None:
    table = self_seconds()
    solves = run["window"].solves
    if not table or not solves:
        return None
    return 1e3 * (table.get("devmod.writeback", 0.0)
                  + table.get("devmod.drain", 0.0)) / solves
