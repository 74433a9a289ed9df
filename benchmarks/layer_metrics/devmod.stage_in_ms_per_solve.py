"""Host time a solve spends staging tiles in: the self time of
``devmod.stage_in`` (the walk that parts hits from misses, the host-to-device
copy of every miss, one call a tile, and the landing) from the program's phase
table, over the window's solves.  Of a 4 MiB miss's time 165-190 us are
jaxlib's call, outside the interpreter, and the rest is Python (PERF.md,
section 5)."""

from phases import self_seconds


def read(run: dict) -> float | None:
    table = self_seconds()
    solves = run["window"].solves
    if "devmod.stage_in" not in table or not solves:
        return None
    return 1e3 * table["devmod.stage_in"] / solves
