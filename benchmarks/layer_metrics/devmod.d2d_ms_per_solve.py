"""Host time a solve spends starting copies from one accelerator to another:
the self time of ``devmod.d2d`` (under ``devmod.stage_in``: the
``jax.device_put`` of the misses whose newest copy is another chip's array,
one call a batch) from the program's phase table, over the window's solves.
Nothing where the table has no such span: a program before PR 40, or a window
in which no tile changed chip."""

from phases import self_seconds


def read(run: dict) -> float | None:
    table = self_seconds()
    solves = run["window"].solves
    if "devmod.d2d" not in table or not solves:
        return None
    return 1e3 * table["devmod.d2d"] / solves
