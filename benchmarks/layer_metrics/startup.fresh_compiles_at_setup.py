"""Programs compiled afresh during set-up (the copied CompileMeter): 0 on a
warm compile cache, the number of programs the cell needs on a cold one."""


def read(run: dict) -> float | None:
    return float(run["window"].compiles_setup["fresh"])
