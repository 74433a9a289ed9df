"""Host time per task outside the device module: the wall of add_taskpool
and wait, less the device module's own phase walls (stage-in, dispatch,
complete, drain), over the tasks the accelerators ran."""


def read(run: dict) -> float | None:
    win = run["window"]
    c = win.counters
    if not c["executed_tasks"]:
        return None
    host = win.spans["add_taskpool"] + win.spans["wait"]
    phases = c["t_stage_in"] + c["t_dispatch"] + c["t_complete"] + c["t_drain"]
    return 1e6 * (host - phases) / c["executed_tasks"]
