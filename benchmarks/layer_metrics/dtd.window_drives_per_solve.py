"""How often a solve's inserter found the window full and executed until
the threshold (``dtd_window_drives``, the process's totals over the pools
that have terminated, warm-up solves included: they insert the same program),
over those solves, counted as the tasks inserted over the tasks of one
solve.  It repeats exactly: 2 where 4,096 tasks go through a window of 2,048
/ 1,024.  A program without the counters reads as nothing."""


def read(run: dict) -> float | None:
    from parsec_tpu.dtd import insert
    t = getattr(insert, "dtd_totals", None)
    if not t or not t["dtd_inserted"]:
        return None
    return t["dtd_window_drives"] * run["problem"].tasks / t["dtd_inserted"]
