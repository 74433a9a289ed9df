"""Of the tasks inserted, the share that had completed when ``wait()``
closed the insertion (``dtd_tasks_in_window / dtd_inserted``, the process's
totals, warm-up solves included): how much of the execution ran under
discovery, from the inserter's window drives, and not after it.  0 where the
window never fills; it repeats exactly on a client that is inserter and only
worker.  A program without the counters reads as nothing."""


def read(run: dict) -> float | None:
    from parsec_tpu.dtd import insert
    t = getattr(insert, "dtd_totals", None)
    if not t or not t["dtd_inserted"]:
        return None
    return 100.0 * t["dtd_tasks_in_window"] / t["dtd_inserted"]
