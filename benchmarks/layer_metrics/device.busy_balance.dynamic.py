"""The mean chip's busy time over the busiest chip's, in percent, from the
reduced device trace (``trace_reduce.reduce``: ``busy_s`` is the mean over
the cell's chips, ``busiest_busy_s`` the largest): 100 is even, 25 is one
chip of four working alone.  Nothing without a device trace (an untraced
run, a rehearsal)."""


def read(run: dict) -> float | None:
    tr = run["trace"]
    if not tr or not run["peaks"] or not tr["busiest_busy_s"]:
        return None
    return 100.0 * tr["busy_s"] / tr["busiest_busy_s"]
