"""The pivoting panel's share of its roofline: the window's PANEL tasks at
the least time each could take, max(FLOPs / peak FLOP/s, bytes / peak
bytes/s) with a panel of m rows counted m nb^2 - nb^3 / 3 FLOP and 2 m nb 4
bytes (``problems/getrf_tiled.py:least_seconds``), over the device seconds
of the operations of the class's programs (``jit_fused_getrf_panel``) in
the trace's ``device_ops``.  Those are the window's ten longest operations
only (``trace_reduce.reduce``): where the list is full, an operation of the
class may have been dropped from it, so the busy time the ten do not account
for is counted as the class's.  The share is then never overstated, and
understated by that time at most (``kernel.tsmqr_roofline`` reads nothing
there instead: on this cell's first traced run the unaccounted 0.18 s were
7.5% of the class's seconds, and it would read nothing on every run: PERF.md,
PR 42).  Nothing where none of the class's operations is listed or the
problem has no such class."""

PROGRAMS = ("jit_fused_getrf_panel/",)
CLASSES = ("PANEL",)
KEPT = 10           # trace_reduce.reduce keeps this many operations


def read(run: dict) -> float | None:
    tr, peaks, prob = run["trace"], run["peaks"], run["problem"]
    if not tr or not peaks or not hasattr(prob, "least_seconds"):
        return None
    ops = tr["device_ops"]
    seconds = sum(s for name, s in ops if name.startswith(PROGRAMS))
    solves = run["window"].solves
    if not seconds or not solves:
        return None
    if len(ops) >= KEPT:
        # what the ten leave unaccounted may be the class's: counted as its
        seconds += max(tr["busy_s"] - sum(s for _, s in ops), 0.0)
    least = solves * prob.least_seconds(CLASSES, peaks)
    return 100.0 * least / run["cell"].chips / seconds
