"""The swap kernels' share of their roofline: the window's SWPTRSM and
SWPLEFT tasks at the least time each could take, max(FLOPs / peak FLOP/s,
bytes / peak bytes/s), the bytes the rows IPIV(k) moves, at most nb pairs
read and written (16 nb^2 a task), the FLOPs SWPTRSM's triangular solve,
nb^3 (``problems/getrf_tiled.py:least_seconds``), over the device seconds
of the operations of the classes' programs (``jit_fused_getrf_swptrsm``,
``jit_fused_getrf_swpleft``) in the trace's ``device_ops``, with the busy
time the ten kept operations leave unaccounted counted as the classes'
where the list is full (``kernel.lu_panel_roofline``'s rule: never
overstated); nothing where none of their operations is listed or the
problem has no such classes.  A kernel that rewrites whole tiles reads far
under 100: the least bytes are the rows that move."""

PROGRAMS = ("jit_fused_getrf_swptrsm/", "jit_fused_getrf_swpleft/")
CLASSES = ("SWPTRSM", "SWPLEFT")
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
