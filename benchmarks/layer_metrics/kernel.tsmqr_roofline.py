"""The TSMQR kernel's share of the chip's peak: the window's TSMQR tasks times
LAPACK's count for one (4 nb^3: ``problems/geqrf_tiled.py:class_flops``) over
the peak FLOP/s, over the device seconds of the operations of the class's
programs (``jit_fused_qr_tsmqr``, and ``jit_qr_tsmqr`` for a batch of one)
in the trace's ``device_ops``.  Those are the window's ten longest operations
only (``trace_reduce.reduce``): where the list is full, an operation of the
class may have been dropped from it, and what was dropped is within the busy
time the ten do not account for (``busy_s`` less their sum).  A share is
reported only where that is under a twentieth of the class's seconds, so it
is overstated by at most 5% of itself; else, and where none of the class's
operations is listed or the problem has no such class, this reads nothing.
While the kernel is three dense f32 products (6 nb^3 at six bf16 passes) it
cannot pass 4 / 36 = 11%."""

PROGRAMS = ("jit_fused_qr_tsmqr/", "jit_qr_tsmqr/")
KEPT = 10           # trace_reduce.reduce keeps this many operations
UNSEEN = 0.05       # of the class's seconds, at most


def read(run: dict) -> float | None:
    tr, peaks = run["trace"], run["peaks"]
    flops = getattr(run["problem"], "class_flops", {}).get("TSMQR")
    if not tr or not peaks or not flops:
        return None
    ops = tr["device_ops"]
    seconds = sum(s for name, s in ops if name.startswith(PROGRAMS))
    solves = run["window"].solves
    if not seconds or not solves:
        return None
    if len(ops) >= KEPT \
            and tr["busy_s"] - sum(s for _, s in ops) > UNSEEN * seconds:
        return None
    least = solves * flops / peaks["flops_per_s"]
    return 100.0 * least / run["cell"].chips / seconds
