"""Host microseconds inside the jitted call (the span ``devmod.call``: the
fused program's call alone, or the body of a task submitted alone) per array
the call handed back: the window's ``call_ns`` over its ``results`` (lanes x
written flows a call), summed over the rows of the device module's call
table.  Per result because that is what the records pointed to (PERF.md,
PR 37, step 0: 64 more arguments cost nothing, the time followed the
results); the rows hold ``args`` (lanes x flows: the buffers handed in) beside
it, and PERF.md quotes both ratios for every class, so the per-argument
reading is struck or kept on evidence.  The results of a fused call count
its pad lanes too (a batch of B tasks is padded to the next power of two,
and the call allocates a result for every lane): a row's ``tasks`` beside
``calls`` x lanes says how many of them no task asked for (a quarter on the
16k Cholesky's TRSM and SYRK, a fifth on the QR's TSMQR: PERF.md, PR 38).
Nothing on a program without the table or in a window that made no call."""

from call_table import rows, total


def read(run: dict) -> float | None:
    table = rows()
    if not table or not total(table, "results"):
        return None
    return total(table, "call_ns") / 1e3 / total(table, "results")
