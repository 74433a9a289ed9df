"""Programs the chip still owed when the next was enqueued, on average: at
every dispatch the device module counts the entries of its in-flight ring
whose result is not ready yet (``jax.Array.is_ready()``, which does not
block), and this is ``depth_sum / calls`` over the window's rows of the call
table.  Near 0: the chip had nothing to do when the host came, the host
starves it; near the ring's length (``device_tpu_max_inflight``, 32): the
chip bounds the solve.  Nothing on a program without the table."""

from call_table import rows, total


def read(run: dict) -> float | None:
    table = rows()
    if not table or not total(table, "calls"):
        return None
    return total(table, "depth_sum") / total(table, "calls")
