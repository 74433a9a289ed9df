"""Of the bytes the in-flight ring charged the HBM budget at the moment of
each dispatch (``_held_bytes``: superseded versions and pad lanes of the
dispatches the *host* has not confirmed), the share whose dispatch the *chip*
had already run (its result was ready): ``held_run_bytes_sum /
held_bytes_sum`` over the window's rows of the call table, in percent.  Those
bytes are free on the chip, or will be as soon as the host lets go, and the
budget still counts them: near 100 means ``devmod.pressure`` confirms early
for nothing.  Nothing on a program without the table or where the ring never
held a byte."""

from call_table import rows, total


def read(run: dict) -> float | None:
    table = rows()
    if not table or not total(table, "held_bytes_sum"):
        return None
    return 100.0 * total(table, "held_run_bytes_sum") \
        / total(table, "held_bytes_sum")
