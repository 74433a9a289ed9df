"""Of the arrays the window's dispatches handed back (``results``: lanes x
written flows a call, pad lanes included, and the one to three of a task
submitted alone), the share that took the buffer of the version it
supersedes, so that the call allocated no output for it
(``results_donated``: a fused call all of whose written tiles the device
module alone held donates them to its program, and its pad lanes write to a
recycled scratch pool): ``results_donated / results`` over the window's rows
of the call table, in percent.  What is left is what the host still pays
libtpu an allocation for before the launch: the per-task bodies, a written
flow whose result is another shape than its input, and any fused call in
which someone else kept a tile's array (a host copy after a memory edge, a
registered send, a pushed-out tile written again).  Nothing on a program
whose rows lack the field (the parent of PR 39) or without the table."""

from call_table import rows, total


def read(run: dict) -> float | None:
    table = rows()
    if not table or not total(table, "results") \
            or any("results_donated" not in row for row in table):
        return None
    return 100.0 * total(table, "results_donated") / total(table, "results")
