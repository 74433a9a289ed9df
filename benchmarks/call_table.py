"""The device module's call table (``parsec_tpu/device/tpu.py``, PR 38): one
row a (task class, lanes) of what the window's dispatches handed to their
calls and how long each call kept the host, filled by the program only while
its phase plane is on, so a ``--trace 1`` window's rows hold exactly that
window.  Three per-layer metrics read it
(``layer_metrics/devmod.call_us_per_result``, ``devmod.chip_queue_depth``,
``devmod.held_already_run_share``; ``devmod.dispatch_own_us_per_task`` reads
the phase table beside it).  A program without the table (the parent of
PR 38), or a window in which the plane never came on, reads as nothing and
the metric is left out of the line."""

from __future__ import annotations

KEY = ("task_class", "lanes")       # of a row; every other field is a count


def rows() -> list[dict] | None:
    """The rows of ``debug_state()["call_table"]``, summed over the
    accelerators by (task class, lanes); None where there is nothing to
    read."""
    from parsec_tpu.device import registry
    out: dict[tuple, dict] = {}
    for d in registry.devices:
        if not hasattr(d, "call_table"):
            continue
        for row in d.debug_state()["call_table"]:
            mine = out.setdefault(tuple(row[k] for k in KEY), {})
            for k, v in row.items():
                mine[k] = v if k in KEY else mine.get(k, 0) + v
    return list(out.values()) or None


def total(table: list[dict], field: str) -> int:
    return sum(row[field] for row in table)
