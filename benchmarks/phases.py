"""The program's own phase table (``parsec_tpu/prof/spans.py``, PR 27): self
time per named span of a dynamic solve, accumulated in the program while a
profiler session is open, so a ``--trace 1`` window holds exactly its own
solves.  Five per-layer metrics read it (``layer_metrics/``).  A program
without the plane (the parent of PR 27), or a window in which it never came
on, reads as an empty table and the metric is left out of the line."""

from __future__ import annotations


def self_seconds() -> dict[str, float]:
    """``name -> self seconds`` over the traced window; {} where there is
    nothing to read."""
    from parsec_tpu.prof import spans
    totals = getattr(spans, "phase_totals", None)
    return {k: v[0] / 1e9 for k, v in totals().items()} if totals else {}

