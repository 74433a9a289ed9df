"""Tasks of the pivoted LU's swap classes (SWPTRSM, SWPLEFT: a step's swaps
of the columns right and left of its panel) per XLA call that ran them: the
device module's counters ``tasks_by_class / calls_by_class`` summed over the
accelerators.  1.0 means every swap paid a dispatch of its own; a step has
NT - 1 of them.  The process's totals, warm-up solves included: they run
the same graph.  Nothing where the program has no such counters or ran no
such class."""

SWAPS = ("SWPTRSM", "SWPLEFT")


def read(run: dict) -> float | None:
    from parsec_tpu.device import registry
    accel = [d for d in registry.devices
             if d.type != "cpu" and hasattr(d, "calls_by_class")]
    calls = sum(d.calls_by_class.get(c, 0) for d in accel for c in SWAPS)
    if not calls:
        return None
    return sum(d.tasks_by_class.get(c, 0)
               for d in accel for c in SWAPS) / calls
