"""Tasks of the hierarchical QR's TT classes (TTQRT, TTMQR: the kills of
one domain head by another and their updates) per XLA call that ran them:
the device module's counters ``tasks_by_class / calls_by_class`` summed
over the accelerators.  Above 1.0 means the kills of a tree level, or their
updates, shared a call; at 1.0 each paid a dispatch of its own.  The
process's totals, warm-up solves included: they run the same graph.
Nothing where the program has no such counters or ran no such class."""

TT = ("TTQRT", "TTMQR")


def read(run: dict) -> float | None:
    from parsec_tpu.device import registry
    accel = [d for d in registry.devices
             if d.type != "cpu" and hasattr(d, "calls_by_class")]
    calls = sum(d.calls_by_class.get(c, 0) for d in accel for c in TT)
    if not calls:
        return None
    return sum(d.tasks_by_class.get(c, 0)
               for d in accel for c in TT) / calls
