"""Tasks of the QR's panel classes (GEQRT, TSQRT: a serial chain down each
panel) per XLA call that ran them: the device module's counters
``tasks_by_class / calls_by_class`` summed over the accelerators.  1.0 means
every link of the chain paid a dispatch of its own; more, that the flood
found links of several panels ready at once.  The process's totals, warm-up
solves included: they run the same graph.  Nothing where the program has no
such counters or ran no such class."""

PANEL = ("GEQRT", "TSQRT")


def read(run: dict) -> float | None:
    from parsec_tpu.device import registry
    accel = [d for d in registry.devices
             if d.type != "cpu" and hasattr(d, "calls_by_class")]
    calls = sum(d.calls_by_class.get(c, 0) for d in accel for c in PANEL)
    if not calls:
        return None
    return sum(d.tasks_by_class.get(c, 0)
               for d in accel for c in PANEL) / calls
