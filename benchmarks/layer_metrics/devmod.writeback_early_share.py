"""Of the dirty tiles the device module wrote back, the share whose
device-to-host transfer a push-out at the graph's memory edge had started on
the very array read (``writebacks_early / writebacks``, summed over the
accelerators): how often the mechanism engages, not how far a transfer had
got.  The process's totals, warm-up solves included: they run the same
graph.  A program without the counters reads as nothing."""


def read(run: dict) -> float | None:
    from parsec_tpu.device import registry
    accel = [d for d in registry.devices if d.type != "cpu"]
    total = sum(getattr(d, "writebacks", 0) for d in accel)
    if not total:
        return None
    return 100.0 * sum(d.writebacks_early for d in accel) / total
