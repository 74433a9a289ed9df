"""Of the enqueues that found an accelerator's in-flight ring past its count
(``device_tpu_max_inflight``, 32), the share that did not wait for the
oldest dispatch because another accelerator of the context had run all it
was given and nobody was feeding it (PR 41: the thread that would wait is
the one that could feed it): ``ring_excused / (ring_excused +
ring_bounded)`` summed over the accelerators, the process's totals (warm-up
solves included: the same graph), in percent.  0: the count held at every
such enqueue, as it does with one accelerator; 100: the byte budget alone
bounded the rings.  Nothing where no enqueue met the count, or on a program
without the counters (the parent of PR 41)."""


def read(run: dict) -> float | None:
    from parsec_tpu.device import registry
    accel = [d for d in registry.devices if d.type != "cpu"]
    excused = sum(getattr(d, "ring_excused", 0) for d in accel)
    bounded = sum(getattr(d, "ring_bounded", 0) for d in accel)
    if not excused + bounded:
        return None
    return 100.0 * excused / (excused + bounded)
