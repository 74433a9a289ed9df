"""Ready tasks the device module's flood popped from the scheduler only to
hand them back (another class than the batch's, or another accelerator's),
per task run: the counters ``flood_putbacks / executed_tasks`` summed over
the accelerators.  Zero where the scheduler's ready queue is keyed by task
class; about 21 on the 64-panel Cholesky where every batch drained the queue.
The process's totals, warm-up solves included: they run the same graph.  A
program without the counter reads as nothing."""


def read(run: dict) -> float | None:
    from parsec_tpu.device import registry
    accel = [d for d in registry.devices
             if d.type != "cpu" and hasattr(d, "flood_putbacks")]
    tasks = sum(d.executed_tasks for d in accel)
    if not tasks:
        return None
    return sum(d.flood_putbacks for d in accel) / tasks
