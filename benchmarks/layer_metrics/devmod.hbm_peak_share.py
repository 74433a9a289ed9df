"""The fullest chip's peak of device memory over the run
(``peak_bytes_in_use``, as ``Window.end`` read it) as a share of what the
chip's allocator may hand out (``bytes_limit``): how near the run came to the
edge the device module's budget keeps it from.  A backend that keeps no such
statistic (the CPU of a rehearsal) reads as nothing."""


def read(run: dict) -> float | None:
    import jax
    peak = run["window"].memory_peak_bytes
    limits = [(d.memory_stats() or {}).get("bytes_limit", 0)
              for d in jax.local_devices()]
    if not peak or not all(limits):
        return None
    return 100.0 * peak / min(limits)
