"""The most bytes that only unconfirmed dispatches kept alive on one chip:
tile versions a later dispatch superseded and padding lanes of the fused
programs (the device module's counter ``inflight_held_bytes_peak``, the
largest over the accelerators).
The process's peak, warm-up solves included: they run the same graph.  A
program without the counter reads as nothing."""


def read(run: dict) -> float | None:
    from parsec_tpu.device import registry
    peaks = [d.inflight_held_bytes_peak for d in registry.devices
             if hasattr(d, "inflight_held_bytes_peak")]
    return max(peaks) / 1e9 if peaks else None
