"""The share of the window in which the host waited for the chip and not the
chip for the host: blocked in ``block_until_ready`` on the oldest dispatch
(``devmod.inflight_wait``, from the in-flight bound and from ``sync``), over
the window's wall."""

from phases import self_seconds


def read(run: dict) -> float | None:
    table = self_seconds()
    if not table:
        return None
    return 100.0 * table.get("devmod.inflight_wait", 0.0) \
        / run["window"].wall_s
