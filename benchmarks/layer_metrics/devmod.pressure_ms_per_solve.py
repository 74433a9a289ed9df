"""Host time a solve spends because of the HBM budget: confirming its oldest
dispatches early and queueing evictions so that the next stage-in or dispatch
fits (``devmod.pressure``, self time from the program's phase table; the wait
itself is ``devmod.inflight_wait``'s).  Zero where the budget never pressed;
nothing where the program has no such accounting."""

from phases import self_seconds


def read(run: dict) -> float | None:
    from parsec_tpu.device import registry
    table = self_seconds()
    solves = run["window"].solves
    if not table or not solves or not any(
            hasattr(d, "pressure_confirms") for d in registry.devices):
        return None
    return 1e3 * table.get("devmod.pressure", 0.0) / solves
