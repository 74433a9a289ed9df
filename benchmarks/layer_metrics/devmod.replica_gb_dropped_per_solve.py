"""Bytes of clean copies the HBM LRU dropped before the flush, per solve: a
tile another holder (the host, or the chip that wrote it) still has at the
same version, so that it left without a device-to-host copy and is not in
``devmod.evicted_gb_per_solve``.  The counter ``replica_bytes_dropped``
summed over the accelerators, over the process's solves, warm-up included
(the same graph).  A tile dropped and needed again is staged again, and then
shows in ``devmod.h2d_gb_per_solve`` or ``devmod.d2d_gb_per_solve``.  Nothing
where the program has no such counter."""


def read(run: dict) -> float | None:
    from parsec_tpu.device import registry
    counted = [d.replica_bytes_dropped for d in registry.devices
               if hasattr(d, "replica_bytes_dropped")]
    solves = run["window"].solves + run["cell"].traffic["warmup_solves"]
    if not counted or not solves:
        return None
    return sum(counted) / solves / 1e9
