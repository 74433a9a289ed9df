"""Bytes of tiles the HBM LRU evicted (written back through the w2r queue
and dropped before the flush), per solve: the device module's counter
``evicted_bytes`` summed over the accelerators, over the process's solves,
warm-up included (the same graph).  Zero where every tile stayed until the
flush; nothing where the program has no such counter."""


def read(run: dict) -> float | None:
    from parsec_tpu.device import registry
    counted = [d.evicted_bytes for d in registry.devices
               if hasattr(d, "evicted_bytes")]
    solves = run["window"].solves + run["cell"].traffic["warmup_solves"]
    if not counted or not solves:
        return None
    return sum(counted) / solves / 1e9
