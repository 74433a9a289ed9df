"""Of the stage-in's cache hits (a (task, flow) reference whose tile is on
the chip already, ``cache_hits``), the share that touched the LRU: a batch
moves each distinct hit copy to the LRU's recent end once, however
many of its tasks reference it (``lru_touches``): ``lru_touches /
cache_hits`` summed over the accelerators, the process's totals (warm-up
solves included: the same graph), in percent.  100: every hit touched the
LRU, as every one did before; the lower, the more of a batch's references
share a tile.  ``lru_recharged`` beside it, near 0, says the touch is a move
alone.  Nothing where no reference hit, or on a program without the counter
(one from before the touch)."""


def read(run: dict) -> float | None:
    from parsec_tpu.device import registry
    accel = [d for d in registry.devices if d.type != "cpu"]
    if not any(hasattr(d, "lru_touches") for d in accel):
        return None
    touches = sum(getattr(d, "lru_touches", 0) for d in accel)
    hits = sum(getattr(d, "cache_hits", 0) for d in accel)
    if not hits:
        return None
    return 100.0 * touches / hits
