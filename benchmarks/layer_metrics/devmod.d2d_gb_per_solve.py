"""Bytes the device module copied from one accelerator to another (a
stage-in miss whose newest copy was another chip's array), per solve: the
counter ``bytes_d2d`` summed over the accelerators, over the process's
solves, warm-up included (the same graph).  Zero with one accelerator.
``Device.bytes_d2d`` was declared long before anything counted it, so a
program is taken to count it where it has ``d2d_tiles`` beside it (PR 40);
on any other this reads nothing."""


def read(run: dict) -> float | None:
    from parsec_tpu.device import registry
    counted = [d.bytes_d2d for d in registry.devices
               if d.type != "cpu" and hasattr(d, "d2d_tiles")]
    solves = run["window"].solves + run["cell"].traffic["warmup_solves"]
    if not counted or not solves:
        return None
    return sum(counted) / solves / 1e9
