"""The lowered path: the PTG compiled to one XLA program at set-up
(``lower_taskpool``, ``warm``), its stores put on the device once, then the
pool's own jitted program called back to back on the resident stores.  Each
call is one solve.  No scheduler, no device module."""

from __future__ import annotations

from collections import deque


def run(cell, prob, win) -> list[dict]:
    import jax

    from parsec_tpu.ptg.lowering import lower_taskpool

    t = cell.traffic
    low = lower_taskpool(prob.pool(prob.collections()))
    low.warm()
    program = low.jitted()
    stores = jax.device_put(low.initial_stores(), jax.local_devices()[0])
    for _ in range(t["warmup_calls"]):
        jax.block_until_ready(program(stores))

    kept = {}
    inflight: deque = deque()
    win.begin()
    while win.open():
        with win.span("call"):
            out = {k: v for k, v in program(stores).items()
                   if k in low.written_collections}
        inflight.append(out)
        if len(inflight) > t["inflight_calls"]:
            with win.span("sync"):
                jax.block_until_ready(inflight.popleft())
        if win.solves == win.pick:
            kept["pick"] = out
        kept["last"] = out
        win.solved()
    with win.span("sync"):
        jax.block_until_ready(list(inflight))
    win.end()
    inflight.clear()
    del stores, out
    results = []
    for key in list(kept):
        results.append(prob.result_of_store(kept.pop(key)))
    return results
