"""N-rank harness: one runtime context per rank over a shared fabric.

The rebuild's analog of the reference's oversubscribed-MPI test runs
(``mpiexec --oversubscribe -np N``, SURVEY §4): each rank is a thread owning
its own :class:`~parsec_tpu.runtime.context.Context` (rank-local scheduler,
dep table, taskpool registry) attached to the shared
:class:`~parsec_tpu.comm.engine.InprocFabric`.  The *protocol* layer —
activation messages, rendezvous GETs, propagation trees, termdet pending
actions — is exercised exactly as it would be across hosts; only the byte
transport is in-process.

Usage::

    def body(ctx, rank, nranks):
        A = TwoDimBlockCyclic("A", ..., P=nranks, myrank=rank)
        tp = build_my_ptg(A)
        ctx.add_taskpool(tp)
        ctx.wait()
        return result_visible_on(rank)

    results = run_multirank(4, body)
"""

from __future__ import annotations

import threading
from typing import Any, Callable

from ..runtime.context import Context
from .engine import InprocFabric
from .remote_dep import RemoteDepEngine


def run_multirank(nranks: int, fn: Callable[[Context, int, int], Any],
                  nb_cores: int = 0, timeout: float = 120.0,
                  transport: str = "inproc",
                  devices: list | None = None) -> list[Any]:
    """Run ``fn(ctx, rank, nranks)`` on every rank; returns per-rank results.

    ``nb_cores=0`` ranks drive progress from ``wait()`` (the master-thread
    funneled mode) — the default for tests, deterministic and cheap.

    ``transport="device"`` attaches the device-backed engine
    (:mod:`parsec_tpu.comm.device_fabric`): rank *i* owns JAX device *i* —
    its payloads move device-to-device and its device chores run on that
    chip alone — the configuration the driver's multichip dryrun
    certifies.
    """
    if transport == "device":
        from .device_fabric import DeviceFabric
        fabric: InprocFabric = DeviceFabric(nranks, devices)
    else:
        fabric = InprocFabric(nranks)
    results: list[Any] = [None] * nranks
    errors: list[BaseException | None] = [None] * nranks

    def rank_main(rank: int) -> None:
        ctx = Context(nb_cores=nb_cores, nb_ranks=nranks, my_rank=rank,
                      accelerators=[fabric.devices[rank]]
                      if transport == "device" else None)
        eng = RemoteDepEngine(ctx, fabric.attach(rank))
        try:
            ctx.start()
            results[rank] = fn(ctx, rank, nranks)
            # context-level drain: every rank must stay responsive until the
            # whole fabric is silent (late writebacks/acks), then tear down
            eng.quiesce(timeout=timeout / 2)
            ctx.fini()
        except BaseException as e:  # surfaced to the caller below
            errors[rank] = e
            try:
                ctx.abort()
            except Exception:
                pass

    threads = [threading.Thread(target=rank_main, args=(r,),
                                name=f"rank{r}", daemon=True)
               for r in range(nranks)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout)
        if t.is_alive():
            raise TimeoutError(f"{t.name} did not finish within {timeout}s "
                               f"(errors so far: {errors})")
    for r, e in enumerate(errors):
        if e is not None:
            raise RuntimeError(f"rank {r} failed") from e
    return results
