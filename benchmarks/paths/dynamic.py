"""The dynamic path: every solve is a bare ``Context``, one PTG taskpool, the
wait, the device sync and the flush that brings the result tiles back to the
host, which is where ``testing_?gemm``'s timing ends and what makes the next
solve start with every tile on the host again.

The traffic file states how the client process treats its own heap, because
the program's write-back hands every solve a fresh host buffer per result
tile (1 GB a GEMM solve) and the solve's time follows where glibc finds it:
``mallopt`` (glibc parameters set before the first solve) and
``collect_between_solves`` (the cyclic garbage of the solve before, which
holds its tiles, goes before the next solve starts, inside the window).  What
they change is measured: PERF.md, Findings, PR 25."""

from __future__ import annotations

import ctypes
import gc

MALLOPT = {"M_TRIM_THRESHOLD": -1, "M_MMAP_THRESHOLD": -3}   # <malloc.h>


def run(cell, prob, win) -> list[dict]:
    from parsec_tpu.runtime import Context

    t = cell.traffic
    libc = ctypes.CDLL("libc.so.6")
    for param, value in t["mallopt"].items():
        if libc.mallopt(MALLOPT[param], value) != 1:
            raise SystemExit(f"mallopt({param}, {value}) refused")

    def solve() -> tuple:
        with win.span("build_pool"):
            colls = prob.collections()
            pool = prob.pool(colls)
            ctx = Context(nb_cores=t["nb_cores"])
        with win.span("add_taskpool"):
            ctx.add_taskpool(pool)
        with win.span("wait"):
            ctx.wait(timeout=t["solve_timeout_s"])
        with win.span("sync"):
            for d in ctx.accelerators():
                d.sync()
        with win.span("flush"):
            for d in ctx.accelerators():
                d.flush_cache()
        with win.span("fini"):
            ctx.fini()
        return colls

    for _ in range(t["warmup_solves"]):
        solve()
    kept = {}
    win.begin()
    while win.open():
        if t["collect_between_solves"]:
            with win.span("collect"):
                gc.collect()
        colls = solve()
        # a solve counts once its result is read where the user reads it: the
        # host copies, as they are now and not as a later read would find them
        with win.span("read_back"):
            tiles = prob.result(colls)
            win.tiles_absent += prob.result_tiles - len(tiles)
        if win.solves == win.pick:
            kept["pick"] = tiles
        kept["last"] = tiles
        win.solved()
    win.end()
    return list(kept.values())
