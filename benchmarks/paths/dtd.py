"""The DTD path: every solve is a bare ``Context``, one ``DTDTaskpool``, the
problem's insertion program run by the client thread (which executes whenever
the window is full), the pool's wait, the device sync and the flush that
brings the result tiles back to the host.  The window discipline, the client's
heap and what is kept for the comparison are ``paths/dynamic.py``'s; the
traffic file states the window sizes in force."""

from __future__ import annotations

import ctypes
import gc
import os

from harness import load_module

WINDOW = ("dtd_window_size", "dtd_threshold_size")


def run(cell, prob, win) -> list[dict]:
    import parsec_tpu.dtd  # noqa: F401  registers the window's parameters
    from parsec_tpu.core.params import params
    from parsec_tpu.runtime import Context

    t = cell.traffic
    mallopt = load_module("paths", "dynamic").MALLOPT
    libc = ctypes.CDLL("libc.so.6")
    for param, value in t["mallopt"].items():
        if libc.mallopt(mallopt[param], value) != 1:
            raise SystemExit(f"mallopt({param}, {value}) refused")
    for key in WINDOW:
        if params.get(key) != t[key] and "PARSEC_MCA_" + key not in os.environ:
            raise SystemExit(f"{key} is {params.get(key)}, the traffic file "
                             f"says {t[key]}")

    def solve() -> tuple:
        with win.span("build_pool"):
            colls = prob.collections()
            pool = prob.pool()
            ctx = Context(nb_cores=t["nb_cores"])
        with win.span("add_taskpool"):
            ctx.add_taskpool(pool)
        with win.span("insert"):
            prob.insert(pool, colls)
        with win.span("wait"):
            pool.wait(timeout=t["solve_timeout_s"])
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
