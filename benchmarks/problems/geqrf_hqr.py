"""A = Q.R, the hierarchical tile QR of ``dplasma_sgeqrf_param`` over an M x N
f32 block-cyclic matrix A, factored in place, with the block reflectors'
triangular factors in TS (GEQRT and TS kills) and TT (TT kills):
``models/qr.py:tiled_hqr_ptg`` over ``models/qrtree.py:QRTree``
(``geqrf-hqr-128kx8k``).

Seeded operand tiles made once, the program's collections and PTG for one
solve, the algorithm's FLOPs (2MN^2 - 2N^3/3) and least bytes and each
class's, and the comparison with the plain reference (``reference_hqr.py``)
on both of the configuration's guarantees; ``probe_gap`` is the larger of
the two gaps.  The task counts come from the reference's own tree, not the
program's.

M is ``M_over_N`` times N, so that a rehearsal (``run.py --rehearse``,
which cuts N and nb) keeps the grid's 16 : 1 shape and the tree its work.

Host memory is part of the deployment, as in ``problems/geqrf_tiled.py``: a
result is 2,264 tiles of 4 MiB (8.84 GiB) and the dynamic path keeps two
beside the solve in progress.  So *every* solve is reduced where it is read
back, inside the window, to what the comparison reads of it, the two probe
products in float64, and the finished collections leave the runner's hands
there.  The seconds are the runner's ``read_back`` span and this module's
log line.
"""

from __future__ import annotations

import resource
import sys
import time

import numpy as np

import reference as ref
import reference_hqr as refh
from harness import host_tile, load_module

_Products = load_module("problems", "potrf_tiled")._Products

# LAPACK's operation counts of one task, in nb^3, and the tiles it reads
# and writes at the least (a T it writes, not reads)
FLOPS = {"GEQRT": 4 / 3, "UNMQR": 2, "TSQRT": 2, "TTQRT": 2 / 3,
         "TSMQR": 4, "TTMQR": 2}
TILES = {"GEQRT": 3, "UNMQR": 4, "TSQRT": 5, "TTQRT": 5, "TSMQR": 6,
         "TTMQR": 6}


class Problem:
    """``models/qr.py:tiled_hqr_ptg`` over seeded tiles of plain normals."""

    def __init__(self, cfg: dict, seed: int) -> None:
        # a program without the hierarchical QR fails here, before any data
        # is made: at once, and not after solve_timeout_s
        from parsec_tpu.models.qr import tiled_hqr_ptg
        from parsec_tpu.models.qrtree import QRTree
        self._ptg, self._tree = tiled_hqr_ptg, QRTree
        self.n, self.nb = cfg["N"], cfg["nb"]
        self.m = cfg["M_over_N"] * self.n
        self.a, self.low = cfg["a"], cfg["tree"]
        mt, nt = self.mt, self.nt = self.m // self.nb, self.n // self.nb
        self.tiles = refh.hqr_tiles(seed, self.m, self.n, self.nb)
        self.zero = ref.zero_blocks((1, self.nb, self.nb))[0]
        counts = dict.fromkeys(FLOPS, 0)
        self.ts_keys, self.tt_keys = [], []
        for k, step in enumerate(refh.phases(mt, nt, self.a, self.low)):
            for phase in step:
                for what, _, m in phase:
                    panel, update = {"ge": ("GEQRT", "UNMQR"),
                                     "ts": ("TSQRT", "TSMQR"),
                                     "tt": ("TTQRT", "TTMQR")}[what]
                    counts[panel] += 1
                    counts[update] += nt - 1 - k
                    (self.tt_keys if what == "tt" else self.ts_keys).append(
                        (m, k))
        self.counts = counts
        self.tasks = sum(counts.values())
        self.result_tiles = len(self.tiles) + len(self.ts_keys) \
            + len(self.tt_keys)
        nb3, tile = float(self.nb) ** 3, self.nb * self.nb * 4.0
        # tasks of a class x its LAPACK count: they sum to 2MN^2 - 2N^3/3
        self.class_flops = {c: n * FLOPS[c] * nb3 for c, n in counts.items()}
        self.class_bytes = {c: n * TILES[c] * tile for c, n in counts.items()}
        self.flops = 2.0 * self.m * self.n ** 2 - 2.0 * self.n ** 3 / 3.0
        # A read once, A, TS and TT written once
        self.min_bytes = (2.0 * len(self.tiles) + len(self.ts_keys)
                          + len(self.tt_keys)) * tile
        self.X = ref.probes(seed, self.n)
        self.reduced_s: list[float] = []   # each read-back's reduction

    def least_seconds(self, classes: tuple, peaks: dict) -> float:
        """One solve's tasks of ``classes`` at the roofline, each task
        bound by its FLOPs or its bytes."""
        return sum(max(self.class_flops[c] / peaks["flops_per_s"],
                       self.class_bytes[c] / peaks["bytes_per_s"])
                   for c in classes)

    def collections(self) -> list:
        from parsec_tpu.data_dist.matrix import TwoDimBlockCyclic
        m, n, nb = self.m, self.n, self.nb
        return [TwoDimBlockCyclic("A", m, n, nb, nb, dtype=np.float32,
                                  init_fn=lambda i, j, shape: self.tiles[i, j])
                ] + [TwoDimBlockCyclic(name, m, n, nb, nb, dtype=np.float32,
                                       init_fn=lambda i, j, shape: self.zero)
                     for name in ("TS", "TT")]

    def pool(self, colls: list):
        # a tree a pool: its tables are built when the pool is enqueued
        return self._ptg(*colls, self._tree(self.mt, self.nt, self.a,
                                            self.low))

    def result(self, colls: list):
        """A's, TS's and TT's tiles as the solve left them on the host,
        reduced to the probe products; a tile the host does not hold is
        missing from the answer, and then nothing is reduced."""
        A, TS, TT = colls
        colls.clear()

        def held(dc, keys) -> dict:
            tiles = {key: host_tile(dc.data_of(*key)) for key in keys}
            return {k: v for k, v in tiles.items() if v is not None}

        return self.reduce(held(A, self.tiles), held(TS, self.ts_keys),
                           held(TT, self.tt_keys))

    def reduce(self, tiles_a: dict, tiles_ts: dict, tiles_tt: dict):
        held = len(tiles_a) + len(tiles_ts) + len(tiles_tt)
        if held != self.result_tiles:
            return [None] * held           # absent tiles: no product to form
        t0 = time.perf_counter()
        got = refh.hqr_got(tiles_a, tiles_ts, tiles_tt, self.X, self.nb,
                           self.a, self.low)
        self.reduced_s.append(time.perf_counter() - t0)
        return _Products(got, held)

    def reference(self) -> None:
        print("[geqrf_hqr] read-backs reduced to probe products, seconds "
              "each (inside the window): "
              + " ".join(f"{s:.3f}" for s in self.reduced_s)
              + "; peak RSS up to the window's end "
              f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20:.2f}"
              " GiB", file=sys.stderr, flush=True)
        ax = refh.apply(self.tiles, self.X, self.nb, self.m)
        self.want = (ax, refh.apply_t(self.tiles, ax, self.nb, self.n))

    def gap(self, tiles) -> float:
        if len(tiles) != self.result_tiles \
                or not isinstance(tiles, _Products):
            return float("inf")
        return max(ref.gap(g, w) for g, w in zip(tiles.got, self.want))

    def control(self, store: str = "bfloat16", precision: str = "highest"):
        return self.reduce(*refh.hqr_control(self.tiles, self.nb, self.a,
                                             self.low, store, precision))
