"""A = L.Lt, lower, at a size whose dense matrix does not fit beside its
tiles (``potrf-64k``: 17 GB dense, 8.7 GB of lower tiles).

The same solve as ``problems/potrf.py`` (its collections, its PTG, its result
tiles, its counts); what differs is that the seeded operand, the reference and
the control are made tile by tile (``reference_tiled.py``) and no dense matrix
ever exists.

Host memory is part of the deployment, and at this size the path's habits
do not fit the host (40 GiB, of which the TPU runtime, JAX and the process
take 13: PERF.md, section 4).  The dynamic path keeps the result tiles of two
solves for the comparison after the window (the last, which its loop's
variables pin while the next one runs, and one of the first few): beside the
operands and the solve in progress that is four times 8.7 GB.  So a result
is kept as what the comparison reads of it and not as tiles: *every* solve
is reduced where it is read back, inside the window, to ``L.(Lt.X)`` on the
seeded probes, in float64, from the host tiles as they are then (0.9 to
1.3 s on the chip's host: PERF.md, section 4).  Every solve, so that each
window pays the same whatever solve its seed picks; the seconds are the
runner's ``read_back`` span, which on this cell holds nothing else, and this
module's log line.  The finished collection is taken
out of the runner's hands at the same place (``collections()`` hands out a
list, ``result()`` empties it): its host tiles go with the solve's garbage
before the next solve starts.  What is left is the operands and the solve in
progress.
"""

from __future__ import annotations

import resource
import sys
import time

import numpy as np

import reference as ref
import reference_tiled as reft
from harness import load_module

_Dense = load_module("problems", "potrf").Problem


class _Products:
    """A result reduced to its probe products; as long as the tiles were."""

    def __init__(self, got: np.ndarray, tiles: int) -> None:
        self.got, self.tiles = got, tiles

    def __len__(self) -> int:
        return self.tiles


class Problem(_Dense):
    """``models/cholesky.py:tiled_cholesky_ptg`` over seeded lower tiles."""

    def __init__(self, cfg: dict, seed: int) -> None:
        self.n, self.nb = cfg["N"], cfg["nb"]
        nt = self.nt = self.n // self.nb
        self.seed = seed
        # contiguous host tiles of the lower triangle, made once: the program
        # replaces a tile's host copy on write-back and never writes into it
        self.tiles = reft.spd_tiles(seed, self.n, self.nb)
        self.result_tiles = len(self.tiles)
        self.tasks = nt + nt * (nt - 1) + nt * (nt - 1) * (nt - 2) // 6
        self.flops = self.n ** 3 / 3.0
        # the lower triangle read once and written once
        self.min_bytes = 2.0 * len(self.tiles) * self.nb * self.nb * 4
        self.X = ref.probes(seed, self.n)
        self.reduced_s: list[float] = []   # each read-back's reduction

    def collections(self) -> list:
        return list(super().collections())

    def result(self, colls: list):
        tiles = super().result(colls)
        colls.clear()
        if len(tiles) != self.result_tiles:
            return tiles                   # absent tiles: no product to form
        t0 = time.perf_counter()
        got = reft.potrf_got(tiles, self.X, self.nb)
        self.reduced_s.append(time.perf_counter() - t0)
        return _Products(got, len(tiles))

    def reference(self) -> None:
        print("[potrf_tiled] read-backs reduced to probe products, seconds "
              "each (inside the window): "
              + " ".join(f"{s:.3f}" for s in self.reduced_s)
              + "; peak RSS up to the window's end "
              f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20:.2f}"
              " GiB", file=sys.stderr, flush=True)
        self.want = reft.sym_apply(self.tiles, self.X, self.nb)

    def gap(self, tiles) -> float:
        if len(tiles) != self.result_tiles:
            return float("inf")
        got = tiles.got if isinstance(tiles, _Products) \
            else reft.potrf_got(tiles, self.X, self.nb)
        return ref.gap(got, self.want)

    def control(self) -> dict:
        return reft.potrf_control(self.tiles, self.nb)
