"""A = L.Lt, lower, symmetric block-cyclic f32 (``models/cholesky.py``).

One of the solves a configuration can name (``"algorithm"`` in its file,
found here by that name): seeded operands, the program's collections and PTG
for one solve, the algorithm's FLOPs and least bytes, and the comparison with
the plain reference.  A path module drives a problem; a problem knows no path.
"""

from __future__ import annotations

import numpy as np

import reference as ref
from harness import host_tile


class Problem:
    """A = L.Lt, lower, over a symmetric block-cyclic f32 matrix:
    ``models/cholesky.py:tiled_cholesky_ptg``."""

    def __init__(self, cfg: dict, seed: int) -> None:
        self.n, self.nb = cfg["N"], cfg["nb"]
        nt = self.nt = self.n // self.nb
        self.seed = seed
        self.a = ref.spd_data(seed, self.n)
        nb = self.nb
        # contiguous host tiles of the lower triangle, made once: the program
        # replaces a tile's host copy on write-back and never writes into it
        self.tiles = {(m, k): None for m in range(nt) for k in range(m + 1)}

        def cut(m: int) -> None:
            for k in range(m + 1):
                self.tiles[m, k] = np.ascontiguousarray(
                    self.a[m * nb:(m + 1) * nb, k * nb:(k + 1) * nb])

        ref._parallel(nt, cut)
        self.result_tiles = len(self.tiles)
        self.tasks = nt + nt * (nt - 1) + nt * (nt - 1) * (nt - 2) // 6
        self.flops = self.n ** 3 / 3.0
        # the lower triangle read once and written once
        self.min_bytes = 2.0 * len(self.tiles) * nb * nb * 4

    def collections(self) -> tuple:
        from parsec_tpu.data_dist.matrix import SymTwoDimBlockCyclic
        n, nb = self.n, self.nb
        return (SymTwoDimBlockCyclic(
            "A", n, n, nb, nb, dtype=np.float32,
            init_fn=lambda m, k, shape: self.tiles[m, k]),)

    def pool(self, colls: tuple):
        from parsec_tpu.models.cholesky import tiled_cholesky_ptg
        return tiled_cholesky_ptg(colls[0])

    def result(self, colls: tuple) -> dict:
        """L's tiles as the solve left them on the host; a tile the host does
        not hold is missing from the answer."""
        A = colls[0]
        tiles = {(m, k): host_tile(A.data_of(m, k)) for (m, k) in self.tiles}
        return {k: v for k, v in tiles.items() if v is not None}

    def reference(self) -> None:
        self.X = ref.probes(self.seed, self.n)
        self.want = ref.potrf_want(self.a, self.X)

    def gap(self, tiles: dict) -> float:
        if len(tiles) != self.result_tiles:
            return float("inf")
        return ref.gap(ref.potrf_got(tiles, self.X, self.nb), self.want)

    def control(self) -> dict:
        return ref.tiles_of(ref.potrf_control(self.a, self.nb), self.nb,
                            lower=True)
