"""C = A.B over square f32 tiles (``models/tiled_gemm.py``).

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
    """C = A.B over square f32 tiles: ``models/tiled_gemm.py``."""

    def __init__(self, cfg: dict, seed: int) -> None:
        self.n, self.nb = cfg["N"], cfg["nb"]
        self.nt = self.n // self.nb
        self.seed = seed
        self.A4, self.B4 = ref.gemm_data(seed, self.n, self.nb)
        # C's host tiles before a solve: zeros, allocated and touched once
        # (the program replaces a tile's host copy on write-back and never
        # writes into it, so every solve can start from the same zeros)
        self.C4 = ref.zero_blocks(self.A4.shape)
        self.tasks = self.nt ** 3
        self.result_tiles = self.nt ** 2
        self.flops = 2.0 * self.n ** 3
        # least traffic of one solve on the device: A and B read, C written
        self.min_bytes = 3.0 * self.n * self.n * 4

    def collections(self) -> tuple:
        """Fresh A, B, C for one solve: A and B over the seeded host tiles,
        C at zero."""
        from parsec_tpu.data_dist.matrix import TiledMatrix
        n, nb = self.n, self.nb
        A = TiledMatrix("A", n, n, nb, nb, dtype=np.float32,
                        init_fn=lambda m, k, shape: self.A4[m, k])
        B = TiledMatrix("B", n, n, nb, nb, dtype=np.float32,
                        init_fn=lambda k, j, shape: self.B4[k, j])
        C = TiledMatrix("C", n, n, nb, nb, dtype=np.float32,
                        init_fn=lambda m, j, shape: self.C4[m, j])
        return A, B, C

    def pool(self, colls: tuple):
        from parsec_tpu.models.tiled_gemm import tiled_gemm_ptg
        return tiled_gemm_ptg(*colls)

    def result(self, colls: tuple) -> dict:
        """C's tiles as the solve left them on the host; a tile the host does
        not hold is missing from the answer."""
        C = colls[2]
        tiles = {(m, j): host_tile(C.data_of(m, j))
                 for m in range(self.nt) for j in range(self.nt)}
        return {k: v for k, v in tiles.items() if v is not None}

    def result_of_store(self, store) -> dict:
        """C's tiles from the lowered program's dense output store."""
        c = np.asarray(store["C"])
        assert c.shape == (self.n, self.n), c.shape
        return ref.tiles_of(c, self.nb)

    def reference(self) -> None:
        self.X = ref.probes(self.seed, self.n)
        self.want = ref.gemm_want(self.A4, self.B4, self.X)

    def gap(self, tiles: dict) -> float:
        if len(tiles) != self.result_tiles:
            return float("inf")
        return ref.gap(ref.apply_tiles(tiles.items(), self.X, self.nb,
                                       self.n), self.want)

    def control(self) -> dict:
        return ref.tiles_of(ref.gemm_control(self.A4, self.B4), self.nb)
