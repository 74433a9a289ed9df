"""P.A = L.U, LU with partial pivoting of ``dplasma_sgetrf_1d`` over an f32
block-cyclic matrix A, factored in place, and IPIV, the pivots, one int32
tile a panel (``models/lu.py:tiled_getrf_ptg``; ``getrf-44k``).

Seeded operand tiles made once, the program's collections and PTG for one
solve, the algorithm's FLOPs and least bytes and each kernel's, and the
comparison with the plain reference (``reference_lu.py``) on the
configuration's guarantees.  ``probe_gap`` is ``|P^T.L.U.x - A.x| / |A.x|``
on the probes, and reads infinite where IPIV is not a pivot sequence
(ipiv[i] in [i, N)) or where a multiplier of L passes ``MAX_L``: partial
pivoting's own guarantee, which ``run.py`` (the benchmark's, not this
cell's to edit) has no number of its own for.

Host memory is part of the deployment, as in ``problems/geqrf_tiled.py``: a
result is 1,936 tiles of 4 MiB (7.56 GiB), and the dynamic path keeps two
beside the solve in progress.  So *every* solve is reduced where it is read
back, inside the window, to what the comparison reads of it (the probe
product, max |l| and whether IPIV is valid), and the finished collections
leave the runner's hands there.  The reduction is reference work inside the
window, as on the QR cell (15-18% there): on the chip it takes 1.0-1.3 s a
solve, 15% of the window (``read_back`` 6.97 of 45.79 s; PERF.md, PR 42).
Its seconds are the runner's ``read_back`` span and this module's log line.
"""

from __future__ import annotations

import resource
import sys
import time

import numpy as np

import reference as ref
import reference_lu as refl
from harness import host_tile, load_module

_Products = load_module("problems", "potrf_tiled")._Products

# partial pivoting makes every multiplier a quotient |a| / |pivot| with
# |a| <= |pivot|: at most 1, and 1 + a few ulps where the kernel multiplies
# by the pivot's reciprocal; incremental (tile-local) pivoting reads 10 and
# more on normals.  2^-20 is eight ulps of 1 in f32
MAX_L = 1.0 + 2.0 ** -20


class Problem:
    """``models/lu.py:tiled_getrf_ptg`` over seeded tiles of plain
    normals."""

    def __init__(self, cfg: dict, seed: int) -> None:
        # a program without the pivoted LU fails here, before any data is
        # made: at once, and not after solve_timeout_s
        from parsec_tpu.models.lu import ipiv_matrix, tiled_getrf_ptg
        self._ptg, self._ipiv = tiled_getrf_ptg, ipiv_matrix
        self.n, self.nb = cfg["N"], cfg["nb"]
        nt = self.nt = self.n // self.nb
        nb = float(self.nb)
        self.tiles = refl.lu_tiles(seed, self.n, self.nb)
        self.result_tiles = len(self.tiles) + nt
        counts = {"PANEL": nt, "SWPTRSM": nt * (nt - 1) // 2,
                  "GEMM": (nt - 1) * nt * (2 * nt - 1) // 6,
                  "SWPLEFT": nt * (nt - 1) // 2}
        self.tasks = sum(counts.values())
        # each kernel's operations and least bytes, for the tasks of one
        # solve (class_flops sums to 2N^3/3)
        self.class_flops = {c: sum(self.task_flops(c, k) * n
                                   for k, n in self.steps(c))
                            for c in counts}
        self.class_bytes = {c: sum(self.task_bytes(c, k) * n
                                   for k, n in self.steps(c))
                            for c in counts}
        self.flops = 2.0 * self.n ** 3 / 3.0
        # A read once and written once, IPIV written once
        self.min_bytes = 2.0 * len(self.tiles) * nb * nb * 4 + nt * 4 * nb * 4
        self.X = ref.probes(seed, self.n)
        self.reduced_s: list[float] = []   # each read-back's reduction

    def steps(self, cls: str) -> list[tuple[int, int]]:
        """(k, tasks of ``cls`` at step k) over a solve."""
        nt = self.nt
        return [(k, {"PANEL": 1, "SWPTRSM": nt - 1 - k,
                     "GEMM": (nt - 1 - k) ** 2, "SWPLEFT": k}[cls])
                for k in range(nt)]

    def task_flops(self, cls: str, k: int) -> float:
        """LAPACK's count for one task of ``cls`` at step k: the panel of
        m = (NT - k) nb rows m nb^2 - nb^3 / 3, TRSM nb^3, GEMM 2 nb^3, a
        swap nothing."""
        nb = float(self.nb)
        m = (self.nt - k) * nb
        return {"PANEL": m * nb * nb - nb ** 3 / 3, "SWPTRSM": nb ** 3,
                "GEMM": 2 * nb ** 3, "SWPLEFT": 0.0}[cls]

    def task_bytes(self, cls: str, k: int) -> float:
        """The least bytes one task of ``cls`` at step k moves: the panel
        reads and writes its m x nb column; a swap task reads and writes
        the rows IPIV(k) moves, at most nb pairs (2 nb rows of nb f32); a
        GEMM reads three tiles and writes one."""
        nb = float(self.nb)
        m = (self.nt - k) * nb
        pairs = 2 * 2 * nb * nb * 4
        return {"PANEL": 2 * m * nb * 4, "SWPTRSM": pairs,
                "GEMM": 4 * nb * nb * 4, "SWPLEFT": pairs}[cls]

    def least_seconds(self, classes: tuple, peaks: dict) -> float:
        """One solve's tasks of ``classes`` at the roofline, each task
        bound by its FLOPs or its bytes."""
        return sum(n * max(self.task_flops(c, k) / peaks["flops_per_s"],
                           self.task_bytes(c, k) / peaks["bytes_per_s"])
                   for c in classes for k, n in self.steps(c))

    def collections(self) -> list:
        from parsec_tpu.data_dist.matrix import TwoDimBlockCyclic
        n, nb = self.n, self.nb
        return [TwoDimBlockCyclic("A", n, n, nb, nb, dtype=np.float32,
                                  init_fn=lambda m, k, shape: self.tiles[m, k]),
                self._ipiv(n, nb)]

    def pool(self, colls: list):
        return self._ptg(colls[0], colls[1])

    def result(self, colls: list):
        """A's and IPIV's tiles as the solve left them on the host, reduced
        to the probe product; a tile the host does not hold is missing from
        the answer, and then nothing is reduced."""
        A, IPIV = colls
        colls.clear()
        tiles = {key: host_tile(A.data_of(*key)) for key in self.tiles}
        tiles = {k: v for k, v in tiles.items() if v is not None}
        piv = [host_tile(IPIV.data_of(0, k)) for k in range(self.nt)]
        piv = [p for p in piv if p is not None]
        held = len(tiles) + len(piv)
        if held != self.result_tiles:
            return [None] * held           # absent tiles: no product to form
        return self.reduce(tiles, np.concatenate([p[0] for p in piv]))

    def reduce(self, tiles: dict, ipiv: np.ndarray):
        t0 = time.perf_counter()
        got = refl.lu_got(tiles, ipiv, self.X, self.nb)
        self.reduced_s.append(time.perf_counter() - t0)
        return _Products(got, self.result_tiles)

    def reference(self) -> None:
        print("[getrf_tiled] read-backs reduced to probe products, seconds "
              "each (inside the window): "
              + " ".join(f"{s:.3f}" for s in self.reduced_s)
              + "; peak RSS up to the window's end "
              f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20:.2f}"
              " GiB", file=sys.stderr, flush=True)
        self.want = refl.apply(self.tiles, self.X, self.nb)

    def gap(self, tiles) -> float:
        if len(tiles) != self.result_tiles \
                or not isinstance(tiles, _Products):
            return float("inf")
        got, max_l, valid = tiles.got
        print(f"[getrf_tiled] max |l| {max_l!r}, ipiv valid {valid}",
              file=sys.stderr, flush=True)
        if not valid or not max_l <= MAX_L:
            return float("inf")
        return ref.gap(got, self.want)

    def control(self, precision: str = "high"):
        import jax
        opts = {"xla_tpu_scoped_vmem_limit_kib": "98304"} \
            if jax.devices()[0].platform == "tpu" else None
        return self.reduce(*refl.lu_control(self.tiles, self.nb, precision,
                                            opts))
