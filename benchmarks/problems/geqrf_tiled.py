"""A = Q.R, the flat-tree tile QR of ``dplasma_sgeqrf`` over two block-cyclic
f32 matrices: A, factored in place, and T, the block reflectors' triangular
factors (``models/qr.py:tiled_qr_ptg``; ``geqrf-32k``).

Seeded operand tiles made once, the program's collections and PTG for one
solve, the algorithm's FLOPs and least bytes, and the comparison with the
plain reference (``reference_qr.py``) on both of the configuration's
guarantees; ``probe_gap`` is the larger of the two gaps.

Host memory is part of the deployment, as in ``problems/potrf_tiled.py``: a
result is 1,552 tiles of 4 MiB (6.06 GiB) and the dynamic path keeps two
beside the solve in progress, which this host cannot hold (PERF.md, section
4).  So *every* solve is reduced where it is read back, inside the window,
to what the comparison reads of it, the two probe products in float64, and
the finished collections leave the runner's hands there (``collections()``
hands out a list, ``result()`` empties it).  The seconds are the runner's
``read_back`` span, which on this cell holds nothing else, and this module's
log line.
"""

from __future__ import annotations

import resource
import sys
import time

import numpy as np

import reference as ref
import reference_qr as refq
from harness import host_tile, load_module


_Products = load_module("problems", "potrf_tiled")._Products


class Problem:
    """``models/qr.py:tiled_qr_ptg`` over seeded tiles of plain normals."""

    def __init__(self, cfg: dict, seed: int) -> None:
        # a program without the QR model fails here, before any data is made
        from parsec_tpu.models.qr import tiled_qr_ptg
        self._ptg = tiled_qr_ptg
        self.n, self.nb = cfg["N"], cfg["nb"]
        nt = self.n // self.nb
        nb3 = float(self.nb) ** 3
        # contiguous host tiles made once: the program replaces a tile's
        # host copy on write-back and never writes into it; T's tiles start
        # as one shared tile of zeros
        self.tiles = refq.qr_tiles(seed, self.n, self.nb)
        self.zero = ref.zero_blocks((1, self.nb, self.nb))[0]
        self.t_keys = [(m, k) for m in range(nt) for k in range(m + 1)]
        self.result_tiles = len(self.tiles) + len(self.t_keys)
        per_class = {"GEQRT": (nt, 4 / 3), "UNMQR": (nt * (nt - 1) // 2, 2),
                     "TSQRT": (nt * (nt - 1) // 2, 2),
                     "TSMQR": ((nt - 1) * nt * (2 * nt - 1) // 6, 4)}
        # tasks of a class x its LAPACK count: they sum to 4N^3/3
        self.class_flops = {c: n * f * nb3 for c, (n, f) in per_class.items()}
        self.tasks = sum(n for n, _ in per_class.values())
        self.flops = 4.0 * self.n ** 3 / 3.0
        # A read once, A and T written once
        self.min_bytes = (2.0 * len(self.tiles) + len(self.t_keys)) \
            * self.nb * self.nb * 4
        self.X = ref.probes(seed, self.n)
        self.reduced_s: list[float] = []   # each read-back's reduction

    def collections(self) -> list:
        from parsec_tpu.data_dist.matrix import TwoDimBlockCyclic
        n, nb = self.n, self.nb
        return [TwoDimBlockCyclic("A", n, n, nb, nb, dtype=np.float32,
                                  init_fn=lambda m, k, shape: self.tiles[m, k]),
                TwoDimBlockCyclic("T", n, n, nb, nb, dtype=np.float32,
                                  init_fn=lambda m, k, shape: self.zero)]

    def pool(self, colls: list):
        return self._ptg(colls[0], colls[1])

    def result(self, colls: list):
        """A's and T's tiles as the solve left them on the host, reduced to
        the probe products; a tile the host does not hold is missing from
        the answer, and then nothing is reduced."""
        A, T = colls
        colls.clear()

        def held(dc, keys) -> dict:
            tiles = {key: host_tile(dc.data_of(*key)) for key in keys}
            return {k: v for k, v in tiles.items() if v is not None}

        return self.reduce(held(A, self.tiles), held(T, self.t_keys))

    def reduce(self, tiles_a: dict, tiles_t: dict):
        held = len(tiles_a) + len(tiles_t)
        if held != self.result_tiles:
            return [None] * held           # absent tiles: no product to form
        t0 = time.perf_counter()
        got = refq.qr_got(tiles_a, tiles_t, self.X, self.nb)
        self.reduced_s.append(time.perf_counter() - t0)
        return _Products(got, held)

    def reference(self) -> None:
        print("[geqrf_tiled] read-backs reduced to probe products, seconds "
              "each (inside the window): "
              + " ".join(f"{s:.3f}" for s in self.reduced_s)
              + "; peak RSS up to the window's end "
              f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20:.2f}"
              " GiB", file=sys.stderr, flush=True)
        ax = refq.apply(self.tiles, self.X, self.nb)
        self.want = (ax, refq.apply_t(self.tiles, ax, self.nb))

    def gap(self, tiles) -> float:
        if len(tiles) != self.result_tiles \
                or not isinstance(tiles, _Products):
            return float("inf")
        return max(ref.gap(g, w) for g, w in zip(tiles.got, self.want))

    def control(self):
        return self.reduce(*refq.qr_control(self.tiles, self.nb))
