"""The plain reference of the Cholesky and its seeded data, tile by tile: for
a matrix whose dense form does not fit beside its tiles (N=65,536: 17 GB
dense, 8.7 GB of lower tiles).  numpy and plain ``jax.numpy`` only, as
``reference.py``: nothing here imports ``parsec_tpu`` or takes anything the
program has made.

The same construction and the same comparison as ``reference.spd_data`` and
``reference.potrf_want`` / ``potrf_got``: a symmetric matrix with N(0, 1/2)
off the diagonal and the row's absolute sum plus one on it, the gap
``|L.(Lt.X) - A.X| / |A.X|`` on the seeded probes in float64.  What differs is
that only the lower tiles ever exist: tile (m, k) is drawn from
``default_rng([seed, MAT_SPD_TILE, m, k])``, and every product walks the
tiles.  At a size where both fit, the dense matrix built from these tiles
gives ``reference.potrf_want`` the same answer (``tests/``).
"""

from __future__ import annotations

import contextlib
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import reference as ref

try:
    from threadpoolctl import threadpool_limits
except ImportError:      # not a declared dependency: slower without, not wrong

    def threadpool_limits(*args, **kwargs):
        return contextlib.nullcontext()

MAT_SPD_TILE = 5
# one core is left to the process's other threads (PJRT's, the profiler's)
THREADS = max(1, (os.cpu_count() or 2) - 1)


def spd_tiles(seed: int, n: int, nb: int) -> dict:
    """The lower tiles ``(m, k), k <= m`` of a symmetric, diagonally dominant
    f32 matrix, each a contiguous (nb, nb) array; a diagonal tile holds both
    its triangles."""
    nt = n // nb
    tiles = {(m, k): None for m in range(nt) for k in range(m + 1)}
    rows = np.zeros((nt, nb))            # absolute sums along each tile row
    cols = np.zeros((nt, nt, nb))        # [m, k]: tile (m, k) summed down

    def fill(i: int) -> None:
        m = nt - 1 - i                   # the longest rows first
        for k in range(m + 1):
            t = np.random.default_rng(
                [seed, MAT_SPD_TILE, m, k]).standard_normal(
                    (nb, nb), dtype=np.float32)
            if k == m:
                t = (t + t.T) * np.float32(0.5)
                np.fill_diagonal(t, 0.0)
            else:
                t *= np.float32(np.sqrt(0.5))
                cols[m, k] = np.abs(t).sum(axis=0, dtype=np.float64)
            rows[m] += np.abs(t).sum(axis=1, dtype=np.float64)
            tiles[m, k] = t

    ref._parallel(nt, fill)
    # row i of the whole matrix: its tiles left of and on the diagonal, and
    # by symmetry the columns of the tiles below the diagonal tile
    for m in range(nt):
        np.fill_diagonal(tiles[m, m],
                         (rows[m] + cols[:, m].sum(axis=0) + 1.0)
                         .astype(np.float32))
    return tiles


def dense_of(tiles: dict, nb: int) -> np.ndarray:
    """The whole symmetric matrix (small sizes: the tests)."""
    nt = 1 + max(m for m, _ in tiles)
    a = np.empty((nt * nb, nt * nb), np.float32)
    for (m, k), t in tiles.items():
        a[m * nb:(m + 1) * nb, k * nb:(k + 1) * nb] = t
        a[k * nb:(k + 1) * nb, m * nb:(m + 1) * nb] = t.T
    return a


_WORKERS: dict = {}     # nb -> (pool, a float64 tile per thread, lower mask)


def _workers(nb: int) -> tuple:
    """The threads and their float64 tile buffers, made once and kept for
    the process: a reduction that runs inside a measured window (every
    read-back of ``problems/potrf_tiled.py``) then finds no thread, arena or
    page new.  With a fresh pool and a fresh 8 MB conversion a tile the
    client's heap grew by 0.33 GiB a reduction and one reduction in three
    took 3 to 5 s instead of 1.2 (my chip runs, PR 29)."""
    if nb not in _WORKERS:
        _WORKERS[nb] = (ThreadPoolExecutor(THREADS),
                        [np.empty((nb, nb)) for _ in range(THREADS)],
                        np.tril(np.ones((nb, nb), bool)))
    return _WORKERS[nb]


def _summed(tiles: dict, shape: tuple, nb: int, add,
            lower: bool = False) -> np.ndarray:
    """``add(acc, tile, m, k)`` over all tiles, each as float64 (``lower``:
    a diagonal tile without its strict upper part), on a few threads with an
    accumulator each (numpy drops the GIL in the conversions and the
    products), summed at the end.  BLAS keeps to one thread a call
    meanwhile, where ``threadpoolctl`` is installed: OpenBLAS lets one
    threaded product run at a time, so eight callers would queue (5 times
    slower, measured)."""
    pool, bufs, low = _workers(nb)
    items = list(tiles.items())

    def part(i: int) -> np.ndarray:
        acc, buf = np.zeros(shape), bufs[i]
        for (m, k), t in items[i::THREADS]:
            np.copyto(buf, t)
            if lower and m == k:
                np.multiply(buf, low, out=buf)
            add(acc, buf, m, k)
        return acc

    with threadpool_limits(1, user_api="blas"):
        return sum(pool.map(part, range(THREADS)))


def sym_apply(tiles: dict, X: np.ndarray, nb: int) -> np.ndarray:
    """``A.X`` in float64 from the lower tiles of a symmetric A."""
    def add(Y: np.ndarray, t: np.ndarray, m: int, k: int) -> None:
        Y[m * nb:(m + 1) * nb] += t @ X[k * nb:(k + 1) * nb]
        if m != k:
            Y[k * nb:(k + 1) * nb] += t.T @ X[m * nb:(m + 1) * nb]

    return _summed(tiles, X.shape, nb, add)


def potrf_got(tiles: dict, X: np.ndarray, nb: int) -> np.ndarray:
    """``L.(Lt.X)`` in float64 from the factored lower tiles.  The diagonal
    tiles keep A's strict upper part: only their lower triangle is L."""
    def add_t(Y: np.ndarray, t: np.ndarray, m: int, k: int) -> None:
        Y[k * nb:(k + 1) * nb] += t.T @ X[m * nb:(m + 1) * nb]

    Y = _summed(tiles, X.shape, nb, add_t, lower=True)

    def add(Z: np.ndarray, t: np.ndarray, m: int, k: int) -> None:
        Z[m * nb:(m + 1) * nb] += t @ Y[k * nb:(k + 1) * nb]

    return _summed(tiles, X.shape, nb, add, lower=True)


def potrf_control(tiles: dict, nb: int) -> dict:
    """``reference.potrf_control`` tile by tile: the right-looking blocked
    Cholesky with every tile stored in bfloat16 between tile operations,
    each operation computed in f32 from the stored values.  The lower tiles
    of the factor as float32 numpy arrays."""
    import jax
    import jax.numpy as jnp
    from jax.scipy.linalg import solve_triangular
    store = jnp.bfloat16
    f32 = jnp.float32
    nt = 1 + max(m for m, _ in tiles)

    @jax.jit
    def potrf(t):
        return jnp.tril(jnp.linalg.cholesky(t.astype(f32))).astype(store)

    @jax.jit
    def trsm(lkk, t):
        return solve_triangular(lkk.astype(f32), t.astype(f32).T,
                                lower=True).T.astype(store)

    @jax.jit
    def update(t, a, b):
        return (t.astype(f32) - jnp.dot(
            a, b.T, preferred_element_type=f32)).astype(store)

    s = {key: jnp.asarray(t).astype(store) for key, t in tiles.items()}
    for k in range(nt):
        s[k, k] = potrf(s[k, k])
        for m in range(k + 1, nt):
            s[m, k] = trsm(s[k, k], s[m, k])
        for m in range(k + 1, nt):
            for n in range(k + 1, m + 1):
                s[m, n] = update(s[m, n], s[m, k], s[n, k])
    return {key: np.asarray(t.astype(f32)) for key, t in s.items()}
