"""The plain reference of the tile QR (``geqrf-32k``): seeded data, the two
probe products that decide ``correct``, and the lower-precision control.
numpy and plain ``jax.numpy`` only, as ``reference.py``: nothing here imports
``parsec_tpu`` or takes anything the program has made.

A = Q.R with A in nb-square tiles.  What the program leaves behind
(DPLASMA's ``sgeqrf`` layout with ``ib = nb``): R in the upper triangle of A's
tiles on and above the diagonal; the unit-lower V_k strictly below the
diagonal of tile (k, k); V2_mk, the lower block of the reflectors ``[I; V2]``
of panel step (m, k), in tile (m, k), m > k; the block reflectors'
triangular factors in the tiles T(m, k), m >= k, of a second matrix.  Then

    Q = Q_00 . Q_10 .. Q_(NT-1)0 . Q_11 . Q_21 ..      (the order applied)
    Q_kk = I - V_k . T_kk . V_k^T       on block row k
    Q_mk = I - [I; V2] . T_mk . [I; V2]^T   on block rows k and m

and the two numbers compared, in float64 on seeded probes X, are

    |Q.(R.X) - A.X| / |A.X|        (``testing_?geqrf``'s residual, on probes)
    |R^T.(R.X) - A^T.(A.X)| / |A^T.(A.X)|     (R alone: whatever V and T hold)

The control is the same tile algorithm in plain ``jnp`` with every written
tile *stored* in bfloat16 between tile operations, each operation computed
in f32 from the stored values (``qr_control``).
"""

from __future__ import annotations

import functools
import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import reference as ref
import reference_tiled as reft

MAT_QR_TILE = 7


def qr_tiles(seed: int, n: int, nb: int) -> dict:
    """All tiles ``(m, n)`` of a square f32 matrix of standard normals, each
    a contiguous (nb, nb) array drawn from its own stream."""
    nt = n // nb
    tiles = {(m, k): None for m in range(nt) for k in range(nt)}

    def fill(m: int) -> None:
        for k in range(nt):
            tiles[m, k] = np.random.default_rng(
                [seed, MAT_QR_TILE, m, k]).standard_normal(
                    (nb, nb), dtype=np.float32)

    ref._parallel(nt, fill)
    return tiles


def dense_of(tiles: dict, nb: int) -> np.ndarray:
    """The whole matrix (small sizes: the tests); an absent tile is zero."""
    nt = 1 + max(m for m, _ in tiles)
    a = np.zeros((nt * nb, nt * nb), np.float32)
    for (m, k), t in tiles.items():
        a[m * nb:(m + 1) * nb, k * nb:(k + 1) * nb] = t
    return a


def apply(tiles: dict, X: np.ndarray, nb: int) -> np.ndarray:
    """``A.X`` in float64 from the tiles."""
    def add(Y: np.ndarray, t: np.ndarray, m: int, k: int) -> None:
        Y[m * nb:(m + 1) * nb] += t @ X[k * nb:(k + 1) * nb]

    return reft._summed(tiles, X.shape, nb, add)


def apply_t(tiles: dict, Y: np.ndarray, nb: int) -> np.ndarray:
    """``A^T.Y`` in float64 from the tiles."""
    def add(Z: np.ndarray, t: np.ndarray, m: int, k: int) -> None:
        Z[k * nb:(k + 1) * nb] += t.T @ Y[m * nb:(m + 1) * nb]

    return reft._summed(tiles, Y.shape, nb, add)


def _r_tiles(tiles_a: dict) -> dict:
    """R's tiles as float64-ready views: on the diagonal only the upper
    triangle is R (below it lies V_k)."""
    return {(m, k): np.triu(t) if m == k else t
            for (m, k), t in tiles_a.items() if m <= k}


_LOCAL = threading.local()


@functools.cache
def _pool() -> ThreadPoolExecutor:
    """Threads kept for the process, with two float64 tile buffers each
    (``_bufs``): a reduction inside a measured window finds no thread and no
    page new (``reference_tiled._workers`` says what that cost)."""
    return ThreadPoolExecutor(reft.THREADS)


def _bufs(nb: int) -> tuple:
    got = getattr(_LOCAL, "bufs", None)
    if got is None or got[0].shape != (nb, nb):
        got = _LOCAL.bufs = (np.empty((nb, nb)), np.empty((nb, nb)))
    return got


def qr_got(tiles_a: dict, tiles_t: dict, X: np.ndarray, nb: int) -> tuple:
    """``(Q.(R.X), R^T.(R.X))`` in float64 from the factored tiles.

    ``Y = R.X``, then the reflectors last first: for k descending, for m
    descending ``W = T_mk.(Y_k + V2^T.Y_m); Y_k -= W; Y_m -= V2.W``, then
    ``Y_k -= V_k.(T_kk.(V_k^T.Y_k))``.  Step (m, k) waits for (m+1, k), which
    leaves Y_k, and for (m, k+1), which leaves Y_m, and for nothing else: the
    steps of one anti-diagonal m + k run side by side."""
    nt = 1 + max(m for m, _ in tiles_a)
    r = _r_tiles(tiles_a)
    Y = apply(r, X, nb)
    rtr = apply_t(r, Y, nb)
    eye = np.eye(nb)
    strict = np.tril(np.ones((nb, nb), bool), -1)

    def rows(i: int) -> slice:
        return slice(i * nb, (i + 1) * nb)

    def step(mk: tuple) -> None:
        m, k = mk
        v, t = _bufs(nb)
        np.copyto(v, tiles_a[m, k])
        np.copyto(t, tiles_t[m, k])
        if m == k:
            np.multiply(v, strict, out=v)
            v += eye
            Y[rows(k)] -= v @ (t @ (v.T @ Y[rows(k)]))
            return
        w = t @ (Y[rows(k)] + v.T @ Y[rows(m)])
        Y[rows(k)] -= w
        Y[rows(m)] -= v @ w

    with reft.threadpool_limits(1, user_api="blas"):
        for d in range(2 * (nt - 1), -1, -1):
            front = [(d - k, k) for k in range(max(0, d - nt + 1),
                                               d // 2 + 1)]
            list(_pool().map(step, front))
    return Y, rtr


def qr_control(tiles: dict, nb: int, store: str = "bfloat16",
               precision: str = "highest") -> tuple[dict, dict]:
    """The flat-tree tile QR with every written tile stored as ``store``
    (the control: bfloat16) between tile operations, each operation computed
    in f32, every product at ``precision``, from the stored values.  A's and
    T's tiles as float32 numpy arrays, in the program's layout.

    ``store="float32"`` is a sound run (tests hold it), and with
    ``precision="high"`` the second control, read on the chip to place the
    limit (PERF.md, section 7): every product at the precision next below
    the configuration's, three bf16 passes for six.  A CPU computes both
    precisions alike, so it is no control there."""
    import jax
    import jax.numpy as jnp
    store, f32 = jnp.dtype(store), jnp.float32
    nt = 1 + max(m for m, _ in tiles)
    eye = jnp.eye(nb, dtype=f32)

    def larft(v, tau):
        # tau = 0 (LAPACK's last reflector of a square tile) is H = I: its
        # row and column of T are zero.  Left as 1 / tau = inf on the
        # diagonal, the CPU's inverse gave that and the TPU's did not (the
        # control then read 1.1e-2 with f32 tiles: PERF.md, PR 36)
        live = tau != 0
        s = jnp.where(live[:, None] & live[None, :], jnp.triu(v.T @ v, 1), 0)
        s = s + jnp.diag(jnp.where(live, 1.0 / jnp.where(live, tau, 1), 1.0))
        t = jax.scipy.linalg.solve_triangular(
            s, jnp.eye(len(tau), dtype=f32), lower=False)
        return jnp.where(live[None, :], t, 0)

    def jit(fn):
        def run(*tiles_in):
            with jax.default_matmul_precision(precision):
                out = fn(*(t.astype(f32) for t in tiles_in))
            return tuple(o.astype(store) for o in out)
        return jax.jit(run)

    @jit
    def geqrt(a):
        ht, tau = jnp.linalg.qr(a, mode="raw")
        h = ht.T
        return h, larft(jnp.tril(h, -1) + eye, tau)

    @jit
    def unmqr(vkk, t, c):
        v = jnp.tril(vkk, -1) + eye
        return (c - v @ (t.T @ (v.T @ c)),)

    @jit
    def tsqrt(rkk, b):
        ht, tau = jnp.linalg.qr(jnp.concatenate([jnp.triu(rkk), b]),
                                mode="raw")
        h = ht.T
        v2 = h[nb:]
        return (jnp.triu(h[:nb]) + jnp.tril(rkk, -1), v2,
                larft(jnp.concatenate([eye, v2]), tau))

    @jit
    def tsmqr(a1, a2, v2, t):
        w = t.T @ (a1 + v2.T @ a2)
        return a1 - w, a2 - v2 @ w

    a = {key: jnp.asarray(t).astype(store) for key, t in tiles.items()}
    tt = {}
    for k in range(nt):
        a[k, k], tt[k, k] = geqrt(a[k, k])
        for n in range(k + 1, nt):
            (a[k, n],) = unmqr(a[k, k], tt[k, k], a[k, n])
        for m in range(k + 1, nt):
            a[k, k], a[m, k], tt[m, k] = tsqrt(a[k, k], a[m, k])
            for n in range(k + 1, nt):
                a[k, n], a[m, n] = tsmqr(a[k, n], a[m, n], a[m, k], tt[m, k])
    return ({key: np.asarray(t.astype(f32)) for key, t in a.items()},
            {key: np.asarray(t.astype(f32)) for key, t in tt.items()})
