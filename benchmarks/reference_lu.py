"""The plain reference of LU with partial pivoting (``getrf-44k``): seeded
data, the probe product and the two pivoting checks that decide
``correct``, and the lower-precision control.  numpy and plain
``jax.numpy`` only, as ``reference.py``: nothing here imports
``parsec_tpu`` or takes anything the program has made.

What the program leaves behind (LAPACK's ``sgetrf`` layout): U on and above
the diagonal of A's tiles, the unit-lower L strictly below it with its rows
permuted as every later step's swaps left them, and the pivots ``ipiv``
(0-based global rows: row i was swapped with row ipiv[i], i ascending), so
that ``P.A = L.U`` with ``P`` the swaps in that order.  The numbers compared,
in float64 on seeded probes X, are

    |P^T.(L.(U.X)) - A.X| / |A.X|        (``testing_sgetrf``'s residual)
    max |l| over L                        (partial pivoting: at most 1)
    whether ipiv[i] lies in [i, N) for every i

The control is the same right-looking algorithm with the tile as its block,
in plain ``jnp``, every product at ``precision`` (``lu_control``).
"""

from __future__ import annotations

import numpy as np

import reference as ref
import reference_tiled as reft
from reference_qr import apply, dense_of  # noqa: F401  (A.X from tiles)

MAT_LU_TILE = 11


def lu_tiles(seed: int, n: int, nb: int) -> dict:
    """All tiles ``(m, n)`` of a square f32 matrix of standard normals, each
    a contiguous (nb, nb) array drawn from its own stream: dense, not
    diagonally dominant, so a solve pivots."""
    nt = n // nb
    tiles = {(m, k): None for m in range(nt) for k in range(nt)}

    def fill(m: int) -> None:
        for k in range(nt):
            tiles[m, k] = np.random.default_rng(
                [seed, MAT_LU_TILE, m, k]).standard_normal(
                    (nb, nb), dtype=np.float32)

    ref._parallel(nt, fill)
    return tiles


def unswap(Y: np.ndarray, ipiv: np.ndarray) -> np.ndarray:
    """``P^T.Y`` in place: the swaps undone, last first."""
    for i in range(len(ipiv) - 1, -1, -1):
        p = ipiv[i]
        if p != i:
            Y[[i, p]] = Y[[p, i]]
    return Y


def lu_got(tiles_a: dict, ipiv: np.ndarray, X: np.ndarray,
           nb: int) -> tuple:
    """``(P^T.(L.(U.X)), max |l|, ipiv valid)`` in float64 from the
    factored tiles, in one pass over them for each product."""
    n = X.shape[0]
    upper = {k: t for k, t in tiles_a.items() if k[0] <= k[1]}
    lower = {k: t for k, t in tiles_a.items() if k[0] >= k[1]}
    tri_u = np.triu(np.ones((nb, nb), bool))
    tri_l = np.tril(np.ones((nb, nb), bool), -1)
    most = [0.0] * len(lower)
    index = {k: i for i, k in enumerate(lower)}

    def add_u(Y, t, m, k):
        if m == k:
            t = np.where(tri_u, t, 0.0)
        Y[m * nb:(m + 1) * nb] += t @ X[k * nb:(k + 1) * nb]

    Y = reft._summed(upper, X.shape, nb, add_u)

    def add_l(Z, t, m, k):
        if m == k:
            t = np.where(tri_l, t, 0.0)
        most[index[m, k]] = float(np.abs(t).max())
        Z[m * nb:(m + 1) * nb] += t @ Y[k * nb:(k + 1) * nb]

    Z = Y + reft._summed(lower, X.shape, nb, add_l)
    ipiv = np.asarray(ipiv, np.int64)
    valid = ipiv.shape == (n,) and bool(
        np.all((ipiv >= np.arange(n)) & (ipiv < n)))
    if valid:
        unswap(Z, ipiv)
    return Z, max(most), valid


def lu_control(tiles: dict, nb: int, precision: str = "high",
               options: dict | None = None) -> tuple[dict, np.ndarray]:
    """LU with partial pivoting by the program's algorithm (right-looking,
    a panel of one tile column's width), in plain ``jnp`` on tile columns
    of the whole height, every product and triangular solve at
    ``precision``.  Step k rolls a column up by k tiles and zeroes what
    wrapped around, so one program serves every k: a zero row never wins a
    pivot search and stays zero.  A's tiles as float32 numpy arrays and
    ipiv, in the program's layout.  ``options``: XLA's, for the panel
    (the TPU's compiler needs more scoped VMEM for a tall LU:
    ``models/lu.py``).  A CPU computes every precision alike, so it is no
    control there: with ``precision="highest"`` it is a sound run."""
    import jax
    import jax.numpy as jnp
    nt = 1 + max(m for m, _ in tiles)
    n = nt * nb
    rows = jnp.arange(n)

    def lifted(col, k):
        s = k * nb
        return jnp.where((rows < n - s)[:, None], jnp.roll(col, -s, 0), 0.0)

    def panel(col, k):
        with jax.default_matmul_precision(precision):
            lu, piv, perm = jax.lax.linalg.lu(lifted(col, k))
        s = k * nb
        out = jnp.where((rows >= s)[:, None], jnp.roll(lu, s, 0), col)
        return out, perm, piv + s

    def right(col, lcol, perm, k):
        with jax.default_matmul_precision(precision):
            s = k * nb
            x = jnp.roll(col, -s, 0)[perm]
            lk = lifted(lcol, k)
            u = jax.scipy.linalg.solve_triangular(
                jnp.tril(lk[:nb], -1) + jnp.eye(nb), x[:nb], lower=True,
                unit_diagonal=True)
            x = jnp.concatenate([u, x[nb:] - lk[nb:] @ u])
            return jnp.where((rows >= s)[:, None], jnp.roll(x, s, 0), col)

    def left(col, perm, k):
        s = k * nb
        x = jnp.roll(col, -s, 0)[perm]
        return jnp.where((rows >= s)[:, None], jnp.roll(x, s, 0), col)

    panel = jax.jit(panel, compiler_options=options)
    right, left = jax.jit(right), jax.jit(left)
    cols = [jnp.asarray(np.concatenate([tiles[m, k] for m in range(nt)]))
            for k in range(nt)]
    ipiv = []
    for k in range(nt):
        cols[k], perm, piv = panel(cols[k], k)
        ipiv.append(np.asarray(piv))
        for j in range(nt):
            if j > k:
                cols[j] = right(cols[j], cols[k], perm, k)
            elif j < k:
                cols[j] = left(cols[j], perm, k)
    got = {}
    for k in range(nt):
        c = np.asarray(cols[k])
        cols[k] = None
        for m in range(nt):
            got[m, k] = np.ascontiguousarray(c[m * nb:(m + 1) * nb])
    return got, np.concatenate(ipiv)
