"""The plain reference of the two solves, the seeded data, and the
lower-precision control.  numpy and plain ``jax.numpy`` only: nothing here
imports ``parsec_tpu`` or takes anything the program has made.

What is compared is a relative gap on seeded probe vectors, in float64 on the
host, so every element of every result tile counts:

- GEMM: ``|C.X - A.(B.X)| / |A.(B.X)|``.  A and B are drawn so that every
  value is exactly representable in bfloat16 (see ``gemm_data``): the
  configuration states f32 tiles at the default matmul precision, whose bf16
  operand pass then loses nothing, and only the f32 accumulation separates the
  program from float64.
- Cholesky: ``|L.(Lt.X) - A.X| / |A.X|``, the residual ``testing_?potrf``
  checks, from the lower tiles the program wrote back.

The control is the same solve in plain ``jnp`` with every tile *stored* in
bfloat16 between tile operations, the step below the f32 tiles the
configurations state (``gemm_control``, ``potrf_control``).
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

NPROBE = 4
# fixed integer per matrix: block i of matrix M under --seed s is drawn from
# default_rng([s, M, i]) whatever the run
MAT_A, MAT_B, MAT_PROBE, MAT_SPD = 1, 2, 3, 4


def _round_to_bf16(x: np.ndarray) -> None:
    """Round f32 values to the nearest bfloat16 (ties to even), in place,
    keeping the f32 container."""
    u = x.view(np.uint32)
    u += np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1))
    u &= np.uint32(0xFFFF0000)


def _parallel(n: int, fn) -> None:
    """``fn(i)`` for i < n on eight threads: numpy drops the GIL in its
    generators and loops, and first touches of fresh pages run side by side."""
    with ThreadPoolExecutor(8) as pool:
        list(pool.map(fn, range(n)))


def _normal_blocks(seed: int, mat: int, shape: tuple, bf16: bool) -> np.ndarray:
    """A float32 array of standard normals, block ``i`` along axis 0 from its
    own stream."""
    out = np.empty(shape, np.float32)

    def fill(i: int) -> None:
        rng = np.random.default_rng([seed, mat, i])
        rng.standard_normal(out[i].shape, dtype=np.float32, out=out[i])
        if bf16:
            _round_to_bf16(out[i])

    _parallel(shape[0], fill)
    return out


def zero_blocks(shape: tuple) -> np.ndarray:
    """Float32 zeros with every page touched (``np.zeros`` alone maps them
    lazily, and the first solve to read them would pay)."""
    out = np.empty(shape, np.float32)
    _parallel(shape[0], lambda i: out[i].fill(0.0))
    return out


def probes(seed: int, n: int) -> np.ndarray:
    return np.random.default_rng([seed, MAT_PROBE]).standard_normal(
        (n, NPROBE))


def gemm_data(seed: int, n: int, nb: int) -> tuple[np.ndarray, np.ndarray]:
    """A and B as tile-major arrays ``T[m, k]`` of contiguous (nb, nb) f32
    tiles, every value bfloat16-representable."""
    nt = n // nb
    return (_normal_blocks(seed, MAT_A, (nt, nt, nb, nb), bf16=True),
            _normal_blocks(seed, MAT_B, (nt, nt, nb, nb), bf16=True))


def spd_data(seed: int, n: int) -> np.ndarray:
    """A dense, symmetric, diagonally dominant (so SPD by Gershgorin) f32
    matrix in O(n^2) host work: off-diagonal entries ~N(0, 1/2), diagonal the
    row's absolute sum plus one (as ``models/cholesky.py:make_spd_fast``)."""
    rows = 1024 if n % 1024 == 0 else n
    g = _normal_blocks(seed, MAT_SPD, (n // rows, rows, n),
                       bf16=False).reshape(n, n)
    a = np.empty((n, n), np.float32)

    def fill(i: int) -> None:
        r = slice(i * rows, (i + 1) * rows)
        np.add(g[r], g[:, r].T, out=a[r])
        a[r] *= 0.5
        d = np.arange(r.start, r.stop)
        a[d, d] = 0.0
        a[d, d] = np.abs(a[r]).sum(axis=1) + 1.0

    _parallel(n // rows, fill)
    return a


def gap(got: np.ndarray, want: np.ndarray) -> float:
    """Relative Frobenius gap; a non-finite result reads infinite."""
    if not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def apply_tiles(tiles, X: np.ndarray, nb: int, rows: int) -> np.ndarray:
    """``T.X`` in float64 over ``((m, n), tile)`` pairs."""
    Y = np.zeros((rows, X.shape[1]))
    for (m, n), t in tiles:
        Y[m * nb:(m + 1) * nb] += np.asarray(t, np.float64) @ \
            X[n * nb:(n + 1) * nb]
    return Y


def grid(T: np.ndarray):
    """``((m, n), tile)`` pairs of a tile-major array."""
    return (((m, n), T[m, n]) for m in range(T.shape[0])
            for n in range(T.shape[1]))


def gemm_want(A4: np.ndarray, B4: np.ndarray, X: np.ndarray) -> np.ndarray:
    nb, n = A4.shape[2], X.shape[0]
    return apply_tiles(grid(A4), apply_tiles(grid(B4), X, nb, n), nb, n)


def potrf_want(a: np.ndarray, X: np.ndarray, rows: int = 1024) -> np.ndarray:
    return np.concatenate([a[i:i + rows].astype(np.float64) @ X
                           for i in range(0, a.shape[0], rows)])


def potrf_got(tiles: dict, X: np.ndarray, nb: int) -> np.ndarray:
    """``L.(Lt.X)`` from the factored lower tiles.  The diagonal tiles keep
    A's strict upper part: only their lower triangle is L."""
    L = [((m, k), np.tril(np.asarray(t, np.float64)) if m == k else t)
         for (m, k), t in tiles.items()]
    Y = np.zeros_like(X)
    for (m, k), t in L:
        Y[k * nb:(k + 1) * nb] += np.asarray(t, np.float64).T @ \
            X[m * nb:(m + 1) * nb]
    return apply_tiles(L, Y, nb, X.shape[0])


# ---------------------------------------------------------------------------
# the control: the same solves with tiles stored in a lower precision
# ---------------------------------------------------------------------------

def dense_of(T4: np.ndarray) -> np.ndarray:
    nt, _, nb, _ = T4.shape
    return T4.transpose(0, 2, 1, 3).reshape(nt * nb, nt * nb)


def tiles_of(dense: np.ndarray, nb: int, lower: bool = False) -> dict:
    nt = dense.shape[0] // nb
    return {(m, n): dense[m * nb:(m + 1) * nb, n * nb:(n + 1) * nb]
            for m in range(nt) for n in range(m + 1 if lower else nt)}


def gemm_control(A4: np.ndarray, B4: np.ndarray) -> np.ndarray:
    """C as the k-chain of tile GEMMs leaves it when A, B and the running C
    are stored in bfloat16: ``C <- bf16(C + A[:, k].B[k, :])``, products
    accumulated in f32.  Returns the dense C as float32."""
    import jax.numpy as jnp
    store = jnp.bfloat16
    nb = A4.shape[2]
    a = jnp.asarray(dense_of(A4)).astype(store)
    b = jnp.asarray(dense_of(B4)).astype(store)
    c = jnp.zeros((a.shape[0], b.shape[1]), store)
    for k in range(0, a.shape[1], nb):
        acc = jnp.dot(a[:, k:k + nb], b[k:k + nb],
                      preferred_element_type=jnp.float32)
        c = (c.astype(jnp.float32) + acc).astype(store)
    return np.asarray(c.astype(jnp.float32))


def potrf_control(a: np.ndarray, nb: int) -> np.ndarray:
    """The right-looking blocked Cholesky with the matrix stored in
    bfloat16 after every panel step: factor the diagonal block, solve the
    panel, update the trailing matrix, each computed in f32 from the stored
    values.  Returns the dense lower factor as float32."""
    import jax.numpy as jnp
    from jax.scipy.linalg import solve_triangular
    store = jnp.bfloat16
    n = a.shape[0]
    s = jnp.asarray(a).astype(store)
    for k in range(0, n, nb):
        e = k + nb
        lkk = jnp.linalg.cholesky(s[k:e, k:e].astype(jnp.float32))
        s = s.at[k:e, k:e].set(jnp.tril(lkk).astype(store))
        if e == n:
            break
        lkk = s[k:e, k:e].astype(jnp.float32)
        panel = solve_triangular(
            lkk, s[e:, k:e].astype(jnp.float32).T, lower=True).T
        s = s.at[e:, k:e].set(panel.astype(store))
        p = s[e:, k:e]
        upd = s[e:, e:].astype(jnp.float32) - jnp.dot(
            p, p.T, preferred_element_type=jnp.float32)
        s = s.at[e:, e:].set(upd.astype(store))
    return np.asarray(jnp.tril(s.astype(jnp.float32)))
