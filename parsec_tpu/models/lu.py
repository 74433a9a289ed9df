"""Tiled LU factorization (no pivoting): the second dense-factorization PTG.

The classic right-looking tile algorithm (the dplasma ``dgetrf_nopiv``
shape; same task-class anatomy as Cholesky but with TWO panel classes):

- ``GETRF(k)``  — packed in-place LU of the diagonal tile;
- ``TRSM_L(k,n)`` — row panel:  ``U(k,n) = inv(unit-L_kk) · A(k,n)``;
- ``TRSM_U(m,k)`` — column panel: ``L(m,k) = A(m,k) · inv(U_kk)``;
- ``GEMM(m,n,k)`` — trailing update ``A(m,n) -= L(m,k) · U(k,n)``,
  chained over ``k`` exactly like the Cholesky GEMM chain.

No pivoting: callers must supply diagonally-dominant (or otherwise
nopiv-stable) matrices — the reference's dplasma nopiv variants carry the
same contract.  Triangular applies use the identity-solve + matmul form
(see cholesky.py: measured faster on TPU, and the unrolled lowering CSEs
the one inverse across a whole panel).
"""

from __future__ import annotations

from typing import Any

import numpy as np

from .. import ptg
from ..data_dist.matrix import TiledMatrix
from ..device.kernels import register_kernel, traceable_body


def lu_flops(n: int) -> float:
    return 2.0 * n ** 3 / 3.0


def make_dd(n: int, seed: int = 0) -> np.ndarray:
    """A diagonally dominant matrix (nopiv-stable)."""
    rng = np.random.RandomState(seed)
    a = rng.randn(n, n).astype(np.float32)
    return a + n * np.eye(n, dtype=np.float32)


def unpack_lu(packed: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Split a packed in-place factorization into (unit-L, U)."""
    L = np.tril(packed, -1) + np.eye(packed.shape[0], dtype=packed.dtype)
    return L, np.triu(packed)


# ---------------------------------------------------------------------------
# kernels — CPU (numpy)
# ---------------------------------------------------------------------------


def _getrf_nopiv_np(a: np.ndarray) -> np.ndarray:
    a = np.array(a, dtype=np.float64)
    n = a.shape[0]
    for j in range(n - 1):
        a[j + 1:, j] /= a[j, j]
        a[j + 1:, j + 1:] -= np.outer(a[j + 1:, j], a[j, j + 1:])
    return a.astype(np.float32)


def _getrf_cpu(es: Any, task: Any, g: Any, l: Any) -> None:
    t = task.flow_data("T")
    t.value = _getrf_nopiv_np(np.asarray(t.value))
    t.version += 1


def _trsm_l_cpu(es: Any, task: Any, g: Any, l: Any) -> None:
    packed = np.asarray(task.flow_data("LK").value, np.float64)
    L = np.tril(packed, -1) + np.eye(packed.shape[0])
    c = task.flow_data("C")
    c.value = np.linalg.solve(L, np.asarray(c.value,
                                            np.float64)).astype(np.float32)
    c.version += 1


def _trsm_u_cpu(es: Any, task: Any, g: Any, l: Any) -> None:
    packed = np.asarray(task.flow_data("UK").value, np.float64)
    U = np.triu(packed)
    c = task.flow_data("C")
    c.value = np.linalg.solve(U.T, np.asarray(c.value, np.float64).T) \
        .T.astype(np.float32)
    c.version += 1


def _gemm_cpu(es: Any, task: Any, g: Any, l: Any) -> None:
    a = np.asarray(task.flow_data("A").value, np.float32)
    b = np.asarray(task.flow_data("B").value, np.float32)
    c = task.flow_data("C")
    c.value = np.asarray(c.value, np.float32) - a @ b
    c.version += 1


# ---------------------------------------------------------------------------
# kernels — TPU traceables (shared dyld names with the device bodies)
# ---------------------------------------------------------------------------


def _jnp():
    import jax
    import jax.numpy as jnp
    import jax.scipy.linalg as jsl
    return jax, jnp, jsl


def _getrf_traceable(t):
    jax, jnp, _ = _jnp()
    n = t.shape[0]
    idx = jnp.arange(n)

    def body(j, a):
        piv = a[j, j]
        below = idx > j
        col = jnp.where(below, a[:, j] / piv, a[:, j])
        a = a.at[:, j].set(col)
        row = a[j, :]
        mask = below[:, None] & (idx[None, :] > j)
        return a - jnp.where(mask, jnp.outer(col, row), 0.0)

    return jax.lax.fori_loop(0, n - 1, body, t.astype(jnp.float32))


def _trsm_l_traceable(packed, c):
    from ..ops.gemm import _precision as _mm_precision
    _, jnp, jsl = _jnp()
    n = packed.shape[0]
    L = jnp.tril(packed.astype(jnp.float32), -1) + jnp.eye(n)
    linv = jsl.solve_triangular(L, jnp.eye(n), lower=True,
                                unit_diagonal=True)
    return jnp.matmul(linv, c.astype(jnp.float32),
                      precision=_mm_precision())


def _trsm_u_traceable(packed, c):
    from ..ops.gemm import _precision as _mm_precision
    _, jnp, jsl = _jnp()
    n = packed.shape[0]
    U = jnp.triu(packed.astype(jnp.float32))
    uinv = jsl.solve_triangular(U, jnp.eye(n), lower=False)
    return jnp.matmul(c.astype(jnp.float32), uinv,
                      precision=_mm_precision())


def _gemm_nn_traceable(a, b, c):
    from ..ops.gemm import _precision as _mm_precision
    _, jnp, _ = _jnp()
    return c.astype(jnp.float32) - jnp.dot(
        a.astype(jnp.float32), b.astype(jnp.float32),
        preferred_element_type=jnp.float32, precision=_mm_precision())


register_kernel("lu_getrf", "tpu", traceable_body(_getrf_traceable))
register_kernel("lu_trsm_l", "tpu", traceable_body(_trsm_l_traceable))
register_kernel("lu_trsm_u", "tpu", traceable_body(_trsm_u_traceable))
register_kernel("lu_gemm", "tpu", traceable_body(_gemm_nn_traceable))


def _register_traceables() -> None:
    from ..ptg.lowering import register_traceable
    register_traceable("lu_getrf", _getrf_traceable)
    register_traceable("lu_trsm_l", _trsm_l_traceable)
    register_traceable("lu_trsm_u", _trsm_u_traceable)
    register_traceable("lu_gemm", _gemm_nn_traceable)


_register_traceables()


# ---------------------------------------------------------------------------
# the PTG
# ---------------------------------------------------------------------------


def tiled_lu_ptg(A: TiledMatrix, devices: str = "auto") -> "ptg.PTGTaskpool":
    """Build the nopiv LU PTG over a square tile grid (factors in place)."""
    NT = A.mt
    assert A.mt == A.nt, "LU needs a square tile grid"
    p = ptg.PTGBuilder("lu", A=A, NT=NT)

    # ---- GETRF(k) ---------------------------------------------------------
    ge_ = p.task("GETRF", k=ptg.span(0, lambda g, l: g.NT - 1))
    ge_.affinity("A", lambda g, l: (l.k, l.k))
    ge_.priority(lambda g, l: 4 * (g.NT - l.k) + 4)
    fT = ge_.flow("T", ptg.RW)
    fT.input(data=("A", lambda g, l: (l.k, l.k)), guard=lambda g, l: l.k == 0)
    fT.input(pred=("GEMM", "C", lambda g, l: {"m": l.k, "n": l.k,
                                              "k": l.k - 1}),
             guard=lambda g, l: l.k > 0)
    fT.output(succ=("TRSM_L", "LK",
                    lambda g, l: [{"k": l.k, "n": n}
                                  for n in range(l.k + 1, g.NT)]),
              guard=lambda g, l: l.k < g.NT - 1)
    fT.output(succ=("TRSM_U", "UK",
                    lambda g, l: [{"m": m, "k": l.k}
                                  for m in range(l.k + 1, g.NT)]),
              guard=lambda g, l: l.k < g.NT - 1)
    fT.output(data=("A", lambda g, l: (l.k, l.k)))

    # ---- TRSM_L(k, n): row panel -----------------------------------------
    tl = p.task("TRSM_L",
                k=ptg.span(0, lambda g, l: g.NT - 2),
                n=ptg.span(lambda g, l: l.k + 1, lambda g, l: g.NT - 1))
    tl.affinity("A", lambda g, l: (l.k, l.n))
    tl.priority(lambda g, l: 4 * (g.NT - l.k) + 2)
    tl.flow("LK", ptg.READ).input(
        pred=("GETRF", "T", lambda g, l: {"k": l.k}))
    tlc = tl.flow("C", ptg.RW)
    tlc.input(data=("A", lambda g, l: (l.k, l.n)),
              guard=lambda g, l: l.k == 0)
    tlc.input(pred=("GEMM", "C", lambda g, l: {"m": l.k, "n": l.n,
                                               "k": l.k - 1}),
              guard=lambda g, l: l.k > 0)
    tlc.output(succ=("GEMM", "B",
                     lambda g, l: [{"m": m, "n": l.n, "k": l.k}
                                   for m in range(l.k + 1, g.NT)]))
    tlc.output(data=("A", lambda g, l: (l.k, l.n)))

    # ---- TRSM_U(m, k): column panel --------------------------------------
    tu = p.task("TRSM_U",
                k=ptg.span(0, lambda g, l: g.NT - 2),
                m=ptg.span(lambda g, l: l.k + 1, lambda g, l: g.NT - 1))
    tu.affinity("A", lambda g, l: (l.m, l.k))
    tu.priority(lambda g, l: 4 * (g.NT - l.m) + 2)
    tu.flow("UK", ptg.READ).input(
        pred=("GETRF", "T", lambda g, l: {"k": l.k}))
    tuc = tu.flow("C", ptg.RW)
    tuc.input(data=("A", lambda g, l: (l.m, l.k)),
              guard=lambda g, l: l.k == 0)
    tuc.input(pred=("GEMM", "C", lambda g, l: {"m": l.m, "n": l.k,
                                               "k": l.k - 1}),
              guard=lambda g, l: l.k > 0)
    tuc.output(succ=("GEMM", "A",
                     lambda g, l: [{"m": l.m, "n": n, "k": l.k}
                                   for n in range(l.k + 1, g.NT)]))
    tuc.output(data=("A", lambda g, l: (l.m, l.k)))

    # ---- GEMM(m, n, k): trailing update, chained over k -------------------
    gm = p.task("GEMM",
                m=ptg.span(1, lambda g, l: g.NT - 1),
                n=ptg.span(1, lambda g, l: g.NT - 1),
                k=ptg.span(0, lambda g, l: min(l.m, l.n) - 1))
    gm.affinity("A", lambda g, l: (l.m, l.n))
    gm.priority(lambda g, l: 4 * (g.NT - max(l.m, l.n)))
    gm.flow("A", ptg.READ).input(
        pred=("TRSM_U", "C", lambda g, l: {"m": l.m, "k": l.k}))
    gm.flow("B", ptg.READ).input(
        pred=("TRSM_L", "C", lambda g, l: {"k": l.k, "n": l.n}))
    gc = gm.flow("C", ptg.RW)
    gc.input(data=("A", lambda g, l: (l.m, l.n)),
             guard=lambda g, l: l.k == 0)
    gc.input(pred=("GEMM", "C", lambda g, l: {"m": l.m, "n": l.n,
                                              "k": l.k - 1}),
             guard=lambda g, l: l.k > 0)
    gc.output(succ=("GEMM", "C", lambda g, l: {"m": l.m, "n": l.n,
                                               "k": l.k + 1}),
              guard=lambda g, l: l.k < min(l.m, l.n) - 1)
    gc.output(succ=("GETRF", "T", lambda g, l: {"k": l.m}),
              guard=lambda g, l: l.k == l.m - 1 and l.m == l.n)
    gc.output(succ=("TRSM_L", "C", lambda g, l: {"k": l.m, "n": l.n}),
              guard=lambda g, l: l.k == min(l.m, l.n) - 1 and l.m < l.n)
    gc.output(succ=("TRSM_U", "C", lambda g, l: {"m": l.m, "k": l.n}),
              guard=lambda g, l: l.k == min(l.m, l.n) - 1 and l.m > l.n)

    nb = A.mb
    ge_.time_estimate(lambda task, dev:
                      (2 * nb ** 3 / 3) / (dev.gflops_fp32 * 1e9))
    for t in (tl, tu):
        t.time_estimate(lambda task, dev: nb ** 3 / (dev.gflops_fp32 * 1e9))
    gm.time_estimate(lambda task, dev:
                     2 * nb ** 3 / (dev.gflops_fp32 * 1e9))

    if devices in ("auto", "tpu"):
        ge_.body(device="tpu", dyld="lu_getrf")
        tl.body(device="tpu", dyld="lu_trsm_l")
        tu.body(device="tpu", dyld="lu_trsm_u")
        gm.body(device="tpu", dyld="lu_gemm")
    if devices in ("auto", "cpu"):
        ge_.body(_getrf_cpu)
        tl.body(_trsm_l_cpu)
        tu.body(_trsm_u_cpu)
        gm.body(_gemm_cpu)
    return p.build()
