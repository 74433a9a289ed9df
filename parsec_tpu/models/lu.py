"""Tiled LU factorization: two PTGs.

``tiled_getrf_ptg`` is **LU with partial pivoting** (LAPACK's blocked
``sgetrf``, DPLASMA's ``dplasma_sgetrf_1d``): ``P.A = L.U`` for any
nonsingular A, every multiplier |l| <= 1.  ``tiled_lu_ptg`` is the **nopiv**
variant below, which needs a diagonally dominant (or otherwise nopiv-stable)
input.

**Nopiv** (``tiled_lu_ptg``): the classic right-looking tile algorithm (the
dplasma ``dgetrf_nopiv`` shape; same task-class anatomy as Cholesky but with
TWO panel classes):

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

**Partial pivoting** (``tiled_getrf_ptg``): a pivot is the largest entry of
what is left of its column, so a panel task sees the whole tile column
A(k:NT-1, k), and a swap task the whole column A(k:NT-1, n).  Such a class
has a flow a tile row (``T0`` .. ``T{NT-1}``); an instance at step k holds
the NT - k from the diagonal down and leaves the rows above ``null``.  Its
input deps outnumber the 64 bits of a dep mask, so the runtime tracks it by
count (``TaskClass.counted``).  The classes:

- ``PANEL(k)``     — LU with partial pivoting of A(k:NT-1, k): the packed L
  and U_kk in those tiles, and the pivots in IPIV(k);
- ``SWPTRSM(k,n)`` — n > k: IPIV(k)'s swaps on A(k:NT-1, n), then
  ``A(k,n) <- L_kk^-1 . A(k,n)``;
- ``GEMM(m,n,k)``  — ``A(m,n) -= A(m,k) . A(k,n)``, m, n > k;
- ``SWPLEFT(k,n)`` — n < k: IPIV(k)'s swaps on A(k:NT-1, n), chained over k
  for each column (LAPACK's convention: L comes back row-permuted), after
  every GEMM of step n has read column n (a CTL join on SWPTRSM(n+1, .)).

IPIV(k) is an int32 tile of 4 x nb: row 0 LAPACK's ``ipiv`` in 0-based
global rows (row k*nb + i was swapped with row ipiv[i], i ascending), rows
1-3 the same permutation as the row moves the swap tasks make (panel-local
rows: the source of each of the nb top rows; the rows below the top block
that change, and the top row each receives; -1 past the last).  A tile of
IPIV enters holding the identity (``ipiv_matrix``): PANEL(k) reads its base
row k*nb there.  The host reads no pivot before the solve's flush.

On the device the row flows are padded with tiles of zeros to a multiple of
``BUCKET`` (``TaskClassBuilder.pad_rows``): a zero row never wins a pivot
search and stays zero, so the kernels need not know the height, and the
programs of a solve are one per bucket and lane count.  Every product and
triangular solve of these kernels is traced at ``Precision.HIGHEST``
(``qr.py:_highest``).
"""

from __future__ import annotations

from typing import Any

import numpy as np

from .. import ptg
from ..data_dist.matrix import TiledMatrix
from ..device.kernels import register_kernel, traceable_body
from .qr import _highest

# the row flows of a wide class are padded with zero tiles to a multiple of
# this on the device: one program a bucket of heights (PERF.md, PR 42)
BUCKET = 8


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
# partial pivoting — CPU bodies (float64 inside)
# ---------------------------------------------------------------------------


def ipiv_matrix(n: int, nb: int) -> TiledMatrix:
    """IPIV for an n x n matrix in nb tiles: one int32 tile of 4 x nb a
    panel, each holding the identity (its base row, the plan of no swap)."""
    from ..data_dist.matrix import TwoDimBlockCyclic

    def identity(m: int, k: int, shape: tuple) -> np.ndarray:
        t = np.full((4, nb), -1, np.int32)
        t[0] = k * nb + np.arange(nb)
        t[1] = np.arange(nb)
        return t

    return TwoDimBlockCyclic("IPIV", 4, n, 4, nb, dtype=np.int32,
                             init_fn=identity)


def swap_plan(perm: np.ndarray, nb: int) -> tuple:
    """Rows 1-3 of IPIV from the panel's permutation (row i of the
    factored stack is row ``perm[i]`` of the stack before)."""
    m = perm.shape[0]
    moved = np.flatnonzero((perm != np.arange(m)) & (np.arange(m) >= nb))
    dest = np.full(nb, -1, np.int64)
    src = np.full(nb, -1, np.int64)
    dest[:moved.size] = moved
    src[:moved.size] = perm[moved]
    return perm[:nb], dest, src


def _rows(task: Any) -> list:
    """The task's present row flows (``T0`` ..), top down."""
    return [c for f, c in zip(task.task_class.flows, task.data)
            if f.name[0] == "T" and f.name[1:].isdigit() and c is not None]


def _panel_cpu(es: Any, task: Any, g: Any, l: Any) -> None:
    rows = _rows(task)
    nb = np.asarray(rows[0].value).shape[0]
    x = np.concatenate([np.asarray(c.value, np.float64) for c in rows])
    m = x.shape[0]
    perm = np.arange(m)
    piv = np.zeros(nb, np.int64)
    for j in range(nb):
        p = j + int(np.argmax(np.abs(x[j:, j])))
        piv[j] = p
        if p != j:
            x[[j, p]] = x[[p, j]]
            perm[[j, p]] = perm[[p, j]]
        if x[j, j] != 0.0:
            x[j + 1:, j] /= x[j, j]
        x[j + 1:, j + 1:] -= np.outer(x[j + 1:, j], x[j, j + 1:])
    for i, c in enumerate(rows):
        c.value = x[i * nb:(i + 1) * nb].astype(np.float32)
        c.version += 1
    pc = task.flow_data("P")
    top, dest, src = swap_plan(perm, nb)
    pc.value = np.stack([l.k * nb + piv, top, dest, src]).astype(np.int32)
    pc.version += 1


def _swap_np(task: Any, k: int) -> tuple[list, np.ndarray]:
    """IPIV(k)'s swaps, LAPACK's ``laswp``, on the stack of present rows."""
    rows = _rows(task)
    x = np.concatenate([np.asarray(c.value, np.float64) for c in rows])
    ipiv = np.asarray(task.flow_data("P").value)[0]
    nb = ipiv.shape[0]
    for i, p in enumerate(ipiv - k * nb):
        if p != i:
            x[[i, p]] = x[[p, i]]
    return rows, x


def _land_rows(rows: list, x: np.ndarray) -> None:
    nb = x.shape[1]
    for i, c in enumerate(rows):
        c.value = x[i * nb:(i + 1) * nb].astype(np.float32)
        c.version += 1


def _swptrsm_cpu(es: Any, task: Any, g: Any, l: Any) -> None:
    rows, x = _swap_np(task, l.k)
    nb = x.shape[1]
    lk = np.asarray(task.flow_data("L").value, np.float64)
    x[:nb] = np.linalg.solve(np.tril(lk, -1) + np.eye(nb), x[:nb])
    _land_rows(rows, x)


def _swpleft_cpu(es: Any, task: Any, g: Any, l: Any) -> None:
    _land_rows(*_swap_np(task, l.k))


def _gemm64_cpu(es: Any, task: Any, g: Any, l: Any) -> None:
    a = np.asarray(task.flow_data("A").value, np.float64)
    b = np.asarray(task.flow_data("B").value, np.float64)
    c = task.flow_data("C")
    c.value = (np.asarray(c.value, np.float64) - a @ b).astype(np.float32)
    c.version += 1


# ---------------------------------------------------------------------------
# partial pivoting — TPU traceables (every product at HIGHEST)
# ---------------------------------------------------------------------------


@_highest
def _panel_traceable(p, *rows):
    """``P . [rows] = L . U``: the packed factors back in the rows, IPIV(k)
    in ``p``'s place.  ``rows``: the column from the diagonal tile down,
    tiles of zeros after (they stay zero: a multiplier of a zero row is
    zero, and a zero never wins a pivot search over a nonzero)."""
    jax, jnp, _ = _jnp()
    nb = rows[0].shape[0]
    x = jnp.concatenate([jnp.asarray(r, jnp.float32) for r in rows])
    m = x.shape[0]
    lu, piv, perm = jax.lax.linalg.lu(x)
    r = jnp.arange(m)
    dest = jnp.nonzero((perm != r) & (r >= nb), size=nb, fill_value=-1)[0]
    src = jnp.where(dest >= 0, perm[jnp.maximum(dest, 0)], -1)
    plan = jnp.stack([p[0, 0] + piv, perm[:nb], dest, src]).astype(jnp.int32)
    return (plan,) + tuple(lu[i * nb:(i + 1) * nb] for i in range(len(rows)))


# LAPACK's blocked LU in XLA's TPU expansion keeps a 128-column block of
# the whole stack in scoped VMEM: 16 MiB by default holds a stack of about
# 14 tiles; a column of 48 needs 44 MiB (PR 42, step 0: PERF.md)
_panel_traceable.tpu_compiler_options = {
    "xla_tpu_scoped_vmem_limit_kib": "98304"}


def _swapped(p, rows):
    """The rows after IPIV's swaps, from rows 1-3 of ``p``: the top tile
    gathered whole, every other tile rewritten where a top row lands in it
    (step 0 of PR 42 chose this form: PERF.md)."""
    _, jnp, _ = _jnp()
    nb = rows[0].shape[0]
    m = nb * len(rows)
    top, dest, src = p[1], p[2], p[3]
    dest = jnp.where(dest >= 0, dest, m)
    idx = jnp.full((m,), -1, jnp.int32).at[dest].set(src, mode="drop")
    t0 = jnp.asarray(rows[0], jnp.float32)
    out = [jnp.concatenate(rows)[top]]
    for i in range(1, len(rows)):
        ix = idx[i * nb:(i + 1) * nb]
        out.append(jnp.where(ix[:, None] >= 0, t0[jnp.maximum(ix, 0)],
                             rows[i]))
    return out


@_highest
def _swptrsm_traceable(lk, p, *rows):
    """IPIV(k)'s swaps on the column, then ``A(k,n) <- L_kk^-1 . A(k,n)``."""
    _, jnp, jsl = _jnp()
    out = _swapped(p, rows)
    n = lk.shape[0]
    L = jnp.tril(jnp.asarray(lk, jnp.float32), -1) + jnp.eye(n)
    out[0] = jsl.solve_triangular(L, out[0], lower=True, unit_diagonal=True)
    return tuple(out)


@_highest
def _swpleft_traceable(p, *rows):
    return tuple(_swapped(p, rows))


@_highest
def _gemm_hi_traceable(a, b, c):
    return _gemm_nn_traceable(a, b, c)


_GETRF = {"getrf_panel": _panel_traceable,
          "getrf_swptrsm": _swptrsm_traceable,
          "getrf_swpleft": _swpleft_traceable,
          "getrf_gemm": _gemm_hi_traceable}


def _register_getrf() -> None:
    from ..ptg.lowering import register_traceable
    for name, tr in _GETRF.items():
        register_kernel(name, "tpu", traceable_body(tr))
        register_traceable(name, tr)


_register_getrf()


# ---------------------------------------------------------------------------
# the PTGs
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


def tiled_getrf_ptg(A: TiledMatrix, IPIV: TiledMatrix,
                    devices: str = "auto") -> "ptg.PTGTaskpool":
    """Build the LU with partial pivoting PTG over a square tile grid: A
    is factored in place (U on and above the diagonal, L below it, rows
    permuted as LAPACK leaves them), IPIV(0, k) takes panel k's pivots
    (``ipiv_matrix`` makes it)."""
    NT = A.mt
    assert A.mt == A.nt, "LU needs a square tile grid"
    assert (IPIV.mt, IPIV.nt, IPIV.nb) == (1, NT, A.nb), "IPIV: 1 x NT tiles"
    p = ptg.PTGBuilder("getrf", A=A, IPIV=IPIV, NT=NT)
    last = lambda g: g.NT - 1                                   # noqa: E731
    # the critical path (PANEL(k+1), and what it waits for of step k) ahead
    # of the rest of step k: a lookahead of one
    crit = 8 * NT + 8
    row_m = lambda g, l: f"T{l.m}"                              # noqa: E731
    row_k = lambda g, l: f"T{l.k}"                              # noqa: E731

    # ---- PANEL(k) ---------------------------------------------------------
    pa = p.task("PANEL", k=ptg.span(0, lambda g, l: last(g)))
    pa.affinity("A", lambda g, l: (l.k, l.k))
    pa.priority(lambda g, l: crit + 4 * (g.NT - l.k) + 3)
    fP = pa.flow("P", ptg.RW)
    fP.input(data=("IPIV", lambda g, l: (0, l.k)))
    fP.output(succ=("SWPTRSM", "P",
                    lambda g, l: [{"k": l.k, "n": n}
                                  for n in range(l.k + 1, g.NT)]),
              guard=lambda g, l: l.k < last(g))
    fP.output(succ=("SWPLEFT", "P",
                    lambda g, l: [{"k": l.k, "n": n} for n in range(l.k)]),
              guard=lambda g, l: l.k > 0)
    fP.output(data=("IPIV", lambda g, l: (0, l.k)))
    for i in range(NT):
        f = pa.flow(f"T{i}", ptg.RW)
        f.input(null=True, guard=lambda g, l, i=i: i < l.k)
        f.input(data=("A", lambda g, l, i=i: (i, l.k)),
                guard=lambda g, l: l.k == 0)
        f.input(pred=("GEMM", "C", lambda g, l, i=i: {"m": i, "n": l.k,
                                                      "k": l.k - 1}),
                guard=lambda g, l, i=i: 0 < l.k <= i)
        f.output(succ=("SWPTRSM", "L",
                       lambda g, l: [{"k": l.k, "n": n}
                                     for n in range(l.k + 1, g.NT)]),
                 guard=lambda g, l, i=i: i == l.k < last(g))
        f.output(data=("A", lambda g, l: (l.k, l.k)),
                 guard=lambda g, l, i=i: i == l.k)
        f.output(succ=("GEMM", "A",
                       lambda g, l, i=i: [{"m": i, "n": n, "k": l.k}
                                          for n in range(l.k + 1, g.NT)]),
                 guard=lambda g, l, i=i: i > l.k)
        f.output(succ=("SWPLEFT", f"T{i}",
                       lambda g, l: {"k": l.k + 1, "n": l.k}),
                 guard=lambda g, l, i=i: i > l.k)

    # ---- SWPTRSM(k, n), n > k ---------------------------------------------
    st = p.task("SWPTRSM",
                k=ptg.span(0, lambda g, l: g.NT - 2),
                n=ptg.span(lambda g, l: l.k + 1, lambda g, l: last(g)))
    st.affinity("A", lambda g, l: (l.k, l.n))
    st.priority(lambda g, l: 4 * (g.NT - l.k) + 2
                + (crit if l.n == l.k + 1 else 0))
    st.flow("L", ptg.READ).input(pred=("PANEL", row_k,
                                       lambda g, l: {"k": l.k}))
    st.flow("P", ptg.READ).input(pred=("PANEL", "P",
                                       lambda g, l: {"k": l.k}))
    st.flow("X", ptg.CTL).output(
        succ=("SWPLEFT", "X", lambda g, l: {"k": l.k, "n": l.k - 1}),
        guard=lambda g, l: l.k > 0)
    for i in range(NT):
        f = st.flow(f"T{i}", ptg.RW)
        f.input(null=True, guard=lambda g, l, i=i: i < l.k)
        f.input(data=("A", lambda g, l, i=i: (i, l.n)),
                guard=lambda g, l: l.k == 0)
        f.input(pred=("GEMM", "C", lambda g, l, i=i: {"m": i, "n": l.n,
                                                      "k": l.k - 1}),
                guard=lambda g, l, i=i: 0 < l.k <= i)
        f.output(succ=("GEMM", "B",
                       lambda g, l: [{"m": m, "n": l.n, "k": l.k}
                                     for m in range(l.k + 1, g.NT)]),
                 guard=lambda g, l, i=i: i == l.k)
        f.output(data=("A", lambda g, l: (l.k, l.n)),
                 guard=lambda g, l, i=i: i == l.k)
        f.output(succ=("GEMM", "C",
                       lambda g, l, i=i: {"m": i, "n": l.n, "k": l.k}),
                 guard=lambda g, l, i=i: i > l.k)

    # ---- GEMM(m, n, k), m, n > k ------------------------------------------
    gm = p.task("GEMM",
                m=ptg.span(1, lambda g, l: last(g)),
                n=ptg.span(1, lambda g, l: last(g)),
                k=ptg.span(0, lambda g, l: min(l.m, l.n) - 1))
    gm.affinity("A", lambda g, l: (l.m, l.n))
    gm.priority(lambda g, l: 4 * (g.NT - l.k) + 1
                + (crit if l.n == l.k + 1 else 0))
    gm.flow("A", ptg.READ).input(pred=("PANEL", row_m,
                                       lambda g, l: {"k": l.k}))
    gm.flow("B", ptg.READ).input(pred=("SWPTRSM", row_k,
                                       lambda g, l: {"k": l.k, "n": l.n}))
    gc = gm.flow("C", ptg.RW)
    gc.input(pred=("SWPTRSM", row_m, lambda g, l: {"k": l.k, "n": l.n}))
    gc.output(succ=("PANEL", row_m, lambda g, l: {"k": l.k + 1}),
              guard=lambda g, l: l.n == l.k + 1)
    gc.output(succ=("SWPTRSM", row_m,
                    lambda g, l: {"k": l.k + 1, "n": l.n}),
              guard=lambda g, l: l.n > l.k + 1)

    # ---- SWPLEFT(k, n), n < k ---------------------------------------------
    sl = p.task("SWPLEFT",
                k=ptg.span(1, lambda g, l: last(g)),
                n=ptg.span(0, lambda g, l: l.k - 1))
    sl.affinity("A", lambda g, l: (l.k, l.n))
    sl.priority(lambda g, l: 0)
    sl.flow("P", ptg.READ).input(pred=("PANEL", "P",
                                       lambda g, l: {"k": l.k}))
    # every GEMM of step n has read column n: SWPTRSM(n+1, j) for every j
    # ran after them all (PANEL(n+1), whose IPIV this task reads, covers
    # the column j = n+1)
    sl.flow("X", ptg.CTL).input(
        pred=("SWPTRSM", "X", lambda g, l: [{"k": l.k, "n": j}
                                            for j in range(l.k + 1, g.NT)]),
        guard=lambda g, l: l.n == l.k - 1, ranged=True)
    for i in range(NT):
        f = sl.flow(f"T{i}", ptg.RW)
        f.input(null=True, guard=lambda g, l, i=i: i < l.k)
        f.input(pred=("PANEL", f"T{i}", lambda g, l: {"k": l.n}),
                guard=lambda g, l, i=i: l.k == l.n + 1 <= i)
        f.input(pred=("SWPLEFT", f"T{i}",
                      lambda g, l: {"k": l.k - 1, "n": l.n}),
                guard=lambda g, l, i=i: l.n + 1 < l.k <= i)
        f.output(succ=("SWPLEFT", f"T{i}",
                       lambda g, l: {"k": l.k + 1, "n": l.n}),
                 guard=lambda g, l, i=i: i > l.k)
        f.output(data=("A", lambda g, l: (l.k, l.n)),
                 guard=lambda g, l, i=i: i == l.k)

    nb = A.mb
    pa.time_estimate(lambda task, dev:
                     (NT - task.locals["k"]) * nb ** 3
                     / (dev.gflops_fp32 * 1e9))
    for t in (st, sl):
        t.time_estimate(lambda task, dev: nb ** 3 / (dev.gflops_fp32 * 1e9))
    gm.time_estimate(lambda task, dev:
                     2 * nb ** 3 / (dev.gflops_fp32 * 1e9))
    st.pad_rows(2, BUCKET)
    sl.pad_rows(1, BUCKET)
    pa.pad_rows(1, BUCKET)

    if devices in ("auto", "tpu"):
        pa.body(device="tpu", dyld="getrf_panel")
        st.body(device="tpu", dyld="getrf_swptrsm")
        sl.body(device="tpu", dyld="getrf_swpleft")
        gm.body(device="tpu", dyld="getrf_gemm")
    if devices in ("auto", "cpu"):
        pa.body(_panel_cpu)
        st.body(_swptrsm_cpu)
        sl.body(_swpleft_cpu)
        gm.body(_gemm64_cpu)
    return p.build()
