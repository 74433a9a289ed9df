"""Tiled Cholesky factorization as a PTG taskpool (POTRF/TRSM/SYRK/GEMM).

The classic irregular-guard PTG (the reference's DPLASMA-style ``dpotrf``
shape over the symmetric distribution,
``data_dist/matrix/sym_two_dim_rectangle_cyclic.c``; BASELINE.md staged
config #5): a triangular execution space, four task classes whose mix shifts
with ``k``, and dataflow that crosses ranks along both rows and columns of
the 2-D block-cyclic grid — the canonical stress test for guard evaluation
and the remote-dep protocol that a chain-collapsible GEMM never exercises.

Factorizes the lower-triangular part in place: ``A = L·Lᵀ``.

Dataflow (left-looking, lower):

- ``POTRF(k)``: ``T = chol(A[k,k])``; feeds every ``TRSM(m,k)``.
- ``TRSM(m,k)``: ``C = A[m,k] · inv(Lₖₖᵀ)``; feeds ``SYRK(m,k)`` and the
  ``GEMM``\\ s of row/column ``m``.
- ``SYRK(m,k)``: ``A[m,m] -= C·Cᵀ`` accumulated along ``k``; the last one
  feeds ``POTRF(m)``.
- ``GEMM(m,n,k)``: ``A[m,n] -= A[m,k]·A[n,k]ᵀ`` accumulated along ``k``;
  the last one feeds ``TRSM(m,n)``.

Both CPU (numpy) and TPU (jax, kernel-registry incarnations ``potrf`` /
``trsm_rlt`` / ``syrk_ln`` / ``gemm_nt``) bodies are attached; best-device
selection picks per task exactly as the reference's multi-chore GPU hooks
do (``jdf_generate_code_hook_gpu``).
"""

from __future__ import annotations

from typing import Any

import numpy as np

from .. import ptg
from ..data_dist.matrix import SymTwoDimBlockCyclic
from ..device.kernels import register_kernel

# ---------------------------------------------------------------------------
# kernels — CPU (numpy)
# ---------------------------------------------------------------------------


def _potrf_cpu(es: Any, task: Any, g: Any, l: Any) -> None:
    t = task.data[0]
    t.value = np.linalg.cholesky(np.asarray(t.value, np.float32))
    t.version += 1


def _trsm_cpu(es: Any, task: Any, g: Any, l: Any) -> None:
    lkk = np.asarray(task.data[0].value, np.float32)
    c = task.data[1]
    b = np.asarray(c.value, np.float32)
    # X·Lₖₖᵀ = B  ⇔  Lₖₖ·Xᵀ = Bᵀ
    c.value = np.linalg.solve(lkk, b.T).T
    c.version += 1


def _syrk_cpu(es: Any, task: Any, g: Any, l: Any) -> None:
    a = np.asarray(task.data[0].value, np.float32)
    t = task.data[1]
    t.value = np.asarray(t.value, np.float32) - a @ a.T
    t.version += 1


def _gemm_nt_cpu(es: Any, task: Any, g: Any, l: Any) -> None:
    a = np.asarray(task.data[0].value, np.float32)
    b = np.asarray(task.data[1].value, np.float32)
    c = task.data[2]
    c.value = np.asarray(c.value, np.float32) - a @ b.T
    c.version += 1


# ---------------------------------------------------------------------------
# kernels — TPU (jax; resolved through the kernel registry by dyld name)
# ---------------------------------------------------------------------------


def _jax():
    import jax
    import jax.numpy as jnp
    import jax.scipy.linalg as jsl
    return jax, jnp, jsl


def potrf_tpu_body(es: Any, task: Any, device: Any) -> Any:
    jax, jnp, _ = _jax()
    t = task.data[0]
    t.value = jnp.linalg.cholesky(t.value.astype(jnp.float32))
    t.version += 1
    return t.value


def trsm_tpu_body(es: Any, task: Any, device: Any) -> Any:
    jax, jnp, jsl = _jax()
    lkk = task.data[0].value
    c = task.data[1]
    # right-solve against Lᵀ via the explicit triangular inverse: XLA
    # specializes the identity-rhs solve and the MXU eats the extra
    # matmul (its gain over the direct rhs solve: not measured on this
    # machine); slightly weaker forward error than substitution on
    # ill-conditioned panels
    c.value = _trsm_traceable(lkk, c.value)
    c.version += 1
    return c.value


def syrk_tpu_body(es: Any, task: Any, device: Any) -> Any:
    jax, jnp, _ = _jax()
    a = task.data[0].value.astype(jnp.float32)
    t = task.data[1]
    t.value = t.value.astype(jnp.float32) - jnp.dot(
        a, a.T, preferred_element_type=jnp.float32)
    t.version += 1
    return t.value


def gemm_nt_tpu_body(es: Any, task: Any, device: Any) -> Any:
    jax, jnp, _ = _jax()
    a = task.data[0].value.astype(jnp.float32)
    b = task.data[1].value.astype(jnp.float32)
    c = task.data[2]
    c.value = c.value.astype(jnp.float32) - jnp.dot(
        a, b.T, preferred_element_type=jnp.float32)
    c.version += 1
    return c.value


register_kernel("potrf", "tpu", potrf_tpu_body)
register_kernel("trsm_rlt", "tpu", trsm_tpu_body)
register_kernel("syrk_ln", "tpu", syrk_tpu_body)
register_kernel("gemm_nt", "tpu", gemm_nt_tpu_body)


# ---------------------------------------------------------------------------
# traceable incarnations (the compiled-lowering side of the dyld names:
# pure functions of the flow values, in flow declaration order)
# ---------------------------------------------------------------------------


def _mm_precision():
    """The ``gemm_precision`` policy, shared with the dynamic-path GEMM
    body: ``highest`` forces full-precision multiplies on TPU, where the
    default runs f32 tiles through bf16 MXU passes (fast, ~3 decimal
    digits).  One home for the mapping (``ops/gemm.py``), imported lazily
    so building a PTG never pulls jax."""
    from ..ops.gemm import _precision
    return _precision()


def _potrf_traceable(t):
    _, jnp, _ = _jax()
    return jnp.linalg.cholesky(t.astype(jnp.float32))


def _trsm_traceable(lkk, c):
    """X = C · inv(Lₖₖ)ᵀ, computed as (inv(Lₖₖ) · Cᵀ)ᵀ with the inverse
    from one identity solve.  TPU-first: the substitution loop (slow,
    sequential) runs once against the identity and the per-tile work is a
    matmul; in the unrolled lowering XLA CSEs the identical inverse across
    every TRSM of one panel, so a whole panel pays ONE solve."""
    _, jnp, jsl = _jax()
    lkk = lkk.astype(jnp.float32)
    linv = jsl.solve_triangular(lkk, jnp.eye(lkk.shape[0], dtype=lkk.dtype),
                                lower=True)
    return jnp.matmul(linv, c.astype(jnp.float32).T,
                      precision=_mm_precision()).T


def _syrk_traceable(a, t):
    _, jnp, _ = _jax()
    a = a.astype(jnp.float32)
    return t.astype(jnp.float32) - jnp.dot(
        a, a.T, preferred_element_type=jnp.float32,
        precision=_mm_precision())


def _gemm_nt_traceable(a, b, c):
    _, jnp, _ = _jax()
    return c.astype(jnp.float32) - jnp.dot(
        a.astype(jnp.float32), b.astype(jnp.float32).T,
        preferred_element_type=jnp.float32, precision=_mm_precision())


def _register_traceables() -> None:
    from ..ptg.lowering import register_traceable
    register_traceable("potrf", _potrf_traceable)
    register_traceable("trsm_rlt", _trsm_traceable)
    register_traceable("syrk_ln", _syrk_traceable)
    register_traceable("gemm_nt", _gemm_nt_traceable)


_register_traceables()


# ---------------------------------------------------------------------------
# the PTG
# ---------------------------------------------------------------------------


def tiled_cholesky_ptg(A: SymTwoDimBlockCyclic,
                       devices: str = "auto") -> ptg.PTGTaskpool:
    """Build the lower-Cholesky PTG over a symmetric block-cyclic matrix."""
    NT = A.mt
    assert A.mt == A.nt, "Cholesky needs a square tile grid"
    p = ptg.PTGBuilder("cholesky", A=A, NT=NT)

    # ---- POTRF(k) ---------------------------------------------------------
    po = p.task("POTRF", k=ptg.span(0, lambda g, l: g.NT - 1))
    po.affinity("A", lambda g, l: (l.k, l.k))
    po.priority(lambda g, l: 3 * (g.NT - l.k) + 3)   # critical path first
    fT = po.flow("T", ptg.RW)
    fT.input(data=("A", lambda g, l: (l.k, l.k)), guard=lambda g, l: l.k == 0)
    fT.input(pred=("SYRK", "T", lambda g, l: {"m": l.k, "k": l.k - 1}),
             guard=lambda g, l: l.k > 0)
    # range arrow: -> T TRSM(k+1..NT-1, k)
    fT.output(succ=("TRSM", "T",
                    lambda g, l: [{"m": m, "k": l.k}
                                  for m in range(l.k + 1, g.NT)]),
              guard=lambda g, l: l.k < g.NT - 1)
    fT.output(data=("A", lambda g, l: (l.k, l.k)))

    # ---- TRSM(m, k), m > k ------------------------------------------------
    tr = p.task("TRSM",
                k=ptg.span(0, lambda g, l: g.NT - 2),
                m=ptg.span(lambda g, l: l.k + 1, lambda g, l: g.NT - 1))
    tr.affinity("A", lambda g, l: (l.m, l.k))
    tr.priority(lambda g, l: 3 * (g.NT - l.m) + 2)
    tT = tr.flow("T", ptg.READ)
    tT.input(pred=("POTRF", "T", lambda g, l: {"k": l.k}))
    tC = tr.flow("C", ptg.RW)
    tC.input(data=("A", lambda g, l: (l.m, l.k)), guard=lambda g, l: l.k == 0)
    tC.input(pred=("GEMM", "C",
                   lambda g, l: {"m": l.m, "n": l.k, "k": l.k - 1}),
             guard=lambda g, l: l.k > 0)
    tC.output(succ=("SYRK", "A", lambda g, l: {"m": l.m, "k": l.k}))
    # range arrow: A-operand of GEMM(m, k+1..m-1, k)
    tC.output(succ=("GEMM", "A",
                    lambda g, l: [{"m": l.m, "n": n, "k": l.k}
                                  for n in range(l.k + 1, l.m)]),
              guard=lambda g, l: l.m - l.k > 1)
    # range arrow: B-operand of GEMM(m+1..NT-1, m, k)
    tC.output(succ=("GEMM", "B",
                    lambda g, l: [{"m": mm, "n": l.m, "k": l.k}
                                  for mm in range(l.m + 1, g.NT)]),
              guard=lambda g, l: l.m < g.NT - 1)
    tC.output(data=("A", lambda g, l: (l.m, l.k)))

    # ---- SYRK(m, k), k < m ------------------------------------------------
    sy = p.task("SYRK",
                m=ptg.span(1, lambda g, l: g.NT - 1),
                k=ptg.span(0, lambda g, l: l.m - 1))
    sy.affinity("A", lambda g, l: (l.m, l.m))
    sy.priority(lambda g, l: 3 * (g.NT - l.m) + 1)
    sA = sy.flow("A", ptg.READ)
    sA.input(pred=("TRSM", "C", lambda g, l: {"m": l.m, "k": l.k}))
    sT = sy.flow("T", ptg.RW)
    sT.input(data=("A", lambda g, l: (l.m, l.m)), guard=lambda g, l: l.k == 0)
    sT.input(pred=("SYRK", "T", lambda g, l: {"m": l.m, "k": l.k - 1}),
             guard=lambda g, l: l.k > 0)
    sT.output(succ=("SYRK", "T", lambda g, l: {"m": l.m, "k": l.k + 1}),
              guard=lambda g, l: l.k < l.m - 1)
    sT.output(succ=("POTRF", "T", lambda g, l: {"k": l.m}),
              guard=lambda g, l: l.k == l.m - 1)

    # ---- GEMM(m, n, k), k < n < m ----------------------------------------
    ge = p.task("GEMM",
                m=ptg.span(2, lambda g, l: g.NT - 1),
                n=ptg.span(1, lambda g, l: l.m - 1),
                k=ptg.span(0, lambda g, l: l.n - 1))
    ge.affinity("A", lambda g, l: (l.m, l.n))
    ge.priority(lambda g, l: 3 * (g.NT - l.m))
    gA = ge.flow("A", ptg.READ)
    gA.input(pred=("TRSM", "C", lambda g, l: {"m": l.m, "k": l.k}))
    gB = ge.flow("B", ptg.READ)
    gB.input(pred=("TRSM", "C", lambda g, l: {"m": l.n, "k": l.k}))
    gC = ge.flow("C", ptg.RW)
    gC.input(data=("A", lambda g, l: (l.m, l.n)), guard=lambda g, l: l.k == 0)
    gC.input(pred=("GEMM", "C",
                   lambda g, l: {"m": l.m, "n": l.n, "k": l.k - 1}),
             guard=lambda g, l: l.k > 0)
    gC.output(succ=("GEMM", "C",
                    lambda g, l: {"m": l.m, "n": l.n, "k": l.k + 1}),
              guard=lambda g, l: l.k < l.n - 1)
    gC.output(succ=("TRSM", "C", lambda g, l: {"m": l.m, "k": l.n}),
              guard=lambda g, l: l.k == l.n - 1)

    # flops-based time estimates feed best-device selection
    nb = A.mb
    po.time_estimate(lambda task, dev:
                     (nb ** 3 / 3) / (dev.gflops_fp32 * 1e9))
    tr.time_estimate(lambda task, dev: nb ** 3 / (dev.gflops_fp32 * 1e9))
    sy.time_estimate(lambda task, dev: nb ** 3 / (dev.gflops_fp32 * 1e9))
    ge.time_estimate(lambda task, dev:
                     2 * nb ** 3 / (dev.gflops_fp32 * 1e9))

    if devices in ("auto", "tpu"):
        po.body(device="tpu", dyld="potrf")
        tr.body(device="tpu", dyld="trsm_rlt")
        sy.body(device="tpu", dyld="syrk_ln")
        ge.body(device="tpu", dyld="gemm_nt")
    if devices in ("auto", "cpu"):
        po.body(_potrf_cpu)
        tr.body(_trsm_cpu)
        sy.body(_syrk_cpu)
        ge.body(_gemm_nt_cpu)
    return p.build()


def cholesky_flops(N: int) -> float:
    return N ** 3 / 3.0 + N ** 2 / 2.0


def make_spd(n: int, seed: int = 0) -> np.ndarray:
    """A well-conditioned SPD test matrix."""
    rng = np.random.RandomState(seed)
    a = rng.randn(n, n).astype(np.float32) / np.sqrt(n)
    return (a @ a.T + np.eye(n, dtype=np.float32) * 4.0).astype(np.float32)


def make_spd_fast(n: int, seed: int = 0) -> np.ndarray:
    """A diagonally-dominant SPD matrix in O(n²) host work — the bench-scale
    constructor (``make_spd``'s Gram product is an n³ host matmul: minutes
    at n=16384).  Symmetric with diag ≳ Σ|off-diag| per row ⇒ SPD by
    Gershgorin; entries ~N(0,1) keep the factors dense and well-scaled."""
    rng = np.random.RandomState(seed)
    a = rng.randn(n, n).astype(np.float32)
    s = (a + a.T) * 0.5
    np.fill_diagonal(s, np.abs(s).sum(axis=1) + 1.0)
    return s
