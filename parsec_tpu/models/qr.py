"""Tiled QR factorization: the PTGs of DPLASMA's ``zgeqrf.jdf`` (the flat
tree, ``tiled_qr_ptg``) and ``zgeqrf_param.jdf`` (a hierarchical tree,
``tiled_hqr_ptg``).

The tile algorithm of ``dplasma_sgeqrf`` with A in tiles and the block
reflectors' triangular factors in a second descriptor T (tile (m, k), m >= k):

- ``GEQRT(k)``    — Householder QR of the diagonal tile: R in its upper
  triangle, the unit-lower V strictly below, ``T(k,k) = larft(V, tau)``;
- ``UNMQR(k,n)``  — row panel: ``A(k,n) <- Q_kk^T . A(k,n)``;
- ``TSQRT(m,k)``  — QR of the stack ``[triu(R_kk); A(m,k)]``: a new R, the
  reflectors' lower block V2 in A(m,k) and their T in T(m,k).  A serial
  chain over m: each link takes the R the link before it left;
- ``TSMQR(m,n,k)`` — trailing update of the pair ``[A(k,n); A(m,n)]`` by
  that block reflector, chained over m along a column and over k in place.

``dplasma_sgeqrf_param`` runs the same kernels over a tree
(``qrtree.py``): a GEQRT on the head of every domain, the domain's rows
killed onto it by TS kills, and the heads killed onto row k by two more:

- ``TTQRT(m,k)``  — QR of two stacked upper triangles ``[triu(R_p);
  triu(A(m,k))]`` (LAPACK ``tpqrt`` with l = nb): the reflectors' lower
  block V2 is upper triangular and goes to A(m,k)'s upper triangle, where
  the head's own GEQRT reflectors stay below it;
- ``TTMQR(m,n,k)`` — TSMQR's update with that triangular V2 (``tpmqrt``).

GEQRT and TSMQR write two tiles a task, TSQRT three.  The inner blocking is
the tile (``ib = nb``): T is a full nb x nb upper-triangular tile and a block
reflector is applied as three dense products.

Precision: every product and triangular solve of the six traceables is traced
under ``jax.default_matmul_precision("highest")`` (``_highest``: true f32 on
the TPU's MXU, ``Precision.HIGHEST``), stated here and selected by no
parameter: a Householder QR whose reflector products are
rounded to bfloat16 is not a QR to f32 (1e-2 against 1e-6 on the probes).
"""

from __future__ import annotations

import functools
from typing import Any

import numpy as np

from .. import ptg
from ..data_dist.matrix import TiledMatrix
from ..device.kernels import register_kernel, traceable_body
from .qrtree import TS, TT, QRTree


# ---------------------------------------------------------------------------
# kernels — CPU (numpy, float64 inside as lu.py's)
# ---------------------------------------------------------------------------


def _house_np(a: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """LAPACK ``geqr2``: R in the upper triangle, the reflectors' vectors
    (unit diagonal implied) below it, and their scalars tau."""
    a = np.array(a, dtype=np.float64)
    m, n = a.shape
    tau = np.zeros(n)
    for j in range(min(m, n)):
        alpha, x = a[j, j], a[j + 1:, j]
        xnorm = np.linalg.norm(x)
        if xnorm == 0.0:
            continue                      # H_j = I
        beta = -np.copysign(np.hypot(alpha, xnorm), alpha)
        tau[j] = (beta - alpha) / beta
        x /= alpha - beta
        a[j, j] = beta
        v = np.concatenate(([1.0], x))
        a[j:, j + 1:] -= tau[j] * np.outer(v, v @ a[j:, j + 1:])
    return a, tau


def larft_np(v: np.ndarray, tau: np.ndarray) -> np.ndarray:
    """LAPACK's recurrence (``larft``, forward, columnwise): the upper
    triangular T with ``H_0 H_1 .. = I - V T V^T``."""
    n = v.shape[1]
    t = np.zeros((n, n))
    for i in range(n):
        t[:i, i] = -tau[i] * (t[:i, :i] @ (v[:, :i].T @ v[:, i]))
        t[i, i] = tau[i]
    return t


def _unit_lower_np(h: np.ndarray) -> np.ndarray:
    return np.tril(h, -1) + np.eye(*h.shape)


def _write(copy: Any, value: np.ndarray) -> None:
    copy.value = value.astype(np.float32)
    copy.version += 1


def _geqrt_cpu(es: Any, task: Any, g: Any, l: Any) -> None:
    a = task.flow_data("A")
    h, tau = _house_np(np.asarray(a.value))
    _write(a, h)
    _write(task.flow_data("T"), larft_np(_unit_lower_np(h), tau))


def _unmqr_cpu(es: Any, task: Any, g: Any, l: Any) -> None:
    vk = _unit_lower_np(np.asarray(task.flow_data("V").value, np.float64))
    t = np.asarray(task.flow_data("T").value, np.float64)
    c = task.flow_data("C")
    cv = np.asarray(c.value, np.float64)
    _write(c, cv - vk @ (t.T @ (vk.T @ cv)))


def _tsqrt_cpu(es: Any, task: Any, g: Any, l: Any) -> None:
    r, b = task.flow_data("R"), task.flow_data("B")
    rv = np.asarray(r.value, np.float64)
    nb = rv.shape[0]
    h, tau = _house_np(np.vstack([np.triu(rv), np.asarray(b.value)]))
    v2 = h[nb:]
    # V_kk, strictly below the diagonal, stays as GEQRT left it
    _write(r, np.triu(h[:nb]) + np.tril(rv, -1))
    _write(b, v2)
    _write(task.flow_data("T"), larft_np(np.vstack([np.eye(nb), v2]), tau))


def _ttqrt_cpu(es: Any, task: Any, g: Any, l: Any) -> None:
    r, b = task.flow_data("R"), task.flow_data("B")
    rv = np.asarray(r.value, np.float64)
    bv = np.asarray(b.value, np.float64)
    nb = rv.shape[0]
    h, tau = _house_np(np.vstack([np.triu(rv), np.triu(bv)]))
    # the stack's lower block stays upper triangular through every
    # reflection: its zeros are exact
    v2 = np.triu(h[nb:])
    _write(r, np.triu(h[:nb]) + np.tril(rv, -1))
    # the head's GEQRT reflectors, strictly below, stay as they were
    _write(b, v2 + np.tril(bv, -1))
    _write(task.flow_data("T"), larft_np(np.vstack([np.eye(nb), v2]), tau))


def _tsmqr_cpu(es: Any, task: Any, g: Any, l: Any, tri: bool = False
               ) -> None:
    a1, a2 = task.flow_data("A1"), task.flow_data("A2")
    v = np.asarray(task.flow_data("V").value, np.float64)
    if tri:                 # TTMQR: V2 is the tile's upper triangle
        v = np.triu(v)
    t = np.asarray(task.flow_data("T").value, np.float64)
    a1v = np.asarray(a1.value, np.float64)
    a2v = np.asarray(a2.value, np.float64)
    w = t.T @ (a1v + v.T @ a2v)
    _write(a1, a1v - w)
    _write(a2, a2v - v @ w)


def _ttmqr_cpu(es: Any, task: Any, g: Any, l: Any) -> None:
    _tsmqr_cpu(es, task, g, l, tri=True)


# ---------------------------------------------------------------------------
# kernels — TPU traceables (shared dyld names with the device bodies)
# ---------------------------------------------------------------------------

def _jnp():
    import jax
    import jax.numpy as jnp
    import jax.scipy.linalg as jsl
    return jax, jnp, jsl


def _dot(a, b):
    """A tile product.  Its precision is the traceable's (``_highest``)."""
    _, jnp, _ = _jnp()
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _householder(x):
    """``(h, tau)`` = Householder QR of ``x`` in LAPACK's packed form: XLA's
    blocked expansion (``jnp.linalg.qr``, raw mode: ``(h^T, tau)``).  On the
    chip it takes 2.3 ms for one 2048 x 1024 stack and 45 ms for 31 under
    ``vmap``, where a ``fori_loop`` of 1,024 masked column steps in
    ``lu.py:_getrf_traceable``'s style took 12.5 ms for one and 8.0 s for
    eight (PERF.md, PR 36, step 0)."""
    _, jnp, _ = _jnp()
    ht, tau = jnp.linalg.qr(x, mode="raw")
    return ht.T, tau


def larft(v, tau):
    """The block reflector's triangular factor in closed form:
    ``T = (striu(V^T V) + diag(1/tau))^-1``, one triangular solve against
    the identity (equal to ``larft_np``'s recurrence: tests/test_qr.py).  A
    reflector with ``tau = 0`` is the identity: its row and column of T are
    zero."""
    _, jnp, jsl = _jnp()
    n = v.shape[1]
    live = tau != 0
    s = jnp.where(live[:, None] & live[None, :], jnp.triu(_dot(v.T, v), 1),
                  0.0)
    s = s + jnp.diag(jnp.where(live, 1.0 / jnp.where(live, tau, 1.0), 1.0))
    t = jsl.solve_triangular(s, jnp.eye(n, dtype=v.dtype), lower=False)
    return jnp.where(live[None, :], t, 0.0)


def _highest(fn):
    """``fn`` traced under ``jax.default_matmul_precision("highest")``: the
    one place that sets the precision of its products, its QR and its
    triangular solves."""
    def traced(*args):
        jax, _, _ = _jnp()
        with jax.default_matmul_precision("highest"):
            return fn(*args)
    traced.__name__ = fn.__name__
    return traced


@_highest
def _geqrt_traceable(a, t):
    """``(h, tau)`` = Householder QR of the tile; ``A <- h``,
    ``T <- larft(tril(h, -1) + I, tau)``."""
    _, jnp, _ = _jnp()
    h, tau = _householder(jnp.asarray(a, jnp.float32))
    v = jnp.tril(h, -1) + jnp.eye(h.shape[0], dtype=h.dtype)
    return h, larft(v, tau)


@_highest
def _unmqr_traceable(v, t, c):
    """``C <- Q_kk^T . C = C - Vk . (T^T . (Vk^T . C))``, ``Vk`` the unit
    lower part of the packed diagonal tile, whatever R its upper holds."""
    _, jnp, _ = _jnp()
    vk = jnp.tril(jnp.asarray(v, jnp.float32), -1) + jnp.eye(v.shape[0],
                                                        dtype=jnp.float32)
    c = jnp.asarray(c, jnp.float32)
    return c - _dot(vk, _dot(t.T, _dot(vk.T, c)))


@_highest
def _tsqrt_traceable(r, b, t):
    """``(h, tau)`` = Householder QR of the stack ``[triu(R); B]``.  Its
    reflectors are ``[I; V2]`` with ``V2 = h[nb:]``: the top block of ``h``
    stays upper triangular.  ``R <- triu(h[:nb]) + tril(R, -1)``,
    ``B <- V2``, ``T <- larft([I; V2], tau)``."""
    _, jnp, _ = _jnp()
    r, b = jnp.asarray(r, jnp.float32), jnp.asarray(b, jnp.float32)
    nb = r.shape[0]
    h, tau = _householder(jnp.concatenate([jnp.triu(r), b], axis=0))
    v2 = h[nb:]
    v = jnp.concatenate([jnp.eye(nb, dtype=r.dtype), v2], axis=0)
    return jnp.triu(h[:nb]) + jnp.tril(r, -1), v2, larft(v, tau)


@_highest
def _tsmqr_traceable(a1, a2, v, t):
    """``W = T^T . (A1 + V^T . A2)``; ``A1 <- A1 - W``; ``A2 <- A2 - V . W``:
    three dense products (6 nb^3 where LAPACK's triangular T counts 4)."""
    _, jnp, _ = _jnp()
    a1, a2 = jnp.asarray(a1, jnp.float32), jnp.asarray(a2, jnp.float32)
    w = _dot(t.T, a1 + _dot(v.T, a2))
    return a1 - w, a2 - _dot(v, w)


@_highest
def _ttqrt_traceable(r, b, t):
    """``(h, tau)`` = Householder QR of ``[triu(R); triu(B)]``.  Its
    reflectors are ``[I; V2]`` with ``V2 = triu(h[nb:])``: the lower block
    keeps the zeros below its diagonal through every reflection.
    ``R <- triu(h[:nb]) + tril(R, -1)``, ``B <- V2 + tril(B, -1)`` (the
    head's GEQRT reflectors, bit for bit), ``T <- larft([I; V2], tau)``."""
    _, jnp, _ = _jnp()
    r, b = jnp.asarray(r, jnp.float32), jnp.asarray(b, jnp.float32)
    nb = r.shape[0]
    h, tau = _householder(jnp.concatenate([jnp.triu(r), jnp.triu(b)],
                                          axis=0))
    v2 = jnp.triu(h[nb:])
    v = jnp.concatenate([jnp.eye(nb, dtype=r.dtype), v2], axis=0)
    return (jnp.triu(h[:nb]) + jnp.tril(r, -1), v2 + jnp.tril(b, -1),
            larft(v, tau))


@_highest
def _ttmqr_traceable(a1, a2, v, t):
    """TSMQR's three dense products with ``V2 = triu(V)``: the tile's
    strictly lower part is its head's GEQRT reflectors, not this one's
    (6 nb^3 where LAPACK's ``tpmqrt`` counts 2)."""
    _, jnp, _ = _jnp()
    return _tsmqr_traceable(a1, a2, jnp.triu(v), t)


# a fused batch of a QR's lanes runs as one batched QR (``vmap``): one lane
# of these is a program of 12-18 MiB for the v5e, and 32 lanes unrolled
# compiled to 570 MiB in 73 s where stacked they compile to 50 MiB in 34 s
# (compiled for the described v5e, not run: PERF.md, section 6)
for _tr in (_geqrt_traceable, _tsqrt_traceable, _ttqrt_traceable):
    _tr.vmap_lanes = True

_TRACEABLES = {"qr_geqrt": _geqrt_traceable, "qr_unmqr": _unmqr_traceable,
               "qr_tsqrt": _tsqrt_traceable, "qr_tsmqr": _tsmqr_traceable,
               "qr_ttqrt": _ttqrt_traceable, "qr_ttmqr": _ttmqr_traceable}


@functools.cache
def _program(name: str):
    """The traceable as one program a task (``jit_<name>`` on the trace),
    built at the first batch of one: a PTG is built without jax."""
    jax, _, _ = _jnp()

    def program(*vals):
        return _TRACEABLES[name](*vals)
    program.__name__ = name
    return jax.jit(program)


def _register() -> None:
    from ..ptg.lowering import register_traceable
    for name, tr in _TRACEABLES.items():
        register_kernel(name, "tpu", traceable_body(
            lambda *vals, _name=name: _program(_name)(*vals),
            jitted=functools.partial(_program, name)))
        register_traceable(name, tr)


_register()


# ---------------------------------------------------------------------------
# the PTG
# ---------------------------------------------------------------------------


def tiled_qr_ptg(A: TiledMatrix, T: TiledMatrix,
                 devices: str = "auto") -> "ptg.PTGTaskpool":
    """Build the flat-tree QR PTG over a square tile grid: A is factored in
    place (R above, reflectors below), T(m, k), m >= k, takes the block
    reflectors' triangular factors."""
    NT = A.mt
    assert A.mt == A.nt, "QR needs a square tile grid"
    assert (T.mt, T.nt, T.mb, T.nb) == (A.mt, A.nt, A.mb, A.nb), \
        "T is tiled as A"
    p = ptg.PTGBuilder("qr", A=A, T=T, NT=NT)
    last = lambda g: g.NT - 1                                   # noqa: E731

    # ---- GEQRT(k) ---------------------------------------------------------
    ge = p.task("GEQRT", k=ptg.span(0, lambda g, l: last(g)))
    ge.affinity("A", lambda g, l: (l.k, l.k))
    ge.priority(lambda g, l: 4 * (g.NT - l.k) + 3)    # the panel first
    gA = ge.flow("A", ptg.RW)
    gA.input(data=("A", lambda g, l: (l.k, l.k)), guard=lambda g, l: l.k == 0)
    gA.input(pred=("TSMQR", "A2", lambda g, l: {"k": l.k - 1, "m": l.k,
                                                "n": l.k}),
             guard=lambda g, l: l.k > 0)
    gA.output(succ=("UNMQR", "V",
                    lambda g, l: [{"k": l.k, "n": n}
                                  for n in range(l.k + 1, g.NT)]),
              guard=lambda g, l: l.k < last(g))
    gA.output(succ=("TSQRT", "R", lambda g, l: {"k": l.k, "m": l.k + 1}),
              guard=lambda g, l: l.k < last(g))
    gA.output(data=("A", lambda g, l: (l.k, l.k)),
              guard=lambda g, l: l.k == last(g))
    gT = ge.flow("T", ptg.RW)
    gT.input(data=("T", lambda g, l: (l.k, l.k)))
    gT.output(succ=("UNMQR", "T",
                    lambda g, l: [{"k": l.k, "n": n}
                                  for n in range(l.k + 1, g.NT)]),
              guard=lambda g, l: l.k < last(g))
    gT.output(data=("T", lambda g, l: (l.k, l.k)))

    # ---- UNMQR(k, n), n > k: row panel ------------------------------------
    un = p.task("UNMQR",
                k=ptg.span(0, lambda g, l: g.NT - 2),
                n=ptg.span(lambda g, l: l.k + 1, lambda g, l: last(g)))
    un.affinity("A", lambda g, l: (l.k, l.n))
    un.priority(lambda g, l: 4 * (g.NT - l.k) + 1)
    un.flow("V", ptg.READ).input(
        pred=("GEQRT", "A", lambda g, l: {"k": l.k}))
    un.flow("T", ptg.READ).input(
        pred=("GEQRT", "T", lambda g, l: {"k": l.k}))
    uC = un.flow("C", ptg.RW)
    uC.input(data=("A", lambda g, l: (l.k, l.n)), guard=lambda g, l: l.k == 0)
    uC.input(pred=("TSMQR", "A2", lambda g, l: {"k": l.k - 1, "m": l.k,
                                                "n": l.n}),
             guard=lambda g, l: l.k > 0)
    uC.output(succ=("TSMQR", "A1", lambda g, l: {"k": l.k, "m": l.k + 1,
                                                 "n": l.n}))

    # ---- TSQRT(m, k), m > k: the serial chain down a panel ----------------
    # The strictly lower part of the R tile (V_kk) is carried through every
    # link unchanged, so UNMQR(k, .) reads the right V from any version of
    # the tile (tests/test_qr.py holds it bit for bit).
    ts = p.task("TSQRT",
                k=ptg.span(0, lambda g, l: g.NT - 2),
                m=ptg.span(lambda g, l: l.k + 1, lambda g, l: last(g)))
    ts.affinity("A", lambda g, l: (l.m, l.k))
    ts.priority(lambda g, l: 4 * (g.NT - l.k) + 2)
    tR = ts.flow("R", ptg.RW)
    tR.input(pred=("GEQRT", "A", lambda g, l: {"k": l.k}),
             guard=lambda g, l: l.m == l.k + 1)
    tR.input(pred=("TSQRT", "R", lambda g, l: {"k": l.k, "m": l.m - 1}),
             guard=lambda g, l: l.m > l.k + 1)
    tR.output(succ=("TSQRT", "R", lambda g, l: {"k": l.k, "m": l.m + 1}),
              guard=lambda g, l: l.m < last(g))
    tR.output(data=("A", lambda g, l: (l.k, l.k)),
              guard=lambda g, l: l.m == last(g))
    tB = ts.flow("B", ptg.RW)
    tB.input(data=("A", lambda g, l: (l.m, l.k)), guard=lambda g, l: l.k == 0)
    tB.input(pred=("TSMQR", "A2", lambda g, l: {"k": l.k - 1, "m": l.m,
                                                "n": l.k}),
             guard=lambda g, l: l.k > 0)
    tB.output(succ=("TSMQR", "V",
                    lambda g, l: [{"k": l.k, "m": l.m, "n": n}
                                  for n in range(l.k + 1, g.NT)]))
    tB.output(data=("A", lambda g, l: (l.m, l.k)))
    tT = ts.flow("T", ptg.RW)
    tT.input(data=("T", lambda g, l: (l.m, l.k)))
    tT.output(succ=("TSMQR", "T",
                    lambda g, l: [{"k": l.k, "m": l.m, "n": n}
                                  for n in range(l.k + 1, g.NT)]))
    tT.output(data=("T", lambda g, l: (l.m, l.k)))

    # ---- TSMQR(m, n, k), m > k, n > k: trailing update ---------------------
    tm = p.task("TSMQR",
                k=ptg.span(0, lambda g, l: g.NT - 2),
                m=ptg.span(lambda g, l: l.k + 1, lambda g, l: last(g)),
                n=ptg.span(lambda g, l: l.k + 1, lambda g, l: last(g)))
    tm.affinity("A", lambda g, l: (l.m, l.n))
    tm.priority(lambda g, l: 4 * (g.NT - l.k))
    m1 = tm.flow("A1", ptg.RW)
    m1.input(pred=("UNMQR", "C", lambda g, l: {"k": l.k, "n": l.n}),
             guard=lambda g, l: l.m == l.k + 1)
    m1.input(pred=("TSMQR", "A1", lambda g, l: {"k": l.k, "m": l.m - 1,
                                                "n": l.n}),
             guard=lambda g, l: l.m > l.k + 1)
    m1.output(succ=("TSMQR", "A1", lambda g, l: {"k": l.k, "m": l.m + 1,
                                                 "n": l.n}),
              guard=lambda g, l: l.m < last(g))
    m1.output(data=("A", lambda g, l: (l.k, l.n)),
              guard=lambda g, l: l.m == last(g))
    m2 = tm.flow("A2", ptg.RW)
    m2.input(data=("A", lambda g, l: (l.m, l.n)), guard=lambda g, l: l.k == 0)
    m2.input(pred=("TSMQR", "A2", lambda g, l: {"k": l.k - 1, "m": l.m,
                                                "n": l.n}),
             guard=lambda g, l: l.k > 0)
    m2.output(succ=("GEQRT", "A", lambda g, l: {"k": l.k + 1}),
              guard=lambda g, l: l.m == l.k + 1 and l.n == l.k + 1)
    m2.output(succ=("UNMQR", "C", lambda g, l: {"k": l.k + 1, "n": l.n}),
              guard=lambda g, l: l.m == l.k + 1 and l.n > l.k + 1)
    m2.output(succ=("TSQRT", "B", lambda g, l: {"k": l.k + 1, "m": l.m}),
              guard=lambda g, l: l.n == l.k + 1 and l.m > l.k + 1)
    m2.output(succ=("TSMQR", "A2", lambda g, l: {"k": l.k + 1, "m": l.m,
                                                 "n": l.n}),
              guard=lambda g, l: l.m > l.k + 1 and l.n > l.k + 1)
    tm.flow("V", ptg.READ).input(
        pred=("TSQRT", "B", lambda g, l: {"k": l.k, "m": l.m}))
    tm.flow("T", ptg.READ).input(
        pred=("TSQRT", "T", lambda g, l: {"k": l.k, "m": l.m}))

    # LAPACK's counts feed best-device selection
    nb = A.mb
    for tc, count in ((ge, 4 / 3), (un, 2), (ts, 2), (tm, 4)):
        tc.time_estimate(lambda task, dev, _c=count:
                         _c * nb ** 3 / (dev.gflops_fp32 * 1e9))

    if devices in ("auto", "tpu"):
        ge.body(device="tpu", dyld="qr_geqrt")
        un.body(device="tpu", dyld="qr_unmqr")
        ts.body(device="tpu", dyld="qr_tsqrt")
        tm.body(device="tpu", dyld="qr_tsmqr")
    if devices in ("auto", "cpu"):
        ge.body(_geqrt_cpu)
        un.body(_unmqr_cpu)
        ts.body(_tsqrt_cpu)
        tm.body(_tsmqr_cpu)
    return p.build()


# ---------------------------------------------------------------------------
# the hierarchical PTG (zgeqrf_param.jdf)
# ---------------------------------------------------------------------------

# the panel's classes and flows, and the trailing update's: a head's tile
# (``head``), the killer's tile through its kills (``on``), a killed head's
# own tile (``own``), the column a task works on (``col``)
_PANEL = {"head": ("GEQRT", "A"), TS: "TSQRT", TT: "TTQRT", "on": "R",
          "own": "B", "col": lambda l: l.k}
_UPDATE = {"head": ("UNMQR", "C"), TS: "TSMQR", TT: "TTMQR", "on": "A1",
           "own": "A2", "col": lambda l: l.n}


def _at(side: dict, l: Any, m: int) -> dict:
    """The locals of ``side``'s task on row m at the step (and column) of
    ``l``."""
    if side is _PANEL:
        return {"k": l.k, "m": m}
    return {"k": l.k, "m": m, "n": l.n}


# the most lanes a device batch of an update class holds.  A lane of these
# is its own 2.3-3 MiB of code for the v5e, and each power of two up to the
# largest batch a program the compile cache keeps (0.55-0.6 MiB of cache a
# lane); at 64 lanes a solve's programs came to 235 MiB of cache, past the
# 190 the chip's machine keeps, and every run compiled them all again.  At
# 32 UNMQR and TSMQR run the programs of the flat tree's cells, and a TT
# level's 16 kills bound TTMQR (PERF.md, section 6)
UPDATE_LANES = {"UNMQR": 32, "TSMQR": 32, "TTMQR": 16}


def tiled_hqr_ptg(A: TiledMatrix, TS_: TiledMatrix, TT_: TiledMatrix,
                  tree: QRTree, devices: str = "auto") -> "ptg.PTGTaskpool":
    """Build the hierarchical QR PTG of ``zgeqrf_param.jdf`` over a tall or
    square tile grid: A is factored in place (R in its top NT x NT tiles,
    above the diagonal; every reflector below it: a TS kill's V2 fills its
    tile, a TT kill's V2 is the upper triangle of a head's tile whose lower
    part holds the head's GEQRT reflectors).  TS(m, k) takes the triangular
    factor of GEQRT and TSQRT on row m at step k, TT(m, k) that of TTQRT;
    nothing else of them is read or written.  Every edge comes from
    ``tree`` (``qrtree.py``), whose tables are built when the pool is
    enqueued; the pool keeps ``panel_levels``, the panel's critical path
    in kernels summed over the steps."""
    MT, NT = A.mt, A.nt
    if (tree.mt, tree.nt) != (MT, NT):
        raise ValueError(f"{tree!r} is not over A's {MT} x {NT} tiles")
    for T in (TS_, TT_):
        assert (T.mt, T.nt, T.mb, T.nb) == (A.mt, A.nt, A.mb, A.nb), \
            "TS and TT are tiled as A"
    t = tree
    p = ptg.PTGBuilder("hqr", A=A, TS=TS_, TT=TT_, NT=NT)
    steps = ptg.span(0, NT - 1)
    cols = ptg.span(lambda g, l: l.k + 1, NT - 1)
    more = lambda g, l: l.k < NT - 1                        # noqa: E731

    def after(fb: Any, side: dict, killer: Any, nxt: Any) -> None:
        """Where the tile of row ``killer(l)`` goes once it has killed the
        rows before ``nxt(l)`` (None: all of its kills): to its next kill,
        to the TT kill of itself, or, on row k, home."""
        for kind in (TS, TT):
            fb.output(succ=(side[kind], side["on"],
                            lambda g, l: _at(side, l, nxt(l))),
                      guard=lambda g, l, _k=kind:
                      (q := nxt(l)) is not None and t.kind(l.k, q) == _k)
        fb.output(succ=(side[TT], side["own"],
                        lambda g, l: _at(side, l, killer(l))),
                  guard=lambda g, l: nxt(l) is None and killer(l) != l.k)
        fb.output(data=("A", lambda g, l: (l.k, side["col"](l))),
                  guard=lambda g, l: nxt(l) is None and killer(l) == l.k)

    def before(fb: Any, side: dict, killer: Any, prev: Any) -> None:
        """Where the tile of row ``killer(l)`` comes from when it has killed
        the rows up to ``prev(l)`` (None: none yet, its head task's)."""
        cls, flow = side["head"]
        fb.input(pred=(cls, flow, lambda g, l: _at(side, l, killer(l))),
                 guard=lambda g, l: prev(l) is None)
        for kind in (TS, TT):
            fb.input(pred=(side[kind], side["on"],
                           lambda g, l: _at(side, l, prev(l))),
                     guard=lambda g, l, _k=kind:
                     (q := prev(l)) is not None and t.kind(l.k, q) == _k)

    def from_step_before(fb: Any, side: dict) -> None:
        """Row m's tile in this task's column, as step k - 1 left it: the
        update of the kill of m there."""
        fb.input(data=("A", lambda g, l: (l.m, side["col"](l))),
                 guard=lambda g, l: l.k == 0)
        for kind, cls in ((TS, "TSMQR"), (TT, "TTMQR")):
            fb.input(pred=(cls, "A2", lambda g, l: {
                "k": l.k - 1, "m": l.m, "n": side["col"](l)}),
                guard=lambda g, l, _k=kind:
                l.k > 0 and t.kind(l.k - 1, l.m) == _k)

    def to_step_after(fb: Any) -> None:
        """An update's row-m tile onward, to the first task on it at step
        k + 1: the panel's or the update's, as row m is a head there."""
        for cls, flow, panel, head in (
                ("GEQRT", "A", True, True), ("TSQRT", "B", True, False),
                ("UNMQR", "C", False, True), ("TSMQR", "A2", False, False)):
            fb.output(succ=(cls, flow, (lambda g, l: {"k": l.k + 1,
                                                      "m": l.m})
                            if panel else (lambda g, l: {
                                "k": l.k + 1, "m": l.m, "n": l.n})),
                      guard=lambda g, l, _p=panel, _h=head:
                      (l.n == l.k + 1) is _p
                      and t.is_head(l.k + 1, l.m) is _h)

    def updates(cls: str, flow: str) -> tuple:
        return (cls, flow, lambda g, l: [{"k": l.k, "m": l.m, "n": n}
                                         for n in range(l.k + 1, NT)])

    killer = lambda l: t.killer(l.k, l.m)                   # noqa: E731
    me = lambda l: l.m                                      # noqa: E731
    first = lambda l: t.first_kill(l.k, l.m)                # noqa: E731
    last = lambda l: t.last_kill(l.k, l.m)                  # noqa: E731
    prev = lambda l: t.prev_kill(l.k, l.m)                  # noqa: E731
    nxt = lambda l: t.next_kill(l.k, l.m)                   # noqa: E731

    # ---- GEQRT(k, m): m a head of step k -----------------------------------
    ge = p.task("GEQRT", k=steps, m=lambda g, l: t.heads(l.k))
    ge.affinity("A", lambda g, l: (l.m, l.k))
    ge.priority(lambda g, l: 4 * (NT - l.k) + 3)          # the panel first
    gA = ge.flow("A", ptg.RW)
    from_step_before(gA, _PANEL)
    gA.output(succ=updates("UNMQR", "V"), guard=more)
    after(gA, _PANEL, me, first)
    gT = ge.flow("T", ptg.RW)
    gT.input(data=("TS", lambda g, l: (l.m, l.k)))
    gT.output(succ=updates("UNMQR", "T"), guard=more)
    gT.output(data=("TS", lambda g, l: (l.m, l.k)))

    # ---- UNMQR(k, m, n): a head's row ---------------------------------------
    un = p.task("UNMQR", k=ptg.span(0, NT - 2),
                m=lambda g, l: t.heads(l.k), n=cols)
    un.affinity("A", lambda g, l: (l.m, l.n))
    un.priority(lambda g, l: 4 * (NT - l.k) + 1)
    un.batch_max(UPDATE_LANES["UNMQR"])
    un.flow("V", ptg.READ).input(
        pred=("GEQRT", "A", lambda g, l: {"k": l.k, "m": l.m}))
    un.flow("T", ptg.READ).input(
        pred=("GEQRT", "T", lambda g, l: {"k": l.k, "m": l.m}))
    uC = un.flow("C", ptg.RW)
    from_step_before(uC, _UPDATE)
    after(uC, _UPDATE, me, first)

    # ---- TSQRT(k, m) / TTQRT(k, m): the kill of row m ---------------------
    # R is the killer's tile, carried from kill to kill; its strictly lower
    # part (the killer's GEQRT reflectors) is never changed, so UNMQR reads
    # the right V from any version of the tile.  TTQRT's B is a killed
    # head's tile after its own kills, whose lower part is kept the same way
    kills = {}
    for name, kind, tcoll in (("TSQRT", TS, "TS"), ("TTQRT", TT, "TT")):
        rows = t.ts_rows if kind == TS else t.tt_rows
        kq = p.task(name, k=steps, m=lambda g, l, _r=rows: _r(l.k))
        kq.affinity("A", lambda g, l: (l.m, l.k))
        kq.priority(lambda g, l: 4 * (NT - l.k) + 2)
        kR = kq.flow("R", ptg.RW)
        before(kR, _PANEL, killer, prev)
        after(kR, _PANEL, killer, nxt)
        kB = kq.flow("B", ptg.RW)
        if kind == TS:
            from_step_before(kB, _PANEL)
        else:
            before(kB, _PANEL, me, last)
        kB.output(succ=updates("TSMQR" if kind == TS else "TTMQR", "V"),
                  guard=more)
        kB.output(data=("A", lambda g, l: (l.m, l.k)))
        kT = kq.flow("T", ptg.RW)
        kT.input(data=(tcoll, lambda g, l: (l.m, l.k)))
        kT.output(succ=updates("TSMQR" if kind == TS else "TTMQR", "T"),
                  guard=more)
        kT.output(data=(tcoll, lambda g, l: (l.m, l.k)))
        kills[kind] = kq

    # ---- TSMQR(k, m, n) / TTMQR(k, m, n): the kill's update of column n ---
    mqrs = {}
    for name, kind in (("TSMQR", TS), ("TTMQR", TT)):
        rows = t.ts_rows if kind == TS else t.tt_rows
        mq = p.task(name, k=ptg.span(0, NT - 2),
                    m=lambda g, l, _r=rows: _r(l.k), n=cols)
        mq.affinity("A", lambda g, l: (l.m, l.n))
        mq.priority(lambda g, l: 4 * (NT - l.k))
        mq.batch_max(UPDATE_LANES[name])
        m1 = mq.flow("A1", ptg.RW)
        before(m1, _UPDATE, killer, prev)
        after(m1, _UPDATE, killer, nxt)
        m2 = mq.flow("A2", ptg.RW)
        if kind == TS:
            from_step_before(m2, _UPDATE)
        else:
            before(m2, _UPDATE, me, last)
        to_step_after(m2)
        src = "TSQRT" if kind == TS else "TTQRT"
        mq.flow("V", ptg.READ).input(
            pred=(src, "B", lambda g, l: {"k": l.k, "m": l.m}))
        mq.flow("T", ptg.READ).input(
            pred=(src, "T", lambda g, l: {"k": l.k, "m": l.m}))
        mqrs[kind] = mq

    # LAPACK's counts feed best-device selection
    nb = A.mb
    classes = ((ge, 4 / 3, "qr_geqrt", _geqrt_cpu),
               (un, 2, "qr_unmqr", _unmqr_cpu),
               (kills[TS], 2, "qr_tsqrt", _tsqrt_cpu),
               (kills[TT], 2 / 3, "qr_ttqrt", _ttqrt_cpu),
               (mqrs[TS], 4, "qr_tsmqr", _tsmqr_cpu),
               (mqrs[TT], 2, "qr_ttmqr", _ttmqr_cpu))
    for tc, count, dyld, cpu in classes:
        tc.time_estimate(lambda task, dev, _c=count:
                         _c * nb ** 3 / (dev.gflops_fp32 * 1e9))
        if devices in ("auto", "tpu"):
            tc.body(device="tpu", dyld=dyld)
        if devices in ("auto", "cpu"):
            tc.body(cpu)
    pool = p.build()

    def enqueued(tp: Any) -> None:
        # the tree's tables, built here: under ctx.add_taskpool's span
        tp.panel_levels = t.panel_levels
    pool.on_enqueue = enqueued
    return pool
