"""Tiled QR factorization (flat tree): the PTG of DPLASMA's ``zgeqrf.jdf``.

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

GEQRT and TSMQR write two tiles a task, TSQRT three.  The inner blocking is
the tile (``ib = nb``): T is a full nb x nb upper-triangular tile and a block
reflector is applied as three dense products.

Precision: every product and triangular solve of the four traceables is traced
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


def _tsmqr_cpu(es: Any, task: Any, g: Any, l: Any) -> None:
    a1, a2 = task.flow_data("A1"), task.flow_data("A2")
    v = np.asarray(task.flow_data("V").value, np.float64)
    t = np.asarray(task.flow_data("T").value, np.float64)
    a1v = np.asarray(a1.value, np.float64)
    a2v = np.asarray(a2.value, np.float64)
    w = t.T @ (a1v + v.T @ a2v)
    _write(a1, a1v - w)
    _write(a2, a2v - v @ w)


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


_TRACEABLES = {"qr_geqrt": _geqrt_traceable, "qr_unmqr": _unmqr_traceable,
               "qr_tsqrt": _tsqrt_traceable, "qr_tsmqr": _tsmqr_traceable}


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
