"""The plain reference of the hierarchical tile QR (``geqrf-hqr-128kx8k``):
seeded data, the two probe products that decide ``correct``, and the
lower-precision control.  numpy and plain ``jax.numpy`` only, as
``reference_qr.py``, whose helpers it shares: nothing here imports
``parsec_tpu`` or takes anything the program has made, its tree included.

A = Q.R with A, M x N, in nb-square tiles (MT x NT of them, MT >= NT).  The
tree, built here from (MT, NT, a, low) alone: at step k the rows k .. MT-1
fall into domains of ``a`` rows counted from row k; each domain's first row
(its head) is factored by GEQRT, the domain's other rows are killed onto it
in row order (TS kills), and the heads are killed onto row k level by level
(TT kills): in a ``binary`` tree the head of rank i with i mod 2^(l+1) = 0
kills the head of rank i + 2^l at level l, in a ``flat`` one head 0 kills
the others in order.  What the program leaves behind (DPLASMA's
``sgeqrf_param`` layout, ``ib = nb``):

- R in the tiles A(m, n), m <= n < NT (the upper triangle of the diagonal
  tiles);
- a head h's GEQRT reflectors, unit lower, strictly below the diagonal of
  A(h, k), their T in TS(h, k);
- a TS kill of row m: ``[I; V2]`` with V2 the whole tile A(m, k), T in
  TS(m, k);
- a TT kill of head m: ``[I; V2]`` with V2 the *upper* triangle of A(m, k)
  (its lower part is m's GEQRT's), T in TT(m, k).

Each block reflector is ``Q_x = I - [I; V2] T [I; V2]^T`` on the killer's
and the killed row (``I - V T V^T`` on the head's row for a GEQRT), and
``Q`` is their product in the order applied, so ``Q.Y`` applies them last
first.  The two numbers compared, in float64 on seeded probes X, are

    |Q.(R.X) - A.X| / |A.X|        (``testing_?geqrf``'s residual, on probes)
    |R^T.(R.X) - A^T.(A.X)| / |A^T.(A.X)|     (R alone: whatever V and T hold)

The control is the same tile algorithm in plain ``jnp`` with every written
tile *stored* in ``store`` between tile operations, each operation computed
in f32 at ``precision`` from the stored values (``hqr_control``).
"""

from __future__ import annotations

import heapq
import threading

import numpy as np

import reference as ref
import reference_qr as refq
import reference_tiled as reft


def hqr_tiles(seed: int, m: int, n: int, nb: int) -> dict:
    """All tiles ``(i, j)`` of an m x n f32 matrix of standard normals, each
    a contiguous (nb, nb) array drawn from its own stream (the streams of
    ``reference_qr.qr_tiles``)."""
    mt, nt = m // nb, n // nb
    tiles = {(i, j): None for i in range(mt) for j in range(nt)}

    def fill(i: int) -> None:
        for j in range(nt):
            tiles[i, j] = np.random.default_rng(
                [seed, refq.MAT_QR_TILE, i, j]).standard_normal(
                    (nb, nb), dtype=np.float32)

    ref._parallel(mt, fill)
    return tiles


def phases(mt: int, nt: int, a: int, low: str) -> list[list[list]]:
    """Per step k, the kills in the order applied, as phases of kills that
    touch disjoint rows: ``[("ge", h, h), ..]`` for the heads' GEQRTs, then
    ``[("ts", p, m), ..]`` for the j-th TS kill of every domain, then
    ``[("tt", p, m), ..]`` for every TT level (one kill a phase in a flat
    tree)."""
    steps = []
    for k in range(nt):
        heads = list(range(k, mt, a))
        out = [[("ge", h, h) for h in heads]]
        for j in range(1, a):
            ts = [("ts", h, h + j) for h in heads if h + j < mt]
            if ts:
                out.append(ts)
        if low == "flat":
            out += [[("tt", heads[0], h)] for h in heads[1:]]
        else:
            level = 1
            while level < len(heads):
                out.append([("tt", heads[i], heads[i + level])
                            for i in range(0, len(heads) - level, 2 * level)])
                level *= 2
        steps.append(out)
    return steps


def apply(tiles: dict, X: np.ndarray, nb: int, rows: int) -> np.ndarray:
    """``A.X`` in float64 from the tiles of an A of ``rows`` rows."""
    def add(Y: np.ndarray, t: np.ndarray, m: int, k: int) -> None:
        Y[m * nb:(m + 1) * nb] += t @ X[k * nb:(k + 1) * nb]

    return reft._summed(tiles, (rows, X.shape[1]), nb, add)


def apply_t(tiles: dict, Y: np.ndarray, nb: int, cols: int) -> np.ndarray:
    """``A^T.Y`` in float64 from the tiles of an A of ``cols`` columns."""
    def add(Z: np.ndarray, t: np.ndarray, m: int, k: int) -> None:
        Z[k * nb:(k + 1) * nb] += t.T @ Y[m * nb:(m + 1) * nb]

    return reft._summed(tiles, (cols, Y.shape[1]), nb, add)


def replay_order(mt: int, nt: int, a: int, low: str) -> tuple[list, list]:
    """The kills ``(k, what, p, m)`` of every step, the last applied first,
    and for each the kills it waits for: the one before it in that order on
    each of its rows.  Kills that share no row commute, so running each as
    soon as those it waits for are done gives what the order gives, bit for
    bit: every row sees its kills in the same order."""
    ops = [(k,) + op for k, step in enumerate(phases(mt, nt, a, low))
           for phase in step for op in phase][::-1]
    last: dict = {}
    waits = []
    for i, (_, _, p, m) in enumerate(ops):
        waits.append({last[r] for r in (p, m) if r in last})
        last[p] = last[m] = i
    return ops, waits


def _dataflow(ops: list, waits: list, run) -> None:
    """``run(op)`` for every op on the threads of ``reference_qr``, each op
    once those it waits for are done, the earliest ready first: no thread
    waits for a phase's slowest kill."""
    left = [len(w) for w in waits]
    after: list[list] = [[] for _ in ops]
    for i, w in enumerate(waits):
        for j in w:
            after[j].append(i)
    ready = [i for i, n in enumerate(left) if not n]
    heapq.heapify(ready)
    cv = threading.Condition()
    state: dict = {"done": 0, "error": None}

    def worker(_: int) -> None:
        while True:
            with cv:
                while not ready and state["done"] < len(ops) \
                        and state["error"] is None:
                    cv.wait()
                if not ready:
                    return
                i = heapq.heappop(ready)
            try:
                run(ops[i])
            except BaseException as e:      # the caller raises it
                with cv:
                    state["error"] = e
                    ready.clear()
                    cv.notify_all()
                return
            with cv:
                state["done"] += 1
                for j in after[i]:
                    left[j] -= 1
                    if not left[j]:
                        heapq.heappush(ready, j)
                cv.notify_all()

    list(refq._pool().map(worker, range(reft.THREADS)))
    if state["error"] is not None:
        raise state["error"]


def hqr_got(tiles_a: dict, tiles_ts: dict, tiles_tt: dict, X: np.ndarray,
            nb: int, a: int, low: str) -> tuple:
    """``(Q.(R.X), R^T.(R.X))`` in float64 from the factored tiles.

    ``Y = [R.X; 0]``, then every reflector, the last applied first
    (``replay_order``), each kill as soon as the kills before it on its rows
    are done, on the threads of ``reference_qr``.  A TS or TT kill of row m
    by p: ``W = T.(Y_p + V2^T.Y_m); Y_p -= W; Y_m -= V2.W``; a GEQRT of head
    h: ``Y_h -= V.(T.(V^T.Y_h))``."""
    mt = 1 + max(m for m, _ in tiles_a)
    nt = 1 + max(n for _, n in tiles_a)
    r = {(m, n): np.triu(t) if m == n else t
         for (m, n), t in tiles_a.items() if m <= n}
    Y = np.zeros((mt * nb, X.shape[1]))
    Y[:nt * nb] = refq.apply(r, X, nb)
    rtr = refq.apply_t(r, Y[:nt * nb], nb)
    strict = np.tril(np.ones((nb, nb), bool), -1)
    upper = ~strict

    def rows(i: int) -> slice:
        return slice(i * nb, (i + 1) * nb)

    def kill(op: tuple) -> None:
        k, what, p, m = op
        v, t = refq._bufs(nb)
        np.copyto(v, tiles_a[m, k])
        np.copyto(t, (tiles_tt if what == "tt" else tiles_ts)[m, k])
        if what == "ge":
            np.multiply(v, strict, out=v)
            np.fill_diagonal(v, 1.0)
            Y[rows(p)] -= v @ (t @ (v.T @ Y[rows(p)]))
            return
        if what == "tt":
            np.multiply(v, upper, out=v)
        w = t @ (Y[rows(p)] + v.T @ Y[rows(m)])
        Y[rows(p)] -= w
        Y[rows(m)] -= v @ w

    with reft.threadpool_limits(1, user_api="blas"):
        _dataflow(*replay_order(mt, nt, a, low), kill)
    return Y, rtr


def hqr_control(tiles: dict, nb: int, a: int, low: str,
                store: str = "bfloat16", precision: str = "highest"
                ) -> tuple[dict, dict, dict]:
    """The hierarchical tile QR with every written tile stored as ``store``
    (the control: bfloat16) between tile operations, each operation computed
    in f32, every product at ``precision``, from the stored values.  A's,
    TS's and TT's tiles as float32 numpy arrays, in the program's layout.

    ``store="float32"`` is a sound run (tests hold it), and with
    ``precision="high"`` the second control, read on the chip to place the
    limit: every product at the precision next below the configuration's,
    three bf16 passes for six.  A CPU computes both precisions alike, so it
    is no control there."""
    import jax
    import jax.numpy as jnp
    store, f32 = jnp.dtype(store), jnp.float32
    mt = 1 + max(m for m, _ in tiles)
    nt = 1 + max(n for _, n in tiles)
    eye = jnp.eye(nb, dtype=f32)

    def larft(v, tau):
        # tau = 0 is H = I: its row and column of T are zero
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
    def geqrt(x):
        ht, tau = jnp.linalg.qr(x, mode="raw")
        h = ht.T
        return h, larft(jnp.tril(h, -1) + eye, tau)

    @jit
    def unmqr(vh, t, c):
        v = jnp.tril(vh, -1) + eye
        return (c - v @ (t.T @ (v.T @ c)),)

    def kill_qr(tri: bool):
        @jit
        def kill(rp, b):
            low_b = jnp.triu(b) if tri else b
            ht, tau = jnp.linalg.qr(jnp.concatenate([jnp.triu(rp), low_b]),
                                    mode="raw")
            h = ht.T
            v2 = jnp.triu(h[nb:]) if tri else h[nb:]
            kept = v2 + jnp.tril(b, -1) if tri else v2
            return (jnp.triu(h[:nb]) + jnp.tril(rp, -1), kept,
                    larft(jnp.concatenate([eye, v2]), tau))
        return kill

    def kill_mqr(tri: bool):
        @jit
        def update(a1, a2, v, t):
            v2 = jnp.triu(v) if tri else v
            w = t.T @ (a1 + v2.T @ a2)
            return a1 - w, a2 - v2 @ w
        return update

    qr_of = {"ts": kill_qr(False), "tt": kill_qr(True)}
    mqr_of = {"ts": kill_mqr(False), "tt": kill_mqr(True)}
    a_ = {key: jnp.asarray(t).astype(store) for key, t in tiles.items()}
    ts, tt = {}, {}
    for k, step in enumerate(phases(mt, nt, a, low)):
        for phase in step:
            for what, p, m in phase:
                if what == "ge":
                    a_[p, k], ts[p, k] = geqrt(a_[p, k])
                    for n in range(k + 1, nt):
                        (a_[p, n],) = unmqr(a_[p, k], ts[p, k], a_[p, n])
                    continue
                t_of = tt if what == "tt" else ts
                a_[p, k], a_[m, k], t_of[m, k] = qr_of[what](a_[p, k],
                                                             a_[m, k])
                for n in range(k + 1, nt):
                    a_[p, n], a_[m, n] = mqr_of[what](a_[p, n], a_[m, n],
                                                      a_[m, k], t_of[m, k])

    def host(d: dict) -> dict:
        return {key: np.asarray(t.astype(f32)) for key, t in d.items()}

    return host(a_), host(ts), host(tt)
