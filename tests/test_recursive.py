"""Recursive task bodies: nested taskpools over sub-tiled flow data.

The analog of the reference's recursive apps
(``parsec/recursive.h``, ``tests/apps/recursive/``): an outer task's body
spawns a nested taskpool over a :class:`SubtileCollection` of its RW tile,
detaches, and completes when the sub-DAG drains — so outer successors see
the sub-writes exactly as if the body had produced them.
"""

import numpy as np

from parsec_tpu import ptg
from parsec_tpu.comm import run_multirank
from parsec_tpu.data_dist.matrix import SubtileCollection, TiledMatrix, \
    TwoDimBlockCyclic
from parsec_tpu.models.tiled_gemm import tiled_gemm_ptg, \
    tiled_gemm_recursive_ptg
from parsec_tpu.runtime import Context, recursive_call


def _mats(n, nb, nranks=1, rank=0, seed=0):
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((n, n)).astype(np.float32)
    b = rng.standard_normal((n, n)).astype(np.float32)
    c = rng.standard_normal((n, n)).astype(np.float32)
    # tile COPIES: home tiles are views into the source array, and the run
    # mutates C in place — the dense references must stay pristine
    if nranks == 1:
        A = TiledMatrix.from_dense("A", a.copy(), nb, nb)
        B = TiledMatrix.from_dense("B", b.copy(), nb, nb)
        C = TiledMatrix.from_dense("C", c.copy(), nb, nb)
    else:
        mk = lambda nm, arr: TwoDimBlockCyclic.from_dense(
            nm, arr.copy(), nb, nb, P=nranks, Q=1, myrank=rank)
        A, B, C = mk("A", a), mk("B", b), mk("C", c)
    return a, b, c, A, B, C


# ---------------------------------------------------------------------------
# single rank
# ---------------------------------------------------------------------------

def test_recursive_gemm_single_rank():
    """Outer 2x2 tiles, inner 4x4 sub-tiles: C += A@B exact."""
    a, b, c, A, B, C = _mats(32, 16)          # 2x2 outer tiles of 16
    tp = tiled_gemm_recursive_ptg(A, B, C, sub_mb=4, sub_nb=4)
    with Context(nb_cores=0) as ctx:
        ctx.add_taskpool(tp)
        ctx.wait(timeout=60)
    np.testing.assert_allclose(C.to_dense(), c + a @ b, rtol=1e-3, atol=1e-4)


def test_recursive_cutoff_falls_to_cpu_chore():
    """min_tile >= tile size: the evaluate hook skips the recursive chore
    and the plain CPU incarnation runs (reference evaluate protocol)."""
    a, b, c, A, B, C = _mats(16, 8, seed=1)
    tp = tiled_gemm_recursive_ptg(A, B, C, sub_mb=4, sub_nb=4, min_tile=8)
    with Context(nb_cores=0) as ctx:
        ctx.add_taskpool(tp)
        ctx.wait(timeout=60)
    np.testing.assert_allclose(C.to_dense(), c + a @ b, rtol=1e-3, atol=1e-4)


def test_recursive_with_worker_threads():
    a, b, c, A, B, C = _mats(32, 16, seed=2)
    tp = tiled_gemm_recursive_ptg(A, B, C, sub_mb=8, sub_nb=8)
    with Context(nb_cores=2) as ctx:
        ctx.add_taskpool(tp)
        ctx.wait(timeout=60)
    np.testing.assert_allclose(C.to_dense(), c + a @ b, rtol=1e-3, atol=1e-4)


def test_recursive_depth_two():
    """A nested pool whose bodies recurse again (depth-2 sub-tiling)."""
    a, b, c, A, B, C = _mats(32, 16, seed=3)

    p = ptg.PTGBuilder("rec2", A=A, B=B, C=C, MT=C.mt, NT=C.nt, KT=A.nt)
    t = p.task("GEMM",
               m=ptg.span(0, lambda g, l: g.MT - 1),
               n=ptg.span(0, lambda g, l: g.NT - 1),
               k=ptg.span(0, lambda g, l: g.KT - 1))
    t.affinity("C", lambda g, l: (l.m, l.n))
    fa = t.flow("A", ptg.READ)
    fa.input(data=("A", lambda g, l: (l.m, l.k)))
    fb = t.flow("B", ptg.READ)
    fb.input(data=("B", lambda g, l: (l.k, l.n)))
    fc = t.flow("C", ptg.RW)
    fc.input(data=("C", lambda g, l: (l.m, l.n)), guard=lambda g, l: l.k == 0)
    fc.input(pred=("GEMM", "C", lambda g, l: {"m": l.m, "n": l.n, "k": l.k - 1}),
             guard=lambda g, l: l.k > 0)
    fc.output(succ=("GEMM", "C", lambda g, l: {"m": l.m, "n": l.n, "k": l.k + 1}),
              guard=lambda g, l: l.k < g.KT - 1)
    fc.output(data=("C", lambda g, l: (l.m, l.n)),
              guard=lambda g, l: l.k == g.KT - 1)

    def body(es, task, g, l):
        asub = SubtileCollection.of_copy(task.data[0], 8, 8)
        bsub = SubtileCollection.of_copy(task.data[1], 8, 8)
        csub = SubtileCollection.of_copy(task.data[2], 8, 8)
        # the inner pool itself recurses once more, to 4x4 sub-sub-tiles
        inner = tiled_gemm_recursive_ptg(asub, bsub, csub, sub_mb=4, sub_nb=4)
        return recursive_call(es, task, inner, collections=(csub,))

    t.body(body, device="recursive")
    tp = p.build()
    with Context(nb_cores=0) as ctx:
        ctx.add_taskpool(tp)
        ctx.wait(timeout=60)
    np.testing.assert_allclose(C.to_dense(), c + a @ b, rtol=1e-3, atol=1e-4)


def test_recursive_callback_and_async_protocol():
    """The completion chain fires callback before outer successors run."""
    order = []
    a, b, c, A, B, C = _mats(16, 16, seed=4)   # one outer tile

    p = ptg.PTGBuilder("rcb", A=A, B=B, C=C)
    t = p.task("G", z=ptg.span(0, 0))
    t.affinity("C", lambda g, l: (0, 0))
    fc = t.flow("C", ptg.RW)
    fc.input(data=("C", lambda g, l: (0, 0)))
    fc.output(succ=("S", "X", lambda g, l: {"z": 0}))

    def gbody(es, task, g, l):
        sub = SubtileCollection.of_copy(task.data[0], 8, 8)
        asub = SubtileCollection.of_copy(
            A.data_of(0, 0).newest_copy(), 8, 8)
        bsub = SubtileCollection.of_copy(
            B.data_of(0, 0).newest_copy(), 8, 8)
        inner = tiled_gemm_ptg(asub, bsub, sub, devices="cpu")
        return recursive_call(
            es, task, inner,
            callback=lambda tp_, outer: order.append("callback"),
            collections=(sub,))

    t.body(gbody, device="recursive")

    s = p.task("S", z=ptg.span(0, 0))
    s.affinity("C", lambda g, l: (0, 0))
    fx = s.flow("X", ptg.READ)
    fx.input(pred=("G", "C", lambda g, l: {"z": 0}))

    def sbody(es, task, g, l):
        order.append("successor")

    s.body(sbody)
    tp = p.build()
    with Context(nb_cores=0) as ctx:
        ctx.add_taskpool(tp)
        ctx.wait(timeout=60)
    assert order == ["callback", "successor"]


# ---------------------------------------------------------------------------
# 8-rank mesh
# ---------------------------------------------------------------------------

def _rec_rank_body(ctx, rank, nranks):
    n, nb = 32, 4            # 8x1 block-cyclic outer tiles, one row per rank
    a, b, c, A, B, C = _mats(n, nb, nranks=nranks, rank=rank, seed=7)
    tp = tiled_gemm_recursive_ptg(A, B, C, sub_mb=2, sub_nb=2)
    ctx.add_taskpool(tp)
    ctx.wait(timeout=120)
    ctx.comm_barrier()
    # every rank checks its own home tiles against the dense result
    want = c + a @ b
    for m in range(C.mt):
        for nn in range(C.nt):
            if C.rank_of(m, nn) != rank:
                continue
            got = np.asarray(C.data_of(m, nn).newest_copy().value)
            np.testing.assert_allclose(
                got, want[m * nb:(m + 1) * nb, nn * nb:(nn + 1) * nb],
                rtol=1e-3, atol=1e-4)
    return True


def test_recursive_gemm_2rank_nested_pools_stay_local():
    """The nested pools are rank-private: their collections (sub-tiles of a
    flow copy) name no rank, so a successor inside one is this rank's own,
    never an activation sent to the rank such a collection would say."""
    res = run_multirank(2, _rec_rank_body)
    assert all(res)


def test_recursive_gemm_8rank_mesh():
    """Outer tiles block-cyclic over 8 ranks; every rank's bodies spawn
    rank-private nested pools (different counts per rank) without
    desynchronizing the collective taskpool id sequence."""
    res = run_multirank(8, _rec_rank_body)
    assert all(res)
