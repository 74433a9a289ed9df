"""Stress: the round's features composed (the tests/runtime/stress analog).

Each configuration runs a full block-cyclic GEMM through the dynamic
multi-rank runtime with a different combination of worker threads, the
dedicated comm thread, coalescing, and scheduler modules — the goal is
racing the protocol layers against each other, not numerics novelty.
"""

import numpy as np
import pytest

from parsec_tpu.comm import run_multirank
from parsec_tpu.core.params import params
from parsec_tpu.data_dist.matrix import TwoDimBlockCyclic
from parsec_tpu.models.tiled_gemm import tiled_gemm_ptg


def _gemm_body(ctx, rank, nranks):
    n, nb = 96, 16
    rng = np.random.RandomState(41)
    a = rng.randn(n, n).astype(np.float32)
    b = rng.randn(n, n).astype(np.float32)
    P = 2 if nranks % 2 == 0 else 1
    Q = nranks // P
    A = TwoDimBlockCyclic.from_dense("A", a, nb, nb, P=P, Q=Q, myrank=rank)
    B = TwoDimBlockCyclic.from_dense("B", b, nb, nb, P=P, Q=Q, myrank=rank)
    C = TwoDimBlockCyclic("C", n, n, nb, nb, P=P, Q=Q, myrank=rank)
    ctx.add_taskpool(tiled_gemm_ptg(A, B, C, devices="cpu"))
    ctx.wait(timeout=180)
    ctx.comm_barrier()
    return C.to_dense()


def _check(res):
    n = 96
    rng = np.random.RandomState(41)
    a = rng.randn(n, n).astype(np.float32)
    b = rng.randn(n, n).astype(np.float32)
    got = np.zeros((n, n), np.float32)
    for part in res:
        got += part
    np.testing.assert_allclose(got, a @ b, rtol=1e-3, atol=1e-3)


CONFIGS = [
    # (nranks, nb_cores, comm_thread, coalesce, sched)
    (8, 0, False, True, "lfq"),      # wide mesh, funneled
    (4, 2, True, True, "lfq"),       # workers + comm thread + coalescing
    (4, 2, True, False, "ll"),       # comm thread, no coalescing, LIFO zoo
    (2, 3, False, True, "pbq"),      # hierarchical scheduler under workers
]


@pytest.mark.parametrize("nranks,cores,cthread,coal,sched", CONFIGS)
def test_gemm_stress(param, nranks, cores, cthread, coal, sched):
    param("comm_thread", cthread)
    param("comm_coalesce", coal)
    param("sched", sched)
    _check(run_multirank(nranks, _gemm_body, nb_cores=cores, timeout=240))


# ---------------------------------------------------------------------------
# round-4 feature interplay: recursive bodies + DTD discovery + live props
# + steal accounting racing on one context
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rep", range(3))
def test_round4_features_race(param, tmp_path, rep):
    """Recursive GEMM (nested pools) and body-driven DTD discovery run
    CONCURRENTLY on one 4-worker context while the properties stream
    writes snapshots and print_steals counts — the protocols must not
    interfere (nested local-only pools, insert locks, PINS chains,
    props registry)."""
    from parsec_tpu.core.mca import repository
    from parsec_tpu.data_dist.matrix import TiledMatrix
    from parsec_tpu.dtd import DTDTaskpool
    from parsec_tpu.models.irregular import (haar_project_dtd,
                                             haar_project_reference)
    from parsec_tpu.models.tiled_gemm import tiled_gemm_recursive_ptg
    from parsec_tpu.runtime import Context

    param("props_stream", str(tmp_path / f"props{rep}.json"))
    param("props_stream_interval", 0.02)
    comp = repository.find("pins", "print_steals")
    mod = comp.open()
    try:
        rng = np.random.default_rng(rep)
        n, nb = 32, 8
        a = rng.standard_normal((n, n)).astype(np.float32)
        b = rng.standard_normal((n, n)).astype(np.float32)
        c = rng.standard_normal((n, n)).astype(np.float32)
        A = TiledMatrix.from_dense("A", a.copy(), nb, nb)
        B = TiledMatrix.from_dense("B", b.copy(), nb, nb)
        C = TiledMatrix.from_dense("C", c.copy(), nb, nb)
        with Context(nb_cores=4) as ctx:
            rec = tiled_gemm_recursive_ptg(A, B, C, sub_mb=4, sub_nb=4)
            ctx.add_taskpool(rec)
            dtd = DTDTaskpool(f"haar{rep}")
            ctx.add_taskpool(dtd)
            tree = haar_project_dtd(dtd, 1.0, 1e-4, min_depth=4,
                                    max_depth=18)
            dtd.wait(timeout=180)
            ctx.wait(timeout=180)
        np.testing.assert_allclose(C.to_dense(), c + a @ b, rtol=1e-3,
                                   atol=1e-4)
        want = haar_project_reference(1.0, 1e-4, min_depth=4, max_depth=18)
        assert set(tree) == set(want)
        # the observability protocols must have actually observed: the
        # stream wrote snapshots and the steal counter saw the 4 workers
        import json
        snap = json.load(open(tmp_path / f"props{rep}.json"))
        assert "props" in snap and any(
            k.startswith("rank0") for k in snap["props"])
        assert sum(mod.steals.values()) > 0
    finally:
        comp.close(mod)
