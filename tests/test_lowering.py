"""Taskpool→XLA lowering: the compiled incarnation of regular PTG graphs.

The analog of the reference's chore/incarnation contract
(``parsec_internal.h:396-402``): the same taskpool object that runs through
the dynamic scheduler lowers to one jitted XLA program.  Correctness is
checked against numpy oracles and against the dynamic-runtime execution of
the *same* taskpool.
"""

import numpy as np
import pytest

from parsec_tpu import ptg
from parsec_tpu.data_dist.matrix import TiledMatrix
from parsec_tpu.models.tiled_gemm import tiled_gemm_ptg
from parsec_tpu.ptg.lowering import (LoweringError, lower_taskpool,
                                     register_traceable)
from parsec_tpu.runtime import Context


def _gemm_fixture(n=12, nb=4, seed=0):
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((n, n)).astype(np.float32)
    b = rng.standard_normal((n, n)).astype(np.float32)
    A = TiledMatrix.from_dense("A", a, nb, nb)
    B = TiledMatrix.from_dense("B", b, nb, nb)
    C = TiledMatrix.from_dense("C", np.zeros((n, n), np.float32), nb, nb)
    return a, b, A, B, C


def test_gemm_lowers_to_chain_collapse():
    """The k-chain of GEMM(m,n,k) collapses to one contraction."""
    a, b, A, B, C = _gemm_fixture()
    low = lower_taskpool(tiled_gemm_ptg(A, B, C))
    assert low.mode == "chain-collapse"
    low.execute()
    np.testing.assert_allclose(C.to_dense(), a @ b, rtol=1e-4, atol=1e-4)


def test_gemm_lowered_matches_dynamic_runtime():
    """Compiled and dynamic incarnations of the SAME taskpool agree."""
    a, b, A, B, C = _gemm_fixture(n=8, nb=4, seed=1)
    lower_taskpool(tiled_gemm_ptg(A, B, C)).execute()

    A2 = TiledMatrix.from_dense("A2", a, 4, 4)
    B2 = TiledMatrix.from_dense("B2", b, 4, 4)
    C2 = TiledMatrix.from_dense("C2", np.zeros((8, 8), np.float32), 4, 4)
    ctx = Context(nb_cores=2)
    try:
        ctx.add_taskpool(tiled_gemm_ptg(A2, B2, C2))
        ctx.wait(timeout=60)
    finally:
        ctx.fini()
    np.testing.assert_allclose(C.to_dense(), C2.to_dense(), rtol=1e-5)


def test_gemm_step_fn_is_pure_and_rerunnable():
    """step_fn is a pure stores->stores function: two applications == C+2AB.
    Identity tile grids select the dense store layout (operands read in
    natural [lm, ln] layout, zero gather traffic)."""
    import jax

    a, b, A, B, C = _gemm_fixture(n=8, nb=4, seed=2)
    low = lower_taskpool(tiled_gemm_ptg(A, B, C))
    st = low.initial_stores()
    assert st["C"].shape == (8, 8)    # dense layout chosen
    fn = jax.jit(low.step_fn)
    st = fn(fn(st))
    np.testing.assert_allclose(np.asarray(st["C"]), 2 * (a @ b),
                               rtol=1e-4, atol=1e-4)


def test_gemm_permuted_operand_uses_stacked_gather():
    """A non-identity tile grid (B stored key-transposed) falls back to the
    stacked-store einsum emission and still computes correctly."""
    n, nb = 8, 4
    rng = np.random.default_rng(7)
    a = rng.standard_normal((n, n)).astype(np.float32)
    b = rng.standard_normal((n, n)).astype(np.float32)
    A = TiledMatrix.from_dense("A", a, nb, nb)
    # tile (i, j) of collection Bt holds logical B block (j, i)
    Bt = TiledMatrix("Bt", n, n, nb, nb, dtype=np.float32,
                     init_fn=lambda i, j, s: b[j * nb:(j + 1) * nb,
                                               i * nb:(i + 1) * nb])
    C = TiledMatrix.from_dense("C", np.zeros((n, n), np.float32), nb, nb)
    MT, NT, KT = C.mt, C.nt, A.nt

    p = ptg.PTGBuilder("gemm_bt", A=A, Bt=Bt, C=C, MT=MT, NT=NT, KT=KT)
    t = p.task("GEMM",
               m=ptg.span(0, lambda g, l: g.MT - 1),
               n=ptg.span(0, lambda g, l: g.NT - 1),
               k=ptg.span(0, lambda g, l: g.KT - 1))
    fa = t.flow("A", ptg.READ)
    fa.input(data=("A", lambda g, l: (l.m, l.k)))
    fb = t.flow("B", ptg.READ)
    fb.input(data=("Bt", lambda g, l: (l.n, l.k)))   # transposed storage
    fc = t.flow("C", ptg.RW)
    fc.input(data=("C", lambda g, l: (l.m, l.n)), guard=lambda g, l: l.k == 0)
    fc.input(pred=("GEMM", "C",
                   lambda g, l: {"m": l.m, "n": l.n, "k": l.k - 1}),
             guard=lambda g, l: l.k > 0)
    fc.output(succ=("GEMM", "C",
                    lambda g, l: {"m": l.m, "n": l.n, "k": l.k + 1}),
              guard=lambda g, l: l.k < g.KT - 1)
    fc.output(data=("C", lambda g, l: (l.m, l.n)),
              guard=lambda g, l: l.k == g.KT - 1)
    t.body(device="tpu", dyld="gemm")

    low = lower_taskpool(p.build())
    assert low.mode == "chain-collapse"
    st = low.initial_stores()
    assert st["Bt"].ndim == 3         # stacked (gather) layout
    low.execute()
    np.testing.assert_allclose(C.to_dense(), a @ b, rtol=1e-4, atol=1e-4)


register_traceable("lower_scale2", lambda x: x * 2.0)


def _scale_chain_ptg(x, nb=4, K=3):
    X = TiledMatrix.from_dense("X", x.copy(), nb, nb)
    p = ptg.PTGBuilder("chain", X=X, K=K, MT=X.mt, NT=X.nt)
    t = p.task("SCALE",
               m=ptg.span(0, lambda g, l: g.MT - 1),
               n=ptg.span(0, lambda g, l: g.NT - 1),
               k=ptg.span(0, lambda g, l: g.K - 1))
    f = t.flow("V", ptg.RW)
    f.input(data=("X", lambda g, l: (l.m, l.n)), guard=lambda g, l: l.k == 0)
    f.input(pred=("SCALE", "V",
                  lambda g, l: {"m": l.m, "n": l.n, "k": l.k - 1}),
            guard=lambda g, l: l.k > 0)
    f.output(succ=("SCALE", "V",
                   lambda g, l: {"m": l.m, "n": l.n, "k": l.k + 1}),
             guard=lambda g, l: l.k < g.K - 1)
    f.output(data=("X", lambda g, l: (l.m, l.n)),
             guard=lambda g, l: l.k == g.K - 1)
    t.body(device="tpu", dyld="lower_scale2")
    return p.build(), X


def test_unrolled_chain_with_pred_edges():
    """A non-bilinear accumulation chain through the forced unrolled pass:
    value forwarding across pred edges, final store writeback only."""
    x = np.arange(64, dtype=np.float32).reshape(8, 8)
    tp, X = _scale_chain_ptg(x)
    low = lower_taskpool(tp, passes="unrolled")
    assert low.mode == "unrolled"
    low.execute()
    np.testing.assert_allclose(X.to_dense(), x * 8.0)


def test_wavefront_chain_auto_selected_and_matches():
    """auto picks the wavefront pass for a non-bilinear chain; per-level
    batched emission computes the same result as unrolled."""
    x = np.arange(64, dtype=np.float32).reshape(8, 8)
    tp, X = _scale_chain_ptg(x)
    low = lower_taskpool(tp)
    assert low.mode == "wavefront"
    low.execute()
    np.testing.assert_allclose(X.to_dense(), x * 8.0)


def test_read_flow_forwarding_through_two_classes():
    """READ flows forward their input to successors; two classes chain."""
    nb = 4
    x = np.full((4, 4), 3.0, np.float32)
    X = TiledMatrix.from_dense("X", x, nb, nb)
    Y = TiledMatrix.from_dense("Y", np.zeros((4, 4), np.float32), nb, nb)

    p = ptg.PTGBuilder("fwd", X=X, Y=Y)
    t1 = p.task("SRC", z=ptg.span(0, 0))
    f1 = t1.flow("A", ptg.READ)
    f1.input(data=("X", lambda g, l: (0, 0)))
    f1.output(succ=("DST", "B", lambda g, l: {"z": 0}))
    t1.body(device="tpu", dyld="lower_scale2")

    t2 = p.task("DST", z=ptg.span(0, 0))
    f2 = t2.flow("B", ptg.RW)
    f2.input(pred=("SRC", "A", lambda g, l: {"z": 0}))
    f2.output(data=("Y", lambda g, l: (0, 0)))
    t2.body(device="tpu", dyld="lower_scale2")

    low = lower_taskpool(p.build())
    assert low.mode == "wavefront"
    low.execute()
    # SRC's READ flow forwards X unchanged (its result is not a writable
    # flow); DST doubles it once.
    np.testing.assert_allclose(Y.to_dense(), x * 2.0)


def test_wavefront_program_is_level_sized_not_task_sized():
    """The wavefront emission is O(levels·classes): for a K-step chain over
    many tiles its jaxpr is a small multiple of K, far below the unrolled
    pass's O(tasks) trace (the round-3 perf ceiling on Cholesky/stencil)."""
    import jax

    x = np.zeros((32, 32), np.float32)
    tp, X = _scale_chain_ptg(x, nb=4, K=3)        # 64 tasks per level
    wf = lower_taskpool(tp, passes="wavefront")
    un = lower_taskpool(tp, passes="unrolled")
    n_wf = len(jax.make_jaxpr(wf.step_fn)(wf.initial_stores()).eqns)
    n_un = len(jax.make_jaxpr(un.step_fn)(un.initial_stores()).eqns)
    assert n_wf < n_un / 5, (n_wf, n_un)
    assert n_wf < 48, n_wf                        # ~a handful of ops per level
    # (48, not a tighter bound: the exact eqn count drifts a few ops
    # between jax releases — 42 on 0.4.37 — and the level-sized-vs-
    # task-sized claim is carried by the n_un/5 ratio assert above)


def test_wavefront_war_hazard_falls_back_to_unrolled():
    """A version that must survive past a later in-place write cannot run
    through in-place wavefront stores — auto degrades to unrolled and the
    forwarded value is still the ORIGINAL tile."""
    x = np.full((4, 4), 3.0, np.float32)
    X = TiledMatrix.from_dense("X", x, 4, 4)
    Y = TiledMatrix.from_dense("Y", np.zeros((4, 8), np.float32), 4, 4)

    p = ptg.PTGBuilder("war", X=X, Y=Y)
    # SRC reads X(0,0) and forwards it two levels down to DST
    t1 = p.task("SRC", z=ptg.span(0, 0))
    f1 = t1.flow("A", ptg.READ)
    f1.input(data=("X", lambda g, l: (0, 0)))
    f1.output(succ=("MID", "B", lambda g, l: {"z": 0}))
    t1.body(device="tpu", dyld="lower_scale2")
    t2 = p.task("MID", z=ptg.span(0, 0))
    f2 = t2.flow("B", ptg.READ)
    f2.input(pred=("SRC", "A", lambda g, l: {"z": 0}))
    f2.output(succ=("DST", "C", lambda g, l: {"z": 0}))
    t2.body(device="tpu", dyld="lower_scale2")
    t3 = p.task("DST", z=ptg.span(0, 0))
    f3 = t3.flow("C", ptg.RW)
    f3.input(pred=("MID", "B", lambda g, l: {"z": 0}))
    f3.output(data=("Y", lambda g, l: (0, 0)))
    t3.body(device="tpu", dyld="lower_scale2")
    # WRITER updates X(0,0) in place (no collection out-arrow: a scratch
    # write in wavefront terms), racing the forwarded original
    t4 = p.task("WRITER", z=ptg.span(0, 0))
    f4 = t4.flow("V", ptg.RW)
    f4.input(data=("X", lambda g, l: (0, 0)))
    f4.output(succ=("SINK", "W", lambda g, l: {"z": 0}))
    t4.body(device="tpu", dyld="lower_scale2")
    t5 = p.task("SINK", z=ptg.span(0, 0))
    f5 = t5.flow("W", ptg.RW)
    f5.input(pred=("WRITER", "V", lambda g, l: {"z": 0}))
    f5.output(data=("Y", lambda g, l: (0, 1)))
    t5.body(device="tpu", dyld="lower_scale2")

    low = lower_taskpool(p.build())
    assert low.mode == "unrolled"     # wavefront detected the WAR hazard
    low.execute()
    d = Y.to_dense()
    np.testing.assert_allclose(d[:4, :4], x * 2.0)       # original forwarded
    np.testing.assert_allclose(d[:4, 4:8], x * 4.0)      # WRITER·2 then SINK·2


def test_wavefront_scratch_never_shadows_collection_read():
    """An in-place (scratch) version parked on a store row must not be
    visible to a LATER direct ``data=`` read of that row — the source
    program still sees the pristine tile.  The wavefront pass detects the
    shadowing and auto falls back to unrolled."""
    x = np.full((4, 8), 3.0, np.float32)
    X = TiledMatrix.from_dense("X", x, 4, 4)      # tiles (0,0), (0,1)
    Y = TiledMatrix.from_dense("Y", np.zeros((4, 4), np.float32), 4, 4)

    p = ptg.PTGBuilder("shadow", X=X, Y=Y)
    # WRITER doubles X(0,0) in place (succ-only out-arrow: scratch write)
    t1 = p.task("WRITER", z=ptg.span(0, 0))
    f1 = t1.flow("V", ptg.RW)
    f1.input(data=("X", lambda g, l: (0, 0)))
    f1.output(succ=("SINK", "W", lambda g, l: {"z": 0}))
    t1.body(device="tpu", dyld="lower_scale2")
    t2 = p.task("SINK", z=ptg.span(0, 0))
    f2 = t2.flow("W", ptg.READ)
    f2.input(pred=("WRITER", "V", lambda g, l: {"z": 0}))
    t2.body(device="tpu", dyld="lower_scale2")
    # PRE pushes READER to level 1 via a CTL edge; READER then reads X(0,0)
    # directly — AFTER the scratch write has landed on its row
    t3 = p.task("PRE", z=ptg.span(0, 0))
    f3 = t3.flow("P", ptg.READ)
    f3.input(data=("X", lambda g, l: (0, 1)))
    c3 = t3.flow("GO", ptg.CTL)
    c3.output(succ=("READER", "D", lambda g, l: {"z": 0}))
    t3.body(device="tpu", dyld="lower_scale2")
    t4 = p.task("READER", z=ptg.span(0, 0))
    f4 = t4.flow("D", ptg.CTL)
    f4.input(pred=("PRE", "GO", lambda g, l: {"z": 0}))
    f5 = t4.flow("E", ptg.READ)
    f5.input(data=("X", lambda g, l: (0, 0)))
    f5.output(data=("Y", lambda g, l: (0, 0)))
    t4.body(device="tpu", dyld="lower_scale2")

    low = lower_taskpool(p.build())
    assert low.mode == "unrolled"
    low.execute()
    np.testing.assert_allclose(Y.to_dense(), x[:, :4])  # pristine, not 2x


register_traceable("lower_halo_sum",
                   lambda c, l, r: c + (0.0 if l is None else l.sum())
                   + (0.0 if r is None else r.sum()))


def test_wavefront_missing_inputs_pass_none():
    """Flows with no active input arrow (stencil boundaries) reach the
    traceable as ``None``; boundary tasks group separately from interior."""
    nb = 2
    x = np.arange(12, dtype=np.float32).reshape(2, 6)
    X = TiledMatrix.from_dense("X", x.copy(), 2, nb)
    NT = X.nt

    p = ptg.PTGBuilder("halo", X=X, NT=NT)
    t = p.task("H", i=ptg.span(0, lambda g, l: g.NT - 1))
    fc = t.flow("C", ptg.RW)
    fc.input(data=("X", lambda g, l: (0, l.i)))
    fc.output(data=("X", lambda g, l: (0, l.i)))
    fl = t.flow("L", ptg.READ)
    fl.input(data=("X", lambda g, l: (0, l.i - 1)),
             guard=lambda g, l: l.i > 0)
    fr = t.flow("R", ptg.READ)
    fr.input(data=("X", lambda g, l: (0, l.i + 1)),
             guard=lambda g, l: l.i < g.NT - 1)
    t.body(device="tpu", dyld="lower_halo_sum")

    low = lower_taskpool(p.build())
    assert low.mode == "wavefront"
    low.execute()
    tiles = [x[:, 2 * i:2 * i + 2] for i in range(NT)]
    expect = np.hstack([
        tiles[i]
        + (tiles[i - 1].sum() if i > 0 else 0.0)
        + (tiles[i + 1].sum() if i < NT - 1 else 0.0)
        for i in range(NT)])
    np.testing.assert_allclose(X.to_dense(), expect)


def test_python_body_is_not_lowerable():
    X = TiledMatrix.from_dense("X", np.zeros((4, 4), np.float32), 4, 4)
    p = ptg.PTGBuilder("nope", X=X)
    t = p.task("T", z=ptg.span(0, 0))
    f = t.flow("V", ptg.RW)
    f.input(data=("X", lambda g, l: (0, 0)))
    f.output(data=("X", lambda g, l: (0, 0)))
    t.body(lambda es, task, g, l: None)       # python-only body
    with pytest.raises(LoweringError):
        lower_taskpool(p.build())


def test_ragged_tiles_are_not_lowerable():
    a = np.zeros((6, 6), np.float32)          # 6/4 -> ragged edge tiles
    A = TiledMatrix.from_dense("A", a, 4, 4)
    B = TiledMatrix.from_dense("B", a.copy(), 4, 4)
    C = TiledMatrix.from_dense("C", a.copy(), 4, 4)
    with pytest.raises(LoweringError):
        lower_taskpool(tiled_gemm_ptg(A, B, C))


def test_writeback_bumps_versions():
    a, b, A, B, C = _gemm_fixture(n=8, nb=4, seed=3)
    v0 = C.data_of(0, 0).newest_copy().version
    lower_taskpool(tiled_gemm_ptg(A, B, C)).execute()
    assert C.data_of(0, 0).newest_copy().version == v0 + 1


# ---------------------------------------------------------------------------
# persistent lowering/compile cache (ISSUE 2)
# ---------------------------------------------------------------------------

def test_lowering_cache_hit_reuses_executable_and_matches_miss():
    """Two structurally identical lowerings share ONE jitted executable
    (the second invocation pays no trace/compile) and produce identical
    numerics — hit == miss bit-for-bit."""
    from parsec_tpu.ptg.lowering import lowering_cache

    a, b, A, B, C = _gemm_fixture(n=12, nb=4, seed=3)
    low1 = lower_taskpool(tiled_gemm_ptg(A, B, C))
    h0, m0 = lowering_cache.hits, lowering_cache.misses
    jf1 = low1.jitted()
    out1 = np.asarray(jf1(low1.initial_stores())["C"])

    a2, b2, A2, B2, C2 = _gemm_fixture(n=12, nb=4, seed=3)
    low2 = lower_taskpool(tiled_gemm_ptg(A2, B2, C2))
    assert low2.signature == low1.signature
    jf2 = low2.jitted()
    assert jf2 is jf1, "second lowering must hit the executable cache"
    assert lowering_cache.hits >= h0 + 1
    out2 = np.asarray(jf2(low2.initial_stores())["C"])
    np.testing.assert_array_equal(out1, out2)
    # identity tile grids lower to the dense store layout: out IS [n, n]
    np.testing.assert_allclose(out1, a @ b, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("n,nb", [(16, 4), (64, 32)])
def test_lowering_cache_second_invocation_compiles_nothing(
        compile_requests, n, nb):
    """The acceptance pin: a repeat lowered stage in one process hits the
    process-wide lowering cache and asks XLA for no compile at all (cold
    includes a real XLA compile; warm is a dict hit + cached call)."""
    from parsec_tpu.ptg.lowering import lowering_cache

    def once():
        _, _, A, B, C = _gemm_fixture(n=n, nb=nb, seed=11)
        low = lower_taskpool(tiled_gemm_ptg(A, B, C))
        out = low.jitted()(low.initial_stores())
        float(np.asarray(out["C"]).reshape(-1)[0])

    once()
    hits, requests = lowering_cache.hits, compile_requests()
    once()
    assert lowering_cache.hits - hits >= 1
    assert compile_requests() == requests


def _chol(n=128, nb=32):
    from parsec_tpu.data_dist.matrix import SymTwoDimBlockCyclic
    from parsec_tpu.models.cholesky import make_spd, tiled_cholesky_ptg
    a = make_spd(n)
    A = SymTwoDimBlockCyclic.from_dense("A", a.copy(), nb, nb)
    return tiled_cholesky_ptg(A), lambda: (
        np.tril(A.to_dense()),
        np.linalg.cholesky(a.astype(np.float64)).astype(np.float32))


def _gemm64():
    a, b, A, B, C = _gemm_fixture(n=64, nb=32, seed=3)
    return tiled_gemm_ptg(A, B, C), lambda: (C.to_dense(), a @ b)


def _stencil():
    from parsec_tpu.data_dist.matrix import VectorTwoDimCyclic
    from parsec_tpu.models.stencil import stencil_1d_ptg, stencil_reference
    base = np.random.default_rng(3).standard_normal(64)
    w = np.array([0.2, 0.6, 0.2])
    V = VectorTwoDimCyclic("V", lm=64, mb=16,
                           init_fn=lambda m, size: base[m * 16:m * 16 + size]
                           .copy())
    return stencil_1d_ptg(V, w, 12), lambda: (
        np.concatenate([np.asarray(V.data_of(i).newest_copy().value)
                        for i in range(V.mt)]),
        stencil_reference(base, w, 12))


@pytest.mark.parametrize("make,passes,mode,calls", [
    (_gemm64, "chain-collapse", "chain-collapse", 1),
    (_chol, "wavefront", "wavefront", 1),
    (_chol, "unrolled", "unrolled", 1),
    (_chol, "regions", "region", 4),
    (_stencil, "auto", "wavefront", 1)],
    ids=["chain-collapse", "wavefront", "unrolled", "regions", "scan"])
def test_xla_calls_per_dag_by_emission(param, make, passes, mode, calls):
    """What one DAG costs in XLA dispatches under each emission, on its
    smoke shape: a whole-pool emission is one call whatever the graph (the
    nt=4 Cholesky's 20 tasks, the stencil's 12 sweeps folded into one
    ``lax.scan``), the region plan one call a region."""
    import jax

    from parsec_tpu.device.device import xla_calls_total
    from parsec_tpu.ptg.lowering import lower_regions
    param("lowering_scan_min", 4)
    tp, result = make()
    if passes == "regions":
        low = lower_regions(tp, max_tasks=6)
        assert low.stats()["ntasks"] == 20 and len(low.regions) == calls
    else:
        low = lower_taskpool(tp, passes=passes)
    assert low.mode == mode
    if make is _stencil:
        prims = {e.primitive.name for e in jax.make_jaxpr(low.step_fn)(
            low.initial_stores()).eqns}
        assert "scan" in prims, prims
    before = xla_calls_total()
    low.execute()
    assert xla_calls_total() - before == calls
    got, expect = result()
    np.testing.assert_allclose(got, expect, rtol=1e-3, atol=1e-4)


def test_lowering_cache_distinguishes_different_structures():
    """Structurally different programs must carry different signatures
    (no false sharing of executables).  Same kernel + same collection
    names + different wavefront structure (stencil sweep lengths) is the
    sharpest case: only the emitted level plan differs."""
    from parsec_tpu.data_dist.matrix import VectorTwoDimCyclic
    from parsec_tpu.models.stencil import stencil_1d_ptg

    def low(iters):
        V = VectorTwoDimCyclic("V", lm=1 << 10, mb=1 << 8, P=1,
                               init_fn=lambda m, size:
                               np.zeros(size, np.float32))
        w = np.full(3, 1.0 / 3.0)
        return lower_taskpool(stencil_1d_ptg(V, w, iters))

    l4, l8 = low(4), low(8)
    assert l4.mode == l8.mode == "wavefront"
    assert l4.signature != l8.signature


def test_lowering_cache_param_disables_sharing(param):
    param("lowering_cache", False)
    _, _, A, B, C = _gemm_fixture(n=12, nb=4, seed=5)
    low1 = lower_taskpool(tiled_gemm_ptg(A, B, C))
    _, _, A2, B2, C2 = _gemm_fixture(n=12, nb=4, seed=5)
    low2 = lower_taskpool(tiled_gemm_ptg(A2, B2, C2))
    assert low1.jitted() is not low2.jitted()


def test_lowered_execute_goes_through_cache():
    """LoweredTaskpool.execute() (the collection-writeback convenience)
    rides the same cached executable."""
    a, b, A, B, C = _gemm_fixture(n=8, nb=4, seed=6)
    low1 = lower_taskpool(tiled_gemm_ptg(A, B, C))
    low1.execute()
    a2, b2, A2, B2, C2 = _gemm_fixture(n=8, nb=4, seed=6)
    low2 = lower_taskpool(tiled_gemm_ptg(A2, B2, C2))
    low2.execute()
    assert low2._jitted is low1._jitted
    np.testing.assert_allclose(C.to_dense(), C2.to_dense(), rtol=1e-5)
    np.testing.assert_allclose(C.to_dense(), a @ b, rtol=1e-4, atol=1e-4)
