"""Dep-storage variants (VERDICT r4 missing #5): the hashed tier
(``parsec_hash_find_deps``) vs the index-array tier
(``parsec_default_find_deps`` / ``-M index-array``) — correctness under
both, plus the count the fold-in claim rests on: on a dense space the
hashed tier pays one table entry an edge and leaves none behind."""

import numpy as np
import pytest

from parsec_tpu import ptg
from parsec_tpu.runtime import Context


def _ep_pool(NT=40, DEPTH=25):
    p = ptg.PTGBuilder("ep", NT=NT, DEPTH=DEPTH)
    t = p.task("EP",
               d=ptg.span(0, lambda g, l: g.DEPTH - 1),
               n=ptg.span(0, lambda g, l: g.NT - 1))
    f = t.flow("ctl", ptg.CTL)
    f.input(pred=("EP", "ctl", lambda g, l: {"d": l.d - 1, "n": l.n}),
            guard=lambda g, l: l.d > 0)
    f.output(succ=("EP", "ctl", lambda g, l: {"d": l.d + 1, "n": l.n}),
             guard=lambda g, l: l.d < g.DEPTH - 1)
    t.body(lambda es, task, g, l: None)
    return p.build()


def _drain_ep(param, storage, native, NT=40, DEPTH=25):
    """Drain the dense EP grid; the dep tracker afterwards and how many
    entries its hashed table was handed."""
    param("deps_storage", storage)
    param("runtime_native", native)
    ctx = Context(nb_cores=0)
    inserts = []
    insert = ctx.deps._table.insert
    ctx.deps._table.insert = lambda k, v: (inserts.append(k), insert(k, v))[1]
    ctx.add_taskpool(_ep_pool(NT, DEPTH))
    ctx.wait(timeout=120)
    ctx.fini()
    return ctx.deps, len(inserts)


def test_index_array_tier_selected_for_static_boxes(param):
    param("deps_storage", "index-array")
    ctx = Context(nb_cores=0)
    assert ctx.deps._index_store is not None
    tp = _ep_pool(8, 6)
    ctx.add_taskpool(tp)
    ctx.wait(timeout=60)
    store = ctx.deps._index_store
    # the tier genuinely engaged: one dense array allocated for the EP
    # class, every non-startup task's dep released through it, and the
    # array purged at taskpool termination
    assert store.allocated == 1, "index-array tier never engaged"
    assert store.releases == 8 * (6 - 1)      # DEPTH-1 arrivals per lane
    assert not store._arrays                   # purged at termination
    ctx.fini()


def test_space_extents_captured_for_static_ranges():
    tp = _ep_pool(8, 6)
    tc = tp.task_class("EP")
    assert tc.space_extents == ((0, 6), (0, 8))


def test_gemm_numerics_identical_under_index_array(param):
    from parsec_tpu.data_dist.matrix import TiledMatrix
    from parsec_tpu.models.tiled_gemm import tiled_gemm_ptg

    param("deps_storage", "index-array")
    rng = np.random.default_rng(31)
    a = rng.standard_normal((48, 48)).astype(np.float32)
    b = rng.standard_normal((48, 48)).astype(np.float32)
    A = TiledMatrix.from_dense("A", a, 16, 16)
    B = TiledMatrix.from_dense("B", b, 16, 16)
    C = TiledMatrix.from_dense("C", np.zeros((48, 48), np.float32), 16, 16)
    ctx = Context(nb_cores=2)
    ctx.add_taskpool(tiled_gemm_ptg(A, B, C, devices="cpu"))
    ctx.wait(timeout=60)
    ctx.fini()
    np.testing.assert_allclose(C.to_dense(), a @ b, rtol=1e-4, atol=1e-4)


def test_hashed_fold_in_costs_nothing_on_dense_spaces(param):
    """Drain the same 1,000-task dense EP grid under direct indexing and
    under the hashed Python tier.  'Folding index-array into the hashed
    interface costs nothing' as counts: the indexed tier takes every one of
    the 960 edges and the hashed table sees none; the hashed tier is handed
    at most one entry an edge (a single-input task is ready on arrival) and
    keeps none once the pool has drained."""
    edges = 40 * 24
    deps, inserts = _drain_ep(param, "index-array", native=False)
    assert deps._index_store.releases == edges
    assert inserts == 0 and len(deps._table) == 0
    deps, inserts = _drain_ep(param, "hash", native=False)
    assert deps._index_store is None
    assert inserts <= edges and len(deps._table) == 0


def test_triangular_space_falls_back_cleanly(param):
    """A class whose ranges depend on earlier params has no static box:
    the index-array tier must fall back to the hashed tier, silently."""
    param("deps_storage", "index-array")
    done = []
    p = ptg.PTGBuilder("tri", N=6)
    t = p.task("T",
               i=ptg.span(0, lambda g, l: g.N - 1),
               j=ptg.span(0, lambda g, l: l.i))    # triangular
    f = t.flow("ctl", ptg.CTL)
    f.input(pred=("T", "ctl", lambda g, l: {"i": l.i - 1, "j": l.j}),
            guard=lambda g, l: l.i > 0 and l.j <= l.i - 1)
    f.output(succ=("T", "ctl", lambda g, l: {"i": l.i + 1, "j": l.j}),
             guard=lambda g, l: l.i < g.N - 1)
    t.body(lambda es, task, g, l: done.append((l.i, l.j)))
    tp = p.build()
    assert tp.task_class("T").space_extents is None
    ctx = Context(nb_cores=0)
    ctx.add_taskpool(tp)
    ctx.wait(timeout=60)
    ctx.fini()
    assert len(done) == 6 * 7 // 2


def test_oversized_static_box_falls_back_to_hashed_tier(param):
    """A static box bigger than deps_index_array_max_slots must NOT be
    materialized densely (gigabytes of empty slots for a mostly-empty
    space) — the class silently takes the hashed tier instead."""
    param("deps_storage", "index-array")
    param("deps_index_array_max_slots", 16)   # force the guard
    ctx = Context(nb_cores=0)
    store = ctx.deps._index_store
    assert store is not None
    tp = _ep_pool(8, 6)          # box volume 48 > 16
    ctx.add_taskpool(tp)
    ctx.wait(timeout=60)
    assert store.allocated == 0, "dense array allocated despite the cap"
    assert store.releases == 0
    ctx.fini()
