"""Native (C++) runtime-core tier: build, bindings, hot-path integration.

The analog of the reference's ``tests/class/`` thread-stress suite
(SURVEY §4.1) for the ctypes-bound structures, plus integration checks that
the dispatch hot path actually goes through the native dep table and that
native and Python tiers agree.
"""

import threading

import numpy as np
import pytest

from parsec_tpu import native
from parsec_tpu.runtime.deps import _pack_key64

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native tier not buildable")


def test_ensure_built_returns_lib():
    assert native.ensure_built() is not None


def test_lifo_threaded_stress():
    lifo = native.NativeLifo()
    N, T = 2000, 4
    seen = []
    seen_lock = threading.Lock()

    def worker(base):
        got = []
        for i in range(N):
            lifo.push(base + i)
            if i % 3 == 0:
                v = lifo.pop()
                if v is not None:
                    got.append(v)
        while True:
            v = lifo.pop()
            if v is None:
                break
            got.append(v)
        with seen_lock:
            seen.extend(got)

    ts = [threading.Thread(target=worker, args=(t * N,)) for t in range(T)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    # drain leftovers (races can leave items pushed after a worker's drain)
    while (v := lifo.pop()) is not None:
        seen.append(v)
    assert sorted(seen) == list(range(N * T))
    assert len(lifo) == 0


def test_deptable_mask_protocol():
    t = native.NativeDepTable(64)
    assert not t.release(7, 0b001, 0b111)
    assert not t.release(7, 0b100, 0b111)
    assert len(t) == 1
    assert t.release(7, 0b010, 0b111)       # ready, entry removed
    assert len(t) == 0
    # the key is reusable after readiness (freelist recycling)
    assert t.release(7, 0b1, 0b1)


def test_deptable_double_release_raises():
    t = native.NativeDepTable(64)
    t.release(9, 0b01, 0b11)
    with pytest.raises(AssertionError):
        t.release(9, 0b01, 0b11)


def test_deptable_release_without_a_mask_never_creates_the_entry():
    """Only the arrival that creates a tracker evaluates the task's mask: a
    release that brings none (0) is answered ENTRY_MISSING while no entry
    exists, and updates one that does like any other."""
    t = native.NativeDepTable(64)
    assert t.release(11, 0b01, 0) == native.ENTRY_MISSING
    assert len(t) == 0
    assert t.release(11, 0b01, 0b11) == 0       # created, with its mask
    assert t.release(11, 0b10, 0) == 1          # ready through the entry
    assert len(t) == 0
    assert t.release(11, 0b10, 0) == native.ENTRY_MISSING


def test_deptable_refuses_a_bit_the_task_does_not_wait_for():
    t = native.NativeDepTable(64)
    with pytest.raises(AssertionError, match="waits for"):
        t.release(13, 0b100, 0b011)
    t.release(13, 0b001, 0b011)
    with pytest.raises(AssertionError, match="waits for"):
        t.release(13, 0b100, 0)


def test_deptable_threaded_stress():
    t = native.NativeDepTable(256)
    NKEYS, NBITS = 500, 8
    required = (1 << NBITS) - 1
    ready_counts = [0] * NBITS

    def worker(bit):
        n = 0
        for k in range(NKEYS):
            if t.release(k, 1 << bit, required):
                n += 1
        ready_counts[bit] = n

    ts = [threading.Thread(target=worker, args=(b,)) for b in range(NBITS)]
    for th in ts:
        th.start()
    for th in ts:
        th.join()
    assert sum(ready_counts) == NKEYS       # each key ready exactly once
    assert len(t) == 0


def test_pack_key64_is_exact_or_refused():
    assert _pack_key64(1, 2, (3, 4, 5)) is not None
    # injective on a sample grid
    seen = set()
    for m in range(8):
        for n in range(8):
            for k in range(8):
                seen.add(_pack_key64(1, 2, (m, n, k)))
    assert len(seen) == 512
    # refusals: negative, huge, non-int, too many ids
    assert _pack_key64(1, 2, (-1,)) is None
    assert _pack_key64(1, 2, (1 << 50,)) is None
    assert _pack_key64(1, 2, ("x",)) is None
    assert _pack_key64(1 << 12, 2, (0,)) is None
    assert _pack_key64(1, 1 << 8, (0,)) is None


def _run_ep(nb_cores, sched=None):
    from parsec_tpu import ptg
    from parsec_tpu.runtime import Context

    NT, DEPTH = 10, 20
    done = []
    p = ptg.PTGBuilder("ep", NT=NT, DEPTH=DEPTH, DONE=done)
    t = p.task("EP",
               d=ptg.span(0, lambda g, l: g.DEPTH - 1),
               n=ptg.span(0, lambda g, l: g.NT - 1))
    f = t.flow("ctl", ptg.CTL)
    f.input(pred=("EP", "ctl", lambda g, l: {"d": l.d - 1, "n": l.n}),
            guard=lambda g, l: l.d > 0)
    f.output(succ=("EP", "ctl", lambda g, l: {"d": l.d + 1, "n": l.n}),
             guard=lambda g, l: l.d < g.DEPTH - 1)
    t.body(lambda es, task, g, l: g.DONE.append((l.d, l.n)))
    ctx = Context(nb_cores=nb_cores, scheduler=sched) if sched else \
        Context(nb_cores=nb_cores)
    try:
        ctx.add_taskpool(p.build())
        ctx.wait(timeout=60)
    finally:
        ctx.fini()
    return done


def test_ep_dag_runs_through_native_deptable():
    from parsec_tpu.runtime import Context
    ctx = Context(nb_cores=0)
    try:
        assert ctx.deps.native_enabled
    finally:
        ctx.fini()
    done = _run_ep(nb_cores=2)
    assert len(done) == 200
    assert sorted(done) == sorted((d, n) for d in range(20) for n in range(10))


def test_native_and_python_tiers_agree_on_gemm():
    from parsec_tpu.core.params import params
    from parsec_tpu.data_dist.matrix import TiledMatrix
    from parsec_tpu.models.tiled_gemm import tiled_gemm_ptg
    from parsec_tpu.runtime import Context

    rng = np.random.default_rng(5)
    a, b = rng.standard_normal((8, 8)), rng.standard_normal((8, 8))
    outs = []
    for native_on in (True, False):
        params.set("runtime_native", native_on)
        try:
            A = TiledMatrix.from_dense(f"A{native_on}", a, 4, 4)
            B = TiledMatrix.from_dense(f"B{native_on}", b, 4, 4)
            C = TiledMatrix.from_dense(f"C{native_on}",
                                       np.zeros((8, 8)), 4, 4)
            ctx = Context(nb_cores=2)
            try:
                assert ctx.deps.native_enabled == native_on
                # pin the cpu incarnation: best-device selection is load-
                # dependent and the tpu body computes in f32 — incarnation
                # variance would mask what this test compares (dep tiers)
                ctx.add_taskpool(tiled_gemm_ptg(A, B, C, devices="cpu"))
                ctx.wait(timeout=60)
            finally:
                ctx.fini()
            outs.append(C.to_dense())
        finally:
            params.set("runtime_native", True)
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-6)
    # the cpu body contracts in f32 (gemm_cpu_body): f32-level oracle check
    np.testing.assert_allclose(outs[0], a @ b, atol=1e-5)


def test_ll_scheduler_uses_native_lifo():
    done = _run_ep(nb_cores=2, sched="ll")
    assert len(done) == 200
