"""DTD interface tests — the analog of the reference's ``tests/dsl/dtd/``
suite (task insertion/generation, hazard chains, window backpressure,
scratch/value args, data flush, a DTD tiled GEMM)."""

import numpy as np
import pytest

from parsec_tpu.dtd import (DONT_TRACK, INOUT, INPUT, OUTPUT, SCRATCH, VALUE,
                            DTDTaskpool, Scratch)
from parsec_tpu.runtime.context import Context


@pytest.fixture(params=[0, 3], ids=["caller-driven", "3workers"])
def ctx(request):
    c = Context(nb_cores=request.param)
    yield c
    c.fini()


def test_insert_chain_raw(ctx):
    """RAW chain: each task increments the same tile; order must hold."""
    tp = DTDTaskpool()
    ctx.add_taskpool(tp)
    a = np.zeros((4,), dtype=np.int64)
    trace = []

    def bump(arr, i):
        arr += 1
        trace.append((i, arr[0]))

    for i in range(50):
        tp.insert_task(bump, (a, INOUT), (i, VALUE))
    tp.wait()
    assert a[0] == 50
    assert trace == [(i, i + 1) for i in range(50)]


def test_war_waw_hazards(ctx):
    """Readers between two writers must all run before the second writer
    (WAR), and writers serialize (WAW) — dtd_test_war analog."""
    tp = DTDTaskpool()
    ctx.add_taskpool(tp)
    a = np.array([7.0])
    reads = []

    def write(arr, v):
        arr[0] = v

    def read(arr):
        reads.append(arr[0])

    tp.insert_task(write, (a, OUTPUT), (1.0, VALUE))
    for _ in range(8):
        tp.insert_task(read, (a, INPUT))
    tp.insert_task(write, (a, OUTPUT), (2.0, VALUE))
    tp.insert_task(read, (a, INPUT))
    tp.wait()
    assert reads[:8] == [1.0] * 8
    assert reads[8] == 2.0
    assert a[0] == 2.0


def test_two_tiles_parallel_then_join(ctx):
    tp = DTDTaskpool()
    ctx.add_taskpool(tp)
    x = np.array([1.0])
    y = np.array([2.0])
    z = np.array([0.0])

    def scale(arr, s):
        arr *= s

    def add_into(dst, xa, ya):
        dst[0] = xa[0] + ya[0]

    tp.insert_task(scale, (x, INOUT), (10.0, VALUE))
    tp.insert_task(scale, (y, INOUT), (100.0, VALUE))
    tp.insert_task(add_into, (z, OUTPUT), (x, INPUT), (y, INPUT))
    tp.wait()
    assert z[0] == 10.0 + 200.0


def test_scratch_and_value(ctx):
    tp = DTDTaskpool()
    ctx.add_taskpool(tp)
    out = np.zeros((3,))

    def body(dst, scratch, k):
        scratch[:] = k
        dst[:] = scratch * 2

    tp.insert_task(body, (out, OUTPUT), (Scratch((3,), np.float64), SCRATCH),
                   (21.0, VALUE))
    tp.wait()
    np.testing.assert_allclose(out, 42.0)


def test_functional_update_return(ctx):
    """jax-style bodies return replacement arrays for written flows."""
    tp = DTDTaskpool()
    ctx.add_taskpool(tp)
    t = tp.tile_of_array(np.array([3.0]), key="t")

    def fbody(arr):
        return arr + 1.0   # replaces, does not mutate

    for _ in range(4):
        tp.insert_task(fbody, (t, INOUT))
    tp.wait()
    assert t.data.newest_copy().value[0] == 7.0


def test_window_backpressure(ctx):
    tp = DTDTaskpool()
    ctx.add_taskpool(tp)
    tp.window_size, tp.threshold_size = 16, 8
    a = np.zeros((1,), dtype=np.int64)

    def inc(arr):
        arr += 1

    for _ in range(300):
        tp.insert_task(inc, (a, INOUT))
    tp.wait()
    assert a[0] == 300


def test_dont_track(ctx):
    tp = DTDTaskpool()
    ctx.add_taskpool(tp)
    a = np.zeros((1,))
    seen = []

    def look(arr):
        seen.append(arr[0])

    tp.insert_task(look, (a, INPUT | DONT_TRACK))
    tp.wait()
    assert seen == [0.0]


def test_data_flush(ctx):
    """Flush pushes the final version back to the collection home copy."""
    from parsec_tpu.data_dist.matrix import TiledMatrix

    A = TiledMatrix("A", 8, 8, 4, 4, dtype=np.float64)
    tp = DTDTaskpool()
    ctx.add_taskpool(tp)
    t = tp.tile_of(A, 0, 0)

    def setv(arr):
        arr[:] = 5.0

    tp.insert_task(setv, (t, INOUT))
    tp.data_flush(t)
    tp.wait()
    assert t.flushed
    np.testing.assert_allclose(A.data_of(0, 0).get_copy(0).value, 5.0)


def test_dtd_gemm_correctness(ctx):
    """DTD tiled GEMM vs numpy — dtd_test_simple_gemm analog (CPU path)."""
    rng = np.random.default_rng(0)
    n, nb = 64, 16
    nt = n // nb
    A = rng.standard_normal((n, n)).astype(np.float32)
    B = rng.standard_normal((n, n)).astype(np.float32)
    C = np.zeros((n, n), dtype=np.float32)

    from parsec_tpu.data_dist.matrix import TiledMatrix
    dA = TiledMatrix.from_dense("A", A, nb, nb)
    dB = TiledMatrix.from_dense("B", B, nb, nb)
    dC = TiledMatrix.from_dense("C", C, nb, nb)

    tp = DTDTaskpool()
    ctx.add_taskpool(tp)

    def gemm(c, a, b):
        c += a @ b

    for m in range(nt):
        for nn in range(nt):
            tc = tp.tile_of(dC, m, nn)
            for k in range(nt):
                tp.insert_task(gemm, (tc, INOUT),
                               (tp.tile_of(dA, m, k), INPUT),
                               (tp.tile_of(dB, k, nn), INPUT))
    tp.data_flush_all()
    tp.wait()
    np.testing.assert_allclose(dC.to_dense(), A @ B, rtol=1e-4, atol=1e-4)


def test_task_class_reuse_and_limit(ctx):
    tp = DTDTaskpool()
    ctx.add_taskpool(tp)
    a = np.zeros((1,))

    def inc(arr):
        arr += 1

    for _ in range(5):
        tp.insert_task(inc, (a, INOUT))
    tp.wait()
    assert len(tp._classes) == 1  # one dynamic class per (body, arity)


def test_priority_hint(ctx):
    tp = DTDTaskpool()
    ctx.add_taskpool(tp)
    a = np.zeros((1,))

    def inc(arr):
        arr += 1

    t = tp.insert_task(inc, (a, INOUT), priority=7)
    tp.wait()
    assert t.priority == 7 and t.completed


def test_dtd_gemm_runs_on_the_device_module_and_reads_back_synchronously(
        accel_device):
    """The second front end through the same ``TPUDevice`` (ROADMAP A7): 64
    GEMM tasks inserted at run time go to the accelerator in 4 fused calls,
    48 input tiles are staged once, and since DTD's ``PUSHOUT`` is not
    wired no result tile is pushed out early: the flush reads all 16 back."""
    import parsec_tpu.ops.gemm  # noqa: F401 — registers the "gemm" kernels
    NT, nb = 4, 8
    rng = np.random.default_rng(5)

    def tiles(make):
        return [[make() for _ in range(NT)] for _ in range(NT)]

    A = tiles(lambda: rng.standard_normal((nb, nb), dtype=np.float32))
    B = tiles(lambda: rng.standard_normal((nb, nb), dtype=np.float32))
    C = tiles(lambda: np.zeros((nb, nb), np.float32))

    def gemm(a, b, c):          # the CPU incarnation, not taken here
        c += a @ b

    dev = accel_device
    ctx = Context(nb_cores=0)
    tp = DTDTaskpool()
    ctx.add_taskpool(tp)
    for m in range(NT):
        for n in range(NT):
            for k in range(NT):
                tp.insert_task(gemm, (A[m][k], INPUT), (B[k][n], INPUT),
                               (C[m][n], INOUT), tpu_kernel="gemm")
    tp.wait()
    dev.sync()
    dev.flush_cache()
    for m, n in ((0, 0), (1, 2), (3, 3)):
        got = np.asarray(tp.tile_of_array(C[m][n]).data.newest_copy().value)
        np.testing.assert_allclose(
            got, sum(A[m][k] @ B[k][n] for k in range(NT)),
            rtol=1e-3, atol=1e-4)
    ctx.fini()
    assert (dev.executed_tasks, dev.xla_calls) == (NT ** 3, 4)
    assert dev.bytes_in == 3 * NT * NT * nb * nb * 4
    assert (dev.pushouts, dev.writebacks_early) == (0, 0)
    assert dev.writebacks == NT * NT and dev.bytes_out == NT * NT * nb * nb * 4
