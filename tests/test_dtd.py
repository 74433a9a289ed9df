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
    48 input tiles are staged once, and since no argument carries
    ``PUSHOUT`` no result tile is pushed out early: the flush reads all 16
    back (the flag's semantics: an untagged tile keeps the synchronous
    read)."""
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


# ---------------------------------------------------------------------------
# models/tiled_gemm.py:tiled_gemm_dtd -- the reference harness's insertion
# program (dtd_test_simple_gemm.c): PUSHOUT on a tile's last k, the window
# ---------------------------------------------------------------------------

def _operands(nt, nb, seed=34):
    from parsec_tpu.data_dist.matrix import TiledMatrix
    rng = np.random.default_rng(seed)
    n = nt * nb
    a = rng.standard_normal((n, n)).astype(np.float32)
    b = rng.standard_normal((n, n)).astype(np.float32)
    return a, b, (TiledMatrix.from_dense("A", a, nb, nb),
                  TiledMatrix.from_dense("B", b, nb, nb),
                  TiledMatrix("C", n, n, nb, nb))


def _solve_dtd(colls, pool_cls=DTDTaskpool):
    """One solve as the benchmark's DTD path makes it; the pool and C."""
    from parsec_tpu.models.tiled_gemm import tiled_gemm_dtd
    ctx = Context(nb_cores=0)
    tp = pool_cls()
    ctx.add_taskpool(tp)
    assert tiled_gemm_dtd(tp, *colls) == colls[2].mt ** 3
    tp.wait(timeout=120)
    for d in ctx.accelerators():
        d.sync()
        d.flush_cache()
    ctx.fini()
    return tp, colls[2].to_dense()


def _solve_ptg(colls):
    from parsec_tpu.models.tiled_gemm import tiled_gemm_ptg
    ctx = Context(nb_cores=0)
    ctx.add_taskpool(tiled_gemm_ptg(*colls))
    ctx.wait(timeout=120)
    for d in ctx.accelerators():
        d.sync()
        d.flush_cache()
    ctx.fini()
    return colls[2].to_dense()


@pytest.mark.parametrize("where", ["host_bodies", "accelerator"])
@pytest.mark.parametrize("nt,nb", [(4, 8), (8, 16)])
def test_tiled_gemm_dtd_equals_numpy_and_the_ptg_to_the_last_bit(
        nt, nb, where, request):
    """Against numpy's product of the same seeded operands: rtol 1e-3 of
    the largest entry, because both backends here multiply f32 in f32 and
    differ from numpy's blocked sum only in the order of K = nt * nb <= 128
    additions (about K * 6e-8).  Against ``tiled_gemm_ptg``: equal bit for
    bit, on the host bodies and through the device module alike: every C
    tile is the same chain C_k = C_(k-1) + A_mk . B_kn in ascending k, and a
    fused batch computes each lane by itself, so the two front ends' different
    batch compositions change no lane."""
    if where == "accelerator":
        request.getfixturevalue("accel_device")
    a, b, colls = _operands(nt, nb)
    _, got = _solve_dtd(colls)
    want = a @ b
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-3 * abs(want).max())
    assert (got == _solve_ptg(_operands(nt, nb)[2])).all()


def _check_pushouts(dev, host_tasks, tp, nt, nb):
    """(b): every result tile is flagged, pushed out at its last k and
    found early at the flush; nothing else comes back; no host task."""
    assert tp.pushouts_flagged == nt * nt
    assert dev.pushouts == dev.writebacks == dev.writebacks_early == nt * nt
    assert dev.bytes_out == nt * nt * nb * nb * 4
    assert dev.executed_tasks == nt ** 3 and host_tasks == 0


def _host_device(device_registry):
    (host,) = [d for d in device_registry.devices if d.type == "cpu"]
    return host


def test_pushout_on_the_last_k_starts_every_result_tile_home(
        accel_device, device_registry):
    nt, nb = 4, 8
    host = _host_device(device_registry)
    before = host.executed_tasks
    a, b, colls = _operands(nt, nb)
    tp, got = _solve_dtd(colls)
    np.testing.assert_allclose(got, a @ b, rtol=1e-3, atol=1e-3)
    _check_pushouts(accel_device, host.executed_tasks - before, tp, nt, nb)


def _drives_of(ntasks, window, threshold):
    """Drives and tasks run under discovery when tasks complete one at a
    time (host bodies, ``nb_cores=0``): the inserter engages at window + 1
    in flight and comes back at the threshold."""
    step = window + 1 - threshold
    drives = 1 + (ntasks - (window + 1)) // step
    return drives, drives * step


class _WatchedPool(DTDTaskpool):
    """Records the most tasks ever in flight (only insertion raises it)."""
    peak = 0

    def _window_backpressure(self):
        self.peak = max(self.peak, self._inflight)
        super()._window_backpressure()


def _check_window(tp, ntasks, window, threshold, drives, in_window):
    """(c): the inserter drove, the exact number of times, part of the
    execution ran under discovery and in-flight never passed the window
    (the check follows the insertion, so one past it is the most)."""
    assert tp.inserted == ntasks
    assert (tp.window_drives, tp.tasks_in_window) == (drives, in_window)
    assert 0 < tp.tasks_in_window < ntasks
    assert threshold < tp.peak <= window + 1


# (drives, tasks completed when wait() closed the insertion) of the case below
# through the device module
ACCELERATOR_WINDOW_COUNTS = (13, 466)


@pytest.mark.parametrize("where", ["host_bodies", "accelerator"])
def test_a_small_window_makes_the_inserter_drive(where, param, request):
    """512 tasks through a window of 64 / 32.  On the host bodies the counts
    follow from the sizes; through the device module a drive runs whole
    batches (the first takes the 9 chain heads that are ready, the flood
    fills the later ones), and the counts are what that arrival order gives,
    the same every time.  The totals of the process grow by the pool's when
    it terminates."""
    from parsec_tpu.dtd import insert as dtd_insert
    if where == "accelerator":
        request.getfixturevalue("accel_device")
    nt, nb, window, threshold = 8, 16, 64, 32
    param("dtd_window_size", window)
    param("dtd_threshold_size", threshold)
    a, b, colls = _operands(nt, nb)
    before = dict(dtd_insert.dtd_totals)
    tp, got = _solve_dtd(colls, _WatchedPool)
    np.testing.assert_allclose(got, a @ b, rtol=1e-3, atol=1e-3)
    drives, in_window = _drives_of(nt ** 3, window, threshold) \
        if where == "host_bodies" else ACCELERATOR_WINDOW_COUNTS
    _check_window(tp, nt ** 3, window, threshold, drives, in_window)
    grown = {k: v - before[k] for k, v in dtd_insert.dtd_totals.items()}
    assert grown == {"dtd_inserted": nt ** 3, "dtd_window_drives": drives,
                     "dtd_tasks_in_window": in_window,
                     "dtd_pushouts_flagged": nt * nt}


@pytest.mark.parametrize("fault", ["flag_ignored", "window_never_engages"])
def test_planted_faults_in_the_dtd_front_end_are_seen(
        fault, accel_device, device_registry, param, monkeypatch):
    """(d): ``release_task`` ignoring ``PUSHOUT`` leaves the answer right and
    (b)'s counts wrong (0 push-outs, 16 synchronous reads); a window that
    never engages leaves the answer right and (c)'s counts wrong (no drive,
    512 in flight)."""
    from parsec_tpu.dtd import insert as dtd_insert
    host = _host_device(device_registry)
    before = host.executed_tasks
    if fault == "flag_ignored":
        nt, nb = 4, 8
        monkeypatch.setattr(dtd_insert, "start_home", lambda ctx, copy: None)
    else:
        nt, nb = 8, 16
        param("dtd_window_size", 64)
        param("dtd_threshold_size", 32)
        monkeypatch.setattr(DTDTaskpool, "_execute_and_come_back",
                            lambda self: None)
    a, b, colls = _operands(nt, nb)
    tp, got = _solve_dtd(colls, _WatchedPool)
    np.testing.assert_allclose(got, a @ b, rtol=1e-3, atol=1e-3)
    with pytest.raises(AssertionError):
        if fault == "flag_ignored":
            _check_pushouts(accel_device, host.executed_tasks - before, tp,
                            nt, nb)
        else:
            _check_window(tp, nt ** 3, 64, 32, *ACCELERATOR_WINDOW_COUNTS)
    if fault == "flag_ignored":
        assert (accel_device.pushouts, accel_device.writebacks_early,
                accel_device.writebacks) == (0, 0, nt * nt)
    else:
        assert tp.peak == nt ** 3


def test_a_terminated_pool_lets_its_tasks_go_without_the_collector(
        accel_device):
    """The accessor chains are the last references to a pool's tasks and,
    through ``task.taskpool``, one cycle with it: a terminated pool clears
    them, so a solve's tasks die by reference count and the client's
    ``gc.collect()`` between solves does not have them to traverse (it cost
    the DTD cell 33 ms a solve on the chip where the PTG twin paid 3)."""
    import gc
    from parsec_tpu.dtd.insert import DTDTask

    def alive():
        return sum(isinstance(o, DTDTask) for o in gc.get_objects())

    gc.collect()
    before = alive()
    gc.disable()
    try:
        tp, _ = _solve_dtd(_operands(4, 8)[2])
        assert tp.inserted == 4 ** 3 and alive() == before
    finally:
        gc.enable()
    assert all(t.last_writer is None and not t.last_users
               for t in tp._tiles.values())
