"""Donation in the fused batch program (ISSUE 39, ``device/tpu.py``): a fused
call whose written tiles the device module alone holds donates them, so every
result takes the buffer of the version it supersedes and the call allocates
nothing; pad lanes write to a recycled scratch pool; any other call runs the
program that donates nothing.  CPU stand-in; results, counts and which arrays
were consumed are asserted, never a duration."""

import gc

import numpy as np
import pytest

from parsec_tpu.data.data import COHERENCY_OWNED
from parsec_tpu.data_dist.matrix import (SymTwoDimBlockCyclic, TiledMatrix,
                                         TwoDimBlockCyclic)
from parsec_tpu.device import tpu
from parsec_tpu.device.kernels import find_incarnation
from parsec_tpu.device.tpu import TPUDeviceTask
from parsec_tpu.models.tiled_gemm import tiled_gemm_ptg
from parsec_tpu.runtime import Context
from test_fused_forms import NB, _tasks

KEPT = -1       # ``_OWN_REFS`` under which every tile reads as kept elsewhere


def _gemm(nt: int, seed: int = 39):
    n = nt * NB
    rng = np.random.default_rng(seed)
    a, b, c = (rng.standard_normal((n, n)).astype(np.float32)
               for _ in range(3))
    C = TiledMatrix.from_dense("C", c, NB, NB)
    pool = tiled_gemm_ptg(TiledMatrix.from_dense("A", a, NB, NB),
                          TiledMatrix.from_dense("B", b, NB, NB), C,
                          devices="tpu")
    return pool, lambda: [C.to_dense()]


def _cholesky(nt: int, seed: int = 39):
    from parsec_tpu.models.cholesky import tiled_cholesky_ptg
    n = nt * NB
    rng = np.random.default_rng(seed)
    m = rng.standard_normal((n, n)).astype(np.float32)
    spd = m @ m.T + n * np.eye(n, dtype=np.float32)
    A = SymTwoDimBlockCyclic("A", n, n, NB, NB, dtype=np.float32,
                             init_fn=lambda i, j, shape: spd[
                                 i * NB:(i + 1) * NB,
                                 j * NB:(j + 1) * NB].copy())
    keys = [(i, j) for i in range(nt) for j in range(i + 1)]
    return tiled_cholesky_ptg(A, devices="tpu"), lambda: [
        np.asarray(A.data_of(*k).get_copy(0).value) for k in keys]


def _qr(nt: int, seed: int = 39):
    from parsec_tpu.models.qr import tiled_qr_ptg
    n = nt * NB
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((n, n)).astype(np.float32)
    A = TwoDimBlockCyclic("A", n, n, NB, NB, init_fn=lambda i, j, shape: a[
        i * NB:(i + 1) * NB, j * NB:(j + 1) * NB].copy())
    T = TwoDimBlockCyclic("T", n, n, NB, NB)
    keys = [(i, j) for i in range(nt) for j in range(nt)]
    return tiled_qr_ptg(A, T, devices="tpu"), lambda: [
        np.asarray(M.data_of(*k).get_copy(0).value)
        for M in (A, T) for k in keys if M is A or k[0] >= k[1]]


# the GEMM chain (4 k steps a C tile), the 16-tile Cholesky (4 x 4 tiles:
# 10 lower), the small QR; with how many of the solve's results come out of
# a fused call
PROBLEMS = {"gemm_chain": (lambda: _gemm(4), 64),
            "cholesky_16_tiles": (lambda: _cholesky(4), None),
            "small_qr": (lambda: _qr(4), None)}


def _solve(dev, make, flush=True):
    pool, result = make()
    ctx = Context(nb_cores=0)
    ctx.add_taskpool(pool)
    ctx.wait(timeout=120)
    ring = list(dev._inflight)
    if flush:
        dev.sync()
        dev.flush_cache()
    ctx.fini()
    return result() if flush else None, ring


@pytest.mark.parametrize("problem", list(PROBLEMS))
def test_a_donating_solve_is_the_plain_solve_bit_for_bit(accel_device,
                                                         monkeypatch,
                                                         problem):
    """(a) Every fused call of the solve donates; the same solve with every
    tile read as kept elsewhere runs the programs that donate nothing: the
    results are equal to the bit, the calls are the same calls."""
    dev = accel_device
    make, fused_results = PROBLEMS[problem]
    donated, _ = _solve(dev, make)
    calls, n_donated = dev.xla_calls, dev.donated_results
    assert n_donated > 0 and dev._held_bytes == 0 and not dev._inflight
    if fused_results is not None:
        assert n_donated == fused_results
    programs = set(dev._vmap_cache)
    assert all(len(key) == 3 for key in programs)       # no plain program
    monkeypatch.setattr(tpu, "_OWN_REFS", KEPT)
    plain, _ = _solve(dev, make)
    assert dev.donated_results == n_donated and dev.xla_calls == 2 * calls
    # the same program without donation, one for each that a call needed
    assert set(dev._vmap_cache) == programs | {k + ("plain",)
                                               for k in programs}
    assert all(fn.donates == () for k, fn in dev._vmap_cache.items()
               if k[-1] == "plain")
    assert len(donated) == len(plain)
    for got, want in zip(donated, plain):
        assert np.array_equal(got, want)


@pytest.mark.parametrize("problem", list(PROBLEMS))
def test_a_ring_full_of_donated_results_is_confirmed_and_read(accel_device,
                                                              param, problem):
    """(a) The superseded arrays read ``is_deleted()``, and the ring that
    holds them is probed, confirmed and synchronized without a raise."""
    dev = accel_device
    param("prof_spans", True)       # the probe runs at every dispatch
    try:
        _, ring = _solve(dev, PROBLEMS[problem][0], flush=False)
    finally:
        param("prof_spans", False)
        tpu.spans.uninstall()
    assert ring == list(dev._inflight) and len(ring) >= 4
    consumed = [out for out, _ in ring
                if tpu._first_live(out) is tpu._DONATED]
    assert consumed, "no dispatch's results were donated to a later one"
    for out in consumed:
        with pytest.raises(Exception, match="deleted"):
            next(tpu._arrays(out)).is_ready()
    # the newest entry is never consumed: nothing was enqueued after it
    assert tpu._first_live(ring[-1][0]) is not tpu._DONATED
    depth, held_run = dev._queue_depth()
    assert 0 <= depth <= len(ring) and held_run >= 0
    dev._confirm_oldest()
    assert len(dev._inflight) == len(ring) - 1 and dev.enabled
    dev.sync()
    assert not dev._inflight and dev._held_bytes == 0 and dev.enabled
    assert dev._queue_depth() == (0, 0)
    dev.flush_cache()


def _dispatch(dev, dyld, tasks):
    dev.stage_in_many(tasks)
    submit = find_incarnation(dyld, dev)
    assert dev._run_vmapped([TPUDeviceTask(None, t, submit) for t in tasks])


@pytest.mark.parametrize("count", [5, 17])
@pytest.mark.parametrize("dyld,written", [("gemm", 1), ("qr_tsmqr", 2)])
def test_a_padded_batch_donates_every_lane_on_a_pool_that_does_not_grow(
        accel_device, dyld, written, count):
    """(b) 5 tasks in 8 lanes and 17 in 32: the real lanes are donated, the
    pad lanes write to scratch tiles, at most ``Bp / 2 - 1`` a written flow,
    and fifty more calls allocate none."""
    dev = accel_device
    lanes = 1 << (count - 1).bit_length()
    pads = lanes - count
    assert pads <= lanes // 2 - 1
    tile = NB * NB * 4
    for call in range(1, 52):
        tasks = _tasks(dyld, count, NB)
        before = [[c.value for c in t.data] for t in tasks]  # the host's
        _dispatch(dev, dyld, tasks)
        assert dev.donated_results == call * lanes * written
        assert dev._held_bytes == 0
        ((sig, pool),) = dev._scratch.items()
        assert sig == ((NB, NB), "float32")
        assert len(pool) == pads * written
        assert dev._scratch_bytes == pads * written * tile
        assert not any(p.is_deleted() for p in pool)
        # (d) no donating call wrote through into a host tile
        assert all(c.original.get_copy(0).value is h
                   for t, tiles in zip(tasks, before)
                   for c, h in zip(t.data, tiles))
        dev.sync()
        dev.flush_cache()
    assert dev.debug_state()["scratch_tiles"] == pads * written
    assert set(dev._vmap_cache) == {(dyld, lanes, (sig,) * len(tasks[0].data))}


def test_the_host_s_numpy_tile_is_unchanged_by_a_donating_call(accel_device):
    """(d) On the CPU backend a staged tile may be a zero-copy view of the
    host's numpy tile: the call that is donated it must not write the result
    through."""
    dev = accel_device
    tasks = _tasks("gemm", 4, NB)
    host = [[c.value for c in t.data] for t in tasks]
    kept = [[h.copy() for h in tiles] for tiles in host]
    _dispatch(dev, "gemm", tasks)
    dev.sync()
    assert dev.donated_results == 4
    for t, tiles, copies in zip(tasks, host, kept):
        for c, h, k in zip(t.data, tiles, copies):
            assert c.original.get_copy(0).value is h
            assert np.array_equal(h, k)
        # and the result is the product, on the device
        want = copies[2] + copies[0] @ copies[1]
        assert t.data[2].coherency == COHERENCY_OWNED
        np.testing.assert_allclose(np.asarray(t.data[2].value), want,
                                   rtol=1e-4, atol=1e-5)


def _plain_programs(dev):
    return [k for k in dev._vmap_cache if k[-1] == "plain"]


def test_the_same_array_twice_in_a_call_donates_nothing(accel_device):
    """(c) Two lanes that write one tile (PJRT refuses a buffer donated
    twice), and a lane that reads what another writes: the program that
    donates nothing, results as before."""
    dev = accel_device
    tasks = _tasks("gemm", 4, NB)
    tasks[3].data[2] = tasks[1].data[2]        # C of lane 3 is lane 1's
    tasks[2].data[0] = tasks[0].data[2]        # A of lane 2 is lane 0's C
    a, b, c = ([t.data[i].value.copy() for t in tasks] for i in range(3))
    _dispatch(dev, "gemm", tasks)
    dev.sync()
    assert dev.donated_results == 0 and len(_plain_programs(dev)) == 1
    assert dev.inflight_held_bytes_peak == 4 * NB * NB * 4
    # the shared copy holds one of its two writers' results
    for i in (0, 2):
        np.testing.assert_allclose(np.asarray(tasks[i].data[2].value),
                                   c[i] + a[i] @ b[i], rtol=1e-4, atol=1e-5)
    # with each lane on tiles of its own again, the next call donates
    _dispatch(dev, "gemm", _tasks("gemm", 4, NB))
    assert dev.donated_results == 4


def test_a_host_copy_that_points_at_the_device_array_keeps_it(accel_device):
    """(c) After a memory edge the datum's host copy may hold the device
    array itself (``scheduling.apply_writeback_to_home``): a later writer of
    that tile must not consume it under the host copy."""
    dev = accel_device
    tasks = _tasks("gemm", 4, NB)
    _dispatch(dev, "gemm", tasks)               # donates: the tiles are new
    assert dev.donated_results == 4
    c1 = tasks[1].data[2]
    home = c1.original.get_copy(0)
    home.value = c1.value                       # the memory edge
    first = np.asarray(c1.value).copy()
    for t in tasks:                             # the RW successors
        t.data[2] = t.data[2].original.get_copy(dev.device_index)
    dev._run_vmapped([TPUDeviceTask(None, t, None) for t in tasks])
    dev.sync()
    assert dev.donated_results == 4 and len(_plain_programs(dev)) == 1
    assert not home.value.is_deleted()
    assert np.array_equal(np.asarray(home.value), first)
    a, b = (tasks[1].data[i].original.get_copy(0).value for i in (0, 1))
    np.testing.assert_allclose(np.asarray(c1.value), first + a @ b,
                               rtol=1e-4, atol=1e-5)


def test_a_pushed_out_tile_with_an_rw_successor_is_not_consumed(
        accel_device):
    """(c) A push-out holds the array weakly while its transfer flies; the
    call that writes the tile again leaves it whole, and the write-back
    reads the successor's result."""
    dev = accel_device
    tasks = _tasks("gemm", 4, NB)
    _dispatch(dev, "gemm", tasks)
    c2 = tasks[2].data[2]
    dev.pushout(c2)
    assert dev.pushouts == 1 and tpu._pushed_out(c2)
    pushed, first = c2.pushed, np.asarray(c2.value).copy()
    dev._run_vmapped([TPUDeviceTask(None, t, None) for t in tasks])
    assert dev.donated_results == 4 and len(_plain_programs(dev)) == 1
    gc.collect()
    # nobody else kept the pushed array: if it is still there it is whole
    old = pushed()
    assert old is None or np.array_equal(np.asarray(old), first)
    assert not tpu._pushed_out(c2)              # a newer array is current
    dev.sync()
    dev.flush_cache()
    a, b = (tasks[2].data[i].original.get_copy(0).value for i in (0, 1))
    home = c2.original.get_copy(0)
    assert home.version == c2.version and isinstance(home.value, np.ndarray)
    np.testing.assert_allclose(home.value, first + a @ b, rtol=1e-4,
                               atol=1e-5)
    assert dev.writebacks_early == 0


def test_a_caller_s_reference_keeps_the_array_valid(accel_device):
    """(c) Who keeps the array and not the copy keeps it valid: the call
    sees the reference and donates nothing."""
    dev = accel_device
    tasks = _tasks("gemm", 2, NB)
    _dispatch(dev, "gemm", tasks)
    mine = tasks[0].data[2].value
    dev._run_vmapped([TPUDeviceTask(None, t, None) for t in tasks])
    assert dev.donated_results == 2 and not mine.is_deleted()
    del mine
    dev._run_vmapped([TPUDeviceTask(None, t, None) for t in tasks])
    assert dev.donated_results == 4
    dev.sync()


def _failing_gemm(dev, param, inside: bool):
    """A 2 x 2 x 4 GEMM chain in batches of 4 whose third call fails:
    before the call (the injected hook) or inside it, after the program was
    donated its C tiles."""
    from test_device_pressure import _mk_abc
    a, b, c, A, B, C = _mk_abc(2 * NB, NB, 24, k=4 * NB)
    param("device_tpu_batch_max", 4)
    ran = {"cpu": 0, "calls": 0}
    pool = tiled_gemm_ptg(A, B, C, devices="auto")
    (gemm,) = pool.task_classes
    for chore in gemm.chores:
        if chore.device_type == "cpu":
            hook = chore.hook

            def counted(es, task, _hook=hook):
                ran["cpu"] += 1
                return _hook(es, task)
            chore.hook = counted

    def before_the_call(batch):
        ran["calls"] += 1
        if ran["calls"] > 2 and not inside:
            raise ConnectionResetError("device reset before the call")
        if ran["calls"] == 2 and inside:    # the next call's program
            ((key, fn),) = dev._vmap_cache.items()

            def consumed_then_failed(*flat):
                fn(*flat)
                raise ConnectionResetError("device reset inside the call")
            dev._vmap_cache[key] = consumed_then_failed
            consumed_then_failed.donates = fn.donates
    dev._dispatch_hook = before_the_call
    return pool, ran, (a, b, c, C)


def test_a_failure_before_a_donating_call_demotes_as_before(accel_device,
                                                            param):
    """(e) The hook raises before the call: nothing was consumed, the dirty
    C tiles are salvaged, the device is disabled and the rest of the chain
    runs on the CPU incarnation from the salvaged values."""
    dev = accel_device
    pool, ran, (a, b, c, C) = _failing_gemm(dev, param, inside=False)
    ctx = Context(nb_cores=0)
    ctx.add_taskpool(pool)
    ctx.wait(timeout=120)
    dev.sync()
    ctx.fini()
    assert dev.enabled is False and dev.executed_tasks == 8
    assert dev.donated_results == 8 and ran["cpu"] == 8
    assert not dev._scratch and dev._scratch_bytes == 0
    np.testing.assert_allclose(C.to_dense(), c + a @ b, rtol=1e-3, atol=1e-4)


def test_a_failure_inside_a_donating_call_fails_stop(accel_device, param):
    """(e) The call fails after its program was donated the C tiles: they
    are newer than their host copies and gone, so the run stops; no task is
    recomputed from a deleted or a stale tile."""
    dev = accel_device
    pool, ran, _ = _failing_gemm(dev, param, inside=True)
    ctx = Context(nb_cores=0)
    ctx.add_taskpool(pool)
    with pytest.raises(RuntimeError, match="could not be salvaged"):
        ctx.wait(timeout=120)
    assert dev.enabled is False and ran["calls"] == 3
    assert dev.executed_tasks == 8 and ran["cpu"] == 0
    ctx.fini()
