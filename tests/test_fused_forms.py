"""The form of the fused batch program (``device/tpu.py:_run_vmapped``): one
jitted program a batch that runs the class's traceable once a lane on the
lane's own tiles as they lie: no stack, no ``vmap``, no slices; but for a
traceable that asks for its lanes stacked (``vmap_lanes``: the QR's
Householder classes), one batched body over stacked flows.  CPU
stand-in; only results, program structure and counts are asserted, never a
duration."""

import numpy as np
import pytest

import jax

from parsec_tpu.data.data import data_create
from parsec_tpu.data_dist.matrix import (SymTwoDimBlockCyclic, TiledMatrix,
                                         TwoDimBlockCyclic)
from parsec_tpu.device import tpu
from parsec_tpu.device.kernels import find_incarnation
from parsec_tpu.device.tpu import TPUDeviceTask
from parsec_tpu.prof import spans
from parsec_tpu.ptg.lowering import find_traceable
from parsec_tpu.runtime import Context
from parsec_tpu.runtime.task import Task

NB = 32
# dyld -> (the model that holds the class, the class, its written flows)
CLASSES = {"gemm": ("gemm", "GEMM", 1), "gemm_nt": ("cholesky", "GEMM", 1),
           "trsm_rlt": ("cholesky", "TRSM", 1),
           "syrk_ln": ("cholesky", "SYRK", 1), "qr_unmqr": ("qr", "UNMQR", 1),
           "qr_tsmqr": ("qr", "TSMQR", 2), "qr_tsqrt": ("qr", "TSQRT", 3)}


def _pool(model: str, nb: int, nt: int = 2):
    """A PTG of ``model`` over nt x nt tiles of nb x nb zeros, device
    bodies only: the classes are the real ones, the data are the test's."""
    n = nt * nb
    if model == "gemm":
        from parsec_tpu.models.tiled_gemm import tiled_gemm_ptg
        return tiled_gemm_ptg(*(TiledMatrix(x, n, n, nb, nb) for x in "ABC"),
                              devices="tpu")
    if model == "cholesky":
        from parsec_tpu.models.cholesky import tiled_cholesky_ptg
        return tiled_cholesky_ptg(
            SymTwoDimBlockCyclic("A", n, n, nb, nb, dtype=np.float32),
            devices="tpu")
    from parsec_tpu.models.qr import tiled_qr_ptg
    return tiled_qr_ptg(TwoDimBlockCyclic("A", n, n, nb, nb),
                        TwoDimBlockCyclic("T", n, n, nb, nb), devices="tpu")


def _tasks(dyld: str, count: int, nb: int) -> list[Task]:
    """``count`` tasks of the class behind ``dyld``, each over host tiles of
    its own: well conditioned (a strong diagonal), so that a triangular
    solve or a QR amplifies no rounding."""
    model, cls, _ = CLASSES[dyld]
    tp = _pool(model, nb)
    (tc,) = [c for c in tp.task_classes if c.name == cls]
    assert any(ch.dyld == dyld for ch in tc.chores)
    rng = np.random.default_rng(37 + count)
    tasks = []
    for i in range(count):
        task = Task(tp, tc, {})
        for f in tc.flows:
            if f.is_ctl:
                continue
            tile = (rng.standard_normal((nb, nb)) / nb
                    + 2 * np.eye(nb)).astype(np.float32)
            task.data[f.flow_index] = data_create(
                tile, key=(dyld, i, f.name)).get_copy(0)
        tasks.append(task)
    return tasks


def _dispatch(dev, dyld: str, tasks: list[Task]) -> None:
    dev.stage_in_many(tasks)
    submit = find_incarnation(dyld, dev)
    assert dev._run_vmapped([TPUDeviceTask(None, t, submit) for t in tasks])


@pytest.mark.parametrize("count", [1, 3, 8])
@pytest.mark.parametrize("dyld", list(CLASSES))
def test_a_fused_batch_gives_the_per_task_body_s_results(accel_device, dyld,
                                                         count):
    """One, two and three written flows a lane; 3 lanes are padded to 4 with
    lane 0, whose results are dropped."""
    dev = accel_device
    tasks = _tasks(dyld, count, NB)
    flows = [f for f in tasks[0].task_class.flows if not f.is_ctl]
    written = [f.flow_index for f in flows if f.access & tpu.ACCESS_WRITE]
    assert len(written) == CLASSES[dyld][2]
    before = [[t.data[f.flow_index].value.copy() for f in flows]
              for t in tasks]
    _dispatch(dev, dyld, tasks)
    dev.sync()
    assert dev.xla_calls == dev.batched_dispatches == 1
    assert dev.executed_tasks == count
    assert dev.tasks_by_class == {CLASSES[dyld][1]: count}
    assert dev.calls_by_class == {CLASSES[dyld][1]: 1}
    alone = jax.jit(find_traceable(dyld).apply)
    for task, tiles in zip(tasks, before):
        want = alone(*tiles)
        want = want if isinstance(want, (tuple, list)) else (want,)
        assert len(want) == len(written)
        for fi, new in zip(written, want):
            c = task.data[fi]
            assert c.device_index == dev.device_index and c.version == 2
            np.testing.assert_allclose(np.asarray(c.value), np.asarray(new),
                                       rtol=1e-4, atol=1e-5)


def _count(jaxpr, primitive: str) -> int:
    """Equations of ``primitive`` in the printed ``jaxpr``, nested ones
    included (the jitted program is one ``pjit`` equation of the outer)."""
    return str(jaxpr).count(f" {primitive}[")


@pytest.mark.parametrize("dyld", list(CLASSES))
def test_the_program_is_the_body_once_a_lane_and_nothing_else(accel_device,
                                                              dyld):
    """Three lanes padded to four: the program holds four times the lone
    body's products and joins (pad lane included), so nothing was stacked
    and nothing batched; a ``vmap_lanes`` class's holds the body's products
    once, batched, and a stack a flow.  It goes by its class's name, since
    the benchmark's readers find device time by it, under one cache key a
    padded size."""
    dev = accel_device
    tasks = _tasks(dyld, 3, NB)
    _dispatch(dev, dyld, tasks)
    dev.sync()
    ((key, fn),) = dev._vmap_cache.items()
    assert key[:2] == (dyld, 4) and fn.__name__ == f"fused_{dyld}"
    flows = [f.flow_index for f in tasks[0].task_class.flows if not f.is_ctl]
    flat = [t.data[f].value for f in flows for t in tasks + tasks[:1]]
    fused = jax.make_jaxpr(fn)(*flat)
    alone = jax.make_jaxpr(find_traceable(dyld).apply)(
        *(tasks[0].data[f].value for f in flows))
    assert _count(alone, "dot_general") > 0
    if getattr(find_traceable(dyld).apply, "vmap_lanes", False):
        assert _count(fused, "dot_general") == _count(alone, "dot_general")
        assert _count(fused, "concatenate") >= \
            len(flows) + _count(alone, "concatenate")
    else:
        for primitive in ("dot_general", "concatenate", "slice"):
            assert _count(fused, primitive) == \
                4 * _count(alone, primitive), primitive
    _dispatch(dev, dyld, _tasks(dyld, 4, NB))
    dev.sync()
    assert len(dev._vmap_cache) == 1 and fn._cache_size() == 1


def test_a_solve_s_batched_calls_are_counted_as_before(accel_device):
    """A whole 2 x 2 x 2 GEMM through the scheduler."""
    from parsec_tpu.models.tiled_gemm import tiled_gemm_ptg
    dev, nb = accel_device, NB
    rng = np.random.default_rng(7)
    a, b = (rng.standard_normal((2 * nb, 2 * nb)).astype(np.float32)
            for _ in range(2))
    C = TiledMatrix("C", 2 * nb, 2 * nb, nb, nb)
    ctx = Context(nb_cores=0)
    ctx.add_taskpool(tiled_gemm_ptg(TiledMatrix.from_dense("A", a, nb, nb),
                                    TiledMatrix.from_dense("B", b, nb, nb),
                                    C, devices="tpu"))
    ctx.wait(timeout=120)
    dev.sync()
    dev.flush_cache()
    ctx.fini()
    np.testing.assert_allclose(C.to_dense(), a @ b, rtol=1e-3, atol=1e-3)
    assert dev.tasks_by_class == {"GEMM": 8} and dev.executed_tasks == 8
    # the two k steps of four independent tiles: one call each
    assert dev.xla_calls == dev.batched_dispatches == 2
    assert dev.calls_by_class == {"GEMM": 2}


@pytest.mark.parametrize("kept", [False, True],
                         ids=["donating", "a_tile_kept_elsewhere"])
def test_the_budget_is_asked_for_the_results_alone(accel_device, param, kept):
    """Eight GEMM lanes under a budget that holds the 24 staged tiles and the
    8 results and not a stack of 24 beside them: the call is asked for what
    it allocates and makes no room (asking for stacked operands too would
    open ``devmod.pressure``).  What it allocates is nothing where every C
    tile is the module's alone, since each result takes the buffer of the
    version it supersedes, and its 8 results where someone kept a C tile's
    array: that call runs the program that donates nothing."""
    dev, nb = accel_device, NB
    tile = nb * nb * 4
    tasks = _tasks("gemm", 8, nb)
    dev.stage_in_many(tasks)
    assert dev._mem_bytes == 24 * tile
    keeper = tasks[5].data[2].value if kept else None
    dev._mem_budget = (24 + 8 + 4) * tile
    asked = []
    make_room = dev._make_room
    dev._make_room = lambda need: asked.append(need) or make_room(need)
    param("prof_spans", True)
    spans.phase_refresh()
    spans.phase_reset()
    try:
        submit = find_incarnation("gemm", dev)
        assert dev._run_vmapped([TPUDeviceTask(None, t, submit)
                                 for t in tasks])
        dev.sync()
        pressed = "devmod.pressure" in spans.phase_totals()
    finally:
        param("prof_spans", False)
        spans.phase_refresh()
        spans.phase_reset()
    held = 8 * tile if kept else 0
    assert dev._held_bytes == 0 and dev.inflight_held_bytes_peak == held
    assert asked == [held] and not pressed
    assert dev.evict_stuck == 0 and dev.pressure_confirms == 0
    assert dev.donated_results == (0 if kept else 8)
    assert keeper is None or not keeper.is_deleted()
