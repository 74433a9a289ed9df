"""Tiled QR (``models/qr.py``): the PTG on its CPU bodies and on its jax
traceables through the device module, against ``np.linalg.qr`` and the
benchmark's plain reference; the closed form of ``larft``; TSQRT's carried
V_kk; tasks that write two and three tiles through the fused batch program
and the per-task body; the source's task counts."""

import os

import numpy as np
import pytest

from parsec_tpu.data.data import COHERENCY_OWNED, data_create
from parsec_tpu.data_dist.matrix import TwoDimBlockCyclic
from parsec_tpu.device.kernels import find_incarnation
from parsec_tpu.device.tpu import TPUDeviceTask
from parsec_tpu.models import qr
from parsec_tpu.runtime import Context
from parsec_tpu.runtime.task import Task

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
NB = 32


@pytest.fixture
def refq(monkeypatch):
    """``benchmarks/reference_qr.py``: numpy, nothing of the program."""
    monkeypatch.syspath_prepend(BENCH)
    import reference_qr
    return reference_qr


def _factor(a: np.ndarray, devices: str, dev=None) -> tuple[dict, dict]:
    nt = a.shape[0] // NB
    A = TwoDimBlockCyclic.from_dense("A", a, NB, NB)
    T = TwoDimBlockCyclic("T", a.shape[0], a.shape[0], NB, NB)
    ctx = Context(nb_cores=0)
    ctx.add_taskpool(qr.tiled_qr_ptg(A, T, devices=devices))
    ctx.wait(timeout=120)
    if dev is not None:
        dev.sync()
        dev.flush_cache()
    ctx.fini()

    def host(dc, m, k):
        value = dc.data_of(m, k).get_copy(0).value
        assert isinstance(value, np.ndarray) and value.dtype == np.float32
        return value

    return ({(m, k): host(A, m, k) for m in range(nt) for k in range(nt)},
            {(m, k): host(T, m, k) for m in range(nt) for k in range(m + 1)})


@pytest.mark.parametrize("nt", [1, 2, 4, 6])
@pytest.mark.parametrize("devices", ["cpu", "tpu"])
def test_ptg_against_numpy_and_the_plain_reference(request, refq, devices,
                                                   nt):
    dev = request.getfixturevalue("accel_device") if devices == "tpu" \
        else None
    n = nt * NB
    a = np.random.default_rng([36, nt]).standard_normal(
        (n, n)).astype(np.float32)
    tiles_a, tiles_t = _factor(a, devices, dev)
    if dev is not None:
        assert dev.executed_tasks == nt + nt * (nt - 1) \
            + (nt - 1) * nt * (2 * nt - 1) // 6
        assert sum(dev.tasks_by_class.values()) == dev.executed_tasks
        assert sum(dev.calls_by_class.values()) == dev.xla_calls
    a64 = a.astype(np.float64)
    # (a) np.linalg.qr of the dense matrix: |R| row by row, R^T.R = A^T.A
    r = np.triu(refq.dense_of(tiles_a, NB)).astype(np.float64)
    want = np.linalg.qr(a64, mode="r")
    signs = np.sign(np.diag(r) * np.diag(want))
    assert np.abs(r * signs[:, None] - want).max() \
        < 2e-5 * np.abs(want).max()
    assert np.linalg.norm(r.T @ r - a64.T @ a64) \
        < 2e-6 * np.linalg.norm(a64.T @ a64)
    # (b) the benchmark's comparison: Q.(R.X) from the V and T tiles
    X = np.random.default_rng(99).standard_normal((n, 4))
    qrx, rtrx = refq.qr_got(tiles_a, tiles_t, X, NB)
    assert np.linalg.norm(qrx - a64 @ X) < 5e-6 * np.linalg.norm(a64 @ X)
    np.testing.assert_allclose(rtrx, r.T @ (r @ X), rtol=1e-12, atol=1e-9)
    # T's tiles are the block reflectors' upper-triangular factors
    for t in tiles_t.values():
        assert np.abs(np.tril(t, -1)).max() < 1e-6


@pytest.mark.parametrize("dead", [(), (0, 5)])
def test_larft_closed_form_equals_lapack_s_recurrence(dead):
    rng = np.random.default_rng(7)
    v = np.tril(rng.standard_normal((2 * NB, NB)), -1)
    v[:NB] += np.eye(NB)
    tau = rng.uniform(1.0, 2.0, NB)
    tau[list(dead)] = 0.0                 # H_j = I
    want = qr.larft_np(v, tau)
    got = np.asarray(qr._highest(qr.larft)(v.astype(np.float32),
                                           tau.astype(np.float32)))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    assert not got[list(dead)].any() and not got[:, list(dead)].any()


def _dot_precisions(jaxpr) -> list:
    """The precision of every ``dot_general`` of a jaxpr, nested ones too."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            found.append(eqn.params["precision"])
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _dot_precisions(sub)
    return found


@pytest.mark.parametrize("name,tiles,products", [
    ("qr_geqrt", 2, 1), ("qr_unmqr", 3, 3), ("qr_tsqrt", 3, 1),
    ("qr_tsmqr", 4, 3)])
def test_every_product_of_a_traceable_is_traced_at_the_highest_precision(
        name, tiles, products):
    import jax
    highest = (jax.lax.Precision.HIGHEST,) * 2
    one = [np.ones((NB, NB), np.float32)] * tiles
    row = [np.ones((3, NB, NB), np.float32)] * tiles
    tr = qr._TRACEABLES[name]
    for jaxpr in (jax.make_jaxpr(tr)(*one), jax.make_jaxpr(jax.vmap(tr))(*row)):
        assert _dot_precisions(jaxpr.jaxpr) == [highest] * products


def _tsqrt_tasks(count: int) -> list[Task]:
    """TSQRT tasks of the real class, each over host tiles of its own."""
    n = 2 * NB
    zeros = np.zeros((n, n), np.float32)
    tp = qr.tiled_qr_ptg(TwoDimBlockCyclic.from_dense("A", zeros, NB, NB),
                         TwoDimBlockCyclic("T", n, n, NB, NB), devices="tpu")
    (tc,) = [c for c in tp.task_classes if c.name == "TSQRT"]
    rng = np.random.default_rng(count)
    tasks = []
    for i in range(count):
        task = Task(tp, tc, {"k": 0, "m": 1})
        for f in tc.flows:
            tile = rng.standard_normal((NB, NB)).astype(np.float32)
            task.data[f.flow_index] = data_create(
                tile, key=("x", i, f.name)).get_copy(0)
        tasks.append(task)
    return tasks


def test_tsqrt_leaves_the_strictly_lower_part_of_its_r_tile_bit_for_bit():
    (task,) = _tsqrt_tasks(1)
    r0 = task.flow_data("R").value.copy()
    low = np.tril_indices(NB, -1)
    r1, b1, t1 = (np.asarray(x) for x in qr._tsqrt_traceable(
        *(c.value for c in task.data)))
    assert (r1[low] == r0[low]).all() and not (r1 == r0).all()
    qr._tsqrt_cpu(None, task, None, None)
    assert (task.flow_data("R").value[low] == r0[low]).all()
    # and the two incarnations agree on what they do write
    for got, name in ((r1, "R"), (b1, "B"), (t1, "T")):
        np.testing.assert_allclose(got, task.flow_data(name).value,
                                   rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("count", [31, 1])
def test_every_written_flow_gets_a_new_version_and_a_dirty_device_copy(
        accel_device, count):
    """A batch through the fused program (31 lanes padded to 32, three
    outputs a lane) and a batch of one through the per-task body."""
    dev = accel_device
    tasks = _tsqrt_tasks(count)
    before = [[c.value.copy() for c in t.data] for t in tasks]
    dev.stage_in_many(tasks)
    submit = find_incarnation("qr_tsqrt", dev)
    if count > 1:
        assert dev._run_vmapped([TPUDeviceTask(None, t, submit)
                                 for t in tasks])
        assert dev.calls_by_class == {"TSQRT": 1}
        # three written tiles a lane, 32 lanes: each result took the buffer
        # of the version it supersedes, the pad lane's three a scratch
        # tile's, so the ring holds nothing for the call but the stacked
        # QR's temporaries until it has run
        (fn,) = dev._vmap_cache.values()
        assert dev._held_bytes == fn.temps > 0
        assert dev.donated_results == 32 * 3
        assert dev._scratch_bytes == 3 * NB * NB * 4
    else:
        held = sum(c.value.nbytes for c in dev._written_copies(tasks[0]))
        assert held == 3 * NB * NB * 4
        dev._note_inflight(submit(None, tasks[0], dev), held)
        dev._mark_written(tasks[0])
    dev.sync()
    for task, tiles in zip(tasks, before):
        want = [np.asarray(x) for x in qr._tsqrt_traceable(*tiles)]
        for c, old, new in zip(task.data, tiles, want):
            assert c.device_index == dev.device_index and c.version == 2
            assert c.coherency == COHERENCY_OWNED
            assert c.original.owner_device == dev.device_index
            np.testing.assert_allclose(np.asarray(c.value), new, rtol=1e-4,
                                       atol=1e-5)
            assert not (np.asarray(c.value) == old).all()
    dev.flush_cache()
    for task in tasks:
        for c in task.data:
            host = c.original.get_copy(0)
            assert host.version == 2 and isinstance(host.value, np.ndarray)


def test_task_counts_at_32_tiles_from_the_ptg_s_own_enumeration():
    n = 32 * NB
    A = TwoDimBlockCyclic("A", n, n, NB, NB)
    tp = qr.tiled_qr_ptg(A, TwoDimBlockCyclic("T", n, n, NB, NB))
    counts = {tc.name: sum(1 for _ in tp._tc_builders[tc.name]
                           ._enumerate_space()) for tc in tp.task_classes}
    assert counts == {"GEQRT": 32, "UNMQR": 496, "TSQRT": 496,
                      "TSMQR": 10416}
    assert sum(counts.values()) == 11440 and not A._store
    tp.validate()


def test_lu_s_device_bodies_write_every_written_flow_through_the_helper():
    """``device/kernels.py:traceable_body``: one value or a tuple, each
    written flow its own; a kernel that returns too few is refused."""
    from types import SimpleNamespace as NS

    from parsec_tpu.data.data import ACCESS_READ, ACCESS_RW
    from parsec_tpu.device.kernels import traceable_body
    flows = [NS(is_ctl=False, access=a, flow_index=i)
             for i, a in enumerate((ACCESS_READ, ACCESS_RW, ACCESS_RW))]
    task = NS(task_class=NS(flows=flows, name="X"),
              data=[NS(value=float(i), version=1) for i in range(3)])
    assert traceable_body(lambda a, b, c: (a + b, a + c))(None, task,
                                                          None) == (1.0, 2.0)
    assert [(c.value, c.version) for c in task.data] == \
        [(0.0, 1), (1.0, 2), (2.0, 2)]
    with pytest.raises(ValueError, match="2 written flows"):
        traceable_body(lambda a, b, c: a)(None, task, None)
