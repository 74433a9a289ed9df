"""LU with partial pivoting (``models/lu.py:tiled_getrf_ptg``): the PTG on its
CPU bodies and on its jax traceables through the device module, against
``scipy.linalg.lu_factor`` and the benchmark's plain reference
(``benchmarks/reference_lu.py``); the null flows of a tile column's family
skipped and counted; a class whose input deps outnumber the dep mask's 64
bits released by count on the native table and on the Python one; the int32
pivot tile written back at its newest version; the source's task counts."""

import os

import numpy as np
import pytest
import scipy.linalg as sl

from parsec_tpu.data_dist.matrix import TwoDimBlockCyclic
from parsec_tpu.models import lu
from parsec_tpu import ptg
from parsec_tpu.runtime import Context
from parsec_tpu.runtime.task import MASK_BITS

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
NB = 32


@pytest.fixture
def refl(monkeypatch):
    """``benchmarks/reference_lu.py``: numpy, nothing of the program."""
    monkeypatch.syspath_prepend(BENCH)
    import reference_lu
    return reference_lu


def _factor(a: np.ndarray, devices: str, dev=None):
    """(A's factored tiles, ipiv, IPIV's host tiles, the pool) of one
    solve."""
    nt = a.shape[0] // NB
    A = TwoDimBlockCyclic.from_dense("A", a, NB, NB)
    IPIV = lu.ipiv_matrix(a.shape[0], NB)
    tp = lu.tiled_getrf_ptg(A, IPIV, devices=devices)
    ctx = Context(nb_cores=0)
    ctx.add_taskpool(tp)
    ctx.wait(timeout=120)
    if dev is not None:
        dev.sync()
        dev.flush_cache()
    ctx.fini()

    def host(dc, m, k, dtype):
        value = dc.data_of(m, k).get_copy(0).value
        assert isinstance(value, np.ndarray) and value.dtype == dtype
        return value

    tiles = {(m, k): host(A, m, k, np.float32)
             for m in range(nt) for k in range(nt)}
    piv = [host(IPIV, 0, k, np.int32) for k in range(nt)]
    return tiles, np.concatenate([p[0] for p in piv]), IPIV, tp


@pytest.mark.parametrize("nt", [1, 2, 6])
@pytest.mark.parametrize("devices", ["cpu", "tpu"])
def test_ptg_against_scipy_and_the_plain_reference(request, refl, devices,
                                                   nt):
    dev = request.getfixturevalue("accel_device") if devices == "tpu" \
        else None
    n = nt * NB
    tiles_in = refl.lu_tiles(4200 + nt, n, NB)
    a = refl.dense_of(tiles_in, NB)
    tiles, ipiv, _, _ = _factor(a.copy(), devices, dev)
    # seeded normals: no two candidates of a pivot search tie, so the
    # pivots are LAPACK's to the row
    _, want = sl.lu_factor(a.astype(np.float64))
    assert np.array_equal(ipiv, want)
    X = np.random.default_rng(nt).standard_normal((n, 4))
    got, max_l, valid = refl.lu_got(tiles, ipiv, X, NB)
    assert valid and max_l <= 1.0 + 2.0 ** -20
    ax = refl.apply(tiles_in, X, NB)
    assert np.linalg.norm(got - ax) / np.linalg.norm(ax) < 2e-5
    f = refl.dense_of(tiles, NB).astype(np.float64)
    lu_sp, _ = sl.lu_factor(a.astype(np.float64))
    assert np.abs(f - lu_sp).max() < 1e-3 * np.abs(lu_sp).max()
    if dev is not None:
        expect = {"PANEL": nt, "SWPTRSM": nt * (nt - 1) // 2,
                  "SWPLEFT": nt * (nt - 1) // 2,
                  "GEMM": (nt - 1) * nt * (2 * nt - 1) // 6}
        assert {c: dev.tasks_by_class.get(c, 0) for c in expect} == expect


def test_control_at_the_highest_precision_is_a_sound_run(refl):
    """The control's column algorithm (one program for every step) reads
    the program's answer where the precision is the same: a CPU computes
    every precision alike."""
    n = 4 * NB
    tiles_in = refl.lu_tiles(7, n, NB)
    got, ipiv = refl.lu_control(tiles_in, NB, precision="highest")
    _, want = sl.lu_factor(refl.dense_of(tiles_in, NB).astype(np.float64))
    assert np.array_equal(ipiv, want)
    X = np.random.default_rng(1).standard_normal((n, 4))
    z, max_l, valid = refl.lu_got(got, ipiv, X, NB)
    ax = refl.apply(tiles_in, X, NB)
    assert valid and max_l <= 1.0
    assert np.linalg.norm(z - ax) / np.linalg.norm(ax) < 2e-5


def test_the_reference_refuses_what_is_not_partial_pivoting(refl):
    """Pivoting inside the diagonal tile alone (incremental pivoting's
    GETRF) leaves multipliers far past 1 on normals; a pivot that points
    above its own row is no pivot sequence."""
    n = 4 * NB
    tiles_in = refl.lu_tiles(9, n, NB)
    a = refl.dense_of(tiles_in, NB).astype(np.float64)
    # no pivoting at all: the nopiv factors of a dense normal matrix
    f = a.copy()
    for j in range(n - 1):
        f[j + 1:, j] /= f[j, j]
        f[j + 1:, j + 1:] -= np.outer(f[j + 1:, j], f[j, j + 1:])
    tiles = {(m, k): f[m * NB:(m + 1) * NB, k * NB:(k + 1) * NB]
             for m in range(4) for k in range(4)}
    X = np.random.default_rng(2).standard_normal((n, 4))
    _, max_l, valid = refl.lu_got(tiles, np.arange(n), X, NB)
    assert valid and max_l > 2.0
    _, _, valid = refl.lu_got(tiles, np.arange(n)[::-1].copy(), X, NB)
    assert not valid


def test_null_flows_are_skipped_and_counted(accel_device):
    """An instance at step k leaves its k row flows above the panel null:
    stage-in stages none of them and counts each, and no kernel sees one."""
    nt = 5
    n = nt * NB
    a = np.random.default_rng(5).standard_normal((n, n)).astype(np.float32)
    before = accel_device.null_flows_skipped
    _factor(a, "tpu", accel_device)
    # PANEL(k), SWPTRSM(k, n) and SWPLEFT(k, n) each leave k rows null
    want = sum(k * (1 + (nt - 1 - k) + k) for k in range(nt))
    assert accel_device.null_flows_skipped - before == want


@pytest.mark.parametrize("bucket", [1, 2, 8])
def test_zero_tiles_pad_the_column_and_come_back_zero(accel_device,
                                                       monkeypatch, bucket):
    """The row flows padded with zero tiles to a multiple of the bucket:
    the same answer at every bucket, and the pool of zeros still zeros."""
    monkeypatch.setattr(lu, "BUCKET", bucket)
    nt = 3
    n = nt * NB
    a = np.random.default_rng(6).standard_normal((n, n)).astype(np.float32)
    tiles, ipiv, _, _ = _factor(a, "tpu", accel_device)
    _, want = sl.lu_factor(a.astype(np.float64))
    assert np.array_equal(ipiv, want)
    zeros = [np.asarray(z) for pool in accel_device._zeros.values()
             for z in pool]
    assert bool(zeros) == (bucket > 1)
    assert all(not z.any() for z in zeros)


def test_the_pivot_tile_comes_home_at_its_newest_version(accel_device):
    """IPIV's int32 tiles go through stage-in, the fused call and the
    write-back like any f32 tile: the host holds the newest version of
    each, as a numpy int32 array, and no device copy is newer."""
    nt = 3
    a = np.random.default_rng(8).standard_normal(
        (nt * NB, nt * NB)).astype(np.float32)
    _, ipiv, IPIV, _ = _factor(a, "tpu", accel_device)
    for k in range(nt):
        d = IPIV.data_of(0, k)
        host = d.get_copy(0)
        assert d.newest_copy().version <= host.version
        assert host.value.dtype == np.int32 and host.value.shape == (4, NB)
        assert host.version > 0
        # row 0: global rows of this panel's pivots, in [k nb + i, N)
        assert np.all(host.value[0] >= k * NB + np.arange(NB))


def test_the_source_s_task_counts():
    """44 x 44 tiles: 44 panels, 946 swap-and-TRSM, 27,434 GEMM, 946 left
    swaps: 29,370 tasks; the wide classes are tracked by count."""
    nt = 44
    A = TwoDimBlockCyclic("A", nt * NB, nt * NB, NB, NB)
    tp = lu.tiled_getrf_ptg(A, lu.ipiv_matrix(nt * NB, NB), devices="cpu")
    counts = {tc.name: sum(1 for _ in tp._tc_builders[tc.name]
                           ._enumerate_space()) for tc in tp.task_classes}
    assert counts == {"PANEL": 44, "SWPTRSM": 946, "GEMM": 27434,
                      "SWPLEFT": 946}
    modes = {tc.name: tc.counted for tc in tp.task_classes}
    assert modes == {"PANEL": True, "SWPTRSM": True, "GEMM": False,
                     "SWPLEFT": True}


def _wide_join(width: int, order: list):
    """SRC(i), i < width, each writes a tile; JOIN has a flow a SRC, each
    with two input deps (the tile at first, the SRC after it): 2 x width
    input deps in all."""
    tiles = TwoDimBlockCyclic("X", width, 1, 1, 1)
    p = ptg.PTGBuilder("wide", X=tiles, W=width)
    src = p.task("SRC", i=ptg.span(0, lambda g, l: g.W - 1))
    fs = src.flow("V", ptg.RW)
    fs.input(data=("X", lambda g, l: (l.i, 0)))
    fs.output(succ=("JOIN", lambda g, l: f"V{l.i}", lambda g, l: {"j": 0}))

    @src.body
    def _(es, task, g, l):
        c = task.flow_data("V")
        c.value = np.asarray(c.value) + 1.0 + l.i
        c.version += 1
        order.append(("SRC", l.i))

    join = p.task("JOIN", j=ptg.span(0, 0))
    for i in range(width):
        f = join.flow(f"V{i}", ptg.RW)
        f.input(data=("X", lambda g, l, i=i: (i, 0)),
                guard=lambda g, l: False)
        f.input(pred=("SRC", "V", lambda g, l, i=i: {"i": i}))
        f.output(data=("X", lambda g, l, i=i: (i, 0)))

    @join.body
    def _(es, task, g, l):
        seen = [float(np.asarray(task.flow_data(f"V{i}").value).ravel()[0])
                for i in range(width)]
        order.append(("JOIN", seen))

    return p.build(), tiles


@pytest.mark.parametrize("native", [True, False])
@pytest.mark.parametrize("width", [31, 33, 40])
def test_a_class_past_the_mask_is_released_by_count(param, native, width):
    """2 x width input deps: 62 fit the 64-bit mask, 66 and 80 do not and
    go by count, on the native table and on the Python one: each task runs
    once, the join after every one of its inputs, with every input's
    value."""
    param("runtime_native", native)
    order: list = []
    tp, tiles = _wide_join(width, order)
    join = next(tc for tc in tp.task_classes if tc.name == "JOIN")
    assert join.counted == (2 * width > MASK_BITS)
    ctx = Context(nb_cores=2)
    ctx.add_taskpool(tp)
    ctx.wait(timeout=60)
    ctx.fini()
    srcs = [e for e in order if e[0] == "SRC"]
    assert sorted(i for _, i in srcs) == list(range(width))
    assert order[-1] == ("JOIN", [1.0 + i for i in range(width)])
    assert len(order) == width + 1


def test_today_s_classes_keep_their_mode_and_skip_no_flow(accel_device):
    """No class of the PTGs before this one changes mode, and a solve of
    the nopiv LU leaves no flow null."""
    from parsec_tpu.data_dist.matrix import TiledMatrix
    from parsec_tpu.models import cholesky, qr, tiled_gemm  # noqa: F401
    nt = 3
    a = lu.make_dd(nt * NB)
    A = TiledMatrix.from_dense("A", a, NB, NB)
    tp = lu.tiled_lu_ptg(A, devices="tpu")
    assert not any(tc.counted or tc.pad_rows for tc in tp.task_classes)
    before = accel_device.null_flows_skipped
    ctx = Context(nb_cores=0)
    ctx.add_taskpool(tp)
    ctx.wait(timeout=60)
    accel_device.sync()
    accel_device.flush_cache()
    ctx.fini()
    assert accel_device.null_flows_skipped == before
    L, U = lu.unpack_lu(A.to_dense())
    assert np.abs(L @ U - a).max() < 1e-3 * np.abs(a).max()
