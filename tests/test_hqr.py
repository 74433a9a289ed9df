"""Hierarchical tile QR (``models/qrtree.py``, ``models/qr.py:
tiled_hqr_ptg``): the tree's answers for panel steps over rectangular grids,
the TT kernels on their CPU bodies and traceables against float64 numpy,
the PTG on its CPU bodies and through the device module against numpy and
the benchmark's plain reference, the flat tree as a special case, and the
tree's span and counter."""

import math
import os

import numpy as np
import pytest

from parsec_tpu.data.data import data_create
from parsec_tpu.data_dist.matrix import TwoDimBlockCyclic
from parsec_tpu.models import qr
from parsec_tpu.models.qrtree import TS, TT, QRTree
from parsec_tpu.prof import spans
from parsec_tpu.runtime import Context
from parsec_tpu.runtime.task import Task

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
NB = 32

TREES = [(12, 3, 2, "binary"), (12, 3, 3, "binary"), (12, 3, 5, "binary"),
         (12, 3, 1, "binary"), (12, 3, 12, "binary"), (12, 3, 20, "flat"),
         (12, 3, 3, "flat"), (7, 7, 2, "binary"), (9, 1, 4, "binary"),
         (128, 8, 4, "binary")]


@pytest.fixture
def refh(monkeypatch):
    """``benchmarks/reference_hqr.py``: numpy and plain jax, nothing of the
    program, its tree included."""
    monkeypatch.syspath_prepend(BENCH)
    import reference_hqr
    return reference_hqr


# ---------------------------------------------------------------------------
# the tree
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mt,nt,a,low", TREES)
def test_every_row_below_k_is_killed_once_by_a_live_killer(mt, nt, a, low):
    tree = QRTree(mt, nt, a, low)
    for k in range(nt):
        heads = tree.heads(k)
        assert heads == tuple(range(k, mt, a))
        killed = [m for p in heads for m in tree.kills(k, p)]
        # every row below k exactly once, row k and the rows above never
        assert sorted(killed) == list(range(k + 1, mt))
        assert set(tree.ts_rows(k)) | set(tree.tt_rows(k)) == set(killed)
        assert set(tree.tt_rows(k)) == set(heads) - {k}
        for p in range(mt):
            seq = tree.kills(k, p)
            assert not seq or tree.is_head(k, p)
            # a killer's TS kills come first, its own domain in row order
            kinds = [tree.kind(k, m) for m in seq]
            assert kinds == sorted(kinds)
            assert [m for m in seq if tree.kind(k, m) == TS] == \
                (list(range(p + 1, min(p + a, mt))) if tree.is_head(k, p)
                 else [])
            # a TT kill is of a head by a head of a lower rank
            assert all(tree.is_head(k, m) and m > p for m in seq
                       if tree.kind(k, m) == TT)
            for i, m in enumerate(seq):
                assert tree.killer(k, m) == p
                assert tree.prev_kill(k, m) == (seq[i - 1] if i else None)
                assert tree.next_kill(k, m) == \
                    (seq[i + 1] if i + 1 < len(seq) else None)
            assert tree.first_kill(k, p) == (seq[0] if seq else None)
            assert tree.last_kill(k, p) == (seq[-1] if seq else None)


@pytest.mark.parametrize("mt,nt,a,low", TREES)
def test_a_killed_head_is_done_before_its_killer_reaches_it(mt, nt, a, low):
    """Order the kills as the PTG does (the killer's sequence, one after the
    other) and check that every kill of head m by p comes after all of m's
    own kills: a topological order of the panel exists."""
    tree = QRTree(mt, nt, a, low)
    for k in range(nt):
        done = set()

        def finish(p):
            for m in tree.kills(k, p):
                if tree.kind(k, m) == TT:
                    finish(m)
                    assert set(tree.kills(k, m)) <= done
                done.add(m)
        finish(k)
        assert done == set(range(k + 1, mt))


@pytest.mark.parametrize("mt,nt,a,low", TREES)
def test_panel_levels_is_the_domain_chain_and_the_tree_s_depth(mt, nt, a,
                                                                low):
    tree = QRTree(mt, nt, a, low)
    want = 0
    for k in range(nt):
        heads = len(tree.heads(k))
        tt = math.ceil(math.log2(heads)) if low == "binary" else heads - 1
        want += 1 + (min(a, mt - k) - 1) + tt
    assert tree.panel_levels == want
    if a >= mt:                 # the flat tree: a chain of MT - k a step
        assert want == sum(mt - k for k in range(nt))
    if (mt, nt, a) == (128, 8, 4):
        assert want == 8 * (1 + 3 + 5)


def test_the_tree_refuses_a_wide_grid_and_an_unknown_low_level_tree():
    with pytest.raises(ValueError, match="tall or square"):
        QRTree(3, 4, 2)
    with pytest.raises(ValueError, match="a >= 1"):
        QRTree(4, 2, 0)
    with pytest.raises(ValueError, match="low-level tree"):
        QRTree(4, 2, 2, "greedy")


# ---------------------------------------------------------------------------
# the TT kernels
# ---------------------------------------------------------------------------


def _tt_task(cls: str, tiles: list) -> Task:
    n = 4 * NB
    tp = qr.tiled_hqr_ptg(
        *(TwoDimBlockCyclic(name, n, NB, NB, NB) for name in ("A", "TS",
                                                               "TT")),
        QRTree(4, 1, 1), devices="cpu")
    (tc,) = [c for c in tp.task_classes if c.name == cls]
    task = Task(tp, tc, {"k": 0, "m": 1, "n": 1})
    for f, tile in zip(tc.flows, tiles):
        task.data[f.flow_index] = data_create(
            tile.copy(), key=(cls, f.name)).get_copy(0)
    return task


def test_ttqrt_is_the_qr_of_two_triangles_and_keeps_the_head_s_reflectors():
    rng = np.random.default_rng(46)
    r0, b0 = (rng.standard_normal((NB, NB)).astype(np.float32)
              for _ in range(2))
    t0 = np.zeros((NB, NB), np.float32)
    low = np.tril_indices(NB, -1)
    task = _tt_task("TTQRT", [r0, b0, t0])
    qr._ttqrt_cpu(None, task, None, None)
    r1, b1, t1 = (np.asarray(task.flow_data(f).value, np.float64)
                  for f in ("R", "B", "T"))
    # the strictly lower parts, the heads' GEQRT reflectors, bit for bit
    assert (r1[low] == r0[low]).all() and (b1[low] == b0[low]).all()
    # the new R is the R of np.linalg.qr of the stack, up to row signs
    stack = np.vstack([np.triu(r0), np.triu(b0)]).astype(np.float64)
    want = np.linalg.qr(stack, mode="r")
    signs = np.sign(np.diag(r1)) * np.sign(np.diag(want))
    # f32 storage of an O(1) R: a few ulps of its largest entries
    np.testing.assert_allclose(np.triu(r1) * signs[:, None], want,
                               atol=2e-5 * np.abs(want).max())
    # [I; V2] T [I; V2]^T is the stack's Q: Q^T stack = [R; 0]
    v = np.vstack([np.eye(NB), np.triu(b1)])
    q = np.eye(2 * NB) - v @ t1 @ v.T
    got = q.T @ stack
    assert np.abs(got[NB:]).max() < 1e-5 * np.abs(stack).max()
    np.testing.assert_allclose(got[:NB], np.triu(r1), atol=2e-5)
    # and the traceable computes the same: f32 products at the highest
    # precision against float64 inside, a few ulps of O(1) values apart
    tr = [np.asarray(x) for x in qr._ttqrt_traceable(r0, b0, t0)]
    assert (tr[0][low] == r0[low]).all() and (tr[1][low] == b0[low]).all()
    assert not np.tril(tr[1] - b0, -1).any()
    for got_, name in zip(tr, ("R", "B", "T")):
        np.testing.assert_allclose(got_, task.flow_data(name).value,
                                   rtol=2e-3, atol=2e-4)


def test_ttmqr_applies_the_triangular_reflector_and_ignores_the_lower_part():
    rng = np.random.default_rng(47)
    a1, a2, v, t = (rng.standard_normal((NB, NB)).astype(np.float32)
                    for _ in range(4))
    t = np.triu(t) / NB
    task = _tt_task("TTMQR", [a1, a2, v, t])
    qr._ttmqr_cpu(None, task, None, None)
    # float64: [A1; A2] <- (I - [I; V2] T [I; V2]^T)^T [A1; A2], V2 = triu(V)
    v2 = np.triu(v.astype(np.float64))
    vv = np.vstack([np.eye(NB), v2])
    want = (np.eye(2 * NB) - vv @ t.T.astype(np.float64) @ vv.T) @ \
        np.vstack([a1, a2]).astype(np.float64)
    got = np.vstack([task.flow_data("A1").value, task.flow_data("A2").value])
    # f32 storage of O(NB) values
    np.testing.assert_allclose(got, want, atol=1e-5 * np.abs(want).max())
    tr = np.vstack([np.asarray(x) for x in qr._ttmqr_traceable(
        a1, a2, v + np.tril(rng.standard_normal((NB, NB)), -1)
        .astype(np.float32), t)])
    # the strictly lower part of V (a head's GEQRT reflectors) is not read;
    # f32 products at the highest precision against float64 inside
    np.testing.assert_allclose(tr, want, atol=1e-4 * np.abs(want).max())


# ---------------------------------------------------------------------------
# the PTG
# ---------------------------------------------------------------------------


def _factor(tiles: dict, mt: int, nt: int, tree: QRTree, devices: str,
            dev=None) -> tuple:
    A = TwoDimBlockCyclic("A", mt * NB, nt * NB, NB, NB,
                          init_fn=lambda m, k, shape: tiles[m, k].copy())
    TS_, TT_ = (TwoDimBlockCyclic(name, mt * NB, nt * NB, NB, NB)
                for name in ("TS", "TT"))
    tp = qr.tiled_hqr_ptg(A, TS_, TT_, tree, devices=devices)
    ctx = Context(nb_cores=0)
    ctx.add_taskpool(tp)
    ctx.wait(timeout=120)
    if dev is not None:
        dev.sync()
        dev.flush_cache()
    ctx.fini()

    def host(dc, m, k):
        value = dc.data_of(m, k).get_copy(0).value
        assert isinstance(value, np.ndarray) and value.dtype == np.float32
        return value

    return (tp, {key: host(A, *key) for key in tiles},
            {(m, k): host(TS_, m, k) for k in range(nt)
             for m in range(k, mt)},
            {(m, k): host(TT_, m, k) for k in range(nt)
             for m in tree.tt_rows(k)})


def _task_count(mt: int, nt: int, tree: QRTree) -> dict:
    out = dict.fromkeys(("GEQRT", "UNMQR", "TSQRT", "TTQRT", "TSMQR",
                         "TTMQR"), 0)
    for k in range(nt):
        for cls, upd, rows in (("GEQRT", "UNMQR", tree.heads(k)),
                               ("TSQRT", "TSMQR", tree.ts_rows(k)),
                               ("TTQRT", "TTMQR", tree.tt_rows(k))):
            out[cls] += len(rows)
            out[upd] += len(rows) * (nt - 1 - k)
    return out


@pytest.mark.parametrize("a,low", [(2, "binary"), (3, "binary"),
                                   (12, "binary"), (2, "flat"),
                                   (3, "flat")])
def test_ptg_against_numpy_and_the_plain_reference(refh, a, low):
    mt, nt = 12, 3
    tiles = refh.hqr_tiles(46, mt * NB, nt * NB, NB)
    tree = QRTree(mt, nt, a, low)
    tp, ta, tts, ttt = _factor(tiles, mt, nt, tree, "cpu")
    counts = {tc.name: sum(1 for _ in tp._tc_builders[tc.name]
                           ._enumerate_space()) for tc in tp.task_classes}
    assert counts == _task_count(mt, nt, tree)
    a64 = refh.apply(tiles, np.eye(nt * NB), NB, mt * NB)
    r = np.zeros((nt * NB, nt * NB))
    for m in range(nt):
        for n in range(m, nt):
            r[m * NB:(m + 1) * NB, n * NB:(n + 1) * NB] = \
                np.triu(ta[m, n]) if m == n else ta[m, n]
    # R against np.linalg.qr row by row (up to signs), R^T R = A^T A: the
    # CPU bodies compute in float64 and store f32
    want = np.linalg.qr(a64, mode="r")
    signs = np.sign(np.diag(r) * np.diag(want))
    assert np.abs(r * signs[:, None] - want).max() \
        < 2e-5 * np.abs(want).max()
    assert np.linalg.norm(r.T @ r - a64.T @ a64) \
        < 2e-6 * np.linalg.norm(a64.T @ a64)
    # Q.(R.X) = A.X from the V, TS and TT tiles, in the reference's own
    # kill order: f32 storage of every reflector, a few ulps of A.X
    X = np.random.default_rng(99).standard_normal((nt * NB, 4))
    qrx, rtrx = refh.hqr_got(ta, tts, ttt, X, NB, a, low)
    assert np.linalg.norm(qrx - a64 @ X) < 5e-6 * np.linalg.norm(a64 @ X)
    np.testing.assert_allclose(rtrx, r.T @ (r @ X), rtol=1e-12, atol=1e-9)
    for t in list(tts.values()) + list(ttt.values()):
        assert np.abs(np.tril(t, -1)).max() < 1e-6


def test_the_reference_s_replay_is_the_one_thread_replay_bit_for_bit(
        refh, monkeypatch):
    """The read-back's replay runs kills that share no row side by side,
    each once the kills before it on its rows are done: every row sees its
    kills in the order applied, so Q.(R.X) is the one-thread replay's, to
    the bit, whatever the threads."""
    import reference_tiled
    mt, nt, a = 12, 3, 3
    tiles = refh.hqr_tiles(51, mt * NB, nt * NB, NB)
    _, ta, tts, ttt = _factor(tiles, mt, nt, QRTree(mt, nt, a), "cpu")
    ops, waits = refh.replay_order(mt, nt, a, "binary")
    assert len(ops) == sum(len(phase) for step in refh.phases(
        mt, nt, a, "binary") for phase in step)
    for i, (_, _, p, m) in enumerate(ops):
        # the last kill before it on each of its rows
        assert waits[i] == {max(j for j in range(i) if r in ops[j][2:])
                            for r in {p, m}
                            if any(r in op[2:] for op in ops[:i])}
    X = np.random.default_rng(97).standard_normal((nt * NB, 4))
    got = refh.hqr_got(ta, tts, ttt, X, NB, a, "binary")
    monkeypatch.setattr(reference_tiled, "THREADS", 1)
    alone = refh.hqr_got(ta, tts, ttt, X, NB, a, "binary")
    for g, w in zip(got, alone):
        assert np.array_equal(g, w)


@pytest.mark.parametrize("batch", [True, False])
def test_the_device_path_gives_the_per_task_result(refh, accel_device, param,
                                                   batch):
    """Through the device module on a stand-in accelerator: batched panel
    kills (the fused program, stacked for the QR classes) and one task a
    call give the same factorization, each equal to the reference's f32
    control to the rounding of one QR expansion against another."""
    param("device_tpu_batch", batch)
    mt, nt, a = 12, 3, 3
    tiles = refh.hqr_tiles(47, mt * NB, nt * NB, NB)
    tree = QRTree(mt, nt, a)
    _, ta, tts, ttt = _factor(tiles, mt, nt, tree, "tpu", accel_device)
    dev = accel_device
    assert dev.executed_tasks == sum(_task_count(mt, nt, tree).values())
    assert sum(dev.tasks_by_class.values()) == dev.executed_tasks
    if batch:
        # four domains' GEQRTs at step 0 and two TT kills of level 0 ran
        # in a call each
        assert dev.tasks_by_class["GEQRT"] > dev.calls_by_class["GEQRT"]
        assert dev.tasks_by_class["TTQRT"] > dev.calls_by_class["TTQRT"]
    else:
        assert dev.tasks_by_class == dev.calls_by_class
    ca, cts, ctt = refh.hqr_control(tiles, NB, a, "binary", store="float32")
    for got, want in ((ta, ca), (tts, cts), (ttt, ctt)):
        assert got.keys() == want.keys()
        for key in got:
            np.testing.assert_allclose(got[key], want[key], rtol=1e-4,
                                       atol=1e-4)
    X = np.random.default_rng(98).standard_normal((nt * NB, 4))
    qrx, _ = refh.hqr_got(ta, tts, ttt, X, NB, a, "binary")
    ax = refh.apply(tiles, X, NB, mt * NB)
    # f32 throughout, products at the highest precision
    assert np.linalg.norm(qrx - ax) < 5e-6 * np.linalg.norm(ax)


def test_with_one_domain_the_tree_is_the_flat_tree_bit_for_bit(refh):
    """a = MT on a square grid: no head but row k, no TT kill, the same
    kernels on the same tiles in the same order as ``tiled_qr_ptg``."""
    nt = 5
    tiles = refh.hqr_tiles(48, nt * NB, nt * NB, NB)
    _, ta, tts, ttt = _factor(tiles, nt, nt, QRTree(nt, nt, nt), "cpu")
    assert not ttt
    A = TwoDimBlockCyclic("A", nt * NB, nt * NB, NB, NB,
                          init_fn=lambda m, k, shape: tiles[m, k].copy())
    T = TwoDimBlockCyclic("T", nt * NB, nt * NB, NB, NB)
    ctx = Context(nb_cores=0)
    ctx.add_taskpool(qr.tiled_qr_ptg(A, T, devices="cpu"))
    ctx.wait(timeout=120)
    ctx.fini()
    for key, tile in ta.items():
        assert (A.data_of(*key).get_copy(0).value == tile).all(), key
    for key, tile in tts.items():
        assert (T.data_of(*key).get_copy(0).value == tile).all(), key


def _factor_twice(mt: int, nt: int, levels: list) -> None:
    """One pool of domains of 2 and one of a single domain, each to its
    end; each pool's ``panel_levels`` onto ``levels``."""
    for a in (2, 8):
        tree = QRTree(mt, nt, a)
        A = TwoDimBlockCyclic.from_dense(
            "A", np.random.default_rng(a).standard_normal(
                (mt * NB, nt * NB)).astype(np.float32), NB, NB)
        tp = qr.tiled_hqr_ptg(A, *(TwoDimBlockCyclic(n, mt * NB, nt * NB,
                                                     NB, NB)
                                   for n in ("TS", "TT")), tree,
                              devices="cpu")
        assert not hasattr(tp, "panel_levels")
        ctx = Context(nb_cores=0)
        ctx.add_taskpool(tp)
        ctx.wait(timeout=120)
        ctx.fini()
        levels.append(tp.panel_levels)


def test_the_tree_s_span_and_counter_come_once_a_pool(param):
    """The tree's tables are built when the pool is enqueued, inside
    ``ctx.add_taskpool``'s span, and never again while it runs."""
    param("prof_spans", True)
    spans.phase_reset()
    mt, nt = 8, 2
    levels = []
    try:
        _factor_twice(mt, nt, levels)
    finally:
        param("prof_spans", False)
        spans.uninstall()       # Context installed the request recorder
    table = spans.phase_totals()
    spans.phase_reset()
    assert table["ptg.qrtree"][2] == 2
    # nested: its time is inside ctx.add_taskpool's, off its self time
    assert table["ctx.add_taskpool"][1] >= table["ptg.qrtree"][1]
    # domains of 2: 1 + 1 + ceil(log2 4) and 1 + 1 + ceil(log2 4); one
    # domain: the flat chains of 8 and 7
    assert levels == [4 + 4, 8 + 7]


def test_the_hqr_graph_verifies_and_counts_at_the_cell_s_shape():
    """128 x 8 tiles, domains of four, a binary tree: the configuration's
    5,630 tasks, enumerated without a tile."""
    n = 8 * NB
    A = TwoDimBlockCyclic("A", 16 * n, n, NB, NB)
    tree = QRTree(128, 8, 4)
    tp = qr.tiled_hqr_ptg(A, TwoDimBlockCyclic("TS", 16 * n, n, NB, NB),
                          TwoDimBlockCyclic("TT", 16 * n, n, NB, NB), tree)
    counts = {tc.name: sum(1 for _ in tp._tc_builders[tc.name]
                           ._enumerate_space()) for tc in tp.task_classes}
    assert counts == {"GEQRT": 252, "UNMQR": 890, "TSQRT": 744,
                      "TTQRT": 244, "TSMQR": 2638, "TTMQR": 862}
    assert sum(counts.values()) == 5630 and not A._store
    tp.validate()


# ---------------------------------------------------------------------------
# what a solve leaves the device module to hold: programs and temporaries
# ---------------------------------------------------------------------------


def test_at_the_cell_s_grid_the_update_classes_keep_to_their_lanes(
        accel_device, param):
    """128 x 8 tiles, domains of four, a binary tree: a solve's fused
    programs are the panel's at 32 lanes and the TT kills' at 2 to 16,
    UNMQR and TSMQR at 32 lanes (the flat tree's cells' own programs) and
    TTMQR at 2 to 16, twelve in all (``UPDATE_LANES``).  At 64 lanes a
    solve's programs were 235 MiB of the chip's compile cache, which keeps
    190.  The batches are the flood's, which the chip repeats call for call
    (PERF.md, section 6); the stand-in's small tiles do not change them."""
    param("device_tpu_batch", True)
    mt, nt, a = 128, 8, 4
    tree = QRTree(mt, nt, a)
    tiles = {(m, k): np.random.default_rng([50, m, k]).standard_normal(
        (NB, NB)).astype(np.float32) for m in range(mt) for k in range(nt)}
    _factor(tiles, mt, nt, tree, "tpu", accel_device)
    dev = accel_device
    assert dev.tasks_by_class == _task_count(mt, nt, tree)
    assert sorted((key[0], key[1]) + key[3:] for key in dev._vmap_cache) == \
        [("qr_geqrt", 32), ("qr_tsmqr", 32), ("qr_tsqrt", 32)] \
        + [("qr_ttmqr", n) for n in (2, 4, 8, 16)] \
        + [("qr_ttqrt", n) for n in (2, 4, 8, 16)] + [("qr_unmqr", 32)]
    assert qr.UPDATE_LANES == {"UNMQR": 32, "TSMQR": 32, "TTMQR": 16}


def test_a_class_s_batch_max_bounds_what_the_queue_hands_one_batch(
        accel_device, param):
    """Pending instances of one class go to one batch up to the class's
    ``batch_max``, and without one as many as are queued."""
    from types import SimpleNamespace
    param("device_tpu_batch", True)
    dev = accel_device
    capped, free = (SimpleNamespace(batch_max=3), SimpleNamespace())
    body = object()
    for tc in (capped, free):
        dev._pending.extend(SimpleNamespace(
            task=SimpleNamespace(task_class=tc), submit=body)
            for _ in range(5))
    sizes = []
    while dev._pending:
        sizes.append(len(dev._take_batch_locked()))
    assert sizes == [3, 2, 5]


def test_a_stacked_batch_asks_the_budget_for_its_temporaries(
        refh, accel_device, param, monkeypatch):
    """A batched QR stacks its lanes' tiles and its results beside them: the
    program's temporaries (XLA's memory analysis, read once where the
    program is built) are charged with its results that take no donated
    buffer, to the HBM budget and to the ring, until the call has run.  A
    per-lane program has none charged."""
    from parsec_tpu.device.tpu import _avals
    param("device_tpu_batch", True)
    dev = accel_device
    charged = []
    note = dev._note_inflight

    def noting(first, held):
        charged.append(held)
        return note(first, held)

    monkeypatch.setattr(dev, "_note_inflight", noting)
    mt, nt, a = 12, 3, 3
    tiles = refh.hqr_tiles(49, mt * NB, nt * NB, NB)
    _factor(tiles, mt, nt, QRTree(mt, nt, a), "tpu", dev)
    tile = NB * NB * 4
    stacked = 0
    for key, fn in dev._vmap_cache.items():
        dyld, lanes, sig = key[:3]
        if dyld not in ("qr_geqrt", "qr_tsqrt", "qr_ttqrt"):
            assert not hasattr(fn, "temps"), key
            continue
        stacked += 1
        mem = fn.lower(*_avals([s for s in sig for _ in range(lanes)],
                               dev.jax_device)).compile().memory_analysis()
        assert fn.temps == mem.temp_size_in_bytes > 0, key
        # every flow of the panel's classes is written
        assert lanes * (len(sig) - len(fn.donates)) * tile + fn.temps \
            in charged, key
    assert stacked >= 3
