"""Coverage round-out (VERDICT r2 table): rwlock, show_help aggregation,
vpmap specs, debug marks, iterators_checker, ptg_to_dtd, paranoid mode."""

import threading

import numpy as np
import pytest

from parsec_tpu import ptg
from parsec_tpu.core.params import params
from parsec_tpu.core.rwlock import RWLock
from parsec_tpu.data.data import TileType
from parsec_tpu.data_dist.collection import DictCollection
from parsec_tpu.runtime import Context


class TestRWLock:
    def test_readers_share_writers_exclude(self):
        lk = RWLock()
        state = {"readers": 0, "max_readers": 0, "writer_during_read": False}
        stop = threading.Event()

        def reader():
            for _ in range(200):
                with lk.read():
                    state["readers"] += 1
                    state["max_readers"] = max(state["max_readers"],
                                               state["readers"])
                    state["readers"] -= 1

        def writer():
            for _ in range(50):
                with lk.write():
                    if state["readers"]:
                        state["writer_during_read"] = True

        ts = [threading.Thread(target=reader) for _ in range(4)] + \
             [threading.Thread(target=writer) for _ in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(10)
        stop.set()
        assert not state["writer_during_read"]

    def test_writer_preference(self):
        lk = RWLock()
        lk.acquire_read()
        got_write = threading.Event()

        def w():
            lk.acquire_write()
            got_write.set()
            lk.release_write()

        t = threading.Thread(target=w)
        t.start()
        import time
        time.sleep(0.05)
        # a waiting writer blocks NEW readers
        blocked = threading.Event()

        def r():
            lk.acquire_read()
            blocked.set()
            lk.release_read()

        t2 = threading.Thread(target=r)
        t2.start()
        time.sleep(0.05)
        assert not blocked.is_set()
        lk.release_read()
        t.join(5)
        t2.join(5)
        assert got_write.is_set() and blocked.is_set()


class TestShowHelp:
    def test_dedup_and_flush(self):
        from parsec_tpu.core.output import show_help, show_help_flush
        show_help_flush()
        assert show_help("topic", "sec", "message %d", 1) is True
        assert show_help("topic", "sec", "message %d", 2) is False
        assert show_help("topic", "sec", "message %d", 3) is False
        assert show_help("topic", "other", "different") is True
        counts = show_help_flush()
        assert counts[("topic", "sec")] == 3
        assert counts[("topic", "other")] == 1
        # flushed: the topic prints again
        assert show_help("topic", "sec", "again") is True
        show_help_flush()


class TestVPMap:
    def test_specs(self):
        from parsec_tpu.runtime.vpmap import parse_vpmap
        assert parse_vpmap("", 4, 2) == [0, 1, 0, 1]
        assert parse_vpmap("flat", 4, 2) == [0, 0, 0, 0]
        assert parse_vpmap("rr:3", 6, 1) == [0, 1, 2, 0, 1, 2]
        assert parse_vpmap("list:2,1", 3, 1) == [0, 0, 1]
        with pytest.raises(ValueError):
            parse_vpmap("bogus:1", 2, 1)
        with pytest.raises(ValueError):
            parse_vpmap("list:0", 2, 1)

    def test_file_spec(self, tmp_path, param):
        from parsec_tpu.runtime.vpmap import parse_vpmap
        p = tmp_path / "vpmap"
        p.write_text("# comment\n2\n2\n")
        assert parse_vpmap(f"file:{p}", 4, 1) == [0, 0, 1, 1]

    def test_context_honors_spec(self, param):
        param("runtime_vpmap", "list:2,2")
        ctx = Context(nb_cores=4)
        assert len(ctx.virtual_processes) == 2
        assert [len(vp.execution_streams)
                for vp in ctx.virtual_processes] == [2, 2]
        ctx.fini()


def _small_pool(trace=None):
    p = ptg.PTGBuilder("t", N=4)
    t = p.task("T", i=ptg.span(0, lambda g, l: g.N - 1))
    f = t.flow("ctl", ptg.CTL)
    f.input(pred=("T", "ctl", lambda g, l: {"i": l.i - 1}),
            guard=lambda g, l: l.i > 0)
    f.output(succ=("T", "ctl", lambda g, l: {"i": l.i + 1}),
             guard=lambda g, l: l.i < g.N - 1)
    t.body(lambda es, task, g, l:
           trace.append(l.i) if trace is not None else None)
    return p.build()


class TestDebugMarks:
    def test_ring_captures_events(self, param):
        from parsec_tpu.core.mca import repository
        from parsec_tpu.prof import debug_marks
        comp = repository.find("pins", "debug_marks")
        mod = comp.open()   # install re-creates the module-level ring
        ring = debug_marks.ring
        try:
            run = []
            ctx = Context(nb_cores=0)
            ctx.add_taskpool(_small_pool(run))
            ctx.wait(timeout=30)
            ctx.fini()
        finally:
            comp.close(mod)
        kinds = {k for _, _, k, _ in ring.snapshot()}
        assert {"exec_begin", "exec_end", "release_deps"} <= kinds
        assert "T(i=0)" in ring.dump()

    def test_ring_is_bounded(self):
        from parsec_tpu.prof.debug_marks import MarkRing
        r = MarkRing(8)
        for i in range(100):
            r.mark("k", str(i))
        snap = r.snapshot()
        assert len(snap) == 8
        assert snap[-1][3] == "99"


class TestIteratorsChecker:
    def test_consistent_graph_passes(self):
        from parsec_tpu.core.mca import repository
        comp = repository.find("pins", "iterators_checker")
        mod = comp.open()
        try:
            ctx = Context(nb_cores=0)
            ctx.add_taskpool(_small_pool())
            ctx.wait(timeout=30)
            ctx.fini()
        finally:
            checked = mod.checked_edges
            comp.close(mod)
        assert checked == 3     # chain of 4: three forward edges

    def test_inconsistent_arrow_is_caught(self):
        from parsec_tpu.prof.iterators_checker import (IteratorsCheckerError,
                                                       check_task)
        from parsec_tpu.runtime.task import Task
        p = ptg.PTGBuilder("bad", N=2)
        t = p.task("T", i=ptg.span(0, lambda g, l: g.N - 1))
        f = t.flow("ctl", ptg.CTL)
        # out-arrow claims an edge the successor's in-deps don't declare
        f.output(succ=("T", "ctl", lambda g, l: {"i": l.i + 1}),
                 guard=lambda g, l: l.i < g.N - 1)
        t.body(lambda es, task, g, l: None)
        tp = p.build()
        task = Task(tp, tp.task_class("T"), {"i": 0})
        with pytest.raises(IteratorsCheckerError):
            check_task(task)


class TestPtgToDtd:
    def test_gemm_through_dtd(self):
        from parsec_tpu.data_dist.matrix import TwoDimBlockCyclic
        from parsec_tpu.dtd import ptg_to_dtd
        from parsec_tpu.models.tiled_gemm import tiled_gemm_ptg
        n, nb = 32, 8
        rng = np.random.RandomState(3)
        a = rng.randn(n, n).astype(np.float32)
        b = rng.randn(n, n).astype(np.float32)
        A = TwoDimBlockCyclic.from_dense("A", a, nb, nb)
        B = TwoDimBlockCyclic.from_dense("B", b, nb, nb)
        C = TwoDimBlockCyclic("C", n, n, nb, nb)
        tp = tiled_gemm_ptg(A, B, C, devices="cpu")
        ctx = Context(nb_cores=0)
        ptg_to_dtd(tp, ctx)
        ctx.fini()
        np.testing.assert_allclose(C.to_dense(), a @ b, rtol=1e-4,
                                   atol=1e-4)

    def test_ctl_pool_rejected(self):
        from parsec_tpu.dtd import ptg_to_dtd
        from parsec_tpu.dtd.from_ptg import PTGToDTDError
        ctx = Context(nb_cores=0)
        with pytest.raises(PTGToDTDError):
            ptg_to_dtd(_small_pool(), ctx)
        ctx.fini()


class TestParanoid:
    def test_unordered_writebacks_caught(self, param):
        from parsec_tpu.runtime.scheduling import apply_writeback_to_home
        param("debug_paranoid", True)
        coll = DictCollection("P", dtt=TileType((1,), np.float32),
                              init_fn=lambda *k: np.zeros(1, np.float32))
        from parsec_tpu.data.data import data_create
        c1 = data_create(np.ones(1, np.float32), key="a").get_copy(0)
        c2 = data_create(np.ones(1, np.float32), key="b").get_copy(0)
        c1.version = 3
        c2.version = 2   # strictly older after newer: must be a race
        apply_writeback_to_home(coll, (0,), c1, owner=7)
        with pytest.raises(AssertionError, match="unordered writebacks"):
            apply_writeback_to_home(coll, (0,), c2, owner=7)

    def test_equal_version_writebacks_warn_not_raise(self, param):
        """Two fresh copies at the same version may be legally CTL-ordered:
        the paranoid mode warns instead of rejecting a legal program."""
        from parsec_tpu.core.output import show_help_flush
        from parsec_tpu.data.data import data_create
        from parsec_tpu.runtime.scheduling import apply_writeback_to_home
        param("debug_paranoid", True)
        coll = DictCollection("R", dtt=TileType((1,), np.float32),
                              init_fn=lambda *k: np.zeros(1, np.float32))
        show_help_flush()
        c1 = data_create(np.ones(1, np.float32), key="e1").get_copy(0)
        c2 = data_create(np.ones(1, np.float32), key="e2").get_copy(0)
        apply_writeback_to_home(coll, (0,), c1, owner=8)
        apply_writeback_to_home(coll, (0,), c2, owner=8)   # no raise
        counts = show_help_flush()
        assert counts.get(("paranoid", "equal-version-writeback"), 0) >= 1

    def test_ordered_writebacks_pass(self, param):
        from parsec_tpu.runtime.scheduling import apply_writeback_to_home
        param("debug_paranoid", True)
        coll = DictCollection("Q", dtt=TileType((1,), np.float32),
                              init_fn=lambda *k: np.zeros(1, np.float32))
        from parsec_tpu.data.data import data_create
        for v in (1, 2, 3):
            c = data_create(np.ones(1, np.float32), key=f"v{v}").get_copy(0)
            c.version = v
            apply_writeback_to_home(coll, (0,), c, owner=9)

    def test_normal_run_clean_under_paranoid(self, param):
        param("debug_paranoid", True)
        trace = []
        ctx = Context(nb_cores=0)
        ctx.add_taskpool(_small_pool(trace))
        ctx.wait(timeout=30)
        ctx.fini()
        assert len(trace) == 4


class TestThreadBinding:
    def test_bound_workers_run(self, param):
        """runtime_bind_threads pins workers round-robin (best-effort);
        the run must complete and execute every task either way."""
        param("runtime_bind_threads", True)
        trace = []
        ctx = Context(nb_cores=2)
        ctx.add_taskpool(_small_pool(trace))
        ctx.start()
        ctx.wait(timeout=30)
        ctx.fini()
        assert len(trace) == 4
