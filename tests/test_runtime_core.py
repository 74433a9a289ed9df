"""Runtime-core tests: hand-written and PTG DAGs through the full
scheduling loop (analog of reference tests/runtime/ + examples Ex00-Ex04)."""

import threading

import numpy as np
import pytest

from parsec_tpu import ptg
from parsec_tpu.data import TileType
from parsec_tpu.data_dist import DictCollection
from parsec_tpu.runtime import (Chore, Context, Dep, Flow, Task, TaskClass,
                                Taskpool, compose)


def make_chain_ptg(N, coll, trace=None):
    """Ex04_ChainData shape: T(0..N-1), one datum threading through."""
    p = ptg.PTGBuilder("chain", N=N, A=coll)
    t = p.task("T", k=ptg.span(0, lambda g, l: g.N - 1))
    t.affinity("A", lambda g, l: (0,))
    f = t.flow("A", ptg.RW)
    f.input(data=("A", lambda g, l: (0,)), guard=lambda g, l: l.k == 0)
    f.input(pred=("T", "A", lambda g, l: {"k": l.k - 1}),
            guard=lambda g, l: l.k > 0)
    f.output(succ=("T", "A", lambda g, l: {"k": l.k + 1}),
             guard=lambda g, l: l.k < g.N - 1)
    f.output(data=("A", lambda g, l: (0,)), guard=lambda g, l: l.k == g.N - 1)

    @t.body
    def body(es, task, g, l):
        copy = task.flow_data("A")
        copy.value = copy.value + 1
        if trace is not None:
            trace.append(l.k)

    return p.build()


class TestStartStop:
    def test_init_fini(self):
        # Ex00_StartStop: init + fini with no taskpool
        ctx = Context(nb_cores=0)
        ctx.start()
        ctx.wait()
        ctx.fini()

    def test_repeated_init_fini(self):
        for _ in range(3):
            ctx = Context(nb_cores=0)
            ctx.fini()


class TestChain:
    @pytest.mark.parametrize("nb_cores", [0, 2])
    def test_chain_data_updates_in_order(self, nb_cores):
        N = 16
        coll = DictCollection("A", dtt=TileType((4,), np.float32))
        trace = []
        tp = make_chain_ptg(N, coll, trace)
        ctx = Context(nb_cores=nb_cores)
        ctx.add_taskpool(tp)
        ctx.start()
        tp.wait(timeout=30)
        ctx.fini()
        assert trace == list(range(N))  # strict chain order
        np.testing.assert_allclose(coll.data_of(0).newest_copy().value,
                                   np.full((4,), N, np.float32))

    def test_two_taskpools_same_context(self):
        c1 = DictCollection("A", dtt=TileType((2,), np.float32))
        c2 = DictCollection("B", dtt=TileType((2,), np.float32))
        tp1, tp2 = make_chain_ptg(5, c1), make_chain_ptg(7, c2)
        ctx = Context(nb_cores=0)
        ctx.add_taskpool(tp1)
        ctx.add_taskpool(tp2)
        ctx.wait(timeout=30)
        ctx.fini()
        assert c1.data_of(0).newest_copy().value[0] == 5
        assert c2.data_of(0).newest_copy().value[0] == 7

    def test_compound_sequential_composition(self):
        coll = DictCollection("A", dtt=TileType((2,), np.float32))
        order = []
        tps = []
        for i in range(3):
            trace = []
            tp = make_chain_ptg(4, coll, trace)
            tp.on_complete = (lambda i: lambda _tp: order.append(i))(i)
            tps.append(tp)
        comp = compose(*tps)
        ctx = Context(nb_cores=2)
        ctx.add_taskpool(comp)
        ctx.start()
        comp.wait(timeout=30)
        ctx.fini()
        assert order == [0, 1, 2]
        assert coll.data_of(0).newest_copy().value[0] == 12


class TestBranchingAndGuards:
    def test_fork_join_diamond(self):
        """A(0) -> B,C (fork) -> D (join): guarded multi-out, multi-in."""
        coll = DictCollection("X", dtt=TileType((1,), np.float32),
                              init_fn=lambda *k: np.zeros(1, np.float32))
        p = ptg.PTGBuilder("diamond", X=coll)
        a = p.task("A", i=lambda g, l: range(1))
        fa = a.flow("V", ptg.RW)
        fa.input(data=("X", lambda g, l: (0,)))
        fa.output(succ=("B", "V", lambda g, l: {"i": 0}))
        fa.output(succ=("C", "V", lambda g, l: {"i": 0}))

        @a.body
        def abody(es, task, g, l):
            c = task.flow_data("V")
            c.value = c.value + 1

        results = {}
        for name, add in (("B", 10), ("C", 100)):
            t = p.task(name, i=lambda g, l: range(1))
            fl = t.flow("V", ptg.READ)
            fl.input(pred=("A", "V", lambda g, l: {"i": 0}))
            ctl = t.flow("done", ptg.CTL)
            ctl.output(succ=("D", "start", lambda g, l: {"i": 0}))

            def mk(nm, addv):
                def b(es, task, g, l):
                    results[nm] = float(task.flow_data("V").value[0]) + addv
                return b

            t.body(mk(name, add))
        d = p.task("D", i=lambda g, l: range(1))
        ctl_in = d.flow("start", ptg.CTL)
        ctl_in.input(pred=("B", "done", lambda g, l: {"i": 0}))
        ctl_in.input(pred=("C", "done", lambda g, l: {"i": 0}))

        joined = []

        @d.body
        def dbody(es, task, g, l):
            joined.append(sorted(results.values()))

        tp = p.build()
        ctx = Context(nb_cores=2)
        ctx.add_taskpool(tp)
        ctx.start()
        tp.wait(timeout=30)
        ctx.fini()
        assert joined == [[11.0, 101.0]]

    def test_guard_excludes_dep(self):
        """Guarded outputs only fire when the predicate holds (branching)."""
        coll = DictCollection("X", dtt=TileType((1,), np.float32))
        seen = []
        p = ptg.PTGBuilder("branch", N=6, X=coll)
        t = p.task("T", k=ptg.span(0, lambda g, l: g.N - 1))
        f = t.flow("V", ptg.RW)
        f.input(data=("X", lambda g, l: (l.k,)))
        # only even k notify the sink
        ctl = t.flow("c", ptg.CTL)
        ctl.output(succ=("S", "in_", lambda g, l: {"k": l.k}),
                   guard=lambda g, l: l.k % 2 == 0)
        t.body(lambda es, task, g, l: None)
        s = p.task("S", k=lambda g, l: range(0, g.N, 2))
        sf = s.flow("in_", ptg.CTL)
        sf.input(pred=("T", "c", lambda g, l: {"k": l.k}))
        s.body(lambda es, task, g, l: seen.append(l.k))
        tp = p.build()
        ctx = Context(nb_cores=0)
        ctx.add_taskpool(tp)
        ctx.wait(timeout=30)
        ctx.fini()
        assert sorted(seen) == [0, 2, 4]


class TestEP:
    """Embarrassingly-parallel CTL-only DAG (tests/runtime/scheduling/ep.jdf):
    NT chains of DEPTH tasks — the dispatch-overhead shape."""

    def _build(self, NT, DEPTH, counter):
        p = ptg.PTGBuilder("ep", NT=NT, DEPTH=DEPTH)
        t = p.task("EP",
                   d=ptg.span(0, lambda g, l: g.DEPTH - 1),
                   n=ptg.span(0, lambda g, l: g.NT - 1))
        f = t.flow("ctl", ptg.CTL)
        f.input(pred=("EP", "ctl", lambda g, l: {"d": l.d - 1, "n": l.n}),
                guard=lambda g, l: l.d > 0)
        f.output(succ=("EP", "ctl", lambda g, l: {"d": l.d + 1, "n": l.n}),
                 guard=lambda g, l: l.d < g.DEPTH - 1)
        t.body(lambda es, task, g, l: counter.append(None))
        return p.build()

    @pytest.mark.parametrize("sched", ["lfq", "ap", "spq", "gd", "rnd", "ip",
                                       "ll", "llp", "pbq", "ltq", "lhq"])
    def test_all_schedulers_run_ep(self, sched):
        count = []
        tp = self._build(8, 5, count)
        ctx = Context(nb_cores=2, scheduler=sched)
        ctx.add_taskpool(tp)
        ctx.start()
        tp.wait(timeout=60)
        ctx.fini()
        assert len(count) == 8 * 5

    def test_ep_single_threaded(self):
        count = []
        tp = self._build(4, 3, count)
        ctx = Context(nb_cores=0)
        ctx.add_taskpool(tp)
        ctx.wait(timeout=30)
        ctx.fini()
        assert len(count) == 12


class TestHandWrittenTaskClass:
    """Layer-2 exit test from SURVEY §7: no DSL, raw TaskClass objects."""

    def test_manual_chain(self):
        N = 5
        log = []
        tc = TaskClass(
            "man",
            params=["k"],
            flows=[Flow("c", "CTL",
                        deps_in=[Dep(guard=lambda l: l["k"] > 0,
                                     target_class="man", target_flow="c",
                                     target_params=lambda l: {"k": l["k"] - 1})],
                        deps_out=[Dep(guard=lambda l: l["k"] < N - 1,
                                      target_class="man", target_flow="c",
                                      target_params=lambda l: {"k": l["k"] + 1})])],
            chores=[Chore("cpu", hook=lambda es, t: log.append(t.locals["k"]) or 0)],
        )

        class ManualTP(Taskpool):
            def nb_local_tasks(self):
                return N

            def startup(self, context):
                t = Task(self, self.task_classes[0], {"k": 0})
                return [t]

        tp = ManualTP(name="manual", task_classes=[tc])
        ctx = Context(nb_cores=0)
        ctx.add_taskpool(tp)
        ctx.wait(timeout=30)
        ctx.fini()
        assert log == list(range(N))


class TestPriorities:
    def test_priority_order_with_ap(self):
        """With a single worker + ap scheduler, independent ready tasks run
        highest-priority first."""
        seen = []
        p = ptg.PTGBuilder("prio", N=8)
        t = p.task("P", k=ptg.span(0, lambda g, l: g.N - 1))
        t.priority(lambda g, l: l.k)
        t.body(lambda es, task, g, l: seen.append(l.k))
        tp = p.build()
        ctx = Context(nb_cores=0, scheduler="ap")
        ctx.add_taskpool(tp)
        ctx.wait(timeout=30)
        ctx.fini()
        # the keep-highest slot takes one; the rest must be descending
        assert seen[1:] == sorted(seen[1:], reverse=True)
