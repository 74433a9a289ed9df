"""Compiled-DAG executor (runtime/dagrun.py): the native inner loop.

Adversarial strategy: every test runs the same taskpool twice — once with
``runtime_dag_compile`` on (native select→release) and once forced dynamic —
and asserts identical results.  The compiled path is an incarnation of the
scheduler, so its only observable difference must be speed.
"""

import numpy as np
import pytest

from parsec_tpu import ptg
from parsec_tpu.core.params import params
from parsec_tpu.data.data import TileType
from parsec_tpu.data_dist.collection import DictCollection
from parsec_tpu.runtime import Context
from parsec_tpu.runtime.dagrun import (CompiledDag, VecCompiledDag,
                                       compile_taskpool_dag)


def ep_pool(NT=8, DEPTH=5, trace=None):
    p = ptg.PTGBuilder("ep", NT=NT, DEPTH=DEPTH)
    t = p.task("EP",
               d=ptg.span(0, lambda g, l: g.DEPTH - 1),
               n=ptg.span(0, lambda g, l: g.NT - 1))
    f = t.flow("ctl", ptg.CTL)
    f.input(pred=("EP", "ctl", lambda g, l: {"d": l.d - 1, "n": l.n}),
            guard=lambda g, l: l.d > 0)
    f.output(succ=("EP", "ctl", lambda g, l: {"d": l.d + 1, "n": l.n}),
             guard=lambda g, l: l.d < g.DEPTH - 1)
    t.body(lambda es, task, g, l:
           trace.append((l.d, l.n)) if trace is not None else None)
    return p.build()


def run_pool(tp, **ctx_kw):
    ctx = Context(**ctx_kw)
    ctx.add_taskpool(tp)
    ctx.wait(timeout=60)
    ctx.fini()


@pytest.fixture
def dynamic_only():
    old = params.get("runtime_dag_compile")
    params.set("runtime_dag_compile", False)
    yield
    params.set("runtime_dag_compile", old)


class TestVectorPath:
    def test_ep_compiles_vectorized(self):
        tp = ep_pool()
        ctx = Context(nb_cores=0)
        dag = compile_taskpool_dag(tp, ctx)
        assert isinstance(dag, VecCompiledDag)
        assert dag.ntasks == 8 * 5
        ctx.fini()

    def test_ep_executes_every_task_once(self):
        trace = []
        run_pool(ep_pool(trace=trace), nb_cores=0)
        assert sorted(trace) == [(d, n) for d in range(5) for n in range(8)]

    def test_dependency_order_respected(self):
        trace = []
        run_pool(ep_pool(trace=trace), nb_cores=0)
        pos = {t: i for i, t in enumerate(trace)}
        for d in range(1, 5):
            for n in range(8):
                assert pos[(d - 1, n)] < pos[(d, n)], \
                    f"EP({d},{n}) ran before its predecessor"

    def test_threaded_context_drives_compiled_pool(self):
        trace = []
        run_pool(ep_pool(trace=trace), nb_cores=2)
        assert len(trace) == 40

    def test_the_dispatch_shape_never_asks_the_scheduler(self, monkeypatch):
        """2,000 tasks of the EP shape (50 lanes x 40) take the compiled-DAG
        path whole: the pool carries its compiled DAG, every task runs once,
        and the scheduler module is neither asked for a task nor handed
        one."""
        trace = []
        tp = ep_pool(50, 40, trace)
        ctx = Context(nb_cores=0)
        asked = []
        mod = type(ctx.scheduler)
        for name in ("select", "schedule"):
            monkeypatch.setattr(mod, name, lambda *a, _n=name, **kw:
                                asked.append(_n))
        ctx.add_taskpool(tp)
        assert tp._compiled_dag is not None
        ctx.wait(timeout=60)
        ctx.fini()
        assert len(trace) == 2000 == len(set(trace))
        assert asked == []

    def test_matches_dynamic(self, dynamic_only):
        trace = []
        run_pool(ep_pool(trace=trace), nb_cores=0)
        assert sorted(trace) == [(d, n) for d in range(5) for n in range(8)]


class TestScalarPath:
    def chain_pool(self, coll, n=6):
        """RW chain over one tile: T(0) -> T(1) -> ... each adds 1."""
        p = ptg.PTGBuilder("chain", N=n, A=coll)
        t = p.task("T", i=ptg.span(0, lambda g, l: g.N - 1))
        f = t.flow("V", ptg.RW)
        f.input(data=("A", lambda g, l: (0,)), guard=lambda g, l: l.i == 0)
        f.input(pred=("T", "V", lambda g, l: {"i": l.i - 1}),
                guard=lambda g, l: l.i > 0)
        f.output(succ=("T", "V", lambda g, l: {"i": l.i + 1}),
                 guard=lambda g, l: l.i < g.N - 1)
        f.output(data=("A", lambda g, l: (0,)),
                 guard=lambda g, l: l.i == g.N - 1)

        @t.body
        def body(es, task, g, l):
            c = task.flow_data("V")
            c.value = c.value + 1

        return p.build()

    def test_data_chain_compiles_scalar(self):
        coll = DictCollection("A", dtt=TileType((2,), np.float32),
                              init_fn=lambda *k: np.zeros(2, np.float32))
        tp = self.chain_pool(coll)
        ctx = Context(nb_cores=0)
        dag = compile_taskpool_dag(tp, ctx)
        assert isinstance(dag, CompiledDag) and dag.ntasks == 6
        ctx.fini()

    def test_data_chain_result(self):
        coll = DictCollection("A", dtt=TileType((2,), np.float32),
                              init_fn=lambda *k: np.zeros(2, np.float32))
        run_pool(self.chain_pool(coll), nb_cores=0)
        assert coll.data_of(0).newest_copy().value[0] == 6

    def test_data_chain_matches_dynamic(self, dynamic_only):
        coll = DictCollection("A", dtt=TileType((2,), np.float32),
                              init_fn=lambda *k: np.zeros(2, np.float32))
        run_pool(self.chain_pool(coll), nb_cores=0)
        assert coll.data_of(0).newest_copy().value[0] == 6

    def test_priority_pool_takes_scalar_path(self):
        p = ptg.PTGBuilder("prio", N=4)
        t = p.task("T", i=ptg.span(0, lambda g, l: g.N - 1))
        t.flow("ctl", ptg.CTL).output(
            succ=("U", "ctl", lambda g, l: {"i": l.i}))
        t.priority(lambda g, l: l.i)
        t.body(lambda es, task, g, l: None)
        u = p.task("U", i=ptg.span(0, lambda g, l: g.N - 1))
        u.flow("ctl", ptg.CTL).input(
            pred=("T", "ctl", lambda g, l: {"i": l.i}))
        u.body(lambda es, task, g, l: None)
        tp = p.build()
        ctx = Context(nb_cores=0)
        dag = compile_taskpool_dag(tp, ctx)
        assert isinstance(dag, CompiledDag)   # priority -> scalar builder
        ctx.fini()
        run_pool(tp, nb_cores=0)

    def test_triangular_space_takes_scalar_path(self):
        """Dependent ranges (l.i bound in l.j's range) resist vectorizing."""
        seen = []
        p = ptg.PTGBuilder("tri", N=5)
        t = p.task("T", i=ptg.span(0, lambda g, l: g.N - 1),
                   j=ptg.span(0, lambda g, l: l.i))
        t.flow("ctl", ptg.CTL)
        t.body(lambda es, task, g, l: seen.append((l.i, l.j)))
        tp = p.build()
        ctx = Context(nb_cores=0)
        dag = compile_taskpool_dag(tp, ctx)
        assert isinstance(dag, CompiledDag) and dag.ntasks == 15
        ctx.fini()
        run_pool(tp, nb_cores=0)
        assert sorted(seen) == [(i, j) for i in range(5)
                                for j in range(i + 1)]


class TestHookProtocol:
    def test_again_is_retried(self):
        from parsec_tpu.runtime.task import HOOK_RETURN_AGAIN
        attempts = {}

        p = ptg.PTGBuilder("again", N=6)
        t = p.task("T", i=ptg.span(0, lambda g, l: g.N - 1))
        t.flow("ctl", ptg.CTL)

        @t.body
        def body(es, task, g, l):
            k = attempts.get(l.i, 0)
            attempts[l.i] = k + 1
            if k < 2:
                return HOOK_RETURN_AGAIN
            return None

        run_pool(p.build(), nb_cores=0)
        assert all(v == 3 for v in attempts.values())

    def test_again_with_batch_overflow(self):
        """Retry merge must not overflow the fixed completion buffer: a
        >1024-wide wavefront plus a carried AGAIN task in one pass."""
        from parsec_tpu.runtime.task import HOOK_RETURN_AGAIN
        state = {"again": True, "ran": 0}

        p = ptg.PTGBuilder("wide", N=2200)
        t = p.task("T", i=ptg.span(0, lambda g, l: g.N - 1))
        t.flow("ctl", ptg.CTL)

        @t.body
        def body(es, task, g, l):
            state["ran"] += 1
            if l.i == 0 and state["again"]:
                state["again"] = False
                return HOOK_RETURN_AGAIN
            return None

        run_pool(p.build(), nb_cores=0)
        assert state["ran"] == 2201   # 2200 tasks + one retry

    def test_wait_timeout_leaves_pool_resumable(self):
        import time as _t
        p = ptg.PTGBuilder("slow", N=30)
        t = p.task("T", i=ptg.span(0, lambda g, l: g.N - 1))
        f = t.flow("ctl", ptg.CTL)   # chain: one task per wavefront, so
        f.input(pred=("T", "ctl", lambda g, l: {"i": l.i - 1}),
                guard=lambda g, l: l.i > 0)   # the per-batch deadline bites
        f.output(succ=("T", "ctl", lambda g, l: {"i": l.i + 1}),
                 guard=lambda g, l: l.i < g.N - 1)
        t.body(lambda es, task, g, l: _t.sleep(0.01))
        ctx = Context(nb_cores=0)
        ctx.add_taskpool(p.build())
        with pytest.raises(TimeoutError):
            ctx.wait(timeout=0.05)
        ctx.wait(timeout=30)   # resumes and finishes
        ctx.fini()

    def test_body_exception_does_not_wedge_fini(self):
        p = ptg.PTGBuilder("boom", N=3)
        t = p.task("T", i=ptg.span(0, lambda g, l: g.N - 1))
        t.flow("ctl", ptg.CTL)

        def body(es, task, g, l):
            raise ValueError("body failure")
        t.body(body)

        ctx = Context(nb_cores=0)
        ctx.add_taskpool(p.build())
        with pytest.raises(ValueError):
            ctx.wait(timeout=30)
        ctx.fini()   # must not hang on the aborted pool


class TestFallbacks:
    def test_device_chore_falls_back_to_dynamic(self):
        p = ptg.PTGBuilder("dev", N=2)
        t = p.task("T", i=ptg.span(0, lambda g, l: g.N - 1))
        t.flow("ctl", ptg.CTL)
        t.body(lambda es, task, g, l: None)
        t.body(device="tpu", dyld="nonexistent_kernel")
        tp = p.build()
        ctx = Context(nb_cores=0)
        assert compile_taskpool_dag(tp, ctx) is None
        ctx.fini()

    def test_multirank_falls_back(self):
        tp = ep_pool()
        ctx = Context(nb_cores=0)
        ctx.nb_ranks = 2   # simulate distributed: release must route remote
        assert compile_taskpool_dag(tp, ctx) is None
        ctx.nb_ranks = 1
        ctx.fini()

    def test_pins_active_still_compiles_and_fires_events(self):
        """Round-4 contract flip: PINS no longer forces the dynamic
        fallback — the fast path compiles AND emits per-task EXEC plus
        batch-granular DAG_FETCH/DAG_COMPLETE events."""
        from parsec_tpu.prof import pins
        execs, batches = [], []
        cb_e = lambda es, t: execs.append(t.uid)
        cb_b = lambda es, n: batches.append(n)
        pins.register(pins.PinsEvent.EXEC_BEGIN, cb_e)
        pins.register(pins.PinsEvent.DAG_COMPLETE_END, cb_b)
        try:
            tp = ep_pool()
            ctx = Context(nb_cores=0)
            assert compile_taskpool_dag(tp, ctx) is not None
            ctx.fini()
            run_pool(ep_pool(), nb_cores=0)
        finally:
            pins.unregister(pins.PinsEvent.EXEC_BEGIN, cb_e)
            pins.unregister(pins.PinsEvent.DAG_COMPLETE_END, cb_b)
        assert sorted(execs) == list(range(8 * 5))   # every task observed
        assert batches and sum(batches) == 8 * 5     # batch sizes accounted

    def test_param_gate(self, dynamic_only):
        tp = ep_pool()
        ctx = Context(nb_cores=0)
        assert compile_taskpool_dag(tp, ctx) is None
        ctx.fini()
