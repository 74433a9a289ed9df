"""The drive loop: every taskpool goes select -> execute -> release through
the scheduler module, on worker threads or inline from ``wait()``.

What these hold: every task runs once and after its predecessors, data
chains give their answer, the hook protocol (AGAIN, an exception, a
deadline) leaves the context usable, the CPU device counts the bodies it
runs, and an input flow fed by several deps from one class takes each
arrival on the dep that names its sender (the JDF of Ex07_RAW_CTL).
"""

import time

import numpy as np
import pytest

from parsec_tpu import ptg
from parsec_tpu.data.data import TileType
from parsec_tpu.data_dist.collection import DictCollection
from parsec_tpu.runtime import Context
from parsec_tpu.runtime.task import HOOK_RETURN_AGAIN


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


def chain_pool(coll, n=6):
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


def run_pool(tp, **ctx_kw):
    ctx = Context(**ctx_kw)
    ctx.add_taskpool(tp)
    ctx.wait(timeout=60)
    ctx.fini()
    return ctx


# ---------------------------------------------------------------------------
# every task once, in order
# ---------------------------------------------------------------------------

def test_ep_executes_every_task_once():
    trace = []
    run_pool(ep_pool(trace=trace), nb_cores=0)
    assert sorted(trace) == [(d, n) for d in range(5) for n in range(8)]


def test_dependency_order_respected():
    trace = []
    run_pool(ep_pool(trace=trace), nb_cores=0)
    pos = {t: i for i, t in enumerate(trace)}
    for d in range(1, 5):
        for n in range(8):
            assert pos[(d - 1, n)] < pos[(d, n)], \
                f"EP({d},{n}) ran before its predecessor"


def test_threaded_context_runs_every_task():
    trace = []
    run_pool(ep_pool(trace=trace), nb_cores=2)
    assert sorted(trace) == [(d, n) for d in range(5) for n in range(8)]


def test_the_dispatch_shape_goes_through_the_scheduler(param, monkeypatch):
    """2,000 tasks of the EP shape (50 lanes x 40): each is handed to the
    scheduler module once and selected from it once (the keep-hot slot,
    which passes the module by, is off), and each runs once."""
    param("runtime_keep_highest_priority_task", False)
    trace = []
    tp = ep_pool(50, 40, trace)
    ctx = Context(nb_cores=0)
    handed, selected = [], []
    mod = type(ctx.scheduler)
    schedule, select = mod.schedule, mod.select

    def counted_schedule(self, es, tasks, distance=0):
        handed.extend(t.uid for t in tasks)
        return schedule(self, es, tasks, distance)

    def counted_select(self, es):
        t, distance = select(self, es)
        if t is not None:
            selected.append(t.uid)
        return t, distance

    monkeypatch.setattr(mod, "schedule", counted_schedule)
    monkeypatch.setattr(mod, "select", counted_select)
    ctx.add_taskpool(tp)
    ctx.wait(timeout=60)
    ctx.fini()
    assert len(trace) == 2000 == len(set(trace))
    assert len(handed) == len(set(handed)) == 2000
    assert sorted(selected) == sorted(handed)


def test_cpu_bodies_are_counted_by_the_cpu_device():
    """``execute_task`` notes each host body on the CPU device, to the
    task: the accounting a run's ``tasks_off`` (tasks that did not run on
    an accelerator) is read from."""
    from parsec_tpu.device.device import cpu_device
    before = cpu_device.executed_tasks
    run_pool(ep_pool(12, 7), nb_cores=0)
    assert cpu_device.executed_tasks - before == 12 * 7


def test_pins_exec_fires_once_for_every_task():
    from parsec_tpu.prof import pins
    execs = []
    cb = lambda es, t: execs.append(t.uid)
    pins.register(pins.PinsEvent.EXEC_BEGIN, cb)
    try:
        tp = ep_pool()
        run_pool(tp, nb_cores=0)
    finally:
        pins.unregister(pins.PinsEvent.EXEC_BEGIN, cb)
    assert len(execs) == len(set(execs)) == 8 * 5


# ---------------------------------------------------------------------------
# shapes
# ---------------------------------------------------------------------------

def test_data_chain_result():
    coll = DictCollection("A", dtt=TileType((2,), np.float32),
                          init_fn=lambda *k: np.zeros(2, np.float32))
    run_pool(chain_pool(coll), nb_cores=0)
    assert coll.data_of(0).newest_copy().value[0] == 6


def test_priority_pool_runs():
    seen = []
    p = ptg.PTGBuilder("prio", N=4)
    t = p.task("T", i=ptg.span(0, lambda g, l: g.N - 1))
    t.flow("ctl", ptg.CTL).output(
        succ=("U", "ctl", lambda g, l: {"i": l.i}))
    t.priority(lambda g, l: l.i)
    t.body(lambda es, task, g, l: seen.append(("T", l.i)))
    u = p.task("U", i=ptg.span(0, lambda g, l: g.N - 1))
    u.flow("ctl", ptg.CTL).input(
        pred=("T", "ctl", lambda g, l: {"i": l.i}))
    u.body(lambda es, task, g, l: seen.append(("U", l.i)))
    run_pool(p.build(), nb_cores=0)
    assert sorted(seen) == [(c, i) for c in "TU" for i in range(4)]
    for i in range(4):
        assert seen.index(("T", i)) < seen.index(("U", i))


def test_triangular_space_runs_every_task():
    """Dependent ranges (l.i bound in l.j's range)."""
    seen = []
    p = ptg.PTGBuilder("tri", N=5)
    t = p.task("T", i=ptg.span(0, lambda g, l: g.N - 1),
               j=ptg.span(0, lambda g, l: l.i))
    t.flow("ctl", ptg.CTL)
    t.body(lambda es, task, g, l: seen.append((l.i, l.j)))
    run_pool(p.build(), nb_cores=0)
    assert sorted(seen) == [(i, j) for i in range(5) for j in range(i + 1)]


def _several_deps_pool(order, variant, nreaders=4, ndeps=4):
    """Ex07_RAW_CTL's shape: ``Bcast`` feeds ``Recv(r)`` and ``Update``;
    ``Update.ctl`` has one unguarded input dep from each ``Recv(r)``, told
    apart only by the predecessor's params.  ``variant`` picks how the
    release walk reaches ``Update``: by the class's plan, or per edge (a
    successor with its own key function, a simulated pool)."""
    coll = DictCollection("M", dtt=TileType((1,), np.float32),
                          init_fn=lambda *k: np.zeros(1, np.float32))
    p = ptg.PTGBuilder("rawctl", M=coll, NR=nreaders)
    w = p.task("Bcast", k=ptg.span(0, 0))
    fw = w.flow("A", ptg.RW)
    fw.input(data=("M", lambda g, l: (0,)))
    fw.output(succ=("Update", "A", lambda g, l: {"k": 0}))
    fw.output(succ=("Recv", "A", lambda g, l: [{"r": r}
                                                for r in range(g.NR)]))

    @w.body
    def wbody(es, task, g, l):
        task.flow_data("A").value = np.full(1, 7.0, np.float32)

    t = p.task("Recv", r=ptg.span(0, lambda g, l: g.NR - 1))
    t.flow("A", ptg.READ).input(pred=("Bcast", "A", lambda g, l: {"k": 0}))
    t.flow("ctl", ptg.CTL).output(
        succ=("Update", "ctl", lambda g, l: {"k": 0}))
    t.body(lambda es, task, g, l: order.append(("read", l.r)))

    u = p.task("Update", k=ptg.span(0, 0))
    fu = u.flow("A", ptg.RW)
    fu.input(pred=("Bcast", "A", lambda g, l: {"k": 0}))
    fu.output(data=("M", lambda g, l: (0,)))
    fc = u.flow("ctl", ptg.CTL)
    for r in range(ndeps):
        fc.input(pred=("Recv", "ctl", lambda g, l, r=r: {"r": r}))
    if variant == "keyed":
        u.make_key(lambda g, l: ("update", l.k))
    if variant == "simulated":
        u.simcost(lambda g, l: 1.0)

    @u.body
    def ubody(es, task, g, l):
        order.append(("update",))
        a = task.flow_data("A")
        a.value = np.asarray(a.value) * 100

    return p.build(), coll


@pytest.mark.parametrize("variant", ["planned", "keyed", "simulated"])
def test_several_deps_from_one_class_take_the_one_naming_the_sender(
        variant):
    order = []
    tp, coll = _several_deps_pool(order, variant)
    ctx = run_pool(tp, nb_cores=0)
    assert order[-1] == ("update",), order
    assert sorted(order[:-1]) == [("read", r) for r in range(4)]
    assert float(coll.data_of(0).newest_copy().value[0]) == 700.0
    # Bcast's five edges and the four readers' edges to Update; a keyed
    # Update takes its five per edge, a simulated pool all nine
    assert ctx.release_edges == 9
    assert ctx.release_edges_planned == {"planned": 9, "keyed": 4,
                                         "simulated": 0}[variant]


@pytest.mark.parametrize("variant", ["planned", "keyed"])
def test_an_arrival_no_dep_names_is_refused(variant):
    """A fifth reader's edge to an ``Update`` that waits for four: no input
    dep names the sender, so the release raises instead of setting another
    reader's bit."""
    tp, _ = _several_deps_pool([], variant, nreaders=5, ndeps=4)
    ctx = Context(nb_cores=0)
    ctx.add_taskpool(tp)
    with pytest.raises(LookupError, match="no active input dep from Recv"):
        ctx.wait(timeout=30)
    ctx.fini()


# ---------------------------------------------------------------------------
# the hook protocol and the wait
# ---------------------------------------------------------------------------

def test_again_is_retried():
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
    assert attempts == {i: 3 for i in range(6)}


def test_again_in_a_wide_wavefront():
    """One AGAIN among 2,200 ready tasks runs again once, and only it."""
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


def test_wait_timeout_leaves_pool_resumable():
    p = ptg.PTGBuilder("slow", N=30)
    t = p.task("T", i=ptg.span(0, lambda g, l: g.N - 1))
    f = t.flow("ctl", ptg.CTL)   # a chain: one task ready at a time
    f.input(pred=("T", "ctl", lambda g, l: {"i": l.i - 1}),
            guard=lambda g, l: l.i > 0)
    f.output(succ=("T", "ctl", lambda g, l: {"i": l.i + 1}),
             guard=lambda g, l: l.i < g.N - 1)
    t.body(lambda es, task, g, l: time.sleep(0.01))
    ctx = Context(nb_cores=0)
    ctx.add_taskpool(p.build())
    with pytest.raises(TimeoutError):
        ctx.wait(timeout=0.05)
    ctx.wait(timeout=30)   # resumes and finishes
    ctx.fini()


def _failing_pool():
    p = ptg.PTGBuilder("boom", N=3)
    t = p.task("T", i=ptg.span(0, lambda g, l: g.N - 1))
    t.flow("ctl", ptg.CTL)

    def body(es, task, g, l):
        raise ValueError("body failure")
    t.body(body)
    return p.build()


def test_body_exception_does_not_wedge_fini():
    ctx = Context(nb_cores=0)
    ctx.add_taskpool(_failing_pool())
    with pytest.raises(ValueError):
        ctx.wait(timeout=30)
    ctx.fini()   # must not hang on the aborted pool


def test_threaded_wait_surfaces_a_body_exception():
    """A worker thread's failure wakes the waiter, which raises it as the
    cause; ``fini()`` then tears down without draining the dead pool and
    without raising it a second time."""
    ctx = Context(nb_cores=2)
    ctx.add_taskpool(_failing_pool())
    with pytest.raises(RuntimeError) as exc:
        ctx.wait(timeout=30)
    assert isinstance(exc.value.__cause__, ValueError)
    ctx.fini()
    assert not any(t.is_alive() for t in ctx._threads)


def test_pool_added_from_a_body_completes_in_the_same_wait():
    """On an ``nb_cores=0`` context a body enqueues a second pool; the
    ``wait()`` that is running the body drives that pool to its end too."""
    inner_trace = []
    inner = ep_pool(4, 3, inner_trace)
    p = ptg.PTGBuilder("outer", N=1)
    t = p.task("T", i=ptg.span(0, 0))
    t.flow("ctl", ptg.CTL)
    t.body(lambda es, task, g, l: es.context.add_taskpool(inner))

    ctx = Context(nb_cores=0)
    ctx.add_taskpool(p.build())
    ctx.wait(timeout=60)
    assert sorted(inner_trace) == [(d, n) for d in range(3) for n in range(4)]
    assert ctx.test(inner)
    ctx.fini()


def test_a_pool_is_found_by_comm_id_only_once_its_tasks_are_counted():
    """An activation looks its pool up by comm id (``_tp_by_comm_id``); one
    found before ``add_taskpool`` counted the pool's tasks would complete a
    task on a zero counter (``nb_tasks went negative`` on one rank, every
    other rank left at the barrier).  Until then it is not found, and the
    comm engine replays what arrived at ``taskpool_registered``."""
    tp = ep_pool(4, 3)
    ctx = Context(nb_cores=0)
    seen = []
    count = tp.nb_local_tasks

    def counted():
        seen.append(ctx._tp_by_comm_id.get(tp.comm_id))
        return count()

    tp.nb_local_tasks = counted
    ctx.add_taskpool(tp)
    assert seen == [None]
    assert ctx._tp_by_comm_id[tp.comm_id] is tp
    ctx.wait(timeout=60)
    ctx.fini()

