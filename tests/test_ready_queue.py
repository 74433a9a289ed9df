"""The default scheduler's ready queue (ISSUE 30): priority heaps bucketed by
task class (``core/hbbuffer.py:ReadyQueue``), ``SchedulerModule.select_class``
and the counts of the device module's flood on the CPU stand-in.

The queue cases drive the real ``LFQModule`` on bare streams (no Context);
the count gate runs the benchmark cells' DAGs at 8 x 8 tiles through
``Context(nb_cores=0)`` with the CPU device wrapped as an accelerator: exact
numbers, no clock.
"""

import threading

import numpy as np
import pytest

from parsec_tpu.core.hbbuffer import ReadyQueue
from parsec_tpu.runtime import Context
from parsec_tpu.runtime.scheduling import ExecutionStream, VirtualProcess
from parsec_tpu.sched.modules import APModule, GDModule, LFQModule


class _T:
    """What a scheduler reads of a task."""
    __slots__ = ("task_class", "priority", "name")

    def __init__(self, task_class, priority, name):
        self.task_class, self.priority, self.name = task_class, priority, name

    def __repr__(self):
        return f"{self.task_class}:{self.name}"


def _tasks(spec):
    """``"a3 b0 a1"`` -> tasks of class ``a``/``b`` with those priorities,
    named by position."""
    return [_T(w[0], int(w[1:]), i) for i, w in enumerate(spec.split())]


def _module(mod_cls, nstreams=1):
    class _Ctx:
        virtual_processes: list = []

    ctx = _Ctx()
    vp = VirtualProcess(0, ctx)
    ctx.virtual_processes = [vp]
    vp.execution_streams = [ExecutionStream(i, vp, ctx)
                            for i in range(nstreams)]
    mod = mod_cls()
    mod.install(ctx)
    for es in vp.execution_streams:
        mod.flow_init(es)
    return mod, ctx, vp.execution_streams


def _drain(mod, es):
    out = []
    while True:
        t, d = mod.select(es)
        if t is None:
            return out
        out.append((t, d))


def _names(pairs):
    return [t.name for t, _ in pairs]


_CASES = []


def case(*variants):
    """Register a function as one case of ``test_ready_queue`` for each tuple
    of arguments in ``variants`` (or once, without arguments)."""
    def add(fn):
        for args in variants or [()]:
            _CASES.append(pytest.param(fn, args, id="-".join(
                [fn.__name__] + [getattr(a, "name", str(a)) for a in args])))
        return fn
    return add


@case()
def priority_order_oldest_first_among_equals():
    mod, _, (es,) = _module(LFQModule)
    ts = _tasks("a1 b5 a5 c1 b9 a5 c0")
    mod.schedule(es, ts[:4])
    mod.schedule(es, ts[4:])
    assert _names(_drain(mod, es)) == [4, 1, 2, 5, 0, 3, 6]


@case()
def newest_first_until_a_priority_then_the_flip_is_one_way():
    mod, _, (es,) = _module(LFQModule)
    q = es.sched_private
    mod.schedule(es, _tasks("a0 b0 a0 b0"))
    assert mod.select(es)[0].name == 3 and not q._prio
    late = _tasks("a0 b2 a0")
    mod.schedule(es, late)              # b2 flips the queue, for good
    assert q._prio
    # best priority, then the oldest of what is left: arrival order
    got = _drain(mod, es)
    assert [(t.task_class, t.name) for t, _ in got] == [
        ("b", 1), ("a", 0), ("b", 1), ("a", 2), ("a", 0), ("a", 2)]
    assert got[0][0] is late[1]
    mod.schedule(es, _tasks("a0 a0"))
    assert q._prio and _names(_drain(mod, es)) == [0, 1]


@case()
def the_system_queue_is_fifo_whatever_the_priorities():
    mod, _, (es,) = _module(LFQModule)
    mod.schedule(es, _tasks("a1 b9 a5"), 1)     # distance > 0: system
    assert len(es.sched_private) == 0
    assert [(t.name, d) for t, d in _drain(mod, es)] == [
        (0, 99), (1, 99), (2, 99)]


@case()
def select_class_takes_only_its_class_best_first_at_most_want():
    mod, ctx, (es,) = _module(LFQModule)
    ts = _tasks("a1 b7 a9 c3 a9 b2 a4")
    mod.schedule(es, ts)
    mod.schedule(es, _tasks("a8 b8"), 1)            # two in the system queue
    taken, put_back = mod.select_class(es, "a", 3)
    assert put_back == 0
    assert [(t.name, d) for t, d in taken] == [(2, 0), (4, 0), (6, 0)]
    assert mod.pending_tasks(ctx) == 6
    # past the local bucket it goes on into the system queue's
    taken, put_back = mod.select_class(es, "a", 5)
    assert put_back == 0
    assert [(t.name, d) for t, d in taken] == [(0, 0), (0, 99)]
    assert mod.select_class(es, "a", 5) == ([], 0)
    assert mod.select_class(es, "b", 0) == ([], 0)
    # every other task is where it was, in the order it had
    assert [(t.task_class, t.name, d) for t, d in _drain(mod, es)] == [
        ("b", 1, 0), ("c", 3, 0), ("b", 5, 0), ("b", 1, 99)]


@case()
def select_class_newest_first_while_no_priority_was_seen():
    mod, _, (es,) = _module(LFQModule)
    mod.schedule(es, _tasks("a0 b0 a0 a0 b0"))
    taken, _ = mod.select_class(es, "a", 2)
    assert _names(taken) == [3, 2]
    assert _names(_drain(mod, es)) == [4, 1, 0]


@case()
def select_class_reaches_a_sibling_stream():
    mod, _, (es0, es1) = _module(LFQModule, nstreams=2)
    mod.schedule(es0, _tasks("a1 b1 a2"))
    taken, put_back = mod.select_class(es1, "a", 8)
    assert put_back == 0
    assert [(t.name, d) for t, d in taken] == [(2, 1), (0, 1)]
    assert [(t.name, d) for t, d in _drain(mod, es1)] == [(1, 1)]


@case()
def past_the_bound_a_release_spills_in_arrival_order():
    mod, ctx, (es,) = _module(LFQModule)
    cap = mod._cap
    ts = [_T("a", i % 7, i) for i in range(cap + 40)]
    mod.schedule(es, ts)
    assert len(es.sched_private) == cap
    assert len(ctx.virtual_processes[0].sched_private.system) == 40
    got = _drain(mod, es)
    local = sorted(ts[:cap], key=lambda t: (-t.priority, t.name))
    assert [t for t, _ in got] == local + ts[cap:]
    assert {d for _, d in got[:cap]} == {0} and {d for _, d in got[cap:]} == {99}


@case((0,), (3,))
def two_pushers_a_thief_and_the_spill_deliver_each_task_once(prio):
    mod, ctx, (es0, es1) = _module(LFQModule, nstreams=2)
    n = 4000                            # far past the bound: both queues fill
    mine = [[_T("ab"[i % 2], prio * (i % 5), (w, i)) for i in range(n)]
            for w in range(2)]
    got = [[], []]
    stop = threading.Event()
    thief_took = threading.Event()

    def push(w):
        for i in range(0, n, 50):
            mod.schedule(es0, mine[w][i:i + 50])

    def pop(es, out):
        if es is es0:
            # the owner pops once the thief holds a task, stolen or
            # spilled: else a late-starting thief may find nothing left
            thief_took.wait(timeout=60)
        while not stop.is_set() or mod.pending_tasks(ctx):
            t, _ = mod.select(es)
            if t is not None:
                out.append(t)
            if len(out) % 7 == 0:       # the flood's pop, in between
                out += [t for t, _ in mod.select_class(es, "a", 3)[0]]
            if out and es is es1:
                thief_took.set()

    threads = [threading.Thread(target=push, args=(w,)) for w in range(2)]
    poppers = [threading.Thread(target=pop, args=(es, got[i]))
               for i, es in enumerate((es0, es1))]
    for th in threads + poppers:
        th.start()
    for th in threads:
        th.join()
    stop.set()
    for th in poppers:
        th.join(timeout=60)
        assert not th.is_alive()
    names = [t.name for t in got[0] + got[1]]
    assert len(names) == 2 * n == len(set(names))
    assert got[1], "the sibling stream never stole or took a spilled task"
    assert mod.pending_tasks(ctx) == 0
    assert es0.sched_private._prio == bool(prio)


@case((APModule,), (GDModule,))
def the_default_select_class_serves_a_module_without_buckets(mod_cls):
    mod, ctx, (es,) = _module(mod_cls)
    mod.schedule(es, _tasks("a1 b7 a9 c3 a9 b2 a4"))
    taken, put_back = mod.select_class(es, "a", 3)
    assert {t.task_class for t, _ in taken} == {"a"} and len(taken) == 3
    if mod_cls is APModule:             # best first, oldest among equals
        assert _names(taken) == [2, 4, 6] and put_back == 1     # b7
    else:                               # gd: arrival order
        assert _names(taken) == [0, 2, 4] and put_back == 2     # b7 and c3
    # what it popped on the way went back, and nothing else moved
    assert mod.pending_tasks(ctx) == 4
    taken, put_back = mod.select_class(es, "a", 8)
    assert len(taken) == 1 and put_back == 3       # drained, all handed back
    rest = _drain(mod, es)
    assert sorted(t.name for t, _ in rest) == [1, 3, 5]
    assert mod.select_class(es, "a", 8) == ([], 0)


@case()
def two_hundred_pushed_the_owner_pops_newest_the_thief_steals_oldest():
    mod, _, (es0, es1) = _module(LFQModule, nstreams=2)
    ts = [_T("ab"[i % 2], 0, i) for i in range(200)]
    mod.schedule(es0, ts)
    q = es0.sched_private
    assert len(q) == 200 and len(es1.sched_private) == 0
    assert [(t.name, d) for t, d in (mod.select(es0), mod.select(es1))] == [
        (199, 0), (0, 1)]
    # a class pop reads its own bucket and no other
    others = q._buckets["a"]
    before = list(others)
    assert _names(mod.select_class(es0, "b", 3)[0]) == [197, 195, 193]
    assert q._buckets["a"] is others and list(others) == before
    assert len(q) == 195
    assert _names(_drain(mod, es1))[:3] == [1, 2, 3]    # oldest first


@case()
def a_bare_queue_never_compares_tasks_and_counts_itself():
    q = ReadyQueue()
    ts = _tasks("a2 a2 b2 b2")
    q.push_all(ts)
    assert len(q) == 4 and q.steal() is ts[0] and q.pop() is ts[1]
    assert q.pop_class("b", 9) == ts[2:] and len(q) == 0
    assert q.pop() is None and q.steal() is None and q.pop_class("a", 1) == []
    assert not q._buckets                # a drained class leaves no bucket


@pytest.mark.parametrize("case,args", _CASES)
def test_ready_queue(case, args):
    case(*args)


# --------------------------------------------------------------------------
# the flood's counts on the device path
# --------------------------------------------------------------------------

def _gemm(p, nb=8):
    from parsec_tpu.data_dist.matrix import TiledMatrix
    from parsec_tpu.models.tiled_gemm import tiled_gemm_ptg
    n = p * nb
    rng = np.random.default_rng(30)
    a = rng.standard_normal((n, n)).astype(np.float32)
    b = rng.standard_normal((n, n)).astype(np.float32)
    A, B = (TiledMatrix.from_dense(k, v, nb, nb) for k, v in
            (("A", a), ("B", b)))
    C = TiledMatrix.from_dense("C", np.zeros((n, n), np.float32), nb, nb)
    return (tiled_gemm_ptg(A, B, C, devices="tpu"), p ** 3,
            lambda: (C.to_dense(), a @ b))


def _potrf(p, nb=8):
    from parsec_tpu.data_dist.matrix import SymTwoDimBlockCyclic
    from parsec_tpu.models.cholesky import make_spd, tiled_cholesky_ptg
    a = make_spd(p * nb)
    A = SymTwoDimBlockCyclic.from_dense("A", a, nb, nb)
    ntasks = p + p * (p - 1) + p * (p - 1) * (p - 2) // 6
    return (tiled_cholesky_ptg(A, devices="tpu"), ntasks,
            lambda: (np.tril(A.to_dense()), np.linalg.cholesky(
                a.astype(np.float64)).astype(np.float32)))


def _solve_counted(dev, param, monkeypatch, sched, make, p):
    """One solve under ``sched`` on ``dev`` (one accelerator or several);
    what the scheduler took in and handed out and what reached a device by
    the hot loop."""
    from parsec_tpu.sched.api import SchedulerModule
    devs = dev if isinstance(dev, tuple) else (dev,)
    param("sched", sched)
    tp, ntasks, result = make(p)
    ctx = Context(nb_cores=0)
    mod = type(ctx.scheduler)
    assert mod.name == sched
    assert (mod.select_class is SchedulerModule.select_class) == (sched != "lfq")
    n = {"pushed": 0, "popped": 0, "hot": 0}

    def counting(fn, key, count):
        def wrapped(*a, **kw):
            out = fn(*a, **kw)
            n[key] += count(a, out)
            return out
        return wrapped

    monkeypatch.setattr(mod, "schedule", counting(
        mod.schedule, "pushed", lambda a, out: len(a[2])))
    monkeypatch.setattr(mod, "select", counting(
        mod.select, "popped", lambda a, out: out[0] is not None))
    if sched == "lfq":      # the default's pops are its selects, counted above
        monkeypatch.setattr(mod, "select_class", counting(
            mod.select_class, "popped", lambda a, out: len(out[0])))
    for d in devs:
        d.kernel_scheduler = counting(
            d.kernel_scheduler, "hot", lambda a, out: 1)
    ctx.add_taskpool(tp)
    ctx.wait(timeout=600)
    for d in devs:
        d.sync()
        d.flush_cache()
    ctx.fini()
    got, expect = result()
    np.testing.assert_allclose(got, expect, rtol=1e-3, atol=1e-4)
    # each once: the result says so too
    assert sum(d.executed_tasks for d in devs) == ntasks
    assert sum(d.flood_selected for d in devs) + n["hot"] == ntasks
    return n


@pytest.mark.parametrize("make,p,ntasks,calls", [
    (_potrf, 32, 5984, 308), (_gemm, 16, 4096, 64), (_potrf, 16, 816, 72)],
    ids=["potrf32", "gemm16", "potrf16"])
def test_the_flood_pops_one_task_for_each_it_runs(
        accel_device, param, monkeypatch, make, p, ntasks, calls):
    dev = accel_device
    n = _solve_counted(dev, param, monkeypatch, "lfq", make, p)
    assert dev.executed_tasks == ntasks
    assert dev.flood_putbacks == 0
    # nothing left the scheduler twice; what never entered it ran from the
    # stream's keep-hot slot
    assert n["popped"] == n["pushed"] <= ntasks
    # the batches today's order gives (the bound and the FIFO spill held)
    assert dev.xla_calls == calls


@pytest.mark.parametrize("sched,p,ntasks", [("gd", 32, 5984)] + [
    (s, 16, 816) for s in ("ap", "spq", "ip", "rnd", "ll", "llp", "pbq",
                           "ltq", "lhq")])
def test_a_scheduler_without_buckets_still_floods_and_counts_its_put_backs(
        accel_device, param, monkeypatch, sched, p, ntasks):
    dev = accel_device
    n = _solve_counted(dev, param, monkeypatch, sched, _potrf, p)
    assert dev.executed_tasks == ntasks
    assert dev.flood_putbacks > 0
    # every put-back is one more push and one more pop of the same task
    assert n["popped"] == n["pushed"]
    assert n["popped"] - dev.flood_putbacks <= ntasks


@pytest.mark.parametrize("make,p,tiles_in,tiles_out,hits,reads", [
    (_gemm, 16, 3 * 256, 256, 8197, 3 * 4096),
    (_potrf, 16, 136, 136, 2040, 16 + 2 * 120 + 2 * 120 + 3 * 560),
    (_potrf, 32, 528, 528, 16368, 32 + 2 * 496 + 2 * 496 + 3 * 4960)],
    ids=["gemm16", "potrf16", "potrf32"])
def test_a_solve_stages_each_tile_once_and_pushes_each_result_out_once(
        accel_device, device_registry, param, monkeypatch, make, p,
        tiles_in, tiles_out, hits, reads):
    """What a solve of a cell's graph moves (8 x 8 f32 tiles, 256 bytes
    each): every input tile staged once (C's zeros too), the reads that hit
    the device's cache, every result tile pushed out at its memory edge
    and collected once by the flush, nothing evicted, no dispatch confirmed
    early for room, and no task on the host CPU device."""
    dev = accel_device
    (host,) = [d for d in device_registry.devices if d.type == "cpu"]
    host_tasks = host.executed_tasks
    _solve_counted(dev, param, monkeypatch, "lfq", make, p)
    assert dev.bytes_in == tiles_in * 256
    # every read of a flow is one look-up in the device's cache
    assert (dev.cache_hits, dev.cache_hits + dev.cache_misses) == (hits, reads)
    assert dev.pushouts == dev.writebacks == dev.writebacks_early == tiles_out
    assert dev.bytes_out == tiles_out * 256
    assert (dev.evicted_bytes, dev.pressure_confirms, dev.evict_stuck) == (
        0, 0, 0)
    assert host.executed_tasks == host_tasks


@pytest.mark.parametrize("make,p,completions,edges", [
    (_gemm, 16, 3840, 3840), (_potrf, 16, 815, 2040),
    (_potrf, 32, 5983, 16368)], ids=["gemm16", "potrf16", "potrf32"])
def test_a_release_walks_each_edge_of_the_dag_once(
        accel_device, param, monkeypatch, make, p, completions, edges):
    """What ``sched.release`` does per cell graph (ROADMAP S2's yardstick):
    every task with a successor is one ``release_many`` call, and the
    records of all calls are the DAG's edges, each once (the flow reads that
    no collection serves: the look-ups that hit in the test above)."""
    from parsec_tpu.runtime.deps import DependencyTracking
    n = {"calls": 0, "records": 0}
    release_many = DependencyTracking.release_many

    def counted(self, tp, records):
        n["calls"] += 1
        n["records"] += len(records)
        return release_many(self, tp, records)

    monkeypatch.setattr(DependencyTracking, "release_many", counted)
    _solve_counted(accel_device, param, monkeypatch, "lfq", make, p)
    assert (n["calls"], n["records"]) == (completions, edges)


@pytest.mark.parametrize("make,p,ntasks,completions,edges", [
    (_gemm, 16, 4096, 3840, 3840), (_potrf, 16, 816, 815, 2040),
    (_potrf, 32, 5984, 5983, 16368)], ids=["gemm16", "potrf16", "potrf32"])
def test_a_release_goes_by_plan_and_asks_each_successor_once(
        accel_device, param, monkeypatch, make, p, ntasks, completions,
        edges):
    """What the release plan hoists, per cell graph: every edge handed to a
    successor went through a resolved plan; a successor's required mask is
    evaluated when its tracker is created, once a successor *task* and not
    once an arrival; and a completion builds one namespace of the
    completing task's locals and one of each successor's (16 a task before
    the plan), which every guard, range, mask and priority then shares."""
    from parsec_tpu.ptg import dsl
    from parsec_tpu.runtime import scheduling
    from parsec_tpu.runtime.deps import DependencyTracking
    from parsec_tpu.runtime.task import TaskClass
    n = {"masks": 0, "namespaces": 0, "releasing": 0}
    release_many = DependencyTracking.release_many
    input_dep_mask = TaskClass.input_dep_mask
    complete_execution = scheduling.complete_execution

    def releasing(fn):
        def wrapped(*a, **kw):
            n["releasing"] += 1
            try:
                return fn(*a, **kw)
            finally:
                n["releasing"] -= 1
        return wrapped

    def counted_mask(self, locals_):
        n["masks"] += bool(n["releasing"])   # the start-up asks too
        return input_dep_mask(self, locals_)

    class CountedNS(dsl._NS):
        def __init__(self, **kw):
            n["namespaces"] += bool(n["releasing"])
            super().__init__(**kw)

    monkeypatch.setattr(DependencyTracking, "release_many",
                        releasing(release_many))
    monkeypatch.setattr(scheduling, "complete_execution",
                        releasing(complete_execution))
    monkeypatch.setattr(TaskClass, "input_dep_mask", counted_mask)
    monkeypatch.setattr(dsl, "_NS", CountedNS)
    before = dict(scheduling.release_totals)
    _solve_counted(accel_device, param, monkeypatch, "lfq", make, p)
    # the context's counters, as its teardown adds them to the process's
    assert (scheduling.release_totals["edges"] - before["edges"],
            scheduling.release_totals["planned"] - before["planned"]) == (
        edges, edges)
    assert n["masks"] == completions
    assert n["namespaces"] <= ntasks + edges


@pytest.mark.parametrize("fault,caught", [
    ("dep_index", "not one the task waits for|are not ones the task waits"
                  "|satisfied twice"),
    ("flow_index", "Not equal to tolerance")])
def test_a_plan_that_resolves_the_wrong_input_is_caught(
        accel_device, param, monkeypatch, fault, caught):
    """Planted faults in the release plan of the 16-panel Cholesky: a
    candidate that names the input dep before its own sets a bit the
    successor does not wait for or one another edge sets too, which the
    dep-bit assertions of whichever tier holds the tracker refuse; GEMM's A and B operands resolved to
    each other's flow arrive, make the task ready and give a wrong factor,
    which the solve's answer shows."""
    from parsec_tpu.runtime import scheduling
    plan_edge = scheduling._plan_edge

    def faulty(tp, tc, flow, dep):
        ep = plan_edge(tp, tc, flow, dep)
        if fault == "dep_index":
            # the bit of the input dep declared before the one resolved
            ep.cands = tuple((bit >> 1 or bit, g) for bit, g in ep.cands)
        if fault == "flow_index" and dep.target_class == "GEMM" \
                and dep.target_flow in ("A", "B"):
            other = 1 - ep.succ_fi          # flows A and B are 0 and 1
            ep.succ_fi = other
            ep.cands = tuple((1 << ep.succ_tc.dep_bit(other, 0), g)
                             for _, g in ep.cands)
        return ep

    # an assertion that fails inside a device batch's completions is
    # reported by the module, which demotes itself; the solve then dies for
    # want of a device
    import logging
    import re
    from parsec_tpu.core.output import debug_stream
    said = []
    handler = logging.Handler()
    handler.emit = lambda record: said.append(record.getMessage())
    debug_stream._log.addHandler(handler)
    monkeypatch.setattr(scheduling, "_plan_edge", faulty)
    try:
        with pytest.raises(Exception) as exc:
            _solve_counted(accel_device, param, monkeypatch, "lfq", _potrf,
                           16)
    finally:
        debug_stream._log.removeHandler(handler)
    said.append(str(exc.value))
    assert re.search(caught, "\n".join(said)), said


@pytest.mark.parametrize("make,p,ntasks,calls", [
    (_potrf, 16, 816, 227), (_gemm, 8, 512, 64)], ids=["potrf16", "gemm8"])
def test_a_batch_never_passes_device_tpu_batch_max(
        accel_device, param, monkeypatch, make, p, ntasks, calls):
    """Under a cap of 8 the regular graph runs in exactly ntasks / 8 calls
    and the irregular one in the calls its arrival order gives."""
    param("device_tpu_batch_max", 8)
    _solve_counted(accel_device, param, monkeypatch, "lfq", make, p)
    assert accel_device.executed_tasks == ntasks
    assert accel_device.xla_calls == calls >= ntasks / 8


def test_without_the_local_bound_the_flood_sees_strict_priority_order(
        accel_device, param, monkeypatch):
    """``sched_lfq_buffer_size`` keeps arrival order past the first 256
    ready tasks, and that order fills the Cholesky's batches (PERF.md,
    PR 30, finding 2): lifted, the 32-panel graph takes 311 calls where the
    bounded queue takes 308."""
    param("sched_lfq_buffer_size", 100000000)
    _solve_counted(accel_device, param, monkeypatch, "lfq", _potrf, 32)
    assert accel_device.executed_tasks == 5984
    assert accel_device.flood_putbacks == 0
    assert accel_device.xla_calls == 311


def test_two_accelerators_hand_back_what_best_device_gives_the_other(
        accel_device, device_registry, param, monkeypatch):
    """One ``Context`` over two accelerators (ROADMAP A6): a flood pops its
    class, keeps the tasks ``best_device`` gives its own chip and hands the
    rest back, so both run their share and the put-backs are counted."""
    import jax

    from parsec_tpu.device.tpu import TPUDevice
    devs = (accel_device, device_registry.add(TPUDevice(jax.devices()[1])))
    n = _solve_counted(devs, param, monkeypatch, "lfq", _potrf, 16)
    assert n["popped"] == n["pushed"]       # a put-back is a push and a pop
    assert [d.executed_tasks for d in devs] == [479, 337]
    assert [d.flood_putbacks for d in devs] == [272, 232]
    # a tile another chip wrote crosses once to each chip that reads it, and
    # is counted apart from the host's tiles (PR 40)
    assert [d.d2d_tiles for d in devs] == [57, 78]
    assert [d.bytes_d2d for d in devs] == [57 * 256, 78 * 256]
    assert sum(d.bytes_in for d in devs) == 136 * 256
