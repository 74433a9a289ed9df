"""The persistent serving layer (parsec_tpu/serve/): concurrent
submission, admission control, fair scheduling, deadlines, drain, and the
live-enqueue context plumbing underneath it (ISSUE 3).

The flagship test drives the acceptance shape: >= 2 tenants submitting
>= 50 mixed cholesky/pingpong/reduction taskpools from >= 4 client
threads into ONE running server, every ticket resolving with a verified
result.
"""

from __future__ import annotations

import itertools
import threading
import time

import numpy as np
import pytest

from parsec_tpu import ptg
from parsec_tpu.data.data import TileType
from parsec_tpu.data_dist.collection import DictCollection
from parsec_tpu.data_dist.matrix import SymTwoDimBlockCyclic, VectorTwoDimCyclic
from parsec_tpu.runtime import Context
from parsec_tpu.runtime.context import ContextWaitTimeout
from parsec_tpu.runtime.taskpool import Taskpool
from parsec_tpu.sched.api import SchedulerModule
from parsec_tpu.serve import (AdmissionController, AdmissionRejected,
                              DeadlineExceeded, RuntimeServer,
                              TicketCancelled)
from parsec_tpu.serve.fair import FairScheduler

_uniq = itertools.count()


# ---------------------------------------------------------------------------
# request builders — each returns (taskpool, check_fn)
# ---------------------------------------------------------------------------

def _chain_pool(nb: int = 5, body_sleep: float = 0.0):
    tag = next(_uniq)
    coll = DictCollection(f"chainA{tag}", dtt=TileType((1,), np.float32),
                          init_fn=lambda *k: np.zeros(1, np.float32))
    p = ptg.PTGBuilder(f"chain{tag}", A=coll, NB=nb)
    t = p.task("T", i=ptg.span(0, lambda g, l: g.NB - 1))
    f = t.flow("V", ptg.RW)
    f.input(data=("A", lambda g, l: (0,)), guard=lambda g, l: l.i == 0)
    f.input(pred=("T", "V", lambda g, l: {"i": l.i - 1}),
            guard=lambda g, l: l.i > 0)
    f.output(succ=("T", "V", lambda g, l: {"i": l.i + 1}),
             guard=lambda g, l: l.i < g.NB - 1)
    f.output(data=("A", lambda g, l: (0,)), guard=lambda g, l: l.i == g.NB - 1)

    def body(es, task, g, l):
        if body_sleep:
            time.sleep(body_sleep)
        v = task.flow_data("V")
        v.value = v.value + 1

    t.body(body)

    def check():
        got = float(coll.data_of(0).newest_copy().value[0])
        assert got == nb, (got, nb)

    return p.build(), check


def _cholesky_pool(n: int = 64, nb: int = 32):
    from parsec_tpu.models.cholesky import make_spd, tiled_cholesky_ptg
    a = make_spd(n)
    A = SymTwoDimBlockCyclic.from_dense(f"chol{next(_uniq)}", a, nb, nb)
    tp = tiled_cholesky_ptg(A)

    def check():
        got = np.asarray(A.data_of(0, 0).newest_copy().value)
        expect = np.linalg.cholesky(a[:nb, :nb].astype(np.float64))
        err = float(np.max(np.abs(np.tril(got) - expect)))
        assert err < 1e-3, err

    return tp, check


def _pingpong_pool(nt: int = 6):
    from parsec_tpu.models.pingpong import pingpong_ptg
    V = VectorTwoDimCyclic(f"pp{next(_uniq)}", lm=4, mb=4, P=1,
                           init_fn=lambda m, size:
                           np.zeros(size, np.float32))
    tp = pingpong_ptg(V, nt)

    def check():
        got = float(np.asarray(V.data_of(0).newest_copy().value)[0])
        assert got == nt, (got, nt)

    return tp, check


def _reduction_pool(nt: int = 5):
    from parsec_tpu.models.reduction import bt_reduction_ptg
    rng = np.random.default_rng(nt)
    base = rng.standard_normal((nt, 4)).astype(np.float32)
    V = VectorTwoDimCyclic(f"red{next(_uniq)}", lm=nt * 4, mb=4, P=1,
                           init_fn=lambda m, size: base[m, :size].copy())
    tp = bt_reduction_ptg(V)

    def check():
        got = np.asarray(V.data_of(0).newest_copy().value)
        np.testing.assert_allclose(got, base.sum(axis=0), rtol=1e-4,
                                   atol=1e-5)

    return tp, check


_MAKERS = [_chain_pool, _cholesky_pool, _pingpong_pool, _reduction_pool]


# ---------------------------------------------------------------------------
# the acceptance shape: concurrent mixed submission
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("makers,each", [
    (_MAKERS, 14), ([lambda: _chain_pool(nb=4)], 4)], ids=["mixed", "chains"])
def test_concurrent_mixed_submissions_all_tickets_resolve(makers, each):
    """2 tenants, 4 client threads, 56 mixed pools (or 16 four-task chains,
    each awaited before the next) into one hot server — every ticket
    resolves and every result verifies."""
    server = RuntimeServer(nb_cores=2)
    errors: list[BaseException] = []
    done = []
    lock = threading.Lock()

    def client(cid: int):
        tenant = f"tenant{cid % 2}"
        try:
            for i in range(each):
                tp, check = makers[(cid + i) % len(makers)]()
                tk = server.submit(tp, tenant=tenant)
                tk.result(timeout=120)
                check()
                assert tk.state == "done"
                assert tk.latency_s is not None and tk.latency_s >= 0
                with lock:
                    done.append(tenant)
        except BaseException as e:      # noqa: BLE001 — surfaced below
            errors.append(e)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    assert len(done) == 4 * each
    s = server.stats()
    assert s["completed"] == 4 * each and s["failed"] == 0
    assert set(s["per_tenant_completed"]) == {"tenant0", "tenant1"}
    # the fair shim really carried the load (dynamic path, not bypassed)
    assert sum(s["fair_dispatched"].values()) > 0
    server.drain(timeout=60)
    assert not any(t.is_alive() for t in server.context._threads)


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------

def test_admission_shed_nonblocking_under_budget():
    server = RuntimeServer(
        nb_cores=1, admission=AdmissionController(max_inflight=1))
    slow, _check = _chain_pool(nb=2, body_sleep=0.15)
    tk = server.submit(slow)
    fast, _ = _chain_pool(nb=2)
    with pytest.raises(AdmissionRejected):
        server.submit(fast, block=False)
    tk.result(timeout=30)
    s = server.stats()
    assert s["rejected"] == 1
    assert s["admission"]["rejected"] >= 1
    server.drain(timeout=30)


def test_admission_backpressure_blocks_until_capacity():
    server = RuntimeServer(
        nb_cores=1, admission=AdmissionController(max_inflight=1))
    slow, _ = _chain_pool(nb=2, body_sleep=0.1)
    t_slow = server.submit(slow)
    fast, check = _chain_pool(nb=2)
    tk = server.submit(fast, block=True)     # waits for the slow one
    tk.result(timeout=30)
    t_slow.result(timeout=30)
    check()
    assert server.stats()["admission"]["blocked_waits"] >= 1
    server.drain(timeout=30)


def test_deadline_expired_submission_is_shed():
    server = RuntimeServer(
        nb_cores=1, admission=AdmissionController(max_inflight=1))
    slow, _ = _chain_pool(nb=2, body_sleep=0.3)
    t_slow = server.submit(slow)
    fast, _ = _chain_pool(nb=2)
    with pytest.raises(DeadlineExceeded):
        server.submit(fast, deadline=0.05)
    assert server.stats()["admission"]["shed_deadline"] == 1
    t_slow.result(timeout=30)
    server.drain(timeout=30)


def test_already_expired_deadline_sheds_even_with_free_budget():
    server = RuntimeServer(nb_cores=1)
    tp, _ = _chain_pool(nb=2)
    with pytest.raises(DeadlineExceeded):
        server.submit(tp, deadline=0.0)   # already late: never starts
    assert server.stats()["admission"]["shed_deadline"] == 1
    server.drain(timeout=30)


def test_admission_cancel_probe_and_ticket_cancel_semantics():
    adm = AdmissionController(max_inflight=1)
    adm.admit("a")
    flag = {"c": False}

    def canceller():
        time.sleep(0.05)
        flag["c"] = True
        adm.kick()

    threading.Thread(target=canceller).start()
    with pytest.raises(TicketCancelled):
        adm.admit("a", cancelled=lambda: flag["c"], timeout=5.0)
    adm.release("a")
    # a ticket that already ran cannot be cancelled
    server = RuntimeServer(nb_cores=1)
    tp, _ = _chain_pool(nb=2)
    tk = server.submit(tp)
    tk.result(timeout=30)
    assert tk.cancel() is False
    server.drain(timeout=30)


def test_submit_after_drain_rejected():
    server = RuntimeServer(nb_cores=1)
    tp, _ = _chain_pool(nb=2)
    server.submit(tp).result(timeout=30)
    server.drain(timeout=30)
    tp2, _ = _chain_pool(nb=2)
    with pytest.raises(AdmissionRejected):
        server.submit(tp2)


# ---------------------------------------------------------------------------
# fair scheduling
# ---------------------------------------------------------------------------

class _StubInner(SchedulerModule):
    name = "stub"

    def __init__(self):
        self.items = []

    def schedule(self, es, tasks, distance=0):
        self.items.extend(tasks)

    def select(self, es):
        return (self.items.pop(0), 0) if self.items else (None, 0)

    def pending_tasks(self, context):
        return len(self.items)


class _FakeSub:
    def __init__(self, tenant, priority=0, deadline_at=None):
        self.tenant = tenant
        self.priority = priority
        self.deadline_at = deadline_at


class _FakeTask:
    __slots__ = ("taskpool", "priority", "tag")

    def __init__(self, sub, tag, priority=0):
        class _TP:          # minimal taskpool stand-in
            pass
        self.taskpool = _TP()
        self.taskpool._serve_sub = sub
        self.priority = priority
        self.tag = tag


def test_fair_scheduler_weighted_share_is_proportional():
    fair = FairScheduler(_StubInner())
    fair.set_weight("heavy", 3.0)
    fair.set_weight("light", 1.0)
    heavy, light = _FakeSub("heavy"), _FakeSub("light")
    fair.schedule(None, [_FakeTask(heavy, f"h{i}") for i in range(40)])
    fair.schedule(None, [_FakeTask(light, f"l{i}") for i in range(40)])
    picks = [fair.select(None)[0].taskpool._serve_sub.tenant
             for _ in range(40)]
    h = picks.count("heavy")
    assert 28 <= h <= 32, picks     # WFQ: 3:1 share within rounding
    # drains completely and falls back to the inner when empty
    rest = [fair.select(None)[0] for _ in range(40)]
    assert all(t is not None for t in rest)
    assert fair.select(None) == (None, 0)


def test_fair_scheduler_inner_nested_work_dispatches_first():
    """Non-serve tasks (nested local_only pools spawned by serve bodies)
    must not be starved behind the tenant queues — they block a parent
    submission that already holds an admission slot."""
    fair = FairScheduler(_StubInner())
    fair.schedule(None, [_FakeTask(_FakeSub("a"), "fair0")])

    class _Plain:
        priority = 0
    plain = _Plain()
    plain.taskpool = type("_TP", (), {})()      # no _serve_sub
    fair.schedule(None, [plain])
    assert fair.select(None)[0] is plain        # nested work first
    assert fair.select(None)[0].tag == "fair0"
    assert fair.select(None) == (None, 0)


def test_fair_scheduler_priority_then_deadline_within_tenant():
    fair = FairScheduler(_StubInner())
    lo = _FakeSub("a", priority=0)
    hi = _FakeSub("a", priority=5)
    soon = _FakeSub("a", priority=0, deadline_at=100.0)
    fair.schedule(None, [_FakeTask(lo, "lo")])
    fair.schedule(None, [_FakeTask(soon, "soon")])
    fair.schedule(None, [_FakeTask(hi, "hi")])
    order = [fair.select(None)[0].tag for _ in range(3)]
    assert order == ["hi", "soon", "lo"]


def test_serve_fair_is_mca_selectable_and_never_double_wrapped():
    """``Context(scheduler="serve_fair")`` yields the shim over the
    best-priority inner module; a RuntimeServer given that context
    reuses it instead of stacking a second shim."""
    ctx = Context(nb_cores=1, scheduler="serve_fair")
    assert isinstance(ctx.scheduler, FairScheduler)
    assert not isinstance(ctx.scheduler.inner, FairScheduler)
    server = RuntimeServer(context=ctx)
    assert server._fair is ctx.scheduler
    tp, check = _chain_pool(nb=3)
    server.submit(tp).result(timeout=30)
    check()
    server.drain(timeout=30)


def test_tenant_fairness_under_saturation():
    """Backlog both tenants on one worker: the 3x-weighted tenant's
    submissions finish markedly earlier than the 1x tenant's."""
    server = RuntimeServer(
        nb_cores=1, tenant_weights={"heavy": 3.0, "light": 1.0},
        admission=AdmissionController(max_inflight=0,
                                      max_tenant_inflight=0))
    completions: list[str] = []
    lock = threading.Lock()

    def noting(tenant):
        def fn(tp):
            with lock:
                completions.append(tenant)
            return tp
        return fn

    tickets = []
    for _i in range(12):
        for tenant in ("heavy", "light"):
            tp, _ = _chain_pool(nb=4, body_sleep=0.001)
            tickets.append(server.submit(tp, tenant=tenant,
                                         result_fn=noting(tenant)))
    for tk in tickets:
        tk.result(timeout=120)
    first = completions[:12]
    assert first.count("heavy") >= first.count("light") + 2, completions
    server.drain(timeout=60)


# ---------------------------------------------------------------------------
# drain / failure / observability
# ---------------------------------------------------------------------------

def test_drain_is_clean_and_flight_recorder_consistent():
    from parsec_tpu.prof import flight_recorder
    from parsec_tpu.prof.pins import PinsEvent
    rec = flight_recorder.ensure_installed()
    assert rec is not None
    c0, _ = rec.aggregate()
    server = RuntimeServer(nb_cores=2)
    for _i in range(5):
        tp, check = _chain_pool(nb=3)
        server.submit(tp).result(timeout=30)
        check()
    workers = list(server.context._threads)
    server.drain(timeout=30)
    assert not any(t.is_alive() for t in workers)
    c1, _ = rec.aggregate()
    d = [c1[i] - c0[i] for i in range(len(c0))]
    assert d[PinsEvent.SERVE_SUBMIT] == 5
    assert d[PinsEvent.SERVE_ADMIT] == 5
    assert d[PinsEvent.SERVE_START] == 5
    assert d[PinsEvent.SERVE_COMPLETE] == 5
    assert d[PinsEvent.SERVE_REJECT] == 0
    assert d[PinsEvent.SERVE_DRAIN] == 1
    # the run report exposes the same tallies (docs/SERVING.md)
    rep = flight_recorder.runtime_report()
    assert rep["serve"]["submitted"] >= 5


def test_drain_timeout_fails_leftover_tickets_and_clears_books(param):
    param("prof_stall_dump", False)
    server = RuntimeServer(nb_cores=1)
    slow, _ = _chain_pool(nb=2, body_sleep=0.6)
    tk = server.submit(slow)
    time.sleep(0.05)                    # let the worker enter the body
    with pytest.raises(ContextWaitTimeout):
        server.drain(timeout=0.1)
    with pytest.raises(ContextWaitTimeout):
        tk.result(timeout=5)            # failed promptly, not hung
    assert server.stats()["inflight"] == 0
    t0 = time.monotonic()
    server.drain(timeout=5)             # re-entry returns, never wedges
    assert time.monotonic() - t0 < 2


def test_exit_on_exception_fails_blocked_clients_promptly():
    got: list[BaseException] = []

    def waiter(tk):
        try:
            tk.result(timeout=30)
        except BaseException as e:      # noqa: BLE001
            got.append(e)

    with pytest.raises(ValueError):
        with RuntimeServer(nb_cores=1) as server:
            slow, _ = _chain_pool(nb=2, body_sleep=0.5)
            th = threading.Thread(target=waiter,
                                  args=(server.submit(slow),))
            th.start()
            raise ValueError("client bug")
    th.join(timeout=5)
    assert not th.is_alive()            # freed long before its 30s timeout
    assert got and isinstance(got[0], RuntimeError)


def test_worker_failure_fails_inflight_tickets_and_poisons_server():
    server = RuntimeServer(nb_cores=1)
    tag = next(_uniq)
    p = ptg.PTGBuilder(f"boom{tag}")
    t = p.task("BOOM", i=ptg.span(0, lambda g, l: 0))
    t.flow("ctl", ptg.CTL)

    def body(es, task, g, l):
        raise ValueError("serving body exploded")

    t.body(body)
    tk = server.submit(p.build())
    with pytest.raises(RuntimeError):
        tk.result(timeout=30)
    assert tk.state == "failed"
    tp2, _ = _chain_pool(nb=2)
    with pytest.raises(AdmissionRejected):
        server.submit(tp2)
    with pytest.raises(RuntimeError):
        server.drain(timeout=10)


# ---------------------------------------------------------------------------
# warm lowering-cache reuse across submissions
# ---------------------------------------------------------------------------

def _gemm_ptg_pool(n=64, nb=32):
    from parsec_tpu.data_dist.matrix import TiledMatrix
    from parsec_tpu.models.tiled_gemm import tiled_gemm_ptg
    rng = np.random.default_rng(3)
    a = rng.standard_normal((n, n)).astype(np.float32)
    A = TiledMatrix.from_dense("A", a.copy(), nb, nb)
    B = TiledMatrix.from_dense("B", a.copy(), nb, nb)
    C = TiledMatrix.from_dense("C", np.zeros((n, n), np.float32), nb, nb)
    return tiled_gemm_ptg(A, B, C)


def test_repeat_lowered_submissions_hit_warm_cache():
    from parsec_tpu.ptg.lowering import lowering_cache
    server = RuntimeServer(nb_cores=1)
    r1 = server.submit_lowered(_gemm_ptg_pool()).result(timeout=120)
    h0, m0 = lowering_cache.hits, lowering_cache.misses
    r2 = server.submit_lowered(_gemm_ptg_pool()).result(timeout=120)
    # repeat class: no re-compile
    assert (lowering_cache.hits - h0, lowering_cache.misses - m0) == (1, 0)
    assert set(r1) == set(r2)
    np.testing.assert_allclose(np.asarray(r1["C"]), np.asarray(r2["C"]),
                               rtol=1e-4, atol=1e-4)
    server.drain(timeout=60)


# ---------------------------------------------------------------------------
# the context plumbing: live enqueue + per-taskpool wait
# ---------------------------------------------------------------------------

def test_live_concurrent_add_taskpool_thread_safety():
    """N client threads add_taskpool directly into a RUNNING context —
    the satellite's rank-agreed-id/live-enqueue race.  Every pool
    completes with the right value and the terminated pools are retired
    from the comm-id registry (no long-lived-context leak)."""
    ctx = Context(nb_cores=2)
    ctx.start()
    made = []
    lock = threading.Lock()
    errors = []

    def feeder(k):
        try:
            for _i in range(8):
                tp, check = _chain_pool(nb=4)
                ctx.add_taskpool(tp)
                with lock:
                    made.append((tp, check))
        except BaseException as e:      # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=feeder, args=(k,)) for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    ctx.wait(timeout=60)
    for tp, check in made:
        assert tp.test()
        check()
    # comm ids were unique (the lock) and retired at termination
    assert len({tp.comm_id for tp, _ in made}) == 32
    assert ctx.taskpool_list == [] and ctx._tp_by_comm_id == {}
    ctx.fini()


def test_wait_taskpool_and_timeout_names_live_pools(param):
    param("prof_stall_dump", False)
    ctx = Context(nb_cores=1)
    never = Taskpool(name="neverending")
    never.termdet_name = "user_trigger"
    ctx.add_taskpool(never)
    fast, check = _chain_pool(nb=3)
    ctx.add_taskpool(fast)
    # one submission awaited without draining the context
    ctx.wait_taskpool(fast, timeout=30)
    assert fast.test() and ctx.test(fast)
    assert not ctx.test()               # the user-trigger pool still lives
    with pytest.raises(ContextWaitTimeout) as ei:
        ctx.wait_taskpool(never, timeout=0.2)
    assert "neverending" in str(ei.value)
    check()
    never.tdm.trigger()
    ctx.wait(timeout=30)
    ctx.fini()
