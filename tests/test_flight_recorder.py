"""The always-on runtime flight recorder: ring wraparound, the disabled
path's zero-allocation contract, the stall dump a wedged run must produce
(a hung device or peer must not leave a run without self-reported
evidence), the metrics snapshotter, and the unified run-report export."""

import gc
import io
import json
import threading
import time
import tracemalloc

import pytest

from parsec_tpu import ptg
from parsec_tpu.core.params import params  # noqa: F401 — param registry
from parsec_tpu.prof import (export_run_report, flight_recorder, pins,
                             runtime_report, trace_state)
from parsec_tpu.prof.pins import PinsEvent
from parsec_tpu.runtime import Context
from parsec_tpu.runtime.context import ContextWaitTimeout


@pytest.fixture
def fresh_recorder():
    """A private size-8 recorder installed for the test, with whatever
    was installed before (the always-on default) restored after."""
    old_rec, old_hook = flight_recorder.recorder, pins.recorder
    rec = flight_recorder.install(8)
    yield rec
    flight_recorder.recorder, pins.recorder = old_rec, old_hook


# ---------------------------------------------------------------------------
# ring mechanics
# ---------------------------------------------------------------------------

def test_ring_wraparound_keeps_last_n(fresh_recorder):
    for i in range(20):
        pins.fire(PinsEvent.EXEC_END, None, i)
    snap = fresh_recorder.snapshot()
    ring = snap[threading.current_thread().name]
    assert ring["total"] == 20
    assert len(ring["events"]) == 8          # fixed-size: last 8 survive
    assert [e["info"] for e in ring["events"]] == list(range(12, 20))
    assert all(e["event"] == "EXEC_END" for e in ring["events"])


def test_counts_survive_wraparound_and_sum_payloads(fresh_recorder):
    for i in range(30):
        pins.fire(PinsEvent.COMPLETE_EXEC_END, None, None)
    pins.fire(PinsEvent.DEVICE_STAGE_IN, None, 1000)
    pins.fire(PinsEvent.DEVICE_STAGE_IN, None, 24)
    counts, vsums = fresh_recorder.aggregate()
    assert counts[PinsEvent.COMPLETE_EXEC_END] == 30
    assert vsums[PinsEvent.DEVICE_STAGE_IN] == 1024
    rep = runtime_report()
    assert rep["tasks_retired"] == 30     # the snapshotter's meaning
    assert rep["h2d_bytes"] == 1024


def test_idle_selects_become_liveness_ticks_not_ring_spam(fresh_recorder):
    pins.fire(PinsEvent.EXEC_BEGIN, None, 7)
    for _ in range(600):                      # an idle-polling worker
        pins.fire(PinsEvent.SELECT_BEGIN, None, None)
        pins.fire(PinsEvent.SELECT_END, None, None)   # no task: empty
    ring = fresh_recorder.snapshot()[threading.current_thread().name]
    assert ring["total"] == 1                 # real history not rotated out
    assert ring["events"][0]["event"] == "EXEC_BEGIN"
    # only EMPTY selects tick the idle counter: SELECT_BEGIN is
    # payload-free even on productive selects and must not count
    assert ring["idle_selects"] == 600


def test_busy_selects_do_not_count_as_idle(fresh_recorder):
    class _T:
        pass
    task = _T()
    for _ in range(10):                       # a saturated worker
        pins.fire(PinsEvent.SELECT_BEGIN, None, None)
        pins.fire(PinsEvent.SELECT_END, None, task)   # got work
    ring = fresh_recorder.snapshot()[threading.current_thread().name]
    assert ring["idle_selects"] == 0
    assert ring["total"] == 10


def test_recycled_thread_name_keeps_cumulative_counts(fresh_recorder):
    """A later context's worker reusing a thread name must not erase the
    earlier worker's tallies (runtime_report would regress; rates() would
    go negative)."""
    def worker():
        for _ in range(5):
            pins.fire(PinsEvent.COMPLETE_EXEC_END, None, None)
    for _ in range(2):
        t = threading.Thread(target=worker, name="recycled-es")
        t.start()
        t.join()
    counts, _ = fresh_recorder.aggregate()
    assert counts[PinsEvent.COMPLETE_EXEC_END] == 10
    assert len([n for n in fresh_recorder.rings if n == "recycled-es"]) == 1


def test_disabled_path_is_allocation_free():
    """With the recorder uninstalled and no PINS chains, a fire() site
    costs attribute tests only — no allocation (the compiled-out analog
    the perf acceptance criterion pins)."""
    old_rec = pins.recorder
    pins.recorder = None
    try:
        if pins.enabled:
            pytest.skip("a PINS chain is registered by another test")
        payload = object()
        pins.fire(PinsEvent.EXEC_BEGIN, None, payload)     # warm the path
        tracemalloc.start()
        s1 = tracemalloc.take_snapshot()
        for _ in range(1000):
            pins.fire(PinsEvent.EXEC_BEGIN, None, payload)
        s2 = tracemalloc.take_snapshot()
        tracemalloc.stop()
        leaked = [d for d in s2.compare_to(s1, "filename")
                  if d.traceback[0].filename == pins.__file__
                  and d.size_diff > 0]
        assert not leaked, leaked
    finally:
        pins.recorder = old_rec


def test_disabled_dispatch_slot_is_none_and_allocation_free():
    """The ISSUE-2 fast path: hot sites read ``pins.hooks[event]`` — with
    nothing attached the slot IS None, and the slot-pattern loop (index
    load + falsy branch, exactly what scheduling.py compiles in) allocates
    nothing."""
    old_rec = pins.recorder
    pins.recorder = None
    try:
        if pins.enabled:
            pytest.skip("a PINS chain is registered by another test")
        hooks = pins.hooks
        ev = int(PinsEvent.EXEC_BEGIN)
        assert hooks[ev] is None
        payload = object()
        it = range(1000)          # loop machinery allocated up front
        tracemalloc.start()
        before, _ = tracemalloc.get_traced_memory()
        for _ in it:
            h = hooks[ev]
            if h is not None:
                h(None, payload)
        after, _ = tracemalloc.get_traced_memory()
        tracemalloc.stop()
        # zero PER-SITE allocation: 1000 disabled sites may not grow the
        # heap by even half a byte per visit
        assert after - before < 512, (before, after)
    finally:
        pins.recorder = old_rec


def test_recorder_assignment_retargets_dispatch_slots():
    """``pins.recorder = fn`` (the PR-1 install contract AND this file's
    fixtures) must retarget the precompiled slots immediately — and the
    hooks LIST identity must never change, since hot sites bind it once
    at import."""
    table_before = pins.hooks
    seen = []
    old_rec = pins.recorder
    pins.recorder = lambda ev, payload: seen.append((ev, payload))
    try:
        h = pins.hooks[int(PinsEvent.EXEC_BEGIN)]
        assert h is not None
        h(None, 42)
        assert seen == [(PinsEvent.EXEC_BEGIN, 42)]
        pins.fire(PinsEvent.DEVICE_STAGE_IN, None, 7)   # fire() same table
        assert seen[-1] == (PinsEvent.DEVICE_STAGE_IN, 7)
    finally:
        pins.recorder = old_rec
    assert pins.hooks is table_before
    assert pins.recorder is old_rec


def test_chain_registration_compiles_slots_and_unregister_clears():
    calls = []

    def cb(es, payload):
        calls.append(payload)

    old_rec = pins.recorder
    pins.recorder = None
    try:
        ev = PinsEvent.DATA_FLUSH_BEGIN
        if pins.hooks[int(ev)] is not None:
            pytest.skip("another module holds a chain on this event")
        pins.register(ev, cb)
        assert pins.hooks[int(ev)] is not None
        pins.fire(ev, None, "x")
        assert calls == ["x"]
        pins.unregister(ev, cb)
        assert pins.hooks[int(ev)] is None
    finally:
        pins.recorder = old_rec


# ---------------------------------------------------------------------------
# stall dump
# ---------------------------------------------------------------------------

def _hung_pool(ev, n=4):
    p = ptg.PTGBuilder("hangpool", N=n)
    t = p.task("HANG", i=ptg.span(0, lambda g, l: g.N - 1))
    t.body(lambda es, task, g, l: (ev.wait(20), None)[1])
    return p.build()


def test_wait_timeout_raises_typed_and_dumps(tmp_path, param, capsys):
    """A forced Context.wait() timeout on deliberately hung workers
    produces a ContextWaitTimeout (caught by TYPE, not message text) and
    a stall dump naming every worker's last event and the queue depths,
    serialized to stderr and the flightrec-<rank>.json artifact."""
    param("prof_flightrec_dir", str(tmp_path))
    ev = threading.Event()
    ctx = Context(nb_cores=2)
    ctx.add_taskpool(_hung_pool(ev))
    try:
        with pytest.raises(ContextWaitTimeout) as ei:
            ctx.wait(timeout=0.5)
        assert isinstance(ei.value, TimeoutError)   # back-compat contract
        report = ctx.last_stall_report
        assert report is not None
        # every worker is named with its last event
        workers = report["workers"]
        for es_name in ("parsec-es0", "parsec-es1"):
            assert es_name in workers, workers.keys()
            evs = workers[es_name]["events"]
            assert evs, f"{es_name} recorded no events"
            assert evs[-1]["event"] == "EXEC_BEGIN"
            assert evs[-1]["info"] == "HANG"
        # queue depths present (lfq: per-stream + per-VP system queue)
        assert isinstance(report["queue_depths"], dict)
        assert report["queue_depths"], report
        assert "active_taskpools" in report
        # the artifact round-trips as JSON
        art = tmp_path / "flightrec-0.json"
        assert art.exists()
        loaded = json.loads(art.read_text())
        assert loaded["workers"].keys() == workers.keys()
        err = capsys.readouterr().err
        assert "STALL DUMP" in err
        assert "parsec-es0" in err
    finally:
        ev.set()
        ctx.wait(timeout=30)
        ctx.fini()


def test_fini_bounded_drain_aborts_instead_of_hanging(tmp_path, param):
    """fini(timeout=...) on a wedged pool falls through to abort-style
    teardown within the bound instead of blocking forever (ADVICE r5:
    a caller's 'finally: ctx.fini()' hung in exactly this case)."""
    param("prof_flightrec_dir", str(tmp_path))
    ev = threading.Event()
    ctx = Context(nb_cores=1)
    ctx.add_taskpool(_hung_pool(ev, n=1))
    ctx.start()
    time.sleep(0.2)                      # let the worker enter the body
    threading.Timer(0.3, ev.set).start()  # unblock during fini's join
    t0 = time.monotonic()
    ctx.fini(timeout=0.2)                # must NOT raise, must NOT hang
    assert time.monotonic() - t0 < 10
    assert ctx.last_stall_report is not None
    assert (tmp_path / "flightrec-0.json").exists()


def test_fini_after_timed_out_wait_dumps_only_once(tmp_path, param, capsys):
    """bench's 'finally: ctx.fini(expired)' after a timed-out wait must
    not produce a second dump — one diagnosis per stall."""
    param("prof_flightrec_dir", str(tmp_path))
    ev = threading.Event()
    ctx = Context(nb_cores=1)
    ctx.add_taskpool(_hung_pool(ev, n=1))
    with pytest.raises(ContextWaitTimeout):
        ctx.wait(timeout=0.3)
    threading.Timer(0.3, ev.set).start()
    ctx.fini(timeout=0.0)            # expired deadline, abort-style
    assert capsys.readouterr().err.count("STALL DUMP") == 1


def test_wait_timeout_dump_can_be_disabled(param):
    param("prof_stall_dump", False)
    ev = threading.Event()
    ctx = Context(nb_cores=1)
    ctx.add_taskpool(_hung_pool(ev, n=1))
    try:
        with pytest.raises(ContextWaitTimeout):
            ctx.wait(timeout=0.3)
        assert ctx.last_stall_report is None
    finally:
        ev.set()
        ctx.wait(timeout=30)
        ctx.fini()


# ---------------------------------------------------------------------------
# metrics snapshotter
# ---------------------------------------------------------------------------

def test_snapshotter_samples_counters_and_props(param):
    param("prof_snapshot_interval", 0.03)
    snap = flight_recorder.snapshotter
    before = len(snap.series)
    p = ptg.PTGBuilder("sleepy", N=60)
    t = p.task("T", i=ptg.span(0, lambda g, l: g.N - 1))
    t.body(lambda es, task, g, l: time.sleep(0.005))
    with Context(nb_cores=2) as ctx:
        ctx.add_taskpool(p.build())
        ctx.wait(timeout=60)
    assert len(snap.series) > before, "snapshotter never sampled"
    s = snap.series[-1]
    assert "sde" in s and "props" in s and "tasks_retired" in s
    # the thread refcount released on fini: no further samples accumulate
    # (allow a last in-flight sample to land first)
    time.sleep(0.1)
    n = len(snap.series)
    time.sleep(0.12)
    assert len(snap.series) == n


# ---------------------------------------------------------------------------
# unified export
# ---------------------------------------------------------------------------

def test_export_run_report_roundtrip_chrome(tmp_path, param):
    """Flight-recorder events, counter series, and Profiling streams all
    land in ONE chrome trace that round-trips through JSON."""
    from parsec_tpu.core.mca import repository
    trace_state.init()
    comp = repository.find("pins", "task_profiler")
    mod = comp.open()
    try:
        p = ptg.PTGBuilder("exp", N=12)
        t = p.task("T", i=ptg.span(0, lambda g, l: g.N - 1))
        t.body(lambda es, task, g, l: None)
        with Context(nb_cores=0) as ctx:
            ctx.add_taskpool(p.build())
            ctx.wait(timeout=30)
        flight_recorder.snapshotter.sample()
        flight_recorder.snapshotter.sample()
        path = tmp_path / "report.json"
        out = export_run_report(chrome_path=str(path))
        loaded = json.loads(path.read_text())
        evs = loaded["traceEvents"]
        cats = {e.get("cat") for e in evs}
        phases = {e.get("ph") for e in evs}
        assert "flightrec" in cats           # ring instant events (pid 1)
        assert "parsec" in cats              # profiling spans (pid 0)
        assert "C" in phases                 # counter series (pid 2)
        assert any(e.get("name") == "task_exec" for e in evs)
        summary = out["summary"]
        assert summary["tasks_retired"] >= 12
        assert summary["trace_events"] == len(evs)
        assert summary["workers"]
    finally:
        comp.close(mod)
        trace_state.fini()


# ---------------------------------------------------------------------------
# the completion path on the stand-in accelerator
# ---------------------------------------------------------------------------

_IN_COMPLETION = {"RELEASE_DEPS_BEGIN", "RELEASE_DEPS_END",
                  "SCHEDULE_BEGIN", "SCHEDULE_END",
                  "COMPLETE_EXEC_BEGIN", "COMPLETE_EXEC_END"}


@pytest.fixture
def roomy_recorder():
    """A private recorder whose rings keep every record of a small solve,
    with whatever was installed before restored after."""
    old_rec, old_hook = flight_recorder.recorder, pins.recorder
    rec = flight_recorder.install(1 << 14)
    yield rec
    flight_recorder.recorder, pins.recorder = old_rec, old_hook


_NT, _NB = 4, 16           # 4 x 4 x 4 GEMM tasks on 16-square tiles


def _gemm_operands():
    import numpy as np
    from parsec_tpu.data_dist.matrix import TiledMatrix
    a = np.random.default_rng(43).standard_normal(
        (_NT * _NB, _NT * _NB)).astype(np.float32)
    return (a, TiledMatrix.from_dense("A", a, _NB, _NB),
            TiledMatrix.from_dense("B", a.T.copy(), _NB, _NB),
            TiledMatrix.from_dense("C", np.zeros_like(a), _NB, _NB))


def _gemm_solve(front: str, device) -> int:
    """C += A·Aᵀ on ``device`` through one front end, to the flush and
    ``fini``; returns the number of tasks."""
    import numpy as np
    from parsec_tpu.models.tiled_gemm import tiled_gemm_dtd, tiled_gemm_ptg
    a, A, B, C = _gemm_operands()
    ctx = Context(nb_cores=0)
    if front == "ptg":
        ctx.add_taskpool(tiled_gemm_ptg(A, B, C, devices="tpu"))
        ctx.wait(timeout=120)
    else:
        from parsec_tpu.dtd import DTDTaskpool
        tp = DTDTaskpool()
        ctx.add_taskpool(tp)
        tiled_gemm_dtd(tp, A, B, C)
        tp.wait(timeout=120)
    device.sync()
    device.flush_cache()
    ctx.fini()
    np.testing.assert_allclose(C.to_dense(), a @ a.T, rtol=1e-4, atol=1e-3)
    return _NT ** 3


@pytest.mark.parametrize("front", ["ptg", "dtd"])
def test_a_completion_writes_one_record(front, accel_device, roomy_recorder):
    """Each completion on the device path is one ring record, its begin,
    naming the task; the release and schedule pairs inside it and its end
    write none, the end still counts the task retired, and the report's
    ``notes_per_task_retired`` is what the rings were written over it
    (six to eight a completion when each site wrote its own)."""
    ntasks = _gemm_solve(front, accel_device)
    assert accel_device.executed_tasks == ntasks
    rings = roomy_recorder.snapshot().values()
    assert all(r["completing"] is None for r in rings)
    records = [e for r in rings for e in r["events"]]
    done = [e for e in records if e["event"] in _IN_COMPLETION]
    assert len(done) == ntasks
    assert {e["event"] for e in done} == {"COMPLETE_EXEC_BEGIN"}
    assert len({e["task"] for e in done}) == ntasks
    assert all(isinstance(e["task"], int) and e["info"] for e in done)
    rep = runtime_report()
    assert rep["tasks_retired"] == ntasks
    assert rep["notes_per_task_retired"] == round(
        roomy_recorder.writes() / rep["tasks_retired"], 3)
    assert rep["notes_per_task_retired"] == round(
        len(records) / ntasks, 3)
    assert rep["notes_per_task_retired"] < 2


class _ReleaseFailed(BaseException):
    """Escapes the device module's demotion path, which catches
    ``Exception``: the run stops at the completion that raised."""


def test_failed_release_names_its_task_in_the_stall_dump(
        accel_device, roomy_recorder, param, tmp_path):
    param("prof_flightrec_dir", str(tmp_path))
    seen = []

    def release_raises(es, task):
        seen.append((task.uid, task.task_class.name))
        if len(seen) == 5:
            raise _ReleaseFailed

    from parsec_tpu.models.tiled_gemm import tiled_gemm_ptg
    _, A, B, C = _gemm_operands()
    ctx = Context(nb_cores=0)
    ctx.add_taskpool(tiled_gemm_ptg(A, B, C, devices="tpu"))
    pins.register(PinsEvent.RELEASE_DEPS_BEGIN, release_raises)
    try:
        with pytest.raises(_ReleaseFailed):
            ctx.wait(timeout=120)
    finally:
        pins.unregister(PinsEvent.RELEASE_DEPS_BEGIN, release_raises)
        ctx.fini()           # the failure poisoned it: no drain
    err = io.StringIO()
    report = flight_recorder.stall_dump(ctx, "a release raised", file=err)
    ring = report["workers"][threading.current_thread().name]
    uid, cls = seen[-1]
    assert ring["completing"] == {"task": uid, "info": cls}
    assert f"completing task={uid} info={cls}" in err.getvalue()
    # the four completions before it are on the ring too
    begun = [e["task"] for e in ring["events"]
             if e["event"] == "COMPLETE_EXEC_BEGIN"]
    assert begun == [u for u, _ in seen]


@pytest.mark.parametrize("front", ["ptg", "dtd"])
def test_rings_hold_no_task(front, accel_device, roomy_recorder):
    """A record names its task by uid and class: a ring that held a Task
    would keep its data copies alive (and defeat the device module's
    sole-holder probe), so every completed task is gone after fini."""
    import weakref
    from parsec_tpu.runtime.task import Task
    refs = []

    def keep_weakly(es, task):
        refs.append(weakref.ref(task))

    pins.register(PinsEvent.COMPLETE_EXEC_END, keep_weakly)
    try:
        ntasks = _gemm_solve(front, accel_device)
    finally:
        pins.unregister(PinsEvent.COMPLETE_EXEC_END, keep_weakly)
    assert len(refs) == ntasks
    for ring in roomy_recorder.all_rings():
        assert not any(isinstance(x, Task) for rec in ring.slots
                       if rec is not None for x in rec)
    gc.collect()
    assert [r for r in refs if r() is not None] == []


def test_chains_on_release_and_schedule_see_every_event(
        accel_device, roomy_recorder, monkeypatch):
    """The recorder left the release and schedule sites; a PINS chain
    registered there still receives each of them."""
    from parsec_tpu.runtime import context as context_mod
    from parsec_tpu.runtime import scheduling
    released, scheduled, calls = [], [], []
    plain = scheduling.schedule_tasks

    def counted(es, tasks, distance=0):
        if tasks:
            calls.append(len(tasks))
        plain(es, tasks, distance)

    monkeypatch.setattr(scheduling, "schedule_tasks", counted)
    monkeypatch.setattr(context_mod, "schedule_tasks", counted)

    def on_release(es, task):
        released.append(task.uid)

    def on_schedule_end(es, tasks):
        scheduled.append(es)

    pins.register(PinsEvent.RELEASE_DEPS_BEGIN, on_release)
    pins.register(PinsEvent.SCHEDULE_END, on_schedule_end)
    try:
        ntasks = _gemm_solve("ptg", accel_device)
    finally:
        pins.unregister(PinsEvent.RELEASE_DEPS_BEGIN, on_release)
        pins.unregister(PinsEvent.SCHEDULE_END, on_schedule_end)
    assert len(released) == len(set(released)) == ntasks
    # the startup batch, then one a completion that readied GEMM(m, n, k+1)
    assert len(scheduled) == len(calls) == 1 + ntasks - 4 * 4
    assert runtime_report()["tasks_retired"] == ntasks


def test_runtime_report_is_json_serializable_and_compact():
    # the report merges the SLO planes of every LIVE server; servers an
    # earlier test of this process drained hang in cyclic garbage until the
    # collector runs, and their tenants would be counted (8.9 kB after the
    # LLM files with the collector off)
    import gc
    gc.collect()
    rep = runtime_report()
    s = json.dumps(rep)
    assert len(s) < 4096
    assert "tasks_retired" in rep and "workers" in rep
