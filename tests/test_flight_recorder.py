"""The always-on runtime flight recorder: ring wraparound, the disabled
path's zero-allocation contract, the stall dump a wedged run must produce
(a hung device or peer must not leave a run without self-reported
evidence), the metrics snapshotter, and the unified run-report export."""

import json
import threading
import time
import tracemalloc

import pytest

from parsec_tpu import ptg
import parsec_tpu.runtime.dagrun  # noqa: F401 — registers runtime_dag_compile
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
    pins.fire(PinsEvent.DAG_COMPLETE_END, None, 1000)
    pins.fire(PinsEvent.DAG_COMPLETE_END, None, 24)
    counts, vsums = fresh_recorder.aggregate()
    assert counts[PinsEvent.COMPLETE_EXEC_END] == 30
    assert vsums[PinsEvent.DAG_COMPLETE_END] == 1024
    rep = runtime_report()
    assert rep["dynamic_tasks_retired"] == 30
    assert rep["dag_tasks_completed"] == 1024
    assert rep["tasks_retired"] == 1054   # total = the snapshotter's meaning


def test_idle_selects_become_liveness_ticks_not_ring_spam(fresh_recorder):
    pins.fire(PinsEvent.EXEC_BEGIN, None, 7)
    for _ in range(500):                      # an idle-polling worker
        pins.fire(PinsEvent.SELECT_BEGIN, None, None)
        pins.fire(PinsEvent.SELECT_END, None, None)   # no task: empty
    for _ in range(100):                      # a wedged compiled DAG
        pins.fire(PinsEvent.DAG_FETCH_BEGIN, None, None)
        pins.fire(PinsEvent.DAG_FETCH_END, None, 0)   # empty fetch
    ring = fresh_recorder.snapshot()[threading.current_thread().name]
    assert ring["total"] == 1                 # real history not rotated out
    assert ring["events"][0]["event"] == "EXEC_BEGIN"
    # only EMPTY selects / fetches tick the idle counter: SELECT_BEGIN is
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
        pins.fire(PinsEvent.DAG_COMPLETE_END, None, 7)   # fire() same table
        assert seen[-1] == (PinsEvent.DAG_COMPLETE_END, 7)
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
    param("runtime_dag_compile", False)   # dynamic path: per-task PINS
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
    param("runtime_dag_compile", False)
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
    param("runtime_dag_compile", False)
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
    param("runtime_dag_compile", False)
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
    param("runtime_dag_compile", False)
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
    param("runtime_dag_compile", False)
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
