"""The runtime context: worker threads, scheduler, lifecycle.

Rebuild of ``parsec_context_t`` + ``parsec_init`` / ``parsec_fini``
(``parsec.c:370-901``, SURVEY §3.1) and the enqueue/start/wait API
(``runtime.h:155-712``): a context owns virtual processes of execution
streams (worker threads), a scheduler module selected through MCA, the device
registry, the dependency-tracking table, and (when distributed) the comm
engine.  Workers park on a start barrier until ``context_start`` releases
them, then run the §3.3 hot loop until every enqueued taskpool terminates.

Single-threaded contexts (``nb_cores=0``) are first-class: the caller's thread
drives progress from ``wait()`` — the analog of the master-thread funneled
path (``scheduling.c:775-784``) and the mode the TPU device manager favors
(device batching makes worker parallelism less critical than on CPU).
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Callable

from ..core.params import params as _params
from ..core.backoff import Backoff
from ..core.mca import repository
from ..prof import pins, spans
from ..prof.pins import PinsEvent
from .deps import DependencyTracking
from .scheduling import (ExecutionStream, VirtualProcess, release_totals,
                         schedule_tasks, select_task, task_progress)
from .taskpool import Taskpool

_params.register("runtime_num_cores", 0,
                        "worker threads (0 = caller-driven)")
_params.register("runtime_bind_threads", False,
                 "pin worker threads to cores round-robin "
                 "(parsec_bind / hwloc binding analog; Linux only)")
_params.register("sched", "lfq", "scheduler component to use")
# the autotuner's declared domain (docs/TUNING.md): the general-purpose
# scheduler modules (sched/modules.py) — serve_fair is a serving shim
# the RuntimeServer interposes itself, never a search move
_params.declare_knob("sched", values=("lfq", "ap", "spq", "ip", "gd",
                                      "rnd", "ll", "llp", "pbq", "ltq",
                                      "lhq"))
_params.register("termdet", "", "termination detector override")
_params.register("runtime_nb_vp", 1, "number of virtual processes")
_params.register("props_stream", "",
                 "path to stream live properties-dictionary JSON snapshots "
                 "to while the context runs (the aggregator_visu feed; "
                 "empty = off)")
_params.register("props_stream_interval", 0.1,
                 "seconds between live property snapshots")
_params.register("analysis_check", False,
                 "statically verify each taskpool at enqueue "
                 "(analysis.graphcheck): a malformed graph raises a typed "
                 "GraphCheckError instead of hanging — debug/CI runs")


# concurrency contracts, enforced by analysis.runtimelint (docs/ANALYSIS.md):
# context bookkeeping mutates only under _lock (_cond wraps the same RLock);
# whole-enqueue sequences serialize under _submit_lock, acquired OUTSIDE
# _lock when both are needed.
_LOCK_PROTECTED = {
    "Context._active_taskpools": "_lock",
    "Context.taskpool_list": "_lock",
    "Context._tp_by_comm_id": "_lock",
    "Context._next_comm_id": "_lock",
    "Context._failure_listeners": "_lock",
    "Context._worker_error": "_lock",
    "Context._shutdown": "_lock",
}
_LOCK_ALIASES = {"_cond": "_lock"}
_LOCK_ORDER = ("_submit_lock", "_lock")


class ContextWaitTimeout(TimeoutError):
    """Deadline expiry of a bounded :meth:`Context.wait` /
    :meth:`Context.fini` drain — the ONE TimeoutError that is benign
    pacing, not a runtime failure.  Caught by type everywhere (the old
    'context wait timed out' substring test was one reword away from
    silently flipping fini()'s re-raise semantics, ADVICE round 5)."""


class Context:
    def __init__(self, nb_cores: int | None = None,
                 scheduler: str | None = None,
                 nb_ranks: int = 1, my_rank: int = 0,
                 accelerators: list | None = None) -> None:
        """``accelerators``: the JAX devices this context's device chores
        may run on — an in-process rank bound to its own chip passes one
        (``run_multirank(transport="device")``).  ``None``: every
        registered accelerator."""
        spans.phase_refresh()
        with spans.phase("ctx.init"):
            from ..sched import ensure_registered as _sched_ensure
            _sched_ensure()
            from ..device import registry as device_registry
            # the always-on flight recorder hooks pins.fire before any worker
            # can emit an event (prof_flightrec_size=0 opts out)
            from ..prof import flight_recorder as _flightrec
            _flightrec.ensure_installed()
            # request-scoped span recorder (prof_spans=1): installed before
            # any worker runs, so a traced pool's first task is never missed
            spans.ensure_installed()
            # persisted tuning vector (parsec_tpu/tune, ``tune_db=1``): the
            # ambient ``context`` consult applies a stored knob vector NOW —
            # before the core-count read and the scheduler query below
            # resolve the params it may set (env/cli pins always win)
            try:
                from ..tune import apply_ambient
                self.tuned_knobs = apply_ambient("context")
            except Exception:               # noqa: BLE001 — a corrupt tuning
                self.tuned_knobs = None     # DB must never fail a start
            if nb_cores is None:
                nb_cores = _params.get("runtime_num_cores")
            self.nb_cores = nb_cores
            self.nb_ranks = nb_ranks
            self.my_rank = my_rank
            self.started = False
            self._shutdown = False
            self._lock = threading.RLock()
            self._cond = threading.Condition(self._lock)
            self._active_taskpools: list[Taskpool] = []
            self.deps = DependencyTracking()
            # always on, like the device module's counters: the edges
            # release_deps handed to local successors, and those of them
            # that went through a resolved release plan (scheduling.py:
            # _EdgePlan).  Teardown adds them to the process's totals
            # (scheduling.release_totals).
            self.release_edges = 0
            self.release_edges_planned = 0
            self._release_count_lock = threading.Lock()   # any stream adds
            self._release_folded = (0, 0)
            self.taskpool_list: list[Taskpool] = []
            self.comm_engine: Any = None
            # rank-agreed taskpool ids for the wire protocol: ranks enqueue
            # taskpools in the same order, so the per-context sequence agrees
            # (parsec_taskpool_reserve_id / sync_ids analog, parsec.c:2038).
            # The id is a monotonic counter, NOT len(taskpool_list): with live
            # enqueue a long-lived context retires terminated pools from the
            # list, and a length-derived id would recycle and collide.
            self._tp_by_comm_id: dict[int, Taskpool] = {}
            self._next_comm_id = 0
            # serializes whole add_taskpool calls: concurrent client threads
            # submitting into a RUNNING context (the serving shape) must see
            # an atomic id-reserve + termdet-arm + startup-schedule sequence:
            # RLock because compound pools re-enter from completion callbacks
            self._submit_lock = threading.RLock()
            self._failure_listeners: list[Callable[[BaseException], None]] = []
            self._worker_error: BaseException | None = None
            # whether the recorded failure has been raised to a caller —
            # fini() re-raises a failure nobody has seen yet (a silently
            # swallowed worker death would report clean success)
            self._error_surfaced = False

            # devices: the device-module init of ``parsec_init``.  The
            # registry is process-global and every accelerator JAX shows
            # registers in it once; the compile cache is placed first, before
            # anything this process jits
            from ..device.compile_cache import ensure_compile_cache
            from ..device.tpu import init_tpu_devices
            ensure_compile_cache()
            accel = init_tpu_devices()
            self.devices = device_registry
            self._device_mask = None if accelerators is None else frozenset(
                d.device_index for d in accel if d.jax_device in accelerators)

            # virtual processes + streams, per the vpmap spec (vpmap.py)
            from .vpmap import nb_vps, parse_vpmap
            nworkers = max(nb_cores, 0)
            nstreams = max(nworkers, 1)
            assignment = parse_vpmap(_params.get("runtime_vpmap"), nstreams,
                                     _params.get("runtime_nb_vp"))
            self.virtual_processes: list[VirtualProcess] = []
            streams: list[ExecutionStream] = []
            for v in range(nb_vps(assignment)):
                vp = VirtualProcess(v, self)
                self.virtual_processes.append(vp)
            for i in range(nstreams):
                vp = self.virtual_processes[assignment[i]]
                es = ExecutionStream(i if nworkers else -1, vp, self)
                vp.execution_streams.append(es)
                streams.append(es)
            self.streams = streams
            # es used by external (non-worker) threads to submit/progress
            self._submit_es = streams[0] if nworkers == 0 else \
                ExecutionStream(-1, self.virtual_processes[0], self)

            # scheduler via MCA (explicit arg > MCA param > priority query)
            comp = repository.query("sched", context=self, requested=scheduler)
            self.scheduler = comp.open(self)
            self.scheduler.install(self)
            for es in streams:
                self.scheduler.flow_init(es)

            # live properties (dictionary.c role): the context publishes its
            # hot gauges; ``props_stream`` additionally tails them to a JSON
            # file an external observer reads mid-run (aggregator_visu role).
            # The namespace de-collides when several contexts of one rank are
            # live at once, and the getters hold the context only weakly — a
            # context that never reaches fini() must not be kept alive (or
            # have its registrations clobbered/stolen) by the global registry.
            import weakref
            from ..prof.counters import properties, sde
            base = f"rank{my_rank}"
            ns = base
            i = 1
            while properties.has(ns, "sched_pending"):
                ns = f"{base}#{i}"
                i += 1
            self._props_ns = ns
            self._props_stop: Callable[[], None] | None = None
            self._snap_started = False
            self.last_stall_report: dict | None = None
            ref = weakref.ref(self)

            def gauge(fn: Callable[["Context"], Any]) -> Callable[[], Any]:
                def get():
                    c = ref()
                    return fn(c) if c is not None else 0
                return get

            properties.register(ns, "sched_pending",
                                gauge(lambda c: c.scheduler.pending_tasks(c)))
            properties.register(ns, "active_taskpools",
                                gauge(lambda c: len(c._active_taskpools)))
            properties.register(ns, "nb_tasks",
                                gauge(lambda c: sum(
                                    tp.tdm.nb_tasks
                                    for tp in c._active_taskpools
                                    if tp.tdm is not None)))
            properties.register(ns, "sde", sde.snapshot)

            # worker threads
            self._threads: list[threading.Thread] = []
            self._start_barrier = threading.Event()
            if nworkers > 0:
                for es in streams:
                    t = threading.Thread(
                        target=self._worker_main, args=(es,),
                        name=f"parsec-es{es.th_id}", daemon=True)
                    self._threads.append(t)
                    t.start()

    # ------------------------------------------------------------------ API
    def accelerators(self) -> list:
        """The registered accelerator modules this context may use."""
        return [d for d in self.devices.devices
                if d.type != "cpu" and (self._device_mask is None
                                        or d.device_index in self._device_mask)]

    def best_device(self, task: Any, device_type: str) -> Any:
        """``parsec_get_best_device`` over the devices this context may
        use (see ``accelerators``)."""
        return self.devices.best_device(task, device_type,
                                        self._device_mask)

    def add_taskpool(self, tp: Taskpool, local_only: bool = False) -> None:
        """``parsec_context_add_taskpool`` (``scheduling.c:850``).

        Thread-safe and **live**: may be called from any thread while the
        workers are running (the serving shape, ``parsec_tpu/serve/``).
        The whole enqueue — comm-id reservation, termdet arming, startup
        enumeration, initial schedule — runs under ``_submit_lock``, so
        concurrent submissions keep the rank-agreed taskpool-id sequence
        consistent and never interleave their startup pushes.

        ``local_only`` marks a rank-private pool (nested pools spawned by
        recursive task bodies, ``runtime/recursive.py``): it gets a local
        termination detector and NO comm id, so it never participates in
        the wire protocol and ranks may enqueue different numbers of them
        without desynchronizing the rank-agreed taskpool id sequence."""
        spans.phase_refresh()
        with spans.phase("ctx.add_taskpool"):
            with self._submit_lock:
                self._add_taskpool_locked(tp, local_only)

    def _add_taskpool_locked(self, tp: Taskpool,
                             local_only: bool) -> None:  # lint: holds(_submit_lock)
        if _params.get("analysis_check"):
            # verify BEFORE any side effect (id reservation, termdet arm):
            # a rejected pool leaves the context untouched.  DTD pools are
            # empty at enqueue — their check runs at close()/validate().
            from ..ptg.dsl import PTGTaskpool
            if isinstance(tp, PTGTaskpool):
                from ..analysis import check_taskpool
                check_taskpool(tp, nb_ranks=self.nb_ranks,
                               raise_on_error=True)
        tp.context = self
        tp.local_only = local_only = tp.local_only or local_only
        pins.fire(PinsEvent.TASKPOOL_INIT, None, tp)
        if tp.tdm is None:
            # precedence: rank-private forces local > per-pool selection
            # (JDF_PROP_TERMDET_NAME) > MCA param > local
            name = "local" if local_only else \
                (tp.termdet_name or _params.get("termdet") or "local")
            tp.tdm = repository.query("termdet", requested=name).open(self)
        tp.tdm.monitor_taskpool(tp, tp.terminated)
        with self._lock:
            self._active_taskpools.append(tp)
            if local_only:
                tp.comm_id = None
            else:
                self.taskpool_list.append(tp)
                self._next_comm_id += 1
                tp.comm_id = self._next_comm_id
        if tp.on_enqueue is not None:
            tp.on_enqueue(tp)
        n = tp.nb_local_tasks()
        if n >= 0:
            tp.tdm.taskpool_addto_nb_tasks(n)
        startup = tp.startup(self)
        tp.tdm.ready()
        if not local_only:
            # found by comm id only once its tasks are counted: an activation
            # that arrived earlier waits for taskpool_registered's replay, or
            # the task it releases would complete on a zero counter
            with self._lock:
                self._tp_by_comm_id[tp.comm_id] = tp
            if self.comm_engine is not None:
                self.comm_engine.taskpool_registered(tp)
        if startup:
            schedule_tasks(self._submit_es, list(startup), 0)

    def record_failure(self, e: BaseException) -> None:
        """Record a fatal background/driver failure (first one wins) and
        wake every waiter — the one locked path all recording sites share
        (worker threads, the comm thread, the caller-driven loop)."""
        with self._lock:
            if self._worker_error is None:
                self._worker_error = e
            self._cond.notify_all()
            listeners = list(self._failure_listeners)
        for cb in listeners:            # outside the lock: a listener may
            try:                        # fail tickets / take its own locks
                cb(e)
            except Exception:
                pass        # diagnostics must never mask the poison

    def add_failure_listener(
            self, cb: Callable[[BaseException], None]) -> None:
        """Observe context poison (the serving layer fails its in-flight
        tickets from here).  Fires immediately if already poisoned."""
        with self._lock:
            err = self._worker_error
            if err is None:
                self._failure_listeners.append(cb)
                return
        cb(err)

    def start(self) -> None:
        """``parsec_context_start``: open the barrier, wake the comm thread."""
        with self._lock:
            self.started = True
        path = _params.get("props_stream")
        if path and self._props_stop is None:
            from ..prof.counters import properties
            self._props_stop = properties.stream_to(
                path, _params.get("props_stream_interval"))
        interval = _params.get("prof_snapshot_interval")
        if interval > 0 and not self._snap_started:
            from ..prof import flight_recorder
            flight_recorder.snapshotter.start(interval)
            self._snap_started = True
        if self.comm_engine is not None:
            self.comm_engine.enable()
        self._start_barrier.set()
        with self._cond:
            self._cond.notify_all()

    def test(self, tp: Taskpool | None = None) -> bool:
        """``parsec_context_test`` — with ``tp``, the per-taskpool probe
        (``parsec_taskpool_test``): one submission's completion can be
        checked without asking about the whole context."""
        if tp is not None:
            return tp.test()
        with self._lock:
            return not self._active_taskpools

    def _live_desc(self, limit: int = 8) -> str:
        """Name the still-live taskpools (with their termdet counters) for
        timeout messages and stall-dump reasons — a serving context holds
        many concurrent pools and 'context wait timed out' alone says
        nothing about WHICH submission wedged."""
        with self._lock:
            pools = list(self._active_taskpools)
        if not pools:
            return "no live taskpools"
        parts = []
        for tp in pools[:limit]:
            nb = tp.tdm.snapshot()["nb_tasks"] if tp.tdm is not None \
                else "?"
            parts.append(f"{tp.name}[nb_tasks={nb}]")
        more = f" +{len(pools) - limit} more" if len(pools) > limit else ""
        return f"{len(pools)} live taskpools: " + ", ".join(parts) + more

    def wait(self, timeout: float | None = None) -> None:
        """``parsec_context_wait``: block until every taskpool completes.
        A deadline expiry raises :class:`ContextWaitTimeout` — and first
        fires the flight-recorder stall dump, so a wedged run produces a
        diagnosis (every worker's last events, queue depths, in-flight
        comm, device state) instead of silence."""
        if not self.started:
            self.start()
        try:
            self._drive_until(self.test, timeout)
        except ContextWaitTimeout:
            self._stall_dump(f"context wait timed out (timeout={timeout}s; "
                             f"{self._live_desc()})")
            raise

    def wait_taskpool(self, tp: Taskpool,
                      timeout: float | None = None) -> None:
        """Block until ONE taskpool completes — ``parsec_taskpool_wait``
        driven through the context, so a single live submission can be
        awaited without draining everything else.  Deadline expiry raises
        :class:`ContextWaitTimeout` (after the stall dump), naming the
        awaited pool and every still-live one."""
        if not self.started:
            self.start()
        try:
            self._drive_until(tp.test, timeout)
        except ContextWaitTimeout:
            self._stall_dump(
                f"taskpool {tp.name} wait timed out (timeout={timeout}s; "
                f"{self._live_desc()})")
            raise

    def _stall_dump(self, reason: str) -> dict | None:
        if not _params.get("prof_stall_dump"):
            return None
        try:
            from ..prof import flight_recorder
            self.last_stall_report = flight_recorder.stall_dump(self, reason)
        except Exception:      # the dump must never mask the timeout
            pass
        return self.last_stall_report

    def fini(self, timeout: float | None = None) -> None:
        """``parsec_fini``: drain, stop workers, release the scheduler.
        A poisoned context (a recorded worker/driver failure) skips the
        drain — its taskpools can never complete — and tears down like
        :meth:`abort`; if no caller has seen the failure yet (it was
        recorded by a background thread and never raised from a wait),
        it is re-raised AFTER teardown so a crash cannot read as clean
        success.

        ``timeout`` bounds the drain (callers whose wait() already timed
        out pass their expired deadline's remainder: an unbounded fini on
        a wedged device would hang forever in the exact cleanup path
        added for the timed-out case).  On expiry the stall
        dump fires (via :meth:`wait`) and teardown falls through
        abort-style."""
        spans.phase_refresh()
        if self._worker_error is None and not self.test():
            try:
                if not self.started:
                    self.start()
                self._drive_until(self.test, timeout)
            except ContextWaitTimeout:
                # tear down abort-style below; dump only if a timed-out
                # wait() didn't already (a caller's finally re-enters with
                # the expired deadline — one diagnosis per stall, not two)
                if self.last_stall_report is None:
                    self._stall_dump(
                        f"fini drain timed out (timeout={timeout}s)")
        with spans.phase("ctx.fini"):
            with self._lock:
                self._shutdown = True
                self._cond.notify_all()
            self._start_barrier.set()
            for t in self._threads:
                t.join(timeout=5)
            self.scheduler.remove(self)
            if self.comm_engine is not None:
                self.comm_engine.fini()
            self._props_teardown()
        if self._worker_error is not None and not self._error_surfaced:
            self._error_surfaced = True
            raise RuntimeError(
                "a background thread failed") from self._worker_error

    def __enter__(self) -> "Context":
        return self

    def __exit__(self, *exc) -> None:
        if exc[0] is None:
            self.fini()
        else:
            self.abort()

    def abort(self) -> None:
        """Stop workers without draining (exception-path teardown)."""
        with self._lock:
            self._shutdown = True
            self._cond.notify_all()
        self._start_barrier.set()
        for t in self._threads:
            t.join(timeout=5)
        self.scheduler.remove(self)
        self._props_teardown()

    def _props_teardown(self) -> None:
        # the release counters join the process's totals, once each
        now = (self.release_edges, self.release_edges_planned)
        release_totals["edges"] += now[0] - self._release_folded[0]
        release_totals["planned"] += now[1] - self._release_folded[1]
        self._release_folded = now
        if self._props_stop is not None:
            self._props_stop()
            self._props_stop = None
        if self._snap_started:
            from ..prof import flight_recorder
            flight_recorder.snapshotter.release()
            self._snap_started = False
        from ..prof.counters import properties
        for name in ("sched_pending", "active_taskpools", "nb_tasks", "sde"):
            properties.unregister(self._props_ns, name)

    # ------------------------------------------------------- progress loops
    def _bind_worker(self, es: ExecutionStream) -> None:
        """Pin this worker to a core (the hwloc thread-binding analog,
        ``parsec_hwloc_bind_on_core_index``): round-robin over the
        affinity mask the process started with."""
        if not _params.get("runtime_bind_threads"):
            return
        try:
            allowed = sorted(os.sched_getaffinity(0))
            core = allowed[es.th_id % len(allowed)]
            os.sched_setaffinity(0, {core})
        except (AttributeError, OSError):
            pass    # non-Linux or restricted: binding is best-effort

    def _worker_main(self, es: ExecutionStream) -> None:
        es.owner_ident = threading.get_ident()
        self._bind_worker(es)
        self._start_barrier.wait()
        backoff = Backoff()
        while True:
            if self._shutdown:
                return
            try:
                task, distance = select_task(es)
                if task is None:
                    if self.comm_engine is not None and es.th_id == 0:
                        self.comm_engine.progress(es)
                    backoff.wait()
                    continue
                backoff.reset()
                task_progress(es, task, distance)
                # fragmented GETs in flight: a BUSY worker still advances
                # the pipeline between tasks (credit acks, fragment
                # copies) — the T3-style compute/transfer overlap.  The
                # gate is one lock-free int read, so task dispatch with
                # no comm in flight pays a branch, nothing more.
                ce = self.comm_engine
                if ce is not None and es.th_id == 0 \
                        and getattr(ce.ce, "_frag_active", 0):
                    ce.progress(es)
            except BaseException as e:   # surface to waiters, don't hang
                self.record_failure(e)
                return

    def _drive_until(self, predicate: Callable[[], bool],
                     timeout: float | None = None) -> None:
        """Progress from the calling thread until ``predicate`` holds.
        Any failure that escapes to the caller (other than this wait's
        own deadline expiry) marks the recorded context poison as
        *surfaced* — fini() re-raises only failures nobody ever saw."""
        try:
            self._drive_until_inner(predicate, timeout)
        except BaseException as e:
            if not isinstance(e, ContextWaitTimeout):
                self._error_surfaced = True
            raise

    def _drive_until_inner(self, predicate: Callable[[], bool],
                           timeout: float | None = None) -> None:
        """With workers, just wait on the condition; without, run the hot
        loop inline (master-thread funneled mode)."""
        if not self.started:
            self.start()
        deadline = None if timeout is None else time.monotonic() + timeout
        if self._threads:
            while True:
                with self._cond:
                    if self._worker_error is not None:
                        raise RuntimeError(
                            "a worker thread failed") from self._worker_error
                    if predicate():
                        return
                    rem = None if deadline is None else \
                        deadline - time.monotonic()
                    if rem is not None and rem <= 0:
                        raise ContextWaitTimeout(
                            "context wait timed out; " + self._live_desc())
                    # wake on termination or a worker error
                    ok = self._cond.wait_for(
                        lambda: predicate()
                        or self._worker_error is not None, rem)
                    if not ok:
                        raise ContextWaitTimeout(
                            "context wait timed out; " + self._live_desc())
        with spans.phase("ctx.progress"):
            self._drive_inline(predicate, deadline)

    def _drive_inline(self, predicate: Callable[[], bool],
                      deadline: float | None) -> None:
        """The hot loop on the calling thread (master-thread funneled
        mode), until ``predicate`` holds."""
        es = self._submit_es
        es.owner_ident = threading.get_ident()
        backoff = Backoff()
        while not predicate():
            if self._worker_error is not None:
                # a dedicated comm thread records failures here too; the
                # caller-driven loop must surface them, not spin to timeout
                raise RuntimeError(
                    "a background thread failed") from self._worker_error
            if deadline is not None and time.monotonic() > deadline:
                raise ContextWaitTimeout(
                    "context wait timed out; " + self._live_desc())
            try:
                task, distance = select_task(es)
                if task is None:
                    if self.comm_engine is not None:
                        self.comm_engine.progress(es)
                    if predicate():
                        return
                    backoff.wait()
                    continue
                backoff.reset()
                task_progress(es, task, distance)
                # same busy-path overlap gate as _worker_main: fragments
                # keep flowing while the drive loop executes tasks
                ce = self.comm_engine
                if ce is not None and getattr(ce.ce, "_frag_active", 0):
                    ce.progress(es)
            except ContextWaitTimeout:
                raise    # deadline expiry is not a context poison
            except TimeoutError as e:
                self.record_failure(e)   # a body's timeout IS a failure
                raise
            except BaseException as e:
                # an unrecoverable failure in the inline drive (device
                # fail-stop escalation, comm progress on a dead peer)
                # poisons the context: record it so a later fini() tears
                # down instead of re-draining a pool that can never
                # complete
                self.record_failure(e)
                raise

    # ----------------------------------------------------------- internals
    def _taskpool_terminated(self, tp: Taskpool) -> None:
        with self._lock:
            if tp in self._active_taskpools:
                self._active_taskpools.remove(tp)
            if self.comm_engine is None and tp.comm_id is not None:
                # long-lived (serving) contexts must not accumulate every
                # pool they ever ran; without a comm engine nothing can
                # look a terminated pool up by comm id again.  With one,
                # pools stay registered (late wire messages may resolve).
                self._tp_by_comm_id.pop(tp.comm_id, None)
                if tp in self.taskpool_list:
                    self.taskpool_list.remove(tp)
            self._cond.notify_all()
        # reclaim any dep-tracker state the taskpool left behind (nothing in
        # the normal case; an aborted pool would otherwise leak stashed
        # inputs for the context lifetime — the k64 space is context-wide)
        self.deps.purge_taskpool(tp.taskpool_id)

    def comm_barrier(self) -> None:
        """Collective fence: progress until the fabric is globally silent.

        Required before reading data written by a *remote* rank's writeback
        edge — local taskpool termination only covers local tasks plus this
        rank's own in-flight sends (the one-sided-semantics fence)."""
        if self.comm_engine is not None:
            self.comm_engine.quiesce()

    # remote-dep seams, delegated to the comm layer (SURVEY §3.4)
    def remote_dep_accumulate(self, remote, task, flow, dep, succ_tc,
                              succ_locals, rank):
        if self.comm_engine is None:
            raise RuntimeError("remote successor but no comm engine installed")
        return self.comm_engine.accumulate(remote, task, flow, dep, succ_tc,
                                           succ_locals, rank)

    def remote_dep_activate(self, es, task, remote) -> None:
        if self.comm_engine is None:
            raise RuntimeError("remote deps but no comm engine installed")
        self.comm_engine.activate(es, task, remote)
