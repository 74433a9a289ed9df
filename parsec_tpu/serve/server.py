"""The persistent runtime server: a long-lived hot Context serving
concurrent DAG submissions.

A batch :class:`~parsec_tpu.runtime.context.Context` runs enqueue →
start → wait → fini once; the process pays worker spin-up, scheduler
install, and (dominantly) lowering/compile on every request.  The ROADMAP
north star is the opposite shape — a resident runtime absorbing a stream
of independent DAG requests from many clients (MPK, arxiv 2512.22219,
makes the same amortize-over-a-resident-runtime argument) — and the PR-2
persistent lowering cache (warm ~0.4 ms vs ~130 ms cold) only pays off
when the process outlives a single DAG.

:class:`RuntimeServer` keeps one Context's workers running and gives
every client thread::

    server = RuntimeServer(nb_cores=2, tenant_weights={"pro": 4.0})
    ticket = server.submit(taskpool, tenant="pro", priority=1,
                           deadline=0.5)
    result = ticket.result(timeout=30)     # this submission only
    server.drain(timeout=60)               # stop admitting, finish, fini

Pieces:

- **Ticket** — per-submission completion promise over ``core/future.py``
  (``result() / done() / cancel()``), resolved by per-taskpool
  termination detection (``runtime/termdet.py``) — no context drain.
- **Admission** — :class:`~parsec_tpu.serve.admission.AdmissionController`
  budgets (MCA params), blocking backpressure or typed shed.
- **Fairness** — :class:`~parsec_tpu.serve.fair.FairScheduler` wraps the
  context's scheduler: weighted tenant share + priority + deadline
  instead of arrival order.
- **Observability** — every stage fires a ``SERVE_*`` PINS event, so the
  flight recorder, stall dumps, and ``prof.export_run_report()`` cover
  serving with zero extra wiring (``docs/SERVING.md``).
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable

from ..core.future import Future
from ..core.params import params as _params
from ..prof import flight_recorder as _flightrec
from ..prof import pins, spans as _spans
from ..prof.histogram import SLOPlane
from ..prof.pins import PinsEvent
from ..runtime.context import Context, ContextWaitTimeout
from ..runtime.taskpool import Taskpool
from .admission import (AdmissionController, AdmissionRejected,
                        TicketCancelled)
from .fair import FairScheduler

_params.register("serve_num_cores", 2,
                 "worker threads a RuntimeServer's context runs with "
                 "(serving requires >= 1: clients block on tickets, not "
                 "on driving progress)")


class _Submission:
    """The per-submission record the fair scheduler keys on
    (``taskpool._serve_sub``)."""

    __slots__ = ("tenant", "priority", "deadline_at", "cost", "ticket",
                 "result_fn", "released")

    def __init__(self, tenant: str, priority: int,
                 deadline_at: float | None, cost: int,
                 ticket: "Ticket",
                 result_fn: Callable[[Taskpool], Any] | None) -> None:
        self.tenant = tenant
        self.priority = priority
        self.deadline_at = deadline_at
        self.cost = cost
        self.ticket = ticket
        self.result_fn = result_fn
        self.released = False       # admission released exactly once


class Ticket:
    """A submission's handle: state, timing, and a single-assignment
    result future.  States walk ``queued`` → ``running`` → ``done`` /
    ``failed``, or end early at ``rejected`` / ``cancelled``."""

    def __init__(self, server: "RuntimeServer", name: str, tenant: str,
                 priority: int, deadline_at: float | None) -> None:
        self._server = server
        self.name = name
        self.tenant = tenant
        self.priority = priority
        self.deadline_at = deadline_at
        self.state = "queued"
        self.deadline_missed = False
        self.submitted_at = time.monotonic()
        self.admitted_at: float | None = None
        self.started_at: float | None = None
        self.completed_at: float | None = None
        # the request's trace context (prof/spans.py): minted at submit,
        # attached to the taskpool, carried across ranks by the wire
        self.trace = _spans.new_trace()
        self._future: Future = Future()
        self._slock = threading.Lock()
        self._settled = False
        self._cancelled = False

    # -- client API ------------------------------------------------------
    def result(self, timeout: float | None = None) -> Any:
        """Block for THIS submission's completion (the context keeps
        serving others).  Raises the stored failure for failed/rejected/
        cancelled tickets; ``TimeoutError`` on deadline."""
        kind, v = self._future.get(timeout)
        if kind == "err":
            raise v
        return v

    def done(self) -> bool:
        return self._future.is_ready()

    def cancel(self) -> bool:
        """Cancel while still queued for admission.  Returns ``True`` when
        the cancellation will take effect; ``False`` once the submission
        started executing (a live DAG cannot be safely unpicked from the
        dependence trackers) or already finished."""
        with self._slock:
            if self._settled:
                return self.state == "cancelled"
            if self.state != "queued":
                return False
            self._cancelled = True
        self._server._adm.kick()
        return True

    @property
    def latency_s(self) -> float | None:
        if self.completed_at is None:
            return None
        return self.completed_at - self.submitted_at

    # -- settlement (exactly once) --------------------------------------
    def _commit_start(self) -> bool:
        """The queued → running transition, serialized against
        :meth:`cancel` under ``_slock``: exactly one of them wins.  False
        = a cancel landed first and the submission must shed."""
        with self._slock:
            if self._cancelled or self._settled:
                return False
            self.state = "running"
            return True

    def _resolve(self, value: Any) -> bool:
        """Returns True iff THIS call settled the ticket — settlement is
        exactly-once, and the caller that wins owns the stats count."""
        with self._slock:
            if self._settled:
                return False
            self._settled = True
            self.state = "done"
        self.completed_at = time.monotonic()
        if self.deadline_at is not None and \
                self.completed_at > self.deadline_at:
            self.deadline_missed = True
        self._future.set(("ok", value))
        return True

    def _fail(self, exc: BaseException, state: str = "failed") -> bool:
        with self._slock:
            if self._settled:
                return False
            self._settled = True
            self.state = state
        self.completed_at = time.monotonic()
        self._future.set(("err", exc))
        return True


class RuntimeServer:
    """A resident runtime accepting concurrent taskpool submissions.

    Construction starts the context's workers immediately; the server is
    hot until :meth:`drain`.  Usable as a context manager (``__exit__``
    drains)."""

    def __init__(self, nb_cores: int | None = None,
                 scheduler: str | None = None,
                 tenant_weights: dict[str, float] | None = None,
                 admission: AdmissionController | None = None,
                 context: Context | None = None) -> None:
        if context is not None:
            self._ctx = context
        else:
            if nb_cores is None:
                nb_cores = _params.get("serve_num_cores")
            self._ctx = Context(nb_cores=nb_cores, scheduler=scheduler)
        if self._ctx.nb_cores < 1:
            raise ValueError(
                "RuntimeServer needs a context with worker threads "
                "(nb_cores >= 1): clients block on tickets, nobody "
                "drives a caller-driven context")
        # interpose the fair shim before the workers pass the start
        # barrier — they resolve context.scheduler per select call.  A
        # context built with ``scheduler="serve_fair"`` (the MCA-exposed
        # shim, sched/modules.py) already has one: reuse, never stack.
        if isinstance(self._ctx.scheduler, FairScheduler):
            self._fair = self._ctx.scheduler
        else:
            self._fair = FairScheduler(self._ctx.scheduler)
            self._fair.attach(self._ctx)
            self._ctx.scheduler = self._fair
        for tenant, w in (tenant_weights or {}).items():
            self._fair.set_weight(tenant, w)
        self._adm = admission if admission is not None \
            else AdmissionController()
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._inflight: set[Ticket] = set()
        self._draining = False
        self._drained = threading.Event()
        self._poison: BaseException | None = None
        self.submitted = 0
        self.completed = 0
        self.failed = 0
        self.rejected = 0
        self.per_tenant_completed: dict[str, int] = {}
        # per-tenant tuning-DB consult memo (parsec_tpu/tune,
        # ``tune_db=1``): a tenant's FIRST submit_stream probes
        # ``ambient:tenant:<t>`` once and seeds the batcher's adaptive
        # controller from the stored vector
        self._tenant_consulted: set[str] = set()
        self._llm = None            # lazy ContinuousBatcher (submit_stream)
        # the per-tenant SLO metrics plane (prof/histogram.py): queue
        # wait, end-to-end latency, admission sheds here; the LLM
        # batcher adds TTFT + inter-token latency.  runtime_report's
        # `slo` block and the live `slo` property aggregate it for free.
        self._slo = SLOPlane()
        self._drain_s: float | None = None
        # stall dumps name WHOSE request is stuck: per-tenant inflight
        # counts + the oldest live trace id (flight_recorder sections).
        # Registered through a weakref — the global registry must never
        # keep a leaked (never-drained) server alive.
        import weakref
        self._stall_key = f"serve@{id(self):x}"
        ref = weakref.ref(self)

        def _section() -> dict:
            s = ref()
            return s._stall_section() if s is not None else {}

        _flightrec.register_stall_section(self._stall_key, _section)
        self._ctx.add_failure_listener(self._on_context_failure)
        self._ctx.start()

    # -- submission ------------------------------------------------------
    def submit(self, tp: Taskpool, *, tenant: str = "default",
               priority: int = 0, deadline: float | None = None,
               block: bool = True,
               result_fn: Callable[[Taskpool], Any] | None = None
               ) -> Ticket:
        """Submit one taskpool; returns its :class:`Ticket`.

        ``priority`` ranks within the tenant (higher first);
        ``deadline`` is a relative budget in seconds — expiry while
        *queued for admission* sheds (:class:`DeadlineExceeded`), expiry
        after start only flags ``ticket.deadline_missed``.  ``block``
        picks backpressure (wait for budget, bounded by
        ``serve_admission_timeout``) vs immediate shed.  ``result_fn(tp)``
        computes the ticket's value at completion (default: the taskpool
        itself — read your collections off it).

        Served pools run the dynamic scheduler, so the weighted-fair shim
        interleaves tenants at task grain."""
        deadline_at = None if deadline is None \
            else time.monotonic() + deadline
        ticket = Ticket(self, tp.name, tenant, priority, deadline_at)
        pins.fire(PinsEvent.SERVE_SUBMIT, None, (tenant, tp.name))
        with self._lock:
            self.submitted += 1
            closed = self._draining or self._poison is not None
        cost = 1
        if self._adm.max_inflight_tasks:
            n = tp.nb_local_tasks()
            cost = n if n > 0 else _params.get("serve_default_task_cost")
        try:
            if closed:
                raise AdmissionRejected(
                    "server is draining" if self._poison is None
                    else "server context is poisoned")
            self._adm.admit(tenant, cost, block=block,
                            deadline_at=deadline_at,
                            cancelled=lambda: ticket._cancelled)
        except AdmissionRejected as e:
            pins.fire(PinsEvent.SERVE_REJECT, None, (tenant, tp.name))
            with self._lock:
                self.rejected += 1
            if not isinstance(e, TicketCancelled):
                # a voluntary client cancel is NOT an admission shed:
                # the SLO counter must attribute only controller/drain
                # pressure, or operators read cancels as backpressure
                self._slo.inc(tenant, "admission_sheds")
            ticket._fail(e, state="cancelled"
                         if isinstance(e, TicketCancelled) else "rejected")
            raise
        pins.fire(PinsEvent.SERVE_ADMIT, None, (tenant, tp.name))
        ticket.admitted_at = time.monotonic()
        wait_s = ticket.admitted_at - ticket.submitted_at
        self._slo.observe(tenant, "admission_wait_ms", wait_s * 1e3)
        r = _spans.recorder
        if r is not None:
            t1 = time.perf_counter_ns()
            r.record("serve.admission", ticket.trace.trace_id,
                     t1 - int(wait_s * 1e9), t1, tenant=tenant)
        sub = _Submission(tenant, priority, deadline_at, cost, ticket,
                          result_fn)
        tp._serve_sub = sub
        # check-and-register atomically: a drain that began while this
        # thread sat inside admit() must either see the ticket in flight
        # (and wait for it) or shed it here — never tear the context down
        # under a submission registering concurrently.  The queued →
        # running commit also happens BEFORE enqueue and is serialized
        # against cancel(): a cancel() that returned True can never see
        # its submission execute anyway.
        started = ticket._commit_start()
        with self._lock:
            closed = self._draining or self._poison is not None
            if started and not closed:
                self._inflight.add(ticket)
            else:
                self.rejected += 1
        if not started or closed:
            self._adm.release(tenant, cost)
            if started:
                # shed by the drain window; !started is a client cancel
                # and stays out of the admission_sheds attribution
                self._slo.inc(tenant, "admission_sheds")
            pins.fire(PinsEvent.SERVE_REJECT, None, (tenant, tp.name))
            e: AdmissionRejected = TicketCancelled(
                "ticket cancelled before start") if not started \
                else AdmissionRejected("server is draining")
            ticket._fail(e, state="cancelled" if not started
                         else "rejected")
            raise e
        # listener BEFORE enqueue: a trivial pool may terminate inside
        # add_taskpool and must still resolve the ticket.  START fires
        # before enqueue for the same reason — a synchronously-completing
        # pool must record SUBMIT → ADMIT → START → COMPLETE in order
        pins.fire(PinsEvent.SERVE_START, None, (tenant, tp.name))
        ticket.started_at = time.monotonic()
        # the request's trace rides the pool: task-grain spans and the
        # cross-rank wire protocol key off tp._trace from here on
        tp._trace = ticket.trace
        if _spans.recorder is not None:
            tp._trace_enq_ns = time.perf_counter_ns()
        tp.add_completion_listener(self._on_pool_done)
        try:
            self._ctx.add_taskpool(tp)
        except BaseException as e:
            # exactly-once release: the pool may have gone live before the
            # exception, in which case _on_pool_done will still fire at
            # termination — it must not release the budget a second time
            self._release_once(sub)
            with self._lock:
                self._inflight.discard(ticket)
                self.rejected += 1
                self._cond.notify_all()
            pins.fire(PinsEvent.SERVE_REJECT, None, (tenant, tp.name))
            ticket._fail(e, state="rejected")
            raise
        return ticket

    def _release_once(self, sub: _Submission) -> bool:
        """Release a submission's admission budget exactly once — the
        failed-enqueue path and the completion listener can both reach
        it, and a double release would silently loosen the high-water
        marks for the server's lifetime."""
        with self._lock:
            if sub.released:
                return False
            sub.released = True
        self._adm.release(sub.tenant, sub.cost)
        return True

    def submit_lowered(self, tp: Taskpool, **kw: Any) -> Ticket:
        """Submit a PTG pool through the **compiled** incarnation: the
        request executes as one ``lower_taskpool(tp).jitted()`` call on a
        worker thread, and the ticket resolves to the output stores (a
        ``{name: np.ndarray}`` dict).  Repeat submissions of a
        structurally identical class hit the process-wide PR-2
        ``lowering_cache`` and skip trace+compile entirely — the warm
        path that makes a resident server worth keeping hot."""
        import numpy as np

        from .. import ptg as _ptg

        out: dict[str, Any] = {}
        p = _ptg.PTGBuilder(f"lowered:{tp.name}")
        t = p.task("RUN", i=_ptg.span(0, lambda g, l: 0))
        t.flow("ctl", _ptg.CTL)

        def body(es: Any, task: Any, g: Any, l: Any) -> None:
            from ..ptg.lowering import lower_taskpool
            low = lower_taskpool(tp)
            res = low.jitted()(low.initial_stores())
            out["stores"] = {k: np.asarray(v) for k, v in res.items()}

        t.body(body)
        kw.setdefault("result_fn", lambda _tp: out["stores"])
        return self.submit(p.build(), **kw)

    def submit_stream(self, prompt_tokens, *, max_new_tokens: int = 16,
                      tenant: str = "default", priority: int = 0,
                      eos: int | None = None, fork_from=None):
        """Open an LLM generation stream — the session abstraction over
        this server's continuous batcher (``parsec_tpu/llm/batcher.py``;
        ``docs/LLM.md``).  The first call creates the batcher (paged KV
        cache + decode loop thread); every stream then rides the
        iteration-level batch: k-step decode superpools submitted under
        the stream's ``tenant``, so WFQ arbitrates decode against any
        other workload this server carries.  ``eos`` stops generation
        when sampled (handled in-graph by the predicated SAMPLE bodies);
        ``fork_from`` names an earlier stream's ticket with the same
        prompt — the new stream forks its prompt KV copy-on-write
        (``PagedKVCollection.fork``) instead of re-prefilling, so N
        continuations of one prompt share one physical copy of the
        prompt pages until their first divergent write
        (``docs/SERVING.md``).  Returns a
        :class:`~parsec_tpu.llm.batcher.StreamTicket`."""
        with self._lock:
            if self._draining or self._poison is not None:
                raise AdmissionRejected(
                    "server is draining" if self._poison is None
                    else "server context is poisoned")
            if self._llm is None:
                from ..llm.batcher import ContinuousBatcher
                # on a multirank context the batcher's collections pin
                # to THIS rank: decode pools are enqueued here only, so
                # default (rank 0) tile ownership would shell the work
                # out to a rank that never sees the pool
                own = self._ctx.my_rank if self._ctx.nb_ranks > 1 else None
                self._llm = ContinuousBatcher(self, owner_rank=own)
            llm = self._llm
            if tenant not in self._tenant_consulted:
                self._tenant_consulted.add(tenant)
                try:
                    from ..tune import consult_ambient
                    knobs = consult_ambient(f"tenant:{tenant}")
                    if knobs:
                        llm.seed_tenant_knobs(tenant, knobs)
                except Exception:       # noqa: BLE001 — a corrupt tuning
                    pass                # DB must never shed a stream
        return llm.submit_stream(prompt_tokens,
                                 max_new_tokens=max_new_tokens,
                                 tenant=tenant, priority=priority,
                                 eos=eos, fork_from=fork_from)

    # -- completion / failure -------------------------------------------
    def _on_pool_done(self, tp: Taskpool) -> None:
        sub: _Submission = tp._serve_sub
        tp._serve_sub = None
        if self._release_once(sub):
            # only the releasing call announces completion: a pool whose
            # enqueue path already shed (and released) must not add a
            # spurious SERVE_COMPLETE for a submission reported rejected
            pins.fire(PinsEvent.SERVE_COMPLETE, None, (sub.tenant, tp.name))
        ok = False
        try:
            value = sub.result_fn(tp) if sub.result_fn is not None else tp
        except BaseException as e:       # a result_fn bug fails ONE ticket
            settled = sub.ticket._fail(e)
        else:
            settled = ok = sub.ticket._resolve(value)
        with self._lock:
            self._inflight.discard(sub.ticket)
            # only the call that SETTLED the ticket counts it: one already
            # failed by a drain timeout or a poison sweep completing late
            # must not inflate failed (or completed) a second time
            if ok:
                self.completed += 1
                self.per_tenant_completed[sub.tenant] = \
                    self.per_tenant_completed.get(sub.tenant, 0) + 1
            elif settled:
                self.failed += 1
            self._cond.notify_all()
        tk = sub.ticket
        if ok and tk.completed_at is not None:
            # the request's SLO samples: submit -> start (admission +
            # queue) and the end-to-end ticket latency
            if tk.started_at is not None:
                self._slo.observe(sub.tenant, "queue_wait_ms",
                                  (tk.started_at - tk.submitted_at) * 1e3)
            lat = tk.completed_at - tk.submitted_at
            self._slo.observe(sub.tenant, "latency_ms", lat * 1e3)
            r = _spans.recorder
            if r is not None:
                t1 = time.perf_counter_ns()
                r.record("serve.request", tk.trace.trace_id,
                         t1 - int(lat * 1e9), t1, tenant=sub.tenant,
                         args={"pool": tp.name})

    def _on_context_failure(self, e: BaseException) -> None:
        """Context poison (a worker died): fail every in-flight ticket so
        no client blocks forever, and stop admitting."""
        self._adm.close()
        with self._lock:
            self._poison = e
            pending = list(self._inflight)
            self._inflight.clear()
            self._cond.notify_all()
        nfailed = 0
        for tk in pending:
            err = RuntimeError(
                f"runtime context failed while serving {tk.name!r}")
            err.__cause__ = e
            nfailed += tk._fail(err)    # a concurrently-resolving ticket
        with self._lock:                # keeps its own (done) count
            self.failed += nfailed

    # -- lifecycle -------------------------------------------------------
    def drain(self, timeout: float | None = None) -> None:
        """Graceful shutdown: stop admitting, let in-flight submissions
        finish, then ``fini`` the context.  On ``timeout`` expiry the
        remaining tickets fail with :class:`ContextWaitTimeout` and the
        context tears down abort-style (stall dump fires) — the server is
        DOWN either way when this returns/raises."""
        t_drain0 = time.monotonic()
        with self._lock:
            llm = self._llm
        if llm is not None:
            # the batcher submits a pool per decode iteration: let its
            # live streams finish (bounded) BEFORE admission closes, or
            # every mid-generation stream would shed at the door.  stop()
            # is join-idempotent, so concurrent drains may both call it.
            llm.stop(timeout=timeout)
        with self._lock:
            first = not self._draining
            self._draining = True
        if not first:
            # a concurrent drain owns the teardown: wait for IT to finish
            # — returning on mere inflight-emptiness would hand back a
            # server whose workers are still being joined
            if not self._drained.wait(timeout):
                raise ContextWaitTimeout(
                    "concurrent drain still in progress")
            return
        self._adm.close()
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cond:
            ok = self._cond.wait_for(
                lambda: not self._inflight,
                None if deadline is None
                else max(0.0, deadline - time.monotonic()))
            leftover = [] if ok else list(self._inflight)
            # wedged submissions leave the books with their tickets: a
            # stale inflight set would wedge every LATER drain() and lie
            # in stats() forever
            self._inflight.clear()
        pins.fire(PinsEvent.SERVE_DRAIN, None,
                  ("-", f"inflight={len(leftover)}"))
        nfailed = 0
        for tk in leftover:
            nfailed += tk._fail(ContextWaitTimeout(
                f"server drain timed out with {tk.name!r} still in flight"))
        with self._lock:
            self.failed += nfailed
        rem = None if deadline is None \
            else max(0.0, deadline - time.monotonic())
        try:
            self._ctx.fini(timeout=rem)
        finally:
            self._drained.set()     # the server is DOWN, success or not
            self._drain_s = time.monotonic() - t_drain0
            self._slo.observe("_server", "drain_ms", self._drain_s * 1e3)
            _flightrec.unregister_stall_section(self._stall_key)
        if leftover:
            raise ContextWaitTimeout(
                f"server drain timed out ({len(leftover)} submissions "
                f"still in flight)")

    def __enter__(self) -> "RuntimeServer":
        return self

    def __exit__(self, *exc: Any) -> None:
        if exc[0] is None:
            self.drain()
        else:
            # exception-path teardown: fail every in-flight ticket FIRST
            # (abort() records no context poison, so no failure listener
            # would fire) — a client blocked in result() must get a
            # prompt server-shutdown error, not its own full timeout
            self._on_context_failure(
                exc[1] if exc[1] is not None
                else RuntimeError("server aborted"))
            with self._lock:
                self._draining = True
            self._ctx.abort()
            self._drained.set()
            _flightrec.unregister_stall_section(self._stall_key)

    # -- introspection ---------------------------------------------------
    @property
    def context(self) -> Context:
        return self._ctx

    def metrics(self) -> dict:
        """The live per-tenant SLO snapshot (docs/SERVING.md): quantile
        summaries off the histogram plane — TTFT and inter-token latency
        (LLM streams), queue wait, end-to-end latency, admission waits
        and sheds — callable MID-RUN with no synchronization against the
        serving path (histograms are read without locking; a racing
        record at worst misses the snapshot by one sample)."""
        with self._lock:
            inflight = len(self._inflight)
        out = {
            "tenants": self._slo.summary(),
            "inflight": inflight,
            "drain_s": self._drain_s,
            "admission": self._adm.stats(),
        }
        # critical-path attribution over the span plane — present only
        # when the recorder is installed (a drained server's post-mortem
        # reads where its requests' latency went without re-running)
        try:
            from ..prof import spans as _spans
            if _spans.recorder is not None and _spans.recorder.spans:
                from ..prof.critpath import summarize_recorder
                cp = summarize_recorder(compact=True)
                if cp:
                    out["critpath"] = cp
        except Exception:        # noqa: BLE001 — metrics never raise
            pass
        return out

    def _stall_section(self) -> dict:
        """Per-tenant inflight counts + the oldest live request's trace
        id — the stall-dump block that names WHOSE request is stuck."""
        with self._lock:
            tickets = list(self._inflight)
        now = time.monotonic()
        out: dict[str, dict] = {}
        for tk in tickets:
            d = out.setdefault(tk.tenant, {"inflight": 0,
                                           "oldest_trace_id": None,
                                           "oldest_age_s": -1.0,
                                           "oldest_pool": None})
            d["inflight"] += 1
            age = now - tk.submitted_at
            if age > d["oldest_age_s"]:
                d.update(oldest_trace_id=format(tk.trace.trace_id, "x"),
                         oldest_age_s=round(age, 3), oldest_pool=tk.name)
        return out

    def stats(self) -> dict:
        with self._lock:
            llm = self._llm
        extra = {"llm": llm.stats()} if llm is not None else {}
        with self._lock:
            return {
                **extra,
                "submitted": self.submitted,
                "completed": self.completed,
                "failed": self.failed,
                "rejected": self.rejected,
                "inflight": len(self._inflight),
                "draining": self._draining,
                "poisoned": self._poison is not None,
                "per_tenant_completed": dict(self.per_tenant_completed),
                "fair_dispatched": self._fair.dispatch_counts(),
                "admission": self._adm.stats(),
            }
