#!/usr/bin/env python
"""Critical-path micro-benchmarks: the per-task fixed cost, measured on CPU.

The runtime's value proposition is micro-task scheduling overhead in the low
microseconds (PAPER.md; MPK and Design-in-Tiles both argue the per-task fixed
cost, not the kernels, is the lever for fine-grained tensor programs).  This
harness measures exactly that fixed cost — select→prepare→exec→complete→
release — with NOTHING accelerator-dependent, so these host-side counts
stay measurable on a machine without a chip:

- ``bench_dispatch_us``        — per-task latency on the EP CTL DAG through
  the compiled-DAG executor (the headline ``task_dispatch_us`` series) and
  through the dynamic Python scheduler (``dynamic_dispatch_us``);
- ``bench_release_throughput`` — dep-release tasks/s through the dynamic
  path (``release_deps`` → batched ``DependencyTracking.release_many``);
- ``bench_steal_us``           — lfq local-pop and steal latency against the
  sharded per-stream deques (sched/modules.py);
- ``bench_pins_disabled_ns``   — cost of one DISABLED instrumentation site
  (the per-event dispatch-slot fast path, prof/pins.py);
- ``bench_tracing``            — request-tracing costs (prof/spans.py +
  prof/histogram.py): span record ns, SLO histogram record ns, and the
  enabled-vs-disabled dynamic dispatch delta (the ≤1µs/task budget);
- ``bench_lowering_cache``     — first-vs-second compile seconds of an
  identical lowered taskpool (the persistent lowering cache,
  ptg/lowering.py);
- ``bench_lowering``           — XLA calls per DAG and trace/compile
  seconds across the lowering modes (ISSUE 8): dynamic task-per-dispatch
  vs megakernel regions vs whole-pool wavefront/scan vs chain-collapse,
  on cholesky's irregular 4-class DAG (docs/PERF.md, "Region lowering &
  compile budgets");
- ``bench_serve``              — sustained submissions/s and p50/p99
  ticket latency through a RuntimeServer: concurrent client threads,
  two tenants, one hot context (the serving layer, parsec_tpu/serve/);
- ``bench_comm``               — the comm wire data path (ISSUE 4): AM
  roundtrip µs over inproc + localhost sockets, coalesced compact
  activations/s, one-sided GET GB/s at 64KiB/4MiB/64MiB through the
  binary scatter-gather framing + windowed fragmented rendezvous, the
  legacy pickle-framing baseline and speedup ratio, and the overlap
  efficiency of compute retired during a saturating fragmented GET.

``python microbench.py`` prints one JSON object and finishes in seconds on a
CPU-only host.  ``run_all(smoke=True)`` shrinks every config for CI; the
``perf_smoke`` tier-1 marker (tests/test_perf_smoke.py) runs that with 10×
headroom thresholds so gross dispatch-path regressions fail fast without
timing flakes.  docs/PERF.md maps each number to the code it measures.
"""

from __future__ import annotations

import json
import statistics
import time


def _ep_pool(NT: int, DEPTH: int):
    """The reference's tests/runtime/scheduling/ep.jdf shape: NT independent
    lanes of DEPTH chained CTL-only tasks."""
    from parsec_tpu import ptg

    p = ptg.PTGBuilder("ep", NT=NT, DEPTH=DEPTH)
    t = p.task("EP",
               d=ptg.span(0, lambda g, l: g.DEPTH - 1),
               n=ptg.span(0, lambda g, l: g.NT - 1))
    f = t.flow("ctl", ptg.CTL)
    f.input(pred=("EP", "ctl", lambda g, l: {"d": l.d - 1, "n": l.n}),
            guard=lambda g, l: l.d > 0)
    f.output(succ=("EP", "ctl", lambda g, l: {"d": l.d + 1, "n": l.n}),
             guard=lambda g, l: l.d < g.DEPTH - 1)
    t.body(lambda es, task, g, l: None)
    return p


def _drain_ep_us(ntasks: int, reps: int, compiled: bool,
                 traced: bool = False) -> tuple:
    """Median enqueue-to-drain wall time per task in µs, plus whether the
    compiled-DAG executor actually engaged (it silently declines when the
    native extension is unavailable — the reading must say which path it
    measured, or the dispatch trend mixes incomparable series).
    ``traced=True`` attaches a trace context to every pool, so an
    INSTALLED span recorder actually records (the enabled-cost axis of
    ``bench_tracing``)."""
    import parsec_tpu.runtime.dagrun  # noqa: F401 — runtime_dag_compile
    from parsec_tpu.core.params import params
    from parsec_tpu.prof import spans
    from parsec_tpu.runtime import Context

    NT = 50
    DEPTH = max(ntasks // NT, 2)
    builder = _ep_pool(NT, DEPTH)
    saved = params.get("runtime_dag_compile")
    params.set("runtime_dag_compile", compiled)
    engaged = False
    try:
        times = []
        for _ in range(reps):
            tp = builder.build()
            if traced:
                tp._trace = spans.new_trace()
            ctx = Context(nb_cores=0)
            t0 = time.perf_counter()
            ctx.add_taskpool(tp)
            engaged = getattr(tp, "_compiled_dag", None) is not None
            ctx.wait(timeout=600)
            times.append(time.perf_counter() - t0)
            ctx.fini()
        return statistics.median(times) / (NT * DEPTH) * 1e6, engaged
    finally:
        params.set("runtime_dag_compile", saved)


def bench_dispatch_us(ntasks: int = 10000, reps: int = 5) -> dict:
    us, engaged = _drain_ep_us(ntasks, reps, True)
    return {"dispatch_us": round(us, 3), "ntasks": ntasks,
            "dispatch_path": "compiled" if engaged else "dynamic"}


def bench_release_throughput(ntasks: int = 10000, reps: int = 3) -> dict:
    """Dynamic-path drain: every non-startup task arrives through
    ``release_deps`` → ``release_many``, so tasks/s here IS dep-release +
    schedule throughput (body is empty)."""
    us, _ = _drain_ep_us(ntasks, reps, False)
    return {"dynamic_dispatch_us": round(us, 3),
            "release_tasks_per_s": round(1e6 / us, 1),
            "ntasks": ntasks}


class _BenchTask:
    __slots__ = ("priority",)
    task_class = None        # one class: one bucket of the ready queue

    def __init__(self) -> None:
        self.priority = 0


def bench_steal_us(n: int = 200, reps: int = 50) -> dict:
    """lfq local-pop vs steal latency on the per-stream ready queues,
    driven through the real scheduler module (no Context needed)."""
    import parsec_tpu.sched  # noqa: F401 — registers components + params
    from parsec_tpu.sched.modules import LFQModule
    from parsec_tpu.runtime.scheduling import ExecutionStream, VirtualProcess

    class _Ctx:
        virtual_processes: list = []

    ctx = _Ctx()
    vp = VirtualProcess(0, ctx)
    ctx.virtual_processes = [vp]
    es0 = ExecutionStream(0, vp, ctx)
    es1 = ExecutionStream(1, vp, ctx)
    vp.execution_streams = [es0, es1]
    mod = LFQModule()
    mod.install(ctx)
    mod.flow_init(es0)
    mod.flow_init(es1)
    n = min(n, mod._cap)      # beyond capacity spills to the system queue
    tasks = [_BenchTask() for _ in range(n)]

    def run(selector_es) -> float:
        best = None
        for _ in range(reps):
            mod.schedule(es0, list(tasks), 0)
            t0 = time.perf_counter()
            for _i in range(n):
                t, _d = mod.select(selector_es)
                assert t is not None
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        return best / n * 1e6

    return {"local_pop_us": round(run(es0), 4),
            "steal_us": round(run(es1), 4), "n": n}


def bench_pins_disabled_ns(iters: int = 200000) -> dict:
    """One DISABLED instrumentation site (index load + falsy branch) vs
    the always-on recorder-enabled site, through the same dispatch-slot
    pattern the scheduling loop compiles in (prof/pins.py).  The recorder
    is detached for the disabled half and restored after."""
    from parsec_tpu.prof import pins

    hooks = pins.hooks
    ev = int(pins.PinsEvent.EXEC_BEGIN)
    payload = object()

    def run() -> float:
        t0 = time.perf_counter()
        for _ in range(iters):
            h = hooks[ev]
            if h is not None:
                h(None, payload)
        return (time.perf_counter() - t0) / iters * 1e9

    saved = pins.recorder
    pins.recorder = None
    try:
        disabled = run() if hooks[ev] is None else None
    finally:
        pins.recorder = saved
    out = {"pins_disabled_ns": round(disabled, 2)
           if disabled is not None else None}
    if hooks[ev] is not None:       # always-on recorder (or chains) present
        out["pins_enabled_ns"] = round(run(), 2)
    return out


def bench_tracing(ntasks: int = 2000, reps: int = 3,
                  smoke: bool = False) -> dict:
    """The request-tracing cost axes (prof/spans.py, prof/histogram.py):

    - ``span_record_ns``     — one finished-span record (tuple + append,
      the ring-write-shaped enabled cost);
    - ``hist_record_ns``     — one SLO histogram sample (one log, one
      bucket increment);
    - ``tracing_dispatch_off_us`` / ``_on_us`` / ``_delta_us`` — dynamic
      per-task dispatch with the recorder UNINSTALLED (the shipped
      default: the PINS table's one-branch cost, nothing more) vs
      INSTALLED with every pool traced.  The acceptance budget: disabled
      within 10% of the PR-2 overhead baseline, enabled ≤1µs/task
      (both gated with headroom in tests/test_perf_smoke.py)."""
    from parsec_tpu.prof import spans
    from parsec_tpu.prof.histogram import LogHistogram

    if smoke:
        ntasks, reps = 1000, 2
    out: dict = {}
    # -- span record cost (a throwaway recorder; never installed) ------
    rec = spans.SpanRecorder(1 << 20)
    tr = spans.new_trace()
    n = 20000
    t0 = time.perf_counter()
    for _i in range(n):
        rec.record("exec", tr.trace_id, 0, 100)
    out["span_record_ns"] = round(
        (time.perf_counter() - t0) / n * 1e9, 1)
    # -- histogram record cost -----------------------------------------
    h = LogHistogram()
    t0 = time.perf_counter()
    for _i in range(n):
        h.record(1.234)
    out["hist_record_ns"] = round(
        (time.perf_counter() - t0) / n * 1e9, 1)
    # -- enabled-vs-disabled dynamic dispatch --------------------------
    prev = spans.recorder      # a user-installed recorder (and its
    if prev is not None:       # accumulated spans) must survive this
        spans.uninstall()      # measurement — restored object-identical
    off, _ = _drain_ep_us(ntasks, reps, compiled=False)
    spans.install()
    try:
        on, _ = _drain_ep_us(ntasks, reps, compiled=False, traced=True)
        out["tracing_spans_recorded"] = len(spans.recorder.spans)
    finally:
        spans.uninstall()
        if prev is not None:
            spans.install(recorder_obj=prev)
    out["tracing_dispatch_off_us"] = round(off, 3)
    out["tracing_dispatch_on_us"] = round(on, 3)
    out["tracing_dispatch_delta_us"] = round(on - off, 3)
    return out


def bench_lowering_cache(n: int = 96, nb: int = 32) -> dict:
    """Two structurally identical lowerings of a tiled GEMM: the second
    must hit the process-wide lowering cache and skip trace+compile."""
    import numpy as np

    from parsec_tpu.data_dist.matrix import TiledMatrix
    from parsec_tpu.models.tiled_gemm import tiled_gemm_ptg
    from parsec_tpu.ptg.lowering import lower_taskpool, lowering_cache

    def once() -> float:
        rng = np.random.default_rng(7)
        a = rng.standard_normal((n, n)).astype(np.float32)
        A = TiledMatrix.from_dense("A", a.copy(), nb, nb)
        B = TiledMatrix.from_dense("B", a.copy(), nb, nb)
        C = TiledMatrix.from_dense("C", np.zeros((n, n), np.float32), nb, nb)
        low = lower_taskpool(tiled_gemm_ptg(A, B, C))
        st = low.initial_stores()
        t0 = time.perf_counter()
        out = low.jitted()(st)
        float(np.asarray(out["C"]).reshape(-1)[0])
        return time.perf_counter() - t0

    h0, m0 = lowering_cache.hits, lowering_cache.misses
    cold = once()
    warm = once()
    return {"compile_cold_s": round(cold, 4),
            "compile_warm_s": round(warm, 4),
            "cache_hits": lowering_cache.hits - h0,
            "cache_misses": lowering_cache.misses - m0}


def bench_lowering(n: int = 256, nb: int = 32, smoke: bool = False) -> dict:
    """XLA calls per DAG + trace/compile seconds across the lowering modes
    (ISSUE 8, the MPK axis): on cholesky's irregular 4-class DAG, compare
    the dynamic task-per-dispatch path (vmapped batching OFF — every task
    is one XLA dispatch, the boundary cost megakernels delete) against the
    region lowering (one jitted program per convex subgraph), plus the
    whole-pool wavefront/scan emission and the GEMM chain-collapse for the
    per-mode compile-cost axis.  Every number is CPU-measurable; the
    dispatch counts come from the process-wide ledger feeding both paths
    (``device.note_xla_calls``)."""
    import jax
    import numpy as np

    from parsec_tpu.core.params import params
    from parsec_tpu.data_dist.matrix import SymTwoDimBlockCyclic, TiledMatrix
    from parsec_tpu.device import registry
    from parsec_tpu.device.device import xla_calls_total
    from parsec_tpu.device.tpu import TPUDevice
    from parsec_tpu.models.cholesky import make_spd, tiled_cholesky_ptg
    from parsec_tpu.models.tiled_gemm import tiled_gemm_ptg
    from parsec_tpu.ptg.lowering import lower_regions, lower_taskpool
    from parsec_tpu.runtime import Context

    if smoke:
        n, nb = 128, 32
    a = make_spd(n)

    def chol(devices="auto"):
        A = SymTwoDimBlockCyclic.from_dense("A", a.copy(), nb, nb)
        return tiled_cholesky_ptg(A, devices=devices)

    out: dict = {"lowering_n": n, "lowering_nb": nb}

    # --- task-per-dispatch baseline: the dynamic device path, vmapped
    # batching disabled, so EVERY task body is one XLA enqueue ---
    snapshot = list(registry.devices)
    saved_batch = params.get("device_tpu_batch")
    params.set("device_tpu_batch", False)
    dev = TPUDevice(jax.devices()[0])
    registry.add(dev)
    try:
        tp = chol(devices="tpu")
        ledger0, tasks0 = xla_calls_total(), dev.executed_tasks
        ctx = Context(nb_cores=0)
        try:
            ctx.add_taskpool(tp)
            ctx.wait(timeout=120)
            dev.sync()
        finally:
            ctx.fini(timeout=30)
        out["lowering_tasks_per_dag"] = dev.executed_tasks - tasks0
        out["lowering_dispatch_xla_calls"] = xla_calls_total() - ledger0
    finally:
        params.set("device_tpu_batch", saved_batch)
        registry.devices = snapshot
        for i, d in enumerate(registry.devices):
            d.device_index = i

    # --- region mode: one program per verified subgraph, cold then warm
    # (the second structurally identical plan must hit the process cache
    # and report ~0 compile seconds — the AOT-warming contract) ---
    plan = lower_regions(chol())
    plan.compile()
    cold = plan.stats()
    ledger0 = xla_calls_total()
    plan.execute()
    st = plan.stats()
    out["lowering_region_count"] = st["regions"]
    # the same process-wide ledger as the dispatch baseline above, so
    # the two counts are one comparable axis; the plan's own counter
    # rides along as the cross-check (they diverge only if another
    # thread dispatched concurrently)
    out["lowering_region_xla_calls"] = xla_calls_total() - ledger0
    out["lowering_region_plan_xla_calls"] = st["xla_calls"]
    out["lowering_region_trace_s"] = cold["trace_s"]
    out["lowering_region_compile_cold_s"] = cold["compile_s"]
    warm = lower_regions(chol())
    warm.compile()
    out["lowering_region_compile_warm_s"] = warm.stats()["compile_s"]
    if out["lowering_region_xla_calls"]:
        out["lowering_region_xla_call_drop"] = round(
            out["lowering_dispatch_xla_calls"] / out["lowering_region_xla_calls"], 1)

    # --- whole-pool wavefront (scan-folded) emission: ONE program ---
    low = lower_taskpool(chol(), passes="wavefront")
    out["lowering_wavefront_xla_calls"] = 1
    wavefront = low.warm()
    out["lowering_wavefront_trace_s"] = wavefront["trace_s"]
    out["lowering_wavefront_compile_s"] = wavefront["compile_s"]

    # --- chain-collapse: the GEMM k-chain as one contraction ---
    gn, gnb = (64, 32) if smoke else (128, 32)
    rng = np.random.default_rng(3)
    g = rng.standard_normal((gn, gn)).astype(np.float32)
    A = TiledMatrix.from_dense("A", g.copy(), gnb, gnb)
    B = TiledMatrix.from_dense("B", g.copy(), gnb, gnb)
    C = TiledMatrix.from_dense("C", np.zeros((gn, gn), np.float32), gnb, gnb)
    low = lower_taskpool(tiled_gemm_ptg(A, B, C), passes="chain-collapse")
    out["lowering_chain_xla_calls"] = 1
    chain = low.warm()
    out["lowering_chain_trace_s"] = chain["trace_s"]
    out["lowering_chain_compile_s"] = chain["compile_s"]
    return out


def bench_serve(nsub: int = 64, nthreads: int = 4, depth: int = 8,
                nb_cores: int = 2) -> dict:
    """Serving-path fixed cost: ``nthreads`` client threads submit
    ``nsub`` small CTL-chain pools (4 lanes x ``depth``, the EP shape)
    into one hot :class:`RuntimeServer` under two tenants, each blocking
    on its ticket — sustained submissions/s plus p50/p99 end-to-end
    ticket latency.  Pure scheduler path (no accelerator, no lowering):
    the serving layer's admission + fair-queue + live-enqueue overhead
    is what this measures."""
    import threading

    from parsec_tpu.serve import RuntimeServer

    lat: list[float] = []
    lock = threading.Lock()
    errors: list[BaseException] = []
    server = RuntimeServer(nb_cores=nb_cores)
    per = max(nsub // nthreads, 1)

    def client(tenant: str) -> None:
        try:
            for _i in range(per):
                tp = _ep_pool(4, depth).build()
                t0 = time.perf_counter()
                tk = server.submit(tp, tenant=tenant)
                tk.result(timeout=120)
                dt = time.perf_counter() - t0
                with lock:
                    lat.append(dt)
        except BaseException as e:      # noqa: BLE001 — surfaced below
            errors.append(e)

    threads = [threading.Thread(target=client, args=(f"tenant{i % 2}",),
                                name=f"serve-client{i}")
               for i in range(nthreads)]
    t0 = time.perf_counter()
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    wall = time.perf_counter() - t0
    # the per-tenant SLO plane, read LIVE off the still-hot server
    # (RuntimeServer.metrics(), the histogram plane): queue wait +
    # end-to-end latency quantiles per tenant, before drain resets
    # anything — the mid-run acceptance read
    slo = server.metrics()["tenants"]
    server.drain(timeout=60)
    if errors:
        raise errors[0]
    lat.sort()
    n = len(lat)
    return {
        "serve_submits_per_s": round(n / wall, 1),
        "serve_p50_ms": round(lat[n // 2] * 1e3, 3),
        "serve_p99_ms": round(lat[min(int(n * 0.99), n - 1)] * 1e3, 3),
        "serve_nsub": n,
        "serve_threads": nthreads,
        "serve_tasks_per_sub": 4 * depth,
        "serve_slo": slo,
        "serve_drain_s": round(server.metrics()["drain_s"] or 0.0, 4),
    }


def bench_llm(streams_sweep: tuple = (1, 4, 8),
              steps_sweep: tuple = (1, 4, 8), new_tokens: int = 16,
              prompt_len: int = 8, nb_cores: int = 2,
              smoke: bool = False, note=None) -> dict:
    """The LLM serving axis: tokens/s and per-token p50/p99 latency of
    the continuous batcher on a hot RuntimeServer, swept over concurrent
    streams (the request-scale axis the ROADMAP names) AND over
    ``llm_steps_per_pool`` (the ISSUE-9 amortization axis: one k-step
    decode superpool per tenant per iteration, in-graph sampling, so
    submit/termdet overhead is paid 1/k per token).  Streams run under
    per-stream tenants — the ROADMAP's millions-of-users shape, where
    WFQ isolation is a hard boundary and cross-stream batching cannot
    hide the per-pool submit cost, so the k axis measures exactly what
    the superpool amortizes.  (PR 6 benched 2 shared tenants, whose
    intra-tenant batching already amortized submits 4x at 8 streams;
    that axis is still visible as the streams sweep.)  Each point also
    reports ``submits_per_token`` — the amortization claim (k steps ->
    1/k submits) made directly visible — and ``note(**kw)`` (the bench
    harness passes ``_note_partial``) fires per swept point, so a
    mid-sweep deadline keeps the completed points (the BENCH_r04/r05
    lesson).  No accelerator; ``docs/LLM.md``."""
    from parsec_tpu.core.params import params as _params
    from parsec_tpu.llm import ToyLM
    from parsec_tpu.serve import RuntimeServer

    if smoke:
        streams_sweep, steps_sweep, new_tokens = (1, 4), (1, 8), 8
    model = ToyLM()
    out: dict = {"llm_streams_sweep": {}, "llm_steps_sweep": {}}
    # 64-token generations: the first ~10-16 tokens are the transition
    # where the generation settles into its fixed point and the bigram
    # table learns it — the spec axis must measure the draftable steady
    # state, not the warmup (a 32-token stream is ~1/3 warmup and
    # understates the speedup ~2x)
    spec_streams, spec_tokens = 8, max(64, 4 * new_tokens)
    k_top = max(steps_sweep)
    saved_k = _params.get("llm_steps_per_pool")
    server = RuntimeServer(nb_cores=nb_cores)
    try:
        def run_point(ns: int, k: int) -> dict:
            _params.set("llm_steps_per_pool", k)
            before = server.stats().get("llm") or {}
            sub0 = before.get("decode_submits", 0)
            tok0 = before.get("tokens_generated", 0)
            prompts = [[(7 * i + 3 * j) % model.vocab
                        for j in range(prompt_len)] for i in range(ns)]
            t0 = time.perf_counter()
            tks = [server.submit_stream(p, max_new_tokens=new_tokens,
                                        tenant=f"tenant{i}")
                   for i, p in enumerate(prompts)]
            per_token: list[float] = []
            for tk in tks:
                per_token += tk.result(timeout=300)["per_token_s"]
            wall = time.perf_counter() - t0
            per_token.sort()
            n = len(per_token)
            after = server.stats()["llm"]
            d_sub = after["decode_submits"] - sub0
            d_tok = after["tokens_generated"] - tok0
            point = {
                "tokens_per_s": round(ns * new_tokens / wall, 1),
                "p50_ms": round(per_token[n // 2] * 1e3, 3),
                "p99_ms": round(
                    per_token[min(int(n * 0.99), n - 1)] * 1e3, 3),
                "submits_per_token": round(d_sub / max(1, d_tok), 4),
            }
            if note is not None:
                # one UNIQUE key per swept point: _note_partial merges
                # by dict update, so reusing flat keys would leave only
                # the last completed point in a deadline's degrade
                # record instead of all of them
                note(phase="llm", **{f"llm_point_s{ns}_k{k}": point})
            return point

        for ns in streams_sweep:
            out["llm_streams_sweep"][str(ns)] = run_point(ns, k_top)
        top_ns = streams_sweep[-1]
        # the amortization axis, measured IN THE SAME RUN at the top
        # stream count (k_top reuses the streams-sweep point)
        for k in steps_sweep:
            out["llm_steps_sweep"][str(k)] = (
                out["llm_streams_sweep"][str(top_ns)] if k == k_top
                else run_point(top_ns, k))
        base = out["llm_steps_sweep"][str(min(steps_sweep))]
        best = out["llm_steps_sweep"][str(k_top)]
        out["llm_superpool_speedup"] = round(
            best["tokens_per_s"] / max(base["tokens_per_s"], 1e-9), 2)
        out["llm_tokens_per_s"] = best["tokens_per_s"]
        out["llm_p50_ms"] = best["p50_ms"]
        out["llm_p99_ms"] = best["p99_ms"]
        out["llm_steps_per_pool"] = k_top
        out["serve_submits_per_token"] = best["submits_per_token"]
        out["llm_new_tokens"] = new_tokens
        out["llm_prompt_len"] = prompt_len
        out["llm_kv"] = server.stats()["llm"]["kv"]
        # per-tenant TTFT + inter-token latency quantiles off the SLO
        # histogram plane, read LIVE (RuntimeServer.metrics()) while the
        # server is still hot — the same numbers mid-run and in the emit
        out["llm_slo"] = {
            tenant: {k: v for k, v in d.items()
                     if k.startswith(("ttft_ms", "tok_latency_ms",
                                      "queue_wait_ms"))}
            for tenant, d in server.metrics()["tenants"].items()
            if "ttft_ms_p50" in d}
    finally:
        _params.set("llm_steps_per_pool", saved_k)
        server.drain(timeout=60)

    # the speculative-decode axis (ISSUE 12): off/2/4/adaptive on a
    # DRAFTABLE (repetitive) workload at 8 streams — the ROADMAP's
    # 10k+-tok/s leg.  Greedy ToyLM generations collapse to fixed
    # points / short cycles on arithmetic-ramp prompts, which is
    # exactly the templated-continuation shape the n-gram drafter
    # predicts; "off" shares the workload so llm_spec_speedup compares
    # the spec superpool against the PR-9 k-step path, nothing else.
    # Fresh server per point: per-tenant acceptance priors and drafter
    # state must not leak across points.
    saved_spec = {k: _params.get(k) for k in ("llm_spec_k",
                                              "llm_spec_adaptive")}
    # 8 distinct arithmetic-ramp (offset, stride) prompts whose greedy
    # generations collapse fast (~0.9 chain acceptance at draft 16 on
    # the bigram simulation) — the draftable workload the ISSUE-12
    # speedup criterion names; the "off" point runs the SAME prompts
    spec_shapes = ((48, 5), (44, 9), (36, 11), (20, 11),
                   (0, 3), (60, 1), (32, 3), (32, 1))
    spec_prompts = [[(a + b * j) % model.vocab
                     for j in range(prompt_len)]
                    for a, b in spec_shapes[:spec_streams]]

    def run_spec_point(spec_k: int, adaptive: bool) -> dict:
        _params.set("llm_spec_k", spec_k)
        _params.set("llm_spec_adaptive", adaptive)
        with RuntimeServer(nb_cores=nb_cores) as server:
            t0 = time.perf_counter()
            tks = [server.submit_stream(p, max_new_tokens=spec_tokens,
                                        tenant=f"tenant{i}")
                   for i, p in enumerate(spec_prompts)]
            for tk in tks:
                tk.result(timeout=300)
            wall = time.perf_counter() - t0
            llm = server.stats()["llm"]
        return {
            "tokens_per_s": round(spec_streams * spec_tokens / wall, 1),
            "accept_rate": llm.get("spec_accept_rate", 0.0),
            "tokens_per_submit": llm.get("spec_tokens_per_submit", 0.0),
            "rollbacks": llm["kv"]["tail_rollbacks"],
        }

    try:
        out["llm_spec_sweep"] = {}
        for label, k, ad in (("off", 0, False), ("2", 2, False),
                             ("4", 4, False), ("adaptive", 16, True)):
            point = run_spec_point(k, ad)
            out["llm_spec_sweep"][label] = point
            if note is not None:
                note(phase="llm", **{f"llm_spec_{label}": point})
        base = out["llm_spec_sweep"]["off"]["tokens_per_s"]
        out["llm_spec_speedup"] = round(
            out["llm_spec_sweep"]["adaptive"]["tokens_per_s"]
            / max(base, 1e-9), 2)
        out["llm_spec_accept_rate"] = \
            out["llm_spec_sweep"]["adaptive"]["accept_rate"]
        out["llm_spec_streams"] = spec_streams
        out["llm_spec_new_tokens"] = spec_tokens
        if note is not None:
            note(phase="llm", llm_spec_speedup=out["llm_spec_speedup"])
    finally:
        for k, v in saved_spec.items():
            _params.set(k, v)
    return out


def bench_llm_prefix(fracs: tuple = (0.0, 0.5, 0.9), nstreams: int = 8,
                     shared_pages: int = 12, tail_len: int = 8,
                     new_tokens: int = 2, nb_cores: int = 2,
                     page_size: int = 256, reps: int = 2,
                     smoke: bool = False, note=None) -> dict:
    """The automatic-prefix-cache axis (ISSUE 11): TTFT p50/p99 and the
    prefill work actually skipped, swept over the **shared-prefix
    fraction** of the traffic — the millions-of-users shape is most
    requests carrying one system prompt, and the radix trie
    (``llm/prefix_tree.py``) should convert exactly that fraction of
    prefill into copy-on-write page forks.

    Per swept point: a fresh server + batcher with ``llm_prefix_cache=1``
    is warmed by ONE donor stream (its retirement donates the shared
    prompt's pages to the trie), then ``nstreams`` streams arrive of
    which ``frac`` share the donor's prefix (plus per-stream tails — the
    hit-mid-page shape) and the rest carry disjoint prompts (misses).
    TTFT is client-observed: ``StreamTicket.first_token_at`` minus
    submit.  The headline ``llm_prefix_ttft_speedup`` re-runs the top
    fraction with the cache OFF and reports cold/hot TTFT p50 — the
    perf_smoke ``LLM_PREFIX_TTFT_SPEEDUP_MIN`` gate holds it ≥ 2x.
    ``note(**kw)`` fires per point (deadline-death keeps sweep points,
    the BENCH_r04/r05 lesson).  Pure CPU serving path.

    Geometry: 256-token pages — prefill work per cacheable token (chunk
    building + PF page copies) then dominates scheduler task overhead,
    so the measured speedup reflects the work the trie skips rather
    than the per-task cost the superpool axis already measures.  Each
    point runs ``reps`` waves on one hot server and keeps the best p50
    (arrival/iteration phase alignment is the flake source; the wave
    with the cleanest batch boundary is the representative one)."""
    import parsec_tpu.llm.batcher  # noqa: F401 — registers llm_* params
    from parsec_tpu.core.params import params as _params
    from parsec_tpu.llm import ToyLM
    from parsec_tpu.serve import RuntimeServer

    if smoke:
        fracs, nstreams = (0.0, 0.9), 6
    model = ToyLM()
    P = int(page_size)
    shared = [(5 * i + 11) % model.vocab for i in range(shared_pages * P)]
    saved = {k: _params.get(k) for k in ("llm_prefix_cache",
                                         "llm_steps_per_pool",
                                         "llm_page_size")}
    # 1-step superpools: TTFT then measures admission + prefill + one
    # decode step, so the prefill skip is visible instead of drowned
    # under a k-step first iteration
    _params.set("llm_steps_per_pool", 1)
    _params.set("llm_page_size", P)

    def run_point(frac: float, cache_on: bool) -> dict:
        _params.set("llm_prefix_cache", cache_on)
        with RuntimeServer(nb_cores=nb_cores) as server:
            donor = server.submit_stream(shared + [3], max_new_tokens=1,
                                         tenant="pfx")
            donor.result(timeout=300)      # retires -> donates the prefix
            llm0 = server.stats()["llm"]
            nshared = int(round(frac * nstreams))
            best = None
            for rep in range(max(1, reps)):
                # unique parts vary PER WAVE: a later wave's misses must
                # stay misses (the earlier wave's retirees donated their
                # prompts), or the 0.0 point would silently measure
                # repeat-traffic hits instead of the cold path
                prompts = []
                for i in range(nstreams):
                    # distinct mod vocab across (wave, stream) pairs, so
                    # no two "unique" prompts ever alias page runs
                    salt = (rep * nstreams + i) % model.vocab
                    if i < nshared:        # shared prefix + unique tail
                        prompts.append(shared
                                       + [(salt + j) % model.vocab
                                          for j in range(tail_len)])
                    else:                  # disjoint prompt, same length
                        prompts.append([(7 * salt + 3 * j + 1)
                                        % model.vocab
                                        for j in range(len(shared)
                                                       + tail_len)])
                t0 = time.perf_counter()
                tks = [server.submit_stream(p, max_new_tokens=new_tokens,
                                            tenant="pfx") for p in prompts]
                for tk in tks:
                    tk.result(timeout=300)
                wall = time.perf_counter() - t0
                ttfts = sorted((tk.first_token_at - tk.submitted_at) * 1e3
                               for tk in tks
                               if tk.first_token_at is not None)
                n = len(ttfts)
                wave = {
                    "ttft_p50_ms": round(ttfts[n // 2], 3) if n else 0.0,
                    "ttft_p99_ms": round(
                        ttfts[min(int(n * 0.99), n - 1)], 3) if n else 0.0,
                    "tokens_per_s": round(
                        nstreams * new_tokens / wall, 1),
                }
                if best is None or wave["ttft_p50_ms"] < best["ttft_p50_ms"]:
                    best = wave
            llm1 = server.stats()["llm"]
            d_tot = (llm1["prefill_tokens_total"]
                     - llm0["prefill_tokens_total"])
            d_skip = (llm1["prefill_tokens_skipped"]
                      - llm0["prefill_tokens_skipped"])
            best["prefill_skipped_frac"] = round(d_skip / max(1, d_tot), 4)
            best["prefix_hits"] = (llm1["kv"]["prefix_hits"]
                                   - llm0["kv"]["prefix_hits"])
            return best

    out: dict = {"llm_prefix_sweep": {}}
    try:
        for frac in fracs:
            point = run_point(frac, cache_on=True)
            out["llm_prefix_sweep"][str(frac)] = point
            if note is not None:
                note(phase="llm_prefix",
                     **{f"llm_prefix_f{frac}": point})
        top = max(fracs)
        cold = run_point(top, cache_on=False)
        out["llm_prefix_cold"] = cold
        hot = out["llm_prefix_sweep"][str(top)]
        out["llm_prefix_ttft_speedup"] = round(
            cold["ttft_p50_ms"] / max(hot["ttft_p50_ms"], 1e-9), 2)
        out["llm_prefill_skipped_frac"] = hot["prefill_skipped_frac"]
        out["llm_prefix_shared_tokens"] = len(shared)
        if note is not None:
            note(phase="llm_prefix",
                 llm_prefix_ttft_speedup=out["llm_prefix_ttft_speedup"],
                 llm_prefill_skipped_frac=out["llm_prefill_skipped_frac"])
    finally:
        for k, v in saved.items():
            _params.set(k, v)
    return out


def bench_llm_tier(nstreams: int = 4, prompt_pages: int = 3,
                   new_tokens: int = 24, nb_cores: int = 2,
                   smoke: bool = False, note=None) -> dict:
    """The KV-tiering axis (ISSUE 11): the SAME decode workload through
    the accelerator device tier twice — unconstrained, then with the
    device HBM budget squeezed BELOW the live-KV working set — reporting
    the tokens/s ratio (the "prefetch hides the spill" claim: the
    acceptance line is within 30%) plus the tier ledger
    (``host_tier_bytes``, spills, prefetched pages) of the constrained
    run.  Off-TPU the device is the host CPU wrapped as an accelerator
    (the same CPU-coverage trick the device suites use), so the number
    is CPU-provable; tokens are oracle-checked in both runs."""
    import jax

    import parsec_tpu.llm.batcher  # noqa: F401 — registers llm_* params
    from parsec_tpu.device import registry
    from parsec_tpu.device.tpu import TPUDevice
    from parsec_tpu.llm import ContinuousBatcher, ToyLM
    from parsec_tpu.serve import RuntimeServer

    if smoke:
        # >= 2 superpool iterations (k=8): iteration N's evictions are
        # what iteration N+1's prefetch stages back — a single-shot run
        # would race the deferred write-back drain and prefetch nothing
        nstreams, new_tokens = 2, 16
    model = ToyLM()

    def run_once(budget_pages: int | None) -> tuple[float, dict]:
        snapshot = list(registry.devices)
        dev = TPUDevice(jax.devices()[0])
        registry.add(dev)
        try:
            with RuntimeServer(nb_cores=nb_cores) as server:
                b = ContinuousBatcher(server, model=model, devices="tpu")
                # one warmup stream BEFORE the timed batch: both runs
                # then measure steady-state decode, not whichever run
                # happened to pay the process's first jit/vmap builds
                b.submit_stream([1, 2, 3], max_new_tokens=1) \
                    .result(timeout=300)
                if budget_pages is not None:
                    dev._mem_budget = budget_pages * b.kv.page_bytes
                P = b.kv.page_size
                prompts = [[(7 * i + 3 * j + 1) % model.vocab
                            for j in range(prompt_pages * P + 1)]
                           for i in range(nstreams)]
                t0 = time.perf_counter()
                tks = [b.submit_stream(p, max_new_tokens=new_tokens)
                       for p in prompts]
                for p, tk in zip(prompts, tks):
                    got = tk.result(timeout=300)["tokens"]
                    want = model.reference_generate(p, new_tokens)
                    assert got == want, ("tiered decode diverged from "
                                        "the dense oracle", got, want)
                wall = time.perf_counter() - t0
                stats = b.stats()
                b.stop()
            return nstreams * new_tokens / wall, stats
        finally:
            registry.devices = snapshot
            for i, d in enumerate(registry.devices):
                d.device_index = i

    tok_free, _ = run_once(None)
    # working set ~= nstreams * (prompt + decode tail) pages; squeeze to
    # roughly a third so eviction pressure is real every iteration
    squeeze = max(2, nstreams * (prompt_pages + 1) // 3)
    tok_tight, stats = run_once(squeeze)
    out = {
        "llm_tier_tokens_per_s_free": round(tok_free, 1),
        "llm_tier_tokens_per_s_tight": round(tok_tight, 1),
        "llm_tier_tokens_ratio": round(tok_tight / max(tok_free, 1e-9), 3),
        "llm_tier_budget_pages": squeeze,
        "llm_tier_spills": stats["tiers"]["spills"],
        "llm_tier_prefetched_pages": stats["tiers"]["prefetched_pages"],
        "llm_tier_host_bytes_peak": stats["kv"]["host_tier_bytes"],
    }
    if note is not None:
        note(phase="llm_tier", **out)
    return out


def _comm_socket_pair():
    """Two socket fabrics + engines in one process on a free localhost
    port range (the oversubscribed two-rank DCN shape)."""
    from parsec_tpu.comm.multiproc import _free_port_base
    from parsec_tpu.comm.socket_fabric import SocketCommEngine, SocketFabric

    base = _free_port_base(2)
    f0 = SocketFabric(2, 0, base_port=base)
    f1 = SocketFabric(2, 1, base_port=base)
    return SocketCommEngine(f0), SocketCommEngine(f1)


def _comm_wait(engines, pred, sleep_s: float = 0.0002,
               timeout: float = 60.0) -> None:
    """Progress all engines until ``pred()``; the tiny sleep yields the
    GIL to the fabric receive threads (a hard spin would throttle them to
    the interpreter's switch interval and measure the GIL, not the wire)."""
    deadline = time.perf_counter() + timeout
    while not pred():
        for e in engines:
            e.progress()
        if sleep_s:
            time.sleep(sleep_s)
        if time.perf_counter() > deadline:
            raise TimeoutError("comm bench wait timed out")


def _comm_get_gbps(e0, e1, nbytes: int, reps: int) -> float:
    """GET throughput rank1→rank0 for one payload size (warm wire)."""
    import numpy as np
    arr = np.random.default_rng(7).integers(
        0, 255, size=max(nbytes, 1), dtype=np.uint8)
    best = None
    for _ in range(reps):
        h = e1.mem_register(arr, refcount=1, owned=True)
        done: list = []
        t0 = time.perf_counter()
        e0.get(h.wire(), done.append)
        _comm_wait((e0, e1), lambda: done)
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
        assert done[0].nbytes == arr.nbytes
    return arr.nbytes / best / 1e9


def bench_comm(smoke: bool = False) -> dict:
    """The comm data-path numbers (CPU-provable, no accelerator):

    - ``comm_am_roundtrip_us_*``      — ping-pong latency of one small AM
      over the in-process fabric and over localhost sockets;
    - ``comm_activations_per_s``      — coalesced compact-form activation
      batches through the binary socket framing, decoded end to end;
    - ``comm_get_*_gbps``             — one-sided GET throughput per tier
      at 64KiB / 4MiB / 64MiB (socket payloads move as scatter-gather
      binary frames, ≥4MiB as windowed fragments recv_into'd straight
      into the destination buffer);
    - ``comm_get_socket_pickle_gbps`` + ``comm_get_speedup_vs_pickle`` —
      the same 4MiB socket GET over the legacy length-prefixed-pickle
      framing (``comm_wire_binary=False``), the measured baseline the
      zero-copy path is judged against (ISSUE 4 acceptance: ≥3×);
    - ``comm_overlap_efficiency``     — fraction of a saturating 64MiB
      fragmented GET's wall time the consumer spent inside compute units
      (progress interleaved between compute units, the T3-style overlap);
      ``comm_overlap_compute_frac`` is the companion calibrated-compute
      fraction (units x solo unit cost / wall — lower under GIL/core
      contention, the gap is contention overhead).
    """
    import numpy as np

    from parsec_tpu.comm.engine import AM_TAG_USER_BASE, InprocFabric
    from parsec_tpu.core.params import params
    from parsec_tpu.prof import spans as _spans

    out: dict = {}
    reps = 3 if smoke else 5
    # observe the whole stage with a PRIVATE span recorder (the
    # bench_tracing save/restore idiom): every GET below records a
    # comm.get span, so critpath can attribute the stage afterwards —
    # the cross-check ISSUE 16's acceptance pins against the measured
    # comm_overlap_efficiency
    prev_rec = _spans.recorder
    if prev_rec is not None:
        _spans.uninstall()
    rec = _spans.install()
    # smoke keeps the 4MiB point: it is the acceptance size the pickle
    # baseline is compared at, and the ratio there is wide enough
    # (~4x idle) to stay unambiguous under CI load
    sizes = ((65536, "64kib"), (4 << 20, "4mib")) if smoke else \
        ((65536, "64kib"), (4 << 20, "4mib"), (64 << 20, "64mib"))
    saved = {k: params.get(k) for k in
             ("comm_wire_binary", "comm_get_frag_bytes", "comm_get_window")}
    params.set("comm_wire_binary", True)
    params.set("comm_get_frag_bytes", 1 << 20 if smoke else 4 << 20)
    params.set("comm_get_window", 4)
    try:
        # -- AM roundtrip: inproc ------------------------------------------
        fab = InprocFabric(2)
        i0, i1 = fab.attach(0), fab.attach(1)
        n_pp = 200 if smoke else 1000
        count = [0]
        i1.tag_register(AM_TAG_USER_BASE, lambda eng, src, p:
                        i1.send_am(AM_TAG_USER_BASE, src, p))   # echo
        i0.tag_register(AM_TAG_USER_BASE, lambda eng, src, p:
                        count.__setitem__(0, count[0] + 1))     # pong
        t0 = time.perf_counter()
        for _ in range(n_pp):
            want = count[0] + 1
            i0.send_am(AM_TAG_USER_BASE, 1, {"seq": 1})
            _comm_wait((i0, i1), lambda w=want: count[0] >= w, sleep_s=0)
        out["comm_am_roundtrip_us_inproc"] = round(
            (time.perf_counter() - t0) / n_pp * 1e6, 2)

        # -- AM roundtrip + activation batches: localhost sockets ----------
        e0, e1 = _comm_socket_pair()
        pong = [0]
        e0.tag_register(AM_TAG_USER_BASE, lambda eng, src, p:
                        pong.__setitem__(0, pong[0] + 1))
        e1.tag_register(AM_TAG_USER_BASE, lambda eng, src, p:
                        e1.send_am(AM_TAG_USER_BASE, src, p))
        n_pp = 50 if smoke else 200
        # warm the duplex connections first
        e0.send_am(AM_TAG_USER_BASE, 1, 0)
        _comm_wait((e0, e1), lambda: pong[0] == 1)
        t0 = time.perf_counter()
        for _ in range(n_pp):
            want = pong[0] + 1
            e0.send_am(AM_TAG_USER_BASE, 1, 0)
            _comm_wait((e0, e1), lambda w=want: pong[0] >= w, sleep_s=0)
        out["comm_am_roundtrip_us_socket"] = round(
            (time.perf_counter() - t0) / n_pp * 1e6, 2)

        # coalesced activations: compact positional batches with small
        # inline payloads, decoded by the receiver's AM dispatch
        from parsec_tpu.comm.remote_dep import pack_activation
        inline = np.arange(64, dtype=np.float32)       # short-limit rider
        batch = ("B", [pack_activation(
            {"tp": 1, "tc": 0, "locals": {"m": i, "k": 3}, "outputs": [
                {"flow_index": 0, "writeback": False, "version": 1,
                 "inline": inline}],
             "ranks": [0, 1], "tree": "binomial", "priority": i,
             "seq": i, "pos": 1}) for i in range(32)])
        got = [0]
        e1.tag_register(AM_TAG_USER_BASE + 1, lambda eng, src, p:
                        got.__setitem__(0, got[0] + len(p[1])))
        nb = 20 if smoke else 100
        t0 = time.perf_counter()
        for _ in range(nb):
            e0.send_am(AM_TAG_USER_BASE + 1, 1, batch)
        _comm_wait((e0, e1), lambda: got[0] >= nb * 32)
        out["comm_activations_per_s"] = round(
            nb * 32 / (time.perf_counter() - t0), 1)

        # -- GET throughput ladder: socket tier ----------------------------
        for nbytes, label in sizes:
            out[f"comm_get_socket_{label}_gbps"] = round(
                _comm_get_gbps(e0, e1, nbytes, reps), 3)

        # -- overlap: compute retired during a saturating fragmented GET --
        big = np.random.default_rng(3).integers(
            0, 255, size=(8 << 20) if smoke else (64 << 20), dtype=np.uint8)
        a = np.random.default_rng(4).standard_normal((192, 192)) \
            .astype(np.float32)
        unit = lambda: float(np.dot(a, a).sum())        # noqa: E731
        t0 = time.perf_counter()
        n_cal = 20
        for _ in range(n_cal):
            unit()
        unit_s = (time.perf_counter() - t0) / n_cal
        h = e1.mem_register(big, refcount=1, owned=True)
        done: list = []
        units = [0]
        # the overlap GET runs TRACED: its comm.get span plus an exec
        # span per retired unit let critpath recompute the overlap
        # efficiency from the span plane alone (agreement gate below)
        tr = _spans.new_trace()
        _now_ns = time.perf_counter_ns
        busy_ns = 0
        t0 = time.perf_counter()
        e0.get(h.wire(), done.append, trace=tr.trace_id)
        while not done:
            u0 = _now_ns()
            unit()                      # compute retired mid-transfer
            u1 = _now_ns()
            rec.record("exec", tr.trace_id, u0, u1, None, "overlap_unit")
            busy_ns += u1 - u0
            units[0] += 1
            e0.progress()
            e1.progress()
            if time.perf_counter() - t0 > 60.0:
                raise TimeoutError("comm overlap GET did not complete")
        wall = time.perf_counter() - t0
        # wall fraction spent inside compute units — the same quantity
        # critpath recomputes from the span plane (|exec| within the GET
        # window / |GET|), measured independently by inline accumulation
        out["comm_overlap_efficiency"] = round(
            min(busy_ns / 1e9 / wall, 1.0), 3)
        # calibrated-compute fraction: units retired x solo unit cost;
        # trails the wall fraction by the GIL/core contention overhead
        out["comm_overlap_compute_frac"] = round(
            min(units[0] * unit_s / wall, 1.0), 3)
        out["comm_overlap_units"] = units[0]
        e0.fini()
        e1.fini()

        # -- the pickle baseline (legacy framing, monolithic replies) ------
        params.set("comm_wire_binary", False)
        params.set("comm_get_frag_bytes", 0)
        p0, p1 = _comm_socket_pair()
        out["comm_get_socket_pickle_gbps"] = round(
            _comm_get_gbps(p0, p1, 4 << 20, reps), 3)
        p0.fini()
        p1.fini()
        out["comm_get_speedup_vs_pickle"] = round(
            out["comm_get_socket_4mib_gbps"]
            / max(out["comm_get_socket_pickle_gbps"], 1e-9), 2)

        # -- GET throughput ladder: inproc tier (fragment pipeline only,
        # no sockets — the engine-protocol fixed cost) ---------------------
        params.set("comm_wire_binary", True)
        params.set("comm_get_frag_bytes", 1 << 20 if smoke else 4 << 20)
        fab2 = InprocFabric(2)
        j0, j1 = fab2.attach(0), fab2.attach(1)
        for nbytes, label in sizes:
            arr = np.random.default_rng(9).integers(
                0, 255, size=nbytes, dtype=np.uint8)
            best = None
            for _ in range(reps):
                h = j1.mem_register(arr, refcount=1, owned=True)
                done = []
                t0 = time.perf_counter()
                j0.get(h.wire(), done.append)
                _comm_wait((j0, j1), lambda: done, sleep_s=0)
                dt = time.perf_counter() - t0
                best = dt if best is None else min(best, dt)
            out[f"comm_get_inproc_{label}_gbps"] = round(
                nbytes / best / 1e9, 3)

        # -- critpath attribution over the stage's own spans ---------------
        # the traced overlap request's span-derived efficiency must agree
        # with the measured one (ISSUE 16 acceptance: within 15% rel);
        # the untraced ladder GETs contribute the nonzero overlap_lost
        # edge classes (no exec overlapped them by construction)
        try:
            from parsec_tpu.prof.critpath import attribute, normalize
            t0 = time.perf_counter()
            rep = attribute(normalize(list(rec.spans)))
            out["comm_critpath_replay_s"] = round(
                time.perf_counter() - t0, 4)
            req = rep["requests"].get(format(tr.trace_id, "x"))
            if req and req.get("overlap_efficiency") is not None:
                out["comm_critpath_overlap_efficiency"] = round(
                    req["overlap_efficiency"], 3)
            out["comm_critpath_top_lost"] = rep["top_overlap_lost"]
            out["comm_critpath_overlap_lost_ms"] = rep["overlap_lost_ms"]
        except Exception as e:        # noqa: BLE001 — evidence over abort
            out["comm_critpath_error"] = f"{type(e).__name__}: {e}"
    finally:
        for k, v in saved.items():
            params.set(k, v)
        _spans.uninstall()
        if prev_rec is not None:
            _spans.install(recorder_obj=prev_rec)
    return out


def bench_commcheck(smoke: bool = False) -> dict:
    """Static comm-pattern derivation cost (ISSUE 20): the analyzer's own
    wall time and tasks/s over a distributed broadcast pool, plus the
    rank-sweep prediction latency bench.py's ``comm_ranks`` cross-check
    pays per point — commcheck runs in the CI gate and before real
    submissions, so its replay must stay cheap relative to the graphs it
    clears."""
    from parsec_tpu.analysis.commcheck import (check_comm,
                                               predict_collective_traffic)
    from parsec_tpu.comm.collectives import bcast_taskpool
    from parsec_tpu.data_dist.matrix import VectorTwoDimCyclic

    out: dict = {}
    n = 16 if smoke else 64
    reps = 2 if smoke else 3
    best = None
    for _ in range(reps):
        V = VectorTwoDimCyclic("V", lm=1024 * n, mb=1024, P=min(n, 8))
        tp = bcast_taskpool(V, n=n)
        t0 = time.perf_counter()
        cr = check_comm(tp, nb_ranks=min(n, 8))
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    assert cr.pattern == "broadcast", cr
    out["commcheck_derive_s"] = round(best, 4)
    out["commcheck_tasks_per_s"] = round(cr.ntasks / max(best, 1e-9), 1)
    t0 = time.perf_counter()
    predict_collective_traffic(4, payload_bytes=1 << 16)
    out["commcheck_predict_s"] = round(time.perf_counter() - t0, 4)
    return out


def bench_tune(smoke: bool = False) -> dict:
    """Autotuner plumbing costs (ISSUE 18): the search-harness overhead
    per trial (no-op objective, so everything BUT the workload is on
    the clock), and the tuning-DB consult latency over a populated
    store through the cached generation-checked path — the
    Context-start / per-tenant-submit probe the perf_smoke gate pins
    at <= 50us."""
    import os
    import tempfile

    from parsec_tpu.core.params import KnobSpec, params
    from parsec_tpu.tune.db import TuneDB, cached_db
    from parsec_tpu.tune.search import search

    out: dict = {}
    trials = 16 if smoke else 48
    saved = params.get("perfdb")
    with tempfile.TemporaryDirectory(prefix="tune_mb_") as d:
        db = TuneDB(os.path.join(d, "tunedb.jsonl"))
        space = {"a": KnobSpec(name="a", lo=1, hi=1 << 20, scale="log2"),
                 "b": KnobSpec(name="b", values=("x", "y", "z"))}
        params.set("perfdb", False)     # pure harness cost, no ledger I/O
        # backend_signature's first call imports jax — a one-time
        # process cost, not a per-trial one: warm it off the clock
        from parsec_tpu.prof.perfdb import backend_signature
        backend_signature()
        try:
            t0 = time.perf_counter()
            res = search(lambda _k: 1.0, signature="microbench:noop",
                         space=space, budget=trials, restarts=4,
                         objective="cost_s", seed=3, db=db, persist=False)
            dt = time.perf_counter() - t0
        finally:
            params.set("perfdb", saved)
        out["tune_search_trials"] = res["evals"]
        out["tune_search_overhead_us_per_trial"] = round(
            dt / max(res["evals"], 1) * 1e6, 2)
        # the consult path: 200 signatures' bests out of one parsed
        # generation — the dict probe is what repeats per Context/tenant
        nsig = 200
        for i in range(nsig):
            db.note(f"wl:mb:{i}", {"a": i + 1}, float(i + 1),
                    objective="wall_s")
        reps = 500 if smoke else 2000
        cached_db(db.path).best("wl:mb:0", objective="wall_s")  # warm parse
        t0 = time.perf_counter()
        for i in range(reps):
            cached_db(db.path).best(f"wl:mb:{i % nsig}",
                                    objective="wall_s")
        dt = time.perf_counter() - t0
        out["tune_db_records"] = nsig
        out["tune_db_lookup_us"] = round(dt / reps * 1e6, 3)
    return out


def run_all(smoke: bool = False, include_lowering: bool = True,
            include_serve: bool = True, include_comm: bool = True,
            include_llm: bool = True) -> dict:
    """Every micro number in one dict (the bench `overhead` stage payload).
    ``include_lowering=False`` skips the only jax-touching section — the
    scheduling-path numbers then need no accelerator stack at all.
    ``include_serve=False``/``include_comm=False``/``include_llm=False``
    skip the serving/comm/LLM numbers (bench.py runs those in dedicated
    stages instead of twice)."""
    ntasks = 2000 if smoke else 10000
    reps = 3 if smoke else 5
    out: dict = {}
    out.update(bench_dispatch_us(ntasks, reps))
    out.update(bench_release_throughput(ntasks, max(reps - 2, 1)))
    out.update(bench_steal_us())
    out.update(bench_pins_disabled_ns(50000 if smoke else 200000))
    out.update(bench_tracing(smoke=smoke))
    if include_serve:
        out.update(bench_serve(nsub=16 if smoke else 64,
                               depth=4 if smoke else 8))
    if include_llm:
        out.update(bench_llm(smoke=smoke))
        try:
            out.update(bench_llm_prefix(smoke=smoke))
        except Exception as e:        # noqa: BLE001 — evidence over abort
            out["llm_prefix_error"] = f"{type(e).__name__}: {e}"
        try:
            out.update(bench_llm_tier(smoke=smoke))
        except Exception as e:        # noqa: BLE001 — evidence over abort
            out["llm_tier_error"] = f"{type(e).__name__}: {e}"
    if include_comm:
        out.update(bench_comm(smoke=smoke))
    if include_lowering:
        try:
            out.update(bench_lowering_cache())
        except Exception as e:            # noqa: BLE001 — evidence over abort
            out["lowering_cache_error"] = f"{type(e).__name__}: {e}"
        try:
            out.update(bench_lowering(smoke=smoke))
        except Exception as e:            # noqa: BLE001 — evidence over abort
            out["lowering_bench_error"] = f"{type(e).__name__}: {e}"
    try:
        out.update(bench_tune(smoke=smoke))
    except Exception as e:            # noqa: BLE001 — evidence over abort
        out["tune_bench_error"] = f"{type(e).__name__}: {e}"
    try:
        out.update(bench_commcheck(smoke=smoke))
    except Exception as e:            # noqa: BLE001 — evidence over abort
        out["commcheck_bench_error"] = f"{type(e).__name__}: {e}"
    # persistent perf ledger (prof/perfdb.py): every scalar lands under
    # the microbench.run_all workload so consecutive runs accrue EWMA
    # history; MCA perfdb=0 disables, and a ledger failure never costs
    # the run its numbers
    try:
        from parsec_tpu.core.params import params as _params
        from parsec_tpu.prof.perfdb import PerfDB
        if _params.get("perfdb"):
            PerfDB().note_result("microbench.run_all", out)
    except Exception:       # noqa: BLE001 — evidence over abort
        pass
    return out


if __name__ == "__main__":
    import os
    import sys
    smoke = os.environ.get("BENCH_SMOKE") == "1" or "--smoke" in sys.argv
    print(json.dumps(run_all(smoke=smoke)))
