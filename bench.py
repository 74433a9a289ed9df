#!/usr/bin/env python
"""Benchmark driver: prints ONE JSON line with the headline metric.

Headline (BASELINE.md): PTG tiled-GEMM GFLOPS/chip at N=16384, nb=512.
The taskpool executes through the framework's compiled path — the PTG GEMM
dataflow lowered to a single XLA program on the chip (the dynamic-runtime
path covers irregular/distributed graphs; on one chip the lowered program is
the framework's GEMM incarnation).  ``vs_baseline`` is measured GFLOPS over
the north-star target (70% of the chip's peak bf16 GFLOPS, BASELINE.md), so
>= 1.0 beats the target.

``extra`` carries the secondary metric: task-dispatch per-task latency of the
dynamic runtime on the EP CTL-only DAG (the reference's
tests/runtime/scheduling/ep.jdf shape).
"""

from __future__ import annotations

import json
import statistics
import time


def bench_gemm_gflops(n: int = 16384, nb: int = 512, reps: int = 48) -> dict:
    """Steady-state throughput of the PTG tiled-GEMM taskpool, executed
    through the framework's compiled incarnation: ``tiled_gemm_ptg`` builds
    the GEMM(m,n,k) task graph, ``lower_taskpool`` collapses its k-chain to
    one XLA contraction over the tile stores, and a dependent chain of
    ``reps`` taskpool executions runs inside one program.  Each timed
    window closes on ``block_until_ready``."""
    import functools

    import jax
    import numpy as np

    from parsec_tpu.data_dist.matrix import TiledMatrix
    from parsec_tpu.device.tpu import _flop_rating
    from parsec_tpu.models.tiled_gemm import tiled_gemm_ptg
    from parsec_tpu.ptg.lowering import lower_taskpool

    dev = jax.devices()[0]
    kind = getattr(dev, "device_kind", "unknown")
    peak_bf16, _ = _flop_rating(kind.lower())

    import jax.numpy as jnp
    bf16 = np.dtype(jnp.bfloat16)

    def mk(name, dtype):
        def init(m, n_, shape):
            rng = np.random.default_rng((hash((name, m, n_)) & 0x7FFFFFFF))
            return rng.standard_normal(shape, dtype=np.float32).astype(dtype)
        return TiledMatrix(name, n, n, nb, nb, dtype=dtype, init_fn=init)

    A, B = mk("A", bf16), mk("B", bf16)
    C = TiledMatrix("C", n, n, nb, nb, dtype=np.float32,
                    init_fn=lambda m, n_, s: np.zeros(s, np.float32))

    low = lower_taskpool(tiled_gemm_ptg(A, B, C))
    assert low.mode == "chain-collapse", low.mode
    stores = {k: jax.device_put(v, dev) for k, v in
              low.initial_stores().items()}
    step = low.step_fn

    @functools.partial(jax.jit, static_argnames=("reps",))
    def chain(st, reps):
        # the (zero) feedback of C into A makes each taskpool execution
        # loop-carried, so XLA cannot hoist the contraction as invariant
        def body(st, _):
            # tiny in-place (DUS) perturbation instead of a full A+eps copy
            eps = (st["C"].reshape(-1)[0] * 0).astype(st["A"].dtype)
            st = dict(st)
            st["A"] = st["A"].at[0, 0].add(eps)
            return step(st), None
        st, _ = jax.lax.scan(body, st, None, length=reps)
        return st

    _note_partial(phase="compile", lowering_mode=low.mode)
    tc = time.perf_counter()
    jax.block_until_ready(chain(stores, reps))  # compile + warm
    compile_s = time.perf_counter() - tc
    _note_partial(phase="measure", compile_s=round(compile_s, 1))
    times = []
    for _i in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(chain(stores, reps))
        times.append(time.perf_counter() - t0)
    t = statistics.median(times)
    gflops = 2.0 * n * n * n * reps / t / 1e9
    return {
        "gflops": gflops,
        "peak_gflops": peak_bf16,
        "pct_peak": 100.0 * gflops / peak_bf16,
        "device_kind": kind,
        "n": n,
        "nb": nb,
        "reps": reps,
        "seconds": t,
        "compile_s": round(compile_s, 1),
        "lowering": low.mode,
    }


def bench_raw_dot_gflops(n: int = 16384, reps: int = 48) -> dict:
    """Honesty cross-check for the headline (VERDICT r3 weak #6): the same
    flops as ONE bare ``jnp.dot`` chain, no framework anywhere — pct_peak
    rests on the hand-entered flop table, so record what the raw compiler
    achieves on this chip under the identical loop-carry discipline."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((n, n), dtype=np.float32),
                    dtype=jnp.bfloat16)
    b = jnp.asarray(rng.standard_normal((n, n), dtype=np.float32),
                    dtype=jnp.bfloat16)

    @functools.partial(jax.jit, static_argnames=("reps",))
    def chain(a, b, reps):
        def body(c, _):
            # feed (zero of) c back into a so the dot is loop-carried
            eps = (c.reshape(-1)[0] * 0).astype(a.dtype)
            return jnp.dot(a.at[0, 0].add(eps), b,
                           preferred_element_type=jnp.float32), None
        c0 = jnp.zeros((n, n), jnp.float32)
        c, _ = jax.lax.scan(body, c0, None, length=reps)
        return c

    jax.block_until_ready(chain(a, b, reps))   # compile + warm
    times = []
    for _i in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(chain(a, b, reps))
        times.append(time.perf_counter() - t0)
    t = statistics.median(times)
    return {"gflops": 2.0 * n * n * n * reps / t / 1e9, "n": n,
            "reps": reps, "seconds": t}


def bench_dynamic_gemm_gflops(n: int = 8192, nb: int = 1024) -> dict:
    """The dynamic-runtime path on the real chip: PTG GEMM(m,n,k) executed
    task by task through the TPU device module (stage-in, LRU cache, vmapped
    same-class batching) — no lowering.  The number the reference's
    ``dtd_test_simple_gemm`` prints (VERDICT r2 weak #1: the dynamic path
    had never produced a TPU figure)."""
    import jax
    import numpy as np

    from parsec_tpu.data_dist.matrix import TiledMatrix
    from parsec_tpu.device.tpu import init_tpu_devices
    from parsec_tpu.models.tiled_gemm import tiled_gemm_ptg
    from parsec_tpu.runtime import Context

    devs = init_tpu_devices()
    if not devs:
        return {"gflops": 0.0, "note": "no accelerator visible"}
    dev = devs[0]

    def init(name):
        def fn(m, n_, shape):
            rng = np.random.default_rng(hash((name, m, n_)) & 0x7FFFFFFF)
            return rng.standard_normal(shape, dtype=np.float32)
        return fn

    A = TiledMatrix("A", n, n, nb, nb, init_fn=init("A"))
    B = TiledMatrix("B", n, n, nb, nb, init_fn=init("B"))
    C = TiledMatrix("C", n, n, nb, nb,
                    init_fn=lambda m, n_, s: np.zeros(s, np.float32))
    # materialize every tile BEFORE the clock starts: host RNG generation
    # is harness setup, not framework work (the reference's harnesses also
    # exclude matrix generation from the timed region)
    for M in (A, B, C):
        for i in range(M.mt):
            for j in range(M.nt):
                M.data_of(i, j)
    tp = tiled_gemm_ptg(A, B, C, devices="tpu")

    # dispatch latency: one tiny program, enqueue to completion — the
    # per-call floor every dependent device call pays
    import jax.numpy as jnp
    tiny = jax.jit(lambda x: x + 1)
    jax.block_until_ready(tiny(jnp.float32(0)))          # compile
    lats = []
    for _i in range(5):
        r0 = time.perf_counter()
        jax.block_until_ready(tiny(jnp.float32(_i)))
        lats.append(time.perf_counter() - r0)
    dispatch_latency = statistics.median(lats)

    calls0, ts0 = dev.xla_calls, dev.t_stage_in
    td0, tc0, tdr0 = dev.t_dispatch, dev.t_complete, dev.t_drain
    bin0 = dev.bytes_in
    tm0 = dev.t_manager
    ctx = Context(nb_cores=0)
    t0 = time.perf_counter()
    deadline = t0 + 120
    try:
        ctx.add_taskpool(tp)
        ctx.wait(timeout=120)
        t_drained = time.perf_counter() - t0
        dev.sync()
        jax.block_until_ready(
            C.data_of(C.mt - 1, C.nt - 1).newest_copy().value)
        t = time.perf_counter() - t0
    finally:
        # bounded drain reusing this stage's (possibly expired) deadline:
        # a timed-out wait must not leak the Context + tile set into every
        # later stage, and fini on a wedged device must not hang the
        # cleanup forever either (it stall-dumps and aborts instead)
        ctx.fini(timeout=max(0.0, deadline - time.perf_counter()))
    calls = dev.xla_calls - calls0
    h2d = dev.bytes_in - bin0
    stage_s = dev.t_stage_in - ts0
    breakdown = {
        # H2D volume + achieved rate of the stage-in phase
        "h2d_mb": round(h2d / 1e6, 1),
        "h2d_MBps": round(h2d / 1e6 / stage_s, 1) if stage_s > 0 else 0.0,
        # phase walls: what the manager thread actually spent
        "stage_in_s": round(dev.t_stage_in - ts0, 3),
        "dispatch_s": round(dev.t_dispatch - td0, 3),
        "complete_s": round(dev.t_complete - tc0, 3),
        "drain_s": round(dev.t_drain - tdr0, 3),
        "manager_s": round(dev.t_manager - tm0, 3),
        "final_sync_s": round(t - t_drained, 3),
        "xla_calls": calls,
        "dispatch_latency_ms": round(dispatch_latency * 1e3, 2),
        # the dispatch-latency floor: a dependent-call chain cannot
        # finish faster than calls * latency; compare with the measured
        # wall to attribute device-call vs framework cost
        "dispatch_floor_s": round(calls * dispatch_latency, 3),
        # MXU floor: the same flops at the chip's fp32 rating (the
        # dynamic path computes in f32, not the bf16 headline peak)
        "onchip_floor_s": round(
            2.0 * n * n * n / (dev.gflops_fp32 * 1e9), 3),
    }
    return {
        "gflops": 2.0 * n * n * n / t / 1e9,
        "n": n, "nb": nb, "seconds": t,
        "tasks": dev.executed_tasks,
        "batched_dispatches": dev.batched_dispatches,
        "breakdown": breakdown,
    }




def bench_dynamic_cholesky_gflops(n: int = 8192, nb: int = 1024) -> dict:
    """Dynamic-path tiled Cholesky on the chip (BASELINE staged config #5):
    four task classes, triangular space, range arrows."""
    import jax
    import numpy as np

    from parsec_tpu.data_dist.matrix import SymTwoDimBlockCyclic
    from parsec_tpu.device.tpu import init_tpu_devices
    from parsec_tpu.models.cholesky import (cholesky_flops, make_spd,
                                            tiled_cholesky_ptg)
    from parsec_tpu.runtime import Context

    devs = init_tpu_devices()
    if not devs:
        return {"gflops": 0.0, "note": "no accelerator visible"}
    dev = devs[0]
    a = make_spd(n)
    A = SymTwoDimBlockCyclic.from_dense("A", a, nb, nb)
    tp = tiled_cholesky_ptg(A, devices="tpu")
    ctx = Context(nb_cores=0)
    t0 = time.perf_counter()
    deadline = t0 + 120
    try:
        ctx.add_taskpool(tp)
        ctx.wait(timeout=120)
        dev.sync()
        jax.block_until_ready(
            A.data_of(A.mt - 1, A.mt - 1).newest_copy().value)
        t = time.perf_counter() - t0
    finally:
        ctx.fini(timeout=max(0.0, deadline - time.perf_counter()))
    # correctness spot check: || L[0,0] - chol(A)[0,0] tile || small
    got = np.asarray(A.data_of(0, 0).newest_copy().value)
    expect = np.linalg.cholesky(a[:nb, :nb].astype(np.float64))
    err = float(np.max(np.abs(np.tril(got) - expect)))
    return {
        "gflops": cholesky_flops(n) / t / 1e9,
        "n": n, "nb": nb, "seconds": t, "tile00_abs_err": err,
    }


def bench_tuned_cholesky(n: int = 512, nb_bad: int = 32,
                         budget: int = 8) -> dict:
    """The closed-loop autotuner stage (ISSUE 18): a deliberately
    mis-knobbed small dynamic Cholesky — tile ``nb`` far too small, so
    per-task dispatch overhead dominates — is handed to ``tune.search``
    with the tile size as a workload-level knob.  The search must
    recover a sane configuration within its trial budget; the winner
    persists to ``tunedb.jsonl`` under the workload's structural
    signature.  Headline: ``tune_speedup`` = seeded-bad wall / tuned
    wall (perf_smoke gates >= 1.2).  Every trial partial-flushes via
    ``_note_partial`` so a deadline death keeps the search trajectory."""
    import numpy as np

    from parsec_tpu.core.params import KnobSpec
    from parsec_tpu.data_dist.matrix import SymTwoDimBlockCyclic
    from parsec_tpu.device.tpu import init_tpu_devices
    from parsec_tpu.models.cholesky import make_spd, tiled_cholesky_ptg
    from parsec_tpu.runtime import Context
    from parsec_tpu.tune import workload_signature
    from parsec_tpu.tune.search import search

    if not init_tpu_devices():
        return {"tune_speedup": 0.0, "note": "no accelerator visible"}
    a = make_spd(n)

    def one(nb: int) -> float:
        A = SymTwoDimBlockCyclic.from_dense("A", a, nb, nb)
        tp = tiled_cholesky_ptg(A, devices="tpu")
        ctx = Context(nb_cores=0)
        t0 = time.perf_counter()
        try:
            ctx.add_taskpool(tp)
            ctx.wait(timeout=60)
            t = time.perf_counter() - t0
        finally:
            ctx.fini(timeout=30)
        return t

    warmed: set = set()

    def run_once(knobs: dict) -> float:
        # each tile shape compiles its kernels on first touch; the
        # tuner scores STEADY STATE (the config a server would run at),
        # so a trial's first visit to a shape warms it off the clock
        nb = int(knobs.get("nb", nb_bad))
        if nb not in warmed:
            warmed.add(nb)
            one(nb)
        return one(nb)

    sig = workload_signature(
        tiled_cholesky_ptg(
            SymTwoDimBlockCyclic.from_dense("A", a, nb_bad, nb_bad),
            devices="tpu"),
        size_hint=n)
    # the seeded-bad configuration IS the baseline the loop must beat
    baseline_s = run_once({"nb": nb_bad})
    _note_partial(tuned_baseline_s=round(baseline_s, 4))
    space = {"nb": KnobSpec(name="nb", lo=32, hi=max(64, n // 2),
                            scale="log2")}

    def flush(trial: int, score: float, knobs: dict) -> None:
        _note_partial(tune_trials=trial,
                      **{f"tune_trial{trial}_s": round(score, 4),
                         f"tune_trial{trial}_nb": int(knobs.get(
                             "nb", 0))})

    out = search(run_once, signature=sig, space=space, budget=budget,
                 restarts=1, objective="wall_s", seed=0,
                 start={"nb": nb_bad}, note=flush)
    best = out["best"] or {"nb": nb_bad}
    tuned_s = float(out["best_score"] or baseline_s)
    _note_partial(tune_speedup=round(baseline_s / max(tuned_s, 1e-9), 3))
    # correctness is not negotiable for a tuner: the winner's factor is
    # still a Cholesky factor
    A = SymTwoDimBlockCyclic.from_dense("A", a, int(best["nb"]),
                                        int(best["nb"]))
    tp = tiled_cholesky_ptg(A, devices="tpu")
    ctx = Context(nb_cores=0)
    try:
        ctx.add_taskpool(tp)
        ctx.wait(timeout=60)
    finally:
        ctx.fini(timeout=30)
    got = np.asarray(A.data_of(0, 0).newest_copy().value)
    k = int(best["nb"])
    expect = np.linalg.cholesky(a[:k, :k].astype(np.float64))
    err = float(np.max(np.abs(np.tril(got) - expect)))
    return {
        "tune_speedup": round(baseline_s / max(tuned_s, 1e-9), 3),
        "baseline_s": round(baseline_s, 4), "tuned_s": round(tuned_s, 4),
        "nb_bad": nb_bad, "best_nb": int(best["nb"]), "n": n,
        "evals": out["evals"], "pruned": out["pruned"],
        "signature": sig, "db_path": out.get("db_path", ""),
        "tile00_abs_err": err,
    }


def _stage_budgets() -> dict[str, float]:
    """Per-stage wall-clock budgets from the ``bench_stage_budget_s``
    MCA param (env: ``PARSEC_MCA_bench_stage_budget_s``).  Spec grammar:
    a bare float rebudgets EVERY stage; a comma list of ``name=seconds``
    pairs rebudgets named stages (``*=seconds`` sets the default).  The
    hard-coded defaults in :func:`main` are the fallback — this is the
    knob that lets a TPU run give ``lowered_cholesky`` the compile room
    BENCH_r04/r05 lacked without recutting the harness."""
    import os
    spec = ""
    try:
        from parsec_tpu.core.params import params as _p
        _p.register(
            "bench_stage_budget_s", "",
            "per-stage bench budget override: '<seconds>' for all stages "
            "or 'name=sec,name2=sec' ('*' = default); empty keeps the "
            "harness defaults")
        spec = str(_p.get("bench_stage_budget_s") or "")
    except Exception:                      # noqa: BLE001 — env fallback
        spec = os.environ.get("PARSEC_MCA_bench_stage_budget_s", "")
    out: dict[str, float] = {}
    spec = spec.strip()
    if not spec:
        return out
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        if "=" in part:
            name, _, val = part.partition("=")
            try:
                out[name.strip()] = float(val)
            except ValueError:
                pass
        else:
            try:
                out["*"] = float(part)
            except ValueError:
                pass
    return out


_stage_partials: dict[str, dict] = {}


def _note_partial(**kw) -> None:
    """Flush partial metrics from INSIDE a running stage (keyed by the
    ``bench-<stage>`` worker-thread name).  When the stage later dies on
    its deadline — historically in XLA compile (BENCH_r04/r05, rc 124) —
    the degrade record carries whatever landed here instead of losing
    the stage entirely, and ``phase == "compile"`` at timeout turns the
    record into a ``{"status": "compile_timeout"}`` entry.

    Every flush also snapshots the live SLO histogram planes
    (serialized bucket arrays, ``prof/histogram.serialized_planes``): a
    deadline death mid-serve/llm stage keeps the latency DISTRIBUTION
    collected so far — reconstructable with ``LogHistogram.from_dict``
    — not just the counters."""
    import threading
    name = threading.current_thread().name
    if name.startswith("bench-"):
        d = _stage_partials.setdefault(name[len("bench-"):], {})
        d.update(kw)
        try:
            from parsec_tpu.prof.histogram import serialized_planes
            s = serialized_planes()
            if s:
                d["slo_hist"] = s
        except Exception:       # noqa: BLE001 — partials must never raise
            pass
        try:
            # the XLA-dispatch ledger rides every flush too: an rc-124
            # death keeps the calls-per-DAG axis (ISSUE 16 satellite —
            # the r06 campaign reads it off the partial)
            from parsec_tpu.device.device import xla_calls_total
            d["xla_calls_total"] = xla_calls_total()
        except Exception:       # noqa: BLE001 — partials must never raise
            pass


_perfdb_state: dict = {"regressions": []}


def _perfdb_note(name: str, result) -> None:
    """Append this stage's scalars to the persistent perf ledger and
    verdict each against its EWMA history (prof/perfdb.py): the
    regression sentinel's bench hook.  Prints one per-stage verdict
    line to stderr; regressions accumulate into ``_perfdb_state`` and
    ride the emit as ``perfdb_regressions``.  Never raises, and MCA
    ``perfdb=0`` disables it entirely."""
    import sys
    try:
        from parsec_tpu.core.params import params
        from parsec_tpu.prof.perfdb import PerfDB
        if not params.get("perfdb"):
            return
        if isinstance(result, (int, float)) and not isinstance(result, bool):
            result = {"value": float(result)}
        if not isinstance(result, dict):
            return
        notes = PerfDB().note_result(f"bench.{name}", result)
        if not notes:
            return
        reg = [n for n in notes if n["verdict"] == "regressed"]
        imp = [n for n in notes if n["verdict"] == "improved"]
        for n2 in reg:
            _perfdb_state["regressions"].append(
                {"stage": name, "metric": n2["metric"],
                 "value": n2["value"], "z": n2.get("z"),
                 "ewma": n2.get("ewma")})
        if reg:
            verdict = "REGRESSED " + ",".join(
                f"{n['metric']} (z={n['z']})" for n in reg)
        elif imp:
            verdict = "improved " + ",".join(n["metric"] for n in imp)
        elif all(n["verdict"] == "warming" for n in notes):
            verdict = "warming"
        else:
            verdict = "ok"
        print(f"[perfdb] {name}: {len(notes)} metric(s) -> {verdict}",
              file=sys.stderr, flush=True)
    except Exception:       # noqa: BLE001 — the ledger must never cost a run
        pass


def _time_lowered(low, sync_store: str, reps: int = 3):
    """Shared lowered-bench harness: device stores, jit, warm, then the
    median of ``reps`` runs each synced by a device-side SCALAR read —
    ``np.asarray(out)`` would pull the whole store to the host and time
    the transfer (the round-3 bench bug this guards against).
    Returns ``(median_seconds, compile_seconds, last_out)`` — compile is
    attributed separately (VERDICT r4 weak #2: at O(wavefronts x classes)
    ops the XLA compile may itself be the wall; without the split the run
    number is uninterpretable).  ``low.jitted()`` consults the process-wide
    lowering cache, so a re-invoked identical stage reports a near-zero
    ``*_compile_s`` instead of re-paying the trace+compile."""
    import jax
    st = {k: jax.device_put(v) for k, v in low.initial_stores().items()}
    jf = low.jitted()
    # pre-flight BEFORE the first (compiling) call: a deadline death
    # mid-XLA-compile then names the program and its budget context
    # (whole-pool lowerings are one region; the region stage reports
    # its own per-region notes through plan.compile(note=...))
    from parsec_tpu.core.params import params as _mca
    _note_partial(phase="compile", lowering_mode=low.mode, region_count=1,
                  budget_s=float(_mca.get("lowering_compile_budget_s",
                                          0.0) or 0.0))
    tc = time.perf_counter()
    out = jf(st)
    _ = float(out[sync_store].reshape(-1)[0])    # compile + warm
    compile_s = time.perf_counter() - tc
    _note_partial(phase="measure", compile_s=round(compile_s, 1))
    times = []
    for _i in range(reps):
        t0 = time.perf_counter()
        out = jf(st)
        _ = float(out[sync_store].reshape(-1)[0])
        times.append(time.perf_counter() - t0)
    return statistics.median(times), compile_s, out


def bench_lowered_cholesky_gflops(n: int = 16384, nb: int = 512) -> dict:
    """The compiled incarnation of the Cholesky PTG: four task classes,
    triangular space, batched per topological wavefront by the lowering —
    every panel's trailing update lands on the MXU as ONE batched tile
    matmul.  For scale: XLA's own jnp.linalg.cholesky runs n=8192 at ~12
    GFLOPS on a v5e; the wavefront program measures in the TFLOPS.  Synced
    by a device-side scalar read (np.asarray(out) would pull the whole
    factored matrix to the host and time the transfer, which is exactly
    the round-3 bench bug this replaces)."""
    import numpy as np

    from parsec_tpu.data_dist.matrix import SymTwoDimBlockCyclic
    from parsec_tpu.models.cholesky import (cholesky_flops, make_spd_fast,
                                            tiled_cholesky_ptg)
    from parsec_tpu.ptg.lowering import lower_taskpool

    a = make_spd_fast(n)
    A = SymTwoDimBlockCyclic.from_dense("A", a, nb, nb)
    low = lower_taskpool(tiled_cholesky_ptg(A))
    t, compile_s, out = _time_lowered(low, "A")
    # spot-check the first tile against the dense factorization
    got = np.asarray(out["A"][0])
    expect = np.linalg.cholesky(a[:nb, :nb].astype(np.float64))
    err = float(np.max(np.abs(np.tril(got) - expect)))
    return {"gflops": cholesky_flops(n) / t / 1e9, "n": n, "nb": nb,
            "seconds": t, "compile_s": round(compile_s, 1),
            "mode": low.mode, "tile00_abs_err": err}


def bench_region_cholesky_gflops(n: int = 8192, nb: int = 512,
                                 budget_s: float | None = None) -> dict:
    """The megakernel-region incarnation of the Cholesky PTG (ISSUE 8):
    graphcheck-verified regions, one jitted program each, the runtime
    scheduling regions at boundaries — compiled under an explicit budget
    so this stage can never die rc-124 mid-XLA-compile (the BENCH_r04/r05
    shape): regions the budget cannot afford run the eager op-by-op path
    instead, and the stats say which.  Every region's compile progress
    pre-flights through ``_note_partial``, so a deadline death names the
    region that was compiling."""
    import numpy as np

    from parsec_tpu.core.params import params
    from parsec_tpu.data_dist.matrix import SymTwoDimBlockCyclic
    from parsec_tpu.models.cholesky import (cholesky_flops, make_spd_fast,
                                            tiled_cholesky_ptg)
    from parsec_tpu.ptg.lowering import lower_regions

    a = make_spd_fast(n)
    A = SymTwoDimBlockCyclic.from_dense("A", a, nb, nb)
    plan = lower_regions(tiled_cholesky_ptg(A))
    if budget_s is None:
        b = float(params.get("lowering_compile_budget_s") or 0.0)
        # unbudgeted MCA default -> still bound the stage's compile: the
        # harness gives this stage ~150s, leave the rest for execution
        budget_s = b if b > 0 else 90.0
    _note_partial(phase="compile", region_count=len(plan.regions),
                  budget_s=round(budget_s, 1))
    plan.compile(budget_s=budget_s,
                 note=lambda **kw: _note_partial(phase="compile", **kw))
    st = plan.stats()
    _note_partial(phase="measure", compile_s=st["compile_s"],
                  regions_eager=st["regions_eager"])
    # timed region: region-grained scheduling + execution only — table
    # materialization is harness setup (the lowered stages' discipline),
    # writeback rides the pool's completion listener inside the run
    from parsec_tpu.runtime import Context
    table = plan.materialize_table()
    ctx = Context(nb_cores=0)
    t0 = time.perf_counter()
    try:
        ctx.add_taskpool(plan.taskpool(table))
        ctx.wait(timeout=120)
        t = time.perf_counter() - t0
    finally:
        ctx.fini(timeout=30)
    plan.finalize(table)        # no-op when the listener already ran
    st = plan.stats()
    got = np.asarray(A.data_of(0, 0).newest_copy().value)
    expect = np.linalg.cholesky(a[:nb, :nb].astype(np.float64))
    err = float(np.max(np.abs(np.tril(got) - expect)))
    return {"gflops": cholesky_flops(n) / t / 1e9, "n": n, "nb": nb,
            "seconds": t, "mode": "region", "regions": st["regions"],
            "regions_compiled": st["regions_compiled"],
            "regions_eager": st["regions_eager"],
            "xla_calls": st["xla_calls"],
            "trace_s": st["trace_s"], "compile_s": st["compile_s"],
            "budget_s": round(budget_s, 1), "tile00_abs_err": err}


def bench_lowered_lu_gflops(n: int = 8192, nb: int = 512) -> dict:
    """The compiled incarnation of the LU-nopiv PTG — the third dense
    factorization through the wavefront pass (GETRF/TRSM_L/TRSM_U/GEMM,
    square space): every panel's trailing update is one batched tile
    matmul.  Scalar-read synced like the Cholesky stage."""
    import numpy as np

    from parsec_tpu.data_dist.matrix import TiledMatrix
    from parsec_tpu.models.lu import lu_flops, make_dd, tiled_lu_ptg
    from parsec_tpu.ptg.lowering import lower_taskpool

    a = make_dd(n, seed=1).astype(np.float32)
    A = TiledMatrix.from_dense("A", a.copy(), nb, nb)
    low = lower_taskpool(tiled_lu_ptg(A))
    t, compile_s, out = _time_lowered(low, "A")
    # spot-check tile (0,0): L\U packed must match the dense recursion
    from parsec_tpu.models.lu import _getrf_nopiv_np
    got = np.asarray(out["A"][0])
    expect = _getrf_nopiv_np(a[:nb, :nb].astype(np.float64))
    err = float(np.max(np.abs(got - expect)))
    return {"gflops": lu_flops(n) / t / 1e9, "n": n, "nb": nb,
            "seconds": t, "compile_s": round(compile_s, 1),
            "mode": low.mode, "tile00_abs_err": err}


def bench_lowered_stencil_gflops(n: int = 1 << 24, mb: int = 1 << 18,
                                 radius: int = 4, iterations: int = 64) -> dict:
    """The compiled incarnation of the 1-D stencil app (halo-exchange tier):
    T wavefronts, each ONE batched (2R+1)-tap update over all tiles, ghost
    reads as store gathers.  Memory-bound by design — the number measures
    how close the emitted program gets to HBM bandwidth."""
    import numpy as np

    from parsec_tpu.data_dist.matrix import VectorTwoDimCyclic
    from parsec_tpu.models.stencil import (stencil_1d_ptg, stencil_flops,
                                           stencil_reference)
    from parsec_tpu.ptg.lowering import lower_taskpool

    rng = np.random.default_rng(0)
    base = rng.standard_normal(n).astype(np.float32)
    V = VectorTwoDimCyclic("V", lm=n, mb=mb, P=1,
                           init_fn=lambda m, size:
                           base[m * mb:m * mb + size])
    weights = np.full(2 * radius + 1, 1.0 / (2 * radius + 1))
    low = lower_taskpool(stencil_1d_ptg(V, weights, iterations))
    t, compile_s, out = _time_lowered(low, "V")
    # spot-check the first tile against the dense oracle
    got = np.asarray(out["V"][0])
    want = stencil_reference(base, weights, iterations)[:mb]
    err = float(np.max(np.abs(got - want)))
    return {"gflops": stencil_flops(n, radius, iterations) / t / 1e9,
            "seconds": t, "compile_s": round(compile_s, 1), "n": n,
            "mb": mb, "radius": radius,
            "iterations": iterations, "mode": low.mode, "max_abs_err": err}


def bench_dtd_gemm_tpu(n: int = 8192, nb: int = 1024) -> dict:
    """DTD (dynamic task discovery) GEMM on the chip — the reference's
    flagship DTD perf harness (``tests/dsl/dtd/dtd_test_simple_gemm.c:
    649-667``): GEMM(m,n,k) tasks inserted at runtime, hazards discovered
    from tile access chains, bodies dispatched through the TPU device
    module (``tpu_kernel="gemm"`` chores, vmapped same-class batching)."""
    import jax
    import numpy as np

    import parsec_tpu.ops.gemm  # noqa: F401  registers the "gemm" kernels
    from parsec_tpu.device.tpu import init_tpu_devices
    from parsec_tpu.dtd import INOUT, INPUT, DTDTaskpool
    from parsec_tpu.runtime import Context

    devs = init_tpu_devices()
    if not devs:
        return {"gflops": 0.0, "note": "no accelerator visible"}
    dev = devs[0]
    NT = n // nb
    rng = np.random.default_rng(5)

    def tile():
        return rng.standard_normal((nb, nb), dtype=np.float32)

    A = [[tile() for _ in range(NT)] for _ in range(NT)]
    B = [[tile() for _ in range(NT)] for _ in range(NT)]
    C = [[np.zeros((nb, nb), np.float32) for _ in range(NT)]
         for _ in range(NT)]

    def gemm(a, b, c):          # CPU incarnation (fallback chore)
        c += a.astype(np.float32) @ b.astype(np.float32)

    ctx = Context(nb_cores=0)
    tp = DTDTaskpool()
    deadline = time.perf_counter() + 150
    try:
        ctx.add_taskpool(tp)
        t0 = time.perf_counter()
        for m in range(NT):
            for n_ in range(NT):
                for k in range(NT):
                    tp.insert_task(gemm, (A[m][k], INPUT),
                                   (B[k][n_], INPUT),
                                   (C[m][n_], INOUT), tpu_kernel="gemm")
        tp.wait()
        dev.sync()
        jax.block_until_ready(
            tp.tile_of_array(C[0][0]).data.newest_copy().value)
        t = time.perf_counter() - t0
        # spot-check OUTSIDE the timed section: read the final (device)
        # version of one C tile — a full-tile D2H pull, not framework work
        got = np.asarray(tp.tile_of_array(C[0][0]).data.newest_copy().value)
    finally:
        ctx.fini(timeout=max(0.0, deadline - time.perf_counter()))
    want = np.zeros((nb, nb), np.float32)
    for k in range(NT):
        want += A[0][k] @ B[k][0]
    err = float(np.max(np.abs(got - want)) / max(1.0, np.abs(want).max()))
    return {"gflops": 2.0 * n * n * n / t / 1e9, "n": n, "nb": nb,
            "seconds": t, "tile00_rel_err": err,
            "tasks": dev.executed_tasks,
            "batched_dispatches": dev.batched_dispatches}


def bench_overhead() -> dict:
    """The critical-path micro stage (microbench.py): dispatch latency,
    dep-release throughput, lfq local-pop/steal latency, PINS site cost,
    and lowering-cache compile times — ALL measurable with no accelerator,
    so this stage runs FIRST and the perf axis can never go fully dark
    again (ISSUE 2; round 5 shipped no dispatch evidence at all).  The
    lowering-cache half touches jax, so it only runs when the platform is
    explicitly CPU (an unreachable accelerator must not hang the
    always-first stage)."""
    import os

    from microbench import run_all
    smoke = os.environ.get("BENCH_SMOKE") == "1"
    platform = (os.environ.get("BENCH_PLATFORM")
                or os.environ.get("JAX_PLATFORMS") or "")
    out = run_all(smoke=smoke, include_lowering=platform == "cpu",
                  include_serve=False,   # the dedicated serve stage owns it
                  include_comm=False,    # ...and the comm stage likewise
                  include_llm=False)     # ...and the llm stage
    out["gflops"] = 0.0   # not a throughput stage; keep the stage shape
    return out


def bench_comm_stage() -> dict:
    """The comm data-path stage (microbench.bench_comm): AM roundtrip
    latency, coalesced activation throughput, GET GB/s per tier and
    payload size, the pickled-framing baseline + speedup ratio, and
    overlap efficiency during a saturating fragmented GET.  Pure
    CPU+sockets — rides the always-first CPU-safe group with the
    overhead stage, so the comm perf axis has numbers even when no
    accelerator is reachable (ISSUE 4)."""
    import os

    from microbench import bench_comm
    out = bench_comm(smoke=os.environ.get("BENCH_SMOKE") == "1")
    out["gflops"] = 0.0   # not a compute stage; keep the stage shape
    return out


def bench_comm_ranks_stage() -> dict:
    """The collective-tree rank sweep (ISSUE 14): one staged broadcast
    + one tree reduction per rank count, across real subprocess ranks
    (``run_multiproc``).  Emits the worst-rank broadcast/reduce latency
    and the ROOT's egress bytes — the number the tree exists to bound:
    ~⌈log₂ n⌉ payload transfers instead of n-1.  Each completed rank
    count flushes through ``_note_partial`` so a deadline death keeps
    the finished points.

    Each point also carries the static-vs-dynamic agreement cross-check
    (ISSUE 20): ``analysis/commcheck.predict_collective_traffic`` derives
    the expected cross-rank payload bytes per edge class WITHOUT running
    anything, and ``comm_agree_{n}r_err`` is the relative disagreement
    against the measured ``peer_stats`` wire ledger — perfdb verdicts it
    lower-is-better, so drift between the static model and the wire
    shows up in the regression sentinel."""
    import os

    from parsec_tpu.comm.multiproc import run_multiproc
    from parsec_tpu.core.params import params as _p

    smoke = os.environ.get("BENCH_SMOKE") == "1"
    sweep = [2, 4] if smoke else [2, 4, 8]
    payload = int(_p.get("comm_coll_bench_bytes"))
    out: dict = {"gflops": 0.0, "payload_bytes": payload,
                 "tree": _p.get("comm_bcast_tree")}
    for nranks in sweep:
        res = run_multiproc(
            nranks, "parsec_tpu.comm.collectives:_mp_collective_body",
            timeout=240, nb_cores=1)
        digests = {r["digest"] for r in res}
        root_tx = res[0]["peer_stats"].get("tx", {})
        egress = sum(d["bytes"] for d in root_tx.values())
        point = {
            f"bcast_{nranks}r_s": round(max(r["bcast_s"] for r in res), 4),
            f"reduce_{nranks}r_s": round(max(r["reduce_s"] for r in res),
                                         4),
            f"root_egress_{nranks}r_bytes": egress,
            f"root_egress_{nranks}r_payloads": round(
                egress / payload, 2) if payload else 0.0,
            f"bcast_{nranks}r_identical": len(digests) == 1,
        }
        try:
            # partials must never raise: the cross-check is advisory here
            # (tests/test_perf_smoke.py gates it)
            from parsec_tpu.analysis.commcheck import (
                agreement_rel_err, predict_collective_traffic)
            pred = predict_collective_traffic(nranks)
            observed = sum(
                d["bytes"]
                for r in res
                for d in r["peer_stats"].get("tx", {}).values())
            point[f"comm_pred_{nranks}r_bytes"] = pred["total_bytes"]
            point[f"comm_agree_{nranks}r_err"] = round(
                agreement_rel_err(pred["total_bytes"], observed), 4)
            _note_partial(phase="measure", ranks_done=nranks,
                          **{f"pred_{nranks}r_{ec}": b for ec, b
                             in sorted(pred["edge_bytes"].items())})
        except Exception:
            pass
        out.update(point)
        _note_partial(phase="measure", ranks_done=nranks, **point)
    return out


def bench_serve_stage() -> dict:
    """The serving-path stage: sustained concurrent submissions/s and
    p50/p99 ticket latency through a hot RuntimeServer (microbench.py's
    serve entry — pure scheduler path, no accelerator), plus the warm-vs-
    cold lowering-cache split across repeat-class *lowered* submissions —
    the number that justifies keeping the runtime resident (PR 2's warm
    compile only pays when the process outlives one DAG).  The lowered
    half touches jax, so like the overhead stage it only runs when the
    platform is explicitly CPU."""
    import os

    from microbench import bench_serve
    smoke = os.environ.get("BENCH_SMOKE") == "1"
    out = bench_serve(nsub=16 if smoke else 64, depth=4 if smoke else 8)
    platform = (os.environ.get("BENCH_PLATFORM")
                or os.environ.get("JAX_PLATFORMS") or "")
    if platform == "cpu":
        import numpy as np

        from parsec_tpu.data_dist.matrix import TiledMatrix
        from parsec_tpu.models.tiled_gemm import tiled_gemm_ptg
        from parsec_tpu.ptg.lowering import lowering_cache
        from parsec_tpu.serve import RuntimeServer

        n, nb = (64, 32) if smoke else (128, 32)

        def gemm_pool():
            rng = np.random.default_rng(11)
            a = rng.standard_normal((n, n)).astype(np.float32)
            A = TiledMatrix.from_dense("A", a.copy(), nb, nb)
            B = TiledMatrix.from_dense("B", a.copy(), nb, nb)
            C = TiledMatrix.from_dense("C", np.zeros((n, n), np.float32),
                                       nb, nb)
            return tiled_gemm_ptg(A, B, C)

        with RuntimeServer(nb_cores=1) as server:
            h0, m0 = lowering_cache.hits, lowering_cache.misses
            t0 = time.perf_counter()
            server.submit_lowered(gemm_pool()).result(timeout=120)
            cold = time.perf_counter() - t0
            t0 = time.perf_counter()
            server.submit_lowered(gemm_pool()).result(timeout=120)
            warm = time.perf_counter() - t0
            out["serve_lowered_cold_s"] = round(cold, 4)
            out["serve_lowered_warm_s"] = round(warm, 4)
            out["serve_lowered_cache_hits"] = lowering_cache.hits - h0
            out["serve_lowered_cache_misses"] = lowering_cache.misses - m0
    out["gflops"] = 0.0   # not a throughput stage; keep the stage shape
    return out


def bench_llm_stage() -> dict:
    """The LLM inference-serving stage (microbench.bench_llm): tokens/s
    and per-token p50/p99 of the continuous batcher over paged-KV decode
    superpools on a hot RuntimeServer, swept over concurrent streams AND
    over llm_steps_per_pool (the ISSUE-9 amortization axis, with
    serve_submits_per_token making the k-steps -> 1/k-submits claim
    directly visible).  Every swept point pre-flights through
    _note_partial, so a mid-sweep deadline keeps the completed points
    (the BENCH_r04/r05 lesson).  Pure scheduler+serve path on CPU:
    rides the CPU-safe group, so the axis has numbers whatever the
    accelerator weather."""
    import os

    from microbench import bench_llm, bench_llm_prefix, bench_llm_tier
    smoke = os.environ.get("BENCH_SMOKE") == "1"
    out = bench_llm(smoke=smoke, note=_note_partial)
    # the serving-memory axes (ISSUE 11), each flushing per point so a
    # deadline death keeps whatever swept: the shared-prefix-fraction
    # sweep (TTFT p50/p99 + prefill_skipped_frac per point, headline
    # llm_prefix_ttft_speedup vs trie-off) and the HBM-squeeze tier run
    # (tokens/s ratio with the device budget below the working set)
    try:
        out.update(bench_llm_prefix(smoke=smoke, note=_note_partial))
    except Exception as e:            # noqa: BLE001 — evidence over abort
        out["llm_prefix_error"] = f"{type(e).__name__}: {e}"
    try:
        out.update(bench_llm_tier(smoke=smoke, note=_note_partial))
    except Exception as e:            # noqa: BLE001 — evidence over abort
        out["llm_tier_error"] = f"{type(e).__name__}: {e}"
    out["gflops"] = 0.0   # not a compute stage; keep the stage shape
    return out


def bench_dispatch_us(ntasks: int = 2000) -> float:
    """Per-task dispatch latency on the EP DAG (the reference's
    tests/runtime/scheduling/ep.jdf shape): enqueue-to-drain wall time over
    the task count.  Exercises the enqueue-time DAG compilation
    (runtime/dagrun.py) and the native select→release executor — the
    rebuild's answer to scheduling.c:562-575's C hot loop.  Pools the
    compiler refuses take the dynamic Python scheduler instead.  ONE
    measurement implementation process-wide: this delegates to
    microbench.py, so the dedicated stage and the overhead stage can never
    drift into incomparable readings."""
    from microbench import _drain_ep_us
    us, _engaged = _drain_ep_us(ntasks, reps=5, compiled=True)
    return us


_abandoned: list = []    # stages whose worker thread outlived its timeout


def _runtime_report() -> dict:
    """The flight-recorder self-measurement embedded in EVERY stage
    result — degraded ones included, so even an accelerator outage ships
    per-stage runtime evidence (the round-5 lesson: a zero with no
    self-report is indistinguishable from a framework bug).  Must never
    raise: a broken report is itself reported."""
    try:
        from parsec_tpu.prof import runtime_report
        return runtime_report()
    except Exception as e:                     # noqa: BLE001 — evidence
        return {"unavailable": f"{type(e).__name__}: {e}"}


def _staged(name, fn, *a, timeout=120.0, retries=1, **kw):
    """Run one bench stage in a worker thread with a HARD join timeout.

    Two failure modes this guards (VERDICT r4 item 1 — round 4 shipped NO
    numbers because neither was handled):
    - a device call raises (a failed compile, a transfer reset): catch,
      retry, then degrade to an error record;
    - a device call HANGS (a blocked device read never returns): a ``join``
      timeout abandons the stage thread (daemon) and moves on, so one
      stuck ``ctx.wait`` can never eat the rest of the run.  The
      reference's harnesses embody the same rule — they always print
      (``tests/dsl/dtd/dtd_test_simple_gemm.c:649-667``).

    ``timeout`` bounds the stage as a whole — retries share it, so a
    primary stage with retries can never exceed its allotment and push
    the whole run past the driver's patience.  An abandoned thread may
    still be driving the shared device when later stages run; that taint
    is recorded in ``_abandoned`` and surfaced per result (a wrong-but-
    flagged number is reportable; a wrong-and-silent one is not)."""
    import sys
    import threading
    t_stage = time.perf_counter()
    # the degraded-stage taint convention: snapshot the abandoned list
    # BEFORE this stage can add itself, so no degrade path ever lists the
    # stage as its own taint (ADVICE round 5: the budget path diverged)
    prior = list(_abandoned)
    for attempt in range(retries + 1):
        _stage_partials.pop(name, None)   # fresh flush per attempt
        box = {}

        def work():
            try:
                box["out"] = fn(*a, **kw)
            except BaseException as e:        # noqa: BLE001 — degrade, report
                box["err"] = e

        left = timeout - (time.perf_counter() - t_stage)
        if attempt and left <= 1.0:
            print(f"[bench] {name}: stage budget {timeout:.0f}s exhausted "
                  f"after {attempt} attempt(s)", file=sys.stderr, flush=True)
            return {"gflops": 0.0,
                    "error": f"stage budget {timeout:.0f}s exhausted "
                             f"after {attempt} attempt(s)",
                    "runtime_report": _runtime_report(),
                    **({"tainted_by": prior} if prior else {})}
        th = threading.Thread(target=work, daemon=True, name=f"bench-{name}")
        t0 = time.perf_counter()
        th.start()
        th.join(left)
        wall = time.perf_counter() - t0
        if th.is_alive():
            # a stage dying on its deadline mid-XLA-compile is the
            # BENCH_r04/r05 failure shape (rc 124): record it as a typed
            # compile_timeout WITH the partial metrics the stage flushed
            # (_note_partial) instead of losing the stage entirely
            part = dict(_stage_partials.get(name, {}))
            status = "compile_timeout" if part.get("phase") == "compile" \
                else "timeout"
            print(f"[bench] {name}: {status.upper()} after {wall:.1f}s — "
                  f"stage thread abandoned", file=sys.stderr, flush=True)
            _abandoned.append(name)
            return {"gflops": 0.0, "status": status,
                    "error": f"stage timeout after {timeout:.0f}s",
                    **({"partial": part} if part else {}),
                    "runtime_report": _runtime_report(),
                    **({"tainted_by": prior} if prior else {})}
        if "err" in box:
            e = box["err"]
            print(f"[bench] {name}: attempt {attempt + 1} failed "
                  f"({type(e).__name__}: {e})", file=sys.stderr, flush=True)
            if attempt >= retries:
                part = dict(_stage_partials.get(name, {}))
                return {"gflops": 0.0, "error": f"{type(e).__name__}: {e}",
                        **({"partial": part} if part else {}),
                        "runtime_report": _runtime_report()}
            continue
        print(f"[bench] {name}: {wall:.1f}s", file=sys.stderr, flush=True)
        out = box["out"]
        if isinstance(out, dict):
            out.setdefault("runtime_report", _runtime_report())
            if _abandoned:
                # a zombie stage may still be dispatching on the shared
                # device: this stage's counters/deltas are suspect
                out["tainted_by"] = list(_abandoned)
        return out


def main() -> None:
    """Stage order and reporting are built so that a number ALWAYS lands,
    whatever the accelerator weather or the driver's patience:

    - dispatch + the headline GEMM run FIRST (round 4 ordered the headline
      dead last for HBM hygiene and the driver's kill erased the round's
      entire perf story — evidence beats hygiene);
    - after EVERY stage the full cumulative result JSON is re-printed to
      stdout (and mirrored to BENCH_partial.json), so a kill at any moment
      leaves the latest complete line in the tail for the driver to parse;
    - every stage runs under a hard thread-join timeout, and secondaries
      are skipped once the global deadline (BENCH_DEADLINE_S, default 420s
      — below the driver's observed ~600s patience) is near."""
    import os
    import sys
    # BENCH_PLATFORM names the JAX platform for this run; a process that
    # already imported jax (the harness tests) gets the live config too
    if os.environ.get("BENCH_PLATFORM"):
        os.environ["JAX_PLATFORMS"] = os.environ["BENCH_PLATFORM"]
        import jax
        jax.config.update("jax_platforms", os.environ["BENCH_PLATFORM"])
    # observability defaults for the whole run (read when the prof params
    # register, i.e. on the first parsec_tpu import inside a stage): keep
    # the metrics snapshotter sampling so every stage's runtime_report
    # carries a series, and stall dumps land next to the BENCH artifacts
    os.environ.setdefault("PARSEC_MCA_prof_snapshot_interval", "0.25")
    smoke = os.environ.get("BENCH_SMOKE") == "1"
    if smoke:
        # exercise the dynamic device path on the host CPU device too —
        # otherwise the smoke run skips every dynamic stage
        os.environ.setdefault("PARSEC_MCA_device_tpu_allow_cpu", "1")
    n = int(os.environ.get("BENCH_N", "512" if smoke else "16384"))
    deadline = float(os.environ.get("BENCH_DEADLINE_S",
                                    "120" if smoke else "420"))
    t_start = time.perf_counter()
    res: dict = {}

    def _dispatch_us():
        """The dispatch series value: the dedicated stage's reading, else
        the overhead micro stage's, else absent (never a sentinel)."""
        v = res.get("dispatch_us")
        if isinstance(v, (int, float)) and v >= 0:
            return v
        ov = res.get("overhead", {})
        w = ov.get("dispatch_us") if isinstance(ov, dict) else None
        return w if isinstance(w, (int, float)) and w >= 0 else None

    def emit():
        gemm = res.get("gemm") or {}
        peak = gemm.get("peak_gflops") or 1.0
        target = 0.70 * peak
        dyn = res.get("dynamic_gemm", {})
        degraded = {nm: d.get("error") or d.get("skipped")
                    for nm, d in res.items()
                    if isinstance(d, dict) and (d.get("error")
                                                or d.get("skipped"))}
        # the per-stage runtime self-reports (flight-recorder counters,
        # per-worker last activity): EVERY stage ships one, degraded
        # stages included — an accelerator outage still reads as runtime
        # evidence, not silence
        reports = {nm: d["runtime_report"] for nm, d in res.items()
                   if isinstance(d, dict) and "runtime_report" in d}
        line = json.dumps({
            "metric": "ptg_tiled_gemm_gflops_per_chip",
            "value": round(gemm.get("gflops", 0.0), 1),
            "unit": "GFLOPS",
            "vs_baseline": round(gemm.get("gflops", 0.0) / target, 4),
            "extra": {
                "pct_peak": round(gemm.get("pct_peak", 0.0), 2),
                "device_kind": gemm.get("device_kind", "pending"),
                "n": gemm.get("n", n),
                "nb": gemm.get("nb", 0),
                "gemm_seconds": round(gemm.get("seconds", 0.0), 4),
                "gemm_compile_s": gemm.get("compile_s", 0.0),
                "lowering": gemm.get("lowering",
                                     gemm.get("error", "pending")),
                # raw-compiler cross-check: bare jnp.dot, same config;
                # framework/raw ~ 1.0 = the taskpool lowering costs nothing
                "raw_dot_gflops": round(
                    res.get("raw_dot", {}).get("gflops", 0.0), 1),
                # a MISSING dispatch measurement is omitted (formerly a
                # -1.0 sentinel that poisoned trend averages over
                # BENCH_r*.json); the overhead micro stage's reading
                # backstops a skipped/failed dispatch stage
                **({"task_dispatch_us": _dispatch_us()}
                   if _dispatch_us() is not None else {}),
                "overhead": {k: v for k, v in
                             res.get("overhead", {}).items()
                             if k not in ("runtime_report", "gflops")},
                # the comm wire-path stage: AM roundtrips, GET GB/s per
                # tier/size, pickle-baseline speedup, overlap (ISSUE 4)
                "comm": {k: v for k, v in
                         res.get("comm", {}).items()
                         if k not in ("runtime_report", "gflops")},
                # the collective-tree rank sweep: bcast/reduce latency +
                # measured root egress per rank count (ISSUE 14)
                "comm_ranks": {k: v for k, v in
                               res.get("comm_ranks", {}).items()
                               if k not in ("runtime_report", "gflops")},
                # the serving stage: submissions/s, ticket latency, and
                # the warm-vs-cold lowered split (ISSUE 3)
                "serve": {k: v for k, v in
                          res.get("serve", {}).items()
                          if k not in ("runtime_report", "gflops")},
                # the LLM serving stage: tokens/s + per-token p50/p99
                # with concurrent streams as the sweep axis (ISSUE 6)
                "llm": {k: v for k, v in
                        res.get("llm", {}).items()
                        if k not in ("runtime_report", "gflops")},
                "dynamic_gemm_gflops": round(dyn.get("gflops", 0.0), 1),
                "dynamic_gemm_batched": dyn.get("batched_dispatches", 0),
                "dynamic_gemm_breakdown": dyn.get("breakdown", {}),
                "dtd_gemm_tpu_gflops": round(
                    res.get("dtd_gemm", {}).get("gflops", 0.0), 1),
                "dynamic_cholesky_gflops": round(
                    res.get("dynamic_cholesky", {}).get("gflops", 0.0), 1),
                # the closed-loop autotuner stage (ISSUE 18): seeded-bad
                # knobs recovered by tune.search, winner -> tunedb.jsonl
                "tune_speedup": round(
                    res.get("tuned_cholesky", {}).get("tune_speedup",
                                                      0.0), 3),
                "tuned_cholesky": {k: v for k, v in
                                   res.get("tuned_cholesky", {}).items()
                                   if k not in ("runtime_report",
                                                "gflops")},
                # n=8192 is the round-3-comparable config (VERDICT r4 weak
                # #8: keep configs frozen; new sizes are NEW keys)
                "lowered_cholesky_gflops": round(
                    res.get("lowered_cholesky", {}).get("gflops", 0.0), 1),
                "lowered_cholesky_n": res.get("lowered_cholesky",
                                              {}).get("n", 0),
                "lowered_cholesky_compile_s": res.get(
                    "lowered_cholesky", {}).get("compile_s", 0.0),
                "lowered_cholesky_16k_gflops": round(
                    res.get("lowered_cholesky_16k", {}).get("gflops",
                                                            0.0), 1),
                # the megakernel-region stage (ISSUE 8): same DAG, one
                # program per verified region, budgeted staged compile
                "region_cholesky_gflops": round(
                    res.get("region_cholesky", {}).get("gflops", 0.0), 1),
                "region_cholesky_regions": res.get(
                    "region_cholesky", {}).get("regions", 0),
                "region_cholesky_eager": res.get(
                    "region_cholesky", {}).get("regions_eager", 0),
                "region_cholesky_compile_s": res.get(
                    "region_cholesky", {}).get("compile_s", 0.0),
                "lowered_lu_gflops": round(
                    res.get("lowered_lu", {}).get("gflops", 0.0), 1),
                "lowered_lu_compile_s": res.get("lowered_lu",
                                                {}).get("compile_s", 0.0),
                "stencil_gflops": round(
                    res.get("stencil", {}).get("gflops", 0.0), 2),
                "lowered_stencil_gflops": round(
                    res.get("lowered_stencil", {}).get("gflops", 0.0), 1),
                "lowered_stencil_compile_s": res.get(
                    "lowered_stencil", {}).get("compile_s", 0.0),
                "elapsed_s": round(time.perf_counter() - t_start, 1),
                # the regression sentinel's verdicts (prof/perfdb.py):
                # always present so the driver can key on it — empty
                # list = no EWMA-flagged regressions this run
                "perfdb_regressions": list(_perfdb_state["regressions"]),
                "runtime_reports": reports,
                **({"degraded_stages": degraded} if degraded else {}),
                **({"abandoned_stages": list(_abandoned)}
                   if _abandoned else {}),
            },
        })
        print(line, flush=True)
        try:
            with open("BENCH_partial.json", "w") as f:
                f.write(line + "\n")
        except OSError:
            pass

    budgets = _stage_budgets()

    def stage(name, fn, *a, timeout=120.0, retries=0, primary=False, **kw):
        # per-stage MCA/env budget override (bench_stage_budget_s):
        # named entry wins, then the '*' default, then the harness value
        timeout = budgets.get(name, budgets.get("*", timeout))
        left = deadline - (time.perf_counter() - t_start)
        if not primary and left < 15.0:
            print(f"[bench] {name}: SKIPPED ({deadline:.0f}s deadline)",
                  file=sys.stderr, flush=True)
            res[name] = {"gflops": 0.0, "skipped": "deadline exhausted",
                         "runtime_report": _runtime_report()}
        else:
            # a primary stage may overshoot the deadline (the headline
            # matters more than the tail) but never unboundedly — its
            # retries share one stage budget, clamped so the driver's
            # ~600s patience is never at risk
            timeout = (min(timeout, max(left, 60.0)) if primary
                       else min(timeout, max(left, 15.0)))
            res[name] = _staged(name, fn, *a, timeout=timeout,
                                retries=retries, **kw)
        _perfdb_note(name, res[name])
        emit()
        return res[name]

    # smoke configs keep every stage under a few seconds on CPU so the
    # whole harness (ordering, emit, degrade paths) is CI-testable —
    # round 4's lesson: an untested bench harness ships nothing
    cfg = {
        "gemm": dict(n=n, nb=128 if smoke else 512,
                     reps=4 if smoke else 48),
        "raw": dict(n=n, reps=4 if smoke else 48),
        "stencil": dict(n=1 << 16, mb=1 << 12, iterations=4)
        if smoke else {},
        "lchol": dict(n=1024, nb=256) if smoke else dict(n=8192, nb=512),
        "rchol": dict(n=1024, nb=256) if smoke else dict(n=8192, nb=512),
        "lsten": dict(n=1 << 16, mb=1 << 12, iterations=8)
        if smoke else {},
        "llu": dict(n=1024, nb=256) if smoke else {},
        "dyn": dict(n=512, nb=128) if smoke else {},
        "dtd": dict(n=512, nb=128) if smoke else {},
        "lchol16": dict(n=2048, nb=256) if smoke else dict(n=16384,
                                                           nb=512),
        "dchol": dict(n=512, nb=128) if smoke else {},
        "tchol": dict(n=512, nb_bad=32, budget=6)
        if smoke else dict(n=1024, nb_bad=64, budget=8),
    }

    # --- the overhead micro stage runs FIRST, before anything that can
    # touch the accelerator: dispatch/release/steal numbers land even
    # when every accelerator stage is dark (ISSUE 2 satellite) ---
    stage("overhead", bench_overhead, timeout=120.0, primary=True)
    # --- the comm wire-path stage rides the same CPU-safe always-first
    # group: AM latency, GET GB/s vs the pickle baseline, and overlap
    # efficiency need only sockets (ISSUE 4) ---
    stage("comm", bench_comm_stage, timeout=90.0, primary=True)

    # --- primary metrics next: a headline must land within minutes ---
    d = _staged("dispatch", bench_dispatch_us, timeout=90.0)
    res["dispatch_us"] = round(d, 2) if isinstance(d, float) else None
    # the dispatch stage's self-report rides like every other stage's
    # (its headline value stays the flat task_dispatch_us key)
    res["dispatch"] = d if isinstance(d, dict) else \
        {"dispatch_us": res["dispatch_us"]}
    res["dispatch"].setdefault("runtime_report", _runtime_report())
    _perfdb_note("dispatch", res["dispatch"])
    emit()
    stage("gemm", bench_gemm_gflops, timeout=300.0, retries=2,
          primary=True, **cfg["gemm"])
    stage("raw_dot", bench_raw_dot_gflops, timeout=120.0, **cfg["raw"])

    # --- secondaries, most valuable first, each deadline-bounded.  The
    # serving stage leads them: submissions/s and ticket latency need no
    # accelerator (the lowered warm/cold split self-gates on an
    # explicit-CPU platform), so it lands even with no accelerator —
    # but never ahead of the headline (the round-4 ordering lesson) ---
    stage("serve", bench_serve_stage, timeout=150.0)
    stage("llm", bench_llm_stage, timeout=150.0)
    # the collective-tree rank sweep spawns subprocess ranks — CPU-safe
    # but slow, so it rides the secondary group, never ahead of the
    # headline
    stage("comm_ranks", bench_comm_ranks_stage, timeout=600.0)
    from parsec_tpu.models.stencil import run_stencil_bench
    stage("stencil", run_stencil_bench, timeout=60.0, **cfg["stencil"])
    stage("lowered_cholesky", bench_lowered_cholesky_gflops,
          timeout=150.0, **cfg["lchol"])
    stage("region_cholesky", bench_region_cholesky_gflops, timeout=150.0,
          **cfg["rchol"])
    stage("lowered_stencil", bench_lowered_stencil_gflops, timeout=150.0,
          **cfg["lsten"])
    stage("lowered_lu", bench_lowered_lu_gflops, timeout=150.0,
          **cfg["llu"])
    stage("dynamic_gemm", bench_dynamic_gemm_gflops, timeout=150.0,
          **cfg["dyn"])
    stage("dtd_gemm", bench_dtd_gemm_tpu, timeout=150.0, **cfg["dtd"])
    stage("lowered_cholesky_16k", bench_lowered_cholesky_gflops,
          timeout=180.0, **cfg["lchol16"])
    stage("dynamic_cholesky", bench_dynamic_cholesky_gflops,
          timeout=150.0, **cfg["dchol"])
    stage("tuned_cholesky", bench_tuned_cholesky, timeout=150.0,
          **cfg["tchol"])


if __name__ == "__main__":
    main()
