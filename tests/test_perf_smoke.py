"""perf_smoke: critical-path regression guards over microbench.py.

Every threshold carries ~10x headroom over the numbers measured at ISSUE-2
time (docs/PERF.md records those), so a pass is timing-flake-safe in CI
while a genuine dispatch-path regression — an accidental allocation in a
PINS site, a lock on the lfq common path, a lost compile-cache hit — still
fails loudly.  The whole module runs in a few seconds on CPU and is part
of tier-1 (it is deliberately NOT marked slow)."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import microbench  # noqa: E402

pytestmark = pytest.mark.perf_smoke

# measured on the ISSUE-2 CPU baseline (docs/PERF.md):  dispatch 1.3-1.6us,
# dynamic 40-50us, steal 0.8us, local pop 0.3us, pins disabled ~30ns
DISPATCH_US_MAX = 16.0
DYNAMIC_DISPATCH_US_MAX = 500.0
RELEASE_TASKS_PER_S_MIN = 2000.0
LOCAL_POP_US_MAX = 4.0
STEAL_US_MAX = 10.0
PINS_DISABLED_NS_MAX = 500.0
# ISSUE-3 serving baseline: ~300-400 submissions/s, p50 ~4-6ms, p99 ~13ms
# for 4 clients x tiny CTL pools on 2 workers (docs/SERVING.md) — same
# ~10x headroom discipline
SERVE_SUBMITS_PER_S_MIN = 25.0
SERVE_P99_MS_MAX = 250.0
# ISSUE-4 comm wire baseline (docs/COMM.md): AM roundtrip ~7µs inproc /
# ~200-500µs localhost socket, coalesced activations ~15-25k/s, 4MiB
# socket GET ~1.3-2 GB/s binary vs ~0.3-0.5 GB/s pickled (3-4.5x),
# overlap efficiency 0.2-0.5 — thresholds keep the same ~10x headroom so
# only a gross wire-path regression (a reintroduced copy, a dead window,
# a lost speedup) fails
COMM_AM_ROUNDTRIP_US_INPROC_MAX = 100.0
COMM_AM_ROUNDTRIP_US_SOCKET_MAX = 5000.0
COMM_ACTIVATIONS_PER_S_MIN = 1500.0
COMM_GET_SOCKET_4MIB_GBPS_MIN = 0.1
COMM_GET_SPEEDUP_VS_PICKLE_MIN = 1.5
# measured 0.2-0.5 on the ISSUE-4 CPU baseline: the dedicated T3
# overlap gate below holds the 10x-headroom line (ROADMAP T3 item);
# a dead fragment-progress path reads ~0 and fails it
COMM_OVERLAP_EFFICIENCY_MIN = 0.02
# ISSUE-6 LLM serving baseline: ~450 tokens/s at 1 stream, ~1300 at 4
# (continuous batching over paged-KV decode pools, 2 CPU workers),
# per-token p50 ~1-2.5ms / p99 ~4ms.  ISSUE 9 (k-step decode superpools,
# in-graph SAMPLE) multiplied the 4-stream smoke point several-fold, so
# the gate is raised to lock in AT LEAST 2x the PR-6 line (its old gate
# was 100 with ~10x headroom): a regression that quietly re-enters the
# host loop per token fails here by name
LLM_TOKENS_PER_S_MIN = 250.0
LLM_P99_MS_MAX = 250.0
# the amortization itself is gated too: k=8 superpools vs k=1 in the
# SAME run must keep a real multiple (measured ~3-6x on 4 streams; the
# ISSUE-9 acceptance line is >= 3x at 8 streams in the full bench)
LLM_SUPERPOOL_SPEEDUP_MIN = 1.8
# ISSUE-11 prefix cache: at 0.9 shared-prefix overlap the trie must
# skip >= 80% of prefill tokens and shared-prompt TTFT p50 must beat
# the trie-off run of the SAME traffic >= 2x (measured ~2.4x on the
# 64-page smoke shape; the ratio is work-structural — both runs share
# one process back to back — so it carries less timing noise than an
# absolute threshold would)
LLM_PREFIX_TTFT_SPEEDUP_MIN = 2.0
LLM_PREFIX_SKIPPED_FRAC_MIN = 0.8
# ISSUE-12 speculative decode: the adaptive drafter on the draftable
# (repetitive) 8-stream workload must beat the PR-9 k=8 path of the
# SAME workload >= 1.5x (measured ~1.6-1.9x on the smoke shape: the
# batched spec superpool collapses ~k*NP+2k tasks per pool to NP+1 and
# emits up to spec_k+1 tokens per submit), and acceptance-rate-0
# traffic (garbage drafts) must converge spec_k to ~0 and stay within
# 10% of the non-speculative path — the second gate lives in
# tests/test_llm_spec.py where the drafter can be forced adversarial
LLM_SPEC_SPEEDUP_MIN = 1.5
# ISSUE-20 commcheck: the static byte prediction for the collective
# rank sweep must agree with the measured peer_stats wire ledger within
# 15% rel (deterministic workload: (n-1) payload transfers + small
# reduction partials; framing and activation frames are the only slack)
COMMCHECK_AGREE_RELERR_MAX = 0.15


def test_compiled_dispatch_latency():
    r = microbench.bench_dispatch_us(ntasks=2000, reps=3)
    assert r["dispatch_us"] <= DISPATCH_US_MAX, r


def test_dynamic_release_throughput():
    r = microbench.bench_release_throughput(ntasks=2000, reps=1)
    assert r["dynamic_dispatch_us"] <= DYNAMIC_DISPATCH_US_MAX, r
    assert r["release_tasks_per_s"] >= RELEASE_TASKS_PER_S_MIN, r


def test_lfq_pop_and_steal_latency():
    r = microbench.bench_steal_us(n=200, reps=20)
    assert r["local_pop_us"] <= LOCAL_POP_US_MAX, r
    assert r["steal_us"] <= STEAL_US_MAX, r


def test_pins_disabled_site_cost():
    r = microbench.bench_pins_disabled_ns(iters=50000)
    # None = a PINS chain was registered by a concurrently-running module;
    # the dedicated allocation test (test_flight_recorder) still guards it
    if r["pins_disabled_ns"] is None:
        pytest.skip("PINS chains registered; disabled site unmeasurable")
    assert r["pins_disabled_ns"] <= PINS_DISABLED_NS_MAX, r


def test_serve_sustained_submission_throughput():
    """The serving path (admission + fair queue + live enqueue + ticket)
    must sustain concurrent submissions without a gross regression —
    tier-1's guard on the RuntimeServer critical path."""
    r = microbench.bench_serve(nsub=16, nthreads=4, depth=4)
    assert r["serve_nsub"] == 16, r
    assert r["serve_submits_per_s"] >= SERVE_SUBMITS_PER_S_MIN, r
    assert r["serve_p99_ms"] <= SERVE_P99_MS_MAX, r


@pytest.fixture(scope="module")
def comm_numbers():
    """One bench_comm run shared by the wire-path and overlap gates —
    the overlap threshold is its own test (a failure must NAME the T3
    regression), but the measurement need not run twice."""
    return microbench.bench_comm(smoke=True)


def test_comm_wire_path_throughput(comm_numbers):
    """The zero-copy wire data path (ISSUE 4): binary framing + windowed
    fragmented GETs must beat the pickled baseline — tier-1's guard on
    the comm critical path."""
    r = comm_numbers
    assert r["comm_am_roundtrip_us_inproc"] <= \
        COMM_AM_ROUNDTRIP_US_INPROC_MAX, r
    assert r["comm_am_roundtrip_us_socket"] <= \
        COMM_AM_ROUNDTRIP_US_SOCKET_MAX, r
    assert r["comm_activations_per_s"] >= COMM_ACTIVATIONS_PER_S_MIN, r
    assert r["comm_get_socket_4mib_gbps"] >= \
        COMM_GET_SOCKET_4MIB_GBPS_MIN, r
    assert r["comm_get_speedup_vs_pickle"] >= \
        COMM_GET_SPEEDUP_VS_PICKLE_MIN, r


def test_comm_overlap_efficiency_threshold(comm_numbers):
    """The T3 overlap gate (ROADMAP): compute retired during a
    saturating fragmented GET must stay above the 10x-headroom line —
    a regression in busy-worker fragment progress (a blocking recv, a
    lost progress interleave) drives the efficiency toward 0 and fails
    HERE, by name, not inside a grab-bag wire assertion."""
    assert comm_numbers["comm_overlap_efficiency"] >= \
        COMM_OVERLAP_EFFICIENCY_MIN, comm_numbers


def test_critpath_agrees_with_measured_overlap(comm_numbers):
    """ISSUE-16 acceptance: the span-plane replay must reconstruct the
    comm stage's overlap efficiency to within 15% relative of the
    inline-measured number — two independent computations of the same
    wall quantity (span interval algebra vs accumulated unit timers) —
    and the report must name the top-3 overlap_lost edge classes with
    nonzero values (the T3 target list)."""
    r = comm_numbers
    assert "comm_critpath_error" not in r, r.get("comm_critpath_error")
    m = r["comm_overlap_efficiency"]
    c = r["comm_critpath_overlap_efficiency"]
    assert abs(c - m) / max(m, 1e-9) < 0.15, (m, c)
    top = r["comm_critpath_top_lost"]
    assert len(top) == 3 and all(ms > 0 for _cls, ms in top), top
    assert r["comm_critpath_overlap_lost_ms"] > 0, r


def test_critpath_replay_fast_and_disabled_path_free(comm_numbers):
    """ISSUE-16 gates: replaying the whole comm stage's spans stays
    under 1s (analysis-time cost only), and the disabled path is free —
    critpath consumes EXISTING spans, so with no recorder installed
    there is nothing to pay and nothing to summarize."""
    assert comm_numbers["comm_critpath_replay_s"] < 1.0, comm_numbers
    from parsec_tpu.prof import spans
    from parsec_tpu.prof.critpath import summarize_recorder
    prev = spans.recorder
    if prev is not None:
        spans.uninstall()
    try:
        assert spans.recorder is None
        assert summarize_recorder() is None
    finally:
        if prev is not None:
            spans.install(recorder_obj=prev)


def test_perfdb_sentinel_roundtrips_synthetic_regression(tmp_path):
    """ISSUE-16 gate: the EWMA drift detector flags a 10x cliff (both
    metric directions) and stays quiet on 5% noise."""
    from parsec_tpu.prof.perfdb import PerfDB, make_key
    db = PerfDB(path=str(tmp_path / "perfdb.jsonl"))
    kd = make_key("smoke", "dispatch_us", backend=["cpu"])
    kt = make_key("smoke", "tokens_per_s", backend=["cpu"])
    for i in range(16):
        db.append(kd, 100.0 + (i % 2))      # latency-like: lower better
        db.append(kt, 1000.0 - (i % 3))     # throughput: higher better
    assert db.check(kd, 105.0)["verdict"] == "ok"       # 5% noise: quiet
    hi = db.check(kd, 1000.0)                           # 10x slowdown
    assert hi["verdict"] == "regressed" and hi["z"] > 0, hi
    assert db.check(kt, 100.0)["verdict"] == "regressed"   # 10x drop
    assert db.check(kt, 10000.0)["verdict"] == "improved"


@pytest.fixture(scope="module")
def llm_numbers():
    """One bench_llm run shared by the decode-throughput and
    speculative-decode gates (the spec axis rides the same bench)."""
    return microbench.bench_llm(smoke=True)


def test_llm_decode_throughput_and_latency(llm_numbers):
    """The LLM serving path (ISSUE 6 + 9): k-step decode superpools over
    the paged KV cache on a hot RuntimeServer must sustain tokens/s with
    bounded per-token p99, and the superpool amortization (one submit
    per k tokens, in-graph SAMPLE) must hold against the k=1 baseline
    measured in the same run — tier-1's guard on the decode critical
    path (admission + WFQ + live enqueue + ragged ATTN chains)."""
    r = llm_numbers
    assert r["llm_tokens_per_s"] >= LLM_TOKENS_PER_S_MIN, r
    assert r["llm_p99_ms"] <= LLM_P99_MS_MAX, r
    # the sweep axes are really swept: all points present and sane
    sweep = r["llm_streams_sweep"]
    assert set(sweep) == {"1", "4"}, r
    assert all(v["tokens_per_s"] > 0 for v in sweep.values()), r
    ksweep = r["llm_steps_sweep"]
    assert set(ksweep) == {"1", "8"}, r
    assert r["llm_superpool_speedup"] >= LLM_SUPERPOOL_SPEEDUP_MIN, r
    # the amortization claim is structural, not just a timing: k=8
    # superpools must submit at most ~1/8 pool per token (one pool can
    # carry a whole tenant batch, so strictly fewer still passes)
    assert ksweep["8"]["submits_per_token"] <= 1.0 / 8 + 1e-9, r
    assert ksweep["1"]["submits_per_token"] > ksweep["8"][
        "submits_per_token"], r


def test_llm_spec_decode_speedup(llm_numbers):
    """The ISSUE-12 speculative-decode gate: on the draftable 8-stream
    workload the adaptive drafter must beat the non-speculative PR-9
    k=8 path of the SAME workload >= 1.5x, with a real acceptance rate
    behind it (a dead drafter, a VERIFY that rejects everything, or a
    spec pool that quietly serializes again all fail here by name).
    The ratio is work-structural — both points run back to back in one
    process — so it carries less timing noise than an absolute
    threshold would."""
    r = llm_numbers
    sweep = r["llm_spec_sweep"]
    assert set(sweep) == {"off", "2", "4", "adaptive"}, r
    assert all(v["tokens_per_s"] > 0 for v in sweep.values()), r
    assert r["llm_spec_speedup"] >= LLM_SPEC_SPEEDUP_MIN, r
    # the speedup must come from accepted drafts, not a measurement
    # artifact: the adaptive point's acceptance is real and its pools
    # carry more tokens per submit than the fixed-2 point's cap allows
    assert sweep["adaptive"]["accept_rate"] >= 0.3, r
    assert sweep["adaptive"]["tokens_per_submit"] > \
        sweep["2"]["tokens_per_submit"], r
    # (zero rollbacks is legitimate here — on a fully draftable
    # workload the transition phase drafts nothing rather than drafts
    # wrong; forced-rejection rollback coverage lives in
    # tests/test_llm_spec.py where the drafter is made adversarial)


def test_llm_prefix_cache_ttft_speedup():
    """The ISSUE-11 prefix-cache gates: with 90% of traffic sharing one
    system prompt, the radix trie must convert >= 80% of prefill tokens
    into copy-on-write page forks (prefill_skipped_frac) and move the
    client-observed TTFT p50 >= 2x vs the identical traffic with the
    cache off — a dead trie (no donations, no matches, or forks that
    re-prefill anyway) fails both by name."""
    r = microbench.bench_llm_prefix(smoke=True)
    hot = r["llm_prefix_sweep"]["0.9"]
    assert hot["prefix_hits"] > 0, r
    assert r["llm_prefill_skipped_frac"] >= LLM_PREFIX_SKIPPED_FRAC_MIN, r
    assert r["llm_prefix_ttft_speedup"] >= LLM_PREFIX_TTFT_SPEEDUP_MIN, r
    # the no-sharing point keeps the cache honest: nothing to hit
    assert r["llm_prefix_sweep"]["0.0"]["prefix_hits"] == 0, r


# ISSUE-10 tracing budget (docs/OBSERVABILITY.md overhead table), held
# since ISSUE 27 by what repeats exactly: which PINS slots the span
# recorder occupies, how many spans a traced pool leaves, and that the
# phase plane builds nothing while off.  The clock readings of
# ``microbench.bench_tracing`` (dispatch us a task off and on, ns a span
# or histogram record) swing with the six parallel workers of tier-1
# (ROADMAP D10); they are printed for the log, not asserted.


def test_tracing_overhead_within_budget(monkeypatch):
    """The observability gates.  With the span recorder UNINSTALLED (the
    shipped default) its six task-span PINS slots hold no chain of its
    own: tracing added no hot-path site, only the existing PINS branch.
    INSTALLED, a traced pool of n tasks records exactly n ``exec`` and n
    ``release`` spans.  The phase plane, off, builds no object."""
    import parsec_tpu.runtime.dagrun  # noqa: F401 — runtime_dag_compile
    from collections import Counter

    from parsec_tpu.core.params import params
    from parsec_tpu.prof import pins, spans
    from parsec_tpu.prof.pins import PinsEvent
    from parsec_tpu.runtime import Context

    task_span_events = (
        PinsEvent.EXEC_BEGIN, PinsEvent.EXEC_END,
        PinsEvent.RELEASE_DEPS_BEGIN, PinsEvent.RELEASE_DEPS_END,
        PinsEvent.SCHEDULE_BEGIN, PinsEvent.SCHEDULE_END)

    def recorder_chains():
        return [cb for ev in task_span_events
                for cb in pins._chains.get(int(ev), ())
                if isinstance(getattr(cb, "__self__", None),
                              spans._TaskSpans)]

    def built(*a):
        raise AssertionError("the phase plane built a span while off")

    prev = spans.recorder
    if prev is not None:
        spans.uninstall()
    assert spans._task_spans is None and not recorder_chains()
    spans.phase_refresh()
    assert not spans.phase_on
    assert spans.phase("ctx.init") is spans.phase("ctx.fini")
    table = spans.phase_totals()
    monkeypatch.setattr(spans, "_Phase", built)
    nt, depth = 20, 25
    saved = params.get("runtime_dag_compile")
    params.set("runtime_dag_compile", False)    # the dynamic path
    rec = spans.install()
    try:
        assert len(recorder_chains()) == len(task_span_events)
        tp = microbench._ep_pool(nt, depth).build()
        tp._trace = spans.new_trace()
        ctx = Context(nb_cores=0)
        ctx.add_taskpool(tp)
        ctx.wait(timeout=600)
        ctx.fini()
        names = Counter(s[0] for s in rec.by_trace(tp._trace.trace_id))
    finally:
        params.set("runtime_dag_compile", saved)
        spans.uninstall()
        if prev is not None:
            spans.install(recorder_obj=prev)
    assert names["exec"] == names["release"] == nt * depth, names
    assert spans.phase_totals() == table
    print("bench_tracing (clock readings, not asserted):",
          microbench.bench_tracing(smoke=True))


def test_lowering_cache_warm_compile_is_near_zero():
    r = microbench.bench_lowering_cache(n=64, nb=32)
    assert r["cache_hits"] >= 1, r
    # warm "compile" is a dict lookup + cached-executable call: even with
    # 10x headroom it must land far under the cold trace+compile
    assert r["compile_warm_s"] <= max(0.1 * r["compile_cold_s"], 0.05), r


# ISSUE-8 region-lowering baseline (docs/PERF.md "Region lowering &
# compile budgets"): on the smoke cholesky DAG (nt=4, 20 tasks across 4
# classes) the measured drop is 20x task-per-dispatch -> region, and the
# warm region compile is ~0.000s — the >=5x gate is the ISSUE-8
# acceptance line, held with the usual headroom discipline (a lost
# grouping or a dead region cache would crater it)
REGION_XLA_CALL_DROP_MIN = 5.0
REGION_COMPILE_WARM_S_MAX = 0.5


def test_region_lowering_xla_call_drop_and_warm_compile():
    """The MPK axis: region-lowered cholesky must issue >= 5x fewer XLA
    dispatches than the task-per-dispatch dynamic path, and a second
    structurally identical plan must compile for ~free through the
    process lowering cache."""
    r = microbench.bench_lowering(smoke=True)
    # the baseline really is task-per-dispatch: one call per task
    assert r["lowering_dispatch_xla_calls"] == r["lowering_tasks_per_dag"], r
    assert r["lowering_region_xla_call_drop"] >= REGION_XLA_CALL_DROP_MIN, r
    assert r["lowering_region_compile_warm_s"] <= \
        REGION_COMPILE_WARM_S_MAX, r


# ISSUE-18 closed-loop autotuner budgets (docs/TUNING.md overhead
# table): a tuning-DB consult sits on Context start and on the first
# submit of every tenant, so the cached lookup must stay deep in the
# noise (measured ~17µs parse-warm over 200 signatures; the issue pins
# the 50µs line).  The search harness itself — scoped overrides, trial
# memo, perfdb prior probe, JSONL note per trial — measured ~59µs/trial
# against a no-op objective; gated at ~30x headroom so only a
# structural regression (re-parsing the DB per trial, re-importing jax
# inside the loop) trips it.
TUNE_DB_LOOKUP_US_MAX = 50.0
TUNE_SEARCH_OVERHEAD_US_PER_TRIAL_MAX = 2000.0
TUNE_SPEEDUP_MIN = 1.2


def test_tune_search_and_db_overhead():
    r = microbench.bench_tune(smoke=True)
    assert r["tune_db_lookup_us"] <= TUNE_DB_LOOKUP_US_MAX, r
    assert r["tune_search_overhead_us_per_trial"] <= \
        TUNE_SEARCH_OVERHEAD_US_PER_TRIAL_MAX, r
    # the lookup gate measured against a real population, not one row
    assert r["tune_db_records"] >= 200, r


def test_tuned_cholesky_recovers_seeded_bad_tile(param, tmp_path):
    """The ISSUE-18 acceptance headline: handed a deliberately
    mis-tiled dynamic Cholesky (nb far too small, dispatch-bound), the
    autotuner must claw back >= 1.2x within its trial budget and leave
    the winner in tunedb.jsonl.  Measured ~10x on the smoke shape — the
    gate only fails if the loop stops moving the knob, scores the wrong
    run, or loses the steady-state warmup discipline."""
    import bench
    from parsec_tpu.core.params import params
    from parsec_tpu.device import registry
    params.register("device_tpu_allow_cpu", False)
    param("device_tpu_allow_cpu", True)
    param("tune_db_path", str(tmp_path / "tunedb.jsonl"))
    param("perfdb", False)
    snapshot = list(registry.devices)
    try:
        r = bench.bench_tuned_cholesky(n=256, nb_bad=32, budget=4)
    finally:
        registry.devices = snapshot
        for i, d in enumerate(registry.devices):
            d.device_index = i
    assert r["tune_speedup"] >= TUNE_SPEEDUP_MIN, r
    assert r["best_nb"] != r["nb_bad"], r
    assert r["tile00_abs_err"] <= 1e-3, r
    assert Path(r["db_path"]).exists(), r


@pytest.mark.parametrize("nranks", [2, 4])
def test_commcheck_static_vs_wire_agreement(nranks):
    """ISSUE-20 agreement gate at the comm_ranks smoke points: commcheck
    predicts the collective sweep's cross-rank bytes WITHOUT executing,
    and the measured socket ledger (summed tx across every rank) must
    land within 15% rel of it — drift on either side (a static model
    that forgot an edge, a wire path that started double-shipping)
    fails here by name."""
    from parsec_tpu.analysis.commcheck import (agreement_rel_err,
                                               predict_collective_traffic)
    from parsec_tpu.comm.multiproc import run_multiproc
    pred = predict_collective_traffic(nranks)
    assert pred["bcast_pattern"] == "broadcast", pred
    assert pred["reduce_pattern"] == "reduce", pred
    res = run_multiproc(
        nranks, "parsec_tpu.comm.collectives:_mp_collective_body",
        timeout=240, nb_cores=1)
    observed = sum(d["bytes"] for r in res
                   for d in r["peer_stats"]["tx"].values())
    err = agreement_rel_err(pred["total_bytes"], observed)
    assert err <= COMMCHECK_AGREE_RELERR_MAX, \
        (pred["total_bytes"], observed, err)
