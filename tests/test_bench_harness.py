"""The bench harness itself is a deliverable (VERDICT r4 item 1: round 4
shipped NO perf numbers because ``bench.py`` could be killed before its
single JSON line printed).  These tests pin the new contract:

- a full cumulative JSON line is printed after EVERY stage, so a driver
  kill at any moment leaves parseable evidence in the stdout tail;
- the headline GEMM runs before any secondary stage;
- a hung stage is abandoned by the thread-join timeout and recorded as a
  degraded stage, never an unreported hole;
- smoke mode completes end-to-end on CPU in seconds, with the dynamic
  device stages exercised through the allow-cpu device registration.

Reference role: the always-printing watchdogged harnesses
(``tests/dsl/dtd/dtd_test_simple_gemm.c:649-667``).
"""

import json
import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


@pytest.fixture(scope="module")
def smoke_run(tmp_path_factory):
    """One full BENCH_SMOKE=1 run on CPU, shared by the assertions."""
    env = dict(os.environ)
    env.update(BENCH_SMOKE="1", BENCH_PLATFORM="cpu")
    # run from a scratch cwd so BENCH_partial.json lands there — and
    # point the artifact dir at it so perfdb.jsonl (ISSUE 16) does too
    cwd = tmp_path_factory.mktemp("bench")
    env["PARSEC_TPU_ARTIFACT_DIR"] = str(cwd)
    t0 = time.perf_counter()
    p = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                       capture_output=True, text=True, env=env,
                       cwd=str(cwd), timeout=600)
    return p, time.perf_counter() - t0, cwd


def _json_lines(stdout):
    out = []
    for ln in stdout.splitlines():
        ln = ln.strip()
        if ln.startswith("{"):
            out.append(json.loads(ln))
    return out


def test_smoke_completes_and_last_line_parses(smoke_run):
    p, _dt, _cwd = smoke_run
    assert p.returncode == 0, p.stderr[-2000:]
    lines = _json_lines(p.stdout)
    assert len(lines) >= 10          # one cumulative line per stage
    last = lines[-1]
    assert last["metric"] == "ptg_tiled_gemm_gflops_per_chip"
    assert last["value"] > 0
    assert last["unit"] == "GFLOPS"


def test_every_line_is_full_schema(smoke_run):
    """Any line may be the last one the driver sees: each must carry the
    complete schema, not a stage fragment."""
    p, _dt, _cwd = smoke_run
    for ln in _json_lines(p.stdout):
        assert {"metric", "value", "unit", "vs_baseline",
                "extra"} <= set(ln)
        # the dispatch key is OMITTED when unmeasured (never a -1.0
        # sentinel, ISSUE 2); when present it must be a real reading.
        # In a smoke run the always-first overhead stage supplies it on
        # every line.
        v = ln["extra"].get("task_dispatch_us")
        assert v is None or (isinstance(v, (int, float)) and v >= 0), ln
        assert "task_dispatch_us" in _json_lines(p.stdout)[0]["extra"]


def test_headline_lands_before_secondaries(smoke_run):
    """The fourth JSON line (after overhead + comm + dispatch + gemm) must
    already have a nonzero headline — round 4 ordered it dead last and lost
    the round.  The always-first CPU-safe group (overhead, ISSUE 2; comm,
    ISSUE 4) rides ahead of it because it needs no accelerator and runs in
    seconds."""
    p, _dt, _cwd = smoke_run
    lines = _json_lines(p.stdout)
    assert lines[3]["value"] > 0
    assert lines[3]["extra"]["device_kind"] != "pending"
    # the overhead stage's numbers are already on the FIRST line: the perf
    # axis has evidence before any accelerator stage can hang
    ov = lines[0]["extra"]["overhead"]
    assert ov["dispatch_us"] > 0
    assert ov["release_tasks_per_s"] > 0
    assert ov["steal_us"] > 0
    # the comm wire-path stage lands on the SECOND line, still before
    # anything that can touch the accelerator (ISSUE 4): GET throughput, the
    # pickled-framing baseline ratio, and nonzero overlap efficiency
    cm = lines[1]["extra"]["comm"]
    assert cm["comm_am_roundtrip_us_socket"] > 0
    assert cm["comm_get_socket_4mib_gbps"] > 0
    assert cm["comm_get_speedup_vs_pickle"] > 1.0
    assert cm["comm_overlap_efficiency"] > 0


def test_dynamic_stages_exercised_on_cpu(smoke_run):
    """allow-cpu device registration lets smoke cover the dynamic path."""
    p, _dt, _cwd = smoke_run
    last = _json_lines(p.stdout)[-1]
    assert last["extra"]["dynamic_gemm_gflops"] > 0
    assert last["extra"]["dtd_gemm_tpu_gflops"] > 0
    assert last["extra"]["dynamic_gemm_breakdown"].get("xla_calls", 0) > 0


def test_serve_stage_reports_throughput_and_warm_cache(smoke_run):
    """The serving stage (ISSUE 3) ships sustained submissions/s, ticket
    latency percentiles, and the warm-vs-cold lowered split — and the
    warm repeat class really skipped the compile."""
    last = _json_lines(smoke_run[0].stdout)[-1]
    sv = last["extra"]["serve"]
    assert sv["serve_submits_per_s"] > 0
    assert sv["serve_p50_ms"] > 0
    assert sv["serve_p99_ms"] >= sv["serve_p50_ms"]
    assert sv["serve_lowered_cache_hits"] >= 1
    # the cache-hit counter above is the real guard; the wall-clock
    # comparison needs an absolute floor because a populated persistent
    # XLA disk cache (any prior run on this machine) makes the "cold"
    # submission nearly as fast as the warm one — asserting warm < cold
    # outright is then a coin flip on scheduler noise
    assert sv["serve_lowered_warm_s"] <= \
        max(sv["serve_lowered_cold_s"], 0.05)


def test_llm_stage_reports_tokens_per_s_and_sweep(smoke_run):
    """The LLM serving stage (ISSUE 6) ships tokens/s, per-token p50/p99,
    and the concurrent-streams sweep axis."""
    last = _json_lines(smoke_run[0].stdout)[-1]
    llm = last["extra"]["llm"]
    assert llm["llm_tokens_per_s"] > 0
    assert llm["llm_p99_ms"] >= llm["llm_p50_ms"] > 0
    sweep = llm["llm_streams_sweep"]
    assert len(sweep) >= 2 and all(
        v["tokens_per_s"] > 0 for v in sweep.values()), llm


def test_compile_deadline_death_records_typed_partial_entry():
    """The BENCH_r04/r05 failure shape (ISSUE 6 satellite): a stage dying
    on its deadline mid-compile must degrade to a
    ``{"status": "compile_timeout"}`` record carrying the partial
    metrics it flushed — not vanish into a bare timeout."""
    import bench

    def fake_compile_stage():
        bench._note_partial(phase="compile", lowering_mode="wavefront")
        time.sleep(30)

    prior = list(bench._abandoned)
    try:
        res = bench._staged("fakechol", fake_compile_stage, timeout=0.3)
        assert res["status"] == "compile_timeout", res
        assert res["partial"]["lowering_mode"] == "wavefront", res
        assert res["gflops"] == 0.0 and "error" in res

        # past the compile phase, the same death is a plain timeout —
        # but the flushed compile seconds survive into the record
        def fake_measure_stage():
            bench._note_partial(phase="measure", compile_s=3.2)
            time.sleep(30)

        res = bench._staged("fakemeasure", fake_measure_stage, timeout=0.3)
        assert res["status"] == "timeout", res
        assert res["partial"]["compile_s"] == 3.2, res
    finally:
        bench._abandoned[:] = prior


def test_llm_mid_sweep_deadline_keeps_all_completed_points():
    """ISSUE-9 satellite: bench_llm notes every swept (streams, k) point
    under a UNIQUE key (``_note_partial`` merges by dict update), so a
    deadline death mid-sweep degrades to a record carrying ALL the
    completed points — not just the last one."""
    import bench

    def fake_llm_stage():
        bench._note_partial(phase="llm",
                            llm_point_s8_k1={"tokens_per_s": 400.0})
        bench._note_partial(phase="llm",
                            llm_point_s8_k8={"tokens_per_s": 1600.0})
        time.sleep(30)

    prior = list(bench._abandoned)
    try:
        res = bench._staged("fakellm", fake_llm_stage, timeout=0.3)
        assert res["status"] == "timeout", res
        assert res["partial"]["llm_point_s8_k1"]["tokens_per_s"] == 400.0
        assert res["partial"]["llm_point_s8_k8"]["tokens_per_s"] == 1600.0
    finally:
        bench._abandoned[:] = prior


def test_note_partial_flushes_slo_histograms():
    """ISSUE-10 satellite: every ``_note_partial`` flush snapshots the
    live SLO histogram planes as SERIALIZED BUCKET ARRAYS, so a
    deadline death mid-serve/llm stage keeps the latency distribution
    collected so far (reconstructable via ``LogHistogram.from_dict``),
    not just the counters."""
    import bench
    from parsec_tpu.prof.histogram import LogHistogram, SLOPlane

    plane = SLOPlane()              # stays referenced through the stage
    for v in (3.0, 12.5, 40.0):
        plane.observe("tenantX", "ttft_ms", v)

    def fake_slo_stage():
        bench._note_partial(phase="llm", point=1)
        time.sleep(30)

    prior = list(bench._abandoned)
    try:
        res = bench._staged("fakeslo", fake_slo_stage, timeout=0.3)
        assert res["status"] == "timeout", res
        sh = res["partial"]["slo_hist"]
        assert "tenantX" in sh, sh
        h = LogHistogram.from_dict(sh["tenantX"]["ttft_ms"])
        assert h.count == 3
        assert h.quantile(0.5) > 0
    finally:
        bench._abandoned[:] = prior
        plane.reset()


def test_serve_and_llm_stages_emit_per_tenant_slo(smoke_run):
    """ISSUE-10 acceptance: the serve and llm stages emit per-tenant
    quantiles off the histogram plane — the llm stage ttft/tok-latency
    p50/p99 per tenant, the serve stage queue-wait/latency."""
    last = _json_lines(smoke_run[0].stdout)[-1]
    llm_slo = last["extra"]["llm"]["llm_slo"]
    assert llm_slo, last["extra"]["llm"].keys()
    for tenant, d in llm_slo.items():
        assert d["ttft_ms_p50"] > 0, (tenant, d)
        assert d["ttft_ms_p99"] >= d["ttft_ms_p50"], (tenant, d)
        assert d["tok_latency_ms_p99"] >= d["tok_latency_ms_p50"] > 0
    serve_slo = last["extra"]["serve"]["serve_slo"]
    tenants = [t for t in serve_slo if t.startswith("tenant")]
    assert tenants, serve_slo.keys()
    for t in tenants:
        assert serve_slo[t]["latency_ms_p99"] >= \
            serve_slo[t]["latency_ms_p50"] > 0
        assert serve_slo[t]["queue_wait_ms_count"] > 0


def test_lowered_stages_report_compile_seconds(smoke_run):
    last = _json_lines(smoke_run[0].stdout)[-1]
    assert last["extra"]["lowered_cholesky_compile_s"] > 0
    assert last["extra"]["lowered_cholesky_gflops"] > 0
    assert last["extra"]["lowered_lu_gflops"] > 0
    assert last["extra"]["lowered_stencil_gflops"] > 0


def test_partial_file_mirrors_last_line(smoke_run):
    p, _dt, cwd = smoke_run
    with open(os.path.join(str(cwd), "BENCH_partial.json")) as f:
        mirrored = json.loads(f.read())
    last = _json_lines(p.stdout)[-1]
    # elapsed_s differs line to line; compare the stable payload
    mirrored["extra"].pop("elapsed_s"), last["extra"].pop("elapsed_s")
    assert mirrored == last


def test_perfdb_ledger_written_and_verdicts_in_emit(smoke_run):
    """ISSUE-16: a bench run appends every stage's scalars to the
    persistent perf ledger, prints one [perfdb] verdict line per stage,
    and the emit carries the ``perfdb_regressions`` export on EVERY
    cumulative line (any line may be the last one the driver sees)."""
    p, _dt, cwd = smoke_run
    ledger = os.path.join(str(cwd), "perfdb.jsonl")
    assert os.path.exists(ledger), os.listdir(str(cwd))
    recs = [json.loads(ln) for ln in open(ledger) if ln.strip()]
    assert len(recs) > 50, len(recs)        # dozens of metrics x stages
    assert all("key" in r and "value" in r for r in recs)
    assert "[perfdb]" in p.stderr
    for ln in _json_lines(p.stdout):
        assert isinstance(ln["extra"].get("perfdb_regressions"), list), ln


def test_perfdb_accrues_across_invocations_and_verdicts_drift(
        tmp_path, monkeypatch, capsys):
    """ISSUE-16 acceptance, harness form: consecutive invocations of the
    bench perfdb hook accrue history in one ledger file, and once the
    EWMA is warm a 10x cliff in a later invocation is verdicted
    REGRESSED — in the stderr line AND in the ``perfdb_regressions``
    export the next emit would carry."""
    import bench
    monkeypatch.setenv("PARSEC_TPU_ARTIFACT_DIR", str(tmp_path))
    ledger = tmp_path / "perfdb.jsonl"
    prior = dict(bench._perfdb_state)
    try:
        bench._perfdb_state["regressions"] = []
        # invocations 1..3: stable numbers warm the per-key EWMA
        for _ in range(3):
            bench._perfdb_note("fakestage", {"dispatch_us": 100.0})
        n1 = sum(1 for _ in open(ledger))
        assert n1 == 3
        assert bench._perfdb_state["regressions"] == []
        # invocation 4: the 10x cliff
        bench._perfdb_note("fakestage", {"dispatch_us": 1000.0})
        assert sum(1 for _ in open(ledger)) == n1 + 1   # still accruing
        reg = bench._perfdb_state["regressions"]
        assert len(reg) == 1, reg
        assert reg[0]["stage"] == "fakestage"
        assert reg[0]["metric"] == "dispatch_us" and reg[0]["z"] > 0
        err = capsys.readouterr().err
        assert "[perfdb] fakestage" in err and "REGRESSED" in err, err
    finally:
        bench._perfdb_state.clear()
        bench._perfdb_state.update(prior)


def test_deadline_death_flushes_xla_dispatch_ledger():
    """ISSUE-16 satellite: an rc-124-shaped stage death must keep the
    calls-per-DAG axis — every ``_note_partial`` flush snapshots the
    XLA-dispatch ledger total alongside the histogram planes."""
    import bench
    from parsec_tpu.device.device import note_xla_calls, xla_calls_total

    base = xla_calls_total()
    note_xla_calls(7)                      # the stage dispatched work

    def fake_xla_stage():
        bench._note_partial(phase="compile", lowering_mode="region")
        time.sleep(30)

    prior = list(bench._abandoned)
    try:
        res = bench._staged("fakexla", fake_xla_stage, timeout=0.3)
        assert res["status"] == "compile_timeout", res
        assert res["partial"]["xla_calls_total"] >= base + 7, res
    finally:
        bench._abandoned[:] = prior


def test_hung_stage_is_abandoned_not_fatal():
    """A stage that never returns must be timed out, recorded as degraded,
    and must not stop later stages from reporting."""
    import bench
    before = list(bench._abandoned)
    try:
        res = bench._staged("hang", lambda: time.sleep(60), timeout=0.5)
        assert "error" in res and "timeout" in res["error"]
        assert bench._abandoned == before + ["hang"]
        # a later successful stage carries the taint marker
        ok = bench._staged("after", lambda: {"gflops": 1.0}, timeout=5.0)
        assert ok["tainted_by"] == before + ["hang"]
    finally:
        bench._abandoned[:] = before


def test_failing_stage_degrades_with_reason():
    import bench

    def boom():
        raise RuntimeError("device reset")

    res = bench._staged("boom", boom, timeout=5.0)
    assert res["gflops"] == 0.0
    assert "device reset" in res["error"]


def test_every_stage_carries_runtime_report(smoke_run):
    """EVERY stage of the output JSON ships a flight-recorder
    self-report — the per-stage runtime evidence the round-5 outage
    proved is needed even (especially) when a stage degrades."""
    p, _dt, _cwd = smoke_run
    last = _json_lines(p.stdout)[-1]
    reports = last["extra"]["runtime_reports"]
    stage_names = {"dispatch", "gemm", "raw_dot", "serve", "stencil",
                   "lowered_cholesky", "lowered_stencil", "lowered_lu",
                   "dynamic_gemm", "dtd_gemm", "lowered_cholesky_16k",
                   "dynamic_cholesky"}
    assert stage_names <= set(reports), sorted(reports)
    for name in stage_names:
        assert "tasks_retired" in reports[name], (name, reports[name])
    # degraded stages (if any) still carry their self-report
    for name in last["extra"].get("degraded_stages", {}):
        assert name in reports
    # the dynamic stages really self-measured: retired counts are live
    assert reports["dynamic_gemm"]["tasks_retired"] > 0


def test_degraded_stages_carry_runtime_report():
    """Timeout, exception, and budget-exhausted degrade paths all embed
    the runtime self-report block (artificially degraded stages)."""
    import bench
    before = list(bench._abandoned)
    try:
        hung = bench._staged("rr-hang", lambda: time.sleep(30), timeout=0.3)
        assert "runtime_report" in hung
        assert "tasks_retired" in hung["runtime_report"]

        def boom():
            raise RuntimeError("device reset")
        failed = bench._staged("rr-boom", boom, timeout=5.0)
        assert "runtime_report" in failed
    finally:
        bench._abandoned[:] = before


def test_budget_exhausted_logs_and_uses_prior_taint(capsys):
    """The budget-exhausted early return reports like the other degrade
    paths: stderr line + prior-snapshot tainted_by (ADVICE round 5)."""
    import bench
    before = list(bench._abandoned)

    def flaky():
        raise RuntimeError("reset")

    try:
        bench._abandoned[:] = ["earlier-zombie"]
        # timeout < 1s: the retry's remaining budget is under the 1.0s
        # floor, so attempt 2 takes the budget-exhausted early return
        res = bench._staged("rr-budget", flaky, timeout=0.5, retries=3)
        assert "budget" in res["error"]
        # prior snapshot: the pre-existing zombie, never the stage itself
        assert res["tainted_by"] == ["earlier-zombie"]
        assert "runtime_report" in res
        err = capsys.readouterr().err
        assert "budget" in err and "rr-budget" in err
    finally:
        bench._abandoned[:] = before


def test_stage_budget_spec_parses_mca_env_grammar(param):
    """bench_stage_budget_s (ISSUE 8 satellite): '<seconds>' rebudgets
    every stage, 'name=sec' named ones, '*' the default."""
    import bench
    bench._stage_budgets()                 # first call registers the param
    param("bench_stage_budget_s", "gemm=300, lowered_cholesky=240,*=45")
    assert bench._stage_budgets() == {"gemm": 300.0,
                                      "lowered_cholesky": 240.0, "*": 45.0}
    param("bench_stage_budget_s", "75")
    assert bench._stage_budgets() == {"*": 75.0}
    param("bench_stage_budget_s", "")
    assert bench._stage_budgets() == {}
    param("bench_stage_budget_s", "gemm=nonsense")  # malformed: ignored
    assert bench._stage_budgets() == {}


def test_region_stage_budget_shed_completes_instead_of_rc124():
    """ISSUE-8 acceptance, harness form: a region stage whose compile
    budget can afford NOTHING must still complete inside its deadline —
    regions shed to the eager path (stage done, correct result, no
    compile_timeout), and the partial trail names the budget."""
    import bench
    from parsec_tpu.ptg.lowering import lowering_cache

    lowering_cache.clear()                 # force a genuinely cold plan
    res = bench._staged("region-shed", bench.bench_region_cholesky_gflops,
                        n=512, nb=128, budget_s=1e-9, timeout=90.0)
    assert "status" not in res and "error" not in res, res
    assert res["gflops"] > 0
    assert res["regions_eager"] >= 1 and res["regions_compiled"] == 0, res
    assert res["compile_s"] == 0.0
    assert res["tile00_abs_err"] < 1e-4
    # ...and a warm second run compiles for free (the persistent-cache
    # half of the acceptance line): same geometry, same tiny budget,
    # but cache hits are never shed
    res2 = bench._staged("region-warm", bench.bench_region_cholesky_gflops,
                         n=512, nb=128, budget_s=1e-9, timeout=90.0)
    assert "error" not in res2, res2
    # the shed run never compiled, so the in-process cache is still cold
    # for shed regions; a prior COMPILED plan is what warms it
    bench.bench_region_cholesky_gflops(n=512, nb=128, budget_s=60.0)
    res3 = bench._staged("region-warm2", bench.bench_region_cholesky_gflops,
                         n=512, nb=128, budget_s=1e-9, timeout=90.0)
    assert res3["regions_eager"] == 0, res3
    assert res3["compile_s"] <= 0.01, res3


def test_region_stage_lands_in_smoke_emit(smoke_run):
    last = _json_lines(smoke_run[0].stdout)[-1]
    assert last["extra"]["region_cholesky_gflops"] > 0
    assert last["extra"]["region_cholesky_regions"] >= 1
    assert last["extra"]["region_cholesky_eager"] == 0
