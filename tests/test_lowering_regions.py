"""Megakernel region lowering (ISSUE 8): graphcheck-driven region
selection, one jitted program per convex subgraph, runtime scheduling at
region boundaries only, all under an explicit compile budget.

Covers the ISSUE-8 acceptance criteria on CPU:
- region-lowered cholesky (the irregular 4-class POTRF/TRSM/SYRK/GEMM
  DAG) and the LLM decode step match the eager runtime path across
  nb/nt sweeps;
- the region pool itself passes graphcheck (regions must not hide
  WAR/WAW hazards the whole-pool analysis proved ordered);
- XLA dispatches per DAG drop >= 5x vs task-per-dispatch;
- a compile budget the plan cannot afford sheds regions to the eager
  path (the stage completes — no rc-124 death), while a warm second
  plan asks XLA for no compile: every region is a hit in the process
  lowering cache.
"""

import json

import numpy as np
import pytest

from parsec_tpu.analysis import GraphCheckError, select_regions, task_levels
from parsec_tpu.analysis.regions import regions_of_report
from parsec_tpu.data.datatype import TileType
from parsec_tpu.data_dist.collection import DictCollection
from parsec_tpu.data_dist.matrix import SymTwoDimBlockCyclic
from parsec_tpu.data_dist.paged_kv import PagedKVCollection
from parsec_tpu.llm import ToyLM, decode_step_ptg, prefill_chunks
from parsec_tpu.models.cholesky import make_spd, tiled_cholesky_ptg
from parsec_tpu.ptg import lowering
from parsec_tpu.ptg.lowering import lower_regions, lowering_cache
from parsec_tpu.runtime import Context


# ---------------------------------------------------------------------------
# region selection (analysis.regions)
# ---------------------------------------------------------------------------

def _diamond():
    # a -> b, c -> d  plus an isolated 2-chain x -> y (second component)
    return {
        ("A", (0,)): [("B", (0,)), ("C", (0,))],
        ("B", (0,)): [("D", (0,))],
        ("C", (0,)): [("D", (0,))],
        ("D", (0,)): [],
        ("X", (0,)): [("Y", (0,))],
        ("Y", (0,)): [],
    }


def test_task_levels_are_longest_path():
    lv = task_levels(_diamond())
    assert lv[("A", (0,))] == 0
    assert lv[("B", (0,))] == lv[("C", (0,))] == 1
    assert lv[("D", (0,))] == 2
    assert lv[("X", (0,))] == 0 and lv[("Y", (0,))] == 1


def test_select_regions_unbounded_is_one_per_component():
    regs = select_regions(_diamond())
    assert len(regs) == 2
    sizes = sorted(r.ntasks for r in regs)
    assert sizes == [2, 4]
    # independent components share no region-DAG edges
    assert all(not r.preds and not r.succs for r in regs)


def test_select_regions_cap_splits_on_band_boundaries():
    adj = _diamond()
    regs = select_regions(adj, max_tasks=2)
    # regions partition the node set exactly
    assign = {}
    for r in regs:
        for node in r.members:
            assert node not in assign
            assign[node] = r.index
    assert set(assign) == set(adj)
    # bounded size: a region only exceeds the cap when a single level
    # band is itself larger (bands never split)
    for r in regs:
        assert r.ntasks <= 2 or r.level_lo == r.level_hi
    # convexity: every task edge crossing regions matches a region-DAG
    # edge, and region edges always point to later level bands
    for v, succs in adj.items():
        for s in succs:
            if assign[v] != assign[s]:
                assert assign[s] in regs[assign[v]].succs
                assert assign[v] in regs[assign[s]].preds
    for r in regs:
        for p in r.preds:
            assert regs[p].level_lo <= r.level_lo


def test_task_levels_raises_on_cycle():
    adj = {("A", (0,)): [("B", (0,))], ("B", (0,)): [("A", (0,))]}
    with pytest.raises(ValueError, match="cycle"):
        task_levels(adj)


def test_regions_of_report_rejects_truncated_and_failing():
    class FakeReport:
        truncated = True
        ok = True
        name = "fake"
        graph = {}
        ntasks = 0
    with pytest.raises(ValueError, match="truncated"):
        regions_of_report(FakeReport())


def test_regions_of_report_rejects_graphless_nonempty_report():
    """Only check_ptg retains the concrete graph; a DTD/JDF report must
    refuse loudly instead of yielding zero regions for a live pool."""
    class DTDShapedReport:
        truncated = False
        ok = True
        name = "dtd"
        graph = {}
        ntasks = 7
    with pytest.raises(ValueError, match="no concrete task graph"):
        regions_of_report(DTDShapedReport())


# ---------------------------------------------------------------------------
# cholesky: the irregular 4-class DAG, region-lowered vs the eager runtime
# ---------------------------------------------------------------------------

def _chol_eager(a, nb):
    """The eager runtime path: numpy bodies, task-grained scheduling."""
    A = SymTwoDimBlockCyclic.from_dense("A", a.copy(), nb, nb)
    tp = tiled_cholesky_ptg(A, devices="cpu")
    with Context(nb_cores=0) as ctx:
        ctx.add_taskpool(tp)
        ctx.wait(timeout=120)
    return np.tril(A.to_dense())


@pytest.mark.parametrize("n,nb,max_tasks", [
    (64, 16, 0),        # nt=4, one region per component
    (96, 32, 0),        # nt=3
    (128, 32, 6),       # nt=4, forced multi-region (band splits)
    (160, 32, 8),       # nt=5, multi-region with cross-band conflicts
])
def test_region_cholesky_matches_eager_runtime(n, nb, max_tasks):
    a = make_spd(n)
    want = _chol_eager(a, nb)
    A = SymTwoDimBlockCyclic.from_dense("A", a.copy(), nb, nb)
    plan = lower_regions(tiled_cholesky_ptg(A), max_tasks=max_tasks)
    plan.execute()
    got = np.tril(A.to_dense())
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-4)
    # ... and against the dense oracle, so both paths can't be wrong
    expect = np.linalg.cholesky(a.astype(np.float64)).astype(np.float32)
    np.testing.assert_allclose(got, expect, rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("n,nb,ntasks", [(160, 32, 35), (128, 32, 20)],
                         ids=["nt5", "nt4"])
def test_region_cholesky_xla_call_drop_vs_task_per_dispatch(
        accel_device, param, compile_requests, n, nb, ntasks):
    """ISSUE-8 acceptance, both sides counted on the one process-wide
    ledger: with vmapped batching off the dynamic device path makes one
    XLA call a task, the region path at least 5x fewer, and a second,
    structurally identical plan asks XLA for no compile."""
    from parsec_tpu.device.device import xla_calls_total
    a = make_spd(n)

    def chol(devices="auto"):
        A = SymTwoDimBlockCyclic.from_dense("A", a.copy(), nb, nb)
        return tiled_cholesky_ptg(A, devices=devices)

    param("device_tpu_batch", False)
    before = xla_calls_total()
    with Context(nb_cores=0) as ctx:
        ctx.add_taskpool(chol(devices="tpu"))
        ctx.wait(timeout=120)
        accel_device.sync()
    dispatch_calls = xla_calls_total() - before
    assert dispatch_calls == accel_device.executed_tasks == ntasks
    plan = lower_regions(chol())
    before = xla_calls_total()
    plan.execute()
    st = plan.stats()
    assert st["ntasks"] == ntasks
    assert xla_calls_total() - before == st["xla_calls"] >= 1
    assert dispatch_calls / st["xla_calls"] >= 5.0, st
    requests = compile_requests()
    warm = lower_regions(chol())
    warm.compile()
    assert warm.stats()["regions_compiled"] == st["regions_compiled"]
    assert compile_requests() == requests


def test_region_pool_passes_graphcheck():
    """The region pool (one REGION task per region, CTL fan-in edges
    mirroring the region DAG) is a plain PTG pool — graphcheck must
    prove it clean, or region scheduling hides hazards."""
    a = make_spd(128)
    A = SymTwoDimBlockCyclic.from_dense("A", a.copy(), 32, 32)
    plan = lower_regions(tiled_cholesky_ptg(A), max_tasks=6)
    assert len(plan.regions) > 1
    plan.compile()
    table = plan.materialize_table()
    pool = plan.taskpool(table)
    report = pool.validate()
    assert not report.errors, report.summary()
    assert pool.region_plan is plan


def test_region_program_size_is_grouped_not_per_task():
    """O(wavefronts x classes) program size: the region emission groups
    same-class tasks into vmapped calls, so a region's spec count stays
    far below its task count."""
    a = make_spd(256)
    A = SymTwoDimBlockCyclic.from_dense("A", a.copy(), 32, 32)
    plan = lower_regions(tiled_cholesky_ptg(A))     # nt=8 -> 120 tasks
    st = plan.stats()
    assert st["ntasks"] == 120
    assert st["regions"] == 1
    # one program, 120 tasks: the signature's runs payload carries one
    # spec list per (folded) level, not one entry per task
    reg = next(r for r in plan.regions if r.step_fn is not None)
    nspecs = sum(len(specs) for _reps, specs in reg.signature[-1])
    assert nspecs < st["ntasks"] / 2, nspecs


# ---------------------------------------------------------------------------
# compile budget: shed to eager, warm hits are free
# ---------------------------------------------------------------------------

def _fresh_chol_plan(n=160, nb=32, max_tasks=8):
    a = make_spd(n)
    A = SymTwoDimBlockCyclic.from_dense("A", a.copy(), nb, nb)
    return a, A, lower_regions(tiled_cholesky_ptg(A), max_tasks=max_tasks)


def test_compile_budget_sheds_to_eager_and_still_completes():
    lowering_cache.clear()
    a, A, plan = _fresh_chol_plan()
    notes = []
    st = plan.compile(budget_s=1e-9,
                      note=lambda **kw: notes.append(kw))
    data_regions = [r for r in plan.regions if r.step_fn is not None]
    assert st["regions_compiled"] == 0
    assert st["regions_eager"] == len(data_regions)
    assert any(n_.get("eager") for n_ in notes)
    # the stage still completes (no rc-124 compile death) and is correct
    plan.execute()
    got = np.tril(A.to_dense())
    expect = np.linalg.cholesky(a.astype(np.float64)).astype(np.float32)
    np.testing.assert_allclose(got, expect, rtol=1e-3, atol=1e-4)
    assert plan.stats()["xla_calls"] == 0
    assert plan.stats()["eager_runs"] == len(data_regions)


def test_compile_budget_warm_run_is_free(compile_requests):
    """ISSUE-8 acceptance: a warm second run compiles nothing — cache hits
    are never shed, even under a budget no compile could fit."""
    _a, _A, plan = _fresh_chol_plan()
    plan.compile()                        # cold: pays trace + compile
    assert plan.stats()["regions_compiled"] > 0
    _a2, _A2, plan2 = _fresh_chol_plan()  # structurally identical
    notes = []
    requests = compile_requests()
    st = plan2.compile(budget_s=1e-9,
                       note=lambda **kw: notes.append(kw))
    assert st["regions_eager"] == 0
    assert st["regions_compiled"] == plan.stats()["regions_compiled"]
    assert compile_requests() == requests
    assert notes and all(n_.get("cached") for n_ in notes)


def test_budget_staged_compile_is_ascending_and_sheds_monotonically():
    """Staged compile runs SMALLEST region first: the cheap compiles
    bootstrap the per-task cost rate that guards the expensive ones, so
    the largest region sheds BEFORE burning the budget (the 141s
    BENCH_r04/r05 compile could never be the first thing attempted).
    Mixed compiled/eager execution stays correct."""
    lowering_cache.clear()
    a, A, plan = _fresh_chol_plan(max_tasks=6)
    assert len([r for r in plan.regions if r.step_fn is not None]) >= 3
    notes = []
    st = plan.compile(budget_s=3.0,       # CPU compiles are ~0.1-0.5s each
                      note=lambda **kw: notes.append(kw))
    # processing order is ascending by region size
    sizes = [n_["ntasks"] for n_ in notes]
    assert sizes == sorted(sizes), notes
    # shedding is monotone: once the budget stops affording a region,
    # every later (>= as large) region sheds too (cache is cold, so no
    # free hits can interleave)
    eager_flags = [bool(n_.get("eager")) for n_ in notes]
    if any(eager_flags):
        first = eager_flags.index(True)
        assert all(eager_flags[first:]), notes
    assert st["regions_compiled"] >= 1
    plan.execute()
    got = np.tril(A.to_dense())
    expect = np.linalg.cholesky(a.astype(np.float64)).astype(np.float32)
    np.testing.assert_allclose(got, expect, rtol=1e-3, atol=1e-4)


def test_lower_regions_truncated_report_raises_lowering_error(param):
    """A truncated graphcheck enumeration (analysis_max_tasks) cannot
    produce sound regions — and it must surface as LoweringError, the
    documented contract, so callers' fallback paths engage."""
    from parsec_tpu.ptg.lowering import LoweringError

    param("analysis_max_tasks", 5)
    a = make_spd(160)
    A = SymTwoDimBlockCyclic.from_dense("A", a, 32, 32)   # 35 tasks > 5
    with pytest.raises(LoweringError, match="truncated"):
        lower_regions(tiled_cholesky_ptg(A))


# ---------------------------------------------------------------------------
# LLM decode step: parallel per-sequence components, open collections
# ---------------------------------------------------------------------------

MODEL = ToyLM()
H, D = MODEL.num_heads, MODEL.head_dim
PROMPTS = {"a": [3, 7, 11, 5, 9, 2], "b": [1, 40], "c": [8, 8, 2, 6]}


def _decode_setup(devices):
    """One decode-step geometry: pages prefilled host-side (the PF pool's
    straight page copy, done directly), Q loaded with the query token."""
    kv = PagedKVCollection("KV", page_size=4, num_heads=H, head_dim=D)
    Q = DictCollection("Q", dtt=TileType((3, H, D), np.float32))
    O = DictCollection("O", dtt=TileType((H, D), np.float32))
    for seq, prompt in PROMPTS.items():
        kv.alloc_seq(seq)
        chunks = prefill_chunks(MODEL, kv, seq, prompt[:-1])
        for (s, c), tile in chunks.items():
            copy = kv.data_of(s, c).newest_copy()
            copy.value = np.array(tile, copy=True)
            copy.version += 1
        kv.ensure_tail_slot(seq)
        qc = Q.data_of(seq).get_copy(0)
        qc.value = MODEL.q3(prompt[-1])
        qc.version += 1
    return kv, Q, O, decode_step_ptg(kv, Q, O, list(PROMPTS),
                                     devices=devices)


@pytest.mark.parametrize("max_tasks", [0, 4])
def test_region_llm_decode_matches_eager_runtime(max_tasks):
    kv_e, _Qe, O_e, tp_e = _decode_setup("cpu")
    with Context(nb_cores=0) as ctx:
        ctx.add_taskpool(tp_e)
        ctx.wait(timeout=120)

    kv_r, _Qr, O_r, tp_r = _decode_setup("auto")
    plan = lower_regions(tp_r, max_tasks=max_tasks)
    if max_tasks == 0:
        # per-sequence ATTN chains are independent components -> the
        # runtime may execute them as parallel regions
        assert len(plan.regions) == len(PROMPTS)
    plan.execute()

    for seq, prompt in PROMPTS.items():
        got = np.asarray(O_r.data_of(seq).newest_copy().value)
        want = np.asarray(O_e.data_of(seq).newest_copy().value)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
        # the OUT task's tail-page append (KV writeback) must match too
        pe = np.asarray(
            kv_e.data_of(seq, kv_e.npages(seq) - 1).newest_copy().value)
        pr = np.asarray(
            kv_r.data_of(seq, kv_r.npages(seq) - 1).newest_copy().value)
        np.testing.assert_allclose(pr, pe, rtol=1e-5, atol=1e-6)


def test_parallel_identical_regions_share_one_executable():
    """Structurally identical regions (same grouped runs, same avals —
    the decode step's parallel per-seq chains at equal page counts) must
    share ONE compiled executable: the cache key covers what the traced
    program depends on, not the global boundary rows."""
    kv = PagedKVCollection("KV", page_size=4, num_heads=H, head_dim=D)
    Q = DictCollection("Q", dtt=TileType((3, H, D), np.float32))
    O = DictCollection("O", dtt=TileType((H, D), np.float32))
    seqs = [f"s{i}" for i in range(4)]
    for s in seqs:                        # equal geometry: 2 pages each
        kv.alloc_seq(s)
        chunks = prefill_chunks(MODEL, kv, s, [3, 7, 11, 5])
        for (sq, c), tile in chunks.items():
            copy = kv.data_of(sq, c).newest_copy()
            copy.value = np.array(tile, copy=True)
            copy.version += 1
        kv.ensure_tail_slot(s)
        qc = Q.data_of(s).get_copy(0)
        qc.value = MODEL.q3(9)
        qc.version += 1
    plan = lower_regions(decode_step_ptg(kv, Q, O, seqs, devices="auto"))
    assert len(plan.regions) == len(seqs)
    h0, m0 = lowering_cache.hits, lowering_cache.misses
    st = plan.compile()
    assert st["regions_compiled"] == len(seqs)
    assert lowering_cache.misses - m0 <= 1, (
        lowering_cache.misses - m0, "identical regions re-compiled")
    assert lowering_cache.hits - h0 >= len(seqs) - 1


def test_region_llm_decode_pool_passes_graphcheck():
    _kv, _Q, _O, tp = _decode_setup("auto")
    plan = lower_regions(tp)
    plan.compile()
    table = plan.materialize_table()
    pool = plan.taskpool(table)
    report = pool.validate()
    assert not report.errors, report.summary()


# ---------------------------------------------------------------------------
# LLM k-step decode superpool: the ISSUE-9 multi-step generalization
# ---------------------------------------------------------------------------

def _superpool_setup(steps, devices):
    """k-step geometry over PROMPTS, prepped by the library's own
    ``seed_decode_superpool`` (the batcher's seeding contract)."""
    from parsec_tpu.llm import decode_superpool_ptg, seed_decode_superpool
    kv = PagedKVCollection("KV", page_size=4, num_heads=H, head_dim=D)
    Q = DictCollection("Q", dtt=TileType((3, H, D), np.float32))
    O = DictCollection("O", dtt=TileType((H, D), np.float32))
    TOK = DictCollection("TOK", dtt=TileType((3,), np.float32))
    EMB = DictCollection("EMB", dtt=TileType(MODEL.q3_table().shape,
                                             np.float32))
    seed_decode_superpool(MODEL, kv, Q, TOK, EMB, PROMPTS, steps)
    tp = decode_superpool_ptg(kv, Q, O, TOK, EMB, list(PROMPTS),
                              [steps[s] for s in PROMPTS],
                              devices=devices)
    return kv, TOK, tp


@pytest.mark.parametrize("max_tasks", [0, 8])
def test_region_llm_superpool_k_steps_matches_eager_runtime(max_tasks):
    """The ISSUE-9 acceptance: the 1-step eager-vs-region equivalence
    generalizes to k > 1 — cross-step tail-page dataflow, in-graph
    SAMPLE chains, mixed per-seq step counts, page boundaries crossed
    mid-pool — and both paths equal the dense token oracle."""
    steps = {"a": 5, "b": 4, "c": 2}
    kv_e, TOK_e, tp_e = _superpool_setup(steps, "cpu")
    with Context(nb_cores=0) as ctx:
        ctx.add_taskpool(tp_e)
        ctx.wait(timeout=120)

    kv_r, TOK_r, tp_r = _superpool_setup(steps, "auto")
    plan = lower_regions(tp_r, max_tasks=max_tasks)
    if max_tasks == 0:
        # per-sequence chains stay independent components across steps
        assert len(plan.regions) == len(PROMPTS)
    plan.execute()

    from parsec_tpu.llm import read_token_chain

    def toks(TOK, seq, k):
        return read_token_chain(TOK, seq, k)[0]

    for seq, prompt in PROMPTS.items():
        want = MODEL.reference_generate(prompt, steps[seq])
        assert toks(TOK_e, seq, steps[seq]) == want, ("eager", seq)
        assert toks(TOK_r, seq, steps[seq]) == want, ("region", seq)
        # the tail page (appended k/v of every step) must agree too
        pe = np.asarray(
            kv_e.data_of(seq, kv_e.npages(seq) - 1).newest_copy().value)
        pr = np.asarray(
            kv_r.data_of(seq, kv_r.npages(seq) - 1).newest_copy().value)
        np.testing.assert_allclose(pr, pe, rtol=1e-5, atol=1e-6)


def test_region_llm_superpool_pool_passes_graphcheck():
    """The region pool built from a k-step superpool is itself a clean
    PTG pool (region scheduling must not hide the cross-step WAR/WAW
    hazards the whole-pool analysis proved ordered)."""
    steps = {"a": 4, "b": 3, "c": 2}
    _kv, _TOK, tp = _superpool_setup(steps, "auto")
    plan = lower_regions(tp)
    plan.compile()
    table = plan.materialize_table()
    pool = plan.taskpool(table)
    report = pool.validate()
    assert not report.errors, report.summary()


# ---------------------------------------------------------------------------
# graphcheck gating: an unverifiable pool never region-lowers
# ---------------------------------------------------------------------------

def test_lower_regions_refuses_failing_graphcheck():
    from parsec_tpu import ptg

    # a pool whose edge symmetry is broken: A declares a successor edge
    # that B never declares as input
    p = ptg.PTGBuilder("bad", N=2)
    ta = p.task("A", i=ptg.span(0, lambda g, l: g.N - 1))
    fa = ta.flow("ctl", ptg.CTL)
    fa.output(succ=("B", "ctl", lambda g, l: {"i": l.i}))
    ta.body(lambda es, task, g, l: None)
    tb = p.task("B", i=ptg.span(0, lambda g, l: g.N - 1))
    tb.flow("ctl", ptg.CTL)             # no matching input edge
    tb.body(lambda es, task, g, l: None)
    with pytest.raises(GraphCheckError):
        lower_regions(p.build())


# ---------------------------------------------------------------------------
# AOT cache warming CLI
# ---------------------------------------------------------------------------

def test_warm_cache_cli_region_mode(capsys):
    rc = lowering._main(["--warm", "cholesky", "--n", "128", "--nb", "32",
                         "--modes", "region"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["workload"] == "cholesky"
    assert out["region"]["regions"] >= 1
    assert out["region"]["regions_eager"] == 0
    assert "backend" in out                   # the cross-backend cache key


def test_warm_cache_cli_llm_decode_k_workload(capsys):
    """The ISSUE-9 AOT entry: the k-step decode superpool's region
    programs warm through the CLI (scripts/warm_cache.sh ships it in
    the default workload set)."""
    rc = lowering._main(["--warm", "llm_decode_k", "--n", "2", "--nb",
                         "2", "--modes", "region"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["workload"] == "llm_decode_k"
    assert out["nseqs"] == 2 and out["steps"] == 2
    assert out["region"]["regions"] >= 1
    assert out["region"]["regions_eager"] == 0


def test_warm_cache_traces_against_avals_without_executing(compile_requests):
    """warm_cache compiles AOT — collection tiles must stay untouched."""
    out = lowering.warm_cache("cholesky", n=96, nb=32, modes=("region",))
    assert out["region"]["regions_compiled"] >= 1
    # a second warm at the same geometry is a pure cache hit
    requests = compile_requests()
    out2 = lowering.warm_cache("cholesky", n=96, nb=32, modes=("region",))
    assert out2["region"]["regions_compiled"] == \
        out["region"]["regions_compiled"]
    assert compile_requests() == requests
