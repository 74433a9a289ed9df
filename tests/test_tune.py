"""Closed-loop autotuner (ISSUE 18): knob declarations and scoped
overrides, structural signatures, the tuning DB (cross-process), the
budgeted search, ambient consults at Context start / per-tenant submit,
and the live per-tenant adaptation controller.

The acceptance e2e lives here too: a seeded-bad knob vector on a small
decode workload is recovered by ``tune.search`` within a bounded
budget, the winner persists to ``tunedb.jsonl``, a fresh ``Context``
picks it up, and the per-tenant adapter stays oracle-equal
token-for-token while converging."""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from parsec_tpu.core.params import KnobSpec, params
from parsec_tpu.tune import (TuneDB, ambient_signature, apply_ambient,
                             consult_ambient, workload_signature)
from parsec_tpu.tune import db as tunedb_mod
from parsec_tpu.tune.adaptive import GARBAGE_LIMIT, KnobController
from parsec_tpu.tune.search import declared_space, search

REPO = Path(__file__).resolve().parent.parent


# ---------------------------------------------------------------------------
# knob space + scoped overrides (core/params.py)
# ---------------------------------------------------------------------------

def test_knobspec_moves_and_domain():
    s = KnobSpec(name="k", lo=1, hi=8, scale="log2")
    assert s.neighbors(2) == [4, 1]
    assert s.neighbors(8) == [4]            # hi clamp folds the up move
    assert s.contains(8) and not s.contains(9)
    e = KnobSpec(name="m", values=("a", "b", "c"))
    assert e.neighbors("b") == ["a", "c"]
    assert e.neighbors("zz") == ["a", "b", "c"]   # off-domain: full reset
    lin = KnobSpec(name="n", lo=0, hi=10, step=2.0)
    assert lin.neighbors(4) == [6, 2]


def test_declare_knob_idempotent_and_declared_space():
    params.register("tune_t_knob", 4, "test knob")
    s1 = params.declare_knob("tune_t_knob", lo=1, hi=16, scale="log2")
    s2 = params.declare_knob("tune_t_knob", lo=2, hi=999)
    assert s1 is s2 and s2.hi == 16         # first declaration wins
    assert "tune_t_knob" in declared_space(["tune_t_knob"])
    with pytest.raises(KeyError):
        declared_space(["definitely_not_declared"])


def test_overrides_scoped_and_atomic():
    params.register("tune_t_ov", 3, "test")
    with params.overrides({"tune_t_ov": 7}):
        assert params.get("tune_t_ov") == 7
        assert params.lookup("tune_t_ov").source == "set"
    assert params.get("tune_t_ov") == 3
    assert params.lookup("tune_t_ov").source == "default"
    # an unregistered name fails BEFORE anything is applied
    with pytest.raises(KeyError):
        with params.overrides({"tune_t_ov": 9, "tune_t_missing": 1}):
            pass
    assert params.get("tune_t_ov") == 3


def test_runtime_report_carries_knob_vector(param):
    from parsec_tpu.prof.flight_recorder import runtime_report
    params.register("tune_t_rep", 5, "test")
    params.declare_knob("tune_t_rep", lo=1, hi=8)
    param("tune_t_rep", 6)
    rep = runtime_report()
    kn = rep["knobs"]
    assert kn["tune_t_rep"] == 6            # non-default value resolved
    snap = params.snapshot()
    for name in params.knob_space():        # every declared knob rides
        if name in snap:
            assert name in kn, name


# ---------------------------------------------------------------------------
# structural signatures (tune/signature.py over ptg/lowering.py)
# ---------------------------------------------------------------------------

def _gemm_pool(n=12, nb=4, seed=0, tag="x"):
    from parsec_tpu.data_dist.matrix import TiledMatrix
    from parsec_tpu.models.tiled_gemm import tiled_gemm_ptg
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((n, n)).astype(np.float32)
    A = TiledMatrix.from_dense(f"A{tag}", a, nb, nb)
    B = TiledMatrix.from_dense(f"B{tag}", a.T.copy(), nb, nb)
    C = TiledMatrix.from_dense(f"C{tag}", np.zeros((n, n), np.float32),
                               nb, nb)
    return tiled_gemm_ptg(A, B, C)


def test_equal_structure_equal_signature_equal_db_key():
    """The property test: two separately built pools with the same
    structure (different data, different collection names) sign
    identically, so their tuning-DB keys collide — which is the point."""
    s1 = workload_signature(_gemm_pool(seed=0, tag="p"))
    s2 = workload_signature(_gemm_pool(seed=9, tag="q"))
    assert s1 == s2
    be = ["0.4.30", "cpu", ""]
    assert tunedb_mod.make_key(s1, backend=be) == \
        tunedb_mod.make_key(s2, backend=be)


def test_backend_change_different_key_same_signature():
    """Backend is the key's second column, NOT part of the signature: a
    vector tuned on TPU must never apply on CPU, but the structural
    identity survives the port."""
    s = workload_signature(_gemm_pool())
    k_cpu = tunedb_mod.make_key(s, backend=["0.4.30", "cpu", ""])
    k_tpu = tunedb_mod.make_key(s, backend=["0.4.30", "tpu", "v5e"])
    assert k_cpu != k_tpu
    assert json.loads(k_cpu)["sig"] == json.loads(k_tpu)["sig"]


def test_different_structure_different_signature():
    assert workload_signature(_gemm_pool(n=12, nb=4)) != \
        workload_signature(_gemm_pool(n=16, nb=4))
    # explicit size hint separates size classes of one structure
    tp = _gemm_pool()
    assert workload_signature(tp, size_hint=512) != \
        workload_signature(tp, size_hint=8192)


# ---------------------------------------------------------------------------
# the tuning DB (tune/db.py)
# ---------------------------------------------------------------------------

def test_tunedb_best_direction_per_objective(tmp_path):
    db = TuneDB(str(tmp_path / "t.jsonl"))
    be = ["j", "cpu", ""]
    db.note("s", {"k": 1}, 10.0, objective="tokens_per_s", backend=be)
    db.note("s", {"k": 2}, 90.0, objective="tokens_per_s", backend=be)
    db.note("s", {"k": 3}, 5.0, objective="tok_latency_ms", backend=be)
    db.note("s", {"k": 4}, 1.0, objective="tok_latency_ms", backend=be)
    assert db.best("s", objective="tokens_per_s",
                   backend=be)["knobs"] == {"k": 2}
    assert db.best("s", objective="tok_latency_ms",
                   backend=be)["knobs"] == {"k": 4}
    assert db.best("s", objective="wall_s", backend=be) is None
    with pytest.raises(ValueError):
        db.note("s", {"k": 5}, float("nan"))


def test_tunedb_cross_process_roundtrip(tmp_path):
    """A vector noted here is the `best` answer in a fresh interpreter,
    and a vector the CHILD appends is visible to the parent's CACHED
    consult path (the (mtime_ns, size) generation moved)."""
    path = str(tmp_path / "tunedb.jsonl")
    be = ["j", "cpu", ""]
    TuneDB(path).note("wl:x", {"nb": 128, "sched": "spq"}, 1.25,
                      objective="wall_s", backend=be)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    code = (
        "import json\n"
        "from parsec_tpu.tune.db import TuneDB\n"
        f"db = TuneDB({path!r})\n"
        f"rec = db.best('wl:x', objective='wall_s', backend={be!r})\n"
        "print(json.dumps(rec['knobs']))\n"
        f"db.note('wl:x', {{'nb': 256}}, 0.5, objective='wall_s',"
        f" backend={be!r})\n")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         cwd=str(REPO), capture_output=True, text=True,
                         timeout=120)
    assert out.returncode == 0, out.stderr
    assert json.loads(out.stdout.strip()) == {"nb": 128, "sched": "spq"}
    rec = tunedb_mod.cached_db(path).best("wl:x", objective="wall_s",
                                          backend=be)
    assert rec["knobs"] == {"nb": 256}      # 0.5 < 1.25: wall_s is lower


def test_tunedb_tolerates_torn_tail(tmp_path):
    path = str(tmp_path / "t.jsonl")
    db = TuneDB(path)
    db.note("s", {"k": 1}, 1.0, backend=["j", "cpu", ""])
    with open(path, "a") as f:
        f.write('{"key": "torn half-line')
    rec = TuneDB(path).best("s", backend=["j", "cpu", ""])
    assert rec is not None and rec["knobs"] == {"k": 1}


# ---------------------------------------------------------------------------
# ambient consult + apply (tune/__init__.py)
# ---------------------------------------------------------------------------

def test_consult_ambient_filters_to_declared_domain(tmp_path, param):
    path = str(tmp_path / "tunedb.jsonl")
    param("tune_db_path", path)
    params.register("tune_t_consult", 2, "test")
    params.declare_knob("tune_t_consult", lo=1, hi=8)
    TuneDB(path).note(ambient_signature("t_gate"),
                      {"tune_t_consult": 4, "undeclared_thing": 9}, 1.0)
    TuneDB(path).note(ambient_signature("t_oob"),
                      {"tune_t_consult": 99}, 1.0)
    assert consult_ambient("t_gate") == {"tune_t_consult": 4}
    assert consult_ambient("t_oob") is None     # out-of-domain dropped
    param("tune_db", False)
    assert consult_ambient("t_gate") is None    # the gate


def test_apply_ambient_respects_operator_pins(tmp_path, param):
    path = str(tmp_path / "tunedb.jsonl")
    param("tune_db_path", path)
    params.register("tune_t_apply", 2, "test")
    params.declare_knob("tune_t_apply", lo=1, hi=8)
    TuneDB(path).note(ambient_signature("t_apply"),
                      {"tune_t_apply": 8}, 1.0)
    p = params.lookup("tune_t_apply")
    src = p.source
    p.source = "env"                    # simulate an operator env pin
    try:
        assert apply_ambient("t_apply") is None
        assert params.get("tune_t_apply") == 2
    finally:
        p.source = src
    assert apply_ambient("t_apply") == {"tune_t_apply": 8}
    assert params.get("tune_t_apply") == 8
    params.set("tune_t_apply", 2)


# ---------------------------------------------------------------------------
# the search (tune/search.py)
# ---------------------------------------------------------------------------

def test_search_prunes_known_bad_points_from_ledger(tmp_path, param):
    """The perfdb EWMA seeds the search: a vector whose recorded
    history is far worse than the incumbent never spends a trial."""
    from parsec_tpu.prof import perfdb as perfdb_mod
    param("perfdb", True)
    param("perfdb_path", str(tmp_path / "perfdb.jsonl"))
    perf = perfdb_mod.PerfDB()
    space = {"x": KnobSpec(name="x", lo=1, hi=4, step=1.0)}
    sig = "t:prune"
    # known-bad history for x=2 (the only neighbor of the start point)
    bad_key = perfdb_mod.make_key(f"tune.{sig}", "cost_s",
                                  knobs={"x": 2})
    for _ in range(4):
        perf.append(bad_key, 1000.0, run="tune")
    ran: list = []

    def fn(knobs):
        ran.append(dict(knobs))
        return 1.0

    out = search(fn, signature=sig, space=space, budget=8, restarts=1,
                 objective="cost_s", start={"x": 1},
                 db=TuneDB(str(tmp_path / "t.jsonl")), persist=False)
    assert out["pruned"] >= 1, out
    assert {"x": 2} not in ran              # never re-measured
    assert out["best"] == {"x": 1}


def test_search_persists_winner_and_reseeds_from_it(tmp_path, param):
    param("perfdb", False)
    db = TuneDB(str(tmp_path / "t.jsonl"))
    space = {"x": KnobSpec(name="x", lo=1, hi=16, scale="log2")}
    cost = {1: 9.0, 2: 5.0, 4: 2.0, 8: 1.0, 16: 3.0}
    out = search(lambda k: cost[k["x"]], signature="t:seed", space=space,
                 budget=10, restarts=1, objective="cost_s",
                 start={"x": 1}, db=db)
    assert out["best"] == {"x": 8} and out["best_score"] == 1.0
    assert db.best("t:seed", objective="cost_s")["knobs"] == {"x": 8}
    # a later budget-1 search starts FROM the persisted winner
    out2 = search(lambda k: cost[k["x"]], signature="t:seed",
                  space=space, budget=1, restarts=1, objective="cost_s",
                  db=db)
    assert out2["trials"][0]["knobs"] == {"x": 8}


# ---------------------------------------------------------------------------
# the adaptive controller (tune/adaptive.py)
# ---------------------------------------------------------------------------

def _drive(c: KnobController, cost: dict, n: int) -> None:
    for _ in range(n):
        c.observe(cost[c.value])
    while c._probing is not None:           # settle any probe in flight
        c.observe(cost[c.value])


def test_controller_probes_and_adopts_better_value():
    c = KnobController("k", default=4, lo=1, hi=16, probe_every=4,
                       probe_len=2)
    cost = {1: 40.0, 2: 20.0, 4: 10.0, 8: 5.0, 16: 2.0}
    _drive(c, cost, 200)
    assert c._incumbent == 16 and c.adoptions >= 2, c.stats()
    wb = c.take_writeback()
    assert wb == 16
    assert c.take_writeback() is None       # exactly once per adoption


def test_controller_hysteresis_rejects_noise():
    c = KnobController("k", default=4, lo=1, hi=16, probe_every=4,
                       probe_len=2)
    for i in range(300):                    # flat objective, 5% wobble
        c.observe(10.0 + 0.5 * (i % 2))
    while c._probing is not None:
        c.observe(10.0)
    assert c.adoptions == 0 and c._incumbent == 4, c.stats()


def test_controller_garbage_objective_falls_back_bounded():
    """The acceptance property: a garbage objective (non-finite /
    non-positive) kills adaptation within GARBAGE_LIMIT probes and the
    knob returns to its default — and stays there."""
    c = KnobController("k", default=8, lo=1, hi=32, probe_every=4,
                       probe_len=2)
    c.observe(5.0)                          # healthy first sample
    seen = 0
    for x in [float("nan"), float("inf"), -1.0, 0.0] * 4:
        c.observe(x)
        seen += 1
        if c.dead:
            break
    assert c.dead and seen <= GARBAGE_LIMIT, (seen, c.stats())
    assert c.value == 8
    assert c.observe(123.0) == 8            # dead stays pinned to default
    assert c.converged


def test_adaptive_writeback_persists_tenant_vector(tmp_path, param):
    from parsec_tpu.tune import adaptive
    path = str(tmp_path / "t.jsonl")
    param("tune_db_path", path)
    adaptive.writeback("acme", 16, 3.2)
    rec = TuneDB(path).best(ambient_signature("tenant:acme"),
                            objective="tok_latency_ms")
    assert rec["knobs"] == {"llm_steps_per_pool": 16}
    assert rec["source"] == "adaptive"


# ---------------------------------------------------------------------------
# the closed loop, end to end (acceptance)
# ---------------------------------------------------------------------------

def test_closed_loop_decode_search_persist_context_pickup(tmp_path,
                                                          param):
    """Seeded-bad ``llm_steps_per_pool=1`` on a small decode workload:
    ``tune.search`` recovers a deeper superpool within 5 trials, the
    winner lands in tunedb.jsonl under the workload signature AND the
    ambient context tag, and a FRESH Context applies it at start."""
    import parsec_tpu.llm.batcher  # noqa: F401 — registers the knob
    from parsec_tpu.runtime import Context
    from parsec_tpu.serve import RuntimeServer
    path = str(tmp_path / "tunedb.jsonl")
    param("tune_db_path", path)
    param("perfdb", False)
    param("llm_steps_per_pool", 1)          # the seeded-bad vector
    db = TuneDB(path)

    def decode(_knobs):
        # scored by what the knob buys, as a count: decode pools submitted
        # for the 24 tokens (a clock here chose by the host's load)
        with RuntimeServer(nb_cores=2) as srv:
            ts = [srv.submit_stream([3, 7, 11], max_new_tokens=12)
                  for _ in range(2)]
            for t in ts:
                t.result(timeout=120)
            return float(srv.stats()["llm"]["decode_submits"])

    out = search(decode, signature="wl:test:decode",
                 space=declared_space(["llm_steps_per_pool"]), budget=5,
                 restarts=1, objective="cost_s",
                 start={"llm_steps_per_pool": 1}, db=db,
                 ambient_tag="context")
    assert out["evals"] <= 5
    assert out["best"]["llm_steps_per_pool"] >= 2, out
    assert db.best("wl:test:decode", objective="cost_s") is not None
    # the override was scoped: the live param still holds the bad seed
    assert params.get("llm_steps_per_pool") == 1
    # a fresh Context consults ambient:context and applies the winner
    ctx = Context(nb_cores=0)
    try:
        assert ctx.tuned_knobs is not None
        assert ctx.tuned_knobs.get("llm_steps_per_pool", 0) >= 2
        assert params.get("llm_steps_per_pool") == \
            ctx.tuned_knobs["llm_steps_per_pool"]
    finally:
        ctx.fini()


def test_adaptive_oracle_equal_and_server_pickup(tmp_path, param):
    """Live adaptation must move BATCHING, never tokens: the adaptive
    run's streams are token-for-token equal to the default run's, while
    the per-tenant controller is live and seeded from the tuning DB."""
    from parsec_tpu.serve import RuntimeServer
    path = str(tmp_path / "tunedb.jsonl")
    param("tune_db_path", path)
    prompts = [[3, 7, 11, 5], [1, 40, 8]]

    def run():
        with RuntimeServer(nb_cores=2) as srv:
            ts = [srv.submit_stream(p, max_new_tokens=16, tenant="acme")
                  for p in prompts]
            toks = [t.result(timeout=120)["tokens"] for t in ts]
            return toks, (srv._llm._k_seed.get("acme"),
                          srv._llm._k_ctl.get("acme"))

    param("tune_adaptive", False)
    oracle, (seed0, ctl0) = run()
    assert seed0 is None and ctl0 is None   # plane fully dormant when off
    # a persisted per-tenant vector the next server must pick up
    TuneDB(path).note(ambient_signature("tenant:acme"),
                      {"llm_steps_per_pool": 2}, 1.0,
                      objective="tok_latency_ms", source="adaptive")
    param("tune_adaptive", True)
    adapted, (seed, ctl) = run()
    assert seed == 2                        # DB -> server -> batcher seed
    assert ctl is not None and ctl.value >= 1
    assert adapted == oracle                # oracle-equal token-for-token


# ---------------------------------------------------------------------------
# the consult path's reads, and the tile-size acceptance on a device pool
# ---------------------------------------------------------------------------

def test_db_is_parsed_once_for_200_consults(tmp_path, param, monkeypatch):
    """A tuning-DB consult sits on Context start and on the first submit
    of every tenant: over a store of 200 signatures the cached,
    generation-checked path opens the file once for 500 look-ups (and the
    search harness, its ledger off, runs exactly its budget of trials)."""
    import builtins
    param("perfdb", False)
    db = TuneDB(str(tmp_path / "tunedb.jsonl"))
    space = {"a": KnobSpec(name="a", lo=1, hi=1 << 20, scale="log2"),
             "b": KnobSpec(name="b", values=("x", "y", "z"))}
    res = search(lambda _k: 1.0, signature="test:noop", space=space,
                 budget=16, restarts=4, objective="cost_s", seed=3, db=db,
                 persist=False)
    assert 1 <= res["evals"] <= 16
    for i in range(200):
        db.note(f"wl:mb:{i}", {"a": i + 1}, float(i + 1), objective="wall_s")
    reads = []

    def counting_open(path, mode="r", *a, **kw):
        if str(path) == db.path and "r" in mode:
            reads.append(path)
        return builtins.open(path, mode, *a, **kw)

    monkeypatch.setattr(tunedb_mod, "open", counting_open, raising=False)
    for i in range(500):
        rec = tunedb_mod.cached_db(db.path).best(f"wl:mb:{i % 200}",
                                                 objective="wall_s")
        assert rec["knobs"] == {"a": i % 200 + 1}
    assert len(tunedb_mod.cached_db(db.path).records()) == 200
    assert len(reads) == 1


def test_tuned_cholesky_recovers_seeded_bad_tile(accel_device, param,
                                                 tmp_path):
    """The ISSUE-18 acceptance: handed a deliberately mis-tiled dynamic
    Cholesky (nb far too small, dispatch-bound: 120 tasks where 10 or 1
    do), the search moves the knob off the seed within its trial budget
    and leaves the winner in tunedb.jsonl, and the winner's factor is
    still a Cholesky factor.  A trial is scored by what the tile size
    costs in dispatches, the accelerator's XLA calls for the solve: a
    count, where a clock chose by the host's load."""
    from parsec_tpu.data_dist.matrix import SymTwoDimBlockCyclic
    from parsec_tpu.models.cholesky import make_spd, tiled_cholesky_ptg
    from parsec_tpu.runtime import Context
    param("tune_db_path", str(tmp_path / "tunedb.jsonl"))
    param("perfdb", False)
    n, nb_bad = 256, 32
    a = make_spd(n)

    def solve(nb):
        A = SymTwoDimBlockCyclic.from_dense("A", a, nb, nb)
        ctx = Context(nb_cores=0)
        calls = accel_device.xla_calls
        try:
            ctx.add_taskpool(tiled_cholesky_ptg(A, devices="tpu"))
            ctx.wait(timeout=60)
            return float(accel_device.xla_calls - calls), A
        finally:
            ctx.fini(timeout=30)

    sig = workload_signature(tiled_cholesky_ptg(
        SymTwoDimBlockCyclic.from_dense("A", a, nb_bad, nb_bad),
        devices="tpu"), size_hint=n)
    out = search(lambda knobs: solve(int(knobs.get("nb", nb_bad)))[0],
                 signature=sig,
                 space={"nb": KnobSpec(name="nb", lo=32, hi=n // 2,
                                       scale="log2")},
                 budget=4, restarts=1, objective="cost_s", seed=0,
                 start={"nb": nb_bad})
    best = int(out["best"]["nb"])
    assert best != nb_bad and out["evals"] <= 4, out
    assert TuneDB(out["db_path"]).best(sig, objective="cost_s")["knobs"] \
        == {"nb": best}
    _, A = solve(best)
    got = np.asarray(A.data_of(0, 0).newest_copy().value)
    expect = np.linalg.cholesky(a[:best, :best].astype(np.float64))
    assert float(np.max(np.abs(np.tril(got) - expect))) <= 1e-3
