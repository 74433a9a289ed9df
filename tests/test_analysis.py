"""The static-analysis gate: graphcheck over every shipped graph,
runtimelint over the package source, and mutation tests proving the
checker's detection power (a verifier that cannot catch seeded bugs
proves nothing — the ptgpp-error-case suite analog, SURVEY §4).

Runs in tier-1 (no `slow` marker): the graphs are small and the lint is
one AST pass over ~100 files.
"""

import os
import pathlib
import textwrap

import numpy as np
import pytest

from parsec_tpu.analysis import (GraphCheckError, check_dtd, check_jdf,
                                 check_ptg, check_taskpool, lint_file,
                                 lint_self)
from parsec_tpu.analysis.__main__ import _model_graphs, main as cli_main
from parsec_tpu.data.data import ACCESS_READ
from parsec_tpu.data.datatype import TileType
from parsec_tpu.data_dist.collection import DictCollection
from parsec_tpu.data_dist.matrix import SymTwoDimBlockCyclic
from parsec_tpu.models.cholesky import tiled_cholesky_ptg
from parsec_tpu.runtime.task import Dep

REPO = pathlib.Path(__file__).parent.parent

pytestmark = pytest.mark.analysis


def _cholesky(nt: int = 5, P: int = 1, Q: int = 1):
    A = SymTwoDimBlockCyclic("A", nt * 16, nt * 16, 16, 16, P=P, Q=Q)
    return tiled_cholesky_ptg(A, devices="cpu")


# ---------------------------------------------------------------------------
# every shipped graph verifies clean
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name,tp", list(_model_graphs(5)),
                         ids=lambda v: v if isinstance(v, str) else "")
def test_models_verify_clean(name, tp):
    report = check_ptg(tp)
    assert report.ok, (name, report.findings)
    assert report.ntasks > 0


def test_cholesky_multirank_verifies():
    report = check_ptg(_cholesky(5, P=2, Q=2), nb_ranks=4)
    assert report.ok, report.findings


def test_jdf_examples_verify():
    def dc(name):
        return DictCollection(name, dtt=TileType((4,), np.float32),
                              init_fn=lambda *k: np.zeros(4, np.float32))

    for j in ["Ex05_Broadcast.jdf", "Ex06_RAW.jdf", "Ex07_RAW_CTL.jdf"]:
        r = check_jdf(str(REPO / "examples" / "jdf" / j),
                      mydata=dc("mydata"), nodes=3)
        assert r.ok, (j, r.findings)


def test_raw_vs_ctl_hazard_distinction():
    """Ex06 (deliberately unordered RAW fan-out) draws the shared-write
    hazard warning; Ex07 — the same graph with CTL ordering — is silent.
    The checker reproduces the examples' own documentation."""
    def dc(name):
        return DictCollection(name, dtt=TileType((4,), np.float32),
                              init_fn=lambda *k: np.zeros(4, np.float32))

    raw = check_jdf(str(REPO / "examples/jdf/Ex06_RAW.jdf"),
                    mydata=dc("mydata"), nodes=3)
    ctl = check_jdf(str(REPO / "examples/jdf/Ex07_RAW_CTL.jdf"),
                    mydata=dc("mydata"), nodes=3)
    assert any(f.code == "unordered-shared-write" for f in raw.warnings)
    assert not any(f.code == "unordered-shared-write" for f in ctl.findings)


# ---------------------------------------------------------------------------
# detection power: seeded mutations of a known-good graph
# ---------------------------------------------------------------------------


def test_detects_dropped_input_edge():
    """Mutation class 1 (missing edge): drop GEMM's A input (the TRSM.C
    fan-out target) — the producer's range arrow now lands nowhere."""
    tp = _cholesky()
    fA = next(f for f in tp.task_class("GEMM").flows if f.name == "A")
    fA.deps_in.clear()
    report = check_ptg(tp)
    hits = [f for f in report.errors if f.code == "missing-input-edge"]
    assert hits, report.findings
    # provenance: the finding names the PRODUCER side of the broken edge
    assert hits[0].task_class == "TRSM" and hits[0].flow == "C"
    assert "GEMM" in hits[0].message
    assert hits[0].instance is not None     # concrete locals attached


def test_detects_dropped_output_edge():
    """The symmetric half: drop POTRF's range arrow to TRSM — consumers
    now wait on a producer that never sends."""
    tp = _cholesky()
    fT = next(f for f in tp.task_class("POTRF").flows if f.name == "T")
    fT.deps_out = [d for d in fT.deps_out if d.target_class != "TRSM"]
    report = check_ptg(tp)
    hits = [f for f in report.errors if f.code == "missing-output-edge"]
    assert hits, report.findings
    assert hits[0].task_class == "TRSM" and hits[0].flow == "T"


def test_detects_rw_flipped_to_read():
    """Mutation class 2 (access mismatch): GEMM's accumulation chain
    declared READ — consumers would receive the un-accumulated tile."""
    tp = _cholesky()
    next(f for f in tp.task_class("GEMM").flows
         if f.name == "C").access = ACCESS_READ
    report = check_ptg(tp)
    hits = [f for f in report.errors
            if f.code == "read-chain-never-written"]
    assert hits, report.findings
    assert hits[0].task_class == "GEMM" and hits[0].flow == "C"


def test_detects_out_of_range_tile():
    """Mutation class 3: POTRF's affinity maps outside the tile grid."""
    tp = _cholesky()
    po = tp.task_class("POTRF")
    orig = po.affinity
    po.affinity = lambda l: (orig(l)[0], (l["k"], l["k"] + 99))
    report = check_ptg(tp)
    hits = [f for f in report.errors if f.code == "tile-out-of-range"]
    assert hits, report.findings
    assert hits[0].task_class == "POTRF"
    assert hits[0].instance == {"k": 0}


def test_detects_cycle():
    """Mutation class 4: a backward edge closes a 2-cycle in the GEMM
    k-chain."""
    tp = _cholesky(5)
    fC = next(f for f in tp.task_class("GEMM").flows if f.name == "C")
    fC.deps_out.append(Dep(
        target_class="GEMM", target_flow="C",
        target_params=lambda l: {"m": l["m"], "n": l["n"], "k": l["k"] - 1},
        guard=lambda l: l["k"] > 0))
    report = check_ptg(tp)
    hits = [f for f in report.errors if f.code == "dependency-cycle"]
    assert hits, report.findings
    assert hits[0].task_class == "GEMM"
    assert "GEMM" in hits[0].message and "->" in hits[0].message


def test_detects_unbound_global():
    """Probe evaluation surfaces an unbound name in an edge function as a
    typed finding, not a worker-thread AttributeError."""
    from parsec_tpu import ptg
    p = ptg.PTGBuilder("bad", NB=4)
    t = p.task("T", i=ptg.span(0, lambda g, l: g.NB - 1))
    f = t.flow("V", ptg.RW)
    f.input(null=True)
    f.output(succ=("T", "V", lambda g, l: {"i": l.i + g.TYPO}),
             guard=lambda g, l: l.i < g.NB - 1)
    t.body(lambda es, task, g, l: None)
    report = check_ptg(p.build())
    hits = [f for f in report.errors if f.code == "edge-eval-error"]
    assert hits and hits[0].task_class == "T"
    assert "TYPO" in hits[0].message


def test_detects_no_startup():
    """A pool whose every instance waits on a predecessor can never
    start — the classic guard-typo hang, caught before enqueue."""
    from parsec_tpu import ptg
    p = ptg.PTGBuilder("stuck", NB=3)
    t = p.task("T", i=ptg.span(0, lambda g, l: g.NB - 1))
    f = t.flow("V", ptg.RW)
    f.input(pred=("T", "V", lambda g, l: {"i": (l.i - 1) % g.NB}))
    f.output(succ=("T", "V", lambda g, l: {"i": (l.i + 1) % g.NB}))
    t.body(lambda es, task, g, l: None)
    report = check_ptg(p.build())
    codes = {f.code for f in report.errors}
    assert "no-startup-task" in codes
    assert "dependency-cycle" in codes      # the ring is also a cycle


def test_truncated_enumeration_stays_clean():
    """A pool larger than the instance cap verifies a truncated prefix
    without crashing and without false dangling-edge errors (the cap's
    documented contract — membership checks are unreliable mid-prefix)."""
    report = check_ptg(_cholesky(5), max_tasks=3)
    assert report.truncated
    assert report.ok, report.findings
    assert "truncated" in report.summary()


def test_the_64x64_cholesky_dag_is_verified_whole():
    """The DAG of ``potrf-64k`` (BENCHMARK.json): 64 + 4,032 + 4,032 + 41,664
    = 45,760 tasks stay under ``analysis_max_tasks``, so the gate sees every
    one and not a prefix."""
    report = check_ptg(_cholesky(64))
    assert report.ntasks == 45760 and not report.truncated
    assert report.ok, report.findings


def test_gate_mode_raises_typed_error():
    tp = _cholesky()
    next(f for f in tp.task_class("GEMM").flows
         if f.name == "A").deps_in.clear()
    with pytest.raises(GraphCheckError) as ei:
        check_taskpool(tp, raise_on_error=True)
    assert ei.value.findings
    assert "missing-input-edge" in str(ei.value)


# ---------------------------------------------------------------------------
# the enqueue-time hook (MCA analysis_check=1)
# ---------------------------------------------------------------------------


def test_enqueue_hook_rejects_and_leaves_context_clean(param):
    from parsec_tpu.runtime import Context
    param("analysis_check", 1)
    bad = _cholesky()
    next(f for f in bad.task_class("GEMM").flows
         if f.name == "A").deps_in.clear()
    ctx = Context(nb_cores=0)
    try:
        with pytest.raises(GraphCheckError):
            ctx.add_taskpool(bad)
        assert ctx.test()           # no half-enqueued pool left behind
        from parsec_tpu.models.cholesky import make_spd
        A = SymTwoDimBlockCyclic.from_dense("A", make_spd(48), 16, 16)
        good = tiled_cholesky_ptg(A, devices="cpu")
        ctx.add_taskpool(good)      # the context still works
        ctx.wait(timeout=60)
    finally:
        ctx.abort()


def test_ptg_validate_seam():
    assert _cholesky().validate().ok


# ---------------------------------------------------------------------------
# DTD prong
# ---------------------------------------------------------------------------


def test_dtd_validate(param):
    from parsec_tpu.dtd import INOUT, INPUT, DTDTaskpool
    from parsec_tpu.runtime import Context
    ctx = Context(nb_cores=0)
    try:
        tp = DTDTaskpool("dtd_ok")
        ctx.add_taskpool(tp)
        # a declared (closed) key space: tile (5,) is constructible — the
        # store is lazy — but lies outside the declared bounds, the shape
        # a bad tile_of key takes in practice
        dc = DictCollection("D", dtt=TileType((4,), np.float32),
                            init_fn=lambda *k: np.zeros(4, np.float32),
                            keys=[(0,), (1,)])
        t0 = tp.tile_of(dc, 0)
        t1 = tp.tile_of(dc, 1)
        tp.insert_task(lambda a, c: None, (t0, INPUT), (t1, INOUT),
                       name="ok")
        assert tp.validate().ok
        bad = tp.tile_of(dc, 5)
        tp.insert_task(lambda a: None, (bad, INOUT), name="oob")
        report = check_dtd(tp)
        assert any(f.code == "tile-out-of-range" for f in report.errors)
        tp.close()   # analysis_check is off: close() does not re-validate
        ctx.wait(timeout=60)
    finally:
        ctx.abort()


# ---------------------------------------------------------------------------
# runtimelint
# ---------------------------------------------------------------------------


def test_self_lint_is_green():
    """The concurrency/hygiene lint over parsec_tpu/ holds with an EMPTY
    allowlist: zero errors AND zero warnings (ISSUE 5 acceptance)."""
    report = lint_self()
    assert report.nfiles > 80
    assert not report.findings, [repr(f) for f in report.findings]


def _lint_src(tmp_path, src):
    p = tmp_path / "probe.py"
    p.write_text(textwrap.dedent(src))
    return lint_file(str(p))


def test_lint_unlocked_mutation(tmp_path):
    out = _lint_src(tmp_path, """
        import threading
        _LOCK_PROTECTED = {"Box._items": "_lock"}
        class Box:
            def __init__(self):
                self._items = []          # construction: exempt
                self._lock = threading.Lock()
            def good(self):
                with self._lock:
                    self._items.append(1)
            def bad(self):
                self._items.append(1)
            def waived(self):
                self._items.clear()       # lint: unlocked-ok
            def helper(self):  # lint: holds(_lock)
                self._items.pop()
        """)
    assert [f.code for f in out] == ["unlocked-mutation"]
    assert out[0].line == 12


def test_lint_mutating_call_with_result(tmp_path):
    """Pop-with-result (`v = self.x.pop()`) and call-argument mutations
    are mutations too — the dominant idiom in the runtime itself."""
    out = _lint_src(tmp_path, """
        _LOCK_PROTECTED = {"Box._items": "_lock"}
        class Box:
            def bad_assign(self):
                v = self._items.pop()
                return v
            def bad_nested(self, f):
                return f(self._items.pop(0))
            def good(self):
                with self._lock:
                    return self._items.pop()
        """)
    assert [f.code for f in out] == ["unlocked-mutation"] * 2
    assert [f.line for f in out] == [5, 8]


def test_lint_multi_item_with_order(tmp_path):
    """`with a, b:` acquires in order — an inversion on one line is the
    same deadlock shape as lexical nesting."""
    out = _lint_src(tmp_path, """
        _LOCK_ORDER = ("_outer", "_inner")
        class Box:
            def ok(self):
                with self._outer, self._inner:
                    pass
            def inverted(self):
                with self._inner, self._outer:
                    pass
        """)
    assert [f.code for f in out] == ["lock-order"]


def test_lint_condition_alias(tmp_path):
    out = _lint_src(tmp_path, """
        _LOCK_PROTECTED = {"Box._n": "_lock"}
        _LOCK_ALIASES = {"_cond": "_lock"}
        class Box:
            def ok(self):
                with self._cond:
                    self._n += 1
        """)
    assert not out


def test_lint_lock_order(tmp_path):
    out = _lint_src(tmp_path, """
        _LOCK_ORDER = ("_outer", "_inner")
        class Box:
            def ok(self):
                with self._outer:
                    with self._inner:
                        pass
            def inverted(self):
                with self._inner:
                    with self._outer:
                        pass
        """)
    assert [f.code for f in out] == ["lock-order"]


def test_lint_hygiene(tmp_path):
    out = _lint_src(tmp_path, """
        import pickle
        import os          # never used

        def f(b):
            try:
                return pickle.loads(b)
            except:
                pass
        """)
    codes = sorted(f.code for f in out)
    assert codes == ["bare-except", "bare-pickle-loads", "unused-import"]


def test_lint_quoted_annotation_not_flagged(tmp_path):
    out = _lint_src(tmp_path, """
        from typing import Sequence

        def f(x) -> "Sequence[int]":
            return [x]
        """)
    assert not out


# ---------------------------------------------------------------------------
# CLI + iterators_checker fold
# ---------------------------------------------------------------------------


def test_cli_single_model(capsys):
    assert cli_main(["--graph", "cholesky", "--nt", "4"]) == 0
    assert "graphcheck cholesky: OK" in capsys.readouterr().out


def test_cli_self_lint(capsys):
    assert cli_main(["--self-lint"]) == 0
    assert "runtimelint: OK" in capsys.readouterr().out


def test_iterators_checker_reexport():
    """The dynamic (PINS) successor checker folded into the analysis
    namespace: one entry point for both static and runtime checks."""
    from parsec_tpu import analysis
    from parsec_tpu.prof import iterators_checker
    assert analysis.check_task is iterators_checker.check_task
    assert analysis.IteratorsCheckerError \
        is iterators_checker.IteratorsCheckerError


def test_ruff_clean():
    """Style stage of scripts/check.sh promoted into tier-1 (ISSUE 20):
    ruff must be clean over the whole tree when it is installed; skipped
    (not failed) where the toolchain image lacks it — check.sh prints
    the same skip."""
    import subprocess
    import sys
    probe = subprocess.run([sys.executable, "-m", "ruff", "--version"],
                           capture_output=True)
    if probe.returncode != 0:
        pytest.skip("ruff not installed in this environment")
    root = pathlib.Path(__file__).resolve().parents[1]
    out = subprocess.run(
        [sys.executable, "-m", "ruff", "check",
         "parsec_tpu", "tests", "examples"],
        capture_output=True, text=True, cwd=root)
    assert out.returncode == 0, out.stdout + out.stderr
