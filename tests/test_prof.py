"""Observability tier: trace well-formedness, converters, grapher, counters.

Mirrors the reference's profiling tests (SURVEY §4.7): run a taskpool with
tracing on, validate event well-formedness (check-async.py analog), read
the binary dump back, convert to pandas; DOT grapher and SDE counters.
"""

import os

import numpy as np
import pytest

from parsec_tpu.core.mca import repository
from parsec_tpu.data_dist.matrix import TiledMatrix
from parsec_tpu.prof.counters import (TASKS_ENABLED, TASKS_RETIRED,
                                      properties, sde)
from parsec_tpu.prof.profiling import Profiling, profiling
from parsec_tpu.runtime import Context


def _run_small_gemm(nb_cores=2):
    from parsec_tpu.models.tiled_gemm import tiled_gemm_ptg
    rng = np.random.default_rng(0)
    n, nb = 32, 16
    A = rng.standard_normal((n, n)).astype(np.float32)
    B = rng.standard_normal((n, n)).astype(np.float32)
    dA = TiledMatrix.from_dense("A", A, nb, nb)
    dB = TiledMatrix.from_dense("B", B, nb, nb)
    dC = TiledMatrix.from_dense("C", np.zeros((n, n), np.float32), nb, nb)
    ctx = Context(nb_cores=nb_cores)
    ctx.add_taskpool(tiled_gemm_ptg(dA, dB, dC, devices="cpu"))
    ctx.wait(timeout=60)
    ctx.fini()
    np.testing.assert_allclose(dC.to_dense(), A @ B, rtol=1e-4, atol=1e-4)


@pytest.fixture
def traced():
    profiling.init()
    comp = repository.find("pins", "task_profiler")
    mod = comp.open()
    yield profiling
    comp.close(mod)
    profiling.fini()


def test_trace_well_formed_and_converts(tmp_path, traced):
    _run_small_gemm()
    assert traced.validate() == []
    recs = traced.to_records()
    execs = [r for r in recs if r["name"] == "task_exec"]
    assert len(execs) == 8, len(execs)   # 2x2x2 GEMM tasks
    for r in execs:
        assert r["duration_ns"] > 0
        assert r["info.task"] == "GEMM"
    # the four phases nest sanely: prepare <= exec window exists per task
    names = {r["name"] for r in recs}
    assert {"task_exec", "task_prepare_input", "task_release_deps",
            "task_complete"} <= names

    # binary round-trip (dbp dump + pbt2ptt analog)
    path = str(tmp_path / "trace.ptpb")
    traced.dump(path)
    back = Profiling.load(path)
    assert back.validate() == []
    assert len(back.to_records()) == len(recs)
    df = back.to_pandas()
    assert len(df) == len(recs)
    assert (df[df["name"] == "task_exec"]["duration_ns"] > 0).all()
    # info values round-trip with their types, not as repr strings
    assert (df[df["name"] == "task_exec"]["info.task"] == "GEMM").all()


def test_lowered_execute_span(traced):
    """One span per compiled (lowered) taskpool execution."""
    from parsec_tpu.data_dist.matrix import TiledMatrix
    from parsec_tpu.models.tiled_gemm import tiled_gemm_ptg
    from parsec_tpu.ptg.lowering import lower_taskpool

    rng = np.random.default_rng(1)
    a = rng.standard_normal((8, 8)).astype(np.float32)
    A = TiledMatrix.from_dense("A", a, 4, 4)
    B = TiledMatrix.from_dense("B", a.copy(), 4, 4)
    C = TiledMatrix.from_dense("C", np.zeros((8, 8), np.float32), 4, 4)
    low = lower_taskpool(tiled_gemm_ptg(A, B, C))
    low.execute()
    low.execute()
    recs = [r for r in traced.to_records() if r["name"] == "lowered_execute"]
    assert len(recs) == 2
    assert all(r["info.mode"] == low.mode for r in recs)
    assert all(r["duration_ns"] > 0 for r in recs)


def test_standalone_profiling(tmp_path):
    """The sp-demo shape: trace without any runtime."""
    p = Profiling()
    p.init()
    k1, k2 = p.add_dictionary_keyword("phase", "#ff0000", ("step",))
    for i in range(5):
        p.trace(k1, event_id=i, info={"step": i})
        p.trace(k2, event_id=i)
    assert p.validate() == []
    recs = p.to_records()
    assert len(recs) == 5
    assert recs[0]["info.step"] == 0


def test_grapher_dot(tmp_path):
    comp = repository.find("pins", "grapher")
    mod = comp.open()
    try:
        _run_small_gemm(nb_cores=0)
    finally:
        comp.close(mod)
    path = str(tmp_path / "dag.dot")
    mod.write_dot(path)
    text = open(path).read()
    assert text.startswith("digraph")
    assert '"GEMM_0_0_0"' in text
    # the k-chain edge GEMM(0,0,0) -> GEMM(0,0,1) must be realized
    assert '"GEMM_0_0_0" -> "GEMM_0_0_1"' in text
    assert text.count("->") >= 4


def test_sde_counters():
    comp = repository.find("pins", "sde")
    mod = comp.open()
    sde.reset()
    try:
        _run_small_gemm(nb_cores=0)
    finally:
        comp.close(mod)
    snap = sde.snapshot()
    assert snap[TASKS_RETIRED] >= 8
    assert snap[TASKS_ENABLED] >= 1


def test_properties_dictionary(tmp_path):
    vals = {"x": 1}
    properties.register("test", "x", lambda: vals["x"])
    try:
        snap = properties.snapshot()
        assert snap["test"]["x"] == 1
        vals["x"] = 7
        stop = properties.stream_to(str(tmp_path / "live.json"),
                                    interval=0.05)
        import json
        import time
        time.sleep(0.15)
        stop()
        data = json.load(open(tmp_path / "live.json"))
        assert data["props"]["test"]["x"] == 7
    finally:
        properties.unregister("test", "x")


def test_chrome_trace_export(tmp_path, traced):
    """The standard-viewer export (profiling_otf2.c role): trace-event
    JSON consumable by Perfetto / chrome://tracing."""
    import json

    _run_small_gemm()
    path = str(tmp_path / "trace.json")
    trace = traced.to_chrome_trace(path)
    on_disk = json.load(open(path))
    assert on_disk == json.loads(json.dumps(trace))
    evs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert len(evs) == len(traced.to_records())
    execs = [e for e in evs if e["name"] == "task_exec"]
    assert len(execs) == 8
    for e in execs:
        assert e["dur"] > 0
        assert e["args"]["task"] == "GEMM"
    metas = [e for e in trace["traceEvents"] if e["ph"] == "M"]
    assert len(metas) >= 1
    assert {m["tid"] for m in metas} >= {e["tid"] for e in evs}
