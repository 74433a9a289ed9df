"""The five per-layer metrics that read the program's phase table (PR 27):
a traced rehearsal of each dynamic cell reports all five, finite and not
negative; the lowered cell, which runs no scheduler and no device module,
none.  No chip needed.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests/test_phase_metrics.py -q
"""

import json
import math
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

PHASE_METRICS = {
    "sched.flood_release_us_per_task", "devmod.writeback_ms_per_solve",
    "devmod.device_wait_share", "ctx.lifecycle_ms_per_solve",
    "host.unowned_share.dynamic"}


def _rehearse(cell: str) -> dict:
    code = f"""
import json, sys
sys.path[:0] = [{BENCH!r}, {ROOT!r}]
import run
out = run.run_cell(["--workload", {cell!r}, "--seed", "2147483677",
                    "--seconds", "1", "--trace", "1", "--rehearse"])
print("RESULT " + json.dumps({{"correct": out["correct"],
                              "metrics": out["metrics"]}}))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT ")]
    assert lines, proc.stderr[-3000:]
    out = json.loads(lines[-1][7:])
    assert out["correct"], proc.stderr[-3000:]
    return out["metrics"]


def test_manifest_lists_the_phase_metrics_on_the_dynamic_cells_only():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in PHASE_METRICS:
        m = per_layer[name]
        assert m["source"] == "program_span"
        assert m["moves"] == "dynamic.gflops"
        assert m["workloads"] == ["gemm16k.dynamic", "potrf16k.dynamic"]


@pytest.mark.parametrize("cell", ["gemm16k.dynamic", "potrf16k.dynamic"])
def test_a_traced_dynamic_rehearsal_reports_the_five(cell):
    metrics = _rehearse(cell)
    assert PHASE_METRICS <= set(metrics), sorted(metrics)
    for name in PHASE_METRICS:
        value = metrics[name]["value"]
        assert math.isfinite(value) and value >= 0.0, (name, value)
    # shares of a whole
    assert metrics["host.unowned_share.dynamic"]["value"] <= 100.0
    assert metrics["devmod.device_wait_share"]["value"] <= 100.0


def test_the_lowered_cell_reports_none_of_them():
    assert not PHASE_METRICS & set(_rehearse("gemm16k.lowered"))
