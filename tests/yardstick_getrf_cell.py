"""Two tests of the benchmark's own files stopped holding when PR 42 gave
``startup.fresh_compiles_at_setup`` the list of the seven cells accepted
before it (a new cell that reports ``setup_s`` has to be listed on the
metric, or the metric given the accepted cells' list; its reader finds
nothing new on a cell whose set-up compiles what no earlier cell did): a
PR that adds a cell appends, and may not edit a file the benchmark has.

- ``benchmarks/tests/test_yardstick.py::test_rehearsal_correct_unless_broken``
  asserts that every cell's rehearsal reports that metric; here it is
  asserted of the cells the metric lists, and everything else as it was.
- ``benchmarks/tests/test_dtd_gemm.py::
  test_manifest_still_lists_the_64k_cell_third_on_the_dynamic_lists`` reads
  every list that starts with the two 16k cells as a list of the dynamic
  cells; the metric's list starts so and holds ``gemm16k.lowered`` third.

Tier-1's collector (``test_benchmark_yardstick.py``) takes these in the
others' places, so every case still counts (PERF.md, section 7)."""

import json
import os
import subprocess
import sys

import pytest

from yardstick_writeback_early_share import BENCH, ROOT, _load

_y = _load(os.path.join(BENCH, "tests", "test_yardstick.py"))
_d = _load(os.path.join(BENCH, "tests", "test_dtd_gemm.py"))
START_UP = "startup.fresh_compiles_at_setup"


def _listed_on_start_up() -> list:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (m,) = [m for m in manifest["per_layer"] if m["name"] == START_UP]
    return m.get("workloads", [w["name"] for w in manifest["workloads"]])


@pytest.mark.parametrize("cell,fault", _y.CASES)
def test_rehearsal_correct_unless_broken_start_up_where_listed(cell, fault):
    """``test_rehearsal_correct_unless_broken``, the start-up metric asked
    of the cells it lists."""
    code = f"""
import json, os, sys
os.environ["PARSEC_MCA_device_tpu_allow_cpu"] = "1"
sys.path[:0] = [{BENCH!r}, {ROOT!r}]
{_y.FAULTS[fault]}
import run
out = run.run_cell(["--workload", {cell!r}, "--seed", "2147483659",
                    "--seconds", "1", "--trace", "1", "--rehearse"])
print("RESULT " + json.dumps({{"correct": out["correct"],
                              "compared": out["compared"],
                              "metrics": sorted(out["metrics"])}}))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT ")]
    assert lines, proc.stderr[-3000:]
    out = json.loads(lines[-1][7:])
    assert out["correct"] is (fault == "none"), proc.stderr[-3000:]
    if fault == "no_writeback":
        assert out["compared"]["tiles_absent"]["value"] > 0
    # on the CPU no device metric is reported, the counted ones are
    assert (START_UP in out["metrics"]) is (cell in _listed_on_start_up())
    assert not [m for m in out["metrics"] if "roofline" in m or "idle" in m]


def test_manifest_still_lists_the_64k_cell_third_on_the_dynamic_lists_only():
    """The 64k cell follows the two 16k cells on the rate and on every
    per-layer metric of the dynamic cells but the five pinned ones; the
    start-up metric's list is every path's cells, not the dynamic ones."""
    manifest, per_layer = _d._manifest()
    (rate,) = [m for m in manifest["end_to_end"]
               if m["name"] == "dynamic.gflops"]
    assert rate["workloads"][2] == _d.CELL_64K
    for name, m in per_layer.items():
        if name != START_UP and \
                m.get("workloads", [])[:2] == [_d.TWIN, "potrf16k.dynamic"]:
            assert (m["workloads"][2:3] == [_d.CELL_64K]) is \
                (name not in _d.PHASE_METRICS), name
    assert per_layer[START_UP]["workloads"][2] == "gemm16k.lowered"
