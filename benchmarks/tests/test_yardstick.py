"""Guards of the yardstick itself; none needs a chip.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q

- the manifest: every name in BENCHMARK.json leads to its files, every
  per-layer metric moves a metric each of its cells reports;
- the trace reduction against a small trace recorded on the chip;
- the control (the reference with bfloat16 storage) comes out not correct,
  three times over every limit;
- a rehearsal of every cell comes out correct, and comes out not correct with
  the timed path broken underneath: a result tile altered where it is written,
  the write-back to the host left out.
"""

import gzip
import json
import os
import re
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CELLS = [w["name"] for w in MANIFEST["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_manifest():
    import harness
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert "setup_s" in e2e
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", CELLS)) <= set(CELLS), m
    for name in CELLS:
        cell = harness.Cell(name)     # config, traffic, limits, path: all load
        assert NAME.match(name) and len(cell.entry["why"]) <= 200
        for key in ("source", "reduced", "assumed", "deployment", "algorithm"):
            assert key in cell.config, (name, key)
        assert os.path.exists(os.path.join(
            BENCH, "problems", cell.config["algorithm"] + ".py"))
        reported = {m["name"] for m in cell.metrics("end_to_end")}
        assert "setup_s" in reported and len(reported) >= 2, name
        layers = cell.metrics("per_layer")
        assert layers, name
        for m in layers:
            assert m["moves"] in reported, (name, m["name"])
            assert callable(harness.load_module("layer_metrics",
                                                m["name"]).read)


def test_trace_reduction_on_a_recorded_trace():
    """``tests/data/trace_small.json.gz``: the first solve and a half of a
    traced ``gemm16k.dynamic`` run on a TPU v5 lite, cut by hand; the numbers
    below were worked out from its events one by one."""
    import trace_reduce
    with gzip.open(os.path.join(HERE, "data", "trace_small.json.gz"),
                   "rt") as f:
        rec = json.load(f)
    out = trace_reduce.reduce(rec["trace"], set(rec["span_names"]), 1)
    for key, want in rec["expected"].items():
        assert out[key] == pytest.approx(want, rel=1e-9), key
    assert out["busy_s"] <= out["program_s"] <= out["window_s"]
    gaps = sum(v for _, v in out["idle_gaps"])
    assert gaps <= out["window_s"] - out["busy_s"] + 1e-9
    # and on a trace built by hand: two ops of 2 and 3 us inside one program
    # of 6 us, in an 10 us span; the gap in the middle lies in "wait"
    tiny = {"devices": [{"name": "/device:TPU:0",
                         "ops": [["%a.1 = x", 1000, 2000],
                                 ["%b.2 = y", 4000, 3000]],
                         "modules": [["jit_f(7)", 1000, 6000]]}],
            "host": [{"name": "main", "events": [
                ["wait", 0, 10000], ["Inner(f)", 2500, 1200]]}]}
    out = trace_reduce.reduce(tiny, {"wait"}, 1)
    assert out["window_s"] == pytest.approx(10e-6)
    assert out["busy_s"] == pytest.approx(5e-6)
    assert out["program_s"] == pytest.approx(6e-6)
    assert out["launches"] == 1
    assert dict(map(tuple, out["device_ops"])) == pytest.approx(
        {"jit_f/a": 2e-6, "jit_f/b": 3e-6})
    assert dict(map(tuple, out["idle_gaps"])) == pytest.approx(
        {"wait": 4e-6, "wait/Inner": 1e-6})
    assert trace_reduce.reduce({"devices": [], "host": tiny["host"]},
                               {"wait"}, 1) is None


@pytest.mark.parametrize("name", ["gemm16k.dynamic", "potrf16k.dynamic"])
def test_control_reads_above_the_limit(name):
    import control
    import harness
    cell = harness.Cell(name)
    cell.config.update(harness.REHEARSAL_SIZES)
    limits = [c.limits["probe_gap"]["limit"] for c in map(harness.Cell, CELLS)
              if c.config["algorithm"] == cell.config["algorithm"]]
    compared = control.control_compared(cell, seed=11)
    assert not harness.verdict(compared)
    assert compared["probe_gap"]["value"] > 3 * max(limits)


FAULTS = {
    "none": "",
    # a result tile altered where the device module writes it back
    "tile_altered": """
from parsec_tpu.data.data import COHERENCY_EXCLUSIVE, COHERENCY_OWNED
from parsec_tpu.device.tpu import TPUDevice
_wb = TPUDevice._writeback
def _writeback(self, copy):
    if copy.coherency in (COHERENCY_OWNED, COHERENCY_EXCLUSIVE) \
            and copy.original.key[-2:] == (1, 0):
        copy.value = copy.value * 0
    return _wb(self, copy)
TPUDevice._writeback = _writeback
""",
    # the state left unchanged where the user reads it: the flush that brings
    # the result tiles back to the host does nothing, they stay on the device
    "no_writeback": """
from parsec_tpu.device.tpu import TPUDevice
TPUDevice.flush_cache = lambda self: None
""",
    # the lowered program's output altered where it is produced
    "store_altered": """
from parsec_tpu.ptg.lowering import LoweredTaskpool
_j = LoweredTaskpool.jitted
class _Altered:
    def __init__(self, f):
        self.f, self.lower = f, f.lower
    def __call__(self, st):
        out = dict(self.f(st))
        out["C"] = out["C"].at[:128, :128].set(0.0)
        return out
def jitted(self):
    return _Altered(_j(self))
LoweredTaskpool.jitted = jitted
""",
}
CASES = [(c, "none") for c in CELLS] + [
    ("gemm16k.dynamic", "tile_altered"), ("potrf16k.dynamic", "tile_altered"),
    ("gemm16k.dynamic", "no_writeback"), ("potrf16k.dynamic", "no_writeback"),
    ("gemm16k.lowered", "store_altered")]


@pytest.mark.parametrize("cell,fault", CASES)
def test_rehearsal_correct_unless_broken(cell, fault):
    """Skips the look for a chip (``--rehearse``) and drives the rest of a
    run, traced, in a process of its own."""
    code = f"""
import json, os, sys
os.environ["PARSEC_MCA_device_tpu_allow_cpu"] = "1"
sys.path[:0] = [{BENCH!r}, {ROOT!r}]
{FAULTS[fault]}
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
    assert "startup.fresh_compiles_at_setup" in out["metrics"]
    assert not [m for m in out["metrics"] if "roofline" in m or "idle" in m]
