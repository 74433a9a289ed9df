"""Guards of what PR 36 added to the yardstick (configuration ``geqrf-32k``,
cell ``geqrf32k.dynamic``, the plain reference of the tile QR, two per-layer
metrics); none needs a chip.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests/test_geqrf32k.py -q

- the configuration's file is the source uncut (``reduced`` empty) with its
  departures under ``assumed``;
- the manifest's entries stand where they were appended, by position and as
  subsets, so that a later PR can append after them;
- the bfloat16-storage control comes out not correct, and with f32 storage
  it is a sound run;
- a traced rehearsal, sound, reports the two new counters' metric and every
  solve reduced; with three planted faults ``correct`` is false each time: the
  reflector products' operands rounded to bfloat16 (``probe_gap``), one T
  tile's write-back left out (``tiles_absent``), TSQRT writing only its last
  written flow (``probe_gap``);
- the two new readers on hand-made tables read nothing where the counter or
  the class is absent.
"""

import json
import os
import subprocess
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]

CELL, CONFIG = "geqrf32k.dynamic", "geqrf-32k"
ROOFLINE, PANEL = "kernel.tsmqr_roofline", "devmod.panel_tasks_per_xla_call"
# the lists that held the three PTG dynamic cells, and the 64k cell's four
LISTED = {"devmod.tasks_per_xla_call", "devmod.h2d_gb_per_solve",
          "harness.between_solves_share.dynamic", "kernel.dynamic_roofline",
          "device.idle_share.dynamic", "devmod.writeback_early_share",
          "sched.flood_us_per_task", "sched.flood_putbacks_per_task",
          "sched.release_us_per_task", "sched.release_planned_share",
          "devmod.stage_in_ms_per_solve", "devmod.hbm_peak_share",
          "devmod.inflight_held_gb", "devmod.pressure_ms_per_solve",
          "devmod.evicted_gb_per_solve", "startup.fresh_compiles_at_setup"}


def _reader(name):
    import harness
    return harness.load_module("layer_metrics", name)


def test_the_qr_configuration_is_the_source_uncut():
    import harness
    cell = harness.Cell(CELL)
    cfg = cell.config
    assert (cfg["N"], cfg["nb"], cfg["dtype"]) == (32768, 1024, "float32")
    assert cfg["reduced"] == [] and cfg["matmul_precision"] == "highest"
    assert cfg["architecture"] is None
    nt = cfg["N"] // cfg["nb"]
    assert cfg["tasks"] == 11440 == nt + nt * (nt - 1) \
        + (nt - 1) * nt * (2 * nt - 1) // 6
    assert cfg["flops"] == "4N^3/3"
    assert cfg["task_classes"] == ["GEQRT", "UNMQR", "TSQRT", "TSMQR"]
    assert {"N", "nb", "ib", "dense_triangular_products", "precision", "data",
            "T", "reference", "host_memory"} <= set(cfg["assumed"])
    assert (cell.chips, cell.traffic["path"]) == (1, "dynamic")
    assert set(cell.limits) == {"probe_gap", "tasks_off", "tiles_absent"}
    # the problem's own counts, at a size that costs nothing
    small = harness.load_module("problems", cfg["algorithm"]).Problem(
        {"N": 4 * 32, "nb": 32}, seed=1)
    assert small.tasks == 4 + 6 + 6 + 14 and small.result_tiles == 16 + 10
    assert sum(small.class_flops.values()) == pytest.approx(small.flops)
    assert small.min_bytes == (2 * 16 + 10) * 32 * 32 * 4


def test_manifest_lists_the_qr_cell_where_it_was_appended():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    assert [w["name"] for w in manifest["workloads"]].index(CELL) == 5
    cell = manifest["workloads"][5]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "dynamic_host_tiles", 1)
    conf = manifest["configs"][4]
    assert (conf["name"], conf["reduced"]) == (CONFIG, [])
    with open(os.path.join(ROOT, conf["file"])) as f:
        assert json.load(f)["source"] == conf["source"]
    assert not [w for w in manifest["workloads"] if w["chips"] != 1]
    (rate,) = [m for m in manifest["end_to_end"]
               if m["name"] == "dynamic.gflops"]
    assert rate["workloads"][4] == CELL and rate["bound"] == 0.05
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    listed = {n for n, m in per_layer.items()
              if CELL in m.get("workloads", [CELL])}
    # at least these: a later PR may list the cell on a metric it adds
    assert LISTED | {ROOFLINE, PANEL} <= listed
    for name in (ROOFLINE, PANEL):
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["moves"] == "dynamic.gflops"
    assert (per_layer[ROOFLINE]["unit"], per_layer[ROOFLINE]["source"],
            per_layer[ROOFLINE]["layer"]) == ("%", "device_trace", "kernels")
    assert (per_layer[PANEL]["source"], per_layer[PANEL]["layer"]) == \
        ("program_counter", "device module")
    # where they were appended; what a later PR appends comes after them
    names = [m["name"] for m in manifest["per_layer"]]
    assert (names.index(ROOFLINE), names.index(PANEL)) == (28, 29)


def test_the_qr_control_reads_above_the_limit():
    import control
    import harness
    cell = harness.Cell(CELL)
    cell.config.update(harness.REHEARSAL_SIZES)
    compared = control.control_compared(cell, seed=11)
    assert not harness.verdict(compared)
    assert compared["probe_gap"]["value"] > \
        3 * cell.limits["probe_gap"]["limit"]


def test_the_qr_control_differs_from_a_sound_run_by_its_storage_alone():
    """With f32 tiles the control is the sound algorithm, so its bfloat16
    reading is the storage's and nothing else's (on the chip its general
    inverse was not: PERF.md, PR 36)."""
    import harness
    import reference_qr as refq
    cell = harness.Cell(CELL)
    cell.config.update(harness.REHEARSAL_SIZES)
    prob = cell.problem(11)
    prob.reference()
    gap = prob.gap(prob.reduce(*refq.qr_control(
        prob.tiles, prob.nb, store="float32")))
    assert gap < cell.limits["probe_gap"]["limit"] / 3


FAULTS = {
    "none": "",
    # the operands of every product of the four kernels rounded to bfloat16:
    # what the TPU's default precision does to an f32 product
    "bf16_products": """
import jax, jax.numpy as jnp
from parsec_tpu.models import qr
def _dot(a, b):
    a, b = (x.astype(jnp.bfloat16).astype(jnp.float32) for x in (a, b))
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)
qr._dot = _dot
""",
    # one tile of T stays on the device at the flush
    "t_tile_not_written_back": """
from parsec_tpu.device.tpu import TPUDevice
_wb = TPUDevice._writeback
def _writeback(self, copy):
    if copy.original.key == ("T", 5, 2):
        return
    return _wb(self, copy)
TPUDevice._writeback = _writeback
""",
    # TSQRT's bodies, per task and fused, write the last written flow only
    # (T), as models/lu.py's per-task body did before this PR
    "tsqrt_writes_its_last_flow_only": """
from parsec_tpu.device.kernels import register_kernel
from parsec_tpu.device.tpu import TPUDevice
from parsec_tpu.models import qr
def _body(es, task, device):
    out = qr._tsqrt_traceable(*(c.value for c in task.data))
    task.data[2].value = out[2]
    task.data[2].version += 1
    return out[2]
register_kernel("qr_tsqrt", "tpu", _body)
_rv = TPUDevice._run_vmapped
def _run_vmapped(self, batch):
    if batch[0].task.task_class.name == "TSQRT":
        return False
    return _rv(self, batch)
TPUDevice._run_vmapped = _run_vmapped
""",
}


def _rehearse(fault: str) -> dict:
    code = f"""
import json, os, sys
os.environ["PARSEC_MCA_device_tpu_allow_cpu"] = "1"
sys.path[:0] = [{BENCH!r}, {ROOT!r}]
{FAULTS[fault]}
import run
out = run.run_cell(["--workload", {CELL!r}, "--seed", "2147483733",
                    "--seconds", "1", "--trace", "1", "--rehearse"])
from parsec_tpu.device import registry
(dev,) = [d for d in registry.devices if d.type != "cpu"]
print("RESULT " + json.dumps({{"correct": out["correct"],
                              "compared": out["compared"],
                              "metrics": out["metrics"],
                              "state": dev.debug_state()}}))
"""
    # one accelerator, as the cell has (test_potrf64k.py says why)
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="")
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT ")]
    assert lines, proc.stderr[-3000:]
    return json.loads(lines[-1][7:])


def test_a_traced_qr_rehearsal_is_correct_and_reports_the_panel_s_batches():
    out = _rehearse("none")
    assert out["correct"], out["compared"]
    # probe_gap_pick too where the window outlasted the picked solve: a
    # rehearsal's second beside five other workers may not
    assert {"probe_gap", "tasks_off", "tiles_absent"} <= set(out["compared"])
    metrics = out["metrics"]
    # on the CPU no device metric is reported; the counted ones are, and
    # whatever a later PR lists the cell on
    assert LISTED - {"kernel.dynamic_roofline", "device.idle_share.dynamic",
                     "devmod.hbm_peak_share"} | {PANEL} <= set(metrics)
    assert ROOFLINE not in metrics
    assert metrics[PANEL]["unit"] == "tasks/call"
    assert 1.0 <= metrics[PANEL]["value"] <= 8.0
    # 8 x 8 tiles of 128: A's 64 tiles and T's 36 staged once, no more
    assert metrics["devmod.h2d_gb_per_solve"]["value"] == pytest.approx(
        100 * 128 * 128 * 4 / 1e9)
    assert metrics["devmod.writeback_early_share"]["value"] == 100.0
    assert metrics["sched.release_planned_share"]["value"] == 100.0
    assert metrics["sched.flood_putbacks_per_task"]["value"] == 0.0
    assert metrics["devmod.evicted_gb_per_solve"]["value"] == 0.0
    state = out["state"]
    tasks, calls = state["tasks_by_class"], state["calls_by_class"]
    assert set(tasks) == set(calls) == {"GEQRT", "UNMQR", "TSQRT", "TSMQR"}
    solves = state["executed_tasks"] // 204
    assert tasks == {"GEQRT": 8 * solves, "UNMQR": 28 * solves,
                     "TSQRT": 28 * solves, "TSMQR": 140 * solves}
    assert sum(calls.values()) == state["xla_calls"]
    assert metrics[PANEL]["value"] == pytest.approx(
        (tasks["GEQRT"] + tasks["TSQRT"])
        / (calls["GEQRT"] + calls["TSQRT"]))


@pytest.mark.parametrize("fault,number", [
    ("bf16_products", "probe_gap"),
    ("t_tile_not_written_back", "tiles_absent"),
    ("tsqrt_writes_its_last_flow_only", "probe_gap")])
def test_planted_qr_fault_reads_not_correct(fault, number):
    out = _rehearse(fault)
    assert out["correct"] is False
    compared = out["compared"]
    assert compared[number]["value"] > compared[number]["limit"], compared
    assert compared["tasks_off"]["value"] == 0
    if number == "probe_gap":
        assert compared["tiles_absent"]["value"] == 0


def _run(device_ops, solves=9, class_flops=None, peaks=True, busy_s=40.0):
    flops = {"TSMQR": 10416 * 4 * 1024.0 ** 3} if class_flops is None \
        else class_flops
    return {"trace": device_ops and {"device_ops": device_ops,
                                     "busy_s": busy_s},
            "peaks": {"flops_per_s": 197e12} if peaks else None,
            "problem": types.SimpleNamespace(class_flops=flops),
            "window": types.SimpleNamespace(solves=solves),
            "cell": types.SimpleNamespace(chips=1)}


OPS = [["jit_fused_qr_tsmqr/convolution_fusion", 20.0],
       ["jit_fused_qr_tsqrt/while", 9.0],
       ["jit_fused_qr_tsmqr/slice_bitcast_fusion", 4.0],
       ["jit_qr_tsmqr/convolution_fusion", 1.0],
       ["jit_fused_gemm/convolution_add_fusion", 3.0]]
# a full list: ten kept, so operations of the class may have been dropped
FULL = OPS + [[f"jit_fused_qr_unmqr/fusion.{i}", 0.5] for i in range(5)]


@pytest.mark.parametrize("run,expect", [
    (_run(None), None),                         # an untraced run
    (_run(OPS, peaks=False), None),             # a rehearsal: no peaks
    (_run(OPS, class_flops={}), None),          # another problem
    (_run(OPS[1:2] + OPS[4:]), None),           # none of the class's among ten
    (_run(OPS, solves=0), None),
    # ten kept of 39.5 s, 41.0 s busy: 1.5 s unseen is over 5% of the 25 s
    (_run(FULL, busy_s=41.0), None),
    # 1.0 s unseen is not: the share is overstated by 4% of itself at most
    (_run(FULL, busy_s=40.5),
     100.0 * 9 * 10416 * 4 * 1024.0 ** 3 / 197e12 / 25.0),
    # 9 solves x 10,416 tasks x 4 nb^3 at 197 TFLOP/s over 25 s
    (_run(OPS), 100.0 * 9 * 10416 * 4 * 1024.0 ** 3 / 197e12 / 25.0)])
def test_tsmqr_roofline_reader_sums_the_class_s_operations(run, expect):
    got = _reader(ROOFLINE).read(run)
    assert got == (expect if expect is None else pytest.approx(expect))
    if got is not None:
        assert got < 100.0 * 4 / 36            # three dense f32 products


def _dev(**kw):
    return types.SimpleNamespace(type="tpu", **kw)


@pytest.mark.parametrize("devices,expect", [
    # the parent of PR 36: accelerators without the counters
    ([_dev(executed_tasks=816)], None),
    # no panel class ran: another graph
    ([_dev(tasks_by_class={"GEMM": 4096}, calls_by_class={"GEMM": 64})],
     None),
    # summed over the accelerators; the host's device does not count
    ([types.SimpleNamespace(type="cpu", tasks_by_class={"GEQRT": 9},
                            calls_by_class={"GEQRT": 9}),
      _dev(tasks_by_class={"GEQRT": 32, "TSQRT": 496, "TSMQR": 10416},
           calls_by_class={"GEQRT": 32, "TSQRT": 200, "TSMQR": 400}),
      _dev(tasks_by_class={"TSQRT": 4}, calls_by_class={"TSQRT": 2})],
     532 / 234)])
def test_panel_batch_reader_over_the_registry(monkeypatch, devices, expect):
    from parsec_tpu.device import registry
    monkeypatch.setattr(registry, "devices", devices)
    got = _reader(PANEL).read({})
    assert got == (expect if expect is None else pytest.approx(expect))
