"""Guards of what PR 42 added to the yardstick (configuration ``getrf-44k``,
cell ``getrf44k.dynamic``, the plain reference of LU with partial pivoting,
three per-layer metrics); none needs a chip.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests/test_getrf44k.py -q

- the configuration's file is the source with N cut (``reduced`` == ["N"]),
  its task counts and its FLOPs by class, which sum to 2N^3/3;
- the manifest's entries stand where they were appended;
- a traced rehearsal, sound, is correct and reports the swap classes'
  batches; with four planted faults ``correct`` is false each time: the
  pivot search confined to the diagonal tile (max |l|, through
  ``probe_gap``), one column's swaps of one step skipped (``probe_gap``),
  one IPIV tile's write-back left out (``tiles_absent``), every product's
  operands rounded to bfloat16 (``probe_gap``);
- the three new readers on hand-made tables read nothing where the classes
  or the counters are absent.
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

CELL, CONFIG = "getrf44k.dynamic", "getrf-44k"
PANEL_ROOF, SWAP_ROOF = "kernel.lu_panel_roofline", "kernel.lu_swap_roofline"
SWAPS = "devmod.swap_tasks_per_xla_call"
NEW = (PANEL_ROOF, SWAP_ROOF, SWAPS)
# the shared lists the cell was appended to (those that hold the 32k QR
# cell, less the QR's own two)
LISTED = {"devmod.tasks_per_xla_call", "devmod.h2d_gb_per_solve",
          "harness.between_solves_share.dynamic", "kernel.dynamic_roofline",
          "device.idle_share.dynamic", "devmod.writeback_early_share",
          "devmod.hbm_peak_share", "devmod.inflight_held_gb",
          "devmod.pressure_ms_per_solve", "devmod.evicted_gb_per_solve",
          "sched.flood_us_per_task", "sched.flood_putbacks_per_task",
          "sched.release_us_per_task", "sched.release_planned_share",
          "devmod.stage_in_ms_per_solve", "devmod.call_us_per_result",
          "devmod.dispatch_own_us_per_task", "devmod.chip_queue_depth",
          "devmod.held_already_run_share", "devmod.donated_result_share"}
ACCEPTED = ["gemm16k.dynamic", "potrf16k.dynamic", "gemm16k.lowered",
            "potrf64k.dynamic", "gemm16k.dtd", "geqrf32k.dynamic",
            "geqrf52k.ctx4"]


def _reader(name):
    import harness
    return harness.load_module("layer_metrics", name)


def test_the_getrf_configuration_is_the_source_with_n_cut():
    import harness
    cell = harness.Cell(CELL)
    cfg = cell.config
    assert (cfg["N"], cfg["nb"], cfg["dtype"]) == (45056, 1024, "float32")
    assert cfg["reduced"] == ["N"] and cfg["matmul_precision"] == "highest"
    assert cfg["source"].startswith("DPLASMA") and len(cfg["source"]) <= 200
    assert "-N 45056 -t 1024" in cfg["source"]
    nt = cfg["N"] // cfg["nb"]
    assert cfg["tasks"] == 29370 == nt + nt * (nt - 1) \
        + (nt - 1) * nt * (2 * nt - 1) // 6
    assert cfg["flops"] == "2N^3/3"
    assert cfg["task_classes"] == ["PANEL", "SWPTRSM", "GEMM", "SWPLEFT"]
    assert {"N", "program", "left_swaps", "ipiv", "buckets", "precision",
            "data", "reference", "host_memory"} <= set(cfg["assumed"])
    assert (cell.chips, cell.traffic["path"]) == (1, "dynamic")
    assert set(cell.limits) == {"probe_gap", "tasks_off", "tiles_absent"}
    assert all(cell.limits[k]["limit"] == 0
               for k in ("tasks_off", "tiles_absent"))
    # the problem's own counts at the cell's size: no data is made for them
    prob = harness.load_module("problems", cfg["algorithm"]).Problem
    small = prob.__new__(prob)
    small.n, small.nb, small.nt = cfg["N"], cfg["nb"], nt
    flops = {c: sum(small.task_flops(c, k) * n for k, n in small.steps(c))
             for c in cfg["task_classes"]}
    assert sum(flops.values()) == pytest.approx(2 * cfg["N"] ** 3 / 3,
                                                rel=1e-12)
    assert {c: sum(n for _, n in small.steps(c))
            for c in cfg["task_classes"]} == \
        {"PANEL": 44, "SWPTRSM": 946, "GEMM": 27434, "SWPLEFT": 946}


def test_the_getrf_problem_counts_agree_at_a_small_size():
    import harness
    cfg = harness.Cell(CELL).config
    small = harness.load_module("problems", cfg["algorithm"]).Problem(
        {"N": 4 * 32, "nb": 32}, seed=1)
    assert small.tasks == 4 + 6 + 14 + 6 and small.result_tiles == 16 + 4
    assert sum(small.class_flops.values()) == pytest.approx(small.flops)
    # a panel reads and writes its column; a swap at most nb row pairs
    assert small.class_bytes["PANEL"] == 2 * (4 + 3 + 2 + 1) * 32 * 32 * 4
    assert small.class_bytes["SWPLEFT"] == 6 * 16 * 32 * 32


def test_manifest_lists_the_getrf_cell_where_it_was_appended():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    assert [w["name"] for w in manifest["workloads"]].index(CELL) == 7
    cell = manifest["workloads"][7]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "dynamic_host_tiles", 1)
    conf = manifest["configs"][6]
    assert (conf["name"], conf["reduced"]) == (CONFIG, ["N"])
    with open(os.path.join(ROOT, conf["file"])) as f:
        assert json.load(f)["source"] == conf["source"]
    (rate,) = [m for m in manifest["end_to_end"]
               if m["name"] == "dynamic.gflops"]
    assert rate["workloads"][6] == CELL and rate["bound"] == 0.05
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    assert per_layer["startup.fresh_compiles_at_setup"]["workloads"] == \
        ACCEPTED
    listed = {n for n, m in per_layer.items()
              if CELL in m.get("workloads", [CELL])}
    assert LISTED | set(NEW) <= listed
    assert not {"kernel.tsmqr_roofline", "devmod.panel_tasks_per_xla_call",
                "startup.fresh_compiles_at_setup"} & listed
    for name in LISTED:
        assert per_layer[name]["workloads"][-1] == CELL, name
    for name in NEW:
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["moves"] == "dynamic.gflops"
    for name in (PANEL_ROOF, SWAP_ROOF):
        assert (per_layer[name]["unit"], per_layer[name]["source"],
                per_layer[name]["layer"]) == ("%", "device_trace", "kernels")
    assert (per_layer[SWAPS]["source"], per_layer[SWAPS]["layer"]) == \
        ("program_counter", "device module")
    names = [m["name"] for m in manifest["per_layer"]]
    assert [names.index(n) for n in NEW] == [41, 42, 43]


FAULTS = {
    "none": "",
    # incremental pivoting's GETRF: the pivot search inside the diagonal
    # tile alone, the tiles below by U_kk^-1
    "tile_local_pivoting": """
import jax, jax.numpy as jnp
from parsec_tpu.models import lu
from parsec_tpu.ptg.lowering import register_traceable
@lu._highest
def panel(p, *rows):
    nb = rows[0].shape[0]
    top, piv, perm = jax.lax.linalg.lu(rows[0])
    u = jnp.triu(top)
    below = [jax.scipy.linalg.solve_triangular(u.T, r.T, lower=True).T
             for r in rows[1:]]
    none = jnp.full((nb,), -1, jnp.int32)
    plan = jnp.stack([p[0, 0] + piv, perm, none, none]).astype(jnp.int32)
    return (plan, top) + tuple(below)
register_traceable("getrf_panel", panel)
""",
    # SWPTRSM(1, 3) handed a plan of no swap: column 3 keeps step 1's rows
    "one_column_unswapped": """
import types
import jax, numpy as np
from parsec_tpu.device.tpu import TPUDevice
_rv = TPUDevice._run_vmapped
def _run_vmapped(self, batch):
    for d in batch:
        t = d.task
        if t.task_class.name == "SWPTRSM" and (t.locals["k"], t.locals["n"]) == (1, 3):
            nb = t.flow_data("P").value.shape[1]
            plan = np.full((4, nb), -1, np.int32)
            plan[0] = nb + np.arange(nb)
            plan[1] = np.arange(nb)
            t.set_flow_data("P", types.SimpleNamespace(
                value=jax.device_put(plan, self.jax_device)))
    return _rv(self, batch)
TPUDevice._run_vmapped = _run_vmapped
""",
    # one tile of IPIV stays on the device at the flush
    "ipiv_tile_not_written_back": """
from parsec_tpu.device.tpu import TPUDevice
_wb = TPUDevice._writeback
def _writeback(self, copy):
    if copy.original.key == ("IPIV", 0, 2):
        return
    return _wb(self, copy)
TPUDevice._writeback = _writeback
""",
    # the operands of every product rounded to bfloat16: what the TPU's
    # default precision does to an f32 product
    "bf16_products": """
import jax.numpy as jnp
import jax._src.lax.lax as lax_
_dg = lax_.dot_general
def dot_general(lhs, rhs, *a, **k):
    lhs, rhs = (x.astype(jnp.bfloat16).astype(x.dtype) for x in (lhs, rhs))
    return _dg(lhs, rhs, *a, **k)
lax_.dot_general = dot_general
""",
}


def _rehearse(fault: str) -> dict:
    code = f"""
import json, os, sys
os.environ["PARSEC_MCA_device_tpu_allow_cpu"] = "1"
sys.path[:0] = [{BENCH!r}, {ROOT!r}]
{FAULTS[fault]}
import run
out = run.run_cell(["--workload", {CELL!r}, "--seed", "2147483742",
                    "--seconds", "1", "--trace", "1", "--rehearse"])
from parsec_tpu.device import registry
(dev,) = [d for d in registry.devices if d.type != "cpu"]
state = dev.debug_state()
print("RESULT " + json.dumps({{"correct": out["correct"],
                              "compared": out["compared"],
                              "metrics": out["metrics"],
                              "tasks_by_class": dev.tasks_by_class,
                              "calls_by_class": dev.calls_by_class,
                              "executed": dev.executed_tasks,
                              "nulls": dev.null_flows_skipped}}))
"""
    # one accelerator, as the cell has (test_potrf64k.py says why)
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="")
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT ")]
    assert lines, proc.stderr[-3000:]
    return json.loads(lines[-1][7:])


def test_a_traced_getrf_rehearsal_is_correct_and_reports_the_swaps():
    out = _rehearse("none")
    assert out["correct"], out["compared"]
    assert {"probe_gap", "tasks_off", "tiles_absent"} <= set(out["compared"])
    metrics = out["metrics"]
    # on the CPU no device metric is reported; the counted ones are
    assert LISTED - {"kernel.dynamic_roofline", "device.idle_share.dynamic",
                     "devmod.hbm_peak_share"} | {SWAPS} <= set(metrics)
    assert PANEL_ROOF not in metrics and SWAP_ROOF not in metrics
    # 8 x 8 tiles of 128: 64 tiles of A and 8 of IPIV staged once
    assert metrics["devmod.h2d_gb_per_solve"]["value"] == pytest.approx(
        (64 * 128 * 128 * 4 + 8 * 4 * 128 * 4) / 1e9)
    tasks, calls = out["tasks_by_class"], out["calls_by_class"]
    solves = out["executed"] // 204
    assert tasks == {"PANEL": 8 * solves, "SWPTRSM": 28 * solves,
                     "GEMM": 140 * solves, "SWPLEFT": 28 * solves}
    # a wide class's instance is one call
    assert calls["PANEL"] == tasks["PANEL"]
    assert metrics[SWAPS]["value"] == 1.0
    # the rows above each panel: k for PANEL(k), SWPTRSM(k, .), SWPLEFT(k, .)
    assert out["nulls"] == solves * sum(k * (1 + (7 - k) + k)
                                        for k in range(8))


@pytest.mark.parametrize("fault,number", [
    ("tile_local_pivoting", "probe_gap"),
    ("one_column_unswapped", "probe_gap"),
    ("ipiv_tile_not_written_back", "tiles_absent"),
    ("bf16_products", "probe_gap")])
def test_planted_getrf_fault_reads_not_correct(fault, number):
    out = _rehearse(fault)
    assert out["correct"] is False
    compared = out["compared"]
    assert compared[number]["value"] > compared[number]["limit"], compared
    assert compared["tasks_off"]["value"] == 0
    if number == "probe_gap":
        assert compared["tiles_absent"]["value"] == 0


class _Prob:
    """What the roofline readers ask of ``problems/getrf_tiled.py``."""

    def least_seconds(self, classes, peaks):
        return {("PANEL",): 0.1, ("SWPTRSM", "SWPLEFT"): 0.02}[classes]


def _run(device_ops, solves=4, prob=_Prob(), peaks=True, busy_s=20.0):
    return {"trace": device_ops and {"device_ops": device_ops,
                                     "busy_s": busy_s},
            "peaks": {"flops_per_s": 197e12, "bytes_per_s": 819e9}
            if peaks else None,
            "problem": prob,
            "window": types.SimpleNamespace(solves=solves),
            "cell": types.SimpleNamespace(chips=1)}


OPS = [["jit_fused_getrf_gemm/convolution_fusion", 12.0],
       ["jit_fused_getrf_panel/custom-call", 2.0],
       ["jit_fused_getrf_panel/fusion", 2.0],
       ["jit_fused_getrf_swptrsm/fusion", 1.0],
       ["jit_fused_getrf_swpleft/fusion", 1.0]]
FULL = OPS + [[f"jit_fused_getrf_gemm/copy.{i}", 0.3] for i in range(5)]


@pytest.mark.parametrize("name,run,expect", [
    (PANEL_ROOF, _run(None), None),                 # untraced
    (PANEL_ROOF, _run(OPS, peaks=False), None),     # a rehearsal
    (PANEL_ROOF, _run(OPS, prob=object()), None),   # another problem
    (PANEL_ROOF, _run(OPS[:1]), None),              # no panel among ten
    (PANEL_ROOF, _run(OPS, solves=0), None),
    # ten kept of 19.5 s, 20.5 busy: the 1 s unseen is counted the class's
    (PANEL_ROOF, _run(FULL, busy_s=20.5), 100.0 * 4 * 0.1 / 5.0),
    (PANEL_ROOF, _run(FULL, busy_s=19.5), 100.0 * 4 * 0.1 / 4.0),
    # fewer than ten kept: nothing was dropped
    (PANEL_ROOF, _run(OPS, busy_s=30.0), 100.0 * 4 * 0.1 / 4.0),
    (SWAP_ROOF, _run(OPS), 100.0 * 4 * 0.02 / 2.0),
    (SWAP_ROOF, _run(FULL, busy_s=20.0), 100.0 * 4 * 0.02 / 2.5)])
def test_getrf_roofline_readers_sum_their_classes_operations(name, run,
                                                             expect):
    got = _reader(name).read(run)
    assert got == (expect if expect is None else pytest.approx(expect))


def _dev(**kw):
    return types.SimpleNamespace(type="tpu", **kw)


@pytest.mark.parametrize("devices,expect", [
    ([_dev(executed_tasks=816)], None),             # no counters
    ([_dev(tasks_by_class={"GEMM": 4096}, calls_by_class={"GEMM": 64})],
     None),                                         # no swap class ran
    ([types.SimpleNamespace(type="cpu", tasks_by_class={"SWPLEFT": 9},
                            calls_by_class={"SWPLEFT": 9}),
      _dev(tasks_by_class={"SWPTRSM": 946, "SWPLEFT": 946, "GEMM": 9},
           calls_by_class={"SWPTRSM": 946, "SWPLEFT": 400, "GEMM": 1})],
     1892 / 1346)])
def test_swap_batch_reader_over_the_registry(monkeypatch, devices, expect):
    from parsec_tpu.device import registry
    monkeypatch.setattr(registry, "devices", devices)
    got = _reader(SWAPS).read({})
    assert got == (expect if expect is None else pytest.approx(expect))
