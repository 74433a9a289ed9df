"""Guards of the hierarchical QR's part of the yardstick (configuration
``geqrf-hqr-128kx8k``, cell ``hqr128kx8k.dynamic``, the plain reference of
the hierarchical tile QR, one per-layer metric); none needs a chip.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests/test_hqr128kx8k.py -q

- the configuration's file is the source uncut (``reduced`` empty), its
  tree and sizes under ``assumed``;
- the problem's counts (tasks by class, FLOPs, the TS and TT tiles) agree
  with the PTG's own enumeration at a small size, and with the
  configuration's at the cell's;
- the manifest's entries stand where they were appended;
- the bfloat16-storage control comes out not correct, and with f32 storage
  it is a sound run;
- a traced rehearsal, sound, is correct and its counted readers find
  something; with three planted faults ``correct`` is false each time
  (``probe_gap``): one TT kill skipped, one TT kill's V2 zeroed, two heads'
  TT kills done in the other order;
- each class's least time is its LAPACK FLOPs or its tiles at the peak,
  whichever is longer;
- the new reader on a hand-made registry reads nothing where the TT
  classes' counters are absent.
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

CELL, CONFIG = "hqr128kx8k.dynamic", "geqrf-hqr-128kx8k"
TT_CALLS = "devmod.tt_tasks_per_xla_call"
NEW = (TT_CALLS,)
# rooflines the ten-operation guard leaves empty on the cell (the ten
# longest operations leave more busy time unaccounted than a twentieth of
# the classes' seconds), so none is listed on it
UNREAD = {"kernel.tsmqr_roofline", "kernel.hqr_panel_roofline",
          "kernel.ttmqr_roofline"}
# the shared lists the cell was appended to
LISTED = {"devmod.tasks_per_xla_call", "devmod.h2d_gb_per_solve",
          "harness.between_solves_share.dynamic", "kernel.dynamic_roofline",
          "device.idle_share.dynamic", "devmod.writeback_early_share",
          "devmod.hbm_peak_share", "devmod.inflight_held_gb",
          "devmod.pressure_ms_per_solve", "devmod.evicted_gb_per_solve",
          "sched.flood_us_per_task", "sched.flood_putbacks_per_task",
          "sched.release_us_per_task", "sched.release_planned_share",
          "devmod.stage_in_ms_per_solve", "devmod.panel_tasks_per_xla_call",
          "devmod.call_us_per_result", "devmod.dispatch_own_us_per_task",
          "devmod.chip_queue_depth", "devmod.held_already_run_share",
          "devmod.donated_result_share", "devmod.lru_touches_per_hit",
          # the list of the cells accepted before it: the one metric that
          # reads the programs the cell's set-up compiles
          "startup.fresh_compiles_at_setup"}
CLASSES = ["GEQRT", "UNMQR", "TSQRT", "TTQRT", "TSMQR", "TTMQR"]
# a rehearsal of the faults at 512 / 128: 64 x 4 tiles, 2,000 tasks
SMALL = {"N": 512, "nb": 128}


def _reader(name):
    import harness
    return harness.load_module("layer_metrics", name)


def test_the_hqr_configuration_is_the_source_uncut():
    import harness
    import reference_hqr as refh
    cell = harness.Cell(CELL)
    cfg = cell.config
    assert (cfg["M"], cfg["N"], cfg["nb"], cfg["dtype"]) == \
        (131072, 8192, 1024, "float32")
    assert cfg["M"] == cfg["M_over_N"] * cfg["N"]
    assert (cfg["a"], cfg["tree"]) == (4, "binary")
    assert cfg["reduced"] == [] and cfg["matmul_precision"] == "highest"
    assert cfg["architecture"] is None
    assert cfg["source"].startswith("DPLASMA") and len(cfg["source"]) <= 200
    assert "-M 131072 -N 8192 -t 1024 -i 1024 --qr_a 4 --treel 3" \
        in cfg["source"] and "zgeqrf_param.jdf" in cfg["source"]
    assert cfg["task_classes"] == CLASSES
    assert cfg["flops"] == "2MN^2 - 2N^3/3"
    assert {"M", "N", "nb", "ib", "a", "tree", "program", "tasks", "T",
            "dense_triangular_products", "precision", "data", "reference",
            "host_memory"} <= set(cfg["assumed"])
    assert (cell.chips, cell.traffic["path"]) == (1, "dynamic")
    assert set(cell.limits) == {"probe_gap", "tasks_off", "tiles_absent"}
    assert all(cell.limits[k]["limit"] == 0
               for k in ("tasks_off", "tiles_absent"))
    # the reference's own tree at the cell's size, no data made
    mt, nt = cfg["M"] // cfg["nb"], cfg["N"] // cfg["nb"]
    steps = refh.phases(mt, nt, cfg["a"], cfg["tree"])
    kills = {w: [sum(1 for ph in st for op in ph if op[0] == w)
                 for st in steps] for w in ("ge", "ts", "tt")}
    panel = {"GEQRT": "ge", "TSQRT": "ts", "TTQRT": "tt"}
    update = {"UNMQR": "ge", "TSMQR": "ts", "TTMQR": "tt"}
    counts = {c: sum(kills[w]) for c, w in panel.items()}
    counts.update({c: sum(n * (nt - 1 - k) for k, n in enumerate(kills[w]))
                   for c, w in update.items()})
    assert counts == {"GEQRT": 252, "UNMQR": 890, "TSQRT": 744,
                      "TTQRT": 244, "TSMQR": 2638, "TTMQR": 862}
    assert sum(counts.values()) == cfg["tasks"] == 5630
    # the phases of a step: 1 GEQRT, 3 TS links, 5 TT levels
    assert [len(st) for st in steps] == [9] * nt


def test_the_hqr_problem_counts_agree_with_the_ptg_at_a_small_size():
    import harness
    from parsec_tpu.data_dist.matrix import TwoDimBlockCyclic
    from parsec_tpu.models.qr import tiled_hqr_ptg
    from parsec_tpu.models.qrtree import QRTree
    cfg = harness.Cell(CELL).config
    small = harness.load_module("problems", cfg["algorithm"]).Problem(
        dict(cfg, N=3 * 32, nb=32, M_over_N=4, a=3), seed=1)
    mt, nt = 12, 3
    assert (small.mt, small.nt) == (mt, nt)
    tree = QRTree(mt, nt, 3)
    tp = tiled_hqr_ptg(*(TwoDimBlockCyclic(n, mt * 32, nt * 32, 32, 32)
                         for n in ("A", "TS", "TT")), tree)
    counts = {tc.name: sum(1 for _ in tp._tc_builders[tc.name]
                           ._enumerate_space()) for tc in tp.task_classes}
    assert counts == small.counts and sum(counts.values()) == small.tasks
    # the T tiles written: TS on every row of every step, TT on its kills
    assert sorted(small.ts_keys) == [(m, k) for m in range(mt)
                                     for k in range(min(m + 1, nt))]
    assert sorted(small.tt_keys) == sorted(
        (m, k) for k in range(nt) for m in tree.tt_rows(k))
    assert small.result_tiles == mt * nt + len(small.ts_keys) \
        + len(small.tt_keys)
    m, n = 12 * 32.0, 3 * 32.0
    assert small.flops == 2 * m * n * n - 2 * n ** 3 / 3
    assert sum(small.class_flops.values()) == pytest.approx(small.flops,
                                                            rel=1e-12)
    assert small.min_bytes == (2 * mt * nt + len(small.ts_keys)
                               + len(small.tt_keys)) * 32 * 32 * 4


def test_manifest_lists_the_hqr_cell_where_it_was_appended():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    names = [w["name"] for w in manifest["workloads"]]
    assert names.index(CELL) == 8
    cell = manifest["workloads"][8]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "dynamic_host_tiles", 1)
    conf = manifest["configs"][7]
    assert (conf["name"], conf["reduced"]) == (CONFIG, [])
    with open(os.path.join(ROOT, conf["file"])) as f:
        assert json.load(f)["source"] == conf["source"]
    (rate,) = [m for m in manifest["end_to_end"]
               if m["name"] == "dynamic.gflops"]
    assert rate["workloads"][7] == CELL and rate["bound"] == 0.05
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    listed = {n for n, m in per_layer.items()
              if CELL in m.get("workloads", [CELL])}
    assert LISTED | set(NEW) <= listed
    assert not UNREAD & listed
    for name in LISTED:
        # appended: after every cell the manifest had before it
        w = per_layer[name]["workloads"]
        assert w.index(CELL) == len(w) - 1 - len(
            [c for c in w if names.index(c) > 8]), name
    for name in NEW:
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["moves"] == "dynamic.gflops"
    assert (per_layer[TT_CALLS]["source"], per_layer[TT_CALLS]["layer"]) == \
        ("program_counter", "device module")
    order = [m["name"] for m in manifest["per_layer"]]
    assert [order.index(n) for n in NEW] == [45]


def test_the_hqr_controls_read_the_storage_alone():
    """The bfloat16-storage control reads over the limit, three times; with
    f32 tiles the same code is a sound run, under a third of it."""
    import control
    import harness
    cell = harness.Cell(CELL)
    cell.config.update(SMALL)
    compared = control.control_compared(cell, seed=11)
    assert not harness.verdict(compared)
    limit = cell.limits["probe_gap"]["limit"]
    assert compared["probe_gap"]["value"] > 3 * limit
    prob = cell.problem(11)
    prob.reference()
    assert prob.gap(prob.control(store="float32")) < limit / 3


# the kill planted on: step 1's heads are rows 1, 5, 9, ..; head 5 (rank 1)
# is killed by head 1 at level 0
_STEP1_HEAD5 = """
import jax.numpy as jnp
from parsec_tpu.device.tpu import TPUDevice
_si, _mw = TPUDevice.stage_in_many, TPUDevice._mark_written
SAVED = {}
def _target(t):
    return t.task_class.name == "TTQRT" and \\
        (t.locals["k"], t.locals["m"]) == (1, 5)
def stage_in_many(self, tasks):
    _si(self, tasks)
    for t in tasks:
        if _target(t):
            SAVED[id(t)] = [jnp.array(t.flow_data(f).value, copy=True)
                            for f in ("R", "B")]
def _mark_written(self, task):
    if id(task) in SAVED:
        r, b = SAVED.pop(id(task))
        FAULT(task, r, b)
    return _mw(self, task)
TPUDevice.stage_in_many, TPUDevice._mark_written = \\
    stage_in_many, _mark_written
"""
FAULTS = {
    "none": "",
    # the kill does not happen: both tiles as they came, T zero (its
    # updates then change nothing)
    "one_tt_kill_skipped": _STEP1_HEAD5.replace("FAULT(task, r, b)", """\
task.flow_data("R").value, task.flow_data("B").value = r, b
        task.flow_data("T").value = jnp.zeros_like(r)"""),
    # the kill's reflectors lose V2, the head's GEQRT ones stay below it
    "one_tt_v2_zeroed": _STEP1_HEAD5.replace("FAULT(task, r, b)", """\
b1 = task.flow_data("B")
        b1.value = jnp.tril(b1.value, -1)"""),
    # step 1's head 1 kills head 9 (rank 2) before head 5 (rank 1): a QR
    # still, whose reflectors the reference replays in the other order
    "two_heads_kill_order_swapped": """
from parsec_tpu.models import qrtree
_tt = qrtree.QRTree._tt_kills
def _tt_kills(self, heads):
    out = _tt(self, heads)
    if heads[0] == 1:
        seq = out[1]
        seq[0], seq[1] = seq[1], seq[0]
    return out
qrtree.QRTree._tt_kills = _tt_kills
""",
}


def _rehearse(fault: str) -> dict:
    code = f"""
import json, os, sys
os.environ["PARSEC_MCA_device_tpu_allow_cpu"] = "1"
sys.path[:0] = [{BENCH!r}, {ROOT!r}]
{FAULTS[fault]}
import harness
harness.REHEARSAL_SIZES = {SMALL!r}
import run
out = run.run_cell(["--workload", {CELL!r}, "--seed", "2147483746",
                    "--seconds", "1", "--trace", "1", "--rehearse"])
from parsec_tpu.device import registry
(dev,) = [d for d in registry.devices if d.type != "cpu"]
print("RESULT " + json.dumps({{"correct": out["correct"],
                              "compared": out["compared"],
                              "metrics": out["metrics"],
                              "tasks_by_class": dev.tasks_by_class,
                              "calls_by_class": dev.calls_by_class,
                              "executed": dev.executed_tasks}}))
"""
    # one accelerator, as the cell has (test_potrf64k.py says why)
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="")
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT ")]
    assert lines, proc.stderr[-3000:]
    return json.loads(lines[-1][7:])


def test_a_traced_hqr_rehearsal_is_correct_and_batches_the_tree():
    out = _rehearse("none")
    assert out["correct"], out["compared"]
    assert {"probe_gap", "tasks_off", "tiles_absent"} <= set(out["compared"])
    metrics = out["metrics"]
    # on the CPU no device metric is reported; the counted ones are
    assert LISTED - {"kernel.dynamic_roofline", "device.idle_share.dynamic",
                     "devmod.hbm_peak_share"} | {TT_CALLS} <= set(metrics)
    assert not UNREAD & set(metrics)
    # 64 x 4 tiles of 128: the warm-up and the window's solves, whole
    import reference_hqr as refh
    per_solve = sum(len(ph) * (1 + (4 - 1 - k) if ph[0][0] else 0)
                    for k, st in enumerate(refh.phases(64, 4, 4, "binary"))
                    for ph in st)
    tasks, calls = out["tasks_by_class"], out["calls_by_class"]
    assert set(tasks) == set(CLASSES) and sum(tasks.values()) == \
        out["executed"]
    assert out["executed"] % per_solve == 0 and \
        out["executed"] >= 2 * per_solve
    # the tree's kills shared calls: a level's TT kills, a link's TS kills
    assert metrics[TT_CALLS]["value"] > 1.0
    assert metrics["devmod.panel_tasks_per_xla_call"]["value"] > 1.0
    assert metrics[TT_CALLS]["value"] == pytest.approx(
        (tasks["TTQRT"] + tasks["TTMQR"])
        / (calls["TTQRT"] + calls["TTMQR"]))


@pytest.mark.parametrize("fault", ["one_tt_kill_skipped", "one_tt_v2_zeroed",
                                   "two_heads_kill_order_swapped"])
def test_planted_hqr_fault_reads_not_correct(fault):
    out = _rehearse(fault)
    assert out["correct"] is False
    compared = out["compared"]
    assert compared["probe_gap"]["value"] > compared["probe_gap"]["limit"]
    assert compared["tasks_off"]["value"] == 0
    assert compared["tiles_absent"]["value"] == 0


PEAKS = {"flops_per_s": 197e12, "bytes_per_s": 819e9}


@pytest.mark.parametrize("classes,flops,tiles", [
    # LAPACK's count in nb^3 and the tiles read and written, per task
    (("GEQRT",), 4 / 3, 3), (("UNMQR",), 2, 4), (("TSQRT",), 2, 5),
    (("TTQRT",), 2 / 3, 5), (("TSMQR",), 4, 6), (("TTMQR",), 2, 6)])
def test_hqr_class_least_time_is_its_flops_or_its_tiles(classes, flops,
                                                        tiles):
    import harness
    cfg = harness.Cell(CELL).config
    prob = harness.load_module("problems", cfg["algorithm"]).Problem(
        dict(cfg, N=3 * 32, nb=32, M_over_N=4), seed=1)
    (c,) = classes
    n, nb = prob.counts[c], 32.0
    expect = n * max(flops * nb ** 3 / PEAKS["flops_per_s"],
                     tiles * nb * nb * 4 / PEAKS["bytes_per_s"])
    assert n > 0
    assert prob.least_seconds(classes, PEAKS) == pytest.approx(expect,
                                                               rel=1e-12)


def test_hqr_least_time_of_classes_is_their_sum():
    import harness
    cfg = harness.Cell(CELL).config
    prob = harness.load_module("problems", cfg["algorithm"]).Problem(
        dict(cfg, N=3 * 32, nb=32, M_over_N=4), seed=1)
    panel = ("GEQRT", "TSQRT", "TTQRT")
    assert prob.least_seconds(panel, PEAKS) == pytest.approx(
        sum(prob.least_seconds((c,), PEAKS) for c in panel), rel=1e-12)
    # at nb = 32 every task is bound by its bytes; the flops then sum to
    # the algorithm's count and the least time is over them
    assert prob.least_seconds(tuple(CLASSES), PEAKS) >= \
        prob.flops / PEAKS["flops_per_s"]


def _dev(**kw):
    return types.SimpleNamespace(type="tpu", **kw)


@pytest.mark.parametrize("devices,expect", [
    ([_dev(executed_tasks=5630)], None),            # no counters
    ([_dev(tasks_by_class={"TSMQR": 2638}, calls_by_class={"TSMQR": 90})],
     None),                                         # no TT class ran
    ([types.SimpleNamespace(type="cpu", tasks_by_class={"TTQRT": 9},
                            calls_by_class={"TTQRT": 9}),
      _dev(tasks_by_class={"TTQRT": 244, "TTMQR": 862, "TSQRT": 744},
           calls_by_class={"TTQRT": 40, "TTMQR": 60, "TSQRT": 24})],
     1106 / 100)])
def test_tt_batch_reader_over_the_registry(monkeypatch, devices, expect):
    from parsec_tpu.device import registry
    monkeypatch.setattr(registry, "devices", devices)
    got = _reader(TT_CALLS).read({})
    assert got == (expect if expect is None else pytest.approx(expect))
