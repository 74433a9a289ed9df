"""Guards of what PR 34 added to the yardstick (configuration
``dtd-gemm-16k``, cell ``gemm16k.dtd``, path ``dtd``, three per-layer
metrics); none needs a chip.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests/test_dtd_gemm.py -q

- the manifest lists the cell where the reading code finds something, last
  in each list, and the 64k cell still where PR 29 put it;
- the configuration is the source's shapes uncut, on ``gemm-16k``'s data;
- a traced rehearsal, its window cut to fit 512 tasks through the
  environment's ``PARSEC_MCA_`` parameters, reports the three ``dtd.*``
  metrics, exact, and every result tile pushed out early;
- planted faults: the last k of one C tile never inserted, one C tile's chain
  released out of order (its GEMMs never made to wait for each other): the
  gap reads both; ``PUSHOUT`` dropped: correct, and the early share reads 0.

``test_potrf64k.py`` holds the dynamic cells' lists to end with the 64k cell
(``[2:] == [CELL]``), which no list can once a cell is appended after it, and
a PR that adds a cell may not edit that file: tier-1 collects the assertion
from here (``tests/test_benchmark_yardstick.py``; PERF.md, section 7).
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]

CELL, TWIN, CELL_64K = "gemm16k.dtd", "gemm16k.dynamic", "potrf64k.dynamic"
DTD_METRICS = {"dtd.insert_us_per_task", "dtd.window_drives_per_solve",
               "dtd.tasks_in_window_share"}
# the twin's metrics whose readers find something on a DTD solve
SHARED = {"sched.flood_us_per_task", "sched.flood_putbacks_per_task",
          "sched.release_us_per_task", "devmod.tasks_per_xla_call",
          "devmod.h2d_gb_per_solve", "devmod.writeback_early_share",
          "harness.between_solves_share.dynamic", "kernel.dynamic_roofline",
          "device.idle_share.dynamic"}
# and those that do not: a DTD pool has no release plan; sched.host_us_per_task
# takes the runner's add_taskpool + wait and a DTD solve runs half its tasks
# under "insert" (it would read a negative remainder); the five phase metrics
# are pinned to the two 16k PTG cells by test_phase_metrics.py
PHASE_METRICS = {
    "sched.flood_release_us_per_task", "devmod.writeback_ms_per_solve",
    "devmod.device_wait_share", "ctx.lifecycle_ms_per_solve",
    "host.unowned_share.dynamic"}
NOT_LISTED = PHASE_METRICS | {"sched.release_planned_share",
                              "sched.host_us_per_task"}
# the rehearsal's 512 tasks (N=1024, nb=128) through a window of 64 / 32
WINDOW = {"PARSEC_MCA_dtd_window_size": "64",
          "PARSEC_MCA_dtd_threshold_size": "32"}


def _manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    return manifest, {m["name"]: m for m in manifest["per_layer"]}


def test_manifest_lists_the_dtd_cell_where_its_readers_find_something():
    manifest, per_layer = _manifest()
    (rate,) = [m for m in manifest["end_to_end"]
               if m["name"] == "dynamic.gflops"]
    assert rate["workloads"][-1] == CELL and rate["bound"] == 0.05
    for name in DTD_METRICS:
        m = per_layer[name]
        assert m["workloads"] == [CELL] and m["moves"] == "dynamic.gflops"
        assert m["layer"] == "host scheduler"
    for name, m in per_layer.items():
        if TWIN in m.get("workloads", []):
            assert (m["workloads"][-1] == CELL) is (name in SHARED), name
        if name in NOT_LISTED:
            assert CELL not in m["workloads"], name
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert manifest["workloads"][-1] is cell and cell["chips"] == 1
    assert (cell["config"], cell["traffic"]) == ("dtd-gemm-16k",
                                                 "dtd_host_tiles")
    assert manifest["configs"][-1]["name"] == "dtd-gemm-16k"


def test_manifest_still_lists_the_64k_cell_third_on_the_dynamic_lists():
    """What ``test_potrf64k.py`` asserts of the lists' tails, of the third
    entry: the 64k cell follows the two 16k cells on the rate and on every
    per-layer metric of the dynamic cells but the five pinned ones."""
    manifest, per_layer = _manifest()
    (rate,) = [m for m in manifest["end_to_end"]
               if m["name"] == "dynamic.gflops"]
    assert rate["workloads"][2] == CELL_64K
    for name, m in per_layer.items():
        if m.get("workloads", [])[:2] == [TWIN, "potrf16k.dynamic"]:
            assert (m["workloads"][2:3] == [CELL_64K]) is \
                (name not in PHASE_METRICS), name


def test_the_dtd_configuration_is_the_source_uncut_on_the_twin_s_data():
    import harness
    cell, twin = harness.Cell(CELL), harness.Cell(TWIN)
    cfg = cell.config
    assert (cfg["N"], cfg["nb"], cfg["dtype"]) == (16384, 1024, "float32")
    assert cfg["reduced"] == ["matmul_precision"]
    assert cfg["tasks"] == (cfg["N"] // cfg["nb"]) ** 3 == 4096
    assert {"N", "nb", "dtype", "window", "flush"} <= set(cfg["assumed"])
    assert "insertion order" in cfg["guarantees"]
    for key in ("N", "nb", "dtype", "matmul_precision", "tasks", "flops"):
        assert cfg[key] == twin.config[key], key
    assert cell.limits == twin.limits
    t, tw = cell.traffic, twin.traffic
    assert (t["path"], t["nb_cores"]) == ("dtd", 0)
    for key in ("warmup_solves", "pick_from_first", "trace_seconds",
                "mallopt", "collect_between_solves"):
        assert t[key] == tw[key], key
    # the window sizes the file states are the program's own defaults
    import parsec_tpu.dtd  # noqa: F401
    from parsec_tpu.core.params import params
    assert (t["dtd_window_size"], t["dtd_threshold_size"]) == (
        params.get("dtd_window_size"), params.get("dtd_threshold_size"))
    # the same operands, zeros and reference as the twin, from one seed
    cell.config.update(harness.REHEARSAL_SIZES)
    twin.config.update(harness.REHEARSAL_SIZES)
    p, q = cell.problem(2147483711), twin.problem(2147483711)
    assert (p.A4 == q.A4).all() and (p.B4 == q.B4).all()
    assert (p.tasks, p.flops, p.min_bytes) == (q.tasks, q.flops, q.min_bytes)


FAULTS = {
    "none": "",
    # the last GEMM of C(1, 0) is never inserted
    "last_k_skipped": """
from parsec_tpu.dtd import PUSHOUT, DTDTaskpool
_insert = DTDTaskpool.insert_task
def insert_task(self, body, *args, **kw):
    (c, flags) = args[2]
    if c.key == (1, 0) and flags & PUSHOUT:
        return None
    return _insert(self, body, *args, **kw)
DTDTaskpool.insert_task = insert_task
""",
    # the GEMMs of C(1, 0) are never made to wait for each other: those the
    # inserter has discovered by its next drive run in one batch, on one
    # version of the tile, and all but one of their products are lost
    "chain_out_of_order": """
from parsec_tpu.dtd import DTDTaskpool
_link = DTDTaskpool._link_dep
def _link_dep(self, pred, succ):
    if succ.tiles[2].key != (1, 0):
        _link(self, pred, succ)
DTDTaskpool._link_dep = _link_dep
""",
    # the flag never reaches the pool: the semantics of an untagged tile
    "pushout_dropped": """
from parsec_tpu.dtd import PUSHOUT, DTDTaskpool
_insert = DTDTaskpool.insert_task
def insert_task(self, body, *args, **kw):
    (c, flags) = args[2]
    return _insert(self, body, *args[:2], (c, flags & ~PUSHOUT), **kw)
DTDTaskpool.insert_task = insert_task
""",
}


def _rehearse(fault: str) -> dict:
    code = f"""
import json, os, sys
os.environ["PARSEC_MCA_device_tpu_allow_cpu"] = "1"
sys.path[:0] = [{BENCH!r}, {ROOT!r}]
{FAULTS[fault]}
import run
out = run.run_cell(["--workload", {CELL!r}, "--seed", "2147483713",
                    "--seconds", "1", "--trace", "1", "--rehearse"])
print("RESULT " + json.dumps({{"correct": out["correct"],
                              "compared": out["compared"],
                              "metrics": out["metrics"]}}))
"""
    # one accelerator, as the cell has (tier-1's conftest asks for eight host
    # devices, and which of eight a task goes to follows the moment's load)
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="", **WINDOW)
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT ")]
    assert lines, proc.stderr[-3000:]
    return json.loads(lines[-1][7:])


def test_a_traced_dtd_rehearsal_reports_the_front_end_and_the_push_outs():
    out = _rehearse("none")
    assert out["correct"], out["compared"]
    metrics = {k: v["value"] for k, v in out["metrics"].items()}
    assert DTD_METRICS <= set(metrics), sorted(metrics)
    assert not NOT_LISTED & set(metrics)
    assert metrics["dtd.insert_us_per_task"] > 0.0
    assert metrics["sched.release_us_per_task"] > 0.0
    # what tests/test_dtd.py derives for 512 tasks through 64 / 32 on the
    # device module: 13 drives a solve, 466 tasks run under discovery
    assert metrics["dtd.window_drives_per_solve"] == 13.0
    assert metrics["dtd.tasks_in_window_share"] == 100.0 * 466 / 512
    assert metrics["devmod.writeback_early_share"] == 100.0
    assert metrics["devmod.h2d_gb_per_solve"] == pytest.approx(
        3 * 1024 * 1024 * 4 / 1e9)
    assert metrics["sched.flood_putbacks_per_task"] == 0.0


@pytest.mark.parametrize("fault", ["last_k_skipped", "chain_out_of_order"])
def test_a_planted_dtd_fault_reads_in_the_gap(fault):
    out = _rehearse(fault)
    assert out["correct"] is False
    compared = out["compared"]
    assert compared["probe_gap"]["value"] > compared["probe_gap"]["limit"]
    assert compared["tiles_absent"]["value"] == 0
    # a task less is also a task off; a chain out of order is every task run
    assert (compared["tasks_off"]["value"] > 0) is (fault == "last_k_skipped")


def test_pushout_dropped_stays_correct_and_reads_in_the_early_share():
    out = _rehearse("pushout_dropped")
    assert out["correct"], out["compared"]
    assert out["metrics"]["devmod.writeback_early_share"]["value"] == 0.0
    assert "dtd.insert_us_per_task" in out["metrics"]
