"""Guards of what PR 29 added to the yardstick (configuration ``potrf-64k``,
cell ``potrf64k.dynamic``, the tile-wise reference, four per-layer metrics);
none needs a chip.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests/test_potrf64k.py -q

- the manifest lists the cell where the reading code finds something;
- the tile-wise data and reference equal the dense ones where both fit;
- the tile-wise control (bfloat16 storage) comes out not correct;
- a traced rehearsal reports the new counters, under a budget that never
  presses and under one of half the triangle; every solve is reduced where
  it is read back and its collection let go;
- planted faults: an evicted dirty tile dropped instead of written back, a
  device demoted on the way: ``correct`` false each time.

The five metrics of ``test_phase_metrics.py`` do not list the cell: that file
holds their lists to exactly the two 16k cells, and a PR that adds a cell may
not edit it (PERF.md, section 7).
"""

import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]

CELL = "potrf64k.dynamic"
PHASE_METRICS = {
    "sched.flood_release_us_per_task", "devmod.writeback_ms_per_solve",
    "devmod.device_wait_share", "ctx.lifecycle_ms_per_solve",
    "host.unowned_share.dynamic"}
# devmod.hbm_peak_share reads the chip's allocator: nothing on the CPU
COUNTED = {"devmod.inflight_held_gb", "devmod.pressure_ms_per_solve",
           "devmod.evicted_gb_per_solve"}


def test_manifest_lists_the_64k_cell_where_its_readers_find_something():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    (rate,) = [m for m in manifest["end_to_end"]
               if m["name"] == "dynamic.gflops"]
    assert rate["workloads"][-1] == CELL
    # the four this PR brought read what only this cell presses
    for name in COUNTED | {"devmod.hbm_peak_share"}:
        m = per_layer[name]
        assert m["workloads"] == [CELL] and m["moves"] == "dynamic.gflops"
        assert m["layer"] == "device module" and m["better"] == "lower"
    # every other metric of the dynamic cells takes the new one last, but
    # for the five whose lists test_phase_metrics.py pins
    for name, m in per_layer.items():
        if m.get("workloads", [])[:2] == ["gemm16k.dynamic",
                                          "potrf16k.dynamic"]:
            assert (m["workloads"][2:] == [CELL]) is \
                (name not in PHASE_METRICS), name


def test_tile_wise_data_and_reference_equal_the_dense_ones():
    import reference as ref
    import reference_tiled as reft
    n, nb = 1024, 128
    tiles = reft.spd_tiles(2147483693, n, nb)
    assert list(tiles) == [(m, k) for m in range(8) for k in range(m + 1)]
    a = reft.dense_of(tiles, nb)
    # the construction of reference.spd_data: symmetric, N(0, 1/2) off the
    # diagonal, the row's absolute sum plus one on it
    assert a.dtype == np.float32 and (a == a.T).all()
    off = a[np.tril_indices(n, -1)]
    assert abs(off.mean()) < 5e-3 and abs(off.var() - 0.5) < 5e-3
    rest = np.abs(a).sum(axis=1, dtype=np.float64) - np.abs(np.diag(a))
    np.testing.assert_allclose(np.diag(a), rest + 1.0, rtol=1e-6)
    assert all(t.flags.c_contiguous for t in tiles.values())
    # another seed, another matrix; the same seed, the same
    again = reft.spd_tiles(2147483693, n, nb)
    other = reft.spd_tiles(2147483694, n, nb)
    assert all((tiles[k] == again[k]).all() for k in tiles)
    assert not (tiles[3, 1] == other[3, 1]).any()
    # the reference: A.X, and L.(Lt.X) on a float64 factor of the same matrix
    X = ref.probes(7, n)
    want = ref.potrf_want(a, X)
    assert ref.gap(reft.sym_apply(tiles, X, nb), want) < 1e-14
    L = np.linalg.cholesky(a.astype(np.float64)).astype(np.float32)
    lower = ref.tiles_of(L, nb, lower=True)
    np.testing.assert_allclose(reft.potrf_got(lower, X, nb),
                               ref.potrf_got(lower, X, nb), rtol=1e-12)
    assert ref.gap(reft.potrf_got(lower, X, nb), want) < 1e-6
    # and the control, tile by tile, is the dense control
    c_tiles = reft.potrf_control(tiles, nb)
    c_dense = ref.tiles_of(ref.potrf_control(a, nb), nb, lower=True)
    assert ref.gap(reft.potrf_got(c_tiles, X, nb),
                   ref.potrf_got(c_dense, X, nb)) < 1e-6


def test_the_tile_wise_control_reads_above_the_limit():
    import control
    import harness
    cell = harness.Cell(CELL)
    cell.config.update(harness.REHEARSAL_SIZES)
    compared = control.control_compared(cell, seed=11)
    assert not harness.verdict(compared)
    assert compared["probe_gap"]["value"] > \
        3 * cell.limits["probe_gap"]["limit"]


def test_the_configuration_is_the_source_uncut():
    import harness
    cell = harness.Cell(CELL)
    cfg = cell.config
    assert (cfg["N"], cfg["nb"], cfg["dtype"]) == (65536, 1024, "float32")
    assert cfg["reduced"] == ["matmul_precision"]
    nt = cfg["N"] // cfg["nb"]
    assert cfg["tasks"] == nt + nt * (nt - 1) + nt * (nt - 1) * (nt - 2) // 6
    assert (cell.chips, cell.traffic["path"]) == (1, "dynamic")


HALF_TRIANGLE = """
from parsec_tpu.device.tpu import TPUDevice
TPUDevice._hbm_budget = lambda self: 18 * 128 * 128 * 4
"""
FAULTS = {
    "none": "",
    # nothing broken: after each read-back, once the garbage is collected as
    # the traffic collects it before the next solve, no collection is alive
    "watch_collections": """
import gc, weakref
import harness
from parsec_tpu.data_dist.matrix import SymTwoDimBlockCyclic
_made = []
_init = SymTwoDimBlockCyclic.__init__
def _recorded(self, *args, **kwargs):
    _init(self, *args, **kwargs)
    _made.append(weakref.ref(self))
SymTwoDimBlockCyclic.__init__ = _recorded
_solved = harness.Window.solved
def solved(self):
    gc.collect()
    assert _made and not [r for r in _made if r() is not None]
    _solved(self)
harness.Window.solved = solved
""",
    # a budget of half the triangle, nothing broken: evictions, and correct
    "tight_budget": HALF_TRIANGLE,
    # the same, and the drain drops its victims instead of writing them back
    "evicted_dirty_dropped": HALF_TRIANGLE + """
from parsec_tpu.data.data import COHERENCY_INVALID
def _drain(self):
    with self._lru_lock:
        for c in self._evict_q:
            c.original.detach_copy(self.device_index)
            c.coherency = COHERENCY_INVALID
        self._evict_q.clear()
        self._evict_bytes = 0
TPUDevice._drain_evictions = _drain
""",
    # the accelerator fails one dispatch of the window's first solve (the
    # warm-up makes 28 calls), is demoted, and the host's own bodies finish
    # the solves: right answers, from the wrong place
    "device_demoted": """
from parsec_tpu.device.tpu import TPUDevice
_rv = TPUDevice._run_vmapped
_calls = [0]
def _run_vmapped(self, batch):
    _calls[0] += 1
    if _calls[0] == 40:
        raise ConnectionResetError("device reset")
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
out = run.run_cell(["--workload", {CELL!r}, "--seed", "2147483701",
                    "--seconds", "1", "--trace", "1", "--rehearse"])
print("RESULT " + json.dumps({{"correct": out["correct"],
                              "compared": out["compared"],
                              "metrics": out["metrics"]}}))
"""
    # one accelerator, as the cell has: under tier-1's conftest XLA_FLAGS asks
    # for eight host devices, and which of eight a task goes to follows the
    # load of the moment, so batch sizes (and what is staged twice) would too
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="")
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT ")]
    assert lines, proc.stderr[-3000:]
    return json.loads(lines[-1][7:])


@pytest.mark.parametrize("fault", ["none", "tight_budget"])
def test_a_traced_rehearsal_reports_the_budget_at_work(fault):
    out = _rehearse(fault)
    assert out["correct"], out["compared"]
    metrics = out["metrics"]
    assert COUNTED <= set(metrics), sorted(metrics)
    assert "devmod.hbm_peak_share" not in metrics
    assert not PHASE_METRICS & set(metrics)
    for name in COUNTED:
        value = metrics[name]["value"]
        assert math.isfinite(value) and value >= 0.0, (name, value)
    assert metrics["devmod.inflight_held_gb"]["value"] > 0.0
    pressed = fault == "tight_budget"
    assert (metrics["devmod.evicted_gb_per_solve"]["value"] > 0.0) is pressed
    assert (metrics["devmod.pressure_ms_per_solve"]["value"] > 0.0) is pressed
    # the tiles that left early were staged again, and counted
    triangle_gb = 36 * 128 * 128 * 4 / 1e9
    staged = metrics["devmod.h2d_gb_per_solve"]["value"]
    if pressed:
        assert staged > triangle_gb, staged
    else:
        assert staged == pytest.approx(triangle_gb), staged


def test_every_solve_is_reduced_and_its_collection_let_go():
    """Host memory at N=65,536: no finished solve's tiles outlive the
    collection before the next solve, and both compared results are the
    probe products of their solves (the rehearsal fails inside where a
    collection survives)."""
    out = _rehearse("watch_collections")
    assert out["correct"], out["compared"]
    assert {"probe_gap", "probe_gap_pick"} <= set(out["compared"])


@pytest.mark.parametrize("fault,number", [
    ("evicted_dirty_dropped", "probe_gap"), ("device_demoted", "tasks_off")])
def test_planted_fault_reads_not_correct(fault, number):
    out = _rehearse(fault)
    assert out["correct"] is False
    compared = out["compared"]
    if number == "probe_gap":
        # the stale tile shows as absent or in the gap of either solve
        assert compared["tiles_absent"]["value"] > 0 or any(
            compared[k]["value"] > compared[k]["limit"]
            for k in ("probe_gap", "probe_gap_pick") if k in compared)
    else:
        assert compared[number]["value"] > compared[number]["limit"]
        assert compared["probe_gap"]["value"] <= compared["probe_gap"]["limit"]
