"""Guards of what PR 40 added to the yardstick (configuration
``geqrf-52k-g4``, cell ``geqrf52k.ctx4`` on four chips, five per-layer
metrics of what only several accelerators under one ``Context`` do); none
needs a chip.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests/test_geqrf52k_ctx4.py -q

- the configuration's file is the source uncut (``reduced`` empty) with its
  departures under ``assumed``, four accelerators, and the source's line equal
  to the manifest's;
- the manifest's entries stand where they were appended, by position;
- a traced rehearsal over four virtual CPU devices is sound and reports the
  new metrics that need no device trace; it stays sound where the writers of a
  tile move from chip to chip (the write invalidates, no flush lowers the
  host's version), and with both protections taken away the stale copy's
  flush wins and ``correct`` is false;
- the five readers on hand-made tables read nothing where the counter, the
  span or the trace is absent.
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

CELL, CONFIG, TWIN = "geqrf52k.ctx4", "geqrf-52k-g4", "geqrf32k.dynamic"
D2D_GB, D2D_MS = "devmod.d2d_gb_per_solve", "devmod.d2d_ms_per_solve"
BUSIEST, BALANCE = "devmod.busiest_chip_task_share", \
    "device.busy_balance.dynamic"
DROPPED = "devmod.replica_gb_dropped_per_solve"
NEW = [D2D_GB, D2D_MS, BUSIEST, BALANCE, DROPPED]
# the twin's lists the cell is not on: the reader divides the chips' mean
# seconds by one chip's peak (PERF.md, section 7)
NOT_LISTED = {"kernel.tsmqr_roofline"}
# lists without the twin that take the cell last: they read the phase table
# and the runner's walls, which one thread over four chips fills as over one
# (the cell's two largest host costs are sched.release and
# devmod.inflight_wait)
PHASES = {"sched.host_us_per_task", "sched.flood_release_us_per_task",
          "devmod.writeback_ms_per_solve", "devmod.device_wait_share",
          "ctx.lifecycle_ms_per_solve", "host.unowned_share.dynamic"}


def _reader(name):
    import harness
    return harness.load_module("layer_metrics", name)


def test_the_four_chip_configuration_is_the_source_uncut():
    import harness
    cell = harness.Cell(CELL)
    cfg = cell.config
    assert (cfg["N"], cfg["nb"], cfg["dtype"]) == (53248, 1024, "float32")
    assert cfg["reduced"] == [] and cfg["matmul_precision"] == "highest"
    assert cfg["architecture"] is None and cfg["accelerators"] == 4
    nt = cfg["N"] // cfg["nb"]
    assert cfg["tasks"] == 48230 == nt + nt * (nt - 1) \
        + (nt - 1) * nt * (2 * nt - 1) // 6
    assert cfg["flops"] == "4N^3/3" and cfg["algorithm"] == "geqrf_tiled_g4"
    twin = harness.Cell(TWIN).config
    assert set(twin["assumed"]) | {"accelerators", "placement",
                                   "algorithm_module"} == \
        set(cfg["assumed"])
    assert (cell.chips, cell.traffic["path"], cell.entry["traffic"]) == \
        (4, "dynamic", "dynamic_host_tiles")
    assert set(cell.limits) == {"probe_gap", "tasks_off", "tiles_absent"}
    assert cell.limits["tasks_off"]["limit"] == 0 == \
        cell.limits["tiles_absent"]["limit"]
    # A and T do not fit one chip: 4 MiB tiles against bytes_limit
    tiles = nt * nt + nt * (nt + 1) // 2
    assert tiles * 4 * 2 ** 20 > 16.909e9 > (tiles - 52) * 4 * 2 ** 20
    prob = harness.load_module("problems", cfg["algorithm"]).Problem(
        {"N": 4 * 32, "nb": 32}, seed=1)
    assert prob.tasks == 30 and prob.result_tiles == 26


def test_manifest_lists_the_four_chip_cell_where_it_was_appended():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    assert [w["name"] for w in manifest["workloads"]].index(CELL) == 6
    cell = manifest["workloads"][6]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "dynamic_host_tiles", 4)
    # the first cell on four chips; every cell before it has one
    assert [w["chips"] for w in manifest["workloads"][:6]] == [1] * 6
    conf = manifest["configs"][5]
    assert (conf["name"], conf["reduced"]) == (CONFIG, [])
    with open(os.path.join(ROOT, conf["file"])) as f:
        assert json.load(f)["source"] == conf["source"]
    assert len(conf["source"]) <= 200 and "-g 4" in conf["source"]
    (rate,) = [m for m in manifest["end_to_end"]
               if m["name"] == "dynamic.gflops"]
    assert rate["workloads"][5] == CELL and rate["bound"] == 0.05
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    # on every list that holds the twin, right after it, but the one whose
    # arithmetic does not hold over four chips
    for name, m in per_layer.items():
        cells = m.get("workloads", [])
        if TWIN in cells and name not in NOT_LISTED:
            assert cells[cells.index(TWIN) + 1] == CELL, name
        if name in NOT_LISTED:
            assert CELL not in cells, name
        if name in PHASES:
            assert TWIN not in cells and cells[-1] == CELL, name
    names = [m["name"] for m in manifest["per_layer"]]
    assert [names.index(n) for n in NEW] == [35, 36, 37, 38, 39]
    for name in NEW:
        m = per_layer[name]
        assert m["workloads"] == [CELL] and m["moves"] == "dynamic.gflops"
    assert [(per_layer[n]["unit"], per_layer[n]["better"],
             per_layer[n]["source"], per_layer[n]["layer"]) for n in NEW] == [
        ("GB/solve", "lower", "program_counter", "device module"),
        ("ms/solve", "lower", "program_span", "device module"),
        ("%", "lower", "program_counter", "device module"),
        ("%", "higher", "device_trace", "device"),
        ("GB/solve", "lower", "program_counter", "device module")]


# what the rehearsal's process runs before the cell: "" is the program as it
# is; the others move TSMQR's writers from chip to chip (the owner rule never
# does), and the last takes both protections away
_MOVE = """
from parsec_tpu.device.device import DeviceRegistry, task_load
_best = DeviceRegistry.best_device
def best_device(self, task, device_type=None, allowed=None):
    if task.selected_device is None and task.task_class.name == "TSMQR":
        accel = [d for d in self.devices if d.type != "cpu" and d.enabled]
        dev = accel[(task.locals["k"] + task.locals["n"]) % len(accel)]
        task.selected_device = dev
        dev.load_add(task_load(task, dev))
    return _best(self, task, device_type, allowed)
DeviceRegistry.best_device = best_device
"""
FAULTS = {
    "none": "",
    "writers_move": _MOVE,
    "writers_move_and_the_stale_flush_wins": _MOVE + """
from parsec_tpu.device import tpu
tpu.TPUDevice._invalidate_elsewhere = lambda self, d: None
tpu._host_is_newer = lambda host, copy: False
""",
}


def _rehearse(fault: str) -> dict:
    code = f"""
import json, os, sys
os.environ["PARSEC_MCA_device_tpu_allow_cpu"] = "1"
sys.path[:0] = [{BENCH!r}, {ROOT!r}]
{FAULTS[fault]}
import run
out = run.run_cell(["--workload", {CELL!r}, "--seed", "2147484040",
                    "--seconds", "1", "--trace", "1", "--rehearse"])
from parsec_tpu.device import registry
print("RESULT " + json.dumps({{"correct": out["correct"],
                              "compared": out["compared"],
                              "metrics": out["metrics"],
                              "device": out["device"],
                              "states": [d.debug_state()
                                         for d in registry.devices
                                         if d.type != "cpu"]}}))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT ")]
    assert lines, proc.stderr[-3000:]
    return json.loads(lines[-1][7:])


def _sum(states, key):
    return sum(s[key] for s in states)


def test_a_traced_rehearsal_over_four_devices_is_correct_and_reports():
    out = _rehearse("none")
    assert out["correct"], out["compared"]
    assert out["device"]["count"] == 4
    metrics, states = out["metrics"], out["states"]
    # the device trace's balance needs a chip; the other four are counted
    assert set(NEW) - {BALANCE} <= set(metrics) and BALANCE not in metrics
    assert "kernel.tsmqr_roofline" not in metrics
    assert "devmod.panel_tasks_per_xla_call" in metrics
    assert PHASES <= set(metrics)
    assert 0.0 <= metrics["devmod.device_wait_share"]["value"] <= 100.0
    assert metrics["host.unowned_share.dynamic"]["value"] <= 100.0
    solves = _sum(states, "executed_tasks") // 204
    assert _sum(states, "executed_tasks") == 204 * solves
    ran = [s["executed_tasks"] for s in states]
    assert len(ran) == 4 and min(ran) > 0
    assert metrics[BUSIEST]["value"] == pytest.approx(100 * max(ran)
                                                      / sum(ran))
    assert 25.0 <= metrics[BUSIEST]["value"] <= 40.0
    # 8 x 8 tiles of 128: A's 64 tiles and T's 36 from the host, once each
    tile = 128 * 128 * 4
    assert metrics["devmod.h2d_gb_per_solve"]["value"] == pytest.approx(
        100 * tile / 1e9)
    assert _sum(states, "bytes_in") == 100 * tile * solves
    assert _sum(states, "bytes_d2d") == _sum(states, "d2d_tiles") * tile > 0
    assert metrics[D2D_GB]["value"] == pytest.approx(
        _sum(states, "bytes_d2d") / solves / 1e9)
    assert metrics[D2D_MS]["unit"] == "ms/solve"
    assert 0.0 < metrics[D2D_MS]["value"] < \
        metrics["devmod.stage_in_ms_per_solve"]["value"] + \
        metrics[D2D_MS]["value"]
    assert metrics[DROPPED]["value"] == 0.0
    # what a flood popped for another chip went back, and is counted
    assert metrics["sched.flood_putbacks_per_task"]["value"] == pytest.approx(
        _sum(states, "flood_putbacks") / _sum(states, "executed_tasks"))
    assert _sum(states, "flood_putbacks") > 0
    assert metrics["devmod.evicted_gb_per_solve"]["value"] == 0.0
    assert _sum(states, "invalidated_copies") == 0     # one writer a tile


def test_writers_that_move_between_chips_leave_a_sound_result():
    out = _rehearse("writers_move")
    assert out["correct"], out["compared"]
    assert _sum(out["states"], "invalidated_copies") > 0
    assert out["compared"]["tiles_absent"]["value"] == 0


def test_planted_stale_flush_reads_not_correct():
    out = _rehearse("writers_move_and_the_stale_flush_wins")
    assert out["correct"] is False
    compared = out["compared"]
    assert compared["tasks_off"]["value"] == 0
    assert compared["probe_gap"]["value"] > compared["probe_gap"]["limit"] \
        or compared["tiles_absent"]["value"] > 0, compared


def _dev(**kw):
    return types.SimpleNamespace(type="tpu", **kw)


def _run(solves=3, warmup=1, trace=None, peaks=True):
    return {"window": types.SimpleNamespace(solves=solves),
            "cell": types.SimpleNamespace(
                traffic={"warmup_solves": warmup}, chips=4),
            "trace": trace, "peaks": {"flops_per_s": 197e12} if peaks
            else None}


@pytest.mark.parametrize("name,devices,run,expect", [
    # a program that declares bytes_d2d and counts nothing in it (the parent)
    (D2D_GB, [_dev(bytes_d2d=0)], _run(), None),
    (D2D_GB, [types.SimpleNamespace(type="cpu", bytes_d2d=7, d2d_tiles=1),
              _dev(bytes_d2d=6e9, d2d_tiles=1500),
              _dev(bytes_d2d=2e9, d2d_tiles=500)], _run(), 2.0),
    (D2D_GB, [_dev(bytes_d2d=0, d2d_tiles=0)], _run(), 0.0),
    (D2D_GB, [_dev(bytes_d2d=1, d2d_tiles=1)], _run(0, 0), None),
    (DROPPED, [_dev(evicted_bytes=5)], _run(), None),
    (DROPPED, [_dev(replica_bytes_dropped=3e9),
               _dev(replica_bytes_dropped=5e9)], _run(), 2.0),
    (BUSIEST, [_dev(executed_tasks=0)], _run(), None),
    (BUSIEST, [types.SimpleNamespace(type="cpu", executed_tasks=900),
               _dev(executed_tasks=30), _dev(executed_tasks=25),
               _dev(executed_tasks=25), _dev(executed_tasks=20)], _run(),
     30.0),
    (BUSIEST, [_dev(executed_tasks=816)], _run(), 100.0)])
def test_counter_readers_over_the_registry(monkeypatch, name, devices, run,
                                           expect):
    from parsec_tpu.device import registry
    monkeypatch.setattr(registry, "devices", devices)
    got = _reader(name).read(run)
    assert got == (expect if expect is None else pytest.approx(expect))


@pytest.mark.parametrize("run,expect", [
    (_run(), None),                                     # an untraced run
    (_run(trace={"busy_s": 3.0, "busiest_busy_s": 4.0}, peaks=False), None),
    (_run(trace={"busy_s": 0.0, "busiest_busy_s": 0.0}), None),
    (_run(trace={"busy_s": 3.0, "busiest_busy_s": 4.0}), 75.0),
    (_run(trace={"busy_s": 1.0, "busiest_busy_s": 4.0}), 25.0)])
def test_busy_balance_reader_divides_the_mean_by_the_busiest(run, expect):
    assert _reader(BALANCE).read(run) == expect


@pytest.mark.parametrize("table,solves,expect", [
    ({}, 3, None),                                      # no phase plane
    ({"devmod.stage_in": 1.0}, 3, None),                # no tile crossed
    ({"devmod.d2d": 1.0}, 0, None),
    ({"devmod.d2d": 1.5, "devmod.stage_in": 9.0}, 3, 500.0)])
def test_d2d_ms_reader_takes_the_one_row(monkeypatch, table, solves, expect):
    reader = _reader(D2D_MS)
    monkeypatch.setattr(reader, "self_seconds", lambda: table)
    got = reader.read(_run(solves))
    assert got == (expect if expect is None else pytest.approx(expect))
