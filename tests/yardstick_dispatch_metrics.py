"""The four metrics that read inside ``devmod.dispatch`` (PR 38): the manifest
lists them after the thirty PR 37 left, each reader is held to hand-made
tables (no table, an empty one, rows that add up over classes, lanes and
accelerators, no result, a ring that held nothing), a traced rehearsal of a
dynamic cell reports all of its own and the lowered cell none.  No chip
needed.  Collected by ``test_benchmark_yardstick.py`` with the benchmark's
own tests, so that every traced rehearsal of the suite runs on one worker."""

import json
import math
import os
import types

import pytest

from yardstick_writeback_early_share import BENCH, ROOT, _load, _rehearse

FIVE = ["gemm16k.dynamic", "potrf16k.dynamic", "potrf64k.dynamic",
        "gemm16k.dtd", "geqrf32k.dynamic"]
MANIFEST = [
    ("devmod.call_us_per_result", "us/result", "lower", "program_span", FIVE),
    ("devmod.dispatch_own_us_per_task", "us/task", "lower", "program_span",
     FIVE),
    ("devmod.chip_queue_depth", "dispatches", "higher", "program_counter",
     FIVE),
    ("devmod.held_already_run_share", "%", "lower", "program_counter",
     ["potrf64k.dynamic", "geqrf32k.dynamic"])]
NAMES = [m[0] for m in MANIFEST]


@pytest.mark.parametrize("position,entry", list(enumerate(MANIFEST, 30)),
                         ids=NAMES)
def test_manifest_lists_the_dispatch_metric_where_the_issue_put_it(position,
                                                                   entry):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # appended after the 30 entries PR 37 left, and nothing before them moved
    # (a later PR appends after these: no length is held)
    assert bench["per_layer"][29]["name"] == "devmod.panel_tasks_per_xla_call"
    m = bench["per_layer"][position]
    name, unit, better, source, cells = entry
    # a later PR may append its cell to a list, and nothing else
    listed = m.pop("workloads")
    assert listed[:len(cells)] == cells
    assert m == {"name": name, "unit": unit, "better": better,
                 "source": source, "layer": "device module",
                 "moves": "dynamic.gflops"}
    # the five: the cells whose solves go through the device module, in the
    # manifest's order
    assert FIVE == [w["name"] for w in bench["workloads"]
                    if w["traffic"] in ("dynamic_host_tiles",
                                        "dtd_host_tiles")][:5]


def _row(task_class="GEMM", lanes=64, **counts):
    row = {"task_class": task_class, "lanes": lanes, "calls": 0, "tasks": 0,
           "args": 0, "results": 0, "call_ns": 0, "depth_sum": 0,
           "held_bytes_sum": 0, "held_run_bytes_sum": 0}
    assert set(counts) <= set(row)
    return dict(row, **counts)


def _accelerator(*rows):
    return types.SimpleNamespace(
        type="tpu", call_table={}, debug_state=lambda: {"call_table": rows})


# ten full GEMM calls of 4 ms each; a quarter of what the ring held had run
GEMM = _row(calls=10, tasks=640, args=1920, results=640, call_ns=40_000_000,
            depth_sum=12, held_bytes_sum=800, held_run_bytes_sum=200)
# two tasks submitted alone behind a queue, the ring holding nothing
POTRF = _row("POTRF", 1, calls=2, tasks=2, args=2, results=2,
             call_ns=2_000_000, depth_sum=5)
# four calls of 15 tasks in 16 lanes into an empty queue, all it held had run
TRSM = _row("TRSM", 16, calls=4, tasks=60, args=128, results=64,
            call_ns=4_000_000, held_bytes_sum=200, held_run_bytes_sum=200)
WINDOW = types.SimpleNamespace(wall_s=2.0, counters={"executed_tasks": 700})

TABLES = [
    # a program without the table (the parent of PR 38): the host device and
    # an accelerator that has no ``call_table``
    ("no_table", [types.SimpleNamespace(type="cpu"),
                  types.SimpleNamespace(type="tpu")], [None, None, None]),
    # the plane never came on: the table is there and empty
    ("empty_table", [_accelerator()], [None, None, None]),
    # one row; over two accelerators the rows add up by class and lanes
    ("one_row", [_accelerator(GEMM)], [62.5, 1.2, 25.0]),
    ("two_accelerators", [_accelerator(GEMM), _accelerator(GEMM)],
     [62.5, 1.2, 25.0]),
    # rows of several classes and lanes: sums over sums, not a mean of rows
    ("a_task_alone_beside", [_accelerator(GEMM, POTRF)],
     [42_000 / 642, 17 / 12, 25.0]),
    ("a_narrow_batch_beside", [_accelerator(GEMM, TRSM)],
     [44_000 / 704, 12 / 14, 40.0]),
    ("three_classes_on_two", [_accelerator(GEMM, POTRF), _accelerator(TRSM)],
     [46_000 / 706, 17 / 16, 40.0]),
    # calls that handed nothing back and a ring that held nothing
    ("no_result", [_accelerator(_row(calls=3, call_ns=3_000_000))],
     [None, 0.0, None])]
READERS = ["devmod.call_us_per_result", "devmod.chip_queue_depth",
           "devmod.held_already_run_share"]


@pytest.mark.parametrize("reader", range(3), ids=READERS)
@pytest.mark.parametrize("case", TABLES, ids=[t[0] for t in TABLES])
def test_reader_over_a_hand_made_call_table(monkeypatch, case, reader):
    from parsec_tpu.device import registry
    _, devices, expect = case
    monkeypatch.syspath_prepend(BENCH)          # the readers' ``call_table``
    monkeypatch.setattr(registry, "devices", devices)
    module = _load(os.path.join(BENCH, "layer_metrics",
                                READERS[reader] + ".py"))
    got = module.read({"window": WINDOW})
    want = expect[reader]
    assert got == want if want is None else math.isclose(got, want), got


@pytest.mark.parametrize("table,tasks,expect", [
    ({}, 700, None),                                    # no phase plane
    # the parent of PR 38: its dispatch holds the call too
    ({"devmod.dispatch": 0.28, "devmod.inflight_wait": 0.01}, 700, None),
    ({"devmod.dispatch": 0.004, "devmod.land": 0.003}, 0, None),
    ({"devmod.dispatch": 0.004, "devmod.land": 0.003, "devmod.call": 0.27},
     700, 10.0)])
def test_own_us_reader_takes_the_gather_and_the_landing(monkeypatch, table,
                                                        tasks, expect):
    monkeypatch.syspath_prepend(BENCH)          # the reader's ``phases``
    reader = _load(os.path.join(BENCH, "layer_metrics",
                                "devmod.dispatch_own_us_per_task.py"))
    monkeypatch.setattr(reader, "self_seconds", lambda: table)
    window = types.SimpleNamespace(counters={"executed_tasks": tasks})
    got = reader.read({"window": window})
    assert got == expect if expect is None else math.isclose(got, expect)


def test_a_traced_rehearsal_reports_the_cell_s_own_and_the_lowered_none(
        monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=1")
    metrics = _rehearse("potrf16k.dynamic")
    # listed on the 16k Cholesky: the first three (the budget's share is the
    # 64k cell's and the QR's)
    assert set(NAMES) & set(metrics) == set(NAMES[:3]), sorted(metrics)
    for name in NAMES[:3]:
        (unit,) = [m[1] for m in MANIFEST if m[0] == name]
        assert metrics[name]["unit"] == unit
        assert math.isfinite(metrics[name]["value"])
        assert metrics[name]["value"] >= 0.0
    # at most the ring's length
    assert metrics["devmod.chip_queue_depth"]["value"] <= 32
    assert not set(NAMES) & set(_rehearse("gemm16k.lowered"))


def test_a_traced_rehearsal_of_the_qr_cell_reports_all_four(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=1")
    metrics = _rehearse("geqrf32k.dynamic")
    assert set(NAMES) <= set(metrics), sorted(metrics)
    assert 0.0 <= metrics["devmod.held_already_run_share"]["value"] <= 100.0
