"""``sched.flood_us_per_task`` and ``sched.flood_putbacks_per_task`` (PR 30):
every dynamic cell lists both, a traced rehearsal of a dynamic cell reports
the span's share and no put-back (the default scheduler's ready queue is keyed
by task class), the lowered cell reports neither, and a program without the
counter (the parent of PR 30) reads as nothing, not as an error.  No chip
needed.  Collected by ``test_benchmark_yardstick.py`` with the benchmark's
own tests, so that every traced rehearsal of the suite runs on one worker."""

import json
import math
import os
import types

import pytest

from yardstick_writeback_early_share import BENCH, ROOT, _load, _rehearse

US, PUTBACKS = "sched.flood_us_per_task", "sched.flood_putbacks_per_task"


def _reader(name):
    return _load(os.path.join(BENCH, "layer_metrics", name + ".py"))


@pytest.mark.parametrize("name,unit,source", [
    (US, "us/task", "program_span"),
    (PUTBACKS, "tasks/task", "program_counter")])
def test_manifest_lists_the_flood_metric_on_every_dynamic_cell(
        name, unit, source):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (m,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert (m["unit"], m["better"], m["source"]) == (unit, "lower", source)
    assert (m["layer"], m["moves"]) == ("host scheduler", "dynamic.gflops")
    # every cell whose solves go through the device module's flood: the
    # dynamic cells and, since PR 34, the DTD cell
    assert m["workloads"] == [w["name"] for w in bench["workloads"]
                              if w["traffic"] in ("dynamic_host_tiles",
                                                  "dtd_host_tiles")]


def _dev(**kw):
    return types.SimpleNamespace(type="tpu", **kw)


@pytest.mark.parametrize("devices,expect", [
    # the parent of PR 30: accelerators without the counter
    ([_dev(executed_tasks=816)], None),
    # no task ran yet
    ([_dev(executed_tasks=0, flood_putbacks=0)], None),
    # summed over the accelerators; the host's device does not count
    ([types.SimpleNamespace(type="cpu", executed_tasks=5),
      _dev(executed_tasks=600, flood_putbacks=0),
      _dev(executed_tasks=200, flood_putbacks=1200)], 1.5)])
def test_putbacks_reader_over_the_registry(monkeypatch, devices, expect):
    from parsec_tpu.device import registry
    monkeypatch.setattr(registry, "devices", devices)
    assert _reader(PUTBACKS).read({}) == expect


@pytest.mark.parametrize("table,tasks,expect", [
    ({}, 816, None),                            # no phase plane
    ({"sched.release": 1.0}, 816, None),        # the flood never ran
    ({"sched.flood": 0.01, "sched.release": 1.0}, 0, None),
    ({"sched.flood": 0.00816, "sched.release": 1.0}, 816, 10.0)])
def test_flood_us_reader_takes_the_one_row(monkeypatch, table, tasks, expect):
    monkeypatch.syspath_prepend(BENCH)          # the reader's ``phases``
    reader = _reader(US)
    monkeypatch.setattr(reader, "self_seconds", lambda: table)
    window = types.SimpleNamespace(counters={"executed_tasks": tasks})
    got = reader.read({"window": window})
    assert got == expect if expect is None else math.isclose(got, expect)


def test_a_traced_rehearsal_reports_the_flood_and_no_put_back(monkeypatch):
    # one accelerator, as in the cells: under the suite's eight virtual CPU
    # devices the flood hands back the tasks that are another device's
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=1")
    metrics = _rehearse("potrf16k.dynamic")
    assert metrics[PUTBACKS] == {"value": 0.0, "unit": "tasks/task"}
    assert metrics[US]["unit"] == "us/task"
    assert 0.0 < metrics[US]["value"] < \
        metrics["sched.flood_release_us_per_task"]["value"]
    assert not {US, PUTBACKS} & set(_rehearse("gemm16k.lowered"))
