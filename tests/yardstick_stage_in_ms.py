"""``devmod.stage_in_ms_per_solve`` (PR 35): every cell whose solves go
through the device module lists it, a traced rehearsal reports the span's
milliseconds beside the exact bytes, the lowered cell reports nothing, and a
window without the span reads as nothing, not as an error.  No chip needed.
Collected by ``test_benchmark_yardstick.py`` with the benchmark's own tests,
so that every traced rehearsal of the suite runs on one worker.

``benchmarks/tests/test_dtd_gemm.py`` holds the set of the twin's metrics that
list the DTD cell to the nine PR 34 knew (``SHARED``), and a PR that adds a
metric may not edit that file: its assertion is made here with the one added,
and tier-1's collector takes this one in its place (PERF.md, section 7).
PR 38's three metrics of the call that list every dynamic cell are among the
shared ones too."""

import json
import math
import os
import types

import pytest

from yardstick_writeback_early_share import BENCH, ROOT, _load, _rehearse

NAME = "devmod.stage_in_ms_per_solve"


def test_manifest_lists_the_stage_in_metric_on_every_dynamic_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # found by name where PR 35 appended it, and nothing before it moved
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index(NAME) == 27 and names[26] == "dtd.tasks_in_window_share"
    m = bench["per_layer"][27]
    assert (m["unit"], m["better"], m["source"]) == \
        ("ms/solve", "lower", "program_span")
    assert (m["layer"], m["moves"]) == ("device module", "dynamic.gflops")
    assert m["workloads"] == [w["name"] for w in bench["workloads"]
                              if w["traffic"] in ("dynamic_host_tiles",
                                                  "dtd_host_tiles")]


def test_manifest_lists_the_dtd_cell_on_the_twin_s_metrics_that_read_it():
    """``test_dtd_gemm.py``'s assertion of the same lists, with the metrics of
    PR 35, PR 38 and PR 39 among the shared ones."""
    dtd = _load(os.path.join(BENCH, "tests", "test_dtd_gemm.py"))
    shared = dtd.SHARED | {NAME, "devmod.call_us_per_result",
                           "devmod.dispatch_own_us_per_task",
                           "devmod.chip_queue_depth",
                           "devmod.donated_result_share",
                           # PR 42 gave it the list of every accepted cell,
                           # in the manifest's order
                           "startup.fresh_compiles_at_setup"}
    manifest, per_layer = dtd._manifest()
    (rate,) = [m for m in manifest["end_to_end"]
               if m["name"] == "dynamic.gflops"]
    assert rate["workloads"][3] == dtd.CELL and rate["bound"] == 0.05
    for name in dtd.DTD_METRICS:
        m = per_layer[name]
        assert m["workloads"] == [dtd.CELL]
        assert (m["moves"], m["layer"]) == ("dynamic.gflops",
                                            "host scheduler")
    # the DTD cell is a list's entry right after the 64k cell or the twin
    # exactly where its readers find something; a later cell comes after it
    for name, m in per_layer.items():
        cells = m.get("workloads", [])
        if dtd.TWIN in cells:
            i = cells.index(dtd.CELL) if dtd.CELL in cells else 0
            follows = i > 0 and cells[i - 1] in (dtd.CELL_64K, dtd.TWIN)
            assert follows is (name in shared), name
        if name in dtd.NOT_LISTED:
            assert dtd.CELL not in m["workloads"], name
    (cell,) = [w for w in manifest["workloads"] if w["name"] == dtd.CELL]
    assert manifest["workloads"][4] is cell and cell["chips"] == 1
    assert (cell["config"], cell["traffic"]) == ("dtd-gemm-16k",
                                                 "dtd_host_tiles")
    assert manifest["configs"][3]["name"] == "dtd-gemm-16k"


@pytest.mark.parametrize("table,solves,expect", [
    ({}, 14, None),                             # no phase plane
    ({"devmod.dispatch": 1.0}, 14, None),       # nothing was staged in
    ({"devmod.stage_in": 1.0}, 0, None),
    ({"devmod.stage_in": 3.5, "devmod.dispatch": 9.0}, 14, 250.0)])
def test_reader_takes_the_one_row_over_the_solves(monkeypatch, table, solves,
                                                  expect):
    monkeypatch.syspath_prepend(BENCH)          # the reader's ``phases``
    reader = _load(os.path.join(BENCH, "layer_metrics", NAME + ".py"))
    monkeypatch.setattr(reader, "self_seconds", lambda: table)
    got = reader.read({"window": types.SimpleNamespace(solves=solves)})
    assert got == expect if expect is None else math.isclose(got, expect)


@pytest.mark.parametrize("cell,tiles", [("gemm16k.dynamic", 3 * 64),
                                        ("gemm16k.dtd", 3 * 64)])
def test_a_traced_rehearsal_reports_it_beside_the_exact_bytes(
        monkeypatch, cell, tiles):
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=1")
    metrics = _rehearse(cell)
    assert metrics[NAME]["unit"] == "ms/solve"
    assert 0.0 < metrics[NAME]["value"] < 1e4
    # no tile twice, none left out: tiles of 128 x 128 floats
    assert metrics["devmod.h2d_gb_per_solve"]["value"] == pytest.approx(
        tiles * 128 * 128 * 4 / 1e9)


def test_the_lowered_cell_reports_nothing():
    assert NAME not in _rehearse("gemm16k.lowered")
