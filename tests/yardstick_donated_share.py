"""``devmod.donated_result_share`` (PR 39): the manifest lists it after the
thirty-four PR 38 left, on the five dynamic cells; its reader is held to
hand-made call tables (no table, rows of the parent of PR 39, which lack the
field, rows that add up over classes and accelerators, a window that handed
nothing back); a traced rehearsal of a dynamic cell reports it and the lowered
cell does not.  No chip needed.  Collected by ``test_benchmark_yardstick.py``
with the benchmark's own tests, so that every traced rehearsal of the suite
runs on one worker."""

import json
import math
import os
import types

import pytest

from yardstick_dispatch_metrics import FIVE, _accelerator, _row
from yardstick_writeback_early_share import BENCH, ROOT, _load, _rehearse

NAME = "devmod.donated_result_share"


def test_manifest_lists_the_donated_share_after_the_dispatch_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # appended after the 34 entries PR 38 left (a later PR appends after it)
    assert bench["per_layer"][33]["name"] == "devmod.held_already_run_share"
    m = dict(bench["per_layer"][34])
    assert m.pop("workloads")[:5] == FIVE
    assert m == {"name": NAME, "unit": "%", "better": "higher",
                 "source": "program_counter", "layer": "device module",
                 "moves": "dynamic.gflops"}


def _donating(row: dict, donated: int) -> dict:
    return dict(row, results_donated=donated)


# ten GEMM calls of 64 lanes, every result on its predecessor's buffer; four
# TRSM calls of 16 lanes, one of which found a tile kept elsewhere; two tasks
# submitted alone, whose bodies donate nothing
GEMM = _donating(_row(calls=10, tasks=640, results=640), 640)
TRSM = _donating(_row("TRSM", 16, calls=4, tasks=60, results=64), 48)
POTRF = _donating(_row("POTRF", 1, calls=2, tasks=2, results=2), 0)

TABLES = [
    ("no_table", [types.SimpleNamespace(type="cpu"),
                  types.SimpleNamespace(type="tpu")], None),
    ("empty_table", [_accelerator()], None),
    # the parent of PR 39: rows without the field
    ("rows_of_the_parent", [_accelerator(_row(calls=10, results=640))], None),
    ("a_row_of_the_parent_among_them",
     [_accelerator(GEMM, _row("TRSM", 16, calls=4, results=64))], None),
    ("every_result", [_accelerator(GEMM)], 100.0),
    ("sums_over_sums", [_accelerator(GEMM, TRSM, POTRF)],
     100.0 * 688 / 706),
    ("two_accelerators", [_accelerator(GEMM, POTRF), _accelerator(TRSM)],
     100.0 * 688 / 706),
    ("nothing_donated", [_accelerator(POTRF)], 0.0),
    ("no_result", [_accelerator(_donating(_row(calls=3), 0))], None)]


@pytest.mark.parametrize("case", TABLES, ids=[t[0] for t in TABLES])
def test_donated_share_reader_over_a_hand_made_call_table(monkeypatch, case):
    from parsec_tpu.device import registry
    _, devices, want = case
    monkeypatch.syspath_prepend(BENCH)          # the reader's ``call_table``
    monkeypatch.setattr(registry, "devices", devices)
    got = _load(os.path.join(BENCH, "layer_metrics", NAME + ".py")).read({})
    assert got == want if want is None else math.isclose(got, want), got


def test_a_traced_rehearsal_reports_the_donated_share_and_the_lowered_none(
        monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=1")
    metrics = _rehearse("gemm16k.dynamic")
    assert metrics[NAME]["unit"] == "%"
    # one RW chain a C tile, no pad lane, nothing kept elsewhere
    assert metrics[NAME]["value"] == 100.0
    assert NAME not in _rehearse("gemm16k.lowered")
