"""``devmod.ring_excused_share`` (PR 41): the manifest lists it after the forty
PR 40 left, on the four-chip cell alone; its reader is held to hand-made
accelerators (the parent of PR 41, which lacks the counters, a ring that
never met its count, sums over the accelerators); a traced rehearsal of the
four-chip cell with the count cut to 2 reports what the devices counted, and
a one-accelerator cell does not list it.  No chip needed.  Collected by
``test_benchmark_yardstick.py`` with the benchmark's own tests, so that every
traced rehearsal of the suite runs on one worker."""

import json
import os
import types

import pytest

from yardstick_writeback_early_share import BENCH, ROOT, _load

NAME = "devmod.ring_excused_share"
CELL = "geqrf52k.ctx4"


def test_manifest_lists_the_excused_share_on_the_four_chip_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # appended after the 40 entries PR 40 left (a later PR appends after it)
    assert bench["per_layer"][39]["name"] == \
        "devmod.replica_gb_dropped_per_solve"
    assert bench["per_layer"][40] == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "device module",
        "moves": "dynamic.gflops", "workloads": [CELL]}


def _accelerator(**counters):
    return types.SimpleNamespace(type="tpu", **counters)


REGISTRIES = [
    # the parent of PR 41: accelerators without the counters
    ("counters_absent", [types.SimpleNamespace(type="cpu"), _accelerator()],
     None),
    ("the_count_never_met", [_accelerator(ring_excused=0, ring_bounded=0)],
     None),
    ("one_accelerator_always_waits",
     [_accelerator(ring_excused=0, ring_bounded=1800)], 0.0),
    ("never_waited", [_accelerator(ring_excused=7, ring_bounded=0),
                      _accelerator(ring_excused=0, ring_bounded=0)], 100.0),
    # sums over sums; the host's device does not count
    ("four_accelerators",
     [types.SimpleNamespace(type="cpu", ring_excused=99, ring_bounded=1),
      _accelerator(ring_excused=900, ring_bounded=100),
      _accelerator(ring_excused=50, ring_bounded=150),
      _accelerator(ring_excused=0, ring_bounded=0),
      _accelerator(ring_excused=250, ring_bounded=50)], 80.0)]


@pytest.mark.parametrize("case", REGISTRIES, ids=[r[0] for r in REGISTRIES])
def test_excused_share_reader_over_the_registry(monkeypatch, case):
    from parsec_tpu.device import registry
    _, devices, want = case
    monkeypatch.setattr(registry, "devices", devices)
    got = _load(os.path.join(BENCH, "layer_metrics", NAME + ".py")).read({})
    assert got == want


def test_a_traced_rehearsal_over_four_devices_reports_what_they_counted(
        monkeypatch):
    """The count is cut to 2 for the rehearsal's process, through the
    environment the parameter reads, so that every chip's ring meets it
    whatever the stand-in's pace (204 tasks a solve)."""
    ctx4 = _load(os.path.join(BENCH, "tests", "test_geqrf52k_ctx4.py"))
    monkeypatch.setenv("PARSEC_MCA_device_tpu_max_inflight", "2")
    out = ctx4._rehearse("none")
    assert out["correct"], out["compared"]
    excused = sum(s["ring_excused"] for s in out["states"])
    bounded = sum(s["ring_bounded"] for s in out["states"])
    assert excused + bounded > 0
    assert all(2 <= s["ring_peak"] <= s["xla_calls"] for s in out["states"])
    assert out["metrics"][NAME] == {
        "value": pytest.approx(100.0 * excused / (excused + bounded)),
        "unit": "%"}
    # every dispatch left its ring: confirmed in ``sync`` if not before
    assert all(s["inflight_dispatches"] == 0 for s in out["states"])
    # a cell with one accelerator does not list it (and has no peer to ask)
    rehearse = _load(os.path.join(BENCH, "tests",
                                  "test_phase_metrics.py"))._rehearse
    assert NAME not in rehearse("potrf16k.dynamic")
