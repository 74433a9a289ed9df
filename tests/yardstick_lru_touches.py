"""``devmod.lru_touches_per_hit``: the manifest lists it after the forty-four
entries before it, on the dynamic cells of the PTG front end (the six of
its PR and ``hqr128kx8k.dynamic``, appended since) and not on
``gemm16k.dtd`` (``yardstick_stage_in_ms.py`` holds the set of metrics the
DTD cell shares with its twin); its reader is held to hand-made accelerators
(a program from before the touch, which lacks the counter, no hit yet, sums
over the accelerators); a traced rehearsal of a dynamic cell reports a share under
100.  No chip needed.  Collected by ``test_benchmark_yardstick.py`` with the
benchmark's own tests, so that every traced rehearsal of the suite runs on
one worker."""

import json
import math
import os
import types

import pytest

from yardstick_writeback_early_share import BENCH, ROOT, _load, _rehearse

NAME = "devmod.lru_touches_per_hit"
CELLS = ["gemm16k.dynamic", "potrf16k.dynamic", "potrf64k.dynamic",
         "geqrf32k.dynamic", "geqrf52k.ctx4", "getrf44k.dynamic",
         "hqr128kx8k.dynamic"]


def test_manifest_lists_the_touch_share_on_the_ptg_dynamic_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # appended after the 44 entries before it (a later one appends after it)
    assert bench["per_layer"][43]["name"] == "devmod.swap_tasks_per_xla_call"
    assert bench["per_layer"][44] == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "device module",
        "moves": "dynamic.gflops", "workloads": CELLS}
    # in the manifest's order: its dynamic cells less the DTD one
    assert CELLS == [w["name"] for w in bench["workloads"]
                     if w["traffic"] == "dynamic_host_tiles"]


def _accelerator(**counters):
    return types.SimpleNamespace(type="tpu", **counters)


REGISTRIES = [
    # a program from before the touch: accelerators without the counter
    ("counter_absent", [types.SimpleNamespace(type="cpu"),
                        _accelerator(cache_hits=90)], None),
    ("no_hit_yet", [_accelerator(lru_touches=0, cache_hits=0)], None),
    ("every_hit_touched", [_accelerator(lru_touches=40, cache_hits=40)],
     100.0),
    # sums over sums; the host's device does not count
    ("four_accelerators",
     [types.SimpleNamespace(type="cpu", lru_touches=99, cache_hits=1),
      _accelerator(lru_touches=600, cache_hits=1000),
      _accelerator(lru_touches=0, cache_hits=0),
      _accelerator(lru_touches=150, cache_hits=250),
      _accelerator(lru_touches=50, cache_hits=50)], 800 / 13)]


@pytest.mark.parametrize("case", REGISTRIES, ids=[r[0] for r in REGISTRIES])
def test_touch_share_reader_over_the_registry(monkeypatch, case):
    from parsec_tpu.device import registry
    _, devices, want = case
    monkeypatch.setattr(registry, "devices", devices)
    got = _load(os.path.join(BENCH, "layer_metrics", NAME + ".py")).read({})
    assert got == want if want is None else math.isclose(got, want), got


def test_a_traced_rehearsal_reports_a_touch_share_under_100(monkeypatch):
    """The 16k Cholesky at the rehearsal's size on one accelerator: batches
    whose tasks share an input touch each such tile once."""
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=1")
    metrics = _rehearse("potrf16k.dynamic")
    assert metrics[NAME]["unit"] == "%"
    assert 0.0 < metrics[NAME]["value"] < 100.0
