"""``devmod.writeback_early_share`` (PR 28): a traced rehearsal of a dynamic
cell reads 100% (every result tile of both graphs leaves through a memory
edge), the lowered cell reports nothing, and a program without the counters
(the parent of PR 28) reads as nothing, not as an error.  No chip needed;
the rehearsals run in processes of their own
(``benchmarks/tests/test_phase_metrics.py:_rehearse``).  Collected by
``test_benchmark_yardstick.py`` with the benchmark's own tests, so that every
traced rehearsal of the suite runs on one worker: two at once of one cell
would share ``.bench_trace/<cell>`` and lose each other's trace."""

import importlib.util
import json
import os
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
NAME = "devmod.writeback_early_share"


def _load(path: str):
    spec = importlib.util.spec_from_file_location(
        os.path.basename(path)[:-3].replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_rehearse = _load(os.path.join(BENCH, "tests",
                              "test_phase_metrics.py"))._rehearse
_reader = _load(os.path.join(BENCH, "layer_metrics", NAME + ".py"))


def test_manifest_lists_the_early_share_on_the_dynamic_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        (m,) = [m for m in json.load(f)["per_layer"] if m["name"] == NAME]
    assert (m["unit"], m["better"], m["source"]) == \
        ("%", "higher", "program_counter")
    assert (m["layer"], m["moves"]) == ("device module", "dynamic.gflops")
    assert m["workloads"][:2] == ["gemm16k.dynamic", "potrf16k.dynamic"]
    # the cells that write tiles back through the device module
    assert all(w.endswith((".dynamic", ".dtd", ".ctx4")) for w in m["workloads"])


@pytest.mark.parametrize("devices,expect", [
    # the parent of PR 28: accelerators without the counters
    ([types.SimpleNamespace(type="tpu")], None),
    # nothing written back yet
    ([types.SimpleNamespace(type="tpu", writebacks=0, writebacks_early=0)],
     None),
    # summed over the accelerators; the host's device does not count
    ([types.SimpleNamespace(type="cpu"),
      types.SimpleNamespace(type="tpu", writebacks=6, writebacks_early=6),
      types.SimpleNamespace(type="tpu", writebacks=2, writebacks_early=0)],
     75.0)])
def test_early_share_reader_over_the_registry(monkeypatch, devices, expect):
    from parsec_tpu.device import registry
    monkeypatch.setattr(registry, "devices", devices)
    assert _reader.read({}) == expect


def test_a_traced_rehearsal_reads_every_tile_early_and_the_lowered_none():
    value = _rehearse("potrf16k.dynamic")[NAME]
    assert value == {"value": 100.0, "unit": "%"}
    assert NAME not in _rehearse("gemm16k.lowered")
