"""One test of the benchmark's own files stopped holding when
``hqr128kx8k.dynamic`` was appended to the shared lists of the dynamic cells
(a PR that adds a cell appends, and may not edit a file the benchmark has):
``benchmarks/tests/test_getrf44k.py::
test_manifest_lists_the_getrf_cell_where_it_was_appended`` holds every list
the pivoted LU's cell was appended to to *end* with it.  Here the same
assertions hold it to end the cells that the manifest had before it, every
later cell after it, and everything else as it was.

Tier-1's collector (``test_benchmark_yardstick.py``) takes this in the
other's place, so the case still counts."""

import json
import os

from yardstick_writeback_early_share import BENCH, ROOT, _load

_g = _load(os.path.join(BENCH, "tests", "test_getrf44k.py"))


def test_manifest_still_lists_the_getrf_cell_where_it_was_appended():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = [w["name"] for w in manifest["workloads"]]
    assert cells.index(_g.CELL) == 7
    cell = manifest["workloads"][7]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (_g.CONFIG, "dynamic_host_tiles", 1)
    conf = manifest["configs"][6]
    assert (conf["name"], conf["reduced"]) == (_g.CONFIG, ["N"])
    with open(os.path.join(ROOT, conf["file"])) as f:
        assert json.load(f)["source"] == conf["source"]
    (rate,) = [m for m in manifest["end_to_end"]
               if m["name"] == "dynamic.gflops"]
    assert rate["workloads"][6] == _g.CELL and rate["bound"] == 0.05
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    # the seven it held when the pivoted LU's cell came, and the cell
    # appended since
    assert per_layer["startup.fresh_compiles_at_setup"]["workloads"] == \
        _g.ACCEPTED + ["hqr128kx8k.dynamic"]
    listed = {n for n, m in per_layer.items()
              if _g.CELL in m.get("workloads", [_g.CELL])}
    assert _g.LISTED | set(_g.NEW) <= listed
    assert not {"kernel.tsmqr_roofline", "devmod.panel_tasks_per_xla_call",
                "startup.fresh_compiles_at_setup"} & listed
    later = cells[8:]
    for name in _g.LISTED:
        w = per_layer[name]["workloads"]
        # the last of the cells before it; what follows, cells added after
        i = w.index(_g.CELL)
        assert all(cells.index(c) < 7 for c in w[:i]), name
        assert w[i + 1:] == [c for c in later if c in w[i + 1:]], name
    for name in _g.NEW:
        assert per_layer[name]["workloads"] == [_g.CELL]
        assert per_layer[name]["moves"] == "dynamic.gflops"
    for name in (_g.PANEL_ROOF, _g.SWAP_ROOF):
        assert (per_layer[name]["unit"], per_layer[name]["source"],
                per_layer[name]["layer"]) == ("%", "device_trace", "kernels")
    assert (per_layer[_g.SWAPS]["source"], per_layer[_g.SWAPS]["layer"]) == \
        ("program_counter", "device module")
    names = [m["name"] for m in manifest["per_layer"]]
    assert [names.index(n) for n in _g.NEW] == [41, 42, 43]
