"""``benchmarks/tests/test_geqrf32k.py`` asserts, among what it holds of the
manifest, that no cell asks for more than one chip, which stopped being true
when PR 40 appended ``geqrf52k.ctx4`` (a PR that adds a cell appends, and may
not edit a file the benchmark has).  This is that test without that one
assertion; tier-1's collector (``test_benchmark_yardstick.py``) takes it in
the other's place, so the count stays and what the test holds of the QR cell
holds again (PERF.md, section 7, names the line for a ``benchmark`` issue).
``benchmarks/tests/test_geqrf52k_ctx4.py`` asserts which cells have which
chips.

``benchmarks/tests/test_phase_metrics.py`` holds the lists of the five phase
metrics to the two 16k cells, letter for letter; PR 40's review had the
four-chip cell appended to them (its two largest host costs,
``sched.release`` and ``devmod.inflight_wait``, are what two of the five
read).  The second test here is that one with the lists held by their first
two entries, and takes its place in the same way."""

import json
import os

from yardstick_writeback_early_share import BENCH, ROOT, _load

_qr = _load(os.path.join(BENCH, "tests", "test_geqrf32k.py"))


def test_manifest_still_lists_the_qr_cell_where_it_was_appended():
    CELL, CONFIG = _qr.CELL, _qr.CONFIG
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    assert [w["name"] for w in manifest["workloads"]].index(CELL) == 5
    cell = manifest["workloads"][5]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "dynamic_host_tiles", 1)
    conf = manifest["configs"][4]
    assert (conf["name"], conf["reduced"]) == (CONFIG, [])
    with open(os.path.join(ROOT, conf["file"])) as f:
        assert json.load(f)["source"] == conf["source"]
    (rate,) = [m for m in manifest["end_to_end"]
               if m["name"] == "dynamic.gflops"]
    assert rate["workloads"][4] == CELL and rate["bound"] == 0.05
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    listed = {n for n, m in per_layer.items()
              if CELL in m.get("workloads", [CELL])}
    # at least these: a later PR may list the cell on a metric it adds
    assert _qr.LISTED | {_qr.ROOFLINE, _qr.PANEL} <= listed
    assert per_layer[_qr.ROOFLINE]["workloads"] == [CELL]
    # PR 40 appended its cell to the panel's list
    assert per_layer[_qr.PANEL]["workloads"][0] == CELL
    for name in (_qr.ROOFLINE, _qr.PANEL):
        assert per_layer[name]["moves"] == "dynamic.gflops"
    assert (per_layer[_qr.ROOFLINE]["unit"], per_layer[_qr.ROOFLINE]["source"],
            per_layer[_qr.ROOFLINE]["layer"]) == \
        ("%", "device_trace", "kernels")
    assert (per_layer[_qr.PANEL]["source"], per_layer[_qr.PANEL]["layer"]) == \
        ("program_counter", "device module")
    # where they were appended; what a later PR appends comes after them
    names = [m["name"] for m in manifest["per_layer"]]
    assert (names.index(_qr.ROOFLINE), names.index(_qr.PANEL)) == (28, 29)


def test_manifest_still_lists_the_phase_metrics_on_the_two_16k_cells():
    phase = _load(os.path.join(BENCH, "tests", "test_phase_metrics.py"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in phase.PHASE_METRICS:
        m = per_layer[name]
        assert m["source"] == "program_span"
        assert m["moves"] == "dynamic.gflops"
        assert m["workloads"][:2] == ["gemm16k.dynamic", "potrf16k.dynamic"]
        # what was appended since: the four-chip cell (PR 40)
        assert m["workloads"][2:] == ["geqrf52k.ctx4"]
