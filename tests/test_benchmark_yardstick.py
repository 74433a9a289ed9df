"""Tier-1 guards the yardstick: the tests that live with the benchmark
(``benchmarks/tests/``: the manifest, the trace reduction, the control, a
traced rehearsal of every cell sound and broken, the phase metrics, the 64k
cell's own, the DTD cell's own, the QR cell's own, the four-chip cell's own,
the pivoted LU cell's own, the hierarchical QR cell's own)
and ``yardstick_writeback_early_share.py``, ``yardstick_flood_metrics.py``,
``yardstick_stage_in_ms.py``, ``yardstick_dispatch_metrics.py``,
``yardstick_donated_share.py``, ``yardstick_qr_cell.py``,
``yardstick_ring_excused_share.py``, ``yardstick_getrf_cell.py``,
``yardstick_lru_touches.py`` and ``yardstick_hqr_cell.py`` beside this
file are collected here under
their own names, so each counts, and a name that two files give is an error
here and not one test fewer.  They need no chip.  The rehearsals run in
processes of their own, and all from this one file, so that under ``--dist
loadfile`` no two of them trace one cell at once (they would share
``.bench_trace/<cell>``).

One test is not taken over: ``test_potrf64k.py`` holds every list of the
dynamic cells to *end* with the 64k cell, which stopped being true when PR 34
appended ``gemm16k.dtd`` (a PR that adds a cell appends, and may not edit a
file the benchmark has).  ``test_dtd_gemm.py`` asserts the same of the lists'
third entry, so the count stays and the assertion holds again.  Nor is
``test_dtd_gemm.py``'s list of the metrics that the DTD cell shares with its
twin, which stopped being whole when PR 35 added one: the assertion is
``yardstick_stage_in_ms.py``'s, with that one among them.  Nor is
``test_geqrf32k.py``'s test of the manifest, which asserts that every cell has
one chip and stopped holding when PR 40 appended ``geqrf52k.ctx4``:
``yardstick_qr_cell.py`` is that test without that one assertion.  Nor are
the two tests that stopped holding when PR 42 gave the start-up metric the
accepted cells' list: ``yardstick_getrf_cell.py`` has them as they hold
now, and they run where the others stood.  Nor is ``test_getrf44k.py``'s
test of the manifest, which holds the shared lists to end with its cell and
stopped holding when ``hqr128kx8k.dynamic`` was appended:
``yardstick_hqr_cell.py`` has it with the later cell after it."""

import importlib.util
import os

_HERE = os.path.dirname(os.path.abspath(__file__))
_BENCH = os.path.join(os.path.dirname(_HERE), "benchmarks", "tests")

_SUPERSEDED = {
    # by test_manifest_still_lists_the_64k_cell_third_on_the_dynamic_lists
    "test_manifest_lists_the_64k_cell_where_its_readers_find_something",
    # by test_manifest_lists_the_dtd_cell_on_the_twin_s_metrics_that_read_it
    "test_manifest_lists_the_dtd_cell_where_its_readers_find_something",
    # by test_manifest_still_lists_the_qr_cell_where_it_was_appended
    "test_manifest_lists_the_qr_cell_where_it_was_appended",
    # by test_manifest_still_lists_the_phase_metrics_on_the_two_16k_cells
    "test_manifest_lists_the_phase_metrics_on_the_dynamic_cells_only",
    # by test_manifest_still_lists_the_getrf_cell_where_it_was_appended
    "test_manifest_lists_the_getrf_cell_where_it_was_appended"}
# tests of yardstick_getrf_cell.py that take the place of one that stopped
# holding (PR 42), where it stood in the run: the file's order decides what
# its rehearsals meet beside them on the other workers
_IN_PLACE = {
    "test_rehearsal_correct_unless_broken":
        "test_rehearsal_correct_unless_broken_start_up_where_listed",
    "test_manifest_still_lists_the_64k_cell_third_on_the_dynamic_lists":
        "test_manifest_still_lists_the_64k_cell_third_on_the_dynamic_lists_only"}


def _load(folder: str, name: str):
    spec = importlib.util.spec_from_file_location(
        f"benchmarks_tests_{name}", os.path.join(folder, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_getrf_cell = _load(_HERE, "yardstick_getrf_cell")
for _dir, _name in ((_BENCH, "test_yardstick"), (_BENCH, "test_phase_metrics"),
                    (_BENCH, "test_potrf64k"), (_BENCH, "test_dtd_gemm"),
                    (_BENCH, "test_geqrf32k"), (_BENCH, "test_geqrf52k_ctx4"),
                    (_BENCH, "test_getrf44k"), (_BENCH, "test_hqr128kx8k"),
                    (_HERE, "yardstick_writeback_early_share"),
                    (_HERE, "yardstick_flood_metrics"),
                    (_HERE, "yardstick_stage_in_ms"),
                    (_HERE, "yardstick_dispatch_metrics"),
                    (_HERE, "yardstick_donated_share"),
                    (_HERE, "yardstick_qr_cell"),
                    (_HERE, "yardstick_ring_excused_share"),
                    (_HERE, "yardstick_lru_touches"),
                    (_HERE, "yardstick_hqr_cell")):
    _tests = {}
    for _k, _v in vars(_load(_dir, _name)).items():
        if _k in _IN_PLACE:
            _k = _IN_PLACE[_k]
            _v = getattr(_getrf_cell, _k)
        if _k.startswith("test_") and _k not in _SUPERSEDED:
            _tests[_k] = _v
    _twice = sorted(set(_tests) & set(globals()))
    if _twice:
        raise ImportError(f"{_name}.py gives test names another file of the "
                          f"yardstick already gave: {_twice}")
    globals().update(_tests)
