"""``problems/geqrf_tiled.py`` for the cell that runs it on four accelerators
under one ``Context`` (``geqrf-52k-g4``): the same seeded tiles, collections,
PTG, reduction at read-back, plain reference and comparison, and nothing of
its own but the line below.

A program from before PR 40 is not given the cell: on four chips it ran
47,702 of the 48,230 tasks of its first solve in the traffic file's
``solve_timeout_s`` of 300 s and raised (my chip run, PR 40: PERF.md,
section 6, step 0 (b)), and a parent that a check has to wait five minutes
for is worse than one that says at once that it cannot run the cell.  So
this module imports what PR 40 added to the device module, before any data
is made: such a program fails here within its start-up.
"""

from harness import load_module
# the write-back's rule that no flush lowers the host's version: PR 40's
from parsec_tpu.device.tpu import _host_is_newer  # noqa: F401

Problem = load_module("problems", "geqrf_tiled").Problem
