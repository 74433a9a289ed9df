"""Tier-1 guards the yardstick: the tests that live with the benchmark
(``benchmarks/tests/``: the manifest, the trace reduction, the control, a
traced rehearsal of every cell sound and broken, the phase metrics) are
collected here under their own names, so each counts.  They need no chip;
the rehearsals run in processes of their own."""

import importlib.util
import os

_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "benchmarks", "tests")

for _name in ("test_yardstick", "test_phase_metrics"):
    _spec = importlib.util.spec_from_file_location(
        f"benchmarks_tests_{_name}", os.path.join(_DIR, _name + ".py"))
    _mod = importlib.util.module_from_spec(_spec)
    _spec.loader.exec_module(_mod)
    globals().update({k: v for k, v in vars(_mod).items()
                      if k.startswith("test_")})
