#!/usr/bin/env python3
"""The control of the output check: the plain reference put in the program's
place with every tile stored in bfloat16 (``reference.py``), compared by the
same number, against the same reference and by the same verdict as a run of
the cell.  It has to come out not correct.  Not part of a benchmark run:

    python3 benchmarks/control.py --workload gemm16k.dynamic --seeds 1 2 3

prints one line per seed, at the cell's own size, on whatever device JAX
finds, and exits 1 if the control passed on any seed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.dirname(HERE)]


def control_compared(cell, seed: int) -> dict:
    """What a run would compare, with the control's tiles in place of the
    program's."""
    prob = cell.problem(seed)
    prob.reference()
    return {"probe_gap": {"value": prob.gap(prob.control()),
                          "limit": cell.limits["probe_gap"]["limit"]}}


def main() -> int:
    import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", default=[1, 2, 3])
    args = ap.parse_args()
    cell = harness.Cell(args.workload)
    import jax
    passed = 0
    for seed in args.seeds:
        compared = control_compared(cell, seed)
        correct = harness.verdict(compared)
        passed += correct
        print(json.dumps({
            "control_of": cell.name, "seed": seed, "N": cell.config["N"],
            "device_kind": jax.devices()[0].device_kind,
            "correct": correct, "compared": compared}), flush=True)
    return 1 if passed else 0


if __name__ == "__main__":
    raise SystemExit(main())
