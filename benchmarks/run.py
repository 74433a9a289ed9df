#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (data from the seed, one warm-up through the cell's own entry, the
compile cache of ``parsec_tpu/device/compile_cache.py``), then a window of
``--seconds`` in which the cell's path loops whole solves, then the comparison
of what the window left behind with the plain reference (``reference.py``).
The last line of standard output is the result; every earlier line is
commentary.  Without a TPU, or with fewer chips than the cell asks for, it
exits 2 and prints no result.  ``--rehearse`` (never chosen automatically)
runs the same code at N=1024 on CPU devices wrapped as accelerators to debug
the harness; it reports no device metric and prints its result to standard
error only.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def run_cell(argv: list[str] | None = None) -> dict | None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    if args.rehearse:
        # read when the device module registers its parameters
        os.environ["PARSEC_MCA_device_tpu_allow_cpu"] = "1"
    import harness
    import trace_reduce

    cell = harness.Cell(args.workload)
    if args.rehearse:
        cell.config.update(harness.REHEARSAL_SIZES)

    import jax
    devs = jax.devices()
    want = "cpu" if args.rehearse else "tpu"
    if devs[0].platform != want or len(devs) < cell.chips:
        log(f"{cell.name} needs {cell.chips} {want} device(s); jax.devices() "
            f"gives {len(devs)} of platform {devs[0].platform!r}")
        return None
    peaks = harness.load_json("peaks.json")["device_kinds"].get(
        devs[0].device_kind)
    if peaks is None and not args.rehearse:
        raise SystemExit(f"no peaks for device_kind {devs[0].device_kind!r} "
                         "in benchmarks/peaks.json")

    from parsec_tpu.device.compile_cache import ensure_compile_cache
    cache_dir = ensure_compile_cache()
    meter = harness.CompileMeter()
    trace_dir = None
    seconds = args.seconds
    if args.trace:
        trace_dir = os.path.join(ROOT, ".bench_trace", cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
        seconds = min(seconds, cell.traffic["trace_seconds"])
    win = harness.Window(seconds, T_PROCESS, meter, trace_dir, cell.traffic,
                         args.seed)

    t_data = time.perf_counter()
    prob = cell.problem(args.seed)
    log(f"start to data {t_data - T_PROCESS:.2f}s, data "
        f"{time.perf_counter() - t_data:.2f}s")
    results = cell.path.run(cell, prob, win)
    steps = [b - a for a, b in zip([win.t0] + win.ends, win.ends)]
    log("seconds per solve: " + " ".join(f"{x:.3f}" for x in steps[:64]))
    log("spans: " + " ".join(f"{k}={v:.3f}" for k, v in win.spans.items()))

    c = win.counters
    log(f"{cell.name} seed={args.seed} on {devs[0].device_kind} x{cell.chips}:"
        f" {win.solves} solves in {win.wall_s:.3f}s; set-up {win.setup_s:.2f}s"
        f" ({win.compiles_setup['requests']} programs,"
        f" {win.compiles_setup['fresh']} fresh, cache {cache_dir});"
        f" compilations in the window: {win.compiles_window};"
        f" HBM peak {win.memory_peak_bytes / 1e9:.3f} GB;"
        f" tasks {c['executed_tasks']} in {c['xla_calls']} XLA calls,"
        f" host tasks {c['host_tasks']}")

    # ---- the comparison, after the window, the memory reading and the
    # program's state on the device are gone
    t_check = time.perf_counter()
    prob.reference()
    compared = {}
    for i, tiles in enumerate(results):
        key = "probe_gap" if i == len(results) - 1 else "probe_gap_pick"
        gap = prob.gap(tiles)
        # an infinite gap (a tile missing, a value not finite) stays JSON
        compared[key] = {"value": gap if math.isfinite(gap) else 1e30,
                         "limit": cell.limits["probe_gap"]["limit"]}
    # exact counts over every solve of the window, where the cell's limits
    # name them: the accelerators ran exactly the solves' tasks, the host CPU
    # device none, and no accelerator was demoted on the way; every result
    # tile was on the host, at its newest version, when its solve ended
    counts = {"tasks_off": abs(c["executed_tasks"] - win.solves * prob.tasks)
              + c["host_tasks"] + c["devices_disabled"],
              "tiles_absent": win.tiles_absent}
    for key, value in counts.items():
        if key in cell.limits:
            compared[key] = {"value": value,
                             "limit": cell.limits[key]["limit"]}
    correct = win.solves > 0 and harness.verdict(compared)
    log(f"comparison took {time.perf_counter() - t_check:.1f}s")

    run = {"cell": cell, "problem": prob, "window": win, "peaks": peaks,
           "trace": None}
    metrics = {}
    if not args.trace:
        rate = {"value": win.solves * prob.flops / win.wall_s / 1e9,
                "unit": "GFLOP/s"}
        for m in cell.metrics("end_to_end"):
            metrics[m["name"]] = rate if m["name"] != "setup_s" else \
                {"value": win.setup_s, "unit": "s"}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": win.memory_peak_bytes}
    result = {"correct": bool(correct), "attempted": win.solves, "failed": 0,
              "metrics": metrics, "device": device}
    if args.trace:
        names = set(win.spans)
        run["trace"] = trace_reduce.reduce(
            trace_reduce.load_xplane(trace_dir, names), names, cell.chips)
        shutil.rmtree(trace_dir, ignore_errors=True)
        for m in cell.metrics("per_layer"):
            value = harness.load_module("layer_metrics", m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if run["trace"] and not args.rehearse:
            device["busy_s"] = run["trace"]["busy_s"]
            device["window_s"] = run["trace"]["window_s"]
            result["breakdown"] = {k: run["trace"][k]
                                   for k in ("device_ops", "idle_gaps")}
    result["compared"] = compared

    for name, v in compared.items():
        log(f"compared {name}: {v['value']:.6g} (limit {v['limit']:.6g})")
    log(f"correct: {correct}")
    if args.rehearse:
        log("REHEARSAL on cpu, not a chip result: " + json.dumps(result))
    return result


def main() -> int:
    result = run_cell()
    if result is None:
        return 2
    if "--rehearse" not in sys.argv:
        print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
