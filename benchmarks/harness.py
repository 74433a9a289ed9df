"""What every cell's run shares: finding a cell's files by name, the measured
window with its spans, the program's counters read as deltas, the compile
meter.  ``CompileMeter`` is copied from ``chip_smoke.py`` (the smoke stays a
smoke).
"""

from __future__ import annotations

import gc
import importlib.util
import json
import os
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# what --rehearse (run.py) and the tests cut a cell to
REHEARSAL_SIZES = {"N": 1024, "nb": 128}


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(folder: str, name: str):
    """``benchmarks/<folder>/<name>.py``, found by name: a later PR adds a
    path, an algorithm or a per-layer metric as a new file."""
    path = os.path.join(HERE, folder, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"{folder}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with the files its names lead to."""

    def __init__(self, name: str) -> None:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            self.manifest = json.load(f)
        cells = {w["name"]: w for w in self.manifest["workloads"]}
        if name not in cells:
            raise SystemExit(f"unknown workload {name!r}; have {list(cells)}")
        self.name = name
        self.entry = cells[name]
        self.chips = self.entry["chips"]
        conf = {c["name"]: c for c in self.manifest["configs"]}
        with open(os.path.join(ROOT, conf[self.entry["config"]]["file"])) as f:
            self.config = json.load(f)
        self.traffic = load_json("traffic", self.entry["traffic"] + ".json")
        self.limits = load_json("limits", name + ".json")
        self.path = load_module("paths", self.traffic["path"])

    def metrics(self, group: str) -> list[dict]:
        """The cell's metrics of ``end_to_end`` or ``per_layer``."""
        return [m for m in self.manifest[group]
                if self.name in m.get("workloads", [self.name])]

    def problem(self, seed: int):
        return load_module("problems", self.config["algorithm"]).Problem(
            self.config, seed)


class CompileMeter:
    """Counts XLA compile requests through ``jax.monitoring``: every
    request fires one backend-compile duration (a persistent-cache hit
    included); hits fire their own event, so fresh = requests - hits."""

    def __init__(self) -> None:
        import jax.monitoring as mon
        self.requests = self.hits = 0
        self.seconds = 0.0
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _duration(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.seconds += secs

    def snapshot(self) -> dict:
        return {"requests": self.requests, "hits": self.hits,
                "fresh": self.requests - self.hits, "seconds": self.seconds}


_SUMMED = ("executed_tasks", "xla_calls", "bytes_in", "bytes_out",
           "t_stage_in", "t_dispatch", "t_complete", "t_drain")


def program_counters() -> dict:
    """The device module's counters and phase walls, summed over the
    accelerators, and the tasks the host CPU device ran.  Read, never reset:
    a window takes the difference."""
    from parsec_tpu.device import registry
    out = {k: 0 for k in _SUMMED}
    out["host_tasks"] = 0
    out["devices_disabled"] = 0
    for d in registry.devices:
        if d.type == "cpu":
            out["host_tasks"] += d.executed_tasks
            continue
        for k in _SUMMED:
            out[k] += getattr(d, k)
        out["devices_disabled"] += 0 if d.enabled else 1
    return out


def verdict(compared: dict) -> bool:
    """``correct`` of a run, and of the control in its place: every number
    compared is within its limit."""
    return all(v["value"] <= v["limit"] for v in compared.values())


def host_tile(datum):
    """The datum's tile as the host holds it, or None where the host does not
    hold the newest: no host copy, an invalid one, one behind another device's
    version, or a value that is not a numpy array yet.  Never the newest copy
    wherever it lies: a tile still on the device has not come back."""
    import numpy as np
    from parsec_tpu.data.data import COHERENCY_INVALID
    host, newest = datum.get_copy(0), datum.newest_copy()
    if host is None or host.coherency == COHERENCY_INVALID \
            or newest.version > host.version \
            or not isinstance(host.value, np.ndarray):
        return None
    return host.value


def memory_peak_bytes() -> int:
    """The peak on the fullest chip (0 on the CPU backend of a rehearsal,
    which keeps no such statistic)."""
    import jax
    return max((d.memory_stats() or {"peak_bytes_in_use": 0})
               ["peak_bytes_in_use"] for d in jax.local_devices())


class _Span:
    def __init__(self, win: "Window", name: str) -> None:
        import jax.profiler
        self.win, self.name = win, name
        self.note = jax.profiler.TraceAnnotation(name)

    def __enter__(self) -> None:
        self.note.__enter__()
        self.t0 = time.perf_counter()

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter() - self.t0
        self.note.__exit__(*exc)
        spans = self.win.spans
        spans[self.name] = spans.get(self.name, 0.0) + dt


class Window:
    """The measured window.  A path calls ``begin()`` when its set-up is done
    (the set-up clock stops, the trace starts), ``open()`` before each solve,
    ``solved()`` after it and ``end()`` after the last synchronisation."""

    def __init__(self, seconds: float, t_process: float, meter: CompileMeter,
                 trace_dir: str | None, traffic: dict, seed: int) -> None:
        import numpy as np
        self.seconds = seconds
        # the solves whose results are compared: the last of the window and
        # one of its first few, drawn from the seed
        self.pick = int(np.random.default_rng([seed, 99]).integers(
            traffic["pick_from_first"]))
        self.t_process = t_process
        self.meter = meter
        self.trace_dir = trace_dir
        self.spans: dict[str, float] = {}
        self.solves = 0
        self.tiles_absent = 0   # result tiles a solve did not leave on the host
        self.ends: list[float] = []     # each solve's end, for the log

    def span(self, name: str) -> _Span:
        return _Span(self, name)

    def begin(self) -> None:
        import jax.profiler
        # the warm-up's garbage goes now, and what survives is out of the
        # collector's sight: no generation-2 sweep lands inside the window
        gc.collect()
        gc.freeze()
        self.compiles_setup = self.meter.snapshot()
        self.counters0 = program_counters()
        self.spans.clear()
        self.setup_s = time.perf_counter() - self.t_process
        if self.trace_dir:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self.t0 = time.perf_counter()

    def open(self) -> bool:
        """Whether another solve starts."""
        return time.perf_counter() - self.t0 < self.seconds

    def solved(self) -> None:
        self.solves += 1
        self.ends.append(time.perf_counter())

    def end(self) -> None:
        import jax.profiler
        self.wall_s = time.perf_counter() - self.t0
        if self.trace_dir:
            jax.profiler.stop_trace()
        now = program_counters()
        self.counters = {k: now[k] - self.counters0[k] for k in now}
        after = self.meter.snapshot()
        self.compiles_window = after["requests"] - \
            self.compiles_setup["requests"]
        self.memory_peak_bytes = memory_peak_bytes()
