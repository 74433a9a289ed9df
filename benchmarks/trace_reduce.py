"""From the profiler's trace to numbers: device busy and idle time, the time
inside XLA programs, the operations that took most of it, and the idle gaps
by what the runner (and the host under it) was doing.

Two steps, so that the arithmetic is checked on a small recorded trace
(``tests/data/``) without the profiler: ``load_xplane`` turns an
``.xplane.pb`` into plain lists, ``reduce`` works on those alone.

    trace = {"devices": [{"name": "/device:TPU:0",
                          "ops": [[name, start_ns, dur_ns], ...],
                          "modules": [[name, start_ns, dur_ns], ...]}, ...],
             "host": [{"name": thread, "events": [[name, start_ns, dur_ns],
                                                  ...]}, ...]}
"""

from __future__ import annotations

import bisect
import glob
import os
import re

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
MIN_HOST_EVENT_NS = 20_000      # shorter host events label no gap worth a line


def load_xplane(trace_dir: str, span_names: set[str]) -> dict:
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    data = ProfileData.from_file(path)
    trace: dict = {"devices": [], "host": []}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    dev[key] = [[e.name, e.start_ns, e.duration_ns]
                                for e in line.events]
            trace["devices"].append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = [[e.name, e.start_ns, e.duration_ns]
                          for e in line.events
                          if e.duration_ns >= MIN_HOST_EVENT_NS
                          or e.name in span_names]
                if events:
                    trace["host"].append({"name": line.name,
                                          "events": events})
    trace["devices"].sort(key=lambda d: d["name"])
    return trace


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(events: list, lo: float, hi: float) -> list[tuple[float, float]]:
    out = []
    for _, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((a, b))
    return out


def _short(name: str) -> str:
    """``jit_fused(123456)`` -> ``jit_fused``; ``%fusion.12 = ...`` ->
    ``fusion``: one line of the breakdown per program and kind of op."""
    name = name.split(" = ")[0].lstrip("%$")
    name = re.sub(r"\(.*$", "", name)
    name = re.sub(r"[.\d]+$", "", name)
    return name or "?"


class _Line:
    """Host events of one thread, for 'what covers instant t, innermost'."""

    def __init__(self, events: list) -> None:
        self.events = sorted(events, key=lambda e: e[1])
        self.starts = [e[1] for e in self.events]

    def innermost(self, t: float, skip: set[str]) -> str | None:
        i = bisect.bisect_right(self.starts, t)
        for name, s, d in reversed(self.events[max(0, i - 256):i]):
            if s + d > t and name not in skip:
                return name
        return None


def reduce(trace: dict, span_names: set[str], chips: int) -> dict | None:
    """``None`` where the trace holds no device operation inside the
    runner's spans: a reader then has nothing to read."""
    lines = [_Line(h["events"]) for h in trace["host"]]
    spans = sorted((s, s + d, name, i) for i, ln in enumerate(lines)
                   for name, s, d in ln.events if name in span_names)
    devices = trace["devices"][:chips]
    if not spans or not devices:
        return None
    lo, hi = spans[0][0], max(e for _, e, _, _ in spans)

    busy_by_chip, op_s, module_s, launches = [], {}, 0.0, 0
    for dev in devices:
        busy = _union(_clip(dev["ops"], lo, hi))
        busy_by_chip.append(busy)
        mods = sorted((s, s + d, _short(n)) for n, s, d in dev["modules"])
        mstarts = [m[0] for m in mods]
        launches += sum(1 for s, _, _ in mods if lo <= s < hi)
        module_s += sum(b - a for a, b in _union(
            _clip([[n, s, e - s] for s, e, n in mods], lo, hi)))
        for name, s, d in dev["ops"]:
            a, b = max(s, lo), min(s + d, hi)
            if b <= a:
                continue
            j = bisect.bisect_right(mstarts, s) - 1
            prog = mods[j][2] if j >= 0 and mods[j][1] > s else "?"
            key = f"{prog}/{_short(name)}"
            op_s[key] = op_s.get(key, 0.0) + (b - a)
    busy_s = [sum(b - a for a, b in busy) for busy in busy_by_chip]
    if not any(busy_s):
        return None
    busiest = max(range(len(devices)), key=lambda i: busy_s[i])

    # idle gaps of the busiest chip, cut at the runner's spans
    sstarts = [sp[0] for sp in spans]
    gaps: dict[str, float] = {}
    edge = lo
    for a, b in busy_by_chip[busiest] + [(hi, hi)]:
        g0, g1 = edge, a
        edge = max(edge, b)
        j = max(0, bisect.bisect_right(sstarts, g0) - 1)
        cursor = g0
        while cursor < g1:
            while j < len(spans) and spans[j][1] <= cursor:
                j += 1
            if j == len(spans) or spans[j][0] >= g1:
                piece, label = (cursor, g1), "between_solves"
            elif spans[j][0] > cursor:
                piece, label = (cursor, spans[j][0]), "between_solves"
            else:
                s, e, name, li = spans[j]
                piece = (cursor, min(e, g1))
                inner = lines[li].innermost(sum(piece) / 2, span_names)
                label = f"{name}/{_short(inner)}" if inner else name
            gaps[label] = gaps.get(label, 0.0) + piece[1] - piece[0]
            cursor = piece[1]

    def top(d: dict) -> list:
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    n = len(devices)
    return {"window_s": (hi - lo) / 1e9,
            "busy_s": sum(busy_s) / n / 1e9,
            "busiest_busy_s": busy_s[busiest] / 1e9,
            "program_s": module_s / n / 1e9,
            "launches": launches,
            "device_ops": top({k: v / n for k, v in op_s.items()}),
            "idle_gaps": top(gaps)}


def roofline_share(run: dict) -> float | None:
    """The least time the chips could take for the window's solves (the
    larger of the algorithm's FLOPs over peak FLOP/s and its least bytes over
    peak bytes/s, from the configuration's shapes) over the device time
    inside XLA programs, in percent."""
    tr, peaks = run["trace"], run["peaks"]
    if not tr or not peaks or not tr["program_s"]:
        return None
    prob, chips = run["problem"], run["cell"].chips
    least = max(prob.flops / peaks["flops_per_s"],
                prob.min_bytes / peaks["bytes_per_s"]) / chips
    return 100.0 * run["window"].solves * least / tr["program_s"]


def idle_share(run: dict) -> float | None:
    """1 - busy/window of the busiest chip, in percent."""
    tr = run["trace"]
    if not tr or not run["peaks"]:
        return None
    return 100.0 * (1.0 - tr["busiest_busy_s"] / tr["window_s"])
