"""Persistent perf ledger + regression sentinel (ISSUE 16).

An append-only JSONL ledger of every measured perf scalar, keyed by the
same discriminators the lowering cache lives on — a **workload
signature**, the ``(jax version, backend, device kind)`` triple
(:func:`parsec_tpu.ptg.lowering._backend_signature`), and an explicit
**knob vector** — so a number is only ever compared against its own
configuration class, never a different machine's or a different tile
size's.  The autotuner (``tune/search.py``) appends one record per trial
and reads a vector's history back to prune it; the file accrues across
runs (``$PARSEC_TPU_ARTIFACT_DIR/perfdb.jsonl`` by default).

Drift detection is an EWMA per key: :meth:`PerfDB.check` folds the
key's history into an exponentially-weighted mean + variance and
verdicts the new value ``ok`` / ``regressed`` / ``improved`` with a
z-score.  The variance floor is relative (5% of the mean), so steady
history does not manufacture infinite z-scores: a 5% wobble stays
``ok`` while a 10x cliff is unmissable (``tests/test_perf_smoke.py``
pins exactly that pair).  Direction comes from the metric name
(:func:`better_of`): ``*_us``/``*_ms``/``*_s``/latency-like metrics
regress UP, throughput-like metrics regress DOWN.

::

    python -m parsec_tpu.prof.perfdb --history tune.<signature>
    python -m parsec_tpu.prof.perfdb --self-test

MCA knobs: ``perfdb`` (0 disables every append), ``perfdb_path``
(overrides the ledger location).
"""

from __future__ import annotations

import json
import math
import os
import sys
import time
from typing import Iterable

from ..core.params import params as _params

_params.register("perfdb", True,
                 "append autotuner trials to the JSONL perf ledger and "
                 "prune candidates by their EWMA history (0 = no "
                 "ledger writes, no pruning)")
_params.register("perfdb_path", "",
                 "perf ledger location (default: "
                 "$PARSEC_TPU_ARTIFACT_DIR/perfdb.jsonl, else "
                 "/tmp/perfdb.jsonl)")

# EWMA fold + verdict thresholds: alpha weights recent runs, the z gate
# needs a genuinely multi-sigma move, REL_FLOOR stops steady history
# from making sigma ~0 (any change would then be infinite-z), and
# MIN_HISTORY keeps the sentinel quiet until the key has a real mean.
ALPHA = 0.3
Z_THRESHOLD = 4.0
REL_FLOOR = 0.05
MIN_HISTORY = 3

_HIGHER_IS_BETTER = ("per_s", "gbps", "gflops", "throughput", "_hits",
                     "efficiency", "speedup", "rate", "_frac", "pct_")
_LOWER_IS_BETTER = ("latency", "_wait", "_p50", "_p99", "dispatch",
                    "compile", "ttft", "overhead", "_err", "dropped",
                    "_lost", "_relerr")


def better_of(metric: str) -> str:
    """Direction heuristic from the metric name: throughput-shaped
    metrics (rates, GB/s, GFLOPS, hit counts, efficiency) are better
    HIGH; time/latency-shaped ones (``*_us``/``*_ms``/``*_s``,
    latency, compile seconds) better LOW.  The rate check runs first so
    ``tokens_per_s`` never reads as a seconds metric."""
    m = metric.lower()
    if any(t in m for t in _HIGHER_IS_BETTER):
        return "higher"
    if m.endswith(("_us", "_ms", "_ns", "_s", "_seconds")) \
            or any(t in m for t in _LOWER_IS_BETTER):
        return "lower"
    return "higher"


def default_path() -> str:
    p = str(_params.get("perfdb_path") or "")
    if p:
        return p
    return os.path.join(os.environ.get("PARSEC_TPU_ARTIFACT_DIR", "/tmp"),
                        "perfdb.jsonl")


def backend_signature() -> list:
    """The lowering-cache backend triple, degraded gracefully when jax
    is unimportable (the ledger must work on a bare CPU box)."""
    try:
        from ..ptg.lowering import _backend_signature
        return list(_backend_signature())
    except Exception:                       # noqa: BLE001 — ledger > jax
        return ["nojax", "cpu", ""]


def make_key(workload: str, metric: str, backend: list | None = None,
             knobs: dict | None = None) -> str:
    """Canonical key string: equal key ⇒ comparable measurement class
    (same workload structure, same backend triple, same knob vector)."""
    return json.dumps({"workload": workload, "metric": metric,
                       "backend": backend if backend is not None
                       else backend_signature(),
                       "knobs": knobs or {}},
                      sort_keys=True, separators=(",", ":"))


class PerfDB:
    """One ledger file.  ``append`` writes a record; ``check`` verdicts
    a value against the key's EWMA history."""

    def __init__(self, path: str | None = None) -> None:
        self.path = path or default_path()
        self._cache: list[dict] | None = None

    # -- storage ---------------------------------------------------------
    def records(self) -> list[dict]:
        if self._cache is not None:
            return self._cache
        recs: list[dict] = []
        try:
            with open(self.path) as f:
                for ln in f:
                    ln = ln.strip()
                    if not ln:
                        continue
                    try:
                        recs.append(json.loads(ln))
                    except ValueError:
                        continue            # a torn tail line: skip, keep rest
        except OSError:
            pass
        self._cache = recs
        return recs

    def append(self, key: str, value: float, *, unit: str | None = None,
               run: str | None = None, meta: dict | None = None) -> dict:
        rec = {"key": key, "value": float(value), "ts": round(time.time(), 3)}
        if unit:
            rec["unit"] = unit
        if run:
            rec["run"] = run
        if meta:
            rec["meta"] = meta
        line = json.dumps(rec, sort_keys=True, separators=(",", ":"))
        d = os.path.dirname(self.path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(self.path, "a") as f:
            f.write(line + "\n")
        if self._cache is not None:
            self._cache.append(rec)
        return rec

    def history(self, key: str) -> list[float]:
        return [r["value"] for r in self.records()
                if r.get("key") == key and isinstance(r.get("value"),
                                                      (int, float))]

    # -- the sentinel ----------------------------------------------------
    @staticmethod
    def _ewma(values: Iterable[float]) -> tuple[float, float, int]:
        """Fold history (file order = time order) into (mean, std, n)
        with an exponentially-weighted mean and variance."""
        m = v = 0.0
        n = 0
        for x in values:
            n += 1
            if n == 1:
                m, v = x, 0.0
                continue
            d = x - m
            m += ALPHA * d
            v = (1.0 - ALPHA) * (v + ALPHA * d * d)
        return m, math.sqrt(max(v, 0.0)), n

    def check(self, key: str, value: float,
              better: str | None = None) -> dict:
        """Verdict ``value`` against the key's EWMA history: ``ok`` /
        ``regressed`` / ``improved`` (+ ``warming`` below MIN_HISTORY),
        with the signed z-score (positive = above the EWMA)."""
        hist = self.history(key)
        m, sd, n = self._ewma(hist)
        if n < MIN_HISTORY:
            return {"verdict": "warming", "z": 0.0, "n": n, "ewma": m}
        if better is None:
            try:
                better = better_of(json.loads(key).get("metric", ""))
            except ValueError:
                better = "higher"
        sigma = max(sd, REL_FLOOR * abs(m), 1e-12)
        z = (float(value) - m) / sigma
        worse = z < -Z_THRESHOLD if better == "higher" else z > Z_THRESHOLD
        improv = z > Z_THRESHOLD if better == "higher" else z < -Z_THRESHOLD
        verdict = "regressed" if worse else ("improved" if improv else "ok")
        return {"verdict": verdict, "z": round(z, 2), "n": n,
                "ewma": round(m, 6)}

    # -- trial provenance (the autotuner hook) ---------------------------
    def note_trial(self, workload: str, objective: str, value: float, *,
                   knobs: dict | None = None, meta: dict | None = None,
                   backend: list | None = None) -> dict:
        """Append one autotuner trial (``parsec_tpu/tune``): the knob
        vector IS the key's knobs field, so each candidate point accrues
        its own EWMA history — which is exactly what lets a later search
        prune a known-bad vector without re-measuring it."""
        key = make_key(workload, objective, backend=backend, knobs=knobs)
        return self.append(key, float(value), run="tune", meta=meta)


# ---------------------------------------------------------------------------
# self-test (scripts/check.sh gate)
# ---------------------------------------------------------------------------

def self_test() -> int:
    """The sentinel round-trip ``tests/test_perf_smoke.py`` also pins: steady
    history + 5% noise stays ok; a 10x cliff is flagged in BOTH
    directions; histories accrue across PerfDB instances (two
    'invocations' of one file)."""
    import tempfile
    with tempfile.TemporaryDirectory(prefix="perfdb_") as d:
        p = os.path.join(d, "perfdb.jsonl")
        db = PerfDB(p)
        k_hi = make_key("selftest", "tokens_per_s", backend=["t", "c", ""])
        k_lo = make_key("selftest", "dispatch_us", backend=["t", "c", ""])
        for i in range(8):
            db.append(k_hi, 1000.0 + (i % 3) * 10)      # ~1% wobble
            db.append(k_lo, 10.0 + (i % 3) * 0.1)
        db2 = PerfDB(p)                     # a fresh "second invocation"
        assert db2.check(k_hi, 1050.0)["verdict"] == "ok"       # 5% noise
        assert db2.check(k_hi, 100.0)["verdict"] == "regressed"  # 10x down
        assert db2.check(k_hi, 10000.0)["verdict"] == "improved"
        assert db2.check(k_lo, 10.4)["verdict"] == "ok"
        r = db2.check(k_lo, 100.0)          # 10x slower: worse for _us
        assert r["verdict"] == "regressed", r
        assert r["z"] > Z_THRESHOLD, r
        assert db2.check(k_lo, 1.0)["verdict"] == "improved"
        # the commcheck agreement gate rides the _err direction: growing
        # static-vs-wire disagreement must read as a regression
        assert better_of("comm_agree_8r_err") == "lower"
        assert better_of("bytes_relerr") == "lower"
        # cold keys warm silently
        k_new = make_key("selftest", "fresh_metric")
        assert db2.check(k_new, 5.0)["verdict"] == "warming"
        assert len(db2.records()) == 16
    print("perfdb self-test: ok (EWMA sentinel: 5% noise ok, 10x cliff "
          "flagged both directions, cross-instance accrual)")
    return 0


def main(argv: list[str] | None = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--self-test" in argv:
        return self_test()
    path = None
    if "-o" in argv:
        i = argv.index("-o")
        path = argv[i + 1]
        del argv[i:i + 2]
    if "--history" in argv:
        i = argv.index("--history")
        workload = argv[i + 1]
        db = PerfDB(path)
        seen: dict[str, list[float]] = {}
        for r in db.records():
            try:
                kd = json.loads(r["key"])
            except (KeyError, ValueError):
                continue
            if kd.get("workload") == workload:
                seen.setdefault(kd["metric"], []).append(r["value"])
        for metric in sorted(seen):
            vals = seen[metric]
            m, sd, n = PerfDB._ewma(vals)
            print(f"{workload}/{metric}: n={n} ewma={m:.4g} sd={sd:.3g} "
                  f"last={vals[-1]:.4g}")
        return 0
    print(__doc__, file=sys.stderr)
    return 2


if __name__ == "__main__":
    sys.exit(main())
