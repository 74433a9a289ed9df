"""Request-scoped tracing + the per-tenant SLO metrics plane (ISSUE 10):

- histogram property tests: merge associativity (exact bucket-wise),
  quantile error bound vs numpy on random distributions, serialization
  round trip;
- span recorder unit tests + the allocation-free disabled pin (same
  style as ``test_disabled_path_is_allocation_free``);
- server-level SLO: ``RuntimeServer.metrics()`` per-tenant quantiles,
  admission-shed counters, drain time, and the stall-dump section that
  names WHOSE request is stuck (per-tenant inflight + oldest trace id);
- tracemerge: the self-test, and THE acceptance run — a 2-rank
  multiproc run whose activation and fragmented-GET spans stitch into
  one Chrome trace with cross-rank flow arrows.
"""

import json
import os
import threading
import time
import tracemalloc

import numpy as np
import pytest

from parsec_tpu.prof import spans
from parsec_tpu.prof.histogram import LogHistogram, SLOPlane

BODIES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "mp_bodies.py")


# ---------------------------------------------------------------------------
# histogram properties
# ---------------------------------------------------------------------------

def _hist_of(values):
    h = LogHistogram()
    for v in values:
        h.record(v)
    return h


def test_histogram_merge_is_associative_and_exact():
    """(a ⊎ b) ⊎ c == a ⊎ (b ⊎ c) == hist(all) — bucket-exact, so
    per-rank / per-stage histograms combine without loss."""
    rng = np.random.default_rng(42)
    xs = rng.lognormal(1.0, 1.5, 3000) * 5
    a, b, c = xs[:1000], xs[1000:1700], xs[1700:]
    left = _hist_of(a).merge(_hist_of(b)).merge(_hist_of(c))
    right = _hist_of(a).merge(_hist_of(b).merge(_hist_of(c)))
    whole = _hist_of(xs)
    assert left.counts == right.counts == whole.counts
    assert left.count == whole.count == len(xs)
    assert abs(left.total - whole.total) < 1e-6 * whole.total


@pytest.mark.parametrize("dist", ["lognormal", "exponential", "uniform"])
def test_histogram_quantile_error_is_bounded(dist):
    """A reported quantile is the geometric midpoint of its bucket:
    within a factor sqrt(growth) of the empirical quantile.  Tested
    against numpy at the (growth - 1) line — looser than the midpoint
    bound to absorb rank-convention differences at bucket edges."""
    rng = np.random.default_rng(7)
    xs = {"lognormal": rng.lognormal(1.0, 1.0, 5000) * 3,
          "exponential": rng.exponential(20.0, 5000) + 0.01,
          "uniform": rng.uniform(0.5, 400.0, 5000)}[dist]
    h = _hist_of(xs)
    bound = h.growth - 1.0          # ~0.19 at the default 2**0.25
    for q in (0.5, 0.9, 0.99):
        hq = h.quantile(q)
        nq = float(np.percentile(xs, q * 100))
        assert abs(hq - nq) / nq <= bound, (dist, q, hq, nq)


def test_histogram_serialization_round_trip():
    rng = np.random.default_rng(3)
    h = _hist_of(rng.exponential(5.0, 2000))
    h2 = LogHistogram.from_dict(json.loads(json.dumps(h.to_dict())))
    assert h2.counts == h.counts
    assert h2.count == h.count
    for q in (0.5, 0.99):
        assert h2.quantile(q) == h.quantile(q)
    # the serialized form really is a (sparse) bucket array
    d = h.to_dict()
    assert all(isinstance(i, int) and c > 0 for i, c in d["counts"])


def test_histogram_extremes_and_empty():
    h = LogHistogram()
    assert h.quantile(0.5) == 0.0
    h.record(0.0)                     # underflow bucket
    h.record(1e12)                    # overflow bucket
    assert h.count == 2
    assert h.quantile(0.01) == h.lo
    assert h.quantile(0.99) == h._bucket_value(h.nbuckets - 1)
    with pytest.raises(ValueError):
        h.merge(LogHistogram(lo=1.0))


def test_histogram_quantile_clamps_racy_count_divergence():
    """The lock-free record path can lose a bucket increment while
    ``count`` advances (racing completion listeners): quantile must
    clamp its rank to the buckets actually present, never fall through
    to the ~4.6e7 ms overflow midpoint."""
    h = _hist_of([1.0, 2.0, 3.0])
    h.count += 2            # simulate two lost bucket increments
    assert h.quantile(0.99) < 10.0
    empty = LogHistogram()
    empty.count = 5         # pathological: counts all lost
    assert empty.quantile(0.5) == 0.0


def test_slo_plane_summary_and_counters():
    p = SLOPlane()
    for v in (1.0, 2.0, 100.0):
        p.observe("tenantA", "ttft_ms", v)
    p.inc("tenantA", "admission_sheds", 3)
    s = p.summary()
    assert s["tenantA"]["ttft_ms_count"] == 3
    assert s["tenantA"]["ttft_ms_p50"] > 0
    assert s["tenantA"]["ttft_ms_p99"] >= s["tenantA"]["ttft_ms_p50"]
    assert s["tenantA"]["admission_sheds"] == 3
    d = p.to_dict()
    assert "ttft_ms" in d["tenantA"]
    assert d["_counters"]["tenantA"]["admission_sheds"] == 3


# ---------------------------------------------------------------------------
# span recorder
# ---------------------------------------------------------------------------

@pytest.fixture
def installed_spans():
    rec = spans.install()
    try:
        yield rec
    finally:
        spans.uninstall()


def test_disabled_span_path_is_allocation_free():
    """The comm/serve hot-site pattern (``r = spans.recorder; if r is
    not None: ...``) with the recorder uninstalled: zero allocation —
    the same pin as the flight recorder's disabled path."""
    assert spans.recorder is None, "a test left the recorder installed"
    payload = spans  # any attr holder; warm the path
    r = spans.recorder
    if r is not None:
        r.record("x", 0, 0, 0)
    it = range(1000)
    tracemalloc.start()
    before, _ = tracemalloc.get_traced_memory()
    for _ in it:
        r = spans.recorder
        if r is not None:
            r.record("x", 0, 0, 0)
    after, _ = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert after - before < 512, (before, after)
    assert payload is spans


def test_trace_ids_are_unique_and_64bit():
    seen = {spans.new_trace().trace_id for _ in range(1000)}
    assert len(seen) == 1000
    assert all(0 < t < 2 ** 64 for t in seen)


def test_traced_pool_records_task_spans(installed_spans):
    """A traced pool decomposes into queue_wait/schedule/exec/release
    spans; an untraced pool records NOTHING (the per-task getattr
    filter)."""
    from parsec_tpu import ptg
    from parsec_tpu.runtime import Context

    def pool():
        p = ptg.PTGBuilder("chainp", N=6)
        t = p.task("T", i=ptg.span(0, lambda g, l: g.N - 1))
        f = t.flow("ctl", ptg.CTL)
        f.input(pred=("T", "ctl", lambda g, l: {"i": l.i - 1}),
                guard=lambda g, l: l.i > 0)
        f.output(succ=("T", "ctl", lambda g, l: {"i": l.i + 1}),
                 guard=lambda g, l: l.i < g.N - 1)
        t.body(lambda es, task, g, l: None)
        return p.build()

    tp = pool()
    tr = spans.new_trace()
    tp._trace = tr
    tp._trace_enq_ns = time.perf_counter_ns()
    with Context(nb_cores=0) as ctx:
        ctx.add_taskpool(tp)
        ctx.wait(timeout=60)
        n_traced = len(installed_spans.by_trace(tr.trace_id))
        untraced = pool()
        before = len(installed_spans.spans)
        ctx.add_taskpool(untraced)
        ctx.wait(timeout=60)
        assert len(installed_spans.spans) == before
    names = {s[0] for s in installed_spans.by_trace(tr.trace_id)}
    assert {"exec", "release", "queue_wait"} <= names, names
    assert n_traced >= 6 * 2 + 1    # exec+release per task + queue_wait
    # exec spans carry the task-class name (string hot-path form)
    ev = [e for e in spans.to_chrome_events(pid=0)
          if e.get("name") == "exec"]
    assert ev and ev[0]["args"]["task"] == "T"


def test_span_recorder_bounds_memory(installed_spans):
    rec = spans.SpanRecorder(max_spans=100)
    for i in range(500):
        rec.record("x", 1, i, i + 1)
    assert len(rec.spans) <= 100
    assert rec.dropped > 0


def test_reinstalling_hands_back_the_installed_recorder():
    """The save/restore idiom of every test that needs the recorder off
    for a reading: ``uninstall`` then ``install(recorder_obj=prev)`` hands
    back the USER-INSTALLED object — spans accumulated before and a custom
    capacity both survive."""
    rec = spans.install(max_spans=123)
    rec.record("keepme", 7, 0, 1)
    try:
        prev = spans.recorder
        spans.uninstall()
        assert spans.recorder is None
        spans.install().record("other", 8, 0, 1)    # a private recorder
        spans.uninstall()
        spans.install(recorder_obj=prev)
        assert spans.recorder is rec
        assert rec.max == 123
        assert [s[0] for s in rec.spans] == ["keepme"]
    finally:
        spans.uninstall()


# ---------------------------------------------------------------------------
# server SLO + stall sections
# ---------------------------------------------------------------------------

def _ctl_pool(depth=4, lanes=4, body=None):
    from parsec_tpu import ptg
    p = ptg.PTGBuilder("slopool", NT=lanes, DEPTH=depth)
    t = p.task("EP",
               d=ptg.span(0, lambda g, l: g.DEPTH - 1),
               n=ptg.span(0, lambda g, l: g.NT - 1))
    f = t.flow("ctl", ptg.CTL)
    f.input(pred=("EP", "ctl", lambda g, l: {"d": l.d - 1, "n": l.n}),
            guard=lambda g, l: l.d > 0)
    f.output(succ=("EP", "ctl", lambda g, l: {"d": l.d + 1, "n": l.n}),
             guard=lambda g, l: l.d < g.DEPTH - 1)
    t.body(body or (lambda es, task, g, l: None))
    return p.build()


def test_server_metrics_live_and_after_drain():
    """metrics() mid-run returns per-tenant quantiles off the histogram
    plane; drain stamps the drain time."""
    from parsec_tpu.serve import RuntimeServer

    server = RuntimeServer(nb_cores=2)
    tks = [server.submit(_ctl_pool(), tenant=f"t{i % 2}")
           for i in range(8)]
    for tk in tks:
        tk.result(timeout=60)
    m = server.metrics()          # LIVE: the server is still hot
    for tenant in ("t0", "t1"):
        d = m["tenants"][tenant]
        assert d["latency_ms_count"] == 4
        assert d["latency_ms_p99"] >= d["latency_ms_p50"] > 0
        assert d["queue_wait_ms_count"] == 4
        assert d["admission_wait_ms_count"] == 4
    assert m["drain_s"] is None
    server.drain(timeout=60)
    assert server.metrics()["drain_s"] is not None
    # every ticket carried a distinct trace context
    assert len({tk.trace.trace_id for tk in tks}) == len(tks)


def test_admission_sheds_counted_per_tenant():
    from parsec_tpu.serve import RuntimeServer
    from parsec_tpu.serve.admission import (AdmissionController,
                                            AdmissionRejected)

    server = RuntimeServer(
        nb_cores=1, admission=AdmissionController(max_inflight=1))
    gate = threading.Event()

    def slow_body(es, task, g, l):
        gate.wait(10)       # a body must return None (hook rc protocol)

    slow = _ctl_pool(body=slow_body)
    tk = server.submit(slow, tenant="busy")
    try:
        with pytest.raises(AdmissionRejected):
            server.submit(_ctl_pool(), tenant="shed", block=False)
        m = server.metrics()
        assert m["tenants"]["shed"]["admission_sheds"] == 1
    finally:
        gate.set()
        tk.result(timeout=60)
        server.drain(timeout=60)


def test_stall_section_names_stuck_tenant_and_trace():
    """The ISSUE-10 satellite: a stall report carries per-tenant
    inflight counts and the oldest live trace id, so a wedged serve run
    names WHOSE request is stuck."""
    from parsec_tpu.prof import flight_recorder
    from parsec_tpu.serve import RuntimeServer

    server = RuntimeServer(nb_cores=1)
    gate = threading.Event()

    def slow_body(es, task, g, l):
        gate.wait(10)       # a body must return None (hook rc protocol)

    tk = server.submit(_ctl_pool(body=slow_body), tenant="victim")
    try:
        report = flight_recorder.build_stall_report(
            server.context, reason="test")
        sec = [v for k, v in report["sections"].items()
               if k.startswith("serve")]
        assert sec, report.get("sections")
        victim = sec[0]["victim"]
        assert victim["inflight"] == 1
        assert victim["oldest_trace_id"] == format(tk.trace.trace_id,
                                                   "x")
        assert victim["oldest_age_s"] >= 0
        assert victim["oldest_pool"] == tk.name
    finally:
        gate.set()
        tk.result(timeout=60)
        server.drain(timeout=60)
    # the section unregisters with the server: later dumps are clean
    report = flight_recorder.build_stall_report(None, reason="after")
    assert not any(k.startswith("serve")
                   for k in (report.get("sections") or {}))


def test_llm_stream_slo_ttft_and_token_latency():
    """The LLM plane: per-tenant TTFT + inter-token latency quantiles
    from the histogram plane, identical live (metrics()) and after."""
    from parsec_tpu.serve import RuntimeServer

    server = RuntimeServer(nb_cores=2)
    try:
        tks = [server.submit_stream([3, 5, 7], max_new_tokens=4,
                                    tenant=f"u{i}") for i in range(2)]
        for tk in tks:
            tk.result(timeout=120)
        m = server.metrics()
        for i in range(2):
            d = m["tenants"][f"u{i}"]
            assert d["ttft_ms_count"] == 1
            assert d["ttft_ms_p50"] > 0
            assert d["tok_latency_ms_count"] == 4
            assert d["tok_latency_ms_p99"] >= d["tok_latency_ms_p50"] > 0
        # streams carry trace contexts too
        assert len({tk.trace.trace_id for tk in tks}) == 2
    finally:
        server.drain(timeout=60)


def test_runtime_report_carries_slo_block():
    from parsec_tpu.prof import runtime_report
    p = SLOPlane()
    p.observe("reportme", "latency_ms", 5.0)
    rep = runtime_report()
    assert "reportme" in rep["slo"]
    assert rep["slo"]["reportme"]["latency_ms_count"] >= 1


# ---------------------------------------------------------------------------
# tracemerge
# ---------------------------------------------------------------------------

def test_tracemerge_self_test():
    from parsec_tpu.prof import tracemerge
    assert tracemerge.self_test() == 0


def test_tracemerge_unmatched_flows_are_not_stitched(tmp_path):
    from parsec_tpu.prof import tracemerge
    p = tmp_path / "trace-rank0.json"
    p.write_text(json.dumps({"traceEvents": [
        {"name": "comm.get", "ph": "X", "ts": 1.0, "dur": 1.0, "pid": 0,
         "tid": 0, "args": {"flow": "get:0:1", "flow_side": "recv"}}]}))
    stats = tracemerge.merge_traces([str(p)], str(tmp_path / "out.json"))
    assert stats["flows_matched"] == 0
    assert stats["cross_rank_flows"] == 0


def test_two_rank_spans_stitch_across_ranks(tmp_path):
    """THE acceptance run: a 2-rank multiproc chain over the binary
    socket wire produces per-rank Chrome traces that tracemerge
    stitches into ONE trace with cross-rank flow arrows for at least
    one activation AND one fragmented GET (viewable in Perfetto)."""
    from parsec_tpu.comm.multiproc import run_multiproc
    from parsec_tpu.core.params import params
    from parsec_tpu.prof import tracemerge

    os.environ["PARSEC_TEST_TRACE_DIR"] = str(tmp_path)
    saved = params.get("comm_get_frag_bytes")
    # 8 KiB fragments over 32 KiB tiles: every tile hop is a FRAGMENTED
    # GET (the param is forwarded to the subprocess ranks by multiproc)
    params.set("comm_get_frag_bytes", 8192)
    try:
        res = run_multiproc(2, f"{BODIES}:traced_get_body", timeout=180)
    finally:
        params.set("comm_get_frag_bytes", saved)
        os.environ.pop("PARSEC_TEST_TRACE_DIR", None)
    # each rank recorded comm spans (names returned by the body)
    for names in res:
        assert "comm.activate" in names, res
    paths = [str(tmp_path / f"trace-rank{r}.json") for r in (0, 1)]
    for p in paths:
        assert os.path.exists(p)
    merged = tmp_path / "merged_trace.json"
    stats = tracemerge.merge_traces(paths, str(merged))
    # at least one activation hop and one GET stitched ACROSS ranks
    assert stats["cross_rank_flows"] >= 2, stats
    assert stats["flows_by_kind"].get("act", 0) >= 1, stats
    assert stats["flows_by_kind"].get("get", 0) >= 1, stats
    trace = json.loads(merged.read_text())
    evs = trace["traceEvents"]
    s_evs = [e for e in evs if e.get("ph") == "s"]
    f_evs = [e for e in evs if e.get("ph") == "f"]
    assert s_evs and f_evs
    # arrows connect DIFFERENT rank pid namespaces
    assert any(a["pid"] // 100 != b["pid"] // 100
               for a in s_evs for b in f_evs
               if a.get("id") == b.get("id"))
    # the shared trace id survived the wire: traced spans on both ranks
    traced = [e for e in evs
              if (e.get("args") or {}).get("trace") == "beef01"]
    assert {e["pid"] // 100 for e in traced} == {0, 1}


# ISSUE-10 tracing budget (docs/OBSERVABILITY.md overhead table), held
# since ISSUE 27 by what repeats exactly: which PINS slots the span
# recorder occupies, how many spans a traced pool leaves, and that the
# phase plane builds nothing while off.


@pytest.mark.perf_smoke
def test_tracing_overhead_within_budget(monkeypatch):
    """The observability gates.  With the span recorder UNINSTALLED (the
    shipped default) its six task-span PINS slots hold no chain of its
    own: tracing added no hot-path site, only the existing PINS branch.
    INSTALLED, a traced pool of n tasks records exactly n ``exec`` and n
    ``release`` spans.  The phase plane, off, builds no object."""
    from collections import Counter

    from parsec_tpu.prof import pins
    from parsec_tpu.prof.pins import PinsEvent
    from parsec_tpu.runtime import Context

    task_span_events = (
        PinsEvent.EXEC_BEGIN, PinsEvent.EXEC_END,
        PinsEvent.RELEASE_DEPS_BEGIN, PinsEvent.RELEASE_DEPS_END,
        PinsEvent.SCHEDULE_BEGIN, PinsEvent.SCHEDULE_END)

    def recorder_chains():
        return [cb for ev in task_span_events
                for cb in pins._chains.get(int(ev), ())
                if isinstance(getattr(cb, "__self__", None),
                              spans._TaskSpans)]

    def built(*a):
        raise AssertionError("the phase plane built a span while off")

    prev = spans.recorder
    if prev is not None:
        spans.uninstall()
    assert spans._task_spans is None and not recorder_chains()
    spans.phase_refresh()
    assert not spans.phase_on
    assert spans.phase("ctx.init") is spans.phase("ctx.fini")
    table = spans.phase_totals()
    monkeypatch.setattr(spans, "_Phase", built)
    nt, depth = 20, 25
    rec = spans.install()
    try:
        assert len(recorder_chains()) == len(task_span_events)
        tp = _ctl_pool(depth=depth, lanes=nt)
        tp._trace = spans.new_trace()
        ctx = Context(nb_cores=0)
        ctx.add_taskpool(tp)
        ctx.wait(timeout=600)
        ctx.fini()
        names = Counter(s[0] for s in rec.by_trace(tp._trace.trace_id))
    finally:
        spans.uninstall()
        if prev is not None:
            spans.install(recorder_obj=prev)
    assert names["exec"] == names["release"] == nt * depth, names
    assert spans.phase_totals() == table
