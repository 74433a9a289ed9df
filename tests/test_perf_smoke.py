"""perf_smoke: what the critical path does, held as counts.

Every gate here repeats exactly on the CPU stand-in: frames, fragments and
bytes on the wire, allocations at a disabled instrumentation site, spans
of one run read two ways, the ledger's verdicts.  No assertion compares a
wall-clock reading, or a ratio of two, with a constant: a speed is a
median on a named device (``PERF_LEDGER.jsonl``, the root ``PERF.md``),
never a tier-1 gate.  The cases that used to read a clock here live where
their workload is built: ``test_release_batching.py`` (dep release),
``test_ready_queue.py`` (pop and steal order), ``test_serve.py``,
``test_llm.py``, ``test_llm_spec.py``, ``test_llm_prefix.py``,
``test_lowering.py``, ``test_lowering_regions.py``, ``test_tune.py`` and
``test_tracing.py``."""

import pickle
import time
import tracemalloc

import numpy as np
import pytest

from test_comm_wire import _wait, socket_pair  # noqa: F401 — the fixture

pytestmark = pytest.mark.perf_smoke

# ISSUE-20 commcheck: the static byte prediction for the collective
# rank sweep must agree with the measured peer_stats wire ledger within
# 15% rel (deterministic workload: (n-1) payload transfers + small
# reduction partials; framing and activation frames are the only slack)
COMMCHECK_AGREE_RELERR_MAX = 0.15


def test_pins_disabled_site_allocates_nothing():
    """A DISABLED instrumentation site (index load + falsy branch, the
    pattern the scheduling loop compiles in: prof/pins.py) builds no
    object over 50,000 fires.  The always-on flight recorder is detached
    for the reading and restored after."""
    from parsec_tpu.prof import pins

    hooks = pins.hooks
    ev = int(pins.PinsEvent.EXEC_BEGIN)
    if pins._chains.get(ev):
        pytest.skip("a PINS chain is registered on EXEC_BEGIN; the "
                    "allocation test of test_flight_recorder still holds")
    payload = object()
    fires = range(50000)
    saved = pins.recorder
    pins.recorder = None
    try:
        assert hooks[ev] is None
        tracemalloc.start()
        before, _ = tracemalloc.get_traced_memory()
        for _ in fires:
            h = hooks[ev]
            if h is not None:
                h(None, payload)
        after, _ = tracemalloc.get_traced_memory()
        tracemalloc.stop()
    finally:
        pins.recorder = saved
    assert after - before < 512, (before, after)


class _CountingPickle:
    """``pickle`` with its ``dumps`` counted in bytes: what a framing
    serialised the slow way."""

    def __init__(self) -> None:
        self.bytes = 0

    def __getattr__(self, name):
        return getattr(pickle, name)

    def dumps(self, obj, *a, **kw):
        out = pickle.dumps(obj, *a, **kw)
        self.bytes += len(out)
        return out


@pytest.fixture
def comm_run(socket_pair, param, monkeypatch):  # noqa: F811
    """One pass over the socket wire shared by the comm gates below: a
    batch of 32 activations, GETs of 64 KiB and 4 MiB, then an 8 MiB GET
    with compute units retired while it flies, all under a private span
    recorder so critpath can read the same run."""
    from parsec_tpu.comm import codec, socket_fabric
    from parsec_tpu.comm.engine import AM_TAG_USER_BASE
    from parsec_tpu.comm.remote_dep import pack_activation
    from parsec_tpu.prof import spans

    param("comm_wire_binary", True)
    param("comm_get_frag_bytes", 1 << 20)
    param("comm_get_window", 4)
    pickled = _CountingPickle()
    monkeypatch.setattr(socket_fabric, "pickle", pickled)
    monkeypatch.setattr(codec, "pickle", pickled)
    prev = spans.recorder
    if prev is not None:
        spans.uninstall()
    rec = spans.install()
    e0, e1 = socket_pair
    out: dict = {"pickled": pickled}
    try:
        # warm both directions of the duplex connections
        pong = []
        e0.tag_register(AM_TAG_USER_BASE, lambda eng, src, p: pong.append(p))
        e1.tag_register(AM_TAG_USER_BASE, lambda eng, src, p:
                        e1.send_am(AM_TAG_USER_BASE, src, p))
        e0.send_am(AM_TAG_USER_BASE, 1, 0)
        _wait((e0, e1), lambda: pong)

        def tx(e, dst):
            return dict(e.fabric.peer_stats()["tx"][dst])

        # -- 32 compact activations with inline payloads, one send -------
        inline = np.arange(64, dtype=np.float32)
        batch = ("B", [pack_activation(
            {"tp": 1, "tc": 0, "locals": {"m": i, "k": 3}, "outputs": [
                {"flow_index": 0, "writeback": False, "version": 1,
                 "inline": inline}],
             "ranks": [0, 1], "tree": "binomial", "priority": i,
             "seq": i, "pos": 1}) for i in range(32)])
        got = []
        e1.tag_register(AM_TAG_USER_BASE + 1, lambda eng, src, p:
                        got.append(len(p[1])))
        before = tx(e0, 1)
        e0.send_am(AM_TAG_USER_BASE + 1, 1, batch)
        _wait((e0, e1), lambda: got)
        out["activations"] = got
        out["activation_frames"] = tx(e0, 1)["frames"] - before["frames"]

        # -- the GET ladder (untraced: the overlap_lost edge classes) ----
        def registered(nbytes, seed):
            arr = np.random.default_rng(seed).integers(
                0, 255, size=nbytes, dtype=np.uint8)
            return arr, e1.mem_register(arr, refcount=1, owned=True)

        _arr, h = registered(65536, 7)
        done: list = []
        e0.get(h.wire(), done.append)
        _wait((e0, e1), lambda: done)
        before = tx(e1, 0)
        frags0, bytes0 = e1.frags_out, e1.frag_bytes_out
        arr, h = registered(4 << 20, 7)
        done = []
        e0.get(h.wire(), done.append)
        _wait((e0, e1), lambda: done)
        np.testing.assert_array_equal(done[0], arr)
        after = tx(e1, 0)
        out["get"] = {"nbytes": arr.nbytes,
                      "frags": e1.frags_out - frags0,
                      "frag_bytes": e1.frag_bytes_out - bytes0,
                      "tx_frags": after["frags"] - before["frags"],
                      "tx_bytes": after["bytes"] - before["bytes"]}

        # -- compute retired during a fragmented GET, traced ------------
        a = np.random.default_rng(4).standard_normal((192, 192)) \
            .astype(np.float32)
        tr = spans.new_trace()
        now = time.perf_counter_ns

        def landed():
            return e0.fabric.peer_stats()["rx"][1]["frags"]

        def unit():
            u0 = now()
            float(np.dot(a, a).sum())       # one compute unit
            u1 = now()
            rec.record("exec", tr.trace_id, u0, u1, None, "overlap_unit")
            return u1 - u0

        landed0, served0 = landed(), e1.frags_out
        busy_ns = units = 0
        _arr, h = registered(8 << 20, 3)
        done = []
        t0 = now()
        e0.get(h.wire(), done.append, trace=tr.trace_id)
        # the owner serves the first window; from here the consumer only
        # computes, and whatever lands is the receive thread's doing
        _wait((e1,), lambda: e1.frags_out - served0 >= 4)
        while landed() - landed0 < 4:
            busy_ns += unit()
            units += 1
            assert now() - t0 < 60e9, "no fragment landed under compute"
        inside = landed() - landed0
        while not done:
            busy_ns += unit()
            units += 1
            e0.progress()
            e1.progress()
            assert now() - t0 < 60e9, "the overlap GET did not complete"
        out["overlap"] = {"frags": landed() - landed0, "inside": inside,
                          "units": units,
                          "efficiency": min(busy_ns / (now() - t0), 1.0),
                          "trace": tr.trace_id}
        out["spans"] = list(rec.spans)
    finally:
        spans.uninstall()
        if prev is not None:
            spans.install(recorder_obj=prev)
    return out


def test_comm_wire_path_frames_and_bytes(comm_run):
    """The zero-copy wire data path (ISSUE 4) in frames and bytes: a 4 MiB
    GET over the binary framing moves ``ceil(4 MiB / comm_get_frag_bytes)``
    fragments whose payload bytes are the array's, the wire carries those
    plus headers and one meta blob, nothing is pickled, and 32 activations
    sent as one batch ride one frame."""
    g = comm_run["get"]
    assert g["frags"] == g["tx_frags"] == -(-g["nbytes"] // (1 << 20)) == 4
    assert g["frag_bytes"] == g["nbytes"]
    from parsec_tpu.comm.socket_fabric import _HDR
    framing = g["tx_bytes"] - g["nbytes"] - g["frags"] * _HDR.size
    assert 0 < framing < 1024, g           # the first fragment's meta blob
    assert comm_run["pickled"].bytes == 0
    assert comm_run["activations"] == [32]
    assert comm_run["activation_frames"] == 1


def test_comm_overlap_fragments_land_inside_compute_units(comm_run):
    """The T3 overlap gate (ROADMAP), as the count behind the ratio: while
    the consumer only computes and never calls ``progress``, the fabric's
    receive thread lands the whole first window of the GET in flight
    (``comm_get_window`` = 4 of its 8 fragments; the rest wait for the
    consumer's acks).  A blocking recv, or fragments that land only inside
    ``progress``, reads 0 and runs into the hang guard."""
    o = comm_run["overlap"]
    assert (o["inside"], o["frags"]) == (4, 8) and o["units"] >= 1, o


def test_critpath_agrees_with_measured_overlap(comm_run):
    """ISSUE-16 acceptance: the span-plane replay must reconstruct the
    overlap GET's efficiency to within 15% relative of the number
    accumulated inline — two readings of one run's events (span interval
    algebra against summed unit timers), neither held to a constant —
    and name the top-3 overlap_lost edge classes with nonzero values."""
    from parsec_tpu.prof.critpath import attribute, normalize
    rep = attribute(normalize(comm_run["spans"]))
    o = comm_run["overlap"]
    c = rep["requests"][format(o["trace"], "x")]["overlap_efficiency"]
    assert abs(c - o["efficiency"]) / max(o["efficiency"], 1e-9) < 0.15, \
        (o["efficiency"], c)
    top = rep["top_overlap_lost"]
    assert len(top) == 3 and all(ms > 0 for _cls, ms in top), top
    assert rep["overlap_lost_ms"] > 0, rep


def test_critpath_disabled_path_free():
    """critpath consumes EXISTING spans, so with no recorder installed
    there is nothing to pay and nothing to summarize."""
    from parsec_tpu.prof import spans
    from parsec_tpu.prof.critpath import summarize_recorder
    prev = spans.recorder
    if prev is not None:
        spans.uninstall()
    try:
        assert spans.recorder is None
        assert summarize_recorder() is None
    finally:
        if prev is not None:
            spans.install(recorder_obj=prev)


def test_perfdb_sentinel_roundtrips_synthetic_regression(tmp_path):
    """ISSUE-16 gate: the EWMA drift detector flags a 10x cliff (both
    metric directions) and stays quiet on 5% noise."""
    from parsec_tpu.prof.perfdb import PerfDB, make_key
    db = PerfDB(path=str(tmp_path / "perfdb.jsonl"))
    kd = make_key("smoke", "dispatch_us", backend=["cpu"])
    kt = make_key("smoke", "tokens_per_s", backend=["cpu"])
    for i in range(16):
        db.append(kd, 100.0 + (i % 2))      # latency-like: lower better
        db.append(kt, 1000.0 - (i % 3))     # throughput: higher better
    assert db.check(kd, 105.0)["verdict"] == "ok"       # 5% noise: quiet
    hi = db.check(kd, 1000.0)                           # 10x slowdown
    assert hi["verdict"] == "regressed" and hi["z"] > 0, hi
    assert db.check(kt, 100.0)["verdict"] == "regressed"   # 10x drop
    assert db.check(kt, 10000.0)["verdict"] == "improved"


@pytest.mark.parametrize("nranks", [2, 4])
def test_commcheck_static_vs_wire_agreement(nranks):
    """ISSUE-20 agreement gate: commcheck predicts the collective sweep's
    cross-rank bytes WITHOUT executing, and the measured socket ledger
    (summed tx across every rank) must land within 15% rel of it — drift
    on either side (a static model that forgot an edge, a wire path that
    started double-shipping) fails here by name."""
    from parsec_tpu.analysis.commcheck import (agreement_rel_err,
                                               predict_collective_traffic)
    from parsec_tpu.comm.multiproc import run_multiproc
    pred = predict_collective_traffic(nranks)
    assert pred["bcast_pattern"] == "broadcast", pred
    assert pred["reduce_pattern"] == "reduce", pred
    res = run_multiproc(
        nranks, "parsec_tpu.comm.collectives:_mp_collective_body",
        timeout=240, nb_cores=1)
    observed = sum(d["bytes"] for r in res
                   for d in r["peer_stats"]["tx"].values())
    err = agreement_rel_err(pred["total_bytes"], observed)
    assert err <= COMMCHECK_AGREE_RELERR_MAX, \
        (pred["total_bytes"], observed, err)
