"""Live properties export: an external observer reads runtime gauges
mid-run (the ``dictionary.c`` + ``tools/aggregator_visu`` pair, VERDICT r3
missing #4): the context registers its scheduler depth / task gauges in
the properties dictionary and, with ``props_stream`` set, tails JSON
snapshots to a file while taskpools execute.
"""

import threading
import time

import numpy as np

from parsec_tpu import ptg
from parsec_tpu.data_dist.matrix import VectorTwoDimCyclic
from parsec_tpu.prof.counters import properties, read_live_snapshot, sde
from parsec_tpu.runtime import Context


def _slow_chain(V, nt, delay):
    p = ptg.PTGBuilder("slow", V=V, NT=nt, D=delay)
    t = p.task("T", i=ptg.span(0, lambda g, l: g.NT - 1))
    t.affinity("V", lambda g, l: (0,))
    f = t.flow("A", ptg.RW)
    f.input(data=("V", lambda g, l: (0,)), guard=lambda g, l: l.i == 0)
    f.input(pred=("T", "A", lambda g, l: {"i": l.i - 1}),
            guard=lambda g, l: l.i > 0)
    f.output(succ=("T", "A", lambda g, l: {"i": l.i + 1}),
             guard=lambda g, l: l.i < g.NT - 1)
    f.output(data=("V", lambda g, l: (0,)),
             guard=lambda g, l: l.i == g.NT - 1)

    def body(es, task, g, l):
        time.sleep(g.D)
        task.flow_data("A").value[...] += 1.0

    t.body(body)
    return p.build()


def test_snapshot_readable_during_run(tmp_path, param):
    """The acceptance gate: a reader thread observes a streamed snapshot
    WHILE the taskpool is still executing, and the snapshot carries the
    context gauges."""
    path = str(tmp_path / "props.json")
    param("props_stream", path)
    param("props_stream_interval", 0.02)

    V = VectorTwoDimCyclic("V", lm=4, mb=4,
                           init_fn=lambda m, size: np.zeros(size))
    tp = _slow_chain(V, nt=12, delay=0.05)
    seen: list[dict] = []
    ctx = Context(nb_cores=1)

    stop = threading.Event()

    def reader():
        while not stop.is_set():
            try:
                snap = read_live_snapshot(path)
            except (FileNotFoundError, ValueError):
                time.sleep(0.01)
                continue
            if not tp.test():          # captured strictly mid-run
                seen.append(snap)
            time.sleep(0.01)

    th = threading.Thread(target=reader)
    th.start()
    try:
        ctx.add_taskpool(tp)
        ctx.wait(timeout=60)
    finally:
        stop.set()
        th.join(timeout=10)
        ctx.fini()

    assert seen, "no snapshot observed while the taskpool was running"
    snap = seen[-1]
    assert "ts" in snap
    r0 = snap["props"]["rank0"]
    assert r0["active_taskpools"] >= 1
    assert r0["nb_tasks"] >= 1          # tasks still outstanding mid-run
    assert "sched_pending" in r0 and "sde" in r0


def test_properties_registry_lifecycle(param):
    """Context registration appears in the dictionary and is removed at
    fini (no leakage across contexts)."""
    ctx = Context(nb_cores=0)
    # "rank0", or "rank0#1" beside a context that an earlier test of this
    # worker process left alive mid-run: that one keeps "rank0"
    ns = ctx._props_ns
    assert ns.split("#")[0] == "rank0"
    snap = properties.snapshot()
    assert ns in snap and "sched_pending" in snap[ns]
    ctx.fini()
    snap = properties.snapshot()
    assert ns not in snap


def test_custom_property_and_sde_in_snapshot(param):
    properties.register("app", "phase", lambda: "factorize")
    try:
        sde.inc("app::custom", 3)
        snap = properties.snapshot()
        assert snap["app"]["phase"] == "factorize"
        assert sde.get("app::custom") >= 3
    finally:
        properties.unregister("app", "phase")


def test_dashboard_renders_snapshot():
    """The aggregator_visu consumer: a snapshot becomes a readable table
    with one column per rank namespace and sde dicts expanded to rows."""
    from parsec_tpu.prof.dashboard import render_snapshot
    snap = {"ts": 1000.0, "props": {
        "rank0": {"sched_pending": 3, "nb_tasks": 7,
                  "sde": {"parsec::steals": 2}},
        "rank1": {"sched_pending": 0, "nb_tasks": 4,
                  "sde": {"parsec::steals": 9}},
    }}
    text = render_snapshot(snap)
    assert "rank0" in text and "rank1" in text
    assert "sched_pending" in text and "sde:parsec::steals" in text
    lines = text.splitlines()
    row = next(l for l in lines if l.startswith("nb_tasks"))
    assert "7" in row and "4" in row


def test_dashboard_watch_live(tmp_path, param):
    """watch() renders frames from the live stream while a pool runs."""
    import io
    from parsec_tpu.prof.dashboard import watch
    path = str(tmp_path / "props.json")
    param("props_stream", path)
    param("props_stream_interval", 0.02)
    V = VectorTwoDimCyclic("V", lm=4, mb=4,
                           init_fn=lambda m, size: np.zeros(size))
    tp = _slow_chain(V, nt=6, delay=0.03)
    ctx = Context(nb_cores=1)
    try:
        ctx.add_taskpool(tp)
        ctx.start()              # opens the props stream
        time.sleep(0.15)
        buf = io.StringIO()
        watch(path, interval=0.02, iterations=3, out=buf)
        ctx.wait(timeout=60)
    finally:
        ctx.fini()
    text = buf.getvalue()
    assert "rank0" in text and "sched_pending" in text
