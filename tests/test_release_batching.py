"""Hot-path equivalence: batched dep-release vs per-task release.

ISSUE 2 rebuilt ``release_deps`` to accumulate one completing task's
successor releases and push them through ``DependencyTracking.release_many``
(grouped, one lock per dense-tier class group).  These tests pin the
contract over RANDOM layered DAGs:

- the completion SET equals the execution space exactly (nothing lost,
  nothing duplicated) under every storage tier and worker count;
- the ordering CONSTRAINT holds: every task completes strictly after each
  of its DAG predecessors (bodies append to a shared log; a successor's
  body cannot run before the release its predecessor's completion issued);
- the hashed tier (record-at-a-time through ``release_dep``) and the
  dense index-array tier (grouped batch path) drain identical DAGs to
  identical completion sets — the batched path IS the per-task path's
  semantics.

The DAG generator gives every in-edge slot its own CTL flow, so each
arrival lands on a distinct dep bit (the mask protocol's requirement), and
edge tables are plain dict lookups inside guards — exercising guard-driven
``input_dep_mask`` with 0..K_IN active inputs per task.
"""

import random
import threading

import pytest

from parsec_tpu import ptg
from parsec_tpu.runtime import Context

K_IN = 3     # max in-edges per node (one CTL flow per slot)


def _random_dag(rng, layers, width):
    """in_edges[(d, n)] = list of source idx at layer d-1 (slot order)."""
    in_edges = {}
    for d in range(1, layers):
        for n in range(width):
            k = rng.randint(0, K_IN)
            in_edges[(d, n)] = rng.sample(range(width), k) if k else []
    return in_edges


def _build_pool(in_edges, layers, width, log, lock):
    """One task class T(d, n) on a (layers x width) grid; slot-k input flow
    ``in<k>`` fed by T(d-1, src) when the edge table says so."""
    out_edges = {}   # (d, n) -> list of (succ_n, slot)
    for (d, n), srcs in in_edges.items():
        for k, s in enumerate(srcs):
            out_edges.setdefault((d - 1, s), []).append((n, k))

    p = ptg.PTGBuilder("randdag", L=layers, W=width)
    t = p.task("T",
               d=ptg.span(0, lambda g, l: g.L - 1),
               n=ptg.span(0, lambda g, l: g.W - 1))
    for k in range(K_IN):
        f = t.flow(f"in{k}", ptg.CTL)
        f.input(pred=("T", f"in{k}",
                      lambda g, l, k=k:
                      {"d": l.d - 1, "n": in_edges[(l.d, l.n)][k]}),
                guard=lambda g, l, k=k:
                l.d > 0 and k < len(in_edges.get((l.d, l.n), ())))
        # the producing side of slot k: every out-edge of (d, n) that lands
        # in some successor's slot k
        for m in range(width):
            f.output(succ=("T", f"in{k}",
                           lambda g, l, m=m:
                           {"d": l.d + 1, "n": m}),
                     guard=lambda g, l, m=m, k=k:
                     (m, k) in [(sn, sk) for sn, sk
                                in out_edges.get((l.d, l.n), ())])

    def body(es, task, g, l):
        with lock:
            log.append((l.d, l.n))

    t.body(body)
    return p.build()


def _drain(param, in_edges, layers, width, storage, nb_cores):
    param("deps_storage", storage)
    log, lock = [], threading.Lock()
    tp = _build_pool(in_edges, layers, width, log, lock)
    ctx = Context(nb_cores=nb_cores)
    ctx.add_taskpool(tp)
    ctx.wait(timeout=120)
    ctx.fini()
    return log


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
@pytest.mark.parametrize("storage,nb_cores", [
    ("index-array", 0), ("index-array", 2), ("hash", 0), ("hash", 2),
])
def test_random_dag_completion_set_and_ordering(param, seed, storage,
                                                nb_cores):
    rng = random.Random(seed)
    layers, width = 6, 7
    in_edges = _random_dag(rng, layers, width)
    log = _drain(param, in_edges, layers, width, storage, nb_cores)
    # completion set: the whole space, exactly once
    expect = {(d, n) for d in range(layers) for n in range(width)}
    assert len(log) == len(expect), f"{len(log)} != {len(expect)}"
    assert set(log) == expect
    # ordering constraint: every task after each of its predecessors
    pos = {t: i for i, t in enumerate(log)}
    for (d, n), srcs in in_edges.items():
        for s in srcs:
            assert pos[(d - 1, s)] < pos[(d, n)], \
                f"T({d},{n}) completed before its predecessor T({d - 1},{s})"


@pytest.mark.parametrize("seed", [5, 6])
def test_batched_tier_matches_per_record_tier(param, seed):
    """The dense tier's grouped batch release and the hashed tier's
    record-at-a-time release drain one identical DAG to the same set."""
    rng = random.Random(seed)
    layers, width = 5, 6
    in_edges = _random_dag(rng, layers, width)
    a = _drain(param, in_edges, layers, width, "index-array", 0)
    b = _drain(param, in_edges, layers, width, "hash", 0)
    assert set(a) == set(b)
    assert len(a) == len(b)


def test_release_many_groups_take_one_path(param):
    """A wide fan-out (one completion releasing many same-class deps) goes
    through the index-array tier's batch path and still accounts every
    release (the SDE-style engagement proof the dense tier keeps)."""
    param("deps_storage", "index-array")
    width = 16
    # FAN(0) -> every SINK(n): one completing task, 16 same-class records
    in_edges = {(1, n): [0] for n in range(width)}
    log, lock = [], threading.Lock()
    tp = _build_pool(in_edges, 2, width, log, lock)
    ctx = Context(nb_cores=0)
    ctx.add_taskpool(tp)
    ctx.wait(timeout=60)
    store = ctx.deps._index_store
    assert store is not None
    assert store.releases == width     # every fan edge through the tier
    ctx.fini()
    assert len(log) == 2 * width


def test_a_release_pays_one_lock_a_class_group_and_one_schedule_a_batch(
        param, monkeypatch):
    """2,000 tasks in 40 layers of 50, each releasing its two successors of
    the one class: every completion is one ``release_many`` call, which
    takes the class array's lock once for both records, and every batch that made something ready is one
    ``schedule_tasks`` call."""
    from parsec_tpu.runtime import scheduling
    param("deps_storage", "index-array")
    layers, width = 40, 50
    in_edges = {(d, n): [n, (n + 1) % width]
                for d in range(1, layers) for n in range(width)}
    log, lock = [], threading.Lock()
    tp = _build_pool(in_edges, layers, width, log, lock)
    ctx = Context(nb_cores=0)
    n = {"release_many": 0, "records": 0, "ready_batches": 0, "locks": 0,
         "schedule_calls": 0, "scheduled": 0}
    release_many = ctx.deps.release_many
    release_batch = ctx.deps._release_indexed_batch
    schedule_tasks = scheduling.schedule_tasks

    def counted_release(tp, records):
        ready = release_many(tp, records)
        n["release_many"] += 1
        n["records"] += len(records)
        n["ready_batches"] += bool(ready)
        return ready

    def counted_batch(*a):
        n["locks"] += 1             # the class array's lock, once a call
        return release_batch(*a)

    def counted_schedule(es, tasks, distance=0):
        n["schedule_calls"] += 1
        n["scheduled"] += len(tasks)    # before one of them is kept hot
        return schedule_tasks(es, tasks, distance)

    monkeypatch.setattr(ctx.deps, "release_many", counted_release)
    monkeypatch.setattr(ctx.deps, "_release_indexed_batch", counted_batch)
    monkeypatch.setattr(scheduling, "schedule_tasks", counted_schedule)
    ctx.add_taskpool(tp)
    ctx.wait(timeout=120)
    store = ctx.deps._index_store
    ctx.fini()
    assert len(log) == layers * width == len(set(log))
    completions = (layers - 1) * width      # the last layer releases nothing
    assert n["release_many"] == n["locks"] == completions
    assert n["records"] == store.releases == 2 * completions
    # the first layer is scheduled by the start-up, every other task here
    assert n["schedule_calls"] == n["ready_batches"]
    assert n["scheduled"] == completions


# --------------------------------------------------------------------------
# the release plan's fallbacks: edges the plan cannot resolve ahead
# --------------------------------------------------------------------------

N_FB = 4


def _fallback_pool(case, rank, nranks, seen):
    """``S(i)`` adds one to tile ``A(i)`` and feeds ``U(i)`` (beside it) and
    ``T(i)`` (on the next tile's rank); each consumer records what it read.
    ``case`` adds the one thing the release plan cannot state ahead."""
    import numpy as np

    from parsec_tpu.data.data import TileType
    from parsec_tpu.data_dist.matrix import TwoDimBlockCyclic
    A = TwoDimBlockCyclic(
        f"A{case}", lm=N_FB * 8, ln=1, mb=8, nb=1, P=nranks, Q=1,
        myrank=rank, init_fn=lambda m, n, sh: np.full(sh, m, np.float32))
    p = ptg.PTGBuilder(f"fb_{case}", A=A, N=N_FB)
    s = p.task("S", i=ptg.span(0, lambda g, l: g.N - 1))
    s.affinity("A", lambda g, l: (l.i, 0))
    fv = s.flow("V", ptg.RW)
    fv.input(data=("A", lambda g, l: (l.i, 0)))
    fv.output(succ=("U", "V", lambda g, l: {"i": l.i}))
    fv.output(succ=("T", "V", lambda g, l: {"i": l.i}),
              dtt=TileType((2, 4), np.float32) if case == "typed" else None)

    def sbody(es, task, g, l):
        c = task.flow_data("V")
        c.value = np.asarray(c.value) + 1
        c.version += 1

    s.body(sbody)
    if case == "sim":
        s.simcost(lambda g, l: l.i + 1)
    for name, shift in (("U", 0), ("T", 1)):
        c = p.task(name, i=ptg.span(0, lambda g, l: g.N - 1))
        c.affinity("A", lambda g, l, shift=shift: ((l.i + shift) % g.N, 0))
        c.flow("V", ptg.READ).input(pred=("S", "V", lambda g, l: {"i": l.i}))
        if case in ("ranged", "counted") and name == "T":
            c.flow("c", ptg.CTL).output(succ=("J", "c", lambda g, l: {"z": 0}))
        c.body(lambda es, task, g, l, name=name: seen.append(
            (name, l.i, np.asarray(task.flow_data("V").value).copy())))
    if case in ("ranged", "counted"):
        # a join: one declared CTL dep that waits for every T(i)
        j = p.task("J", z=ptg.span(0, 0))
        j.affinity("A", lambda g, l: (0, 0))
        j.flow("c", ptg.CTL).input(
            pred=("T", "c", lambda g, l: [{"i": i} for i in range(g.N)]),
            ranged=True)
        if case == "counted":
            # and a plain dep of the same, counted, class
            u = [tcb for tcb in p._classes if tcb.name == "U"][0]
            u.flow("d", ptg.CTL).output(
                succ=("J", "d", lambda g, l: {"z": 0}),
                guard=lambda g, l: l.i == 0)
            j.flow("d", ptg.CTL).input(pred=("U", "d", lambda g, l: {"i": 0}))
        j.body(lambda es, task, g, l: seen.append(("J", len(seen), None)))
    return p.build()


def _check_fallback(case, seen):
    import numpy as np
    got = {(name, i): v for name, i, v in seen if name != "J"}
    assert sorted(got) == [(n, i) for n in ("T", "U") for i in range(N_FB)]
    for (name, i), v in got.items():
        want = np.full((8, 1), i + 1, np.float32)
        if case == "typed" and name == "T":
            want = want.reshape(2, 4)       # the consumer's declared type
        np.testing.assert_array_equal(v, want)
    if case in ("ranged", "counted"):
        # the join ran once, after every T(i) and (counted) after U(0)
        (at,) = [n for name, n, _ in seen if name == "J"]
        order = [name for name, _, _ in seen[:at]]
        assert order.count("T") == N_FB
        assert case == "ranged" or ("U", 0) in [
            (name, i) for name, i, _ in seen[:at]]


@pytest.mark.parametrize("case,edges,planned", [
    ("plain", 8, 8), ("ranged", 12, 8), ("counted", 13, 8), ("typed", 8, 4),
    ("sim", 8, 0), ("two_rank", 4, 0)])
def test_an_edge_the_plan_cannot_resolve_takes_the_general_walk(
        param, case, edges, planned):
    """The release plan resolves an edge ahead only where nothing more can
    happen on it.  A counted successor (a ranged arrow into it, or a plain
    dep of the same class), a type on the edge, a simulated pool and a pool
    on two ranks keep the per-edge walk for those edges, give the answers
    they always gave, and say so in the counters: of ``release_edges``
    handed to local successors, ``release_edges_planned`` went by plan."""
    seen = []
    if case == "two_rank":
        from parsec_tpu.comm import run_multirank

        def body(ctx, rank, nranks):
            mine = []
            ctx.add_taskpool(_fallback_pool(case, rank, nranks, mine))
            ctx.wait(timeout=60)
            ctx.comm_barrier()
            return mine, ctx.release_edges, ctx.release_edges_planned

        res = run_multirank(2, body)
        seen = res[0][0] + res[1][0]
        # S(i) -> U(i) stays on the rank, S(i) -> T(i) crosses to the other
        assert [r[1] for r in res] == [edges // 2] * 2
        counts = (sum(r[1] for r in res), sum(r[2] for r in res))
    else:
        tp = _fallback_pool(case, 0, 1, seen)
        ctx = Context(nb_cores=0)
        ctx.add_taskpool(tp)
        ctx.wait(timeout=60)
        ctx.fini()
        counts = (ctx.release_edges, ctx.release_edges_planned)
        if case == "sim":
            assert tp.largest_simulation_date == N_FB
    _check_fallback(case, seen)
    assert counts == (edges, planned)
    assert (planned < edges) == (case != "plain")


def test_eight_streams_count_every_edge_they_release(param):
    """The release counters are one pair a context and every stream adds to
    them: eight workers on an eight-core machine's worth of interpreter
    switches (the interval shortened a thousandfold) release a 40 x 50 grid's
    3,900 edges, all by plan, and the counters hold exactly that, which one
    lost update would break."""
    import sys
    layers, width = 40, 50
    in_edges = {(d, n): [n, (n + 1) % width]
                for d in range(1, layers) for n in range(width)}
    log, lock = [], threading.Lock()
    tp = _build_pool(in_edges, layers, width, log, lock)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(interval / 1000)
    try:
        ctx = Context(nb_cores=8)
        ctx.add_taskpool(tp)
        ctx.wait(timeout=120)
        ctx.fini()
    finally:
        sys.setswitchinterval(interval)
    assert len(log) == layers * width == len(set(log))
    assert (ctx.release_edges, ctx.release_edges_planned) == (
        2 * (layers - 1) * width,) * 2
