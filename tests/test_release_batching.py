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

import parsec_tpu.runtime.dagrun  # noqa: F401 — registers runtime_dag_compile

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
    param("runtime_dag_compile", False)   # exercise release_deps itself
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
    param("runtime_dag_compile", False)
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
    the one class: with ``runtime_dag_compile`` off every completion is one
    ``release_many`` call, which takes the class array's lock once for both
    records, and every batch that made something ready is one
    ``schedule_tasks`` call."""
    from parsec_tpu.runtime import scheduling
    param("deps_storage", "index-array")
    param("runtime_dag_compile", False)
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
