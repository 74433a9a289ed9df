"""The phase plane (ISSUE 27, ``prof/spans.py``): every host second of a
dynamic solve gets an owner on the profiler's clock, and costs nothing while
nobody looks.  CPU devices wrapped as accelerators, N=1024 nb=128; only what
repeats exactly is asserted (names, counts, sums against the solve's own
walls), never a duration."""

import json
import time

import numpy as np
import pytest

import jax

from parsec_tpu.data.data import ACCESS_WRITE
from parsec_tpu.data_dist.matrix import SymTwoDimBlockCyclic, TiledMatrix
from parsec_tpu.device import registry
from parsec_tpu.device.tpu import CALL_FIELDS, TPUDevice
from parsec_tpu.prof import spans
from parsec_tpu.runtime import Context

N, NB = 1024, 128
NT = N // NB
# docs/OBSERVABILITY.md, "The phase plane": the names are the contract
DOCUMENTED = {
    "ctx.init", "ctx.add_taskpool", "ctx.progress", "ctx.fini",
    "devmod.manage", "sched.flood", "devmod.prefetch", "devmod.stage_in",
    "devmod.dispatch", "devmod.call", "devmod.land",
    "devmod.inflight_wait", "devmod.sync",
    "devmod.complete", "sched.release", "devmod.pushout", "devmod.drain",
    "devmod.writeback", "devmod.pressure"}
WALLS = ("t_stage_in", "t_dispatch", "t_complete", "t_drain", "t_writeback",
         "t_manager")


def _gemm():
    from parsec_tpu.models.tiled_gemm import tiled_gemm_ptg
    rng = np.random.default_rng(27)
    a = rng.standard_normal((N, N)).astype(np.float32)
    A = TiledMatrix.from_dense("A", a, NB, NB)
    B = TiledMatrix.from_dense("B", a.T.copy(), NB, NB)
    C = TiledMatrix("C", N, N, NB, NB)
    return tiled_gemm_ptg(A, B, C), NT ** 3, {"gemm"}, NT * NT


def _cholesky():
    from parsec_tpu.models.cholesky import tiled_cholesky_ptg
    rng = np.random.default_rng(27)
    m = rng.standard_normal((N, N)).astype(np.float32)
    spd = m @ m.T / N + 2 * np.eye(N, dtype=np.float32)
    A = SymTwoDimBlockCyclic(
        "A", N, N, NB, NB, dtype=np.float32,
        init_fn=lambda i, k, shape: np.ascontiguousarray(
            spd[i * NB:(i + 1) * NB, k * NB:(k + 1) * NB]))
    tasks = NT + NT * (NT - 1) + NT * (NT - 1) * (NT - 2) // 6
    return (tiled_cholesky_ptg(A), tasks, {"trsm_rlt", "syrk_ln", "gemm_nt"},
            NT * (NT + 1) // 2)


PROBLEMS = {"gemm": _gemm, "cholesky": _cholesky}


@pytest.fixture
def one_accelerator(param, monkeypatch, device_registry):
    """One CPU device wrapped as the accelerator, and the plane left as it
    was found: off, its table empty."""
    param("device_tpu_allow_cpu", True)
    real = jax.devices
    monkeypatch.setattr(jax, "devices", lambda *a: real(*a)[:1])
    spans.phase_reset()
    yield
    spans.phase_refresh()
    spans.phase_reset()
    assert not spans.phase_on


def _solve(pool, fused=None):
    """One solve as the benchmark's dynamic path makes it; its wall, the
    deltas of the device's walls and counters over it, and the device.
    ``fused`` collects (task class name, tasks) of every fused batch, in
    order, through the device's hook before the fused call."""
    t0 = time.perf_counter()
    ctx = Context(nb_cores=0)
    (dev,) = [d for d in registry.devices if isinstance(d, TPUDevice)]
    before = {k: getattr(dev, k) for k in WALLS + ("executed_tasks",
                                                   "xla_calls")}
    calls_before = dict(dev.calls_by_class)
    if fused is not None:
        dev._dispatch_hook = lambda batch: fused.append(
            (batch[0].task.task_class.name, len(batch)))
    ctx.add_taskpool(pool)
    ctx.wait(timeout=120)
    dev.sync()
    dev.flush_cache()
    ctx.fini()
    dev._dispatch_hook = None
    wall = time.perf_counter() - t0
    delta = {k: getattr(dev, k) - v for k, v in before.items()}
    delta["calls_by_class"] = {k: v - calls_before.get(k, 0)
                               for k, v in dev.calls_by_class.items()}
    return wall, delta, dev


class _Counted:
    """Stands in for ``jax.profiler.TraceAnnotation``: counts what is
    built."""
    built = 0

    def __init__(self, name, **args):
        type(self).built += 1

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        pass


@pytest.mark.parametrize("problem", PROBLEMS)
def test_off_a_solve_builds_nothing_and_asks_once_per_batch(
        problem, one_accelerator, monkeypatch):
    pool, tasks, _, _ = PROBLEMS[problem]()
    spans.phase_refresh()       # binds the profiler's probe
    asked = []
    probe = spans._session_active
    monkeypatch.setattr(spans, "_session_active",
                        lambda: asked.append(1) or probe())
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Counted)
    _Counted.built = 0
    # the chip's queue is read through ``jax.Array.is_ready`` and by nobody
    # while the plane is off
    probed = []
    array_type = type(jax.numpy.zeros(1))
    is_ready = array_type.is_ready
    monkeypatch.setattr(array_type, "is_ready",
                        lambda self: probed.append(1) or is_ready(self))
    _, delta, dev = _solve(pool)
    assert delta["executed_tasks"] == tasks
    assert spans.phase_totals() == {}
    assert _Counted.built == 0
    assert dev.call_table == {} and dev.debug_state()["call_table"] == []
    assert probed == []
    # Context init, add_taskpool, sync, flush_cache, fini: five a solve
    assert len(asked) <= delta["xla_calls"] + 5, (len(asked), delta)
    assert delta["xla_calls"] < tasks       # per batch is not per task


@pytest.mark.parametrize("how", ["profiler_session", "prof_spans"])
@pytest.mark.parametrize("problem", PROBLEMS)
def test_on_every_second_of_a_solve_has_a_documented_owner(
        problem, how, one_accelerator, param, tmp_path, monkeypatch):
    pool, tasks, classes, result_tiles = PROBLEMS[problem]()
    if how == "prof_spans":
        param("prof_spans", True)
    else:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    # probes: (depth, held run, held, the ring's length) a call
    fused, probes = [], []
    probe = TPUDevice._queue_depth

    def recorded(self):
        depth, held_run = probe(self)
        probes.append((depth, held_run, self._held_bytes,
                       len(self._inflight)))
        return depth, held_run
    monkeypatch.setattr(TPUDevice, "_queue_depth", recorded)
    try:
        wall, delta, dev = _solve(pool, fused)
    finally:
        if how == "prof_spans":
            param("prof_spans", False)
            spans.uninstall()       # Context installed the request recorder
        else:
            jax.profiler.stop_trace()
    table = spans.phase_totals()
    assert set(table) <= DOCUMENTED
    # a solve that fits the budget waits for nothing and makes no room, and
    # ``devmod.prefetch`` is ``prefetch_data``'s (the KV tiers' call): every
    # other name shows
    assert set(table) >= DOCUMENTED - {"devmod.inflight_wait",
                                       "devmod.pressure",
                                       "devmod.prefetch"}, set(table)
    assert "devmod.pressure" not in table
    assert dev.pressure_confirms == 0 and dev.evicted_bytes == 0

    # a wall and its spans are fed from one pair of clock readings
    for names, attr in ((("devmod.stage_in",), "t_stage_in"),
                        (("devmod.dispatch",), "t_dispatch"),
                        (("devmod.complete",), "t_complete"),
                        (("devmod.drain",), "t_drain"),
                        (("devmod.writeback",), "t_writeback"),
                        (("devmod.manage",), "t_manager")):
        inclusive = sum(table[n][1] for n in names) / 1e9
        assert inclusive == pytest.approx(delta[attr], rel=0.02), attr
    owned = sum(row[0] for row in table.values()) / 1e9
    assert 0.9 * wall <= owned <= wall, (owned, wall)
    assert all(row[0] >= 0 for row in table.values()), table
    assert table["sched.release"][2] == tasks == delta["executed_tasks"]
    # a counter per memory edge, inside the release and off its self time
    assert table["devmod.pushout"][2] == result_tiles
    assert table["sched.release"][1] - table["sched.release"][0] \
        == table["devmod.pushout"][1]
    # one span a batch or a solve, none a task
    assert table["devmod.dispatch"][2] == delta["xla_calls"]
    assert table["ctx.init"][2] == table["devmod.writeback"][2] == 1
    programs = {fn.__name__ for fn in dev._vmap_cache.values()}
    assert programs >= {f"fused_{c}" for c in classes}, programs
    assert all(name.startswith("fused_") for name in programs)

    # inside the dispatch: one call and one landing a dispatch, and with the
    # gather (the dispatch's self time) they are the dispatch
    calls = delta["xla_calls"]
    assert table["devmod.call"][2] == table["devmod.land"][2] == calls
    gather, dispatch, _ = table["devmod.dispatch"]
    assert gather + table["devmod.call"][1] + table["devmod.land"][1] \
        == pytest.approx(dispatch, rel=0.02)
    # the call table holds what the batches were: a fused batch of B tasks
    # is one call of Bp lanes, Bp the next power of two; what is left of the
    # solve's calls ran one task each
    flows = {tc.name: ([f for f in tc.flows if not f.is_ctl],
                       [f for f in tc.flows if not f.is_ctl
                        and f.access & ACCESS_WRITE])
             for tc in pool.task_classes}
    expect = {}
    for name, b in fused:
        row = expect.setdefault((name, 1 << (b - 1).bit_length()), [0, 0])
        row[0] += 1
        row[1] += b
    for name, n in delta["calls_by_class"].items():
        alone = n - sum(1 for fused_name, _ in fused if fused_name == name)
        if alone:
            expect[name, 1] = [alone, alone]
    assert sum(n for n, _ in expect.values()) == calls
    if problem == "cholesky":
        assert expect["POTRF", 1] == [NT, NT]       # no fused form
    rows = {(r["task_class"], r["lanes"]): r
            for r in json.loads(json.dumps(dev.debug_state()["call_table"]))}
    assert set(rows) == set(expect) == set(dev.call_table)
    for (name, lanes), (n, b) in expect.items():
        row = rows[name, lanes]
        assert set(row) == {"task_class", "lanes", *CALL_FIELDS}
        assert (row["calls"], row["tasks"]) == (n, b)
        assert row["args"] == n * lanes * len(flows[name][0])
        assert row["results"] == n * lanes * len(flows[name][1])
        # the pad lanes: what the calls ran beyond the tasks they were for
        assert 0 <= n * lanes - b < n * max(lanes // 2, 1)
    assert sum(r["call_ns"] for r in rows.values()) == table["devmod.call"][1]
    # the chip's queue at every enqueue: at most what the ring held then
    # (with one accelerator never past the count), and what has run is part
    # of what the ring holds
    assert len(probes) == calls
    assert all(0 <= depth <= ring <= dev._max_inflight and 0 <= run <= held
               for depth, run, held, ring in probes), probes
    assert dev.ring_peak <= dev._max_inflight and dev.ring_excused == 0
    for i, field in ((0, "depth_sum"), (1, "held_run_bytes_sum"),
                     (2, "held_bytes_sum")):
        assert sum(r[field] for r in rows.values()) \
            == sum(p[i] for p in probes), field


class _Result:
    """What a dispatch hands back, as the probe sees it: ``is_ready``, and
    ``is_deleted`` (``ready`` None: an array a later call was donated,
    which raises when asked whether it is ready, as a ``jax.Array`` does)."""

    def __init__(self, ready, asked):
        self.ready, self.asked = ready, asked

    def is_deleted(self):
        return self.ready is None

    def is_ready(self):
        if self.ready is None:
            raise RuntimeError("Array has been deleted.")
        self.asked.append(self)
        return self.ready


# the ring, oldest first: R an entry the chip has run, N one it has not,
# X one whose body handed back no array, D one all of whose arrays a later
# call was donated; and the depth the probe must give
RINGS = [("empty", "", 0),
         ("all_run", "R" * 32, 0),
         ("none_run", "N" * 32, 32),
         ("one_owed", "R" * 31 + "N", 1),
         ("one_run", "R" + "N" * 31, 31),
         ("half", "R" * 16 + "N" * 16, 16),
         ("odd_ring", "RRRNN", 2),
         # no array: counted with the entry enqueued before it
         ("no_array_after_a_run_one", "RRXNN", 2),
         ("no_array_among_the_owed", "RRNXN", 3),
         ("no_array_oldest", "XNNN", 3),
         ("no_array_newest", "RRRX", 0),
         ("no_arrays_at_all", "XXXX", 0),
         ("no_arrays_around_the_edge", "RXXXNXXX", 4),
         # all donated: as ready as the first later entry with a live array
         ("a_chain_of_donated_calls_the_last_run", "D" * 31 + "R", 0),
         ("a_chain_of_donated_calls_the_last_owed", "D" * 31 + "N", 32),
         ("donated_among_the_run", "RDDRRNN", 2),
         ("donated_among_the_owed", "RRNDDN", 4),
         ("donated_around_the_edge", "RDDDNDDN", 7),
         ("donated_then_no_array", "RRDXNN", 4),
         ("no_array_then_donated", "RRXDNN", 3),
         ("donated_newest", "RRDD", 0),
         ("one_live_result_beside_a_donated_one", "RRMNN", 3)]


@pytest.mark.parametrize("ring,depth", [r[1:] for r in RINGS],
                         ids=[r[0] for r in RINGS])
def test_the_probe_finds_the_first_dispatch_the_chip_still_owes(ring, depth):
    asked = []
    results = {"R": lambda: ((_Result(True, asked), 5.0),),
               "N": lambda: ((_Result(False, asked),), (np.zeros(1),)),
               "X": lambda: (np.float32(1.0), [None]),
               "D": lambda: (_Result(None, asked), (_Result(None, asked),)),
               "M": lambda: (_Result(None, asked), _Result(False, asked))}
    dev = TPUDevice.__new__(TPUDevice)
    # every entry holds 2 ** i bytes, so the sum names the entries counted
    dev._inflight = [(results[kind](), 1 << i) for i, kind in enumerate(ring)]
    got, held_run = dev._queue_depth()
    assert got == depth
    assert held_run == (1 << (len(ring) - depth)) - 1
    # a bisection, not a scan: six probes at most for a ring of 32
    assert len(asked) <= 6


def test_the_manager_s_wall_is_added_to_under_the_module_s_lock(
        one_accelerator, monkeypatch):
    """``t_manager`` closes after its thread gave the managership up, when
    the next manager may be closing its own: the one wall added to under
    ``_mutex_lock``; the manager's other walls close while it manages."""
    from parsec_tpu.device import tpu
    pool, tasks, _, _ = _gemm()
    locked = []                                  # (wall, lock held) an add

    def add(self, dt):
        locked.append((self.attr, self.lock is not None
                       and self.lock.locked()))
        setattr(self.dev, self.attr, getattr(self.dev, self.attr) + dt / 1e9)
    monkeypatch.setattr(tpu._Wall, "_add", add)
    _, delta, dev = _solve(pool)
    assert delta["executed_tasks"] == tasks and delta["t_manager"] > 0
    assert {held for wall, held in locked if wall == "t_manager"} == {True}
    assert {held for wall, held in locked if wall != "t_manager"} == {False}


def test_under_a_tight_budget_the_pressure_has_its_own_span(one_accelerator,
                                                            param):
    """``devmod.pressure`` opens only when the budget presses, under
    ``devmod.manage``; its self time is the confirming and the queueing of
    evictions, the wait stays ``devmod.inflight_wait``'s, and the solve's
    seconds are owned as before."""
    pool, tasks, _, _ = PROBLEMS["cholesky"]()
    param("prof_spans", True)
    Context(nb_cores=0).fini()         # registers the accelerator
    (dev,) = [d for d in registry.devices if isinstance(d, TPUDevice)]
    dev._mem_budget = 40 * NB * NB * 4     # the triangle is 36 tiles
    spans.phase_reset()
    try:
        wall, delta, dev = _solve(pool)
    finally:
        param("prof_spans", False)
        spans.uninstall()
    table = spans.phase_totals()
    assert set(table) <= DOCUMENTED and delta["executed_tasks"] == tasks
    self_ns, inclusive_ns, count = table["devmod.pressure"]
    assert count >= 1 and dev.pressure_confirms >= 1
    assert 0 <= self_ns <= inclusive_ns
    # the waits it caused are inside it and not its own
    assert table["devmod.inflight_wait"][2] >= dev.pressure_confirms
    # where the gather's ``_make_room`` opened it, it is the dispatch's
    # fourth part (it also opens under the stage-in, so not all of the row)
    gather, dispatch, _ = table["devmod.dispatch"]
    under_dispatch = dispatch - gather - table["devmod.call"][1] \
        - table["devmod.land"][1]
    assert -0.02 * dispatch <= under_dispatch <= inclusive_ns
    assert dispatch / 1e9 == pytest.approx(delta["t_dispatch"], rel=0.02)
    owned = sum(row[0] for row in table.values()) / 1e9
    assert 0.9 * wall <= owned <= wall, (owned, wall)


# the DTD front end's two rows (PR 34), on top of the ones above
DTD_DOCUMENTED = {"dtd.insert", "dtd.window"}


def test_a_dtd_solve_owns_its_insertion_and_its_window_drives(
        one_accelerator, param):
    """``dtd.insert`` is a counter per inserted task, outside any span on the
    client's thread; ``dtd.window`` one inclusive span per
    execute-and-come-back with ``ctx.progress`` and the device module's spans
    nested in it, so its self time is next to nothing and the solve's seconds
    stay owned."""
    from parsec_tpu.dtd import DTDTaskpool
    from parsec_tpu.models.tiled_gemm import tiled_gemm_dtd
    rng = np.random.default_rng(34)
    a = rng.standard_normal((N, N)).astype(np.float32)
    colls = (TiledMatrix.from_dense("A", a, NB, NB),
             TiledMatrix.from_dense("B", a.T.copy(), NB, NB),
             TiledMatrix("C", N, N, NB, NB))
    param("dtd_window_size", 64)
    param("dtd_threshold_size", 32)
    param("prof_spans", True)
    try:
        t0 = time.perf_counter()
        ctx = Context(nb_cores=0)
        (dev,) = [d for d in registry.devices if isinstance(d, TPUDevice)]
        tp = DTDTaskpool()
        ctx.add_taskpool(tp)
        tiled_gemm_dtd(tp, *colls)
        tp.wait(timeout=120)
        dev.sync()
        dev.flush_cache()
        ctx.fini()
        wall = time.perf_counter() - t0
    finally:
        param("prof_spans", False)
        spans.uninstall()
    table = spans.phase_totals()
    assert DTD_DOCUMENTED <= set(table) <= DOCUMENTED | DTD_DOCUMENTED
    assert table["dtd.insert"][2] == tp.inserted == NT ** 3
    assert table["dtd.insert"][0] == table["dtd.insert"][1]     # no child
    assert table["dtd.window"][2] == tp.window_drives >= 1
    # each drive, and the wait, is one ctx.progress; the drives' lie inside
    # dtd.window, whose own time is what is left around them
    assert table["ctx.progress"][2] == tp.window_drives + 1
    self_ns, inclusive_ns, _ = table["dtd.window"]
    assert 0 <= self_ns <= 0.05 * inclusive_ns
    assert inclusive_ns < table["ctx.progress"][1]
    assert table["sched.release"][2] == NT ** 3
    assert table["devmod.pushout"][2] == NT * NT
    owned = sum(row[0] for row in table.values()) / 1e9
    assert 0.8 * wall <= owned <= wall, (owned, wall)
