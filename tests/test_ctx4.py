"""One ``Context`` over four accelerators (ISSUE 40; the benchmark's cell
``geqrf52k.ctx4`` runs this on four chips): ``best_device`` deals the tiles'
columns over the accelerators, a flood hands another chip's tasks back to
the scheduler, a miss whose newest copy is another chip's array crosses chip to
chip and is counted apart from the host's tiles, a write makes the other
chips' copies invalid, no flush lowers a host version, a clean copy leaves
the LRU without a device-to-host copy.  CPU stand-in with four of the suite's
virtual devices; results and counts are asserted, never a duration."""

import contextlib
import os
import sys
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from parsec_tpu.data.data import (ACCESS_RW, COHERENCY_INVALID,
                                  COHERENCY_OWNED, COHERENCY_SHARED,
                                  data_create)
from parsec_tpu.data_dist.matrix import SymTwoDimBlockCyclic, TwoDimBlockCyclic
from parsec_tpu.device.tpu import TPUDevice, TPUDeviceTask
from parsec_tpu.prof import spans
from parsec_tpu.runtime import Context
from test_fused_forms import NB, _dispatch, _tasks
from test_phase_spans import _Result

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")


@pytest.fixture
def four(device_registry):
    return [device_registry.add(TPUDevice(jd)) for jd in jax.devices()[:4]]


def _qr(nt=6, nb=32, seed=40):
    from parsec_tpu.models.qr import tiled_qr_ptg
    sys.path.insert(0, BENCH)
    try:
        import reference as ref
        import reference_qr as refq
    finally:
        sys.path.remove(BENCH)
    n = nt * nb
    tiles = refq.qr_tiles(seed, n, nb)
    A = TwoDimBlockCyclic("A", n, n, nb, nb,
                          init_fn=lambda m, k, shape: tiles[m, k])
    T = TwoDimBlockCyclic("T", n, n, nb, nb)
    t_keys = [(m, k) for m in range(nt) for k in range(m + 1)]
    X = ref.probes(seed, n)

    def result():
        a = {k: A.data_of(*k).get_copy(0).value for k in tiles}
        t = {k: T.data_of(*k).get_copy(0).value for k in t_keys}
        return [a[k] for k in sorted(a)] + [t[k] for k in sorted(t)], (a, t)

    def gap(factored) -> float:
        ax = refq.apply(tiles, X, nb)
        want = (ax, refq.apply_t(tiles, ax, nb))
        return max(ref.gap(g, w)
                   for g, w in zip(refq.qr_got(*factored, X, nb), want))

    ntasks = nt + nt * (nt - 1) + (nt - 1) * nt * (2 * nt - 1) // 6
    return (tiled_qr_ptg(A, T, devices="tpu"), ntasks,
            len(tiles) + len(t_keys), result, gap)


def _cholesky(nt=8, nb=32, seed=40):
    from parsec_tpu.models.cholesky import tiled_cholesky_ptg
    n = nt * nb
    m = np.random.default_rng(seed).standard_normal((n, n)).astype(np.float32)
    spd = m @ m.T + n * np.eye(n, dtype=np.float32)
    A = SymTwoDimBlockCyclic.from_dense("A", spd, nb, nb)
    keys = [(i, j) for i in range(nt) for j in range(i + 1)]

    def result():
        return [A.data_of(*k).get_copy(0).value for k in keys], \
            np.tril(A.to_dense())

    def gap(factor) -> float:
        want = np.linalg.cholesky(spd.astype(np.float64))
        return float(np.linalg.norm(factor - want) / np.linalg.norm(want))

    ntasks = nt + nt * (nt - 1) + nt * (nt - 1) * (nt - 2) // 6
    return tiled_cholesky_ptg(A, devices="tpu"), ntasks, len(keys), result, gap


def _solve(devs, make, monkeypatch):
    """One solve over ``devs``; the tiles on the host, what ``gap`` reads,
    and the arrays that crossed from chip to chip, counted from outside the
    module's counters."""
    pool, ntasks, tiles_in, result, gap = make()
    crossed = {"tiles": 0, "bytes": 0}
    transfer = TPUDevice._transfer

    def counting(self, values, far=()):
        for v in values:
            on = getattr(v, "devices", None)
            if on is not None and on() != {self.jax_device}:
                crossed["tiles"] += 1
                crossed["bytes"] += v.nbytes
        return transfer(self, values, far)

    monkeypatch.setattr(TPUDevice, "_transfer", counting)
    ctx = Context(nb_cores=0)
    ctx.add_taskpool(pool)
    ctx.wait(timeout=300)
    for d in devs:
        d.sync()
    for d in devs:
        d.flush_cache()
    ctx.fini()
    tiles, factored = result()
    assert all(isinstance(t, np.ndarray) for t in tiles)
    return tiles, gap(factored), ntasks, tiles_in, crossed


# f32 tiles, 6 x 6 and 8 x 8 of 32: what a sound run reads against float64
LIMIT = {"qr": 2e-5, "cholesky": 2e-6}


@pytest.mark.parametrize("name,make", [("qr", _qr), ("cholesky", _cholesky)])
def test_four_accelerators_factor_what_one_does(four, device_registry,
                                                monkeypatch, name, make):
    """``tiled_qr_ptg`` at NT = 6 and ``tiled_cholesky_ptg`` at NT = 8 over
    four accelerators: right against the float64 reference, equal to the
    one-accelerator run within the f32 limit, every accelerator ran a share,
    what a flood popped for another chip went back and is counted, and the
    bytes are counted by where they came from."""
    tiles4, gap4, ntasks, tiles_in, crossed = _solve(four, make, monkeypatch)
    assert gap4 < LIMIT[name], gap4
    ran = [d.executed_tasks for d in four]
    assert sum(ran) == ntasks and min(ran) > 0, ran
    assert max(ran) <= 0.5 * ntasks, ran      # six and eight columns on four
    # a flood pops its class and hands back what is another chip's: on the
    # stand-in about a put-back a task (0.039 on the chips, where a batch
    # is dispatched while the next tasks become ready: PERF.md, PR 40)
    assert 0 < sum(d.flood_putbacks for d in four) < 2 * ntasks
    # the host's tiles alone, each staged once; the rest came from a chip
    tile = 32 * 32 * 4
    assert sum(d.bytes_in for d in four) == tiles_in * tile
    assert crossed["tiles"] > 0
    assert sum(d.d2d_tiles for d in four) == crossed["tiles"]
    assert sum(d.bytes_d2d for d in four) == crossed["bytes"] \
        == crossed["tiles"] * tile
    assert sum(d.bytes_out for d in four) == tiles_in * tile
    # every tile has one writer chip under the owner rule: nothing to make
    # invalid, no clean copy dropped (the stand-in's budget is not pressed)
    assert sum(d.invalidated_copies + d.replicas_dropped for d in four) == 0
    assert all(d.enabled for d in four)

    # the same solve on one accelerator of its own
    device_registry.devices = [d for d in device_registry.devices
                               if d not in four]
    one = device_registry.add(TPUDevice(jax.devices()[4]))
    tiles1, gap1, _, _, crossed1 = _solve([one], make, monkeypatch)
    assert gap1 < LIMIT[name] and one.executed_tasks == ntasks
    assert crossed1["tiles"] == 0 and one.bytes_d2d == 0 == one.d2d_tiles
    assert one.flood_putbacks == 0 and one.bytes_in == tiles_in * tile
    for got, want in zip(tiles4, tiles1):
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-4)


def test_one_accelerator_counts_a_16_tile_cholesky_as_before(
        four, device_registry, monkeypatch):
    """With one accelerator every counter reads what
    ``tests/test_ready_queue.py`` pins: 816 tasks, each of the 136 tiles in
    once and out once, and none of what several accelerators add."""
    from test_ready_queue import _potrf
    device_registry.devices = [d for d in device_registry.devices
                               if d not in four[1:]]
    dev = four[0]
    pool, ntasks, result = _potrf(16)
    ctx = Context(nb_cores=0)
    ctx.add_taskpool(pool)
    ctx.wait(timeout=300)
    dev.sync()
    dev.flush_cache()
    ctx.fini()
    got, want = result()
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-4)
    assert (dev.executed_tasks, dev.flood_putbacks) == (816, 0) == (ntasks, 0)
    assert dev.bytes_in == dev.bytes_out == 136 * 256
    assert (dev.bytes_d2d, dev.d2d_tiles, dev.invalidated_copies,
            dev.replicas_dropped, dev.replica_bytes_dropped,
            dev.evicted_bytes) == (0, 0, 0, 0, 0, 0)
    assert dev.stats()["bytes_d2d"] == 0 == dev.debug_state()["bytes_d2d"]
    # no peer: the ring's count holds at every enqueue
    assert dev._peers == [] and dev.ring_excused == 0 < dev.ring_bounded
    assert dev.ring_peak == dev._max_inflight


def _writer(copy):
    """A task-like with one RW flow on ``copy``, for ``stage_in_many`` and
    ``_mark_written``."""
    flow = SimpleNamespace(is_ctl=False, access=ACCESS_RW, flow_index=0)
    return SimpleNamespace(task_class=SimpleNamespace(flows=[flow]),
                           data=[copy])


def _write(dev, copy, fn):
    """What a dispatch on ``dev`` does to a tile: stage it in, replace the
    value, one version on, mark it written."""
    task = _writer(copy)
    dev.stage_in_many([task])
    mine = task.data[0]
    assert mine.device_index == dev.device_index
    mine.value = fn(mine.value)
    mine.version += 1
    dev._mark_written(task)
    return mine


@pytest.mark.parametrize("order", [(0, 1), (1, 0)], ids=["ab", "ba"])
@pytest.mark.parametrize("invalidate", [True, False],
                         ids=["invalid", "left_dirty"])
def test_a_tile_written_on_two_chips_comes_home_at_its_newest(
        four, monkeypatch, order, invalidate):
    """Chip A writes a tile, chip B reads A's copy across and writes it
    again: A's copy is invalid, and whichever chip is flushed first the host
    holds B's version.  With the invalidation taken away the write-back's
    own rule still holds: it never lowers the host's version."""
    if not invalidate:
        monkeypatch.setattr(TPUDevice, "_invalidate_elsewhere",
                            lambda self, d: None)
    a, b = four[:2]
    tile = np.arange(NB * NB, dtype=np.float32).reshape(NB, NB)
    datum = data_create(tile.copy(), key=("two", "chips"))
    on_a = _write(a, datum.get_copy(0), lambda v: v + 1.0)
    assert (a.bytes_in, a.bytes_d2d) == (tile.nbytes, 0)
    on_b = _write(b, on_a, lambda v: v * 2.0)
    assert (b.bytes_in, b.bytes_d2d, b.d2d_tiles) == (0, tile.nbytes, 1)
    assert on_b.version == on_a.version + 1 == 3
    assert on_b.coherency == COHERENCY_OWNED
    assert datum.owner_device == b.device_index
    if invalidate:
        assert on_a.coherency == COHERENCY_INVALID
        assert (a.invalidated_copies, b.invalidated_copies) == (0, 1)
    else:
        assert on_a.coherency == COHERENCY_OWNED      # the loser, dirty
    for i in order:
        four[i].sync()
        four[i].flush_cache()
    home = datum.get_copy(0)
    assert isinstance(home.value, np.ndarray) and home.version == 3
    np.testing.assert_array_equal(home.value, (tile + 1.0) * 2.0)
    assert datum.newest_copy() is home and list(datum.device_copies) == [0]
    assert on_a.coherency == COHERENCY_INVALID == on_b.coherency
    # the loser started no transfer home once it was invalid
    assert b.writebacks == 1 and a.writebacks == (0 if invalidate or
                                                  order == (1, 0) else 1)


def test_a_clean_copy_evicted_under_a_small_budget_is_dropped_not_written(
        four):
    """Three tiles under a budget of two: the least recently used one is
    clean (the host holds that version), so it leaves without a
    device-to-host copy and is counted as dropped, not as evicted; staged
    again it comes from the host."""
    dev = four[0]
    tile = NB * NB * 4
    dev._mem_budget = 2 * tile
    data = [data_create(np.full((NB, NB), i, np.float32), key=("clean", i))
            for i in range(3)]
    for d in data:
        dev.stage_in_many([_writer(d.get_copy(0))])
        dev._drain_evictions()
    assert (dev.replicas_dropped, dev.replica_bytes_dropped) == (1, tile)
    assert (dev.evicted_bytes, dev.deferred_evictions, dev.writebacks,
            dev.bytes_out, dev.pushouts) == (0, 0, 0, 0, 0)
    assert data[0].get_copy(dev.device_index) is None
    assert dev.debug_state()["lru_tiles"] == 2
    # a dirty victim still goes home: write the oldest resident, stage two
    kept = _write(dev, data[1].get_copy(0), lambda v: v + 1.0)
    for d in (data[0], data[2], data[0]):
        dev.stage_in_many([_writer(d.get_copy(0))])
        dev._drain_evictions()
    assert kept.coherency == COHERENCY_INVALID
    assert (dev.evicted_bytes, dev.deferred_evictions) == (tile, 1)
    assert data[1].get_copy(0).version == 2
    np.testing.assert_array_equal(data[1].get_copy(0).value, 2.0)
    assert dev.replicas_dropped >= 2 and dev.bytes_in >= 5 * tile


def test_a_copy_made_invalid_leaves_the_lru_as_garbage(four):
    """B holds a replica, A writes the tile: B's replica is garbage.  It
    still weighs on B's budget until the LRU lets it go, and then it goes
    without a copy home and without a count."""
    a, b = four[:2]
    tile = NB * NB * 4
    datum = data_create(np.ones((NB, NB), np.float32), key=("garbage",))
    on_a = _write(a, datum.get_copy(0), lambda v: v + 1.0)
    reader = _writer(on_a)
    b.stage_in_many([reader])
    replica = reader.data[0]
    assert replica.coherency == COHERENCY_SHARED and b.d2d_tiles == 1
    _write(a, on_a, lambda v: v + 1.0)
    assert replica.coherency == COHERENCY_INVALID and a.invalidated_copies == 1
    b._mem_budget = tile
    other = data_create(np.zeros((NB, NB), np.float32), key=("other",))
    b.stage_in_many([_writer(other.get_copy(0))])
    b._drain_evictions()
    assert datum.get_copy(b.device_index) is None
    assert (b.replicas_dropped, b.evicted_bytes, b.writebacks) == (0, 0, 0)
    # read again on B, the tile crosses again, at A's newest version
    again = _writer(on_a)
    b.stage_in_many([again])
    assert again.data[0].version == on_a.version == 3 and b.d2d_tiles == 2
    np.testing.assert_array_equal(np.asarray(again.data[0].value), 3.0)


def test_a_batch_donates_tiles_another_chip_has_just_read(four):
    """A fused call on chip A is donated tiles whose cross-chip copy to B was
    started just before (PJRT orders the donation behind the read: PERF.md,
    PR 40, step 0 (d)): B holds the old version's values, A the new."""
    a, b = four[:2]
    tasks = _tasks("gemm", 4, NB)
    _dispatch(a, "gemm", tasks)
    assert a.donated_results == 4
    first = [np.array(t.data[2].value) for t in tasks]
    # B's tasks read A's C tiles as their A operand
    readers = _tasks("gemm", 4, NB)
    for r, t in zip(readers, tasks):
        r.data[0] = t.data[2]
    b.stage_in_many(readers)
    assert (b.d2d_tiles, b.bytes_d2d) == (4, 4 * NB * NB * 4)
    assert b.bytes_in == 8 * NB * NB * 4
    # A writes the tiles again: the module alone holds them, so it donates
    a._run_vmapped([TPUDeviceTask(None, t, None) for t in tasks])
    assert a.donated_results == 8
    assert b.invalidated_copies == 0 and a.invalidated_copies == 4
    for r, t, old in zip(readers, tasks, first):
        np.testing.assert_array_equal(np.asarray(r.data[0].value), old)
        x, y = (t.data[i].original.get_copy(0).value for i in (0, 1))
        np.testing.assert_allclose(np.asarray(t.data[2].value), old + x @ y,
                                   rtol=1e-4, atol=1e-5)
    for d in (a, b):
        d.sync()
        d.flush_cache()


# the ring's count bound with several accelerators (ISSUE 41)
# --------------------------------------------------------------------------

class _Dispatch(_Result):
    """What a dispatch left in a stand-in ring: ``_Result`` (what the probe
    asks) that also counts who waited for it, reads as run from then on, and
    can fail there, as ``jax.block_until_ready`` meets the array of a program
    that failed on the chip."""

    def __init__(self, ready=False, fails=False):
        super().__init__(ready, [])
        self.waited, self.fails = 0, fails

    def block_until_ready(self):
        self.waited += 1
        if self.fails:
            raise RuntimeError("the program failed on the chip")
        self.ready = True
        return self


def _enqueue(dev, *results, held=0):
    for r in results:
        dev._note_inflight((r,), held)


@pytest.fixture
def entered(monkeypatch):
    """The phase spans the module enters, by name and in order."""
    names = []

    @contextlib.contextmanager
    def phase(name, **args):
        names.append(name)
        yield

    monkeypatch.setattr(spans, "phase", phase)
    return names


@pytest.fixture
def pair(four):
    """Two accelerators of one context, A's count bound cut to 4."""
    a, b = four[:2]
    a._peers, a._max_inflight = [b], 4
    return a, b


def _ring(dev, kinds):
    """R: an entry the chip has run, N: one it has not, X: a body that
    handed back no array, D: one whose array a later call was donated."""
    made = {"R": lambda: (_Dispatch(True),), "N": lambda: (_Dispatch(),),
            "X": lambda: (np.float32(1.0),), "D": lambda: (_Result(None, []),)}
    dev._inflight.extend((made[k](), 0) for k in kinds)


# the peer as the asking chip finds it, and whether that excuses the wait
PEERS = [
    ("ring_empty", lambda b: None, True),
    ("newest_entry_run", lambda b: _ring(b, "RR"), True),
    ("newest_entry_owed", lambda b: _ring(b, "RN"), False),
    ("newest_live_entry_run", lambda b: _ring(b, "RDX"), True),
    ("newest_live_entry_owed", lambda b: _ring(b, "NXD"), False),
    ("no_array_in_the_ring", lambda b: _ring(b, "XX"), True),
    ("being_managed", lambda b: setattr(b, "_managing", True), False),
    ("work_pending", lambda b: b._pending.append(object()), False),
    ("demoted", lambda b: setattr(b, "enabled", False), False)]


@pytest.mark.parametrize("setup,excused", [p[1:] for p in PEERS],
                         ids=[p[0] for p in PEERS])
def test_a_ring_past_its_count_waits_only_where_no_peer_starves(
        pair, entered, setup, excused):
    """Six dispatches the chip has not run on a ring bounded at 4: where the
    other accelerator has run all it was given and nobody feeds it, none is
    waited for and all six stay; else the two oldest are, as with one."""
    a, b = pair
    setup(b)
    owed = [_Dispatch() for _ in range(6)]
    _enqueue(a, *owed)
    if excused:
        assert "devmod.inflight_wait" not in entered
        assert [r.waited for r in owed] == [0] * 6
        assert len(a._inflight) == 6 == a.ring_peak
        assert (a.ring_excused, a.ring_bounded) == (2, 0)
    else:
        assert entered == ["devmod.inflight_wait"] * 2
        assert [r.waited for r in owed] == [1, 1, 0, 0, 0, 0]
        assert len(a._inflight) == 4 == a.ring_peak
        assert (a.ring_excused, a.ring_bounded) == (0, 2)
    state = a.debug_state()
    assert (state["ring_peak"], state["ring_excused"], state["ring_bounded"]) \
        == (a.ring_peak, a.ring_excused, a.ring_bounded)
    # the peer was asked and nothing else: no lock taken, nothing waited for
    assert not b._mutex_lock.locked()
    assert all(not r.waited for out, _ in b._inflight
               for r in out if isinstance(r, _Dispatch))


def test_the_count_holds_again_as_soon_as_the_peer_is_fed(pair, entered):
    """Excused, the ring keeps what the chip owes and lets go of what it has
    run (through ``_confirm``: a failed dispatch among them is raised); once
    the peer has work the next enqueue confirms down to the count."""
    a, b = pair
    _ring(b, "R")
    owed = [_Dispatch() for _ in range(6)]
    _enqueue(a, *owed)
    assert not entered and len(a._inflight) == 6
    owed[0].ready = True                 # the chip ran the oldest meanwhile
    owed.append(_Dispatch())
    _enqueue(a, owed[-1])
    assert entered == ["devmod.inflight_wait"] and owed[0].waited == 1
    assert [out[0] for out, _ in a._inflight] == owed[1:]
    assert (a.ring_excused, a.ring_bounded, a.ring_peak) == (3, 0, 6)
    _ring(b, "N")                        # the peer is given a dispatch
    owed.append(_Dispatch())
    _enqueue(a, owed[-1])
    assert entered == ["devmod.inflight_wait"] * 4
    assert [r.waited for r in owed] == [1, 1, 1, 1, 0, 0, 0, 0]
    assert [out[0] for out, _ in a._inflight] == owed[4:]
    assert (a.ring_excused, a.ring_bounded) == (3, 1)
    b._inflight[-1][0][0].ready = True   # which it runs: excused again
    _enqueue(a, _Dispatch(), _Dispatch())
    assert len(entered) == 4 and len(a._inflight) == 6
    assert (a.ring_excused, a.ring_bounded, a.ring_peak) == (5, 1, 6)
    a.sync()
    assert not a._inflight and len(entered) == 4 + 1 + 6     # devmod.sync


def test_a_peer_taken_up_while_it_is_asked_does_not_starve(pair):
    """The asking thread holds no lock: a manager that takes the peer up
    changes the ring under the walk, or donates the array asked.  Both raise
    there, and the peer is then being fed."""
    a, b = pair

    class Donated(_Dispatch):
        def is_ready(self):
            raise RuntimeError("Array has been deleted.")

    b._inflight.append(((Donated(),), 0))
    assert not b._starving()

    class Grows(_Dispatch):
        def is_deleted(self):
            b._inflight.append(((_Dispatch(),), 0))
            return True

    b._inflight.clear()
    b._inflight.extend([((_Dispatch(True),), 0), ((Grows(),), 0)])
    assert not b._starving()
    b._inflight.clear()
    assert b._starving()


def test_a_solve_over_four_accelerators_counts_every_enqueue_past_the_count(
        four, monkeypatch):
    """The tile QR over four accelerators whose rings are bounded at 2: each
    manager finds the other three of the context, every enqueue that found
    the ring past its count is counted once, as excused or as bounded, the
    rings drain in ``sync``, and the factors are right."""
    past = {d: 0 for d in four}
    note = TPUDevice._note_inflight

    def counting(self, out, held=0):
        before = len(self._inflight)
        note(self, out, held)
        past[self] += out is not None and before + 1 > self._max_inflight

    monkeypatch.setattr(TPUDevice, "_note_inflight", counting)
    for d in four:
        d._max_inflight = 2
    _, gap, ntasks, _, _ = _solve(four, _qr, monkeypatch)
    assert gap < LIMIT["qr"], gap
    assert sum(d.executed_tasks for d in four) == ntasks
    for d in four:
        assert d._peers == [p for p in four if p is not d]
        assert d.ring_excused + d.ring_bounded == past[d] > 0
        assert 2 <= d.ring_peak <= d.xla_calls
        assert not d._inflight and d._held_bytes == 0 and d.enabled


def test_a_context_bound_to_one_of_several_accelerators_keeps_the_count(
        four, param, monkeypatch):
    """A context that may use one accelerator (a rank bound to its chip:
    ``Context(accelerators=...)``) has no peer to ask, however many
    accelerators the process registered and however idle they are."""
    param("device_tpu_allow_cpu", True)      # the mask is made of these
    dev = four[0]
    dev._max_inflight = 2
    pool, ntasks, _, result, gap = _qr()
    ctx = Context(nb_cores=0, accelerators=[dev.jax_device])
    ctx.add_taskpool(pool)
    ctx.wait(timeout=300)
    dev.sync()
    dev.flush_cache()
    ctx.fini()
    assert gap(result()[1]) < LIMIT["qr"]
    assert dev.executed_tasks == ntasks
    assert dev._peers == [] and dev.ring_excused == 0 < dev.ring_bounded
    assert dev.ring_peak == 2


@pytest.mark.parametrize("name,make", [("qr", _qr), ("cholesky", _cholesky)])
@pytest.mark.parametrize("workers,chips", [(2, 2), (4, 4)])
def test_worker_threads_over_several_accelerators_finish_the_solve(
        four, device_registry, name, make, workers, chips):
    """A ``Context`` with worker threads over several accelerators: whoever
    enqueues on a chip nobody manages becomes its manager, so no chip's queue
    waits for a thread that is busy on another chip.  The solve ends inside
    its bound, right, every task run once and on an accelerator."""
    device_registry.devices = [d for d in device_registry.devices
                               if d not in four[chips:]]
    devs = four[:chips]
    pool, ntasks, _, result, gap = make()
    ctx = Context(nb_cores=workers)
    try:
        ctx.add_taskpool(pool)
        ctx.wait(timeout=120)      # raises where the solve does not end
        for d in devs:
            d.sync()
        for d in devs:
            d.flush_cache()
    finally:
        ctx.fini()
    _, factored = result()
    assert gap(factored) < LIMIT[name]
    assert sum(d.executed_tasks for d in devs) == ntasks
    assert not any(d._managing or d._pending for d in devs)
    assert all(d.enabled for d in devs)


@pytest.mark.parametrize("name,make", [("qr", _qr), ("cholesky", _cholesky)])
def test_worker_threads_ask_each_other_s_rings_and_finish_the_solve(
        four, name, make):
    """Four workers over four accelerators whose rings are bounded at 2, the
    interpreter switching threads every 10 us: every manager asks rings that
    another thread is changing (``_starving`` takes no lock).  The solve ends
    inside its bound, right; every enqueue past the count was counted once,
    as excused or bounded, and every dispatch left its ring."""
    for d in four:
        d._max_inflight = 2
    pool, ntasks, _, result, gap = make()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    ctx = Context(nb_cores=4)
    try:
        ctx.add_taskpool(pool)
        ctx.wait(timeout=120)      # raises where the solve does not end
        for d in four:
            d.sync()
        for d in four:
            d.flush_cache()
    finally:
        sys.setswitchinterval(interval)
        ctx.fini()
    assert gap(result()[1]) < LIMIT[name]
    assert sum(d.executed_tasks for d in four) == ntasks
    assert not any(d._managing or d._pending or d._inflight for d in four)
    assert all(d.enabled and d._held_bytes == 0 for d in four)
    assert sum(d.ring_excused + d.ring_bounded for d in four) > 0
    assert all(2 <= d.ring_peak <= d.xla_calls for d in four)


def test_a_program_one_chip_builds_is_compiled_for_its_peers_at_once(
        four, compile_requests):
    """An executable is bound to its chip: four chips that meet the same
    batch would compile it four times, one after the other.  The chip that
    builds a fused program (or meets a per-task body that names its jitted
    function) has its peers compile it beside its own first call, so their
    first calls compile nothing."""
    a, b = four[:2]
    before = compile_requests()
    _dispatch(a, "gemm", _tasks("gemm", 4, NB))
    (key,) = a._vmap_cache
    assert all(d._vmap_cache == {key: a._vmap_cache[key]} for d in four)
    assert compile_requests() - before == 4         # one a chip, at once
    for d in four[1:]:
        _dispatch(d, "gemm", _tasks("gemm", 4, NB))
    assert compile_requests() - before == 4         # and none at their calls
    assert [d.donated_results for d in four] == [4] * 4
    # a per-task body that names its program: the QR's panel kernel
    from parsec_tpu.device.kernels import find_incarnation
    # (tiles of a size no other test's QR has: the body's function is the
    # process's, and so is what it has compiled)
    (task,) = _tasks("qr_tsqrt", 1, 24)            # imports the model
    body = find_incarnation("qr_tsqrt", a)
    a.stage_in_many([task])
    before = compile_requests()
    for t in a._meet_task_program(TPUDeviceTask(None, task, body)):
        t.join()
    assert all(d._task_programs == {body: body.jitted()} for d in four)
    assert compile_requests() - before == 3         # the peers'; a's own call
    body(None, task, a)                             # compiles a's
    assert compile_requests() - before == 4
    (again,) = _tasks("qr_tsqrt", 1, 24)
    b.stage_in_many([again])
    body(None, again, b)
    assert compile_requests() - before == 4
    for d in four:
        d.sync()
