"""Pressure/fault harness for the TPU device module (VERDICT r4 item 7).

The reference exercises its 700-line GPU edge-case surface on real
hardware in CI (``device_gpu.c:845-1528``, ``tests/CMakeLists.txt:70-72``
gating); here the same paths are driven by *injected* faults against a
TPUDevice wrapping the host CPU jax device — the module's logic is
platform-independent XLA, so this coverage is real:

- OOM during stage-in -> LRU eviction + deferred w2r drain, with the
  byte-accounting invariants checked at every drain;
- an XLA dispatch raising MID-RUN (device failure) after earlier batches
  left dirty device tiles -> salvage-writeback + demote + requeue, with
  the salvaged values verified against the partial computation;
- a salvage that cannot write back a newer-than-host tile -> fail-stop
  escalation (wrong answers are worse than stopping);
- the device dying during stage-in (``device_put`` raising) -> the same
  demote protocol from the H2D boundary.
"""

import numpy as np
import pytest

import jax

from parsec_tpu.device import tpu
from parsec_tpu.models.tiled_gemm import tiled_gemm_ptg
from parsec_tpu.runtime import Context
from test_ctx4 import _Dispatch


@pytest.fixture
def dev(accel_device):
    return accel_device    # shared conftest fixture, local name


def _mk_abc(n, mb, seed, k=None):
    """C (n x n) += A (n x k) . B (k x n), k = n unless given."""
    from parsec_tpu.data_dist.matrix import TiledMatrix
    k = n if k is None else k
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((n, k)).astype(np.float32)
    b = rng.standard_normal((k, n)).astype(np.float32)
    c = rng.standard_normal((n, n)).astype(np.float32)
    return (a, b, c, TiledMatrix.from_dense("A", a, mb, mb),
            TiledMatrix.from_dense("B", b, mb, mb),
            TiledMatrix.from_dense("C", c, mb, mb))


def test_eviction_accounting_invariants_hold_at_every_drain(dev):
    """Under a 3-tile budget the w2r queue churns constantly; at every
    drain boundary the byte ledgers must agree with the structures they
    describe (a drift here is silent HBM over/under-subscription)."""
    checks = {"n": 0}
    real_drain = dev._drain_evictions

    def checked_drain():
        real_drain()
        with dev._lru_lock:
            assert dev._mem_bytes == sum(
                getattr(c.value, "nbytes", 0)
                for c in dev._mem_lru.values()), "LRU ledger drift"
            assert dev._evict_bytes == sum(
                getattr(c.value, "nbytes", 0) for c in dev._evict_q), \
                "w2r ledger drift"
            assert dev._mem_bytes >= 0 and dev._evict_bytes >= 0
        checks["n"] += 1

    dev._drain_evictions = checked_drain
    dev._mem_budget = 3 * 16 * 16 * 4
    a, b, c, A, B, C = _mk_abc(64, 16, 21)
    ctx = Context(nb_cores=0)
    ctx.add_taskpool(tiled_gemm_ptg(A, B, C, devices="tpu"))
    ctx.wait(timeout=120)
    dev.sync()
    dev._drain_evictions = real_drain
    dev.flush_cache()
    ctx.fini()
    np.testing.assert_allclose(C.to_dense(), c + a @ b, rtol=1e-3,
                               atol=1e-4)
    assert checks["n"] > 0 and dev.deferred_evictions > 0
    # post-flush: everything accounted down to zero
    assert dev._mem_bytes == 0 and dev._evict_bytes == 0
    assert not dev._mem_lru and not dev._evict_q


def test_mid_run_dispatch_failure_salvages_dirty_tiles_and_requeues(
        dev, param):
    """Batches 1..k succeed and leave dirty C tiles device-resident; then
    the device fails (the vmapped XLA call raises).  The manager must
    salvage the PARTIAL results back to host copies, disable the device,
    and requeue the uncompleted tasks onto the CPU incarnation — final
    numerics prove both the salvage values and the requeue set were
    exact (a dropped dirty tile or a double-run task shows up as a wrong
    product)."""
    a, b, c, A, B, C = _mk_abc(64, 16, 22)
    tp = tiled_gemm_ptg(A, B, C, devices="auto")

    # several small batches so failures land mid-run with dirty residue
    param("device_tpu_batch_max", 8)
    calls = {"n": 0}

    def hook(batch):
        calls["n"] += 1
        if calls["n"] > 2:
            raise ConnectionResetError("device reset mid-batch")

    dev._dispatch_hook = hook
    ctx = Context(nb_cores=0)
    ctx.add_taskpool(tp)
    ctx.wait(timeout=120)
    dev.sync()
    ctx.fini()
    assert calls["n"] > 2, "the failure was never injected"
    assert dev.enabled is False
    assert dev.executed_tasks > 0, "no batch succeeded before the reset"
    np.testing.assert_allclose(C.to_dense(), c + a @ b, rtol=1e-3,
                               atol=1e-4)


def test_unsalvageable_dirty_tile_fails_stop(dev, param):
    """A dirty device tile newer than its host copy that cannot write
    back must STOP the run (recomputing on stale inputs silently
    corrupts results — device_gpu.c's fail-stop discipline)."""
    a, b, c, A, B, C = _mk_abc(32, 16, 23)
    tp = tiled_gemm_ptg(A, B, C, devices="auto")

    param("device_tpu_batch_max", 4)
    calls = {"n": 0}

    def hook(batch):
        calls["n"] += 1
        if calls["n"] > 1:
            raise ConnectionResetError("device reset")

    dev._dispatch_hook = hook

    def broken_writeback(copy):
        raise OSError("D2H path down")

    dev._writeback = broken_writeback
    ctx = Context(nb_cores=0)
    ctx.add_taskpool(tp)
    with pytest.raises(RuntimeError, match="could not be salvaged"):
        ctx.wait(timeout=120)
        dev.sync()
    ctx.fini()


def test_fini_reraises_never_surfaced_background_failure():
    """A worker death recorded while the caller never wait()s must not
    read as clean success: fini() tears down, then re-raises.  A failure
    the caller already saw (raised from wait) is NOT raised twice."""
    import time

    from parsec_tpu import ptg

    def mk_ctx():
        p = ptg.PTGBuilder("boom", N=1)
        t = p.task("T", i=ptg.span(0, 0))
        t.flow("ctl", ptg.CTL)

        def body(es, task, g, l):
            raise ValueError("worker death")
        t.body(body)
        ctx = Context(nb_cores=1)
        ctx.add_taskpool(p.build())
        return ctx

    # never-surfaced: poll without wait(), then fini raises
    ctx = mk_ctx()
    ctx.start()
    deadline = time.monotonic() + 30
    while ctx._worker_error is None and time.monotonic() < deadline:
        time.sleep(0.01)
    assert ctx._worker_error is not None
    with pytest.raises(RuntimeError, match="background thread failed"):
        ctx.fini()

    # surfaced through wait() (as the raw body error on the caller-driven
    # path, or wrapped when a worker recorded it first): fini stays silent
    ctx = mk_ctx()
    with pytest.raises((RuntimeError, ValueError)):
        ctx.wait(timeout=30)
    ctx.fini()


def test_device_failure_during_stage_in_demotes(dev, monkeypatch, param):
    """The H2D boundary dies (device_put raises after N transfers): the
    demote protocol must fire from the stage-in phase too, and the CPU
    incarnations must finish with exact numerics."""
    a, b, c, A, B, C = _mk_abc(64, 16, 24)
    tp = tiled_gemm_ptg(A, B, C, devices="auto")

    param("device_tpu_batch_max", 8)   # several batched transfers
    real_put = jax.device_put
    calls = {"n": 0}

    def flaky_put(x, device=None, **kw):
        calls["n"] += 1
        if calls["n"] > 1:
            raise ConnectionResetError("device reset during H2D")
        return real_put(x, device, **kw)

    monkeypatch.setattr(jax, "device_put", flaky_put)
    ctx = Context(nb_cores=0)
    ctx.add_taskpool(tp)
    ctx.wait(timeout=120)
    dev.sync()
    ctx.fini()
    monkeypatch.undo()
    assert calls["n"] > 1, "the H2D failure was never injected"
    assert dev.enabled is False
    np.testing.assert_allclose(C.to_dense(), c + a @ b, rtol=1e-3,
                               atol=1e-4)


# --------------------------------------------------------------------------
# the write-back in two passes, and pushed-out tiles under eviction and
# salvage (ISSUE 28)
# --------------------------------------------------------------------------

class _FakeValue:
    """A device value that records, in one shared log, when its transfer
    was started and when it was read."""

    nbytes = 16

    def __init__(self, log, tag, start="ok"):
        self.log, self.tag = log, tag
        if start == "ok":
            self.copy_to_host_async = lambda: log.append(("start", tag))
        elif start == "raises":
            self.copy_to_host_async = self._broken
        # start == "absent": no such attribute at all

    def _broken(self):
        self.log.append(("start", self.tag))
        raise RuntimeError("transfer engine down")

    def __array__(self, dtype=None, copy=None):
        self.log.append(("read", self.tag))
        return np.full(4, self.tag, np.float32)


def _dirty_resident(dev, log, tag, start="ok"):
    """One datum whose only current version is a dirty fake value in
    ``dev``'s LRU."""
    from parsec_tpu.data.data import COHERENCY_OWNED, DataCopy, data_create
    datum = data_create(np.zeros(4, np.float32), key=("fake", tag))
    dc = DataCopy(datum, dev.device_index, value=_FakeValue(log, tag, start))
    dc.coherency = COHERENCY_OWNED
    dc.version = 2
    datum.attach_copy(dc)
    datum.owner_device = dev.device_index
    dev._cache_insert(dc, _FakeValue.nbytes)
    return datum


def test_flush_starts_every_transfer_before_the_first_read(dev):
    log = []
    datums = [_dirty_resident(dev, log, tag) for tag in range(1, 6)]
    # one of them was pushed out already: the flush does not start it again
    pushed = datums[2].get_copy(dev.device_index)
    dev.pushout(pushed)
    assert log == [("start", 3)] and dev.pushouts == 1
    dev.flush_cache()
    starts = [i for i, (what, _) in enumerate(log) if what == "start"]
    reads = [i for i, (what, _) in enumerate(log) if what == "read"]
    assert len(starts) == 5 and len(reads) == 5
    assert max(starts) < min(reads), log
    assert (dev.writebacks, dev.writebacks_early) == (5, 1)
    for tag, d in enumerate(datums, 1):
        host = d.get_copy(0)
        assert isinstance(host.value, np.ndarray) and host.version == 2
        np.testing.assert_array_equal(host.value, np.full(4, tag))
        assert d.get_copy(dev.device_index) is None


@pytest.mark.parametrize("start", ["absent", "raises"])
@pytest.mark.parametrize("via", ["flush", "pushout_then_flush", "drain"])
def test_a_value_whose_transfer_cannot_start_still_comes_back(dev, start,
                                                              via):
    log = []
    good = _dirty_resident(dev, log, 1)
    odd = _dirty_resident(dev, log, 2, start)
    if via == "pushout_then_flush":
        dev.pushout(odd.get_copy(dev.device_index))
        assert dev.pushouts == 0
    if via == "drain":
        with dev._lru_lock:
            while dev._mem_lru:
                dev._evict_one_locked()
        dev._drain_evictions()
        assert dev.deferred_evictions == 2
    else:
        dev.flush_cache()
    for tag, d in ((1, good), (2, odd)):
        np.testing.assert_array_equal(d.get_copy(0).value, np.full(4, tag))
    assert (dev.writebacks, dev.writebacks_early) == (2, 0)
    assert not dev._mem_lru and not dev._evict_q and dev._evict_bytes == 0


def test_pushed_out_tiles_survive_eviction_under_pressure(dev):
    """KT = 1: every task's C tile is final, so under a 3-tile budget the
    tiles that reach the w2r queue are pushed-out ones; the drain reads
    them through the transfer the push-out started."""
    a, b, c, A, B, C = _mk_abc(64, 16, 25, k=16)
    dev._mem_budget = 3 * 16 * 16 * 4
    ctx = Context(nb_cores=0)
    ctx.add_taskpool(tiled_gemm_ptg(A, B, C, devices="tpu"))
    ctx.wait(timeout=120)
    assert dev.pushouts == 16
    assert dev.deferred_evictions > 0 and dev.writebacks_early > 0
    dev.sync()
    dev.flush_cache()
    ctx.fini()
    np.testing.assert_allclose(C.to_dense(), c + a @ b, rtol=1e-3, atol=1e-4)
    assert dev.writebacks_early == dev.writebacks == 16
    assert dev._mem_bytes == 0 and dev._evict_bytes == 0


@pytest.mark.parametrize("budget_tiles", [None, 3])
def test_dispatch_failure_salvages_pushed_out_tiles(dev, param, budget_tiles):
    """The device fails with pushed-out dirty tiles in the LRU (and, under
    a tight budget, in ``_evict_q``): the salvage reads them like any other
    dirty tile and the CPU incarnations finish the product."""
    a, b, c, A, B, C = _mk_abc(64, 16, 26, k=16)
    if budget_tiles is not None:
        dev._mem_budget = budget_tiles * 16 * 16 * 4
    param("device_tpu_batch_max", 4)
    calls = {"n": 0}

    def hook(batch):
        calls["n"] += 1
        if calls["n"] > 2:
            raise ConnectionResetError("device reset mid-batch")

    dev._dispatch_hook = hook
    ctx = Context(nb_cores=0)
    ctx.add_taskpool(tiled_gemm_ptg(A, B, C, devices="auto"))
    ctx.wait(timeout=120)
    dev.sync()
    ctx.fini()
    assert calls["n"] > 2 and dev.enabled is False
    assert dev.pushouts == dev.executed_tasks == 8
    assert dev.writebacks_early == 8
    np.testing.assert_allclose(C.to_dense(), c + a @ b, rtol=1e-3, atol=1e-4)


# --------------------------------------------------------------------------
# one byte budget for the LRU and the versions in flight (ISSUE 29)
# --------------------------------------------------------------------------

def _bench(name):
    """A module of ``benchmarks/`` (the plain reference and its seeded data
    import nothing of the program)."""
    import os
    import sys
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    return __import__(name)


def _cholesky_under_budget(dev, n, nb, seed, budget_tiles):
    """One solve of the tiled Cholesky on seeded tiles under a budget of
    ``budget_tiles``; the probe gap against the tile-wise reference, the most
    the module held in LRU + in-flight bytes at any dispatch (in tiles), and
    the result tiles the host holds."""
    from parsec_tpu.data_dist.matrix import SymTwoDimBlockCyclic
    from parsec_tpu.models.cholesky import tiled_cholesky_ptg
    ref, reft, harness = _bench("reference"), _bench("reference_tiled"), \
        _bench("harness")
    tile = nb * nb * 4
    tiles = reft.spd_tiles(seed, n, nb)
    A = SymTwoDimBlockCyclic("A", n, n, nb, nb, dtype=np.float32,
                             init_fn=lambda m, k, shape: tiles[m, k])
    dev._mem_budget = budget_tiles * tile
    peak = [0]
    note = dev._note_inflight

    def noted(out, held=0):
        note(out, held)
        peak[0] = max(peak[0], dev._mem_bytes + dev._held_bytes)

    dev._note_inflight = noted
    ctx = Context(nb_cores=0)
    ctx.add_taskpool(tiled_cholesky_ptg(A, devices="tpu"))
    ctx.wait(timeout=600)
    dev.sync()
    dev.flush_cache()
    ctx.fini()
    got = {k: harness.host_tile(A.data_of(*k)) for k in tiles}
    assert all(v is not None for v in got.values()), "a tile did not come back"
    X = ref.probes(seed, n)
    gap = ref.gap(reft.potrf_got(got, X, nb), reft.sym_apply(tiles, X, nb))
    assert dev._held_bytes == 0 and not dev._inflight
    assert dev._mem_bytes == 0 and dev._evict_bytes == 0
    return gap, peak[0] / tile, len(tiles)


@pytest.mark.parametrize("donating", [True, False],
                         ids=["donating", "every_tile_kept_elsewhere"])
def test_the_64x64_cholesky_dag_stays_inside_a_budget_of_2600_tiles(
        dev, monkeypatch, donating):
    """The DAG of ``potrf-64k`` (64 x 64 tiles, 45,760 tasks) at nb=64: the
    triangle is 2,080 tiles.  Where every fused call is donated its written
    tiles, the results take the buffers of the versions they supersede and
    the ring holds what the per-task bodies superseded alone: 23 tiles at
    most, no dispatch confirmed early.  Where none can be (every tile read
    as kept by someone else: the program that donates nothing), the ring of
    32 dispatches would hold up to 2,000 superseded versions and padding
    lanes beside the triangle; under a budget of 2,600 tiles the module
    confirms its oldest dispatches early and never holds more.  Nothing
    has to be evicted either way."""
    if not donating:
        monkeypatch.setattr(tpu, "_OWN_REFS", -1)
    gap, peak_tiles, triangle = _cholesky_under_budget(dev, 4096, 64, 29, 2600)
    assert dev.executed_tasks == 45760 and triangle == 2080
    # the budget leaves the batches alone: the chip's count, call for call
    assert dev.xla_calls == 1868
    assert gap < 2e-6, gap
    assert peak_tiles <= 2600, peak_tiles
    tile = 64 * 64 * 4
    if donating:
        # every result of the 1,787 fused calls, pad lanes included
        assert dev.donated_results == 47128
        assert dev.inflight_held_bytes_peak == 23 * tile
        assert peak_tiles == 2080 + 23 and dev.pressure_confirms == 0
        # the pad lanes of the widest batch, a tile each, and no more
        assert dev.debug_state()["scratch_tiles"] == 31
        assert dev._scratch_bytes == 31 * tile
    else:
        assert dev.donated_results == 0 and not dev._scratch
        # unbounded, the ring's peak reads 2,000 tiles on this graph
        assert 0 < dev.inflight_held_bytes_peak < 2000 * tile
        assert dev.pressure_confirms >= 1
    assert dev.evicted_bytes == 0 and dev.evict_stuck == 0
    assert dev.bytes_in == 2080 * tile      # nothing staged twice


def test_half_the_triangle_evicts_and_restages_and_loses_nothing(dev):
    """32 x 32 tiles (5,984 tasks) under a budget of half the triangle: the
    LRU evicts through the w2r queue, tiles are staged again, every dirty
    victim reaches the host and the factor is the reference's."""
    tile = 64 * 64 * 4
    gap, peak_tiles, triangle = _cholesky_under_budget(dev, 2048, 64, 31, 264)
    assert dev.executed_tasks == 5984 and triangle == 528
    assert gap < 2e-6, gap
    assert peak_tiles <= 264, peak_tiles
    assert dev.deferred_evictions > 0
    assert dev.evicted_bytes == dev.deferred_evictions * tile
    # what left early came back: staged bytes beyond the triangle
    assert dev.bytes_in > triangle * tile
    # (a clean victim is dropped, not written back, and counted apart)
    assert dev.replicas_dropped > 0
    assert dev.replica_bytes_dropped == dev.replicas_dropped * tile
    assert dev.bytes_in - triangle * tile <= \
        dev.evicted_bytes + dev.replica_bytes_dropped
    assert dev.pressure_confirms >= 1


def test_over_budget_with_nothing_evictable_is_counted(dev):
    """The allocator has to cope, and the module says so."""
    log = []
    _dirty_resident(dev, log, 1)
    dev._mem_budget = 16
    with dev._lru_lock:
        dev._mem_lru[next(iter(dev._mem_lru))].readers = 1     # pinned
    dev._make_room(16)
    assert dev.evict_stuck == 1 and not dev._evict_q
    assert dev.pressure_confirms == 0       # nothing in flight to confirm
    with dev._lru_lock:
        dev._mem_lru[next(iter(dev._mem_lru))].readers = 0
    dev._make_room(16)
    assert dev.evict_stuck == 1 and len(dev._evict_q) == 1
    dev.flush_cache()


def test_the_ring_is_bounded_by_count_when_the_budget_is_far(dev):
    """At the stand-in's 16 GiB the byte bound never trips: a GEMM's ring
    fills to ``device_tpu_max_inflight`` and no dispatch is confirmed for
    pressure."""
    a, b, c, A, B, C = _mk_abc(128, 16, 33)
    ctx = Context(nb_cores=0)
    ctx.add_taskpool(tiled_gemm_ptg(A, B, C, devices="tpu"))
    ctx.wait(timeout=120)
    assert len(dev._inflight) == 8 <= dev._max_inflight
    # every C tile's result took the buffer of the version before it: the
    # ring holds dispatches and no bytes, and all but the newest entries'
    # arrays were consumed by the calls after them
    assert dev.donated_results == dev.executed_tasks == 8 ** 3
    assert dev._held_bytes == dev.inflight_held_bytes_peak == 0
    assert [out.is_deleted() for out, _ in dev._inflight] \
        == [True] * 7 + [False]
    dev.sync()
    dev.flush_cache()
    ctx.fini()
    np.testing.assert_allclose(C.to_dense(), c + a @ b, rtol=1e-3, atol=1e-4)
    assert dev.pressure_confirms == 0 and dev.evict_stuck == 0
    assert dev.evicted_bytes == 0 and dev._held_bytes == 0


# the count bound excused by a starving peer (ISSUE 41): the byte budget and
# the confirmation of every dispatch hold as with one accelerator
# --------------------------------------------------------------------------

@pytest.fixture
def excused(dev, device_registry):
    """``dev`` with its ring bounded at 2 and a peer that starves (nothing
    given, nobody managing): no enqueue past the count waits."""
    peer = device_registry.add(tpu.TPUDevice(jax.devices()[1]))
    dev._peers, dev._max_inflight = [peer], 2
    return dev


def test_a_short_budget_confirms_the_oldest_although_a_peer_starves(excused):
    """Five dispatches that each keep 3 tiles alive under a budget of 10:
    the count (2) is excused, the bytes are not: ``_make_room`` confirms the
    oldest, before the enqueue that would pass the budget, every time."""
    dev, tile = excused, 1 << 10
    dev._mem_budget = 10 * tile
    owed = [_Dispatch() for _ in range(5)]
    for r in owed:
        dev._make_room(3 * tile)         # as ``_run_batch`` asks, before
        dev._note_inflight((r,), 3 * tile)
        assert dev._held_bytes <= dev._mem_budget
    assert [r.waited for r in owed] == [1, 1, 0, 0, 0]
    assert dev.pressure_confirms == 2 and dev.evict_stuck == 0
    assert len(dev._inflight) == 3 == dev.ring_peak
    assert (dev.ring_excused, dev.ring_bounded) == (3, 0)
    assert dev._held_bytes == 9 * tile == dev.inflight_held_bytes_peak
    dev.sync()
    assert dev._held_bytes == 0 and [r.waited for r in owed] == [1] * 5


# where the failed dispatch is met: (the peer starves, what meets it)
MET = [("excused_then_sync", True, "sync"),
       ("excused_then_pressure", True, "pressure"),
       ("excused_then_run_and_dropped", True, "enqueue"),
       ("bounded_at_the_next_enqueue", False, "enqueue")]


@pytest.mark.parametrize("starves,met", [m[1:] for m in MET],
                         ids=[m[0] for m in MET])
def test_a_dispatch_that_failed_is_raised_and_demotes_on_every_path(
        excused, starves, met):
    """Whatever takes an entry out of the ring goes through ``_confirm``:
    a program that failed on the chip is raised there and the device is
    disabled, inside an excused stretch as outside one."""
    dev, tile = excused, 1 << 10
    (peer,) = dev._peers
    failed = _Dispatch(fails=True)
    rest = [_Dispatch() for _ in range(3)]
    if not starves:
        peer._pending.append(object())
        dev._note_inflight((failed,), tile)
        dev._note_inflight((rest[0],), tile)
        with pytest.raises(RuntimeError, match="failed on the chip"):
            dev._note_inflight((rest[1],), tile)
        assert (dev.ring_excused, dev.ring_bounded) == (0, 0)
    else:
        dev._note_inflight((failed,), tile)
        for r in rest:
            dev._note_inflight((r,), tile)    # past the count: not waited
        assert dev.enabled and failed.waited == 0 and dev.ring_excused == 2
        with pytest.raises(RuntimeError, match="failed on the chip"):
            if met == "sync":
                dev.sync()
            elif met == "pressure":
                dev._mem_budget = 4 * tile
                dev._make_room(tile)
            else:       # the chip is past it: the next enqueue drops it
                failed.ready = True
                dev._note_inflight((_Dispatch(),), tile)
    assert failed.waited == 1 and not dev.enabled
    assert all(r.waited == 0 for r in rest)
    peer._pending.clear()
