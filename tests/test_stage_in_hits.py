"""Stage-in's cache hits (``device/tpu.py:stage_in_many``): a hit is a read
of the datum's copy on the device and a recency touch, made once a distinct
copy a batch (``_touch``).  What the LRU holds and charges, what is staged
and what is evicted are what a move to the end per reference gave, on whole
solves and under a budget of half the working set; a copy the LRU does not
hold under its datum takes the charging insert; a written result of another
size is charged its own bytes.  CPU stand-in; counts only."""

import hashlib
import types

import numpy as np
import pytest

import jax.numpy as jnp

from parsec_tpu import ptg
from parsec_tpu.data.data import COHERENCY_SHARED, DataCopy, data_create
from parsec_tpu.data_dist.matrix import TiledMatrix
from parsec_tpu.device import tpu
from parsec_tpu.device.kernels import register_kernel, traceable_body
from parsec_tpu.prof import pins
from parsec_tpu.prof.pins import PinsEvent
from parsec_tpu.ptg.lowering import register_traceable
from parsec_tpu.runtime import Context
from test_ready_queue import _gemm, _potrf

TILE = 8 * 8 * 4


def _ledger_holds(dev) -> None:
    with dev._lru_lock:
        assert dev._mem_bytes == sum(tpu._copy_nbytes(c)
                                     for c in dev._mem_lru.values())


# (graph, budget in tiles or None) -> what the walk with a move to the end
# per reference left: hits, misses, bytes in, the LRU's bytes, tiles and key
# order (a digest of the datums' keys, oldest first), bytes evicted, times
# the budget found nothing evictable, and the distinct hit copies the batches
# touched
SOLVES = {
    ("gemm16", None): (8197, 4091, 196608, 196608, 768, "5f4d1e10173c9440",
                       0, 0, 4976),
    ("potrf16", None): (2040, 136, 34816, 34816, 136, "b698a936999d5c62",
                        0, 0, 1294),
    ("gemm16", 384): (7859, 4429, 207872, 98304, 384, "a37d8b56d77ea175",
                      1280, 0, 4932),
    ("potrf16", 68): (1247, 929, 193024, 9472, 37, "7bd83ef572c9369c",
                      124928, 3, 676)}


def _solve(dev, graph: str, budget: int | None, check=None):
    """One solve of ``graph`` (16 x 16 tiles of 8 x 8) on ``dev``; the LRU
    as the solve left it, before the flush."""
    make = {"gemm16": _gemm, "potrf16": _potrf}[graph]
    if budget is not None:
        dev._mem_budget = budget * TILE
    if check is not None:
        stage_in, note = dev.stage_in_many, dev._note_inflight
        dev.stage_in_many = lambda tasks: (stage_in(tasks), check())
        dev._note_inflight = lambda out, held=0: (note(out, held), check())
    tp, ntasks, result = make(16)
    ctx = Context(nb_cores=0)
    ctx.add_taskpool(tp)
    ctx.wait(timeout=300)
    dev.sync()
    assert dev.executed_tasks == ntasks
    _ledger_holds(dev)
    keys = repr([d.key for d in dev._mem_lru]).encode()
    left = (dev._mem_bytes, len(dev._mem_lru),
            hashlib.sha256(keys).hexdigest()[:16])
    dev.flush_cache()
    ctx.fini()
    got, expect = result()
    np.testing.assert_allclose(got, expect, rtol=1e-3, atol=1e-4)
    return left


@pytest.mark.parametrize("graph,budget", list(SOLVES),
                         ids=[f"{g}_{b or 'no'}_budget" for g, b in SOLVES])
def test_a_solve_stages_and_charges_what_a_touch_per_reference_did(
        accel_device, graph, budget):
    dev = accel_device
    hits, misses, staged, held, tiles, order, evicted, stuck, touched = \
        SOLVES[graph, budget]
    assert _solve(dev, graph, budget) == (held, tiles, order)
    assert (dev.cache_hits, dev.cache_misses, dev.bytes_in) == \
        (hits, misses, staged)
    assert (dev.evicted_bytes, dev.evict_stuck) == (evicted, stuck)
    assert (dev.lru_touches, dev.lru_recharged) == (touched, 0)
    state = dev.debug_state()
    assert (state["lru_touches"], state["lru_recharged"]) == (touched, 0)


_FLOWS = (types.SimpleNamespace(flow_index=0, is_ctl=False),)


def _task(copy):
    """A task of one data flow that references ``copy``: all the walk
    reads of a task."""
    return types.SimpleNamespace(
        task_class=types.SimpleNamespace(flows=_FLOWS), data=[copy])


def _host_tile(key, value=1.0):
    return data_create(np.full((8, 8), value, np.float32), key=key)


def test_a_tile_many_tasks_reference_touches_the_lru_once(accel_device):
    dev = accel_device
    shared, other = _host_tile("shared"), _host_tile("other")
    dev.stage_in_many([_task(shared.get_copy(0)), _task(other.get_copy(0))])
    assert [d.key for d in dev._mem_lru] == ["shared", "other"]
    assert (dev.cache_misses, dev.cache_hits, dev.lru_touches) == (2, 0, 0)
    tasks = [_task(shared.get_copy(0)) for _ in range(12)]
    dev.stage_in_many(tasks)
    here = shared.get_copy(dev.device_index)
    assert all(t.data[0] is here for t in tasks)
    assert (dev.cache_hits, dev.lru_touches, dev.lru_recharged) == (12, 1, 0)
    # the touch moved it to the recent end, and charged nothing more
    assert [d.key for d in dev._mem_lru] == ["other", "shared"]
    assert dev._mem_bytes == 2 * TILE and dev.bytes_in == 2 * TILE
    # the order of a batch's hits is that of their last references
    dev.stage_in_many([_task(other.get_copy(0)), _task(shared.get_copy(0)),
                       _task(other.get_copy(0))])
    assert [d.key for d in dev._mem_lru] == ["shared", "other"]
    assert (dev.cache_hits, dev.lru_touches) == (15, 3)
    dev.flush_cache()


@pytest.mark.parametrize("how", ["evicted", "another_copy"])
def test_a_hit_the_lru_does_not_hold_is_charged_again(accel_device, how):
    dev = accel_device
    d = _host_tile("tile")
    dev.stage_in_many([_task(d.get_copy(0))])
    here = d.get_copy(dev.device_index)
    if how == "evicted":
        # out of the LRU, its write-back still queued: the copy is the
        # device's until the drain
        with dev._lru_lock:
            assert dev._evict_one_locked()
        assert dev._mem_bytes == 0 and dev._evict_bytes == TILE
    else:
        stray = DataCopy(d, dev.device_index,
                         value=np.zeros((4, 8), np.float32))
        dev._cache_insert(stray, stray.value.nbytes)
        assert dev._mem_lru[d] is stray and dev._mem_bytes == TILE // 2
    dev.stage_in_many([_task(d.get_copy(0)), _task(d.get_copy(0))])
    assert (dev.cache_hits, dev.lru_touches, dev.lru_recharged) == (2, 1, 1)
    assert dev._mem_lru[d] is here and dev._mem_bytes == TILE
    # a victim back in the LRU is skipped by the drain, and stays
    dev._drain_evictions()
    assert dev._evict_bytes == 0 and dev.deferred_evictions == 0
    assert d.get_copy(dev.device_index) is here
    _ledger_holds(dev)
    dev.flush_cache()
    assert dev._mem_bytes == 0


def _widen(x):
    """A kernel whose result is twice the rows of its input."""
    return jnp.concatenate([x, x + 1.0])


register_traceable("lru_test_widen", _widen)
register_kernel("lru_test_widen", "tpu", traceable_body(_widen))


def _widening_pool(rows: int):
    """``rows`` independent tasks, each widening its own tile of X once."""
    X = TiledMatrix.from_dense(
        "X", np.arange(rows * 64, dtype=np.float32).reshape(rows * 8, 8), 8, 8)
    p = ptg.PTGBuilder("widen", X=X, MT=rows)
    t = p.task("WIDEN", m=ptg.span(0, lambda g, l: g.MT - 1))
    f = t.flow("V", ptg.RW)
    f.input(data=("X", lambda g, l: (l.m, 0)))
    f.output(data=("X", lambda g, l: (l.m, 0)))
    t.body(device="tpu", dyld="lru_test_widen")
    return p.build(), X


@pytest.mark.parametrize("rows,fused", [(1, False), (4, True)],
                         ids=["submitted_alone", "fused_batch"])
def test_a_result_of_another_size_is_charged_its_own_bytes(
        accel_device, rows, fused):
    dev = accel_device
    tp, X = _widening_pool(rows)
    ctx = Context(nb_cores=0)
    ctx.add_taskpool(tp)
    ctx.wait(timeout=120)
    dev.sync()
    assert dev.executed_tasks == rows
    assert dev.batched_dispatches == int(fused)
    for m in range(rows):
        value = X.data_of(m, 0).get_copy(dev.device_index).value
        assert value.shape == (16, 8)
        np.testing.assert_array_equal(np.asarray(value)[8:],
                                      np.asarray(value)[:8] + 1.0)
    # charged the results' bytes, not the tiles' they replaced
    assert dev._mem_bytes == rows * 2 * TILE
    _ledger_holds(dev)
    dev.flush_cache()
    ctx.fini()
    assert dev._mem_bytes == 0


def test_two_versions_in_one_batch_are_reported_and_the_newest_staged(
        accel_device):
    dev = accel_device
    d = _host_tile("versions")
    old = d.get_copy(0)
    new = DataCopy(d, 0, value=np.full((8, 8), 2.0, np.float32))
    new.version, new.coherency = old.version + 1, COHERENCY_SHARED
    seen = []

    def mixed(es, payload):
        seen.append(payload)

    pins.register(PinsEvent.DEVICE_STAGE_MIXED_VERSIONS, mixed)
    try:
        tasks = [_task(old), _task(new), _task(old)]
        dev.stage_in_many(tasks)
    finally:
        pins.unregister(PinsEvent.DEVICE_STAGE_MIXED_VERSIONS, mixed)
    assert seen == [("versions", new.version, old.version)] * 2
    here = d.get_copy(dev.device_index)
    assert here.version == new.version and all(t.data[0] is here
                                               for t in tasks)
    np.testing.assert_array_equal(np.asarray(here.value), new.value)
    assert (dev.cache_misses, dev.cache_hits, dev.lru_touches) == (3, 0, 0)
    assert dev.bytes_in == TILE == dev._mem_bytes
    dev.flush_cache()


@pytest.mark.parametrize("graph,budget", [("gemm16", 384), ("potrf16", 68)])
def test_under_half_the_working_set_the_lru_stays_inside_the_budget(
        accel_device, graph, budget):
    """At every stage-in's return and every dispatch's enqueue the LRU's
    ledger holds and its bytes are inside the budget; the answer is right
    (``_solve`` compares it)."""
    dev = accel_device
    checks = []

    def check():
        _ledger_holds(dev)
        assert dev._mem_bytes <= dev._mem_budget
        checks.append(dev._mem_bytes)

    _solve(dev, graph, budget, check)
    assert len(checks) > 2 * dev.xla_calls - 1
    # pressed to within a few tiles of the edge (the scratch pool and the
    # ring hold the rest)
    assert dev._mem_budget - 8 * TILE < max(checks)
    assert dev.deferred_evictions > 0
