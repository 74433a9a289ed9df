"""Stage-in's copy (ISSUE 35): a numpy tile goes to the device through the
call ``jax.device_put`` ends in (``device/tpu.py:_host_put``), without
``jax.device_put``'s own Python a tile.  Exact cases on the CPU stand-in, no
clocks: what arrives is what ``jax.device_put`` would have returned, so the
fused programs are the same programs, every tile crosses once, and a solve is
the same solve to the digit with the call and without it.
"""

import numpy as np
import pytest

from parsec_tpu.device import tpu
from parsec_tpu.runtime import Context
from test_ready_queue import _gemm, _potrf      # the cells' graphs, nb = 8

NB = 8
COUNTED = ("bytes_in", "bytes_out", "cache_hits", "cache_misses", "xla_calls",
           "executed_tasks", "evicted_bytes", "pressure_confirms",
           "flood_selected", "flood_putbacks", "pushouts", "writebacks")

_RNG = np.random.default_rng(5)
_HOST_VALUES = {
    "f32-tile": _RNG.standard_normal((NB, NB)).astype(np.float32),
    "bf16-exact-f32": np.float32(_RNG.integers(-8, 8, (4, NB))),
    "int32": np.arange(12, dtype=np.int32).reshape(3, 4),
    "bool": _RNG.integers(0, 2, (5,)).astype(bool),
    "empty": np.zeros((0, 3), np.float32),
    "zero-dim": np.array(2.5, np.float32),
    # what jax.device_put has to see to itself
    "f64-narrowed-by-jax": _RNG.standard_normal((4, 4)),
    "int64-narrowed-by-jax": np.arange(6).reshape(2, 3),
    "transposed-view": _RNG.standard_normal((8, 4)).astype(np.float32).T,
    "strided-view": _RNG.standard_normal((8, 8)).astype(np.float32)[::2],
    "python-float": 3.0,
    "device-array": None,       # made in the test: needs the backend
}
_DIRECT = {"f32-tile", "bf16-exact-f32", "int32", "bool", "empty", "zero-dim"}


@pytest.mark.parametrize("name", _HOST_VALUES)
def test_the_transfer_returns_what_device_put_returns(accel_device, name):
    """Value, dtype, weak type, sharding and committedness: a fused program
    compiled for ``jax.device_put``'s arrays is the program for these."""
    import jax
    jd = accel_device.jax_device
    x = _HOST_VALUES[name]
    if name == "device-array":
        x = jax.device_put(np.ones((2, 2), np.float32), jax.devices()[1])
    direct = tpu._host_put()(x, jd)
    assert (direct is not None) == (name in _DIRECT)
    (got,), want = accel_device._transfer([x]), jax.device_put(x, jd)
    assert type(got) is type(want)
    assert got.aval == want.aval and got.dtype == want.dtype
    assert got.sharding == want.sharding and got.committed and want.committed
    assert got.devices() == {jd}
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_a_list_of_mixed_values_comes_back_in_order(accel_device):
    values = [v for v in _HOST_VALUES.values() if v is not None]
    got = accel_device._transfer(values)
    assert len(got) == len(values)
    for g, x in zip(got, values):
        assert np.array_equal(np.asarray(g), np.asarray(x, dtype=g.dtype))
    assert accel_device._transfer([]) == []


def test_the_abstract_value_and_the_sharding_are_kept_between_tiles(
        accel_device, monkeypatch):
    """What makes the call cheap: one ``ShapedArray`` a (shape, dtype) and one
    sharding a device, however many tiles."""
    import jax
    made = []
    shaped = jax.core.ShapedArray
    monkeypatch.setattr(jax.core, "ShapedArray",
                        lambda *a, **kw: made.append(a) or shaped(*a, **kw))
    monkeypatch.setattr(tpu, "_host_put",
                        lambda put=tpu._host_put.__wrapped__(): put)  # fresh
    tiles = [np.full((NB, NB), i, np.float32) for i in range(40)]
    got = accel_device._transfer(tiles + [np.zeros((3,), np.int32)])
    assert made == [((NB, NB), np.dtype("float32")),
                    ((3,), np.dtype("int32"))]
    assert [float(np.asarray(g)[0, 0]) for g in got[:-1]] == list(range(40))


TILES = {_gemm: lambda p: 3 * p * p, _potrf: lambda p: p * (p + 1) // 2}


def _solve(dev, make, p):
    """One solve on ``dev``: the counters' deltas, how many tiles each
    ``_transfer`` moved, the result."""
    tp, _, result = make(p)
    moved = []
    transfer = dev._transfer
    dev._transfer = lambda values, *far: moved.append(len(values)) or \
        transfer(values, *far)
    before = {k: getattr(dev, k) for k in COUNTED}
    try:
        ctx = Context(nb_cores=0)
        ctx.add_taskpool(tp)
        ctx.wait(timeout=600)
        dev.sync()
        dev.flush_cache()
        ctx.fini()
    finally:
        del dev._transfer
    return ({k: getattr(dev, k) - before[k] for k in COUNTED}, moved,
            result()[0], TILES[make](p))


@pytest.mark.parametrize("make,p,calls", [(_gemm, 16, 64), (_potrf, 16, 72)],
                         ids=["gemm16", "potrf16"])
def test_a_solve_is_the_same_solve_with_the_call_and_without_it(
        accel_device, monkeypatch, make, p, calls):
    """The benchmark cells' graphs (16 x 16 x 16 tiles, 16 panels): every tile
    crosses once, the batches and every count are those of a run that hands
    its tiles to ``jax.device_put``, the result is equal to the digit, and the
    fused programs compiled for the one serve the other."""
    dev = accel_device
    direct = _solve(dev, make, p)
    sizes = {key: fn._cache_size() for key, fn in dev._vmap_cache.items()}
    assert sizes and set(sizes.values()) == {1}
    monkeypatch.setattr(tpu, "_host_put", lambda: None)     # a jaxlib without
    plain = _solve(dev, make, p)
    # no program was compiled again, none was added
    assert {key: fn._cache_size() for key, fn in
            dev._vmap_cache.items()} == sizes
    for n, moved, _, tiles in (direct, plain):
        assert sum(moved) == tiles and n["bytes_in"] == tiles * NB * NB * 4
        assert n["xla_calls"] == calls and n["evicted_bytes"] == 0
    assert direct[0] == plain[0] and direct[1] == plain[1]
    assert np.array_equal(direct[2], plain[2])


def test_prefetch_data_shares_the_transfer(accel_device):
    """The KV tiers' data-grain call moves its datums through the same copy,
    once, and is idempotent."""
    from parsec_tpu.data_dist.matrix import TiledMatrix
    A = TiledMatrix.from_dense(
        "A", _RNG.standard_normal((4 * NB, NB)).astype(np.float32), NB, NB)
    datas = [A.data_of(m, 0) for m in range(4)]
    moved = []
    transfer = accel_device._transfer
    accel_device._transfer = lambda values: moved.append(len(values)) or \
        transfer(values)
    try:
        assert accel_device.prefetch_data(datas) == 4
        assert accel_device.prefetch_data(datas) == 0
    finally:
        del accel_device._transfer
    assert moved == [4] and accel_device.bytes_in == 4 * NB * NB * 4
    for d in datas:
        dev_copy = d.get_copy(accel_device.device_index)
        assert np.array_equal(np.asarray(dev_copy.value), d.get_copy(0).value)
    accel_device.flush_cache()
