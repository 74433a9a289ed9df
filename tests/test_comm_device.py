"""Device-backed transport tests: the task runtime moving device-resident
tiles across the 8-device virtual mesh.

The analog of the reference's distributed tier run over a *real* transport
(SURVEY §4; ``parsec_mpi_funnelled.c``): the same PTG protocol tests as
``test_comm_multirank.py`` but with rank *i* pinned to JAX device *i*,
``mem_register`` pinning payloads device-resident and GET moving them
device-to-device (``parsec_comm_engine.h:176-199`` vtable contract).
"""

import jax
import numpy as np
import pytest

from parsec_tpu import ptg
from parsec_tpu.comm import run_multirank
from parsec_tpu.comm.device_fabric import (DeviceCommEngine, DeviceFabric,
                                           is_device_array)
from parsec_tpu.core.params import params
from parsec_tpu.data_dist.matrix import TwoDimBlockCyclic, VectorTwoDimCyclic


# ---------------------------------------------------------------------------
# engine-level unit tests (the dtd_test_ce.c analog)
# ---------------------------------------------------------------------------

def test_mem_register_pins_to_rank_device():
    fab = DeviceFabric(2)
    e0, e1 = fab.attach(0), fab.attach(1)
    h = e0.mem_register(np.arange(8, dtype=np.float32))
    assert is_device_array(h.value)
    assert h.value.device == fab.devices[0]

    landed = []
    e1.get(h.wire(), landed.append)
    e0.progress()   # serve the GET request
    e1.progress()   # land the reply
    assert len(landed) == 1
    assert is_device_array(landed[0])
    assert landed[0].device == fab.devices[1]   # D2D: consumer-side residency
    np.testing.assert_array_equal(np.asarray(landed[0]),
                                  np.arange(8, dtype=np.float32))
    assert e1.bytes_got == 32


def test_device_array_registration_aliases():
    """Immutable device arrays register without a snapshot copy."""
    fab = DeviceFabric(1)
    e0 = fab.attach(0)
    buf = jax.device_put(np.ones(4, np.float32), fab.devices[0])
    h = e0.mem_register(buf)
    assert h.value is buf   # aliased, not copied: jax arrays are immutable


def test_host_array_registration_copies_at_boundary():
    """Mutable host arrays snapshot inside mem_register (owned=False)."""
    fab = DeviceFabric(1)
    e0 = fab.attach(0)
    buf = np.ones(4, np.float32)
    h = e0.mem_register(buf)
    buf[:] = 99.0
    np.testing.assert_array_equal(np.asarray(h.value), np.ones(4))


# ---------------------------------------------------------------------------
# the protocol tests over the device transport
# ---------------------------------------------------------------------------

def _chain_tp(V, nt: int):
    p = ptg.PTGBuilder("chain", V=V, NT=nt)
    t = p.task("T", i=ptg.span(0, lambda g, l: g.NT - 1))
    t.affinity("V", lambda g, l: (l.i,))
    f = t.flow("A", ptg.RW)
    f.input(data=("V", lambda g, l: (0,)), guard=lambda g, l: l.i == 0)
    f.input(pred=("T", "A", lambda g, l: {"i": l.i - 1}),
            guard=lambda g, l: l.i > 0)
    f.output(succ=("T", "A", lambda g, l: {"i": l.i + 1}),
             guard=lambda g, l: l.i < g.NT - 1)
    f.output(data=("V", lambda g, l: (0,)),
             guard=lambda g, l: l.i == g.NT - 1)

    def body(es, task, g, l):
        # functional update: arriving tiles may be immutable device arrays
        c = task.flow_data("A")
        c.value = np.asarray(c.value) + 1.0

    t.body(body)
    return p.build()


def _chain_body(ctx, rank, nranks):
    nt = 7
    V = VectorTwoDimCyclic("V", lm=nt * 4, mb=4, P=nranks, myrank=rank,
                           init_fn=lambda m, size: np.zeros(size))
    tp = _chain_tp(V, nt)
    ctx.add_taskpool(tp)
    ctx.wait(timeout=60)
    ctx.comm_barrier()
    if rank == 0:
        return np.asarray(V.data_of(0).newest_copy().value).copy()
    return None


@pytest.mark.parametrize("nranks", [2, 4, 8])
def test_chain_across_devices(nranks):
    """Ex03 shape on the device transport: the tile hops device-to-device
    through every rank and writes back to rank 0's home."""
    res = run_multirank(nranks, _chain_body, transport="device")
    np.testing.assert_allclose(res[0], np.full(4, 7.0))


def _gemm_body(ctx, rank, nranks):
    n, nb = 64, 16
    rng = np.random.RandomState(7)
    a = rng.randn(n, n).astype(np.float32)
    b = rng.randn(n, n).astype(np.float32)
    P = 2 if nranks % 2 == 0 else 1
    Q = nranks // P
    from parsec_tpu.models.tiled_gemm import tiled_gemm_ptg
    A = TwoDimBlockCyclic.from_dense("A", a, nb, nb, P=P, Q=Q, myrank=rank)
    B = TwoDimBlockCyclic.from_dense("B", b, nb, nb, P=P, Q=Q, myrank=rank)
    C = TwoDimBlockCyclic("C", n, n, nb, nb, P=P, Q=Q, myrank=rank)
    tp = tiled_gemm_ptg(A, B, C, devices="cpu")
    ctx.add_taskpool(tp)
    ctx.wait(timeout=120)
    ctx.comm_barrier()
    return C.to_dense()   # local tiles only; assembled by the caller


@pytest.mark.parametrize("nranks", [2, 4])
def test_block_cyclic_gemm_on_device_transport(nranks):
    """Distributed GEMM through the task runtime on the device transport;
    every rank's local tiles must match the dense product — and must match
    the single-rank run (the dryrun_multichip contract).  Every rank holds
    the whole of ``a`` and ``b``, so no operand tile crosses ranks here."""
    res = run_multirank(nranks, _gemm_body, transport="device", timeout=180)
    n = 64
    rng = np.random.RandomState(7)
    a = rng.randn(n, n).astype(np.float32)
    b = rng.randn(n, n).astype(np.float32)
    expect = a.astype(np.float32) @ b
    single = run_multirank(1, _gemm_body)[0]
    np.testing.assert_allclose(single, expect, rtol=1e-4)
    # assemble: rank r contributed the tiles it owns; non-owned are zero
    got = np.zeros_like(expect)
    for r in res:
        got += r
    # each tile owned exactly once across ranks
    np.testing.assert_allclose(got, expect, rtol=1e-4)


def test_rendezvous_get_stays_on_device():
    """A payload above the short limit must ride the registered-memory GET
    path and land as a device array on the consumer."""
    old = params.get("comm_short_limit")
    params.set("comm_short_limit", 8)
    seen = []

    def body(ctx, rank, nranks):
        res = _chain_body(ctx, rank, nranks)
        seen.append(ctx.comm_engine.ce.bytes_got)
        return res

    try:
        res = run_multirank(2, body, transport="device")
    finally:
        params.set("comm_short_limit", old)
    np.testing.assert_allclose(res[0], np.full(4, 7.0))
    assert any(b > 0 for b in seen), "no D2D GET traffic recorded"
