"""Collective-tree taskpools (ISSUE 14): staged broadcast + combining
reduction over the PR-4 wire protocol.

Three tiers: static (graphcheck-clean at every kind x size), inproc
multirank execution against numpy oracles, and the 8-process acceptance
run — a 4 MiB broadcast that must land byte-identical on every rank with
root egress bounded by the root's tree-children count (ceil(log2 8) = 3
payload transfers for binomial), measured off the socket fabric's
per-peer traffic ledger."""

import hashlib

import numpy as np
import pytest

from parsec_tpu.analysis import check_ptg
from parsec_tpu.comm import run_multirank, run_multiproc
from parsec_tpu.comm.collectives import (MP_COLLECTIVE_BYTES,
                                         bcast_taskpool, reduce_op,
                                         reduce_taskpool,
                                         register_reduce_op)
from parsec_tpu.core.params import params
from parsec_tpu.data_dist.matrix import VectorTwoDimCyclic

KINDS = ["binomial", "chain", "star"]


def _vec(name, nt, nranks=1, rank=0, init=None):
    return VectorTwoDimCyclic(
        name, lm=nt * 4, mb=4, P=nranks, myrank=rank,
        init_fn=init or (lambda m, s: np.zeros(s, np.float32)))


# ---------------------------------------------------------------------------
# static: every shape is graphcheck-clean
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("n", [1, 2, 3, 5, 8])
def test_collective_pools_graphcheck_clean(kind, n):
    r = check_ptg(bcast_taskpool(_vec("V", n), n=n, kind=kind))
    assert not r.errors, (kind, n, r.errors)
    r = check_ptg(reduce_taskpool(_vec("R", n), _vec("O", 1),
                                  n=n, kind=kind))
    assert not r.errors, (kind, n, r.errors)


def test_reduce_op_registry():
    assert reduce_op("sum") is np.add
    with pytest.raises(KeyError, match="register_reduce_op"):
        reduce_op("xor")
    register_reduce_op("absmax", lambda a, b: np.maximum(np.abs(a),
                                                         np.abs(b)))
    assert reduce_op("absmax") is not None


def test_bad_root_rejected():
    with pytest.raises(ValueError, match="root"):
        bcast_taskpool(_vec("V", 4), n=4, root=4)


# ---------------------------------------------------------------------------
# single-rank execution (tree staging degenerates to local copies)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", KINDS)
def test_bcast_single_rank(kind):
    from parsec_tpu.runtime.context import Context
    n = 5
    V = _vec("V", n, init=lambda m, s:
             np.arange(s, dtype=np.float32) + 9.0 if m == 0
             else np.zeros(s, np.float32))
    with Context(nb_cores=0) as ctx:
        ctx.add_taskpool(bcast_taskpool(V, n=n, kind=kind))
        ctx.wait(timeout=30)
    want = np.arange(4, dtype=np.float32) + 9.0
    for m in range(n):
        got = np.asarray(V.data_of(m).newest_copy().value)
        np.testing.assert_array_equal(got, want, err_msg=f"tile {m}")


@pytest.mark.parametrize("op,oracle", [
    ("sum", lambda cols: np.sum(cols, axis=0)),
    ("max", lambda cols: np.max(cols, axis=0)),
    ("prod", lambda cols: np.prod(cols, axis=0)),
])
def test_reduce_single_rank_matches_numpy(op, oracle):
    from parsec_tpu.runtime.context import Context
    n = 6
    rng = np.random.RandomState(14)
    cols = rng.uniform(0.5, 1.5, size=(n, 4)).astype(np.float32)
    R = _vec("R", n, init=lambda m, s: cols[m].copy())
    O = _vec("O", 1)
    with Context(nb_cores=0) as ctx:
        ctx.add_taskpool(reduce_taskpool(R, O, op=op, n=n))
        ctx.wait(timeout=30)
    got = np.asarray(O.data_of(0).newest_copy().value)
    np.testing.assert_allclose(got, oracle(cols), rtol=1e-6)


# ---------------------------------------------------------------------------
# inproc multirank: the staged tree across rank boundaries
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("nranks", [2, 4])
def test_bcast_multirank_byte_identical(kind, nranks):
    want = np.arange(4, dtype=np.float32) * 2.0 + 3.0

    def body(ctx, rank, nranks):
        V = _vec("V", nranks, nranks=nranks, rank=rank,
                 init=lambda m, s: (
                     np.arange(s, dtype=np.float32) * 2.0 + 3.0
                     if m == 0 else np.zeros(s, np.float32)))
        ctx.add_taskpool(bcast_taskpool(V, n=nranks, kind=kind))
        ctx.wait(timeout=60)
        ctx.comm_barrier()
        return np.asarray(V.data_of(rank).newest_copy().value).copy()

    res = run_multirank(nranks, body, nb_cores=1, timeout=120)
    for rank, got in enumerate(res):
        np.testing.assert_array_equal(got, want, err_msg=f"rank {rank}")


@pytest.mark.parametrize("nranks", [3, 4])
def test_reduce_multirank_matches_numpy(nranks):
    def body(ctx, rank, nranks):
        R = _vec("R", nranks, nranks=nranks, rank=rank,
                 init=lambda m, s: np.full(s, float(m + 1), np.float32))
        O = _vec("O", 1, nranks=nranks, rank=rank)
        ctx.add_taskpool(reduce_taskpool(R, O, op="sum", n=nranks))
        ctx.wait(timeout=60)
        ctx.comm_barrier()
        if rank == 0:
            return np.asarray(O.data_of(0).newest_copy().value).copy()
        return None

    res = run_multirank(nranks, body, nb_cores=1, timeout=120)
    want = np.full(4, sum(range(1, nranks + 1)), np.float32)
    np.testing.assert_allclose(res[0], want)


# ---------------------------------------------------------------------------
# the 8-process acceptance run: byte-identical + O(log n) root egress
# ---------------------------------------------------------------------------

def test_bcast_8rank_multiproc_root_egress_logn():
    nranks = 8
    payload = MP_COLLECTIVE_BYTES                          # 4 MiB
    res = run_multiproc(
        nranks, "parsec_tpu.comm.collectives:_mp_collective_body",
        timeout=300, nb_cores=1)
    mb = max(payload // 4, 1)
    want = np.arange(mb, dtype=np.float32) * 0.5 + 7.0
    want_digest = hashlib.sha256(want.tobytes()).hexdigest()
    for r in res:
        assert r["digest"] == want_digest, \
            f"rank {r['rank']} broadcast not byte-identical"
    assert res[0]["reduce0"] == pytest.approx(sum(range(1, nranks + 1)))

    # root egress: rank 0 serves at most its tree children — for the
    # binomial default that is ceil(log2(8)) = 3 payload transfers (the
    # activation layer's own staged re-serve may hand some of them to
    # interior ranks, so strictly FEWER is legal too).  Everything else
    # on the ledger (activations, GET control, the small reduction
    # tiles) is noise far under one payload.
    assert res[0]["tree"] == "binomial"
    tx = res[0]["peer_stats"]["tx"]
    egress = sum(d["bytes"] for d in tx.values())
    assert egress <= 3 * payload + (1 << 20), \
        f"root egress {egress} exceeds 3 payloads (+1 MiB slack)"
    heavy = [dst for dst, d in tx.items() if d["bytes"] >= payload]
    assert 1 <= len(heavy) <= 3, \
        (heavy, {k: v["bytes"] for k, v in tx.items()})
    # every non-root rank landed the payload exactly once (one heavy
    # inbound peer): the staged tree never double-delivers
    for r in res[1:]:
        rx = r["peer_stats"]["rx"]
        srcs = [s for s, d in rx.items() if d["bytes"] >= payload]
        assert len(srcs) == 1, (r["rank"], srcs)

    # static-vs-dynamic agreement (ISSUE 20): commcheck's executed-nothing
    # byte prediction for this exact workload must agree with the wire
    # ledger within 15% rel — framing, activations, and the reduction
    # partials are the only slack on top of (n-1) payload transfers
    from parsec_tpu.analysis.commcheck import (agreement_rel_err,
                                               predict_collective_traffic)
    pred = predict_collective_traffic(nranks)
    observed = sum(d["bytes"] for r in res
                   for d in r["peer_stats"]["tx"].values())
    err = agreement_rel_err(pred["total_bytes"], observed)
    assert err <= 0.15, (pred["total_bytes"], observed, err)
    # the root-egress prediction is an UPPER bound on the root's own
    # ledger: the staged re-serve can only shed root load onto interior
    # ranks (see the egress comment above), never add to it
    assert egress <= pred["root_egress_bytes"] + (1 << 20), \
        (pred["root_egress_bytes"], egress)


def test_bcast_4rank_auto_tree_root_egress_bounded():
    """``comm_bcast_tree=auto`` (ISSUE 20): the resolved shape's measured
    root egress must be <= the WORST hand-picked shape on the same
    workload.  The 4 MiB payload is far past comm_short_limit, so auto
    resolves to binomial — root serves children(0, 4) = {1, 2}: 2
    payloads, vs star's worst-case 3; the wire must never carry the
    literal "auto" (every rank's resolved tree is concrete)."""
    nranks = 4
    payload = MP_COLLECTIVE_BYTES                          # 4 MiB
    saved = params.get("comm_bcast_tree")
    params.set("comm_bcast_tree", "auto")
    try:
        res = run_multiproc(
            nranks, "parsec_tpu.comm.collectives:_mp_collective_body",
            timeout=300, nb_cores=1)
    finally:
        params.set("comm_bcast_tree", saved)
    digests = {r["digest"] for r in res}
    assert len(digests) == 1, "auto-tree broadcast not byte-identical"
    assert res[0]["tree"] == "auto"         # the param rode the env
    egress = sum(d["bytes"]
                 for d in res[0]["peer_stats"]["tx"].values())
    # worst hand-picked shape is star: root serves n-1 = 3 payloads
    assert egress <= (nranks - 1) * payload + (1 << 20), \
        f"auto root egress {egress} exceeds the star worst case"
    # and the binomial resolution beats it: 2 children + slack
    assert egress <= 2 * payload + (1 << 20), \
        f"auto did not resolve to the egress-bounding shape: {egress}"
