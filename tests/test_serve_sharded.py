"""Sharded RuntimeServer (ISSUE 14): one logical serving plane across
ranks — KV-residency placement, cross-rank exactly-merged SLO metrics,
tree-broadcast config, and dead-rank stream requeue.

Inproc multirank (threads) so the test can reach into every rank's
server object: the frontend is rank 0, workers run ``serve_forever``
until the frontend's SHUTDOWN."""

import threading

import numpy as np  # noqa: F401  (kept: parity with the serve test tier)
import pytest

from parsec_tpu.comm.multirank import run_multirank
from parsec_tpu.llm import ToyLM
from parsec_tpu.serve.sharded import ShardedRuntimeServer, merge_planes

MODEL = ToyLM()


def _run_plane(nranks, frontend_fn, timeout=180):
    """Every rank builds a ShardedRuntimeServer; rank 0 runs
    ``frontend_fn(srv, peers)`` (peers: every rank's server, so tests can
    inject faults / read worker state), workers serve until SHUTDOWN."""
    bar = threading.Barrier(nranks)
    peers: dict[int, ShardedRuntimeServer] = {}

    def body(ctx, rank, nranks):
        srv = ShardedRuntimeServer(ctx)
        peers[rank] = srv
        bar.wait()
        if rank == 0:
            try:
                return frontend_fn(srv, peers)
            finally:
                srv.shutdown()
                bar.wait()
        try:
            srv.serve_forever(idle_timeout=timeout)
        finally:
            srv.close()
            bar.wait()
        return None

    return run_multirank(nranks, body, nb_cores=1, timeout=timeout)[0]


def test_two_rank_oracle_equal_and_metrics_merge_exactly():
    prompts = [[3, 7, 11, 5], [1, 40], [8, 30, 22], [9, 2, 4, 6]]

    def frontend(srv, peers):
        hs = [srv.submit_stream(p, max_new_tokens=10,
                                tenant=f"t{i % 2}")
              for i, p in enumerate(prompts)]
        srv.wait(hs, timeout=120)
        for p, h in zip(prompts, hs):
            assert h.result(timeout=1)["tokens"] == \
                MODEL.reference_generate(p, 10), p
        m = srv.metrics(timeout=30)
        # both ranks decoded (least-loaded fallback spreads the burst)
        assert {h.rank for h in hs} == {0, 1}
        # the merged summary IS merge_planes over the per-rank planes:
        # bucket-exact, not an average of per-rank summaries
        raw = [peers[r]._plane_dict() for r in sorted(peers)]
        assert m["tenants"] == merge_planes(raw)
        assert m["ranks"] == 2
        # per-tenant sample counts survived the merge: the merged count
        # is the SUM of the per-rank histogram counts, never a mean
        for t in ("t0", "t1"):
            want = sum(h["count"] for plane in raw
                       for h in [plane.get(t, {}).get("latency_ms")]
                       if h is not None)
            assert want > 0
            assert m["tenants"][t]["latency_ms_count"] == want
        return True

    assert _run_plane(2, frontend) is True


def test_placement_prefers_prefix_residency_then_least_loaded():
    a, b = [3, 7, 11, 5], [21, 22, 23, 24, 25]

    def frontend(srv, peers):
        ha = srv.submit_stream(a, max_new_tokens=6)
        hb = srv.submit_stream(b, max_new_tokens=6)
        # burst placement: tie on residency -> least loaded spreads
        assert ha.rank == 0 and hb.rank == 1, (ha.rank, hb.rank)
        srv.wait([ha, hb], timeout=120)
        # a repeat of b's prompt routes to b's rank: the router history
        # scores its full-prefix match above rank 0's empty residency
        hc = srv.submit_stream(b, max_new_tokens=6)
        assert hc.rank == 1, hc.rank
        srv.wait([hc], timeout=120)
        assert hc.result(timeout=1)["tokens"] == \
            MODEL.reference_generate(b, 6)
        return True

    assert _run_plane(2, frontend) is True


def test_config_broadcast_rides_the_tree():
    """WFQ weights + admission budgets broadcast along the collective
    tree: with 4 ranks (binomial) the frontend serves ranks 1 and 2 only
    and rank 1 re-forwards to rank 3 — every rank still applies it."""
    import time

    def frontend(srv, peers):
        srv.broadcast_config(weights={"pro": 4.0}, max_inflight=32,
                             max_tenant_inflight=8)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            srv.step()
            if all(peers[r]._local._adm.max_inflight == 32
                   for r in peers):
                break
            time.sleep(0.005)
        for r, p in sorted(peers.items()):
            assert p._local._adm.max_inflight == 32, r
            assert p._local._adm.max_tenant_inflight == 8, r
            assert p._local._fair._weights.get("pro") == 4.0, r
        # the tree: rank 0 forwarded twice (children 1, 2), rank 1 once
        # (child 3), leaves not at all
        assert peers[0].config_forwards == 2
        assert peers[1].config_forwards == 1
        assert peers[2].config_forwards == 0
        assert peers[3].config_forwards == 0
        return True

    assert _run_plane(4, frontend) is True


def test_dead_rank_streams_requeue_oracle_exact():
    """Kill the rank mid-generation: its streams resume on a survivor
    from the last shipped token (prompt + prefix re-dispatch), stay
    token-for-token oracle-equal, and the zombie's late duplicate deltas
    are dropped by the handle's index dedup."""
    import time

    prompt, nmax = [5, 9, 13, 2], 12
    oracle = MODEL.reference_generate(prompt, nmax)

    def frontend(srv, peers):
        # the precondition, stated and not raced for: rank 1 holds the
        # stream, has shipped three of its twelve tokens and is dark.  Rank
        # 1 ships nothing of its own from the start (a stream of twelve
        # tokens can finish between two steps of a loaded frontend, and a
        # finished stream is not requeued); the three tokens arrive through
        # the real handler, as a delta from rank 1 would.
        peers[1].zombie = True
        filler = srv.submit_stream([2, 4], max_new_tokens=nmax)  # rank 0
        # nothing steps between the two submits: rank 0 carries the filler,
        # so the least-loaded rule gives h to rank 1
        h = srv.submit_stream(prompt, max_new_tokens=nmax)       # rank 1
        assert h.rank == 1
        srv._handle(1, {"op": "TOKENS", "sid": h.sid, "base": 0,
                        "toks": list(oracle[:3])})
        assert h.tokens == oracle[:3] and not h.done()
        k = len(h.tokens)
        srv.fail_rank(1)
        assert h.rank == 0 and h.requeues == 1 and h.ranks == [1, 0]
        srv.wait([h, filler], timeout=120)
        assert h.result(timeout=1)["tokens"] == oracle, \
            (h.tokens, oracle, k)
        # resurrect the zombie: everything it still ships replays
        # below the ledger's high-water mark and is dropped
        peers[1].zombie = False
        deadline = time.monotonic() + 30
        while peers[1]._live and time.monotonic() < deadline:
            srv.step()
            time.sleep(0.005)
        srv.step()
        assert h.tokens == oracle            # dedup: nothing re-landed
        # and a replayed delta through the REAL handler (the zombie may
        # or may not have had unshipped tokens left — this one always
        # replays) is dropped AND counted
        srv._handle(1, {"op": "TOKENS", "sid": h.sid, "base": 0,
                        "toks": list(oracle[:2])})
        assert h.tokens == oracle
        assert h.dup_tokens >= 2
        return True

    assert _run_plane(2, frontend) is True
