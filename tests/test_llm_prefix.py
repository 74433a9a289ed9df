"""ISSUE 11: the automatic prefix cache (radix trie over prompt pages)
and tiered KV paging (device -> host -> peer), end to end against the
dense oracle.  ``docs/LLM.md``, "Prefix cache & KV tiers"."""

import numpy as np
import pytest

import parsec_tpu.llm.batcher as batcher_mod
from parsec_tpu.data.data import DataCopy
from parsec_tpu.data_dist.kv_tiers import KVTierMap, PeerKVStore
from parsec_tpu.data_dist.paged_kv import PagedKVCollection
from parsec_tpu.llm import ToyLM, prefill_chunks, prefill_ptg
from parsec_tpu.llm.prefix_tree import PrefixTree
from parsec_tpu.runtime import Context
from parsec_tpu.serve import RuntimeServer

MODEL = ToyLM()
H, D = MODEL.num_heads, MODEL.head_dim


def _kv(page_size=4, **kw):
    return PagedKVCollection("KV", page_size=page_size, num_heads=H,
                             head_dim=D, **kw)


def _fill_seq(kv, seq, ntokens):
    """Allocate + ledger-advance a sequence as if prefilled (bytes are
    irrelevant to trie bookkeeping tests)."""
    kv.alloc_seq(seq)
    P = kv.page_size
    for _ in range((ntokens + P - 1) // P):
        kv.alloc_page(seq)
    kv.note_appended(seq, ntokens)


# ---------------------------------------------------------------------------
# the radix tree vs a brute-force longest-common-prefix oracle
# ---------------------------------------------------------------------------

def _lcp(a, b):
    n = 0
    for x, y in zip(a, b):
        if x != y:
            break
        n += 1
    return n


def test_trie_insert_match_property_vs_lcp_oracle():
    """Randomized donations + matches: adopt must reuse EXACTLY the
    longest full-page common prefix over every retained run — the
    brute-force oracle scans all retained token runs."""
    rng = np.random.default_rng(7)
    kv = _kv(page_size=4, max_pages=2048)
    tree = PrefixTree(kv, budget_bytes=1 << 30)    # no eviction pressure
    P = kv.page_size
    retained_runs: list[tuple] = []
    seqs = 0
    for step in range(120):
        length = int(rng.integers(1, 30))
        prompt = [int(t) for t in rng.integers(0, 4, size=length)]
        if rng.random() < 0.5:
            seq = f"d{seqs}"
            seqs += 1
            _fill_seq(kv, seq, len(prompt) - 1)
            if tree.donate(seq, prompt) is not None:
                retained_runs.append(tuple(prompt[:((len(prompt) - 1)
                                                    // P) * P]))
            kv.free_seq(seq)
        else:
            cacheable = prompt[:-1]
            want = max((_lcp(cacheable, r) // P for r in retained_runs),
                       default=0)
            child = f"c{seqs}"
            seqs += 1
            got = tree.adopt(child, cacheable)
            assert got == want, (step, got, want, cacheable)
            assert kv.seq_len(child) == got * P
            assert kv.npages(child) == got
            kv.free_seq(child)
    s = tree.stats()
    assert s["entries"] == len(set(retained_runs)) == s["donations"]
    assert s["evictions"] == 0


def test_trie_lru_eviction_recycles_pages_and_keeps_warm_entries():
    """Byte budget: donating past it evicts the LEAST recently used
    entry, its pages recycle (free list), and a matched entry is
    touched — so matching keeps an entry alive through later donations."""
    kv = _kv(page_size=2, max_pages=64)
    tree = PrefixTree(kv, budget_bytes=2 * 2 * kv.page_bytes)  # 2 entries
    runs = {}
    for name, base in (("a", 10), ("b", 20), ("c", 30)):
        prompt = [base, base + 1, base + 2, base + 3, 0]   # 2 full pages
        _fill_seq(kv, name, 4)
        runs[name] = tuple(prompt[:4])
        tree.donate(name, prompt)
        kv.free_seq(name)
        if name == "b":
            # touch "a" so "b" is the cold one when "c" arrives
            assert tree.adopt("toucher", list(runs["a"])) == 2
            kv.free_seq("toucher")
    assert tree.stats()["evictions"] == 1
    live = tree.live_entries()
    kept = {e[0] for e in live.values()}
    assert runs["a"] in kept and runs["c"] in kept
    assert runs["b"] not in kept                     # LRU victim
    assert tree.adopt("miss", list(runs["b"])) == 0  # really gone
    # the victim's pages went back to the free list (nothing leaks)
    assert kv.stats()["free_pages"] >= 2


def test_trie_adopt_pins_entry_against_concurrent_eviction_semantics():
    """An adopted child survives eviction of its donor entry: the CoW
    refcounts — not trie residency — keep the shared pages alive."""
    kv = _kv(page_size=2)
    tree = PrefixTree(kv, budget_bytes=1 << 30)
    _fill_seq(kv, "donor", 4)
    d0 = kv.data_of("donor", 0)
    d0.get_copy(0).value[0, 0, 0, 0] = 7.0
    tree.donate("donor", [1, 2, 3, 4, 9])
    kv.free_seq("donor")
    assert tree.adopt("child", [1, 2, 3, 4]) == 2
    tree.clear()                                   # evict everything
    assert tree.stats()["entries"] == 0
    # the child still reads the donated bytes; pages were never recycled
    assert kv.data_of("child", 0).get_copy(0).value[0, 0, 0, 0] == 7.0
    assert kv.data_of("child", 0) is d0


# ---------------------------------------------------------------------------
# fork-under-eviction: CoW privatize must copy the NEWEST bytes and
# version-jump past every stale copy (the ISSUE-11 regression)
# ---------------------------------------------------------------------------

def test_cow_privatize_copies_newest_device_bytes_not_stale_host():
    """A shared tail page whose device copy runs AHEAD of host (deferred
    write-back, device/tpu.py) is privatized by a fork child: the copy
    must source the device bytes, and the private page's version must
    jump past the shared page's every version."""
    kv = _kv(page_size=4)
    kv.alloc_seq("parent")
    for _ in range(2):
        kv.ensure_tail_slot("parent")
        kv.note_appended("parent")
    d = kv.data_of("parent", 0)
    host = d.get_copy(0)
    stale = np.array(host.value, copy=True)
    fresh = np.array(host.value, copy=True)
    fresh[0, 0, 0, 0] = 99.0
    dev = DataCopy(d, 1, value=fresh)
    dev.version = host.version + 3        # device ran ahead of host
    d.attach_copy(dev)
    kv.fork("parent", "child")
    kv.ensure_tail_slot("child")          # privatizes the shared tail
    c = kv.data_of("child", 0).get_copy(0)
    assert c.value[0, 0, 0, 0] == 99.0, "fork copied stale host bytes"
    assert c.version > dev.version, "no version jump past the device copy"
    assert np.array_equal(np.asarray(host.value), stale)  # parent intact


def test_fork_under_device_eviction_end_to_end_oracle(accel_device,
                                                     param):
    """The regression in anger: a tiny device budget keeps KV pages
    cycling through eviction/write-back while trie-forked streams
    privatize shared tails mid-decode — every stream must still equal
    the dense oracle token for token."""
    param("llm_prefix_cache", True)
    accel_device._mem_budget = 3 * 6144    # ~3 pages of (3,16,4,8)·f32
    with RuntimeServer(nb_cores=2) as server:
        from parsec_tpu.llm import ContinuousBatcher
        b = ContinuousBatcher(server, model=MODEL, devices="tpu")
        prompt = list(range(1, 40))        # 2 full pages + partial @16
        t1 = b.submit_stream(prompt, max_new_tokens=5)
        assert t1.result(timeout=120)["tokens"] == \
            MODEL.reference_generate(prompt, 5)
        # same prompt twice: both adopt the donated prefix
        t2 = b.submit_stream(prompt, max_new_tokens=6)
        t3 = b.submit_stream(prompt, max_new_tokens=4)
        assert t2.result(timeout=120)["tokens"] == \
            MODEL.reference_generate(prompt, 6)
        assert t3.result(timeout=120)["tokens"] == \
            MODEL.reference_generate(prompt, 4)
        s = b.stats()
        assert s["kv"]["prefix_hits"] == 2
        assert s["kv"]["prefix_pages_reused"] == 4
        assert accel_device.deferred_evictions > 0, \
            "budget never forced an eviction — the test lost its point"
        b.stop()


# ---------------------------------------------------------------------------
# trie-forked streams vs the oracle through the full serving stack
# ---------------------------------------------------------------------------

def test_trie_streams_match_oracle_mixed_hit_lengths(param):
    """Shared-system-prompt traffic with NO fork_from wiring: full-hit,
    mid-page hit, and miss streams interleave — token-for-token oracle
    equality plus the prefill-skip ledger."""
    param("llm_prefix_cache", True)
    with RuntimeServer(nb_cores=2) as server:
        sysprompt = list(range(1, 34))     # 33 tokens: 2 full pages @16
        cases = [
            sysprompt,                          # exact repeat (full hit)
            sysprompt + [40, 41, 42],           # extension (full-page hit)
            sysprompt[:20] + [50, 51],          # diverges mid page 2
            [60, 61, 62, 63],                   # miss
        ]
        donor = server.submit_stream(sysprompt, max_new_tokens=3)
        assert donor.result(timeout=120)["tokens"] == \
            MODEL.reference_generate(sysprompt, 3)
        tks = [server.submit_stream(p, max_new_tokens=4) for p in cases]
        for p, tk in zip(cases, tks):
            assert tk.result(timeout=120)["tokens"] == \
                MODEL.reference_generate(p, 4), p
        llm = server.stats()["llm"]
        # full hit (2 pages) + extension (2 pages) + mid-page (1 page:
        # LCP 20 tokens -> 1 full page); the miss and the donor hit nothing
        assert llm["kv"]["prefix_hits"] == 3
        assert llm["kv"]["prefix_pages_reused"] == 5
        assert llm["prefill_tokens_skipped"] == 5 * 16
        assert llm["prefix"]["donations"] >= 1
        # per-tenant SLO counters carry the same wins (PR-10 plane)
        t = server.metrics()["tenants"]["default"]
        assert t["prefix_hits"] == 3 and t["prefix_pages_reused"] == 5


@pytest.mark.parametrize("shared_streams", [5, 0], ids=["0.9", "0.0"])
def test_shared_prefix_traffic_skips_its_prefill(param, shared_streams):
    """The ISSUE-11 prefix-cache gate as counts: of six streams, those
    that share the donor's 16-page system prompt (five: 0.9 of the
    traffic) fork its pages and prefill only their tails, so four fifths
    of the wave's prefill tokens are skipped; with nothing shared there is
    nothing to hit.  A dead trie (no donation, no match, or forks that
    prefill anyway) fails both by name."""
    param("llm_prefix_cache", True)
    shared = [(5 * i + 11) % MODEL.vocab for i in range(16 * 16)]
    prompts = [shared + [(i + j) % MODEL.vocab for j in range(8)]
               if i < shared_streams else
               [(7 * i + 3 * j + 1) % MODEL.vocab
                for j in range(len(shared) + 8)] for i in range(6)]
    with RuntimeServer(nb_cores=2) as server:
        donor = server.submit_stream(shared + [3], max_new_tokens=1)
        donor.result(timeout=300)           # retires: donates the prefix
        llm0 = server.stats()["llm"]
        tks = [server.submit_stream(p, max_new_tokens=2) for p in prompts]
        for p, tk in zip(prompts, tks):
            assert tk.result(timeout=300)["tokens"] == \
                MODEL.reference_generate(p, 2)
        llm1 = server.stats()["llm"]
    total = llm1["prefill_tokens_total"] - llm0["prefill_tokens_total"]
    skipped = llm1["prefill_tokens_skipped"] - llm0["prefill_tokens_skipped"]
    hits = llm1["kv"]["prefix_hits"] - llm0["kv"]["prefix_hits"]
    # a prompt's last token is the first decode step's, not a prefill's
    assert total == 6 * (len(shared) + 8 - 1)
    assert (hits, skipped) == (shared_streams, shared_streams * len(shared))
    assert (skipped / total >= 0.8) == bool(shared_streams)


def test_trie_disabled_by_default_keeps_pr9_behavior():
    """llm_prefix_cache defaults OFF: no trie, no retained pages — the
    PR-6/9 contract (every page recycles at stream retirement) holds."""
    with RuntimeServer(nb_cores=2) as server:
        prompt = list(range(1, 40))
        t1 = server.submit_stream(prompt, max_new_tokens=3)
        t2 = server.submit_stream(prompt, max_new_tokens=3)
        for tk in (t1, t2):
            assert tk.result(timeout=120)["tokens"] == \
                MODEL.reference_generate(prompt, 3)
        llm = server.stats()["llm"]
        assert llm["kv"]["prefix_hits"] == 0
        assert llm["kv"]["physical_pages"] == 0
        assert "prefix" not in llm


def test_trie_and_explicit_fork_from_compose(param):
    """fork_from is now optional but still honored: an explicit fork
    rides the parent's live pages; a trie hit serves everyone else."""
    param("llm_prefix_cache", True)
    with RuntimeServer(nb_cores=2) as server:
        prompt = list(range(1, 41))
        t1 = server.submit_stream(prompt, max_new_tokens=6)
        t2 = server.submit_stream(prompt, max_new_tokens=4, fork_from=t1)
        assert t1.result(timeout=120)["tokens"] == \
            MODEL.reference_generate(prompt, 6)
        assert t2.result(timeout=120)["tokens"] == \
            MODEL.reference_generate(prompt, 4)
        llm = server.stats()["llm"]
        assert llm["forked_streams"] == 1          # the explicit fork
        # after both retire, a third stream hits the donated prefix
        t3 = server.submit_stream(prompt, max_new_tokens=3)
        assert t3.result(timeout=120)["tokens"] == \
            MODEL.reference_generate(prompt, 3)
        assert server.stats()["llm"]["kv"]["prefix_hits"] >= 1


# ---------------------------------------------------------------------------
# tail-only prefill (the PF starts seam)
# ---------------------------------------------------------------------------

def test_prefill_chunks_continue_past_shared_prefix_pages():
    kv = _kv(page_size=4)
    _fill_seq(kv, "donor", 8)
    tree = PrefixTree(kv, budget_bytes=1 << 30)
    tree.donate("donor", [1, 2, 3, 4, 5, 6, 7, 8, 9])
    assert tree.adopt("child", [1, 2, 3, 4, 5, 6, 7, 8, 11, 12]) == 2
    chunks = prefill_chunks(MODEL, kv, "child", [11, 12])
    assert list(chunks) == [("child", 2)]          # chunk index continues
    assert kv.seq_len("child") == 10 and kv.npages("child") == 3


def test_tail_prefill_pool_writes_only_tail_pages_and_graphchecks():
    """prefill_ptg(starts=) must neither redo nor overwrite the shared
    prefix pages — and the pool is graphcheck-clean."""
    kv = _kv(page_size=4)
    _fill_seq(kv, "donor", 8)
    sentinel = kv.data_of("donor", 0).get_copy(0)
    sentinel.value[0, 0, 0, 0] = 123.0
    tree = PrefixTree(kv, budget_bytes=1 << 30)
    tree.donate("donor", [1, 2, 3, 4, 5, 6, 7, 8, 9])
    kv.free_seq("donor")
    tree.adopt("child", [1, 2, 3, 4, 5, 6, 7, 8, 11])
    chunks = prefill_chunks(MODEL, kv, "child", [11])
    from parsec_tpu.data_dist.collection import DictCollection
    T = DictCollection("T", dtt=kv.default_dtt,
                       init_fn=lambda *k: chunks[k], keys=list(chunks))
    tp = prefill_ptg(kv, T, ["child"], starts=[2])
    report = tp.validate()
    assert not report.errors and not report.warnings, report
    with Context(nb_cores=0) as ctx:
        ctx.add_taskpool(tp)
        ctx.wait(timeout=60)
    assert kv.data_of("child", 0).get_copy(0).value[0, 0, 0, 0] == 123.0
    tail = kv.data_of("child", 2).get_copy(0).value
    assert np.allclose(tail[0, 0], MODEL.q3(11)[1])   # the tail landed
    with pytest.raises(ValueError):
        prefill_ptg(kv, T, ["child"], starts=[7])     # out of range


# ---------------------------------------------------------------------------
# KV tiering: spill accounting, prefetch, and the peer hop
# ---------------------------------------------------------------------------

def test_hbm_budget_below_working_set_decodes_oracle_equal(accel_device,
                                                           param):
    """The tier soak: device budget far below the live-KV working set;
    pages spill HBM -> host continuously, the batcher prefetches them
    back one superpool ahead — decode completes oracle-equal and the
    tier ledger shows real traffic."""
    param("llm_prefetch_ahead", True)
    accel_device._mem_budget = 4 * 6144    # ~4 pages; WS is ~4x that
    with RuntimeServer(nb_cores=2) as server:
        from parsec_tpu.llm import ContinuousBatcher
        b = ContinuousBatcher(server, model=MODEL, devices="tpu")
        prompts = [list(range(1, 50)), list(range(2, 51)),
                   [7, 9, 11] * 16]
        # 20 tokens at k=8 = 3 superpool iterations per stream: spills
        # from iteration N are in the host ledger when iteration N+1's
        # prefetch runs (a 1-iteration run would race the deferred
        # write-back drain and measure nothing)
        tks = [b.submit_stream(p, max_new_tokens=20) for p in prompts]
        for p, tk in zip(prompts, tks):
            assert tk.result(timeout=240)["tokens"] == \
                MODEL.reference_generate(p, 20), p
        s = b.stats()
        assert s["tiers"]["spills"] > 0
        assert s["tiers"]["prefetched_pages"] > 0
        assert s["kv"]["host_tier_bytes"] >= 0     # key present + sane
        assert "prefetch_inflight" in s["kv"]
        # the aggregate surfaces in runtime_report()["llm"] (satellite)
        from parsec_tpu.prof import runtime_report
        rep = runtime_report().get("llm", {})
        assert "host_tier_bytes" in rep and "prefetch_inflight" in rep
        assert rep["prefix_hits"] >= 0
        b.stop()


def test_peer_tier_spill_and_prefetch_get_roundtrip(param):
    """Host budget pressure pushes a cold page one hop further over the
    comm engine (AM spill -> registered MemHandle), and prefetch pulls
    it back over the GET path with its bytes and version intact."""
    from parsec_tpu.comm.engine import InprocFabric
    param("kv_host_tier_bytes", 1)         # any spill exceeds the budget
    fab = InprocFabric(2)
    e0, e1 = fab.attach(0), fab.attach(1)
    store = PeerKVStore(e1)
    kv = _kv()
    tiers = KVTierMap(kv)
    tiers.attach_peer(e0, 1)
    kv.alloc_seq("a")
    kv.alloc_page("a")
    kv.note_appended("a", 4)
    d = kv.data_of("a", 0)
    host = d.get_copy(0)
    host.value[:] = np.arange(host.value.size,
                              dtype=np.float32).reshape(host.value.shape)
    host.version = 5
    orig = np.array(host.value)
    tiers.note_spill(d, host.value.nbytes)     # as the device hook would
    for _ in range(20):
        e0.progress()
        e1.progress()
    assert d.get_copy(0).value is None          # host bytes released
    assert store.stats()["pages_held"] == 1
    assert tiers.stats()["peer_tier_pages"] == 1
    tiers.prefetch_seqs(["a"])                  # issues the prefetch GET
    for _ in range(20):
        e0.progress()
        e1.progress()
    back = d.get_copy(0)
    assert back.value is not None and np.array_equal(back.value, orig)
    assert back.version == 5
    assert tiers.stats()["peer_fetches"] == 1
    assert store.stats()["pages_held"] == 0     # handle drained
    assert getattr(e0, "prefetch_gets", 0) == 1


def test_peer_spill_keeps_local_bytes_until_ack(param):
    """Until the peer acknowledges custody, the local host copy is the
    page's ONLY copy: a lost spill AM must degrade to 'page stayed
    local', never to 'page gone'."""
    from parsec_tpu.comm.engine import InprocFabric
    param("kv_host_tier_bytes", 1)
    fab = InprocFabric(2)
    e0 = fab.attach(0)
    fab.attach(1)                      # peer rank exists, NO store: the
    kv = _kv()                         # spill AM is never consumed
    tiers = KVTierMap(kv)
    tiers.attach_peer(e0, 1)
    kv.alloc_seq("a")
    kv.alloc_page("a")
    d = kv.data_of("a", 0)
    tiers.note_spill(d, d.get_copy(0).value.nbytes)
    e0.progress()                      # no ACK will ever arrive
    assert d.get_copy(0).value is not None     # bytes stayed reachable
    assert tiers.stats()["peer_tier_pages"] == 1   # address pending


def test_runtime_report_llm_block_survives_batcher_retirement(param):
    """The cumulative-since-process-start contract: a drained server's
    batcher folds its counters into the aggregate, so a bench stage's
    post-run report still shows the cache effectiveness."""
    import parsec_tpu.llm.batcher as bmod
    param("llm_prefix_cache", True)
    before = bmod.aggregate_report()
    with RuntimeServer(nb_cores=2) as server:
        prompt = list(range(1, 41))
        for _ in range(2):
            server.submit_stream(prompt, max_new_tokens=2) \
                .result(timeout=120)
    after = bmod.aggregate_report()
    assert after.get("prefix_hits", 0) - before.get("prefix_hits", 0) == 1
    assert after.get("tokens_generated", 0) \
        - before.get("tokens_generated", 0) == 4


def test_kv_stats_carries_the_issue11_keys_without_tiers():
    kv = _kv()
    s = kv.stats()
    for key in ("prefix_hits", "prefix_pages_reused", "host_tier_bytes",
                "prefetch_inflight"):
        assert key in s and s[key] == 0


def test_fork_prefix_validates_bounds_and_page_alignment():
    kv = _kv(page_size=4)
    _fill_seq(kv, "p", 6)                  # 2 pages, ledger 6
    with pytest.raises(ValueError):
        kv.fork_prefix("p", "c", 3)        # past the table
    with pytest.raises(ValueError):
        kv.fork_prefix("p", "c", 2)        # page 2 only 2 tokens full
    kv.fork_prefix("p", "c", 1)
    assert kv.seq_len("c") == 4 and kv.npages("c") == 1
    with pytest.raises(KeyError):
        kv.fork_prefix("p", "c", 1)        # child exists
