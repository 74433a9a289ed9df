"""The LLM inference subsystem: paged KV cache, ragged attention
kernels, prefill/decode task pools, k-step decode superpools with
in-graph sampling, continuous batching (ISSUES 6 + 9;
``docs/LLM.md``)."""

import numpy as np
import pytest

from parsec_tpu.data.datatype import TileType
from parsec_tpu.data_dist.collection import DictCollection
from parsec_tpu.data_dist.paged_kv import PagedKVCollection
from parsec_tpu.llm import (ContinuousBatcher, ToyLM, decode_step_ptg,
                            decode_superpool_ptg, prefill_chunks,
                            prefill_ptg, read_token_chain,
                            seed_decode_superpool)
from parsec_tpu.ops import ragged_attention as ra
from parsec_tpu.runtime import Context
from parsec_tpu.serve import RuntimeServer

MODEL = ToyLM()
H, D = MODEL.num_heads, MODEL.head_dim


def _kv(page_size=4, **kw):
    return PagedKVCollection("KV", page_size=page_size, num_heads=H,
                             head_dim=D, **kw)


def _paged(tokens, page_size=4):
    """Pack a token history into page tiles + a flat k/v oracle view."""
    ks = np.array([MODEL.q3(t)[1] for t in tokens])
    vs = np.array([MODEL.q3(t)[2] for t in tokens])
    pages = []
    for p in range((len(tokens) + page_size - 1) // page_size):
        tile = np.zeros((3, page_size, H, D), np.float32)
        fill = min(page_size, len(tokens) - p * page_size)
        tile[0, :fill] = ks[p * page_size:p * page_size + fill]
        tile[1, :fill] = vs[p * page_size:p * page_size + fill]
        tile[2, 0, 0, 0] = fill
        pages.append(tile)
    return pages, ks, vs


# ---------------------------------------------------------------------------
# PagedKVCollection
# ---------------------------------------------------------------------------

def test_kv_block_table_alloc_and_bounds_oracle():
    kv = _kv()
    kv.alloc_seq("a")
    assert kv.npages("a") == 0 and kv.seq_len("a") == 0
    for _ in range(9):                       # 9 tokens over 4-slot pages
        kv.ensure_tail_slot("a")
        kv.note_appended("a")
    assert kv.npages("a") == 3
    assert kv.page_fill("a", 0) == 4 and kv.page_fill("a", 2) == 1
    # the has_key bounds oracle is CLOSED: live pages only
    assert kv.has_key("a", 0) and kv.has_key("a", 2)
    assert not kv.has_key("a", 3)            # beyond the table
    assert not kv.has_key("b", 0)            # unknown sequence
    assert not kv.has_key("a", -1) and not kv.has_key("a")
    # data_of resolves through the block table to stable physical pages
    d0 = kv.data_of("a", 0)
    assert d0.key == (kv.name, kv.block_table("a")[0])
    assert kv.rank_of("a", 0) == 0


def test_kv_fork_shares_pages_copy_on_write_and_free_recycles():
    kv = _kv()
    kv.alloc_seq("parent")
    for _ in range(6):                       # 1.5 pages
        kv.ensure_tail_slot("parent")
        kv.note_appended("parent")
    kv.data_of("parent", 1).get_copy(0).value[0, 0, 0, 0] = 42.0
    kv.fork("parent", "child")
    assert kv.block_table("child") == kv.block_table("parent")
    assert kv.stats()["shared_pages"] == 2
    # child's tail write privatizes ONLY the partial tail page (CoW)
    kv.ensure_tail_slot("child")
    pt, ct = kv.block_table("parent"), kv.block_table("child")
    assert pt[0] == ct[0] and pt[1] != ct[1]
    assert kv.cow_copies == 1
    # the copy carried the shared contents
    assert kv.data_of("child", 1).get_copy(0).value[0, 0, 0, 0] == 42.0
    # parent's tail stays writable without a copy (it is private again)
    kv.ensure_tail_slot("parent")
    assert kv.cow_copies == 1
    # free both: every physical page returns to the free list
    kv.free_seq("child")
    kv.free_seq("parent")
    s = kv.stats()
    assert s["seqs"] == 0 and s["physical_pages"] == 0
    assert s["free_pages"] == 3
    # recycled pages come back ZEROED with a bumped version
    kv.alloc_seq("next")
    kv.alloc_page("next")
    c = kv.data_of("next", 0).get_copy(0)
    assert float(np.abs(c.value).max()) == 0.0 and c.version >= 2
    assert kv.pages_recycled == 1


def test_recycled_page_invalidates_stale_device_copies():
    """A dirty device copy running AHEAD of host (deferred writeback,
    device/tpu.py) must never satisfy a stage-in version check after its
    page is recycled to a new sequence."""
    from parsec_tpu.data.data import DataCopy
    kv = _kv()
    kv.alloc_seq("a")
    kv.alloc_page("a")
    d = kv.data_of("a", 0)
    dev = DataCopy(d, 1, value=np.ones(kv.default_dtt.shape, np.float32))
    dev.version = d.get_copy(0).version + 1      # ahead of host
    d.attach_copy(dev)
    kv.free_seq("a")
    kv.alloc_seq("b")
    kv.alloc_page("b")
    d2 = kv.data_of("b", 0)
    assert d2 is d                               # the page recycled
    assert d2.get_copy(1) is None                # device copy detached
    host = d2.get_copy(0)
    assert host.version > dev.version            # stale can never win
    assert float(np.abs(host.value).max()) == 0.0


def test_kv_page_budget_and_double_alloc():
    kv = _kv(max_pages=2)
    kv.alloc_seq("a")
    kv.alloc_page("a")
    kv.alloc_page("a")
    with pytest.raises(MemoryError):
        kv.alloc_page("a")
    with pytest.raises(KeyError):
        kv.alloc_seq("a")


# ---------------------------------------------------------------------------
# ragged attention kernels: every incarnation against the dense oracle
# ---------------------------------------------------------------------------

def test_page_chain_matches_dense_reference_all_incarnations():
    tokens = [3, 7, 11, 5, 9, 2, 40, 22, 8]   # 9 tokens: ragged 3rd page
    pages, ks, vs = _paged(tokens)
    q3 = MODEL.q3(13)
    want = ra.ragged_attention_reference(q3[0], ks, vs)
    for name, step in [
            ("numpy", ra.attn_page_update_np),
            ("jnp", lambda q, p, a: np.asarray(ra._page_update_jnp(q, p, a)))]:
        acc = np.zeros((H, D + 2), np.float32)
        for page in pages:
            acc = np.asarray(step(q3, page, acc))
        got = ra.finalize_acc_np(acc)
        assert np.abs(got - want).max() < 1e-5, name


def test_empty_cache_yields_zero_output_not_nan():
    q3 = MODEL.q3(1)
    acc = ra.attn_page_update_np(q3, np.zeros((3, 4, H, D), np.float32),
                                 np.zeros((H, D + 2), np.float32))
    o = ra.finalize_acc_np(acc)
    assert np.all(np.isfinite(o)) and np.abs(o).max() == 0.0


def test_out_update_appends_kv_at_fill_slot():
    pages, _, _ = _paged([3, 7, 11, 5, 9])    # tail fill = 1
    acc = np.zeros((H, D + 2), np.float32)
    acc[:, D + 1] = 1.0
    q3 = MODEL.q3(13)
    new_page, o = ra.attn_out_np(acc, q3, pages[-1])
    assert np.allclose(new_page[0, 1], q3[1])
    assert np.allclose(new_page[1, 1], q3[2])
    assert new_page[2, 0, 0, 0] == 2
    pj, oj = ra._out_update_jnp(acc, q3, pages[-1],
                                np.zeros((H, D), np.float32))
    assert np.abs(np.asarray(pj) - new_page).max() == 0.0
    assert np.abs(np.asarray(oj) - o).max() == 0.0


# ---------------------------------------------------------------------------
# the PTG pools: graphcheck + execution against the oracle
# ---------------------------------------------------------------------------

def _prefilled(kv, seqs_prompts):
    """Prefill every (seq, prompt[:-1]) through the PF pool on a bare
    context; returns the chunk map used."""
    chunks = {}
    for seq, prompt in seqs_prompts:
        kv.alloc_seq(seq)
        chunks.update(prefill_chunks(MODEL, kv, seq, prompt[:-1]))
    T = DictCollection("T", dtt=kv.default_dtt,
                       init_fn=lambda *k: chunks[k], keys=list(chunks))
    ctx = Context(nb_cores=0)
    tp = prefill_ptg(kv, T, [s for s, _ in seqs_prompts])
    tp.validate()                 # graphcheck: zero errors pre-enqueue
    ctx.add_taskpool(tp)
    ctx.wait(timeout=60)
    ctx.fini()
    return chunks


def test_prefill_and_decode_pools_match_reference_multi_seq():
    kv = _kv()
    prompts = {"a": [3, 7, 11, 5, 9, 2], "b": [1, 40]}
    _prefilled(kv, list(prompts.items()))
    Q = DictCollection("Q", dtt=TileType((3, H, D), np.float32))
    O = DictCollection("O", dtt=TileType((H, D), np.float32))
    for seq, prompt in prompts.items():
        assert kv.seq_len(seq) == len(prompt) - 1
        kv.ensure_tail_slot(seq)
        qc = Q.data_of(seq).get_copy(0)
        qc.value = MODEL.q3(prompt[-1])
        qc.version += 1
    tp = decode_step_ptg(kv, Q, O, list(prompts))
    report = tp.validate()
    assert not report.errors and not report.warnings, report
    ctx = Context(nb_cores=0)
    ctx.add_taskpool(tp)
    ctx.wait(timeout=60)
    ctx.fini()
    for seq, prompt in prompts.items():
        _, ks, vs = _paged(prompt[:-1])
        want = ra.ragged_attention_reference(MODEL.q3(prompt[-1])[0],
                                             ks, vs)
        got = np.asarray(O.data_of(seq).newest_copy().value)
        assert np.abs(got - want).max() < 1e-5, seq
        # the OUT task appended the query token's k/v into the tail page
        tail = np.asarray(
            kv.data_of(seq, kv.npages(seq) - 1).newest_copy().value)
        slot = (len(prompt) - 1) % kv.page_size
        assert np.allclose(tail[0, slot], MODEL.q3(prompt[-1])[1])
        assert tail[2, 0, 0, 0] == slot + 1


def test_graphcheck_rejects_out_of_table_page_reference():
    """The has_key bounds oracle in anger: a decode-shaped pool reading
    one page PAST a sequence's block table must draw a bounds error."""
    from parsec_tpu import ptg
    from parsec_tpu.analysis import check_ptg
    kv = _kv()
    kv.alloc_seq("a")
    kv.alloc_page("a")
    p = ptg.PTGBuilder("bad_decode", KV=kv, NP=1)
    t = p.task("R", i=ptg.span(0, lambda g, l: g.NP - 1))
    f = t.flow("KV", ptg.READ)
    f.input(data=("KV", lambda g, l: ("a", l.i + 1)))   # off the table
    t.body(lambda es, task, g, l: None)
    report = check_ptg(p.build())
    assert report.errors, report
    assert any("KV" in str(e) for e in report.errors), report


def test_decode_through_tpu_device_tier_with_lru_residency(accel_device):
    """The device incarnation: ATTN/OUT dispatch through the TPU device
    module — KV pages and flow tiles ride the HBM LRU, and same-class
    decode tasks coalesce into vmapped batched dispatch."""
    kv = _kv()
    prompts = {"a": [3, 7, 11, 5, 9, 2], "b": [1, 40, 8]}
    for seq, prompt in prompts.items():
        kv.alloc_seq(seq)
        chunks = prefill_chunks(MODEL, kv, seq, prompt[:-1])
        for (s, c), tile in chunks.items():      # host-side prefill
            pg = kv.data_of(s, c).get_copy(0)
            pg.value = tile
            pg.version += 1
    Q = DictCollection("Q", dtt=TileType((3, H, D), np.float32))
    O = DictCollection("O", dtt=TileType((H, D), np.float32))
    for seq, prompt in prompts.items():
        kv.ensure_tail_slot(seq)
        qc = Q.data_of(seq).get_copy(0)
        qc.value = MODEL.q3(prompt[-1])
        qc.version += 1
    tp = decode_step_ptg(kv, Q, O, list(prompts), devices="tpu")
    ctx = Context(nb_cores=0)
    ctx.add_taskpool(tp)
    ctx.wait(timeout=120)
    accel_device.sync()
    ctx.fini()
    for seq, prompt in prompts.items():
        _, ks, vs = _paged(prompt[:-1])
        want = ra.ragged_attention_reference(MODEL.q3(prompt[-1])[0],
                                             ks, vs)
        got = np.asarray(O.data_of(seq).newest_copy().value)
        assert np.abs(got - want).max() < 1e-4, seq
    assert accel_device.executed_tasks == 5      # 3 + 2 ATTN/OUT chains
    # paged-KV residency: the pages went through the device LRU
    assert accel_device.cache_misses > 0
    assert len(accel_device._mem_lru) > 0


# ---------------------------------------------------------------------------
# k-step decode superpools: in-graph SAMPLE chains (ISSUE 9)
# ---------------------------------------------------------------------------

def _superpool_setup(prompts, steps, devices="cpu", eos=None):
    """Build the side collections and run the library's own
    per-iteration prep (``seed_decode_superpool`` — the batcher's
    seeding contract, stated once) for pool-level tests."""
    kv = _kv()
    Q = DictCollection("Q", dtt=TileType((3, H, D), np.float32))
    O = DictCollection("O", dtt=TileType((H, D), np.float32))
    TOK = DictCollection("TOK", dtt=TileType((3,), np.float32))
    EMB = DictCollection("EMB", dtt=TileType(MODEL.q3_table().shape,
                                             np.float32))
    seed_decode_superpool(MODEL, kv, Q, TOK, EMB, prompts, steps, eos=eos)
    tp = decode_superpool_ptg(kv, Q, O, TOK, EMB, list(prompts),
                              [steps[s] for s in prompts],
                              devices=devices)
    return kv, TOK, tp


def _tokens_of(TOK, seq, k):
    return read_token_chain(TOK, seq, k)[0]


def test_superpool_matches_reference_mixed_steps_and_page_boundaries():
    """One pool spanning k autoregressive steps per sequence — DIFFERENT
    k per sequence, with the token positions crossing page boundaries
    mid-pool (page_size 4), must equal the dense oracle token for
    token.  This is the ISSUE-9 tentpole contract: SAMPLE threads token
    -> next query in-graph, OUT threads the tail page across steps."""
    prompts = {"a": [3, 7, 11, 5, 9, 2], "b": [1, 40], "c": [8, 8, 2, 6]}
    steps = {"a": 7, "b": 5, "c": 1}
    kv, TOK, tp = _superpool_setup(prompts, steps)
    report = tp.validate()
    assert not report.errors and not report.warnings, report
    with Context(nb_cores=0) as ctx:
        ctx.add_taskpool(tp)
        ctx.wait(timeout=120)
    for seq, prompt in prompts.items():
        want = MODEL.reference_generate(prompt, steps[seq])
        assert _tokens_of(TOK, seq, steps[seq]) == want, seq


def test_superpool_eos_mid_pool_predicated_tail_is_discarded():
    """A sequence sampling EOS at an interior step finishes THERE: the
    surfaced tokens equal the EOS-truncated oracle, and the predicated
    tail tasks ran without corrupting the other sequence's chain."""
    ref = MODEL.reference_generate([3, 7, 11, 5], 8)
    eos = ref[1]                       # fires mid-pool
    want = MODEL.reference_generate([3, 7, 11, 5], 8, eos=eos)
    assert 1 <= len(want) < 8
    prompts = {"a": [3, 7, 11, 5], "b": [1, 40]}
    steps = {"a": 8, "b": 8}
    kv, TOK, tp = _superpool_setup(prompts, steps, eos=eos)
    with Context(nb_cores=0) as ctx:
        ctx.add_taskpool(tp)
        ctx.wait(timeout=120)
    assert _tokens_of(TOK, "a", 8) == want
    # the un-finished stream is untouched by a's early exit (b never
    # samples eos in 8 steps of this prompt — checked via the oracle)
    want_b = MODEL.reference_generate([1, 40], 8, eos=eos)
    assert _tokens_of(TOK, "b", 8) == want_b


def test_superpool_through_device_tier(accel_device):
    """The full k-step pools-vs-oracle token-equality test with every
    class dispatched through the device module (jnp bodies)."""
    prompts = {"a": [3, 7, 11, 5, 9, 2], "b": [1, 40, 8]}
    steps = {"a": 5, "b": 5}
    kv, TOK, tp = _superpool_setup(prompts, steps, devices="tpu")
    with Context(nb_cores=0) as ctx:
        ctx.add_taskpool(tp)
        ctx.wait(timeout=240)
        accel_device.sync()
    for seq, prompt in prompts.items():
        want = MODEL.reference_generate(prompt, steps[seq])
        assert _tokens_of(TOK, seq, steps[seq]) == want, seq
    assert accel_device.executed_tasks > 0


# ---------------------------------------------------------------------------
# continuous batching on the RuntimeServer
# ---------------------------------------------------------------------------

def test_stream_generation_matches_reference_token_for_token():
    with RuntimeServer(nb_cores=2) as server:
        prompts = [[3, 7, 11, 5], [1], [40, 2, 9, 9, 9, 30, 22, 8]]
        tks = [server.submit_stream(p, max_new_tokens=10,
                                    tenant=f"t{i % 2}")
               for i, p in enumerate(prompts)]
        for p, tk in zip(prompts, tks):
            r = tk.result(timeout=120)
            assert r["tokens"] == MODEL.reference_generate(p, 10)
            assert len(r["per_token_s"]) == 10
        stats = server.stats()["llm"]
        assert stats["streams_completed"] == 3
        assert stats["tokens_generated"] == 30
        # every retired stream's pages returned to the free list
        assert stats["kv"]["physical_pages"] == 0


@pytest.mark.parametrize("streams,k,submits", [(1, 8, 1), (4, 8, 4),
                                               (4, 1, 32)])
def test_a_superpool_pays_one_submit_for_k_tokens(param, streams, k,
                                                  submits):
    """The ISSUE-9 amortization as a count: under one tenant a stream (the
    shape where no cross-stream batching hides a submit) 8 tokens cost one
    decode pool at ``llm_steps_per_pool`` = 8 and eight at 1, whatever the
    number of streams; every token the oracle's."""
    param("llm_steps_per_pool", k)
    prompts = [[(7 * i + 3 * j) % MODEL.vocab for j in range(8)]
               for i in range(streams)]
    with RuntimeServer(nb_cores=2) as server:
        tks = [server.submit_stream(p, max_new_tokens=8, tenant=f"tenant{i}")
               for i, p in enumerate(prompts)]
        for p, tk in zip(prompts, tks):
            assert tk.result(timeout=300)["tokens"] == \
                MODEL.reference_generate(p, 8)
        llm = server.stats()["llm"]
    assert llm["tokens_generated"] == 8 * streams
    assert llm["decode_submits"] == submits     # 1/8 a token, or 1


def test_streams_join_and_leave_midflight_continuous_batching():
    """A late stream joins while earlier ones decode; short streams
    retire without stalling the batch — and everyone still matches the
    oracle (iteration-level scheduling correctness)."""
    with RuntimeServer(nb_cores=2) as server:
        first = server.submit_stream([3, 7, 11], max_new_tokens=12)
        short = server.submit_stream([5, 9], max_new_tokens=2)
        assert short.result(timeout=120)["tokens"] == \
            MODEL.reference_generate([5, 9], 2)
        late = server.submit_stream([8, 30], max_new_tokens=4)
        assert first.result(timeout=120)["tokens"] == \
            MODEL.reference_generate([3, 7, 11], 12)
        assert late.result(timeout=120)["tokens"] == \
            MODEL.reference_generate([8, 30], 4)
        llm = server.stats()["llm"]
        assert llm["streams_completed"] == 3


def test_batcher_validates_inputs_and_rejects_after_stop():
    with RuntimeServer(nb_cores=1) as server:
        with pytest.raises(ValueError):
            server.submit_stream([], max_new_tokens=2)
        with pytest.raises(ValueError):
            server.submit_stream([1], max_new_tokens=0)
        tk = server.submit_stream([1, 2], max_new_tokens=2)
        tk.result(timeout=60)
    # the server drained: the session API sheds, it does not wedge
    from parsec_tpu.serve import AdmissionRejected
    with pytest.raises(AdmissionRejected):
        server.submit_stream([1, 2], max_new_tokens=2)


def test_page_budget_exhaustion_fails_only_the_oversized_stream():
    """Failure containment: a stream whose prompt blows the KV page
    budget fails ALONE — the other tenants'/streams' generation and the
    batcher loop keep going (code-review finding on the catch-all)."""
    with RuntimeServer(nb_cores=2) as server:
        kv = _kv(page_size=2, max_pages=3)
        b = ContinuousBatcher(server, model=MODEL, kv=kv)
        big = b.submit_stream(list(range(1, 10)), max_new_tokens=2,
                              tenant="big")       # prompt needs 4 pages
        small = b.submit_stream([1, 2], max_new_tokens=2, tenant="small")
        with pytest.raises(MemoryError):
            big.result(timeout=60)
        r = small.result(timeout=60)
        assert r["tokens"] == MODEL.reference_generate([1, 2], 2)
        assert small.generated() == r["tokens"]
        # the failed stream's partial pages were reclaimed
        assert b.stats()["kv"]["physical_pages"] == 0
        b.stop()


def test_batcher_direct_on_server_with_custom_kv_geometry():
    """ContinuousBatcher composes with a caller-owned KV collection
    (page size 2 forces multi-page chains immediately)."""
    with RuntimeServer(nb_cores=2) as server:
        kv = _kv(page_size=2)
        b = ContinuousBatcher(server, model=MODEL, kv=kv)
        tk = b.submit_stream([3, 7, 11, 5, 9], max_new_tokens=6)
        assert tk.result(timeout=120)["tokens"] == \
            MODEL.reference_generate([3, 7, 11, 5, 9], 6)
        assert b.stats()["kv"]["physical_pages"] == 0
        # retired streams leave NO side-collection residue either
        assert b.Q.known_keys() == [] and b.O.known_keys() == []
        b.stop()


def test_stream_eos_stops_early_and_matches_truncated_oracle():
    """EOS sampled mid-superpool (ISSUE 9): the stream finishes at the
    EOS token (inclusive), the predicated tail is never surfaced, and
    pages recycle — while a no-EOS stream in the same batch runs to its
    full budget."""
    ref = MODEL.reference_generate([3, 7, 11, 5], 10)
    eos = ref[1]
    want = MODEL.reference_generate([3, 7, 11, 5], 10, eos=eos)
    assert 1 <= len(want) < 10       # genuinely mid-superpool (k=8)
    with RuntimeServer(nb_cores=2) as server:
        te = server.submit_stream([3, 7, 11, 5], max_new_tokens=10,
                                  eos=eos)
        tf = server.submit_stream([1, 40], max_new_tokens=10)
        re_ = te.result(timeout=120)
        assert re_["tokens"] == want
        assert len(re_["per_token_s"]) == len(want)
        assert tf.result(timeout=120)["tokens"] == \
            MODEL.reference_generate([1, 40], 10)
        assert server.stats()["llm"]["kv"]["physical_pages"] == 0


def test_streams_join_and_leave_between_superpools(param):
    """Iteration-level scheduling at superpool grain (k=4): a short
    stream leaves mid-run, a late one joins at the next superpool
    boundary — and every stream still matches the oracle token for
    token."""
    param("llm_steps_per_pool", 4)
    with RuntimeServer(nb_cores=2) as server:
        first = server.submit_stream([3, 7, 11], max_new_tokens=11)
        short = server.submit_stream([5, 9], max_new_tokens=2)
        assert short.result(timeout=120)["tokens"] == \
            MODEL.reference_generate([5, 9], 2)
        late = server.submit_stream([8, 30], max_new_tokens=6)
        assert first.result(timeout=120)["tokens"] == \
            MODEL.reference_generate([3, 7, 11], 11)
        assert late.result(timeout=120)["tokens"] == \
            MODEL.reference_generate([8, 30], 6)
        llm = server.stats()["llm"]
        assert llm["streams_completed"] == 3
        # 11 tokens at k=4 is 4+4+3: the superpool clips to the budget
        assert llm["decode_submits"] < 11 + 2 + 6, llm


def test_fork_on_prompt_shares_pages_until_first_divergent_write():
    """The ISSUE-9 serving surface for PagedKVCollection.fork: streams
    opened with fork_from= share the parent's prompt pages CoW — full
    prompt pages stay physically shared for the streams' lifetime, only
    the tails privatize (at the first divergent write), and every fork
    still matches the oracle."""
    with RuntimeServer(nb_cores=2) as server:
        prompt = list(range(1, 41))    # 39 cached tokens -> 3 pages @16
        t1 = server.submit_stream(prompt, max_new_tokens=6)
        t2 = server.submit_stream(prompt, max_new_tokens=4, fork_from=t1)
        t3 = server.submit_stream(prompt, max_new_tokens=6, fork_from=t1)
        assert t1.result(timeout=120)["tokens"] == \
            MODEL.reference_generate(prompt, 6)
        assert t2.result(timeout=120)["tokens"] == \
            MODEL.reference_generate(prompt, 4)
        assert t3.result(timeout=120)["tokens"] == \
            MODEL.reference_generate(prompt, 6)
        llm = server.stats()["llm"]
        assert llm["forked_streams"] == 2
        kv = llm["kv"]
        # each fork privatized ONLY its tail page (CoW at the first
        # divergent write); the full prompt pages were never copied, so
        # three streams allocated far less than three prompts' worth
        assert kv["cow_copies"] >= 2, kv
        prompt_pages = (len(prompt) - 1 + 15) // 16
        assert kv["pages_allocated"] < 3 * prompt_pages, kv
        assert kv["physical_pages"] == 0       # everything recycled


def test_fork_from_requires_identical_prompt_and_known_ticket():
    with RuntimeServer(nb_cores=2) as server:
        t1 = server.submit_stream([1, 2, 3], max_new_tokens=2)
        with pytest.raises(ValueError, match="identical prompt"):
            server.submit_stream([1, 2, 4], max_new_tokens=2,
                                 fork_from=t1)
        with pytest.raises(ValueError, match="StreamTicket"):
            server.submit_stream([1, 2, 3], max_new_tokens=2,
                                 fork_from=object())
        # a foreign batcher's ticket must be rejected by IDENTITY: its
        # seq ids collide with ours, so accepting it could fork an
        # unrelated local sequence's pages
        with RuntimeServer(nb_cores=1) as other:
            with pytest.raises(ValueError, match="this batcher"):
                other.submit_stream([1, 2, 3], max_new_tokens=2,
                                    fork_from=t1)
        t1.result(timeout=60)


def test_fork_from_retired_parent_falls_back_to_plain_prefill():
    """A fork whose parent already finished (cache freed) must not fail
    the child: it silently prefills on its own and still matches the
    oracle — sharing is an optimization, never a correctness gate."""
    with RuntimeServer(nb_cores=2) as server:
        t1 = server.submit_stream([3, 7, 11, 5], max_new_tokens=2)
        t1.result(timeout=60)          # parent retires, pages freed
        t2 = server.submit_stream([3, 7, 11, 5], max_new_tokens=3,
                                 fork_from=t1)
        assert t2.result(timeout=60)["tokens"] == \
            MODEL.reference_generate([3, 7, 11, 5], 3)
        assert server.stats()["llm"]["forked_streams"] == 0


def test_fork_from_decoding_parent_forks_early_or_falls_back(param):
    """The classification window (ISSUE 12 closed most of it): a child
    classified against a live parent sitting exactly at the prompt
    boundary now forks AT CLASSIFICATION TIME — before the same
    iteration's decode superpool can advance the parent — and CoW
    privatizes the parent's next append away from the child's
    snapshot.  A child that only classifies AFTER the parent advanced
    still takes the documented silent fallback (its own plain
    prefill).  Either way: oracle-exact tokens, never a stream failure
    from iteration timing."""
    import time as _time
    param("llm_steps_per_pool", 2)
    prompt = [3, 7, 11, 5]
    with RuntimeServer(nb_cores=2) as server:
        t1 = server.submit_stream(prompt, max_new_tokens=6)
        deadline = _time.monotonic() + 60
        # submit the child while the parent PREFILLS: it lands in a
        # LATER iteration's fresh batch, where the parent either still
        # sits at its boundary (early fork) or has decoded (fallback)
        while t1.state == "queued":
            assert _time.monotonic() < deadline, "parent never admitted"
            _time.sleep(0.0002)
        t2 = server.submit_stream(prompt, max_new_tokens=3, fork_from=t1)
        assert t1.result(timeout=60)["tokens"] == \
            MODEL.reference_generate(prompt, 6)
        assert t2.result(timeout=60)["tokens"] == \
            MODEL.reference_generate(prompt, 3)
        # sharing is an optimization whose window depends on iteration
        # timing: both resolutions are legal, failure is not
        assert server.stats()["llm"]["forked_streams"] in (0, 1)


def test_batcher_region_lowered_superpools_match_oracle(param):
    """The llm_lower_regions opt-in: the batcher compiles each decode
    superpool into megakernel regions (PR 8) and submits the REGION
    pool — tokens must still equal the oracle exactly (the serving-path
    incarnation of the eager-vs-region equivalence)."""
    param("llm_lower_regions", True)
    param("llm_steps_per_pool", 2)
    with RuntimeServer(nb_cores=2) as server:
        tk = server.submit_stream([3, 7, 11, 5], max_new_tokens=2)
        assert tk.result(timeout=240)["tokens"] == \
            MODEL.reference_generate([3, 7, 11, 5], 2)
        assert server.stats()["llm"]["kv"]["physical_pages"] == 0


def test_step_timeout_defers_page_release_until_pool_terminates():
    """A timed-out step pool may still be RUNNING (serve tickets cannot
    cancel a live DAG): its streams' pages must not recycle to a new
    tenant until the zombie pool actually terminates."""
    from parsec_tpu.llm.batcher import StreamTicket, _Stream
    from parsec_tpu.runtime.taskpool import Taskpool
    with RuntimeServer(nb_cores=1) as server:
        b = ContinuousBatcher(server, model=MODEL, kv=_kv())
        b.kv.alloc_seq("z")
        b.kv.alloc_page("z")
        st = _Stream("z", "t", 0, [1], 1, StreamTicket("z", "t"))
        zombie = Taskpool(name="zombie_step")
        b._retire_failed([st], TimeoutError("step timeout"),
                         defer_pool=zombie)
        with pytest.raises(TimeoutError):
            st.ticket.result(timeout=1)          # client fails promptly...
        assert b.stats()["kv"]["physical_pages"] == 1   # ...pages held
        zombie.terminated()
        assert b.stats()["kv"]["physical_pages"] == 0   # released now
        b.stop()


def test_fork_from_zombie_parent_is_never_ready():
    """A FAILED parent whose page release is deferred behind a
    timed-out zombie pool still has its seq alive and its host-side
    ledger exactly at the prompt boundary — but the zombie pool may
    still be WRITING those pages.  ``_fork_ready`` must refuse it (the
    child then takes the plain-prefill fallback) rather than CoW-share
    pages mid-write."""
    from parsec_tpu.llm.batcher import StreamTicket, _Stream
    from parsec_tpu.runtime.taskpool import Taskpool
    with RuntimeServer(nb_cores=1) as server:
        b = ContinuousBatcher(server, model=MODEL, kv=_kv())
        prompt = [3, 7, 11, 5]
        b.kv.alloc_seq("p")
        prefill_chunks(MODEL, b.kv, "p", prompt[:-1])
        st = _Stream("p", "t", 0, prompt, 4, StreamTicket("p", "t"))
        assert b._fork_ready(st)         # live parent at its boundary
        zombie = Taskpool(name="zombie_step")
        b._retire_failed([st], TimeoutError("step timeout"),
                         defer_pool=zombie)
        # the ledger alone cannot tell this apart from a healthy parent
        assert b.kv.seq_len("p") == len(prompt) - 1
        assert not b._fork_ready(st)     # retired: never fork it
        zombie.terminated()
        b.stop()
