"""Prefill and ragged-decode task classes over the paged KV cache.

The LLM workload expressed in the runtime's own terms (ROADMAP: "ragged
attention task class per Ragged Paged Attention", arxiv 2604.15464):
plain PTG taskpools, so graphcheck statically verifies the per-step
dataflow (edge symmetry, WAR ordering against the KV pages, page-bounds
via :meth:`PagedKVCollection.has_key`) before a single token moves.

**PF(s, c)** — prefill: copy prompt chunk ``c`` of sequence ``s`` into
its KV page.  Embarrassingly parallel across chunks and sequences.

**ATTN(s, p)** — one query against one KV page, online-softmax state
threading along the sequence's ragged page list::

    ATTN(s,0) -> ATTN(s,1) -> ... -> ATTN(s, NP[s]-1) -> OUT(s)

Page tiles are uniform ``(3, page_size, H, D)`` (the fill count rides
in the tensor — ``data_dist/paged_kv.py``), so every live sequence's
ATTN tasks are the SAME class with the SAME shapes: the TPU device
module's fused same-class dispatch (``device/tpu.py:_run_vmapped``)
batches them into one fused XLA call — continuous batching meets the
PR-2 batched dispatch at the kernel level.

**OUT(s)** — finalize the attention output into the O collection and
append the query token's k/v into the tail page.  The tail-page write
is ordered AFTER ``ATTN(s, NP-1)``'s read of the same page by the ACC
chain — the WAR edge graphcheck checks.
"""

from __future__ import annotations

from typing import Any, Sequence

import numpy as np

from .. import ptg
from ..data.datatype import TileType
from ..data_dist.collection import DictCollection
from ..data_dist.paged_kv import META_CH, PagedKVCollection
from ..ops import ragged_attention as ra


def prefill_ptg(kv: PagedKVCollection, T: DictCollection,
                seqs: Sequence[Any], devices: str = "cpu",
                name: str = "llm_prefill",
                starts: Sequence[int] | None = None) -> ptg.PTGTaskpool:
    """PF(s, c) over every allocated page of every listed sequence.
    ``T`` holds the prompt chunk tiles, keyed ``(seq, chunk)``, in the
    same ``(3, page_size, H, D)`` layout as the pages.

    ``starts[i]`` is sequence ``i``'s first chunk to fill — the
    **tail-only prefill** shape (ISSUE 11): a stream admitted through
    the prefix cache already shares its first ``starts[i]`` pages
    copy-on-write with the trie, and the PF tasks must neither redo nor
    overwrite them.  Default 0 everywhere = the full prefill."""
    NP = tuple(kv.npages(s) for s in seqs)
    C0 = (tuple(0 for _ in seqs) if starts is None
          else tuple(int(c) for c in starts))
    if len(C0) != len(seqs) or any(not 0 <= c <= n
                                   for c, n in zip(C0, NP)):
        raise ValueError(f"starts {C0} out of range for page counts {NP}")
    p = ptg.PTGBuilder(name, KV=kv, T=T, SEQS=tuple(seqs), NP=NP,
                       C0=C0, NS=len(seqs))
    t = p.task("PF",
               s=ptg.span(0, lambda g, l: g.NS - 1),
               c=lambda g, l: range(g.C0[l.s], g.NP[l.s]))
    t.affinity("KV", lambda g, l: (g.SEQS[l.s], l.c))
    ft = t.flow("T", ptg.READ)
    ft.input(data=("T", lambda g, l: (g.SEQS[l.s], l.c)))
    fkv = t.flow("KV", ptg.RW)
    fkv.input(data=("KV", lambda g, l: (g.SEQS[l.s], l.c)))
    fkv.output(data=("KV", lambda g, l: (g.SEQS[l.s], l.c)))

    def body(es: Any, task: Any, g: Any, l: Any) -> None:
        chunk = np.asarray(task.flow_data("T").value)
        kvw = task.flow_data("KV")
        kvw.value = np.array(chunk, copy=True)
        kvw.version += 1

    if devices in ("auto", "tpu"):
        t.body(device="tpu", dyld="llm_prefill_copy")
    # the dyld names the traceable twin (ops/ragged_attention.py), so
    # the pool lowers/warms (llm_prefill_tail) and the device tier can
    # batch PF tasks; the CPU body stays the plain copy
    t.body(body, dyld="llm_prefill_copy")
    return p.build()


def decode_step_ptg(kv: PagedKVCollection, Q: DictCollection,
                    O: DictCollection, seqs: Sequence[Any],
                    devices: str = "cpu",
                    name: str = "llm_decode") -> ptg.PTGTaskpool:
    """One decode iteration for every listed sequence.

    Callers must have made the write slot real first
    (:meth:`PagedKVCollection.ensure_tail_slot`), so ``NP[s] >= 1`` and
    the tail page is private — the builder snapshots the page counts.
    """
    NP = tuple(kv.npages(s) for s in seqs)
    assert all(n >= 1 for n in NP), \
        "decode needs ensure_tail_slot() first (NP >= 1)"
    H, D = kv.num_heads, kv.head_dim
    p = ptg.PTGBuilder(name, KV=kv, Q=Q, O=O, SEQS=tuple(seqs), NP=NP,
                       NS=len(seqs))

    t = p.task("ATTN",
               s=ptg.span(0, lambda g, l: g.NS - 1),
               p=lambda g, l: range(g.NP[l.s]))
    t.affinity("KV", lambda g, l: (g.SEQS[l.s], l.p))
    # drain long page chains first: the step's critical path
    t.priority(lambda g, l: g.NP[l.s] - l.p)
    fq = t.flow("Q", ptg.READ)
    fq.input(data=("Q", lambda g, l: (g.SEQS[l.s],)))
    fkv = t.flow("KV", ptg.READ)
    fkv.input(data=("KV", lambda g, l: (g.SEQS[l.s], l.p)))
    facc = t.flow("ACC", ptg.RW, dtt=TileType((H, D + 2), np.float32))
    facc.input(new=True, guard=lambda g, l: l.p == 0)
    facc.input(pred=("ATTN", "ACC", lambda g, l: {"s": l.s, "p": l.p - 1}),
               guard=lambda g, l: l.p > 0)
    facc.output(succ=("ATTN", "ACC",
                      lambda g, l: {"s": l.s, "p": l.p + 1}),
                guard=lambda g, l: l.p < g.NP[l.s] - 1)
    facc.output(succ=("OUT", "ACC", lambda g, l: {"s": l.s}),
                guard=lambda g, l: l.p == g.NP[l.s] - 1)

    def attn_body(es: Any, task: Any, g: Any, l: Any) -> None:
        acc = task.flow_data("ACC")
        acc.value = ra.attn_page_update_np(
            np.asarray(task.flow_data("Q").value),
            np.asarray(task.flow_data("KV").value),
            np.asarray(acc.value))
        acc.version += 1

    if devices in ("auto", "tpu"):
        t.body(device="tpu", dyld="ragged_attn_page")
    t.body(attn_body)

    o = p.task("OUT", s=ptg.span(0, lambda g, l: g.NS - 1))
    o.affinity("KV", lambda g, l: (g.SEQS[l.s], g.NP[l.s] - 1))
    foacc = o.flow("ACC", ptg.READ)
    foacc.input(pred=("ATTN", "ACC",
                      lambda g, l: {"s": l.s, "p": g.NP[l.s] - 1}))
    foq = o.flow("Q", ptg.READ)
    foq.input(data=("Q", lambda g, l: (g.SEQS[l.s],)))
    fkvw = o.flow("KVW", ptg.RW)
    fkvw.input(data=("KV", lambda g, l: (g.SEQS[l.s], g.NP[l.s] - 1)))
    fkvw.output(data=("KV", lambda g, l: (g.SEQS[l.s], g.NP[l.s] - 1)))
    fo = o.flow("O", ptg.WRITE, dtt=TileType((H, D), np.float32))
    fo.input(new=True)
    fo.output(data=("O", lambda g, l: (g.SEQS[l.s],)))

    def out_body(es: Any, task: Any, g: Any, l: Any) -> None:
        kvw = task.flow_data("KVW")
        oc = task.flow_data("O")
        new_page, out = ra.attn_out_np(
            np.asarray(task.flow_data("ACC").value),
            np.asarray(task.flow_data("Q").value),
            np.asarray(kvw.value))
        kvw.value = new_page
        kvw.version += 1
        oc.value = out
        oc.version += 1

    if devices in ("auto", "tpu"):
        o.body(device="tpu", dyld="ragged_attn_out")
    o.body(out_body)
    return p.build()


def preallocate_decode_steps(kv: PagedKVCollection, seq: Any,
                             k: int) -> None:
    """Make ``k`` autoregressive write slots real BEFORE the superpool is
    built: token positions are deterministic (``seq_len .. seq_len+k-1``),
    so every tail page the k steps will touch can be allocated — and a
    fork-shared tail copy-on-write privatized — at build time.  (The
    builder re-derives the per-step page schedule itself from the
    ledger; this only has to make the pages exist.)"""
    if k < 1:
        raise ValueError("k must be >= 1")
    P = kv.page_size
    L0 = kv.seq_len(seq)
    kv.ensure_tail_slot(seq)            # CoW-privatize + first write page
    last_page = (L0 + k - 1) // P
    while kv.npages(seq) <= last_page:
        kv.alloc_page(seq)              # fresh pages are private + zeroed


def _superpool_schedule(kv: PagedKVCollection, seqs: Sequence[Any],
                        steps: Sequence[int], kind: str):
    """The deterministic per-(seq, step/position) page schedule BOTH
    superpool builders share (the k-step SAMPLE pool and the
    speculative per-position pool append the same token positions):
    ``NP[t]`` pages attended, ``WP[t]`` the append page, ``LW[t][p]``
    the last step < t writing page p (-1: frozen — read straight from
    the collection), ``RD[t]`` the later steps whose ATTN re-reads the
    page step t wrote.  LW/RD are exactly the last-writer/reader
    tables graphcheck proves the cross-step (and speculative-tail)
    WAR/WAW ordering from — one derivation, two incarnations."""
    P = kv.page_size
    L0 = tuple(kv.seq_len(s) for s in seqs)
    NP, WP, LW, RD = [], [], [], []
    for si, s in enumerate(seqs):
        wp_s = tuple((L0[si] + t) // P for t in range(steps[si]))
        np_s = tuple(w + 1 for w in wp_s)
        if kv.npages(s) < np_s[-1]:
            raise ValueError(
                f"{kind} needs preallocate_decode_steps() first: "
                f"seq {s!r} has {kv.npages(s)} pages, its "
                f"{steps[si]}-step schedule needs {np_s[-1]}")
        lw_s = []
        for t in range(steps[si]):
            lw_s.append(tuple(
                max((tp_ for tp_ in range(t) if wp_s[tp_] == p),
                    default=-1)
                for p in range(np_s[t])))
        rd_s = tuple(tuple(tt for tt in range(t + 1, steps[si])
                           if lw_s[tt][wp_s[t]] == t)
                     for t in range(steps[si]))
        NP.append(np_s)
        WP.append(wp_s)
        LW.append(tuple(lw_s))
        RD.append(rd_s)
    return L0, tuple(NP), tuple(WP), tuple(LW), tuple(RD)


def decode_superpool_ptg(kv: PagedKVCollection, Q: DictCollection,
                         O: DictCollection, TOK: DictCollection,
                         EMB: DictCollection, seqs: Sequence[Any],
                         steps: Sequence[int], devices: str = "cpu",
                         name: str = "llm_superpool") -> ptg.PTGTaskpool:
    """ONE PTG pool spanning ``steps[i]`` autoregressive decode
    iterations for each listed sequence — the k-step superpool (ISSUE 9).

    Per step t of sequence s::

        ATTN(s,t,p)  online-softmax of q(s,t) over page p, ACC threading
        OUT(s,t)     finalize -> SAMPLE; append q-token k/v to the tail
        SAMPLE(s,t)  in-graph greedy argmax over OUT's logits: writes
                     TOK(s,t) (the token the host reads) and feeds the
                     NEXT step's query q3(token) to ATTN/OUT(s,t+1)

    The host loop runs once per k tokens instead of once per token: the
    per-pool submit/termdet overhead (~1-2 ms) amortizes 1/k, and the
    whole k-step DAG is one graphcheck-verified region-lowerable graph.

    Callers must have (a) preallocated every step's write slot
    (:func:`preallocate_decode_steps` — positions are deterministic),
    (b) seeded ``Q(seq)`` with the current token's q3 stack and
    ``TOK(seq, -1)`` with ``[token, 0, eos]`` (``eos < 0`` = disabled),
    and (c) loaded ``EMB(0,)`` with the model's precomputed q3 stack
    table (:meth:`~parsec_tpu.llm.model.ToyLM.q3_table`).  EOS
    and early-finishing streams are handled by predicated step bodies
    (:func:`~parsec_tpu.ops.ragged_attention.sample_step_np`): a
    finished stream's remaining tasks run but change nothing, so a
    mid-superpool finish wastes at most its own tail tasks.
    """
    NS = len(seqs)
    S = tuple(int(k) for k in steps)
    if len(S) != NS or any(k < 1 for k in S):
        raise ValueError("steps must give every sequence >= 1 step")
    _, NP, WP, LW, RD = _superpool_schedule(kv, seqs, S, "superpool")
    H, D = kv.num_heads, kv.head_dim
    p = ptg.PTGBuilder(name, KV=kv, Q=Q, O=O, TOK=TOK, EMB=EMB,
                       SEQS=tuple(seqs), NS=NS, S=S, NP=NP,
                       WP=WP, LW=LW, RD=RD)

    t = p.task("ATTN",
               s=ptg.span(0, lambda g, l: g.NS - 1),
               t=lambda g, l: range(g.S[l.s]),
               p=lambda g, l: range(g.NP[l.s][l.t]))
    t.affinity("KV", lambda g, l: (g.SEQS[l.s], l.p))
    # drain earlier steps and long page chains first: the critical path
    t.priority(lambda g, l: (g.S[l.s] - l.t) * 1024
               + g.NP[l.s][l.t] - l.p)
    fq = t.flow("Q", ptg.READ)
    fq.input(data=("Q", lambda g, l: (g.SEQS[l.s],)),
             guard=lambda g, l: l.t == 0)
    fq.input(pred=("SAMPLE", "QN",
                   lambda g, l: {"s": l.s, "t": l.t - 1}),
             guard=lambda g, l: l.t > 0)
    fkv = t.flow("KV", ptg.READ)
    fkv.input(data=("KV", lambda g, l: (g.SEQS[l.s], l.p)),
              guard=lambda g, l: g.LW[l.s][l.t][l.p] < 0)
    fkv.input(pred=("OUT", "KVW",
                    lambda g, l: {"s": l.s, "t": g.LW[l.s][l.t][l.p]}),
              guard=lambda g, l: g.LW[l.s][l.t][l.p] >= 0)
    facc = t.flow("ACC", ptg.RW, dtt=TileType((H, D + 2), np.float32))
    facc.input(new=True, guard=lambda g, l: l.p == 0)
    facc.input(pred=("ATTN", "ACC",
                     lambda g, l: {"s": l.s, "t": l.t, "p": l.p - 1}),
               guard=lambda g, l: l.p > 0)
    facc.output(succ=("ATTN", "ACC",
                      lambda g, l: {"s": l.s, "t": l.t, "p": l.p + 1}),
                guard=lambda g, l: l.p < g.NP[l.s][l.t] - 1)
    facc.output(succ=("OUT", "ACC", lambda g, l: {"s": l.s, "t": l.t}),
                guard=lambda g, l: l.p == g.NP[l.s][l.t] - 1)

    def attn_body(es: Any, task: Any, g: Any, l: Any) -> None:
        acc = task.flow_data("ACC")
        acc.value = ra.attn_page_update_np(
            np.asarray(task.flow_data("Q").value),
            np.asarray(task.flow_data("KV").value),
            np.asarray(acc.value))
        acc.version += 1

    if devices in ("auto", "tpu"):
        t.body(device="tpu", dyld="ragged_attn_page")
    t.body(attn_body, dyld="ragged_attn_page")

    o = p.task("OUT", s=ptg.span(0, lambda g, l: g.NS - 1),
               t=lambda g, l: range(g.S[l.s]))
    o.affinity("KV", lambda g, l: (g.SEQS[l.s], g.WP[l.s][l.t]))
    o.priority(lambda g, l: (g.S[l.s] - l.t) * 1024)
    foacc = o.flow("ACC", ptg.READ)
    foacc.input(pred=("ATTN", "ACC",
                      lambda g, l: {"s": l.s, "t": l.t,
                                    "p": g.NP[l.s][l.t] - 1}))
    foq = o.flow("Q", ptg.READ)
    foq.input(data=("Q", lambda g, l: (g.SEQS[l.s],)),
              guard=lambda g, l: l.t == 0)
    foq.input(pred=("SAMPLE", "QN",
                    lambda g, l: {"s": l.s, "t": l.t - 1}),
              guard=lambda g, l: l.t > 0)
    fkvw = o.flow("KVW", ptg.RW)
    fkvw.input(data=("KV", lambda g, l: (g.SEQS[l.s], g.WP[l.s][l.t])),
               guard=lambda g, l: l.t == 0
               or g.WP[l.s][l.t] != g.WP[l.s][l.t - 1])
    fkvw.input(pred=("OUT", "KVW",
                     lambda g, l: {"s": l.s, "t": l.t - 1}),
               guard=lambda g, l: l.t > 0
               and g.WP[l.s][l.t] == g.WP[l.s][l.t - 1])
    fkvw.output(data=("KV", lambda g, l: (g.SEQS[l.s], g.WP[l.s][l.t])))
    fkvw.output(succ=("OUT", "KVW",
                      lambda g, l: {"s": l.s, "t": l.t + 1}),
                guard=lambda g, l: l.t + 1 < g.S[l.s]
                and g.WP[l.s][l.t + 1] == g.WP[l.s][l.t])
    fkvw.output(succ=("ATTN", "KV",
                      lambda g, l: [{"s": l.s, "t": tt,
                                     "p": g.WP[l.s][l.t]}
                                    for tt in g.RD[l.s][l.t]]),
                guard=lambda g, l: bool(g.RD[l.s][l.t]))
    fo = o.flow("O", ptg.WRITE, dtt=TileType((H, D), np.float32))
    fo.input(new=True)
    fo.output(succ=("SAMPLE", "O", lambda g, l: {"s": l.s, "t": l.t}))
    fo.output(data=("O", lambda g, l: (g.SEQS[l.s],)),
              guard=lambda g, l: l.t == g.S[l.s] - 1)

    def out_body(es: Any, task: Any, g: Any, l: Any) -> None:
        kvw = task.flow_data("KVW")
        oc = task.flow_data("O")
        new_page, out = ra.attn_out_np(
            np.asarray(task.flow_data("ACC").value),
            np.asarray(task.flow_data("Q").value),
            np.asarray(kvw.value))
        kvw.value = new_page
        kvw.version += 1
        oc.value = out
        oc.version += 1

    if devices in ("auto", "tpu"):
        o.body(device="tpu", dyld="ragged_attn_out")
    o.body(out_body, dyld="ragged_attn_out")

    sm = p.task("SAMPLE", s=ptg.span(0, lambda g, l: g.NS - 1),
                t=lambda g, l: range(g.S[l.s]))
    sm.affinity("KV", lambda g, l: (g.SEQS[l.s], g.WP[l.s][l.t]))
    sm.priority(lambda g, l: (g.S[l.s] - l.t) * 1024)
    fso = sm.flow("O", ptg.READ)
    fso.input(pred=("OUT", "O", lambda g, l: {"s": l.s, "t": l.t}))
    fst = sm.flow("TOK", ptg.RW, dtt=TileType((3,), np.float32))
    fst.input(data=("TOK", lambda g, l: (g.SEQS[l.s], -1)),
              guard=lambda g, l: l.t == 0)
    fst.input(pred=("SAMPLE", "TOK",
                    lambda g, l: {"s": l.s, "t": l.t - 1}),
              guard=lambda g, l: l.t > 0)
    fst.output(data=("TOK", lambda g, l: (g.SEQS[l.s], l.t)))
    fst.output(succ=("SAMPLE", "TOK",
                     lambda g, l: {"s": l.s, "t": l.t + 1}),
               guard=lambda g, l: l.t < g.S[l.s] - 1)
    fse = sm.flow("EMB", ptg.READ)
    fse.input(data=("EMB", lambda g, l: (0,)))
    fsq = sm.flow("QN", ptg.WRITE, dtt=TileType((3, H, D), np.float32))
    fsq.input(new=True)
    fsq.output(succ=("ATTN", "Q",
                     lambda g, l: [{"s": l.s, "t": l.t + 1, "p": pp}
                                   for pp in range(g.NP[l.s][l.t + 1])]),
               guard=lambda g, l: l.t < g.S[l.s] - 1)
    fsq.output(succ=("OUT", "Q",
                     lambda g, l: {"s": l.s, "t": l.t + 1}),
               guard=lambda g, l: l.t < g.S[l.s] - 1)

    def sample_body(es: Any, task: Any, g: Any, l: Any) -> None:
        tok = task.flow_data("TOK")
        qn = task.flow_data("QN")
        tok_new, qn_new = ra.sample_step_np(
            np.asarray(task.flow_data("O").value),
            np.asarray(tok.value),
            np.asarray(task.flow_data("EMB").value))
        tok.value = tok_new
        tok.version += 1
        qn.value = qn_new
        qn.version += 1

    if devices in ("auto", "tpu"):
        sm.body(device="tpu", dyld="llm_sample")
    sm.body(sample_body, dyld="llm_sample")
    return p.build()


def spec_superpool_ptg(kv: PagedKVCollection, DRAFT: DictCollection,
                       O: DictCollection, STOK: DictCollection,
                       DTOK: DictCollection, EMB: DictCollection,
                       seqs: Sequence[Any], positions: Sequence[int],
                       devices: str = "cpu",
                       name: str = "llm_spec") -> ptg.PTGTaskpool:
    """ONE PTG pool verifying ``positions[i]`` speculative draft
    positions for each listed sequence — the **speculative superpool**
    (ISSUE 12), the draft-k-verify generalization of
    :func:`decode_superpool_ptg`.

    Where the PR-9 superpool chains step t's query out of step t-1's
    SAMPLE (a serial in-graph dependence), here EVERY position's query
    is known at build time — position 0 is the stream's real current
    token and positions 1.. are the drafter's proposals — so the page
    schedule is identical but the Q edges are plain data reads::

        ATTN(s,t,p)   q3(draft_t) over page p, ACC threading — ALL
                      positions' frozen-page reads run in parallel (and
                      fused batch: one class, one shape); only the tail
                      page serializes through OUT's appends
        OUT(s,t)      finalize -> VERIFY; append draft_t's k/v to the
                      tail page (speculative — rolled back on reject)
        VERIFY(s,t)   the in-graph accept decision: emits the target's
                      token at live positions, kills the chain at the
                      first draft mismatch (ops/ragged_attention
                      .verify_step_np) — rejected-branch tail tasks run
                      but change nothing, the PR-9 EOS predication shape

    The host reads the STOK chain once per pool
    (:func:`read_spec_chain`): live positions' tokens surface — between
    1 (position 0 always) and ``positions[i]`` per stream — and the
    batcher rolls the rejected appends back with
    :meth:`PagedKVCollection.rollback_tail` before the next superpool,
    so a rejected draft can never leak stale KV.

    Callers must have preallocated every position's write slot
    (:func:`preallocate_decode_steps` — positions are deterministic)
    and seeded DRAFT/DTOK/STOK via :func:`seed_spec_stream` plus
    ``EMB(0,)`` via :func:`seed_emb_table`.  The WAR/WAW ordering of
    the speculative tail (position t's tail-page read AFTER position
    t-1's append, re-reads of an earlier position's written page) rides
    the same static last-writer/reader tables (LW/RD) graphcheck
    already proves for the PR-9 superpool — the speculative tail is
    schedule-identical, only the acceptance is late-bound.
    """
    NS = len(seqs)
    S = tuple(int(n) for n in positions)
    if len(S) != NS or any(n < 1 for n in S):
        raise ValueError("positions must give every sequence >= 1 "
                         "speculative position")
    # identical schedule math to decode_superpool_ptg (position t
    # appends token L0+t), shared via _superpool_schedule — and with it
    # the WAR/WAW edges graphcheck proves
    _, NP, WP, LW, RD = _superpool_schedule(kv, seqs, S,
                                            "spec superpool")
    H, D = kv.num_heads, kv.head_dim
    p = ptg.PTGBuilder(name, KV=kv, DRAFT=DRAFT, O=O, STOK=STOK,
                       DTOK=DTOK, EMB=EMB, SEQS=tuple(seqs), NS=NS, S=S,
                       NP=NP, WP=WP, LW=LW, RD=RD)

    t = p.task("ATTN",
               s=ptg.span(0, lambda g, l: g.NS - 1),
               t=lambda g, l: range(g.S[l.s]),
               p=lambda g, l: range(g.NP[l.s][l.t]))
    t.affinity("KV", lambda g, l: (g.SEQS[l.s], l.p))
    # the tail-page append chain is the only serial path: drain earlier
    # positions and long page chains first
    t.priority(lambda g, l: (g.S[l.s] - l.t) * 1024
               + g.NP[l.s][l.t] - l.p)
    fq = t.flow("Q", ptg.READ)
    # the structural difference vs the PR-9 superpool: the query is a
    # BUILD-TIME datum (the draft), not SAMPLE(t-1)'s output — every
    # position's frozen-page ATTN is immediately runnable
    fq.input(data=("DRAFT", lambda g, l: (g.SEQS[l.s], l.t)))
    fkv = t.flow("KV", ptg.READ)
    fkv.input(data=("KV", lambda g, l: (g.SEQS[l.s], l.p)),
              guard=lambda g, l: g.LW[l.s][l.t][l.p] < 0)
    fkv.input(pred=("OUT", "KVW",
                    lambda g, l: {"s": l.s, "t": g.LW[l.s][l.t][l.p]}),
              guard=lambda g, l: g.LW[l.s][l.t][l.p] >= 0)
    facc = t.flow("ACC", ptg.RW, dtt=TileType((H, D + 2), np.float32))
    facc.input(new=True, guard=lambda g, l: l.p == 0)
    facc.input(pred=("ATTN", "ACC",
                     lambda g, l: {"s": l.s, "t": l.t, "p": l.p - 1}),
               guard=lambda g, l: l.p > 0)
    facc.output(succ=("ATTN", "ACC",
                      lambda g, l: {"s": l.s, "t": l.t, "p": l.p + 1}),
                guard=lambda g, l: l.p < g.NP[l.s][l.t] - 1)
    facc.output(succ=("OUT", "ACC", lambda g, l: {"s": l.s, "t": l.t}),
                guard=lambda g, l: l.p == g.NP[l.s][l.t] - 1)

    def attn_body(es: Any, task: Any, g: Any, l: Any) -> None:
        acc = task.flow_data("ACC")
        acc.value = ra.attn_page_update_np(
            np.asarray(task.flow_data("Q").value),
            np.asarray(task.flow_data("KV").value),
            np.asarray(acc.value))
        acc.version += 1

    if devices in ("auto", "tpu"):
        t.body(device="tpu", dyld="ragged_attn_page")
    t.body(attn_body, dyld="ragged_attn_page")

    o = p.task("OUT", s=ptg.span(0, lambda g, l: g.NS - 1),
               t=lambda g, l: range(g.S[l.s]))
    o.affinity("KV", lambda g, l: (g.SEQS[l.s], g.WP[l.s][l.t]))
    o.priority(lambda g, l: (g.S[l.s] - l.t) * 1024)
    foacc = o.flow("ACC", ptg.READ)
    foacc.input(pred=("ATTN", "ACC",
                      lambda g, l: {"s": l.s, "t": l.t,
                                    "p": g.NP[l.s][l.t] - 1}))
    foq = o.flow("Q", ptg.READ)
    foq.input(data=("DRAFT", lambda g, l: (g.SEQS[l.s], l.t)))
    fkvw = o.flow("KVW", ptg.RW)
    fkvw.input(data=("KV", lambda g, l: (g.SEQS[l.s], g.WP[l.s][l.t])),
               guard=lambda g, l: l.t == 0
               or g.WP[l.s][l.t] != g.WP[l.s][l.t - 1])
    fkvw.input(pred=("OUT", "KVW",
                     lambda g, l: {"s": l.s, "t": l.t - 1}),
               guard=lambda g, l: l.t > 0
               and g.WP[l.s][l.t] == g.WP[l.s][l.t - 1])
    fkvw.output(data=("KV", lambda g, l: (g.SEQS[l.s], g.WP[l.s][l.t])))
    fkvw.output(succ=("OUT", "KVW",
                      lambda g, l: {"s": l.s, "t": l.t + 1}),
                guard=lambda g, l: l.t + 1 < g.S[l.s]
                and g.WP[l.s][l.t + 1] == g.WP[l.s][l.t])
    fkvw.output(succ=("ATTN", "KV",
                      lambda g, l: [{"s": l.s, "t": tt,
                                     "p": g.WP[l.s][l.t]}
                                    for tt in g.RD[l.s][l.t]]),
                guard=lambda g, l: bool(g.RD[l.s][l.t]))
    fo = o.flow("O", ptg.WRITE, dtt=TileType((H, D), np.float32))
    fo.input(new=True)
    fo.output(succ=("VERIFY", "O", lambda g, l: {"s": l.s, "t": l.t}))
    fo.output(data=("O", lambda g, l: (g.SEQS[l.s],)),
              guard=lambda g, l: l.t == g.S[l.s] - 1)

    def out_body(es: Any, task: Any, g: Any, l: Any) -> None:
        kvw = task.flow_data("KVW")
        oc = task.flow_data("O")
        new_page, out = ra.attn_out_np(
            np.asarray(task.flow_data("ACC").value),
            np.asarray(task.flow_data("Q").value),
            np.asarray(kvw.value))
        kvw.value = new_page
        kvw.version += 1
        oc.value = out
        oc.version += 1

    if devices in ("auto", "tpu"):
        o.body(device="tpu", dyld="ragged_attn_out")
    o.body(out_body, dyld="ragged_attn_out")

    vf = p.task("VERIFY", s=ptg.span(0, lambda g, l: g.NS - 1),
                t=lambda g, l: range(g.S[l.s]))
    vf.affinity("KV", lambda g, l: (g.SEQS[l.s], g.WP[l.s][l.t]))
    vf.priority(lambda g, l: (g.S[l.s] - l.t) * 1024)
    fvo = vf.flow("O", ptg.READ)
    fvo.input(pred=("OUT", "O", lambda g, l: {"s": l.s, "t": l.t}))
    fvs = vf.flow("STOK", ptg.RW, dtt=TileType((4,), np.float32))
    fvs.input(data=("STOK", lambda g, l: (g.SEQS[l.s], -1)),
              guard=lambda g, l: l.t == 0)
    fvs.input(pred=("VERIFY", "STOK",
                    lambda g, l: {"s": l.s, "t": l.t - 1}),
              guard=lambda g, l: l.t > 0)
    fvs.output(data=("STOK", lambda g, l: (g.SEQS[l.s], l.t)))
    fvs.output(succ=("VERIFY", "STOK",
                     lambda g, l: {"s": l.s, "t": l.t + 1}),
               guard=lambda g, l: l.t < g.S[l.s] - 1)
    fvd = vf.flow("DTOK", ptg.READ)
    fvd.input(data=("DTOK", lambda g, l: (g.SEQS[l.s], l.t)))
    fve = vf.flow("EMB", ptg.READ)
    fve.input(data=("EMB", lambda g, l: (0,)))

    def verify_body(es: Any, task: Any, g: Any, l: Any) -> None:
        st = task.flow_data("STOK")
        st.value = ra.verify_step_np(
            np.asarray(task.flow_data("O").value),
            np.asarray(st.value),
            np.asarray(task.flow_data("DTOK").value),
            np.asarray(task.flow_data("EMB").value))
        st.version += 1

    if devices in ("auto", "tpu"):
        vf.body(device="tpu", dyld="llm_verify")
    vf.body(verify_body, dyld="llm_verify")
    return p.build()


def _spec_attend_pages(L0: int, n: int, P: int) -> int:
    """Pages the batched spec pool's LAST position attends: position t
    sees tokens ``[0, L0+t)``, so the deepest read ends at token
    ``L0+n-2`` (the last position never attends its own append).  At
    least 1 — an empty cache still runs one (fully masked) page task."""
    return max(1, (L0 + n - 2) // P + 1)


def spec_batched_ptg(kv: PagedKVCollection, QS: DictCollection,
                     LIM: DictCollection, DTOKS: DictCollection,
                     VOUT: DictCollection, EMB: DictCollection,
                     seqs: Sequence[Any], positions: Sequence[int],
                     pad: int | None = None, devices: str = "cpu",
                     name: str = "llm_spec_batched") -> ptg.PTGTaskpool:
    """The BATCHED speculative superpool — the serving hot path's
    incarnation of draft-k-verify (ISSUE 12): the verify pass really is
    "one more batched ragged-attention call over the paged KV".

    Where :func:`spec_superpool_ptg` carries one task per (position,
    page) with in-graph appends (the predicated-branch incarnation the
    analysis sweep proves WAR/WAW-clean), here the host PRE-STAGES the
    whole draft chain's k/v into the tail slots at seed time
    (:func:`seed_spec_batched` — the slots are exactly the ones
    :meth:`~parsec_tpu.data_dist.paged_kv.PagedKVCollection
    .rollback_tail` scrubs on reject), and the pool collapses to::

        SATTN(s, p)   ALL positions' queries against page p in ONE body
                      (ops/ragged_attention.spec_attn_page_np), causal
                      per-position slot limits from the LIM tile; ACC
                      is the (S, H, D+2) flash-state stack, threaded
                      along the page chain
        SVERIFY(s)    finalize every position, sample the target's
                      tokens, compute the accepted prefix — ONE body
                      per stream, result in VOUT(seq)

    ``NP + 1`` tasks per stream per pool instead of ``~k * NP + 2k`` —
    per-task dispatch stops dominating the speculative win on the
    host-dispatched CPU path (the per-position pool gets the same
    collapse only from fused same-class device dispatch).  The pool
    only READS KV pages, so graphcheck is trivially clean; the
    write-side hazards live in the seed/rollback pair, which the
    batcher serializes against the pool (seed before submit, rollback
    after await — the same host-side discipline as seed_stream_step).

    ``pad``: pad every stream's position axis to this count (default:
    the pool's max) — uniform tile shapes are what let the device tier
    batch SATTN across streams and keep the XLA cache warm across
    iterations.  Padded rows ride zero LIM limits and a zero query:
    they fold nothing in and VERIFY ignores them (the DTOKS count).
    """
    P = kv.page_size
    NS = len(seqs)
    S = tuple(int(n) for n in positions)
    if len(S) != NS or any(n < 1 for n in S):
        raise ValueError("positions must give every sequence >= 1 "
                         "speculative position")
    SP = max(S) if pad is None else int(pad)
    if SP < max(S):
        raise ValueError(f"pad {SP} below the pool's max positions "
                         f"{max(S)}")
    L0 = tuple(kv.seq_len(s) for s in seqs)
    NP = tuple(_spec_attend_pages(L0[i], S[i], P) for i in range(NS))
    for i, s in enumerate(seqs):
        need = (L0[i] + S[i] - 1) // P + 1
        if kv.npages(s) < need:
            raise ValueError(
                f"spec batched pool needs preallocate_decode_steps() "
                f"first: seq {s!r} has {kv.npages(s)} pages, its "
                f"{S[i]}-position schedule needs {need}")
    H, D = kv.num_heads, kv.head_dim
    p = ptg.PTGBuilder(name, KV=kv, QS=QS, LIM=LIM, DTOKS=DTOKS,
                       VOUT=VOUT, EMB=EMB, SEQS=tuple(seqs), NS=NS,
                       S=S, SP=SP, NP=NP)

    t = p.task("SATTN",
               s=ptg.span(0, lambda g, l: g.NS - 1),
               p=lambda g, l: range(g.NP[l.s]))
    t.affinity("KV", lambda g, l: (g.SEQS[l.s], l.p))
    # one serial ACC chain per stream: drain long chains first
    t.priority(lambda g, l: g.NP[l.s] - l.p)
    fq = t.flow("QS", ptg.READ)
    fq.input(data=("QS", lambda g, l: (g.SEQS[l.s],)))
    fkv = t.flow("KV", ptg.READ)
    fkv.input(data=("KV", lambda g, l: (g.SEQS[l.s], l.p)))
    fl = t.flow("LIM", ptg.READ)
    fl.input(data=("LIM", lambda g, l: (g.SEQS[l.s], l.p)))
    facc = t.flow("ACC", ptg.RW,
                  dtt=TileType((SP, H, D + 2), np.float32))
    facc.input(new=True, guard=lambda g, l: l.p == 0)
    facc.input(pred=("SATTN", "ACC",
                     lambda g, l: {"s": l.s, "p": l.p - 1}),
               guard=lambda g, l: l.p > 0)
    facc.output(succ=("SATTN", "ACC",
                      lambda g, l: {"s": l.s, "p": l.p + 1}),
                guard=lambda g, l: l.p < g.NP[l.s] - 1)
    facc.output(succ=("SVERIFY", "ACC", lambda g, l: {"s": l.s}),
                guard=lambda g, l: l.p == g.NP[l.s] - 1)

    def sattn_body(es: Any, task: Any, g: Any, l: Any) -> None:
        acc = task.flow_data("ACC")
        acc.value = ra.spec_attn_page_np(
            np.asarray(task.flow_data("QS").value),
            np.asarray(task.flow_data("KV").value),
            np.asarray(task.flow_data("LIM").value),
            np.asarray(acc.value))
        acc.version += 1

    if devices in ("auto", "tpu"):
        t.body(device="tpu", dyld="llm_spec_attn")
    t.body(sattn_body, dyld="llm_spec_attn")

    vf = p.task("SVERIFY", s=ptg.span(0, lambda g, l: g.NS - 1))
    vf.affinity("KV", lambda g, l: (g.SEQS[l.s], g.NP[l.s] - 1))
    fva = vf.flow("ACC", ptg.READ)
    fva.input(pred=("SATTN", "ACC",
                    lambda g, l: {"s": l.s, "p": g.NP[l.s] - 1}))
    fvd = vf.flow("DTOKS", ptg.READ)
    fvd.input(data=("DTOKS", lambda g, l: (g.SEQS[l.s],)))
    fve = vf.flow("EMB", ptg.READ)
    fve.input(data=("EMB", lambda g, l: (0,)))
    fvo = vf.flow("VOUT", ptg.WRITE,
                  dtt=TileType((SP + 2,), np.float32))
    fvo.input(new=True)
    fvo.output(data=("VOUT", lambda g, l: (g.SEQS[l.s],)))

    def sverify_body(es: Any, task: Any, g: Any, l: Any) -> None:
        vout = task.flow_data("VOUT")
        vout.value = ra.spec_verify_np(
            np.asarray(task.flow_data("ACC").value),
            np.asarray(task.flow_data("DTOKS").value),
            np.asarray(task.flow_data("EMB").value))
        vout.version += 1

    if devices in ("auto", "tpu"):
        vf.body(device="tpu", dyld="llm_spec_verify")
    vf.body(sverify_body, dyld="llm_spec_verify")
    return p.build()


def seed_spec_batched(model: Any, kv: PagedKVCollection,
                      QS: DictCollection, LIM: DictCollection,
                      DTOKS: DictCollection, seq: Any, token: int,
                      draft: Sequence[int], pad: int, *,
                      eos: int | None = None) -> int:
    """Seed ONE stream's batched-spec-superpool inputs AND pre-stage the
    draft chain's k/v into its tail slots (the speculative appends the
    pool's causal LIM masks make visible position by position, and
    ``rollback_tail`` scrubs on reject).  Callers must have run
    :func:`preallocate_decode_steps` first — the staged slots are
    private by then.  Returns the position count ``1 + len(draft)``.

    Tile contracts (change them HERE and in the kernels, nowhere
    else): ``QS(seq)`` ``(pad, 3, H, D)`` per-position q3 stacks;
    ``LIM(seq, p)`` ``(pad,)`` per-position valid-slot counts of page
    p; ``DTOKS(seq)`` ``(pad+2,)`` ``[n, eos, chain..., 0 pad]``."""
    chain = [int(token)] + [int(d) for d in draft]
    n = len(chain)
    if n > pad:
        raise ValueError(f"{n} positions exceed pad {pad}")
    P = kv.page_size
    L0 = kv.seq_len(seq)
    q3s = [model.q3(t) for t in chain]
    # pre-stage the appends, one disciplined host write per touched
    # page (update_page_host: sources the newest live copy — the tier
    # or a device copy may be ahead of host — then detaches accelerator
    # copies and jumps the host version past every one, so a deferred
    # device writeback can never clobber the staged draft k/v); the
    # boundary page's existing accepted slots are preserved
    by_page: dict[int, list[tuple[int, int]]] = {}
    for t in range(n):
        pg, slot = divmod(L0 + t, P)
        by_page.setdefault(pg, []).append((slot, t))
    for pg, entries in by_page.items():

        def stage(val: np.ndarray, _pg: int = pg,
                  _entries: list = entries) -> np.ndarray:
            for slot, t in _entries:
                val[0, slot] = q3s[t][1]
                val[1, slot] = q3s[t][2]
            val[META_CH, 0, 0, 0] = min(P, L0 + n - _pg * P)
            return val

        kv.update_page_host(seq, pg, stage)
    H, D = kv.num_heads, kv.head_dim
    qs = np.zeros((pad, 3, H, D), np.float32)
    for t in range(n):
        qs[t] = q3s[t]
    qc = QS.data_of(seq).get_copy(0)
    qc.value = qs
    qc.version += 1
    for p in range(_spec_attend_pages(L0, n, P)):
        lim = np.zeros(pad, np.float32)
        for t in range(n):
            lim[t] = max(0, min(L0 + t - p * P, P))
        lc = LIM.data_of(seq, p).get_copy(0)
        lc.value = lim
        lc.version += 1
    dt = np.zeros(pad + 2, np.float32)
    dt[0] = n
    dt[1] = -1.0 if eos is None else float(eos)
    dt[2:2 + n] = chain
    dc = DTOKS.data_of(seq).get_copy(0)
    dc.value = dt
    dc.version += 1
    return n


def seed_spec_batched_pool(model: Any, kv: PagedKVCollection,
                           QS: DictCollection, LIM: DictCollection,
                           DTOKS: DictCollection, EMB: DictCollection,
                           prompts: dict[Any, Sequence[int]],
                           drafts: dict[Any, Sequence[int]], *,
                           pad: int | None = None,
                           eos: int | None = None
                           ) -> tuple[dict[Any, int], int]:
    """Host-side prep making :func:`spec_batched_ptg`'s input contract
    executable with CALLER-CHOSEN drafts — the batched twin of
    :func:`seed_spec_superpool`, stated ONCE so the analysis sweep and
    the pool-level tests consume the same staging contract the batcher
    runs: prefill each prompt's pages in place, preallocate every
    position's write slot, stage the draft chains
    (:func:`seed_spec_batched`).  Returns ``(positions per seq, pad)``.
    """
    seed_emb_table(model, EMB)
    if pad is None:
        pad = max(len(d) for d in drafts.values()) + 1
    npos: dict[Any, int] = {}
    for seq, prompt in prompts.items():
        kv.alloc_seq(seq)
        for key, tile in prefill_chunks(model, kv, seq,
                                        prompt[:-1]).items():
            pg = kv.data_of(*key).get_copy(0)
            pg.value = np.array(tile, copy=True)
            pg.version += 1
        npos[seq] = 1 + len(drafts[seq])
        preallocate_decode_steps(kv, seq, npos[seq])
        seed_spec_batched(model, kv, QS, LIM, DTOKS, seq, prompt[-1],
                          drafts[seq], pad, eos=eos)
    return npos, pad


def read_spec_batched(VOUT: DictCollection, seq: Any
                      ) -> tuple[list[int], bool]:
    """Read one stream's batched-spec result: the accepted prefix's
    tokens (1..n per pool) and whether a LIVE position sampled EOS —
    a rejected or post-EOS token never surfaces."""
    v = np.asarray(VOUT.data_of(seq).newest_copy().value)
    m = int(round(float(v[0])))
    return [int(round(float(v[2 + i]))) for i in range(m)], v[1] > 0.5


def seed_spec_stream(model: Any, DRAFT: DictCollection,
                     DTOK: DictCollection, STOK: DictCollection,
                     seq: Any, token: int, draft: Sequence[int], *,
                     eos: int | None = None) -> int:
    """Seed ONE stream's speculative-superpool inputs: position 0's
    query is the real current ``token``, positions 1.. the drafter's
    proposals — ``DRAFT(seq, t)`` the q3 stacks, ``DTOK(seq, t)`` the
    token ids the VERIFY bodies compare, ``STOK(seq, -1)`` the
    ``[token, live=1, done=0, eos]`` accept-chain seed (``eos < 0`` =
    disabled).  Returns the position count (``1 + len(draft)``).  The
    layout contract lives HERE and in the kernel, nowhere else."""
    chain = [int(token)] + [int(d) for d in draft]
    for t, tok in enumerate(chain):
        dc = DRAFT.data_of(seq, t).get_copy(0)
        dc.value = model.q3(tok)
        dc.version += 1
        kc = DTOK.data_of(seq, t).get_copy(0)
        kc.value = np.array([float(tok)], np.float32)
        kc.version += 1
    sc = STOK.data_of(seq, -1).get_copy(0)
    sc.value = np.array([float(token), 1.0, 0.0,
                         -1.0 if eos is None else float(eos)],
                        np.float32)
    sc.version += 1
    return len(chain)


def read_spec_chain(STOK: DictCollection, seq: Any,
                    n: int) -> tuple[list[int], bool]:
    """Read a sequence's n-position VERIFY chain the way the batcher
    does: only LIVE positions' tokens surface (the first draft mismatch
    kills the chain; an EOS at a live position finishes the stream),
    so a rejected or post-EOS token can never reach a client.  Returns
    ``(tokens, done)``."""
    toks: list[int] = []
    done = False
    for t in range(n):
        v = np.asarray(STOK.data_of(seq, t).newest_copy().value)
        if v[1] > 0.5:
            toks.append(int(round(float(v[0]))))
            if v[2] > 0.5:
                done = True
    return toks, done


def seed_spec_superpool(model: Any, kv: PagedKVCollection,
                        DRAFT: DictCollection, DTOK: DictCollection,
                        STOK: DictCollection, EMB: DictCollection,
                        prompts: dict[Any, Sequence[int]],
                        drafts: dict[Any, Sequence[int]], *,
                        eos: int | None = None) -> dict[Any, int]:
    """Host-side prep making :func:`spec_superpool_ptg`'s input contract
    executable with CALLER-CHOSEN drafts (the acceptance rate is then
    exactly the drafts' correctness): prefill each prompt's pages in
    place, preallocate every position's write slot, seed the spec
    collections.  Returns the per-seq position counts.  Pool-level
    tests build on this instead of re-deriving the seeding contract."""
    seed_emb_table(model, EMB)
    npos: dict[Any, int] = {}
    for seq, prompt in prompts.items():
        kv.alloc_seq(seq)
        for key, tile in prefill_chunks(model, kv, seq,
                                        prompt[:-1]).items():
            pg = kv.data_of(*key).get_copy(0)
            pg.value = np.array(tile, copy=True)
            pg.version += 1
        npos[seq] = 1 + len(drafts[seq])
        preallocate_decode_steps(kv, seq, npos[seq])
        seed_spec_stream(model, DRAFT, DTOK, STOK, seq, prompt[-1],
                         drafts[seq], eos=eos)
    return npos


def prefill_chunks(model: Any, kv: PagedKVCollection, seq: Any,
                   tokens: Sequence[int]) -> dict[tuple, np.ndarray]:
    """Host-side prefill prep: allocate ``seq``'s pages for ``tokens``
    and return the ``(seq, chunk) -> tile`` map the T collection serves.
    Advances the length ledger — the PF tasks only move the bytes.

    Chunk indices continue from the sequence's CURRENT page count, so a
    prefix-cache adoptee (first ``m`` pages CoW-shared from the trie,
    ledger at the page boundary) prefills only its unmatched tail:
    ``tokens`` are then ``prompt[m * page_size:-1]`` and land in pages
    ``m, m+1, ...`` — a fresh sequence starts at chunk 0 unchanged."""
    P = kv.page_size
    chunks: dict[tuple, np.ndarray] = {}
    n = len(tokens)
    c0 = kv.npages(seq)
    for j in range((n + P - 1) // P):
        kv.alloc_page(seq)
        part = tokens[j * P:(j + 1) * P]
        tile = np.zeros(kv.default_dtt.shape, kv.dtype)
        for i, tok in enumerate(part):
            q3 = model.q3(tok)
            tile[0, i] = q3[1]
            tile[1, i] = q3[2]
        tile[META_CH, 0, 0, 0] = len(part)
        chunks[(seq, c0 + j)] = tile
    kv.note_appended(seq, n)
    return chunks


def seed_emb_table(model: Any, EMB: DictCollection) -> None:
    """Load ``EMB(0,)`` with the model's precomputed ``(V, 3, H, D)``
    q3 stack table — the tile the in-graph SAMPLE class computes logits
    and next-step queries from (one gather per token)."""
    ec = EMB.data_of(0).get_copy(0)
    ec.value = np.array(model.q3_table(), copy=True)
    ec.version += 1


def seed_stream_step(model: Any, Q: DictCollection, TOK: DictCollection,
                     seq: Any, token: int, *,
                     eos: int | None = None) -> None:
    """Seed ONE stream's per-iteration inputs: ``Q(seq)`` with the
    current token's q3 stack and ``TOK(seq, -1)`` with the
    ``[token, done=0, eos]`` chain-seed tile (``eos < 0`` = disabled) —
    the layout contract the SAMPLE bodies read.  The batcher calls this
    per stream per superpool; if the layout changes, it changes HERE
    and in the kernel, nowhere else."""
    qc = Q.data_of(seq).get_copy(0)
    qc.value = model.q3(token)
    qc.version += 1
    t0 = TOK.data_of(seq, -1).get_copy(0)
    t0.value = np.array([float(token), 0.0,
                         -1.0 if eos is None else float(eos)],
                        np.float32)
    t0.version += 1


def seed_decode_superpool(model: Any, kv: PagedKVCollection,
                          Q: DictCollection, TOK: DictCollection,
                          EMB: DictCollection,
                          prompts: dict[Any, Sequence[int]],
                          steps: dict[Any, int], *,
                          eos: int | None = None) -> None:
    """Host-side prep that makes :func:`decode_superpool_ptg`'s input
    contract executable: prefill each prompt's pages in place (no
    runtime), preallocate every step's write slot, and seed the
    collections through the same :func:`seed_emb_table` /
    :func:`seed_stream_step` the batcher uses.  Pool-level tests build
    on this instead of re-deriving the seeding contract."""
    seed_emb_table(model, EMB)
    for seq, prompt in prompts.items():
        kv.alloc_seq(seq)
        for key, tile in prefill_chunks(model, kv, seq,
                                        prompt[:-1]).items():
            pg = kv.data_of(*key).get_copy(0)
            pg.value = np.array(tile, copy=True)
            pg.version += 1
        preallocate_decode_steps(kv, seq, steps[seq])
        seed_stream_step(model, Q, TOK, seq, prompt[-1], eos=eos)


def read_token_chain(TOK: DictCollection, seq: Any,
                     k: int) -> tuple[list[int], bool]:
    """Read a sequence's k-step TOK chain the way the batcher does:
    tokens past the step whose done flag fired are the predicated tail
    and are never surfaced.  Returns ``(tokens, done)`` — ``done`` is
    the last surfaced step's flag, so an EOS on the final step still
    reads as finished."""
    toks: list[int] = []
    done = False
    for t in range(k):
        v = np.asarray(TOK.data_of(seq, t).newest_copy().value)
        if not done:
            toks.append(int(round(float(v[0]))))
            done = bool(v[1] > 0.5)
    return toks, done
