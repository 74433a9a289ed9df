"""Continuous batching: the LLM session layer over a RuntimeServer.

Orca-style iteration-level scheduling on the serving layer's own
primitives: clients open *streams* (:meth:`ContinuousBatcher
.submit_stream` — surfaced as ``RuntimeServer.submit_stream``), and one
batcher thread runs the decode loop::

    each iteration:
      admit newly-arrived streams   -> submit prefill pools (PF tasks)
      group live streams by tenant  -> ONE k-step decode SUPERPOOL per
                                       tenant (llm_steps_per_pool)
      await decode, read TOK tiles  -> k tokens per stream per submit
      await prefill (it OVERLAPPED the decode superpool), join streams
      retire finished streams       -> kv.free_seq (pages recycle)

The superpool (ISSUE 9) is the amortization move: sampling runs
IN-GRAPH (the SAMPLE task class, ``llm/decode.decode_superpool_ptg``),
so one pool spans ``llm_steps_per_pool`` autoregressive iterations and
the per-pool submit/termdet overhead (~1-2 ms) is paid once per k
tokens, not once per token.  With ``llm_spec_k`` set, streams whose
n-gram drafter has a proposal ride a **speculative superpool** instead
(ISSUE 12, ``llm/decode.spec_superpool_ptg``): the draft's 1+k
positions verify in one batched ragged-attention pass with NO serial
sample chain, the in-graph VERIFY class computes the accepted prefix,
and the rejected tail's speculative KV appends roll back
(``PagedKVCollection.rollback_tail``) before the next pool — per-stream
draft length adapts live from the observed acceptance rate.  EOS and early-finishing streams ride
predicated step bodies — a finished stream's remaining tasks no-op, so
it wastes at most its own tail tasks.  Prefill pools for arriving
streams are submitted BEFORE the decode superpools are awaited, so a
long prompt's chunked prefill overlaps a whole k-step iteration instead
of stalling it; new streams join at the next iteration boundary and
finished streams leave without stalling the batch — with admission
control bounding in-flight pools and WFQ arbitrating decode against
whatever dense-linear-algebra tenants share the server (the soak test
mixes decode with a Cholesky pool, ``tests/test_llm_serve.py``).

Every superpool is a fresh PTG taskpool: the live re-enqueue path PR 3
built (``Context.add_taskpool`` under ``_submit_lock``) runs once per
k-token batch, and terminated pools retire from the process registry
(``runtime/taskpool.py``) so a million-token serving run's footprint
stays bounded by LIVE streams, not by history.  ``fork_from=`` forks a
stream's prompt KV copy-on-write from an already-admitted stream with
the same prompt (``PagedKVCollection.fork``): N continuations share ONE
physical copy of the prompt pages until their first divergent write.
"""

from __future__ import annotations

import itertools
import threading
import time
import weakref
from collections import deque
from typing import Any, Sequence

import numpy as np

from ..core.future import Future
from ..core.params import params as _params
from ..data.datatype import TileType
from ..prof import spans as _spans
from ..data_dist.collection import DictCollection
from ..data_dist.kv_tiers import KVTierMap
from ..data_dist.paged_kv import PagedKVCollection
from .decode import (decode_superpool_ptg, preallocate_decode_steps,
                     prefill_chunks, prefill_ptg, read_spec_batched,
                     read_token_chain, seed_emb_table, seed_spec_batched,
                     seed_stream_step, spec_batched_ptg)
from .model import NgramDrafter, ToyLM
from .prefix_tree import PrefixTree

_params.register("llm_page_size", 16,
                 "tokens per KV page (PagedKVCollection block size)")
_params.register("llm_max_batch", 32,
                 "live decode streams a batcher serves concurrently; "
                 "arrivals beyond it queue for the next free slot")
_params.register("llm_max_pages", 4096,
                 "physical KV pages the batcher's cache may hold")
_params.register("llm_step_timeout", 60.0,
                 "seconds the batcher waits for one decode-step pool "
                 "before failing the streams riding it")
_params.register("llm_steps_per_pool", 8,
                 "autoregressive decode steps one superpool spans (the "
                 "in-graph SAMPLE class carries token -> next query "
                 "between steps): the host loop and its submit/termdet "
                 "overhead run once per k tokens; 1 = the PR-6 "
                 "step-per-pool behavior")
# the autotuner's declared domain (docs/TUNING.md): superpool depth
# moves in powers of two; past ~32 the step-timeout and per-stream
# budget clipping dominate, so the search never wanders further
_params.declare_knob("llm_steps_per_pool", lo=1, hi=32, scale="log2")
_params.register("llm_lower_regions", False,
                 "region-lower each decode superpool (ptg.lowering."
                 "lower_regions) before submission: per-step XLA "
                 "dispatches collapse into one jitted program per "
                 "verified region (compile cost rides the lowering "
                 "cache / AOT warming; pools that cannot lower fall "
                 "back to the dynamic path)")
_params.register("llm_spec_k", 0,
                 "speculative decode (ISSUE 12): draft tokens the "
                 "per-stream n-gram drafter proposes per superpool "
                 "(0 = off).  A spec superpool verifies 1+k positions "
                 "in ONE batched ragged-attention pass — every "
                 "position's query is known at build time, so the "
                 "PR-9 serial SAMPLE chain disappears; the in-graph "
                 "VERIFY chain predicates rejected tails off and the "
                 "batcher rolls their speculative KV appends back "
                 "(PagedKVCollection.rollback_tail)")
_params.register("llm_spec_adaptive", True,
                 "adapt each stream's draft length within "
                 "[0, llm_spec_k] from its observed acceptance-rate "
                 "EWMA: draftable traffic grows toward the cap, "
                 "undraftable traffic converges to 0 and falls back "
                 "to the non-speculative k-step superpool (with a "
                 "periodic cheap probe), so acceptance-rate-0 traffic "
                 "degrades to the PR-9 path instead of paying "
                 "rejected drafts forever")
_params.register("llm_prefetch_ahead", True,
                 "stage live streams' device-evicted KV pages back in "
                 "one superpool ahead of the decode wavefront (the "
                 "kv_tiers.KVTierMap return path): the async device_put "
                 "overlaps the in-flight superpools, so an HBM budget "
                 "below the working set costs bandwidth, not stalls")

# live batchers, weakly held: runtime_report()["llm"] aggregates their
# cache/tier effectiveness without pinning a stopped batcher (or
# importing this module when no LLM workload ever ran).  A stopping
# batcher folds its final counters into _retired_totals so the report
# stays cumulative-since-process-start like every other block (a bench
# stage's drained servers still show up in the post-stage report).
_live_batchers: "weakref.WeakSet[ContinuousBatcher]" = weakref.WeakSet()
_retired_totals: dict[str, int] = {}
_retired_lock = threading.Lock()

_REPORT_KEYS = ("tokens_generated", "streams_completed", "decode_submits",
                "forked_streams", "prefill_tokens_total",
                "prefill_tokens_skipped", "spec_submits", "spec_tokens",
                "spec_drafted", "spec_drafts_accepted")
_REPORT_KV_KEYS = ("prefix_hits", "prefix_pages_reused", "host_tier_bytes",
                   "prefetch_inflight", "physical_pages", "cow_copies",
                   "tail_rollbacks", "slots_rolled_back")

# iterations a converged-off adaptive stream waits before probing spec
# again (2 small probe pools per interval; at k=8 plain pools the probe
# tax is ~3% of throughput — inside the acceptance-rate-0 10% budget)
_SPEC_PROBE_EVERY = 64


def _fold_stats(out: dict, s: dict) -> None:
    for k in _REPORT_KEYS:
        out[k] = out.get(k, 0) + s.get(k, 0)
    for k in _REPORT_KV_KEYS:
        out[k] = out.get(k, 0) + s.get("kv", {}).get(k, 0)


def aggregate_report() -> dict:
    """The ``llm`` block of ``prof.runtime_report()``: counters summed
    across every live batcher plus the folded totals of retired ones —
    present in a report only when this module is already imported AND
    an LLM workload actually ran."""
    with _retired_lock:
        out: dict[str, Any] = dict(_retired_totals)
    for b in list(_live_batchers):
        if not getattr(b, "_folded", False):
            _fold_stats(out, b.stats())
    if out:
        total = out.get("prefill_tokens_total", 0)
        out["prefill_skipped_frac"] = round(
            out.get("prefill_tokens_skipped", 0) / total, 4) if total \
            else 0.0
        # the speculative-decode effectiveness pair (ISSUE 12): how
        # often drafts were right, and how many tokens one spec
        # superpool ride yields per stream — cumulative like the rest
        if out.get("spec_drafted"):
            out["spec_accept_rate"] = round(
                out.get("spec_drafts_accepted", 0)
                / out["spec_drafted"], 4)
        if out.get("spec_submits"):
            out["spec_tokens_per_submit"] = round(
                out.get("spec_tokens", 0) / out["spec_submits"], 4)
    return out


class StreamTicket:
    """One generation stream's handle.  ``tokens`` grows live — snapshot
    with :meth:`generated`; ``result()`` blocks for the finished
    transcript."""

    def __init__(self, name: str, tenant: str) -> None:
        self.name = name
        self.tenant = tenant
        self.state = "queued"
        self.submitted_at = time.monotonic()
        self.tokens: list[int] = []
        self.per_token_s: list[float] = []
        self.prefill_s: float | None = None
        self.first_token_at: float | None = None   # monotonic TTFT stamp
        self.prefix_pages_reused = 0   # trie pages this stream skipped
        # speculative-decode visibility (ISSUE 12): the stream's current
        # (possibly adapted) draft cap and its acceptance-rate EWMA,
        # updated after every spec superpool it rides
        self.spec_k: int | None = None
        self.spec_accept_ewma: float | None = None
        # the stream's trace context (prof/spans.py): the request-scoped
        # identity of this generation, named by stall dumps and carried
        # by every decode superpool ticket the stream rides
        self.trace = _spans.new_trace()
        self._future: Future = Future()

    def generated(self) -> list[int]:
        """Snapshot of the tokens generated so far (the batcher appends
        concurrently; ``list()`` of a list is atomic under the GIL)."""
        return list(self.tokens)

    def result(self, timeout: float | None = None) -> dict:
        """Block for completion; returns ``{"tokens": [...],
        "per_token_s": [...], "prefill_s": ...}``."""
        kind, v = self._future.get(timeout)
        if kind == "err":
            raise v
        return v

    def done(self) -> bool:
        return self._future.is_ready()

    def _resolve(self) -> None:
        self.state = "done"
        self._future.set(("ok", {"tokens": list(self.tokens),
                                 "per_token_s": list(self.per_token_s),
                                 "prefill_s": self.prefill_s}))

    def _fail(self, e: BaseException) -> None:
        self.state = "failed"
        self._future.set(("err", e))


class _Stream:
    __slots__ = ("seq", "tenant", "priority", "prompt", "max_new",
                 "ticket", "cur", "devices", "eos", "fork_from", "k",
                 "spec", "drafter", "spec_k", "spec_ewma", "spec_probe")

    def __init__(self, seq: Any, tenant: str, priority: int,
                 prompt: Sequence[int], max_new: int,
                 ticket: StreamTicket, eos: int | None = None,
                 fork_from: "_Stream | None" = None) -> None:
        self.seq = seq
        self.tenant = tenant
        self.priority = priority
        self.prompt = list(prompt)
        self.max_new = max_new
        self.ticket = ticket
        self.cur = int(prompt[-1])
        self.eos = None if eos is None else int(eos)
        self.fork_from = fork_from      # CoW prompt-KV parent (or None)
        self.k = 1                      # steps the current superpool runs
        self.spec = False               # current pool is speculative
        # the stream's drafter, built LAZILY in the batcher thread the
        # first time speculation considers this stream (llm_spec_k off
        # = never): submit_stream stays O(1) — client-side prompt
        # walking here widens the fork-classification arrival window
        self.drafter: NgramDrafter | None = None
        self.spec_k = -1                # adaptive draft cap (-1 = unset)
        self.spec_ewma = -1.0           # acceptance EWMA (-1 = unset)
        self.spec_probe = 0             # iterations since converged off


class ContinuousBatcher:
    """The decode loop.  Owns the paged KV cache plus the Q/O side
    collections; rides an existing :class:`RuntimeServer` for admission,
    fairness, and the hot context."""

    def __init__(self, server: Any, model: ToyLM | None = None,
                 kv: PagedKVCollection | None = None,
                 max_batch: int | None = None,
                 devices: str = "cpu",
                 owner_rank: int | None = None) -> None:
        self._server = server
        self.model = model or ToyLM()
        H, D = self.model.num_heads, self.model.head_dim
        # owner_rank pins EVERY collection tile to one rank of a
        # multirank context: decode pools are submitted on this rank
        # only (sharded serving, serve/sharded.py), so a default-owned
        # (rank 0) tile on any other rank would shell the whole batch
        # out to a rank that never enqueued the pool
        self.owner_rank = owner_rank
        _pin = None if owner_rank is None else (lambda *k: owner_rank)

        def _dc(name: str, dtt: TileType) -> DictCollection:
            return DictCollection(name, dtt=dtt, rank_of_fn=_pin)

        self.kv = kv or PagedKVCollection(
            "llmKV", page_size=_params.get("llm_page_size"),
            num_heads=H, head_dim=D,
            max_pages=_params.get("llm_max_pages"),
            rank_of_fn=None if owner_rank is None
            else (lambda seq, page: owner_rank))
        assert (self.kv.num_heads, self.kv.head_dim) == (H, D), \
            "model and KV cache disagree on head geometry"
        self.Q = _dc("llmQ", TileType((3, H, D), np.float32))
        self.O = _dc("llmO", TileType((H, D), np.float32))
        # the in-graph SAMPLE class's side collections (ISSUE 9): TOK
        # carries the per-step [token, done, eos] chain tiles the host
        # reads once per superpool; EMB holds the precomputed q3 stack
        # table the SAMPLE kernel computes logits/next-queries from
        # (one gather per token — ToyLM.q3_table)
        self.TOK = _dc("llmTOK", TileType((3,), np.float32))
        # the batched speculative superpool's side collections (ISSUE
        # 12, llm/decode.spec_batched_ptg): QS the per-position query
        # stacks (position 0 the real current token, 1.. the drafter's
        # proposals), LIM the per-(seq, page) causal slot limits, DTOKS
        # the packed draft chain the SVERIFY body compares, VOUT the
        # accepted-prefix result the host reads once per spec pool.
        # Tile shapes are per-pool (padded to llm_spec_k + 1); the
        # declared dtts only serve lazy zero-init before the first seed
        sp0 = max(1, int(_params.get("llm_spec_k"))) + 1
        self.QS = _dc("llmQS", TileType((sp0, 3, H, D), np.float32))
        self.LIM = _dc("llmLIM", TileType((sp0,), np.float32))
        self.DTOKS = _dc("llmDTOKS", TileType((sp0 + 2,), np.float32))
        self.VOUT = _dc("llmVOUT", TileType((sp0 + 2,), np.float32))
        self.EMB = _dc(
            "llmEMB", TileType(self.model.q3_table().shape, np.float32))
        seed_emb_table(self.model, self.EMB)
        self.max_batch = max_batch or _params.get("llm_max_batch")
        self.devices = devices
        # the ISSUE-11 memory hierarchy: an automatic prefix cache over
        # the KV collection (llm_prefix_cache — retired streams donate
        # their prompt pages, arrivals fork the longest retained
        # prefix), and a tier map accounting device-evicted pages +
        # staging them back ahead of the wavefront
        self.prefix = (PrefixTree(self.kv)
                       if _params.get("llm_prefix_cache") else None)
        self.tiers = KVTierMap(self.kv)
        self.prefill_tokens_total = 0     # cacheable tokens admitted
        self.prefill_tokens_skipped = 0   # of those, served by the trie
        # the server's per-tenant SLO plane (prof/histogram.py): TTFT +
        # inter-token latency land there, so RuntimeServer.metrics()
        # answers "what are my per-tenant token p99s" live mid-run
        self._slo = getattr(server, "_slo", None)
        self._lock = threading.Lock()
        self._wake = threading.Event()
        self._pending: deque[_Stream] = deque()
        self._live: list[_Stream] = []
        self._seq_ids = itertools.count()
        self._stop = False
        self._abort: BaseException | None = None
        self.steps = 0
        self.tokens_generated = 0
        self.streams_completed = 0
        self.decode_submits = 0         # superpool submits (1/k per token)
        self.forked_streams = 0         # streams whose prompt KV forked
        # speculative-decode tallies (ISSUE 12): spec_submits counts
        # per-stream spec-superpool rides (the unit spec_tokens_per_
        # submit amortizes over), spec_drafted/accepted the drafter's
        # proposal hit rate
        self.spec_submits = 0
        self.spec_tokens = 0
        self.spec_drafted = 0
        self.spec_drafts_accepted = 0
        # per-tenant acceptance prior (batcher thread only): new streams
        # start their adaptive draft cap where the tenant's traffic
        # converged, so undraftable workloads don't pay the cap->0
        # descent once per stream — only the staggered probes remain
        self._spec_prior: dict[str, float] = {}
        # per-tenant live adaptation of llm_steps_per_pool (ISSUE 18,
        # ``tune_adaptive=1``): one hysteresis EWMA controller per
        # tenant (tune/adaptive.KnobController), fed the same observed
        # inter-token latency the SLO plane quantiles.  _k_seed holds
        # the tuning-DB start points RuntimeServer's per-tenant consult
        # hands over before the controller exists (GIL-atomic dict
        # writes; controllers themselves live on the batcher thread)
        self._k_ctl: dict[str, Any] = {}
        self._k_seed: dict[str, int] = {}
        self._pool_seq = itertools.count()
        _live_batchers.add(self)
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="llm-batcher")
        self._thread.start()

    # -- client API ------------------------------------------------------
    def submit_stream(self, prompt_tokens: Sequence[int],
                      max_new_tokens: int = 16, tenant: str = "default",
                      priority: int = 0, eos: int | None = None,
                      fork_from: StreamTicket | None = None
                      ) -> StreamTicket:
        """Open one generation stream; it joins the running batch at the
        next iteration boundary.

        ``eos`` stops generation early when sampled (the EOS token is
        the last one kept; handled in-graph by the predicated SAMPLE
        bodies, so a mid-superpool finish wastes no other stream's
        work).  ``fork_from`` names an earlier stream's ticket with the
        SAME prompt: the new stream forks its prompt KV copy-on-write
        (``PagedKVCollection.fork``) instead of re-prefilling — N
        continuations of one prompt hold one physical copy of the
        prompt pages until their first divergent write.  When the
        parent has already advanced past its prompt (or retired), the
        fork silently falls back to a normal prefill."""
        if not prompt_tokens:
            raise ValueError("prompt_tokens must be non-empty")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        parent = None
        if fork_from is not None:
            parent = getattr(fork_from, "_stream", None)
            # identity, not shape: another batcher's seq ids collide
            # with ours, so a foreign ticket could fork an UNRELATED
            # local sequence's pages
            if parent is None or getattr(fork_from, "_batcher",
                                         None) is not self:
                raise ValueError("fork_from must be a StreamTicket from "
                                 "this batcher")
            if parent.prompt != list(prompt_tokens):
                raise ValueError("fork_from requires an identical prompt "
                                 "(the shared-prefix pages ARE the fork)")
        seq = next(self._seq_ids)
        ticket = StreamTicket(f"stream{seq}", tenant)
        st = _Stream(seq, tenant, priority, prompt_tokens,
                     max_new_tokens, ticket, eos=eos, fork_from=parent)
        ticket._stream = st
        ticket._batcher = self
        with self._lock:
            if self._stop:
                # typed shed, same contract as server.submit: clients
                # catching AdmissionRejected to back off keep working
                # through the drain window
                from ..serve.admission import AdmissionRejected
                raise AdmissionRejected("llm batcher is stopped")
            self._pending.append(st)
        self._wake.set()
        return ticket

    def seed_tenant_knobs(self, tenant: str, knobs: dict) -> None:
        """Seed a tenant's adaptive start point from a persisted knob
        vector (the RuntimeServer per-tenant tuning-DB consult) —
        consumed when that tenant's controller is created lazily."""
        k = knobs.get("llm_steps_per_pool")
        if isinstance(k, (int, float)) and not isinstance(k, bool) \
                and k >= 1:
            self._k_seed[tenant] = int(k)

    def _tenant_k(self, tenant: str, k_max: int) -> int:
        """The tenant's pool depth this iteration: the global
        ``llm_steps_per_pool`` unless live adaptation is on, then the
        tenant's controller value (seeded from the tuning DB when a
        vector was stored).  Batcher thread only."""
        if not _params.get("tune_adaptive", False):
            return k_max
        ctl = self._k_ctl.get(tenant)
        if ctl is None:
            from ..tune.adaptive import steps_controller
            ctl = steps_controller(tenant, self._k_seed.get(tenant, k_max))
            self._k_ctl[tenant] = ctl
        return max(1, int(ctl.value))

    # -- placement hooks (serve/sharded.py) ------------------------------
    def residency_len(self, prompt_tokens) -> int:
        """How many leading TOKENS of a prospective prompt are already
        resident in this batcher's prefix trie (full pages only) — the
        KV-residency signal the sharded placement router maximizes.  0
        with the prefix cache off."""
        if self.prefix is None:
            return 0
        _seq, pages = self.prefix.match(list(prompt_tokens))
        return pages * self.kv.page_size

    def load(self) -> dict:
        """Live + queued stream counts — the sharded router's
        least-loaded fallback signal."""
        with self._lock:
            return {"live": len(self._live), "queued": len(self._pending)}

    def stats(self) -> dict:
        with self._lock:
            out = {
                "live_streams": len(self._live),
                "queued_streams": len(self._pending),
                "steps": self.steps,
                "tokens_generated": self.tokens_generated,
                "streams_completed": self.streams_completed,
                "decode_submits": self.decode_submits,
                "forked_streams": self.forked_streams,
                "prefill_tokens_total": self.prefill_tokens_total,
                "prefill_tokens_skipped": self.prefill_tokens_skipped,
                "spec_submits": self.spec_submits,
                "spec_tokens": self.spec_tokens,
                "spec_drafted": self.spec_drafted,
                "spec_drafts_accepted": self.spec_drafts_accepted,
            }
        if out["spec_drafted"]:
            out["spec_accept_rate"] = round(
                out["spec_drafts_accepted"] / out["spec_drafted"], 4)
        if out["spec_submits"]:
            out["spec_tokens_per_submit"] = round(
                out["spec_tokens"] / out["spec_submits"], 4)
        if self._k_ctl:
            out["adaptive_k"] = {t: c.stats()
                                 for t, c in self._k_ctl.items()}
        out["kv"] = self.kv.stats()
        out["tiers"] = self.tiers.stats()
        if self.prefix is not None:
            out["prefix"] = self.prefix.stats()
        return out

    def stop(self, timeout: float | None = 60.0) -> None:
        """Graceful: no new streams, finish the live ones, join.  On
        timeout the loop is aborted and leftover streams fail."""
        with self._lock:
            self._stop = True
        self._wake.set()
        self._thread.join(timeout)
        if self._thread.is_alive():
            self._abort = RuntimeError("batcher stop timed out")
            self._wake.set()
            self._thread.join(5.0)
        # fold the final counters into the process aggregate exactly
        # once, so runtime_report()["llm"] stays cumulative after this
        # batcher (and its server) are gone
        with _retired_lock:
            if not getattr(self, "_folded", False):
                self._folded = True
                _fold_stats(_retired_totals, self.stats())

    # -- the iteration loop ---------------------------------------------
    def _loop(self) -> None:
        try:
            while True:
                if self._abort is not None:
                    # checked BEFORE popping arrivals: _fail_all covers
                    # _live + _pending, so anything popped here would
                    # slip through with an unresolved ticket
                    self._fail_all(self._abort)
                    return
                with self._lock:
                    room = self.max_batch - len(self._live)
                    fresh = [self._pending.popleft()
                             for _ in range(min(room, len(self._pending)))]
                    live = list(self._live)
                    stopping = self._stop
                if not fresh and not live:
                    if stopping:
                        return
                    self._wake.wait(0.05)
                    self._wake.clear()
                    continue
                # chunked-prefill interleave (ISSUE 9): arrivals' prefill
                # pools are SUBMITTED first, the live streams' k-step
                # decode superpools run while prefill is in flight, and
                # only then are the prefill tickets awaited — a long
                # prompt overlaps a whole decode iteration instead of
                # stalling it.  Fresh streams join at the NEXT boundary.
                pf = self._prefill_submit(fresh) if fresh else None
                if live:
                    self._decode_step(live)
                if pf is not None:
                    ok = self._prefill_await(pf)
                    with self._lock:
                        self._live.extend(ok)
        except BaseException as e:      # noqa: BLE001 — fail the streams,
            self._fail_all(e)           # never leave clients blocked

    def _retire_failed(self, streams: list[_Stream], e: BaseException,
                       defer_pool: Any = None) -> None:
        """Contain a failure to the streams it actually hit: one tenant's
        shed pool (admission timeout), one stream's exhausted page budget
        — the OTHER tenants' streams keep decoding.

        ``defer_pool`` must be passed when the streams' pool may STILL BE
        RUNNING (a step-timeout: serve tickets cannot cancel a live DAG):
        freeing the KV pages immediately would hand them to a new stream
        while the zombie pool's OUT tasks can still write into them —
        the pages release only when that pool actually terminates (the
        listener fires immediately if it already has)."""
        with self._lock:
            for st in streams:
                if st in self._live:
                    self._live.remove(st)
        seqs = [st.seq for st in streams]
        for st in streams:
            st.ticket._fail(e)
        if defer_pool is None:
            for s in seqs:
                self._release_stream_state(s)
        else:
            defer_pool.add_completion_listener(
                lambda _tp: [self._release_stream_state(s) for s in seqs])

    def _release_stream_state(self, seq: Any) -> None:
        """Everything a retired sequence held: KV pages back to the free
        list, its Q/O side tiles and TOK chain tiles dropped — the
        serving footprint must be bounded by LIVE streams, not by every
        stream ever served.  Safe for a never-allocated seq (all
        no-ops)."""
        self.kv.free_seq(seq)
        self.Q.discard(seq)
        self.O.discard(seq)
        for coll in (self.TOK, self.QS, self.LIM, self.DTOKS,
                     self.VOUT):
            for key in coll.known_keys():
                if key and key[0] == seq:
                    coll.discard(*key)

    def _fail_all(self, e: BaseException) -> None:
        with self._lock:
            victims = self._live + list(self._pending)
            self._live = []
            self._pending.clear()
        for st in victims:
            st.ticket._fail(e)
            self._release_stream_state(st.seq)

    def _fork_ready(self, parent: _Stream) -> bool:
        """Whether a fork parent's cache is EXACTLY its prompt prefix
        (prefilled, not yet decoded) — the only window where forking the
        block table IS forking the prompt.  A retired parent is never
        ready even when its seq still exists: a FAILED parent's page
        release may be deferred behind a timed-out zombie pool that is
        still writing them, and the host-side ledger (advanced at chunk
        time, before any pool ran) cannot tell the difference."""
        if parent.ticket.done():
            return False
        try:
            return self.kv.seq_len(parent.seq) == len(parent.prompt) - 1
        except KeyError:                 # parent retired / never admitted
            return False

    def _admit_via_prefix(self, st: _Stream) -> int:
        """Materialize a fresh stream's sequence, through the prefix
        cache when enabled: the trie matches ``prompt[:-1]`` (the
        cacheable run) and forks the longest retained full-page prefix
        copy-on-write (``PagedKVCollection.fork_prefix``), so only the
        unmatched tail prefills.  Returns the number of pages reused
        (0 = miss or cache disabled — plain ``alloc_seq``)."""
        if self.prefix is None:
            self.kv.alloc_seq(st.seq)
            reused = 0
        else:
            reused = self.prefix.adopt(st.seq, st.prompt[:-1])
        cacheable = len(st.prompt) - 1
        skipped = reused * self.kv.page_size
        with self._lock:
            self.prefill_tokens_total += cacheable
            self.prefill_tokens_skipped += skipped
        if reused:
            st.ticket.prefix_pages_reused = reused
            if self._slo is not None:
                # the per-tenant cache-effectiveness counters (PR-10
                # SLO plane): operators read hit rates next to the TTFT
                # quantiles the hits are supposed to move
                self._slo.inc(st.tenant, "prefix_hits")
                self._slo.inc(st.tenant, "prefix_pages_reused", reused)
        return reused

    def _prefill_submit(self, fresh: list[_Stream]) -> dict:
        """Phase 1 of the chunked-prefill interleave: allocate pages and
        SUBMIT one PF pool per tenant, without awaiting — the caller
        runs the decode superpools while these are in flight.  An
        exhausted page budget fails ONE stream, a shed pool fails ONE
        tenant's arrivals, never the whole batch.  Fork-on-prompt
        children skip prefill entirely: a child of an already-admitted
        parent sitting at its prompt boundary forks HERE (before this
        iteration's decode can advance the parent); a child arriving in
        the same batch as its parent resolves in :meth:`_prefill_await`
        once the parent's pages are real."""
        stream_chunks: dict[Any, dict[tuple, np.ndarray]] = {}
        chunk_starts: dict[Any, int] = {}
        by_tenant: dict[str, list[_Stream]] = {}
        forks: list[_Stream] = []
        ok: list[_Stream] = []
        fresh_ids = {id(st) for st in fresh}
        for st in fresh:
            parent = st.fork_from
            if parent is not None and id(parent) in fresh_ids:
                # parent arrives in THIS batch: its pages are not real
                # until its PF pool completes — defer to _prefill_await
                st.ticket.state = "prefill"
                forks.append(st)
                continue
            if parent is not None and self._fork_ready(parent):
                # already-admitted parent sitting exactly at its prompt
                # boundary: fork NOW, before this iteration's decode
                # superpool advances it (the window that used to force
                # the fallback).  CoW keeps the snapshot honest — the
                # parent's next append privatizes ITS tail, the child
                # keeps the prompt pages.
                try:
                    self.kv.fork(parent.seq, st.seq)
                except BaseException as e:   # noqa: BLE001 — contain
                    self._retire_failed([st], e)
                    continue
                st.fork_from = None
                st.ticket.state = "prefill"
                with self._lock:
                    self.forked_streams += 1
                ok.append(st)
                continue
            st.fork_from = None          # parent advanced: plain prefill
            try:
                reused = self._admit_via_prefix(st)
                # tail-only prefill: chunk indices continue past the
                # trie-shared pages (prefill_chunks reads the page
                # count); a full-prefix hit leaves nothing to chunk
                stream_chunks[st.seq] = prefill_chunks(
                    self.model, self.kv, st.seq,
                    st.prompt[reused * self.kv.page_size:-1])
                chunk_starts[st.seq] = reused
            except BaseException as e:       # noqa: BLE001 — contain
                self._retire_failed([st], e)
                continue
            st.ticket.state = "prefill"
            by_tenant.setdefault(st.tenant, []).append(st)
        t0 = time.perf_counter()
        tickets: list[tuple[Any, Any, list[_Stream]]] = []
        done_t: dict[int, float] = {}
        for tenant, group in by_tenant.items():
            # only streams with tail chunks ride a PF pool: single-token
            # prompts cache nothing, and a FULL-prefix trie hit already
            # holds every cacheable page copy-on-write — both join the
            # batch with prefill_s = 0.0 instead of awaiting a pool
            ok.extend(st for st in group
                      if not stream_chunks.get(st.seq))
            group = [st for st in group if stream_chunks.get(st.seq)]
            seqs = [st.seq for st in group]
            if not seqs:
                continue
            # THIS group's chunks only: the T key space is what lowering
            # and operators may walk, so it must not declare other
            # tenants' (or failed streams') tiles
            chunks: dict[tuple, np.ndarray] = {}
            for st in group:
                chunks.update(stream_chunks.get(st.seq, {}))
            try:
                T = DictCollection(
                    f"llmT{next(self._pool_seq)}",
                    dtt=self.kv.default_dtt,
                    init_fn=lambda *k, _c=chunks: _c[k],
                    keys=list(chunks))
                tp = prefill_ptg(self.kv, T, seqs, devices=self.devices,
                                 name=f"llm_prefill{next(self._pool_seq)}",
                                 starts=[chunk_starts.get(s, 0)
                                         for s in seqs])
                # timestamp the pool's ACTUAL completion: the interleave
                # awaits only after the decode superpools, so awaiting
                # time would inflate prefill_s by a whole iteration
                tp.add_completion_listener(
                    lambda _tp, _d=done_t, _k=id(tp):
                    _d.setdefault(_k, time.perf_counter()))
                tickets.append((self._server.submit(
                    tp, tenant=tenant,
                    priority=max(st.priority for st in group)), tp, group))
            except BaseException as e:       # noqa: BLE001 — contain
                self._retire_failed(group, e)
        return {"t0": t0, "tickets": tickets, "ok": ok, "forks": forks,
                "fresh_ids": fresh_ids, "done_t": done_t}

    def _prefill_await(self, state: dict) -> list[_Stream]:
        """Phase 2: await the PF tickets, then resolve fork children —
        their parent's pages are real now, so ``PagedKVCollection.fork``
        shares them copy-on-write (no bytes move).  Returns the streams
        that join the live batch."""
        ok: list[_Stream] = list(state["ok"])
        for st in ok:
            # single-token prompts cache nothing; early (phase-1) forks
            # shared CoW — either way no bytes moved
            st.ticket.prefill_s = 0.0
        for tk, tp, group in state["tickets"]:
            try:
                tk.result(timeout=_params.get("llm_step_timeout"))
            except BaseException as e:       # noqa: BLE001 — contain
                # the pool may still be running past its timeout: page
                # release rides its completion, not this failure
                self._retire_failed(group, e, defer_pool=tp)
                continue
            # prefill cost = submit -> the pool's own completion stamp,
            # NOT this (post-decode) await instant
            dt = state["done_t"].get(
                id(tp), time.perf_counter()) - state["t0"]
            for st in group:
                st.ticket.prefill_s = dt
            ok.extend(group)
        ok_ids = {id(st) for st in ok}
        fallback: list[_Stream] = []
        for st in state["forks"]:
            parent = st.fork_from
            # deferred forks all have IN-BATCH parents (out-of-batch
            # parents forked at phase-1 classification), and an
            # in-batch parent must have actually COMPLETED its PF pool:
            # the host-side length ledger advances at chunk time,
            # BEFORE the pool runs, so _fork_ready alone cannot prove
            # the parent's pages hold real bytes (a timed-out PF pool
            # may still be writing them).  A miss takes the documented
            # silent fallback: the child re-prefills its own prompt
            # like any fresh stream.
            if not (id(parent) in ok_ids):
                st.fork_from = None
                fallback.append(st)
                continue
            try:
                self.kv.fork(parent.seq, st.seq)
            except BaseException as e:       # noqa: BLE001 — contain
                self._retire_failed([st], e)
                continue
            # never consulted post-fork: clearing it unpins the parent
            # _Stream chain (prompt, ticket, token lists) so footprint
            # stays bounded by LIVE streams even for fork-of-fork trees
            # whose leaf tickets clients keep alive
            st.fork_from = None
            st.ticket.prefill_s = 0.0     # CoW share: no bytes moved
            with self._lock:
                self.forked_streams += 1
            ok_ids.add(id(st))       # a fork of a fork resolves in order
            ok.append(st)
        if fallback:
            # fork_from is cleared, so the batch produces no new forks
            # and this recursion terminates after one level (and sets
            # the fallback streams' own prefill_s)
            ok.extend(self._prefill_await(self._prefill_submit(fallback)))
        for st in ok:
            st.ticket.state = "decoding"
        return ok

    def _maybe_lower_regions(self, tp: Any) -> Any:
        """Opt-in (``llm_lower_regions``): compile the superpool into
        megakernel regions (PR 8, ``ptg.lowering.lower_regions``) and
        submit the REGION pool instead — per-step XLA dispatches
        collapse into one jitted program per verified region, on top of
        the 1/k submit amortization.  The lowering cache and AOT warming
        (``scripts/warm_cache.sh llm_decode_k``) make repeat geometries
        compile-free; anything the lowering refuses runs the dynamic
        path unchanged."""
        if not _params.get("llm_lower_regions"):
            return tp
        from ..ptg.lowering import LoweringError, lower_regions
        try:
            plan = lower_regions(tp)
            plan.compile()
            table = plan.materialize_table()
            return plan.taskpool(table)
        except LoweringError:
            return tp

    def _spec_draft(self, st: _Stream, spec_cap: int,
                    adaptive: bool) -> list[int] | None:
        """Decide whether THIS stream's next superpool is speculative,
        and with what draft.  None = ride the non-speculative PR-9
        superpool (spec off, no remaining budget to draft into, the
        drafter has no proposal, or the adaptive controller converged
        the stream off).  A converged-off stream re-probes every
        ``_SPEC_PROBE_EVERY`` iterations with a 2-token draft and a
        neutral EWMA, so traffic that TURNS draftable is re-detected at
        a bounded (~3%) probe tax."""
        remaining = st.max_new - len(st.ticket.tokens)
        if spec_cap <= 0 or remaining <= 1:
            return None
        if st.drafter is None:
            # first speculative look at this stream: the drafter sees
            # every token the stream KEEPS, prompt first, then whatever
            # was already generated under non-speculative iterations —
            # the table tracks the true history whatever mode ran
            st.drafter = NgramDrafter()
            for t in st.prompt:
                st.drafter.observe(int(t))
            for t in st.ticket.tokens:
                st.drafter.observe(int(t))
        cap = min(spec_cap, remaining - 1)
        if adaptive:
            if st.spec_k < 0:
                # optimistic start at the cap — unless the tenant's
                # traffic already proved undraftable, then start OFF
                # (staggered so a tenant's probes don't align)
                prior = self._spec_prior.get(st.tenant)
                if prior is not None and prior < 0.35:
                    st.spec_k = 0
                    st.spec_probe = (hash(st.seq)
                                     % _SPEC_PROBE_EVERY)
                else:
                    st.spec_k = spec_cap
            if st.spec_k == 0:
                st.spec_probe += 1
                if st.spec_probe < _SPEC_PROBE_EVERY:
                    return None
                st.spec_probe = 0
                st.spec_k = 2
                st.spec_ewma = 0.5
            cap = min(cap, st.spec_k)
        if cap < 1:
            return None
        return st.drafter.draft(st.cur, cap) or None

    def _note_spec(self, st: _Stream, toks: list[int],
                   done: bool) -> None:
        """Fold one spec-superpool ride into the stream's adaptive
        controller and the serving counters/SLO plane.  An EOS finish
        scores 1.0 — the chain was cut by the stream, not by a draft
        miss — so a stream that dies mid-draft never punishes the
        drafter."""
        drafted = st.k - 1
        accepted = len(toks) - 1
        rate = 1.0 if done else accepted / max(1, drafted)
        st.spec_ewma = rate if st.spec_ewma < 0.0 else \
            0.5 * st.spec_ewma + 0.5 * rate
        prior = self._spec_prior.get(st.tenant)
        self._spec_prior[st.tenant] = rate if prior is None else \
            0.5 * prior + 0.5 * rate
        adaptive = bool(_params.get("llm_spec_adaptive"))
        spec_cap = max(0, int(_params.get("llm_spec_k")))
        if adaptive:
            # the live-adaptation shape the autotuning ROADMAP item
            # wants: double toward the cap while drafts land, halve to
            # (eventually) 0 = the non-speculative fallback while they
            # miss — convergence to either extreme takes ~3 pools
            if st.spec_ewma >= 0.6:
                st.spec_k = min(spec_cap, max(2, st.spec_k * 2))
            elif st.spec_ewma < 0.35:
                st.spec_k //= 2
        st.ticket.spec_k = st.spec_k if adaptive else spec_cap
        st.ticket.spec_accept_ewma = round(st.spec_ewma, 4)
        with self._lock:
            self.spec_submits += 1
            self.spec_tokens += len(toks)
            self.spec_drafted += drafted
            self.spec_drafts_accepted += accepted
        if self._slo is not None:
            # the PR-10 SLO plane's per-tenant speculative pair: how
            # often drafts land, and the tokens one submit yields —
            # read live via RuntimeServer.metrics() next to the
            # inter-token quantiles speculation is supposed to move
            self._slo.observe(st.tenant, "spec_accept_rate", rate)
            self._slo.observe(st.tenant, "spec_tokens_per_submit",
                              len(toks))

    def _collect_stream(self, st: _Stream, dt: float) -> bool:
        """Read ONE stream's tokens off its completed superpool and fold
        them into the ticket/ledger/SLO state; returns whether the
        stream finished (EOS or budget).  Speculative streams read the
        accepted prefix and roll their rejected tail back; plain
        streams read the TOK chain."""
        if st.spec:
            # only the accepted prefix surfaces — the SVERIFY body
            # killed the chain at the first draft mismatch (or a live
            # EOS) in-graph
            toks, done = read_spec_batched(self.VOUT, st.seq)
            # every position's k/v was staged into the tail slots at
            # seed time; the ledger advances by the FULL position
            # count, then the rejected tail rolls back (version-jump
            # truncation) so no stale KV survives into the next
            # superpool.  QS/LIM/DTOKS tiles are rewritten by the next
            # seed — they release with the stream, not per iteration
            self.kv.note_appended(st.seq, st.k)
            rejected = st.k - len(toks)
            if rejected:
                self.kv.rollback_tail(
                    st.seq, self.kv.seq_len(st.seq) - rejected)
            self._note_spec(st, toks, done)
        else:
            # tokens past a mid-superpool EOS are the predicated tail —
            # read_token_chain never surfaces them
            toks, done = read_token_chain(self.TOK, st.seq, st.k)
            for t_i in range(st.k):
                self.TOK.discard(st.seq, t_i)
            # the ledger advances by the FULL k: the OUT bodies
            # appended every step's k/v (predication holds tokens, not
            # appends), and a done stream's pages free anyway
            self.kv.note_appended(st.seq, st.k)
        if st.drafter is not None:
            # keep the table aligned with the true history whatever
            # mode this iteration ran, so spec can re-engage any time
            # (never-speculated streams catch up lazily in _spec_draft)
            for t_i in toks:
                st.drafter.observe(t_i)
        st.cur = toks[-1]
        if toks and not st.ticket.tokens:
            # the stream's first token closes its TTFT (the stamp is
            # what the bench prefix sweep quantiles)
            st.ticket.first_token_at = time.monotonic()
            if self._slo is not None:
                self._slo.observe(
                    st.tenant, "ttft_ms",
                    (st.ticket.first_token_at
                     - st.ticket.submitted_at) * 1e3)
        if toks:
            # every token samples the inter-token latency (this
            # iteration's wall amortized over its k tokens)
            tok_ms = dt / len(toks) * 1e3
            if self._slo is not None:
                for _ in toks:
                    self._slo.observe(st.tenant, "tok_latency_ms", tok_ms)
            ctl = self._k_ctl.get(st.tenant)
            if ctl is not None:
                # the adaptive plane folds the same signal; a converged
                # adoption persists to the tuning DB exactly once
                ctl.observe(tok_ms)
                wb = ctl.take_writeback()
                if wb is not None:
                    from ..tune import adaptive as _adaptive
                    _adaptive.writeback(st.tenant, wb,
                                        ctl.ewma_of(wb) or tok_ms)
        with self._lock:
            st.ticket.tokens.extend(toks)
            st.ticket.per_token_s.extend([dt] * len(toks))
            self.tokens_generated += len(toks)
        return done or len(st.ticket.tokens) >= st.max_new

    def _decode_step(self, live: list[_Stream]) -> None:
        """One continuous-batching iteration: ONE decode superpool per
        (tenant, mode) over its live streams — speculative draft-k-
        verify pools for streams whose drafter has a proposal (ISSUE
        12), the PR-9 k-step SAMPLE superpool for the rest, with k =
        ``llm_steps_per_pool`` clipped to each stream's remaining
        budget.  Sampling/verification runs in-graph, so the host reads
        a whole pool's tokens off the TOK/STOK chain tiles per submit;
        a spec stream's rejected tail is rolled back
        (``rollback_tail``) before its next pool.  Failures are
        contained per stream (slot allocation) or per tenant+mode (pool
        shed/failure) — the rest of the batch decodes on."""
        k_max = max(1, int(_params.get("llm_steps_per_pool")))
        spec_cap = max(0, int(_params.get("llm_spec_k")))
        spec_adaptive = bool(_params.get("llm_spec_adaptive"))
        if _params.get("llm_prefetch_ahead"):
            # the tier return path, ahead of the decode wavefront: pages
            # the PREVIOUS iteration's eviction pressure pushed to the
            # host tier stage back in asynchronously while this thread
            # does host-side prep (slot preallocation, seeding, pool
            # build) — an HBM budget below the working set costs
            # overlapped bandwidth instead of synchronous stage-in
            # stalls when the superpool dispatches.  Advisory: a
            # prefetch failure must never fail the batch (on-demand
            # stage-in still serves every page).
            try:
                n = self.tiers.prefetch_seqs([st.seq for st in live])
            except Exception:                # noqa: BLE001 — contain
                n = 0
            if n and self._slo is not None:
                self._slo.inc("_server", "kv_prefetched_pages", n)
        ready: list[_Stream] = []
        for st in live:
            draft = self._spec_draft(st, spec_cap, spec_adaptive)
            try:
                if draft is not None:
                    st.k = 1 + len(draft)
                    st.spec = True
                    # preallocate FIRST: the staged speculative slots
                    # must be private (CoW tails privatize here) before
                    # the seed writes the draft chain's k/v into them
                    preallocate_decode_steps(self.kv, st.seq, st.k)
                    seed_spec_batched(self.model, self.kv, self.QS,
                                      self.LIM, self.DTOKS, st.seq,
                                      st.cur, draft, spec_cap + 1,
                                      eos=st.eos)
                else:
                    st.k = max(1, min(self._tenant_k(st.tenant, k_max),
                                      st.max_new - len(st.ticket.tokens)))
                    st.spec = False
                    preallocate_decode_steps(self.kv, st.seq, st.k)
                    seed_stream_step(self.model, self.Q, self.TOK,
                                     st.seq, st.cur, eos=st.eos)
            except BaseException as e:       # noqa: BLE001 — contain
                self._retire_failed([st], e)
                continue
            ready.append(st)
        # one pool per (tenant, mode): spec and plain streams of a
        # tenant ride SEPARATE superpools in the same iteration (the
        # two graphs differ structurally; WFQ still arbitrates both
        # under the tenant's weight)
        by_group: dict[tuple[str, bool], list[_Stream]] = {}
        for st in ready:
            by_group.setdefault((st.tenant, st.spec), []).append(st)
        t0 = time.perf_counter()
        submitted: list[tuple[Any, Any, list[_Stream]]] = []
        for (tenant, spec), group in by_group.items():
            try:
                if spec:
                    tp = spec_batched_ptg(
                        self.kv, self.QS, self.LIM, self.DTOKS,
                        self.VOUT, self.EMB, [st.seq for st in group],
                        [st.k for st in group], pad=spec_cap + 1,
                        devices=self.devices,
                        name=f"llm_spec{next(self._pool_seq)}")
                else:
                    tp = decode_superpool_ptg(
                        self.kv, self.Q, self.O, self.TOK, self.EMB,
                        [st.seq for st in group], [st.k for st in group],
                        devices=self.devices,
                        name=f"llm_decode{next(self._pool_seq)}")
                tp = self._maybe_lower_regions(tp)
                submitted.append((self._server.submit(
                    tp, tenant=tenant,
                    priority=max(st.priority for st in group)),
                    tp, group))
                with self._lock:
                    self.decode_submits += 1
            except BaseException as e:       # noqa: BLE001 — contain
                self._retire_failed(group, e)
        finished: list[_Stream] = []
        for tk, tp, group in submitted:
            try:
                tk.result(timeout=_params.get("llm_step_timeout"))
            except BaseException as e:       # noqa: BLE001 — contain
                # the pool may still be running past its timeout: page
                # release rides its completion, not this failure
                self._retire_failed(group, e, defer_pool=tp)
                continue
            dt = time.perf_counter() - t0
            for st in group:
                try:
                    if self._collect_stream(st, dt):
                        finished.append(st)
                except BaseException as e:   # noqa: BLE001 — contain
                    # one stream's result/rollback failure (e.g. a
                    # rolled-back page spilled beyond the host tier)
                    # must fail THAT stream, not the batcher
                    self._retire_failed([st], e)
        with self._lock:
            self.steps += 1
            for st in finished:
                self._live.remove(st)
                self.streams_completed += 1
        for st in finished:
            if self.prefix is not None:
                # donate the prompt pages BEFORE free_seq: the trie's
                # retained fork (refcount++) is what keeps them out of
                # the recycle path.  Only cleanly-finished streams
                # donate — a failed stream's pages may be zombie-written
                # (and never reach this loop).  Donation is an
                # optimization: its failure must never fail the stream.
                try:
                    self.prefix.donate(st.seq, st.prompt)
                except Exception:        # noqa: BLE001 — contain
                    pass
            self._release_stream_state(st.seq)
            st.ticket._resolve()
