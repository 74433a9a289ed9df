"""Ragged paged-attention kernels: the LLM decode incarnations.

The per-page online-softmax update at the heart of the decode task class
(``parsec_tpu/llm/decode.py``), in two incarnations sharing one math:

- :func:`attn_page_update_np` / :func:`attn_out_np` — plain numpy, the
  CPU task bodies (fast for the host-dispatched dynamic path: no tracing
  per task);
- jnp twins, registered as **traceables** under ``"ragged_attn_page"`` /
  ``"ragged_attn_out"`` so the PR-2 fused same-class dispatch can batch
  every live sequence's decode task into ONE XLA call — page shapes are
  uniform by construction (the fill count rides inside the page tensor,
  :mod:`parsec_tpu.data_dist.paged_kv`), which is exactly what makes the
  ragged batch uniform.  The device bodies resolve through the lazy
  kernel registry (``device/kernels.py``).

The accumulator tile is ``(H, D+2)``: columns ``[:D]`` the unnormalized
weighted value sum, ``[D]`` the running max, ``[D+1]`` the running
softmax denominator (flash-attention state).  ``l == 0`` encodes the
empty accumulator (zeros-init NEW tiles work unchanged).
"""

from __future__ import annotations

from typing import Any

import numpy as np

from ..device.kernels import register_kernel, register_lazy_kernel
from ..ptg.lowering import register_traceable

NEG_INF = -1e30          # finite sentinel: exp(x - m) underflows to 0.0


# ---------------------------------------------------------------------------
# numpy incarnations (CPU task bodies)
# ---------------------------------------------------------------------------

def attn_page_update_np(q3: np.ndarray, page: np.ndarray,
                        acc: np.ndarray) -> np.ndarray:
    """Online-softmax update of one query against one KV page.

    ``q3``: ``(3, H, D)`` — channel 0 the query (1,2 carry the token's
    k/v for the append stage, unused here); ``page``: ``(3, P, H, D)``
    K/V/meta with ``page[2,0,0,0]`` the fill count; ``acc``: ``(H, D+2)``.
    """
    H, Dp2 = acc.shape
    D = Dp2 - 2
    fill = int(page[2, 0, 0, 0])
    if fill <= 0:
        # empty page: nothing to fold in — the masked math below would
        # produce exactly acc (weights all zero), so skip the whole pass
        return np.array(acc, np.float32, copy=True)
    q = np.asarray(q3[0], np.float32)
    # slice to the filled slots instead of masking the whole page: the
    # invalid rows would get weight 0 anyway, and this body runs once
    # per (task, page) on the serving hot path — einsum's argument
    # parsing alone costs more than the contraction at decode tile sizes
    k = np.asarray(page[0][:fill], np.float32)
    v = np.asarray(page[1][:fill], np.float32)
    scores = (k * q).sum(axis=2) / np.sqrt(D)                # (fill, H)
    l_prev = acc[:, D + 1]
    m_prev = np.where(l_prev > 0, acc[:, D], NEG_INF)
    m_new = np.maximum(m_prev, scores.max(axis=0))
    w = np.exp(scores - m_new[None, :])
    alpha = np.exp(m_prev - m_new)                           # <= 1
    out = np.empty((H, Dp2), np.float32)
    out[:, :D] = acc[:, :D] * alpha[:, None] + (w[:, :, None] * v).sum(axis=0)
    out[:, D] = m_new
    out[:, D + 1] = l_prev * alpha + w.sum(axis=0)
    return out


def finalize_acc_np(acc: np.ndarray) -> np.ndarray:
    """Normalize the flash state to the attention output ``(H, D)``;
    an empty cache (``l == 0``) yields zeros, not NaN."""
    D = acc.shape[1] - 2
    l = acc[:, D + 1]
    return np.where((l > 0)[:, None],
                    acc[:, :D] / np.maximum(l, 1e-30)[:, None],
                    0.0).astype(np.float32)


def attn_out_np(acc: np.ndarray, q3: np.ndarray,
                page: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """The decode epilog: finalize the attention output and append the
    query token's k/v into the tail page at its fill slot.  Returns
    ``(new_page, o)`` — a fresh page array (the home copy may still be
    snapshotted by a reader)."""
    o = finalize_acc_np(acc)
    page = np.array(page, copy=True)
    fill = int(page[2, 0, 0, 0])
    page[0, fill] = q3[1]
    page[1, fill] = q3[2]
    page[2, 0, 0, 0] = fill + 1
    return page, o


def sample_step_np(o: np.ndarray, tok_prev: np.ndarray,
                   q3t: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """The in-graph SAMPLE body: greedy argmax of ``o · E^T`` plus the
    next step's query stack — the host's per-token work (ToyLM.sample +
    q3) moved inside the decode DAG so a k-step superpool never
    re-enters the host loop between tokens (ISSUE 9).

    ``q3t``: the model's precomputed ``(vocab, 3, H, D)`` q/k/v stack
    table (:meth:`ToyLM.q3_table` — channel 0 IS the embedding, so
    logits are ``q3t[:, 0] · o`` and the next query is one gather).
    ``tok_prev``: the ``(3,)`` token-chain tile ``[token, done, eos]``
    threading step t-1 → t (``eos < 0`` disables EOS).  A stream that
    already finished (``done``) holds its token — the predicated step
    body: the remaining tasks run but change nothing, so a mid-superpool
    EOS wastes at most the stream's own tail tasks.  Returns
    ``(tok_tile, q3_next)``.
    """
    V = q3t.shape[0]
    done_p = bool(tok_prev[1] > 0.5)
    eos = float(tok_prev[2])
    logits = q3t[:, 0].reshape(V, -1) @ np.asarray(
        o, np.float32).reshape(-1)
    samp = float(np.argmax(logits))
    tok = float(tok_prev[0]) if done_p else samp
    done = 1.0 if (done_p or (eos >= 0.0 and tok == eos)) else 0.0
    return (np.array([tok, done, eos], np.float32),
            q3t[int(tok) % V])


def verify_step_np(o: np.ndarray, st_prev: np.ndarray, dtok: np.ndarray,
                   q3t: np.ndarray) -> np.ndarray:
    """The in-graph VERIFY body (ISSUE 12): one speculative position's
    accept-or-reject decision, threading the accept state st-1 → st the
    way SAMPLE threads the token chain.

    The speculative superpool attends every draft position's query in
    parallel (all queries are known at build time — the drafter proposed
    them), so acceptance is decided AFTER the fact: position t's query
    (draft token ``dtok``) was correct iff the PREVIOUS position's
    emitted token equals it.  The state tile is ``(4,)``
    ``[token, live, done, eos]`` — ``live`` means this position emitted
    a surfaced token; a rejection (or an EOS at a live position) clears
    ``live`` for every later position, so the rejected branch's tail
    tasks run but change nothing — the PR-9 EOS predication shape.
    Seed at t=-1: ``[cur, 1, 0, eos]`` (position 0's query IS the real
    current token, so it always stays live).

    A dead position holds the prior state verbatim (its computed token
    is never examined), which is what makes an EOS *inside a rejected
    draft branch* invisible: only live positions can finish the stream.
    """
    V = q3t.shape[0]
    tok_prev, live_p, done_p, eos = (float(st_prev[0]), st_prev[1] > 0.5,
                                     st_prev[2] > 0.5, float(st_prev[3]))
    alive = bool(live_p) and not bool(done_p) \
        and tok_prev == float(dtok.reshape(-1)[0])
    if not alive:
        return np.array([tok_prev, 0.0, 1.0 if done_p else 0.0, eos],
                        np.float32)
    logits = q3t[:, 0].reshape(V, -1) @ np.asarray(
        o, np.float32).reshape(-1)
    tok = float(np.argmax(logits))
    done = 1.0 if (eos >= 0.0 and tok == eos) else 0.0
    return np.array([tok, 1.0, done, eos], np.float32)


def _verify_jnp(o: Any, st_prev: Any, dtok: Any, q3t: Any) -> Any:
    """jnp twin of :func:`verify_step_np` — branchless (``jnp.where``)
    so the region lowering and fused same-class dispatch batch every
    stream's VERIFY chain the way they batch SAMPLE."""
    import jax.numpy as jnp
    V = q3t.shape[0]
    st_prev = jnp.asarray(st_prev, jnp.float32)
    tok_prev, eos = st_prev[0], st_prev[3]
    live_p = st_prev[1] > 0.5
    done_p = st_prev[2] > 0.5
    alive = live_p & ~done_p & (tok_prev == jnp.asarray(
        dtok, jnp.float32).reshape(-1)[0])
    logits = q3t[:, 0].reshape(V, -1).astype(jnp.float32) @ jnp.asarray(
        o, jnp.float32).reshape(-1)
    samp = jnp.argmax(logits).astype(jnp.float32)
    tok = jnp.where(alive, samp, tok_prev)
    done = jnp.where(jnp.where(alive, (eos >= 0.0) & (samp == eos),
                               done_p), 1.0, 0.0)
    live = jnp.where(alive, 1.0, 0.0)
    return jnp.stack([tok, live, done, eos]).astype(jnp.float32)


def spec_attn_page_np(qs: np.ndarray, page: np.ndarray, lim: np.ndarray,
                      acc: np.ndarray) -> np.ndarray:
    """The BATCHED speculative incarnation (ISSUE 12): every draft
    position's query against one KV page in ONE body — "the verify pass
    is just one more batched ragged-attention call over the paged KV".

    ``qs``: ``(S, 3, H, D)`` — channel 0 of row t is position t's query
    (padded rows are zeros); ``page``: ``(3, P, H, D)``; ``lim``:
    ``(S,)`` — position t's VALID SLOT COUNT on this page
    (``clip(L0 + t - p*P, 0, P)``, 0 for padded rows), the causal mask
    that replaces the in-tensor fill count: position t must see the
    speculative appends of positions < t and nothing later, and the
    host pre-staged ALL positions' k/v into the tail slots at seed
    time; ``acc``: ``(S, H, D+2)`` flash state per position.

    One ``(P,H,D)x(S,H,D)`` contraction instead of S single-query
    bodies — the task count per token collapses from ~1 per (position,
    page) to ~1 per page, which is what makes speculation a throughput
    win on the host-dispatched path too (the per-position pool wins the
    same way only through fused same-class device dispatch)."""
    S, H, Dp2 = acc.shape
    D = Dp2 - 2
    lim = np.asarray(lim, np.float32)
    # slice to the deepest valid slot instead of contracting the whole
    # page — same rationale as attn_page_update_np's fill slicing: a
    # tail page holding 1-2 valid slots runs once per (stream, page)
    # on the serving hot path, and the masked rows would get weight 0
    # anyway (per-position causal limits still apply via the mask)
    P = int(lim.max())
    if P <= 0:
        # nothing valid for ANY position: the masked math would return
        # exactly acc (the single-query body's empty-page early return)
        return np.array(acc, np.float32, copy=True)
    q = np.asarray(qs[:, 0], np.float32)                      # (S, H, D)
    k = np.asarray(page[0][:P], np.float32)                   # (P, H, D)
    v = np.asarray(page[1][:P], np.float32)
    scores = np.einsum("phd,shd->sph", k, q) / np.sqrt(D)     # (S, P, H)
    valid = (np.arange(P)[None, :] < lim[:, None])            # (S, P)
    scores = np.where(valid[:, :, None], scores, NEG_INF)
    l_prev = acc[:, :, D + 1]                                 # (S, H)
    m_prev = np.where(l_prev > 0, acc[:, :, D], NEG_INF)
    m_new = np.maximum(m_prev, scores.max(axis=1))
    w = np.where(valid[:, :, None],
                 np.exp(scores - m_new[:, None, :]), 0.0)     # (S, P, H)
    alpha = np.exp(m_prev - m_new)                            # (S, H)
    out = np.empty((S, H, Dp2), np.float32)
    out[:, :, :D] = (acc[:, :, :D] * alpha[:, :, None]
                     + np.einsum("sph,phd->shd", w, v))
    out[:, :, D] = m_new
    out[:, :, D + 1] = l_prev * alpha + w.sum(axis=1)
    return out


def _spec_attn_page_jnp(qs: Any, page: Any, lim: Any, acc: Any) -> Any:
    import jax.numpy as jnp
    D = acc.shape[2] - 2
    P = page.shape[1]
    q = qs[:, 0].astype(jnp.float32)
    k = page[0].astype(jnp.float32)
    v = page[1].astype(jnp.float32)
    scores = jnp.einsum("phd,shd->sph", k, q) / jnp.sqrt(jnp.float32(D))
    valid = (jnp.arange(P)[None, :]
             < jnp.asarray(lim, jnp.float32)[:, None])
    scores = jnp.where(valid[:, :, None], scores, NEG_INF)
    l_prev = acc[:, :, D + 1]
    m_prev = jnp.where(l_prev > 0, acc[:, :, D], NEG_INF)
    m_new = jnp.maximum(m_prev, scores.max(axis=1))
    w = jnp.where(valid[:, :, None],
                  jnp.exp(scores - m_new[:, None, :]), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    o = (acc[:, :, :D] * alpha[:, :, None]
         + jnp.einsum("sph,phd->shd", w, v))
    return jnp.concatenate(
        [o, m_new[:, :, None], (l_prev * alpha + w.sum(axis=1))[:, :, None]],
        axis=2).astype(jnp.float32)


def spec_verify_np(acc: np.ndarray, dtoks: np.ndarray,
                   q3t: np.ndarray) -> np.ndarray:
    """The batched VERIFY epilog: finalize every position's attention
    output, sample the target's token per position, and compute the
    accepted prefix — one body per stream per spec superpool.

    ``dtoks``: ``(S+2,)`` ``[n, eos, chain_0..chain_{S-1}, pad]`` with
    ``chain_0`` the real current token and ``chain_1..`` the drafts
    (``eos < 0`` disables EOS).  Position i's query was correct iff
    ``chain_i`` equals the TARGET's token at position i-1 (``chain_0``
    always is), so the emitted tokens are a PREFIX: the target tokens
    up to the first draft mismatch, truncated at a live EOS — an EOS
    the target would sample inside a rejected branch is dead state and
    never finishes the stream.  Returns ``(S+2,)``
    ``[n_emit, done, tok_0..tok_{n_emit-1}, 0 pad]``."""
    S = acc.shape[0]
    V = q3t.shape[0]
    D = acc.shape[2] - 2
    n = int(round(float(dtoks[0])))
    eos = float(dtoks[1])
    l = acc[:, :, D + 1]
    o = np.where((l > 0)[:, :, None],
                 acc[:, :, :D] / np.maximum(l, 1e-30)[:, :, None],
                 0.0).astype(np.float32)                      # (S, H, D)
    logits = o.reshape(S, -1) @ q3t[:, 0].reshape(V, -1).T    # (S, V)
    tgt = np.argmax(logits, axis=1).astype(np.float64)        # (S,)
    out = np.zeros(S + 2, np.float32)
    m = 0
    done = False
    for i in range(n):
        if i > 0 and float(dtoks[2 + i]) != tgt[i - 1]:
            break                                   # first draft mismatch
        out[2 + m] = tgt[i]
        m += 1
        if eos >= 0.0 and tgt[i] == eos:
            done = True                             # live EOS: stop HERE
            break
    out[0] = m
    out[1] = 1.0 if done else 0.0
    return out


def _spec_verify_jnp(acc: Any, dtoks: Any, q3t: Any,
                     vout_scratch: Any = None) -> Any:
    """Branchless jnp twin of :func:`spec_verify_np`: the emitted set is
    always a prefix (accept is a running AND, EOS-kill keeps a prefix),
    so compaction is a mask — no gather/scatter."""
    import jax.numpy as jnp
    S = acc.shape[0]
    V = q3t.shape[0]
    D = acc.shape[2] - 2
    dtoks = jnp.asarray(dtoks, jnp.float32)
    n = dtoks[0]
    eos = dtoks[1]
    chain = dtoks[2:2 + S]
    l = acc[:, :, D + 1]
    o = jnp.where((l > 0)[:, :, None],
                  acc[:, :, :D] / jnp.maximum(l, 1e-30)[:, :, None], 0.0)
    logits = o.reshape(S, -1).astype(jnp.float32) @ \
        q3t[:, 0].reshape(V, -1).astype(jnp.float32).T
    tgt = jnp.argmax(logits, axis=1).astype(jnp.float32)
    idx = jnp.arange(S)
    prev_tgt = jnp.concatenate([chain[:1], tgt[:-1]])
    match = (chain == prev_tgt) & (idx < n)
    live = jnp.cumprod(match.astype(jnp.int32)) > 0
    is_eos = live & (eos >= 0.0) & (tgt == eos)
    cs = jnp.cumsum(is_eos.astype(jnp.int32))
    emit = live & ((cs - is_eos.astype(jnp.int32)) == 0)
    m = emit.sum()
    toks = jnp.where(emit, tgt, 0.0)
    return jnp.concatenate(
        [jnp.stack([m.astype(jnp.float32),
                    jnp.where(is_eos.any(), 1.0, 0.0)]),
         toks]).astype(jnp.float32)


def _sample_jnp(o: Any, tok_prev: Any, q3t: Any,
                qn_scratch: Any = None) -> Any:
    """jnp twin of :func:`sample_step_np` — the traceable incarnation the
    region lowering and the fused same-class dispatch batch over
    (``qn_scratch`` is the QN flow's zeros tile, unused — flow-order
    contract, like ``_out_update_jnp``'s ``o_scratch``)."""
    import jax.numpy as jnp
    V = q3t.shape[0]
    tok_prev = jnp.asarray(tok_prev, jnp.float32)
    done_p = tok_prev[1] > 0.5
    eos = tok_prev[2]
    logits = q3t[:, 0].reshape(V, -1).astype(jnp.float32) @ jnp.asarray(
        o, jnp.float32).reshape(-1)
    samp = jnp.argmax(logits).astype(jnp.float32)
    tok = jnp.where(done_p, tok_prev[0], samp)
    done = jnp.where(done_p | ((eos >= 0.0) & (tok == eos)), 1.0, 0.0)
    qn = q3t[tok.astype(jnp.int32) % V]
    return (jnp.stack([tok, done, eos]).astype(jnp.float32),
            qn.astype(jnp.float32))


def ragged_attention_reference(q: np.ndarray, ks: np.ndarray,
                               vs: np.ndarray) -> np.ndarray:
    """Dense single-shot oracle: softmax(q·K/sqrt(D))·V over an
    unpaginated cache — what the paged online-softmax chain must equal."""
    q = np.asarray(q, np.float64)
    if len(ks) == 0:
        return np.zeros_like(q, dtype=np.float32)
    ks = np.asarray(ks, np.float64)
    vs = np.asarray(vs, np.float64)
    scores = np.einsum("nhd,hd->nh", ks, q) / np.sqrt(q.shape[-1])
    scores -= scores.max(axis=0, keepdims=True)
    w = np.exp(scores)
    w /= w.sum(axis=0, keepdims=True)
    return np.einsum("nh,nhd->hd", w, vs).astype(np.float32)


# ---------------------------------------------------------------------------
# jnp twins: traceables (fused same-class batching) + device bodies
# ---------------------------------------------------------------------------

def _page_update_jnp(q3: Any, page: Any, acc: Any) -> Any:
    import jax.numpy as jnp
    D = acc.shape[1] - 2
    P = page.shape[1]
    q = q3[0].astype(jnp.float32)
    k = page[0].astype(jnp.float32)
    v = page[1].astype(jnp.float32)
    fill = page[2, 0, 0, 0]
    scores = jnp.einsum("phd,hd->ph", k, q) / jnp.sqrt(jnp.float32(D))
    valid = (jnp.arange(P) < fill)[:, None]
    scores = jnp.where(valid, scores, NEG_INF)
    l_prev = acc[:, D + 1]
    m_prev = jnp.where(l_prev > 0, acc[:, D], NEG_INF)
    m_new = jnp.maximum(m_prev, scores.max(axis=0))
    w = jnp.where(valid, jnp.exp(scores - m_new[None, :]), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    o = acc[:, :D] * alpha[:, None] + jnp.einsum("ph,phd->hd", w, v)
    return jnp.concatenate(
        [o, m_new[:, None], (l_prev * alpha + w.sum(axis=0))[:, None]],
        axis=1).astype(jnp.float32)


def _out_update_jnp(acc: Any, q3: Any, page: Any, o_scratch: Any) -> Any:
    import jax.numpy as jnp
    acc, page = jnp.asarray(acc), jnp.asarray(page)
    D = acc.shape[1] - 2
    l = acc[:, D + 1]
    o = jnp.where((l > 0)[:, None],
                  acc[:, :D] / jnp.maximum(l, 1e-30)[:, None], 0.0)
    fill = page[2, 0, 0, 0].astype(jnp.int32)
    page = page.at[0, fill].set(q3[1]).at[1, fill].set(q3[2])
    page = page.at[2, 0, 0, 0].set((fill + 1).astype(page.dtype))
    return page, o.astype(jnp.float32)


def _prefill_copy_jnp(chunk: Any, page: Any) -> Any:
    """PF: the page's new contents ARE the prompt chunk tile.  Trivial
    on purpose — registering it is what makes the prefill pool
    lowerable/warmable (``llm_prefill_tail``, ISSUE 11) and lets the
    device tier batch PF tasks like any other class."""
    import jax.numpy as jnp
    del page
    return jnp.asarray(chunk)


register_traceable("ragged_attn_page", _page_update_jnp)
register_traceable("ragged_attn_out", _out_update_jnp)
register_traceable("llm_sample", _sample_jnp)
register_traceable("llm_verify", _verify_jnp)
register_traceable("llm_spec_attn", _spec_attn_page_jnp)
register_traceable("llm_spec_verify", _spec_verify_jnp)
register_traceable("llm_prefill_copy", _prefill_copy_jnp)


# ---------------------------------------------------------------------------
# device bodies, resolved lazily (register_lazy_kernel: the loaders only
# build jits on the first real dispatch)
# ---------------------------------------------------------------------------

def _load_page_body() -> Any:
    import jax
    fn = jax.jit(_page_update_jnp)

    def body(es: Any, task: Any, device: Any) -> Any:
        acc = task.data[2]
        acc.value = fn(task.data[0].value, task.data[1].value, acc.value)
        acc.version += 1
        return acc.value

    return body


def _load_out_body() -> Any:
    import jax
    fn = jax.jit(_out_update_jnp)

    def body(es: Any, task: Any, device: Any) -> Any:
        kvw, o = task.data[2], task.data[3]
        new_page, out = fn(task.data[0].value, task.data[1].value,
                           kvw.value, o.value)
        kvw.value = new_page
        kvw.version += 1
        o.value = out
        o.version += 1
        return out

    return body


def _load_sample_body() -> Any:
    import jax
    fn = jax.jit(_sample_jnp)

    def body(es: Any, task: Any, device: Any) -> Any:
        # flow order: O, TOK, EMB, QN (llm/decode.py decode_superpool_ptg)
        tok, qn = task.data[1], task.data[3]
        tok_new, qn_new = fn(task.data[0].value, tok.value,
                             task.data[2].value, qn.value)
        tok.value = tok_new
        tok.version += 1
        qn.value = qn_new
        qn.version += 1
        return tok_new

    return body


def _load_verify_body() -> Any:
    import jax
    fn = jax.jit(_verify_jnp)

    def body(es: Any, task: Any, device: Any) -> Any:
        # flow order: O, STOK, DTOK, EMB (llm/decode.py spec_superpool_ptg)
        st = task.data[1]
        st.value = fn(task.data[0].value, st.value,
                      task.data[2].value, task.data[3].value)
        st.version += 1
        return st.value

    return body


def _load_spec_attn_body() -> Any:
    import jax
    fn = jax.jit(_spec_attn_page_jnp)

    def body(es: Any, task: Any, device: Any) -> Any:
        # flow order: QS, KV, LIM, ACC (llm/decode.py spec_batched_ptg)
        acc = task.data[3]
        acc.value = fn(task.data[0].value, task.data[1].value,
                       task.data[2].value, acc.value)
        acc.version += 1
        return acc.value

    return body


def _load_spec_verify_body() -> Any:
    import jax
    fn = jax.jit(_spec_verify_jnp)

    def body(es: Any, task: Any, device: Any) -> Any:
        # flow order: ACC, DTOKS, EMB, VOUT
        vout = task.data[3]
        vout.value = fn(task.data[0].value, task.data[1].value,
                       task.data[2].value, vout.value)
        vout.version += 1
        return vout.value

    return body


def _load_prefill_body() -> Any:
    def body(es: Any, task: Any, device: Any) -> Any:
        # flow order: T, KV (llm/decode.py prefill_ptg).  Device arrays
        # are immutable, so aliasing the staged chunk tile is safe.
        kvw = task.data[1]
        kvw.value = task.data[0].value
        kvw.version += 1
        return kvw.value

    return body


register_lazy_kernel("ragged_attn_page", "tpu", _load_page_body)
register_lazy_kernel("ragged_attn_out", "tpu", _load_out_body)
register_lazy_kernel("llm_sample", "tpu", _load_sample_body)
register_lazy_kernel("llm_verify", "tpu", _load_verify_body)
register_lazy_kernel("llm_spec_attn", "tpu", _load_spec_attn_body)
register_lazy_kernel("llm_spec_verify", "tpu", _load_spec_verify_body)
register_lazy_kernel("llm_prefill_copy", "tpu", _load_prefill_body)


# CPU dyld entries (DTD bodies may name them; the PTG pools attach the
# numpy bodies directly)

def _page_body_cpu(es: Any, task: Any) -> None:
    acc = task.data[2]
    acc.value = attn_page_update_np(np.asarray(task.data[0].value),
                                    np.asarray(task.data[1].value),
                                    np.asarray(acc.value))
    acc.version += 1


def _out_body_cpu(es: Any, task: Any) -> None:
    kvw, o = task.data[2], task.data[3]
    new_page, out = attn_out_np(np.asarray(task.data[0].value),
                                np.asarray(task.data[1].value),
                                np.asarray(kvw.value))
    kvw.value = new_page
    kvw.version += 1
    o.value = out
    o.version += 1


def _sample_body_cpu(es: Any, task: Any) -> None:
    tok, qn = task.data[1], task.data[3]
    tok_new, qn_new = sample_step_np(np.asarray(task.data[0].value),
                                     np.asarray(tok.value),
                                     np.asarray(task.data[2].value))
    tok.value = tok_new
    tok.version += 1
    qn.value = qn_new
    qn.version += 1


def _verify_body_cpu(es: Any, task: Any) -> None:
    st = task.data[1]
    st.value = verify_step_np(np.asarray(task.data[0].value),
                              np.asarray(st.value),
                              np.asarray(task.data[2].value),
                              np.asarray(task.data[3].value))
    st.version += 1


def _spec_attn_body_cpu(es: Any, task: Any) -> None:
    acc = task.data[3]
    acc.value = spec_attn_page_np(np.asarray(task.data[0].value),
                                  np.asarray(task.data[1].value),
                                  np.asarray(task.data[2].value),
                                  np.asarray(acc.value))
    acc.version += 1


def _spec_verify_body_cpu(es: Any, task: Any) -> None:
    vout = task.data[3]
    vout.value = spec_verify_np(np.asarray(task.data[0].value),
                                np.asarray(task.data[1].value),
                                np.asarray(task.data[2].value))
    vout.version += 1


def _prefill_body_cpu(es: Any, task: Any) -> None:
    kvw = task.data[1]
    kvw.value = np.array(np.asarray(task.data[0].value), copy=True)
    kvw.version += 1


register_kernel("ragged_attn_page", "cpu", _page_body_cpu)
register_kernel("ragged_attn_out", "cpu", _out_body_cpu)
register_kernel("llm_sample", "cpu", _sample_body_cpu)
register_kernel("llm_verify", "cpu", _verify_body_cpu)
register_kernel("llm_spec_attn", "cpu", _spec_attn_body_cpu)
register_kernel("llm_spec_verify", "cpu", _spec_verify_body_cpu)
register_kernel("llm_prefill_copy", "cpu", _prefill_body_cpu)
