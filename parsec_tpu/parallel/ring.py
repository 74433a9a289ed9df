"""Ring attention: sequence parallelism over the ICI ring.

The compiled (SPMD) realization of SURVEY §5.7's halo/ring dataflow: the
reference's closest structure is the 1-D stencil's neighbor exchange
(``tests/apps/stencil/stencil_1D.jdf:13-58``); for long-context attention
the same ring becomes blockwise KV rotation with online-softmax
accumulation (Ring Attention; the flash-attention recurrence distributed
over devices).

TPU-first design: ``shard_map`` over a ``sp`` mesh axis; each step computes
one [q-block × kv-block] attention partial on the MXU while
``lax.ppermute`` rotates the KV block to the next neighbor over ICI — XLA
overlaps the permute with the matmul, which is exactly the
communication/computation overlap the reference engineers by hand with
streams and MPI (SURVEY §3.4/§3.5).

Numerics: the online softmax keeps running (max, sum, out) per query row —
mathematically identical to dense softmax(QKᵀ)V up to float reassociation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

_NEG_INF = -1e30


def _block_attention(q, k, v, mask):
    """One [q-block, kv-block] partial: scores, max, exp-weights, pv.

    q: [b, h, nq, d]; k/v: [b, h, nk, d]; mask: [nq, nk] additive.
    Returns (scores_max [b,h,nq], p_sum [b,h,nq], pv [b,h,nq,d]).
    """
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    s = s + mask
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
    return m, l, pv


def ring_attention_local(q, k, v, axis_name: str = "sp",
                         causal: bool = False):
    """Per-shard ring attention body (call under ``shard_map``).

    q/k/v: [b, h, n_local, d] — the sequence axis is sharded over
    ``axis_name``.  Rotates KV blocks ``axis_size`` times; accumulates with
    the online-softmax recurrence.  Returns [b, h, n_local, d] (same
    sharding as q).
    """
    n_dev = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    b, h, n_loc, d = q.shape
    q_pos = my * n_loc + jnp.arange(n_loc)

    def accumulate(acc, t, k_blk, v_blk):
        o, m, l = acc
        src = (my - t) % n_dev                   # block currently held
        if causal:
            k_pos = src * n_loc + jnp.arange(n_loc)
            mask = jnp.where(q_pos[:, None] >= k_pos[None, :], 0.0,
                             _NEG_INF).astype(jnp.float32)
        else:
            mask = jnp.zeros((n_loc, n_loc), jnp.float32)
        bm, bl, bpv = _block_attention(q, k_blk, v_blk, mask)
        m_new = jnp.maximum(m, bm)
        corr = jnp.exp(m - m_new)
        bcorr = jnp.exp(bm - m_new)
        l = l * corr + bl * bcorr
        o = o * corr[..., None] + bpv * bcorr[..., None]
        return (o, m_new, l)

    # t = 0: own block, no rotation yet
    acc0 = (jnp.zeros((b, h, n_loc, d), jnp.float32),
            jnp.full((b, h, n_loc), _NEG_INF, jnp.float32),
            jnp.zeros((b, h, n_loc), jnp.float32))
    acc0 = accumulate(acc0, 0, k, v)

    def step(carry, t):
        o, m, l, k_blk, v_blk = carry
        # rotate KV to the next neighbor first (receive from the previous):
        # after t rotations we hold block (my - t) % n_dev — rotating at
        # the top of the body gives exactly n_dev-1 permutes total
        perm = [(j, (j + 1) % n_dev) for j in range(n_dev)]
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        o, m, l = accumulate((o, m, l), t, k_blk, v_blk)
        return (o, m, l, k_blk, v_blk), None

    (o, m, l, _, _), _ = lax.scan(step, acc0 + (k, v),
                                  jnp.arange(1, n_dev))
    # rows with no visible keys (can't happen for causal with t>=1) keep l=0
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def make_ring_attention(mesh: Mesh, axis_name: str = "sp",
                        causal: bool = False, batch_axis: str | None = "dp",
                        head_axis: str | None = "tp"):
    """Jitted ring attention over ``mesh``: q/k/v [b, h, n, d] with batch
    sharded on ``batch_axis``, heads on ``head_axis``, sequence on
    ``axis_name``."""
    spec = P(batch_axis, head_axis, axis_name, None)

    fn = shard_map(
        functools.partial(ring_attention_local, axis_name=axis_name,
                          causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return jax.jit(fn)


def dense_attention(q, k, v, causal: bool = False):
    """Reference dense softmax attention (correctness oracle)."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        n = q.shape[2]
        mask = jnp.where(jnp.tril(jnp.ones((n, n), bool)), 0.0, _NEG_INF)
        s = s + mask
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)
