"""Ulysses-style all-to-all sequence<->head re-sharding.

SURVEY §5.7: the reference's primitive for axis swaps is the generic
redistribute taskpool (``redistribute.jdf``); on TPU the compiled
equivalent of "re-shard the sequence axis into the head axis" is a single
``lax.all_to_all`` over the sequence-parallel mesh axis — one ICI
all-to-all instead of a task graph.

With ``x: [b, n_local, h, d]`` sharded on ``sp`` over the sequence axis,
:func:`seq_to_heads` returns ``[b, n, h_local, d]`` sharded on ``sp`` over
heads — each device then holds *full sequences for a subset of heads*
(the DeepSpeed-Ulysses layout), so ordinary dense attention runs locally.
:func:`heads_to_seq` is the inverse.
"""

from __future__ import annotations

import jax
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P


def seq_to_heads_local(x, axis_name: str = "sp"):
    """[b, n_loc, h, d] -> [b, n, h/axis, d] (call under shard_map)."""
    return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                          tiled=True)


def heads_to_seq_local(x, axis_name: str = "sp"):
    """[b, n, h_loc, d] -> [b, n/axis, h, d] (call under shard_map)."""
    return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                          tiled=True)


def make_ulysses_attention(mesh: Mesh, attention_fn,
                           axis_name: str = "sp",
                           batch_axis: str | None = "dp"):
    """Sequence-parallel attention by head re-sharding: all-to-all the
    sharded sequence into sharded heads, run ``attention_fn(q, k, v)``
    densely per head group, all-to-all back.

    ``attention_fn`` operates on [b, h_group, n_full, d] — e.g.
    :func:`parsec_tpu.parallel.ring.dense_attention`.
    """
    seq_spec = P(batch_axis, None, axis_name, None)   # [b, h, n, d] on seq

    def local(q, k, v):
        # to head-sharded layout: [b, h, n, d] -> [b, n, h, d] for the
        # collective, then back
        def to_heads(t):
            t = t.transpose(0, 2, 1, 3)               # [b, n_loc, h, d]
            t = seq_to_heads_local(t, axis_name)      # [b, n, h_loc, d]
            return t.transpose(0, 2, 1, 3)            # [b, h_loc, n, d]

        def to_seq(t):
            t = t.transpose(0, 2, 1, 3)               # [b, n, h_loc, d]
            t = heads_to_seq_local(t, axis_name)      # [b, n_loc, h, d]
            return t.transpose(0, 2, 1, 3)            # [b, h, n_loc, d]

        return to_seq(attention_fn(to_heads(q), to_heads(k), to_heads(v)))

    fn = shard_map(local, mesh=mesh,
                   in_specs=(seq_spec, seq_spec, seq_spec),
                   out_specs=seq_spec, check_vma=False)
    return jax.jit(fn)
