"""Composed distributed training steps — the parallelism-pack showcase.

SURVEY §2.12 requires DP/TP/PP/SP/EP to be first-class derived schedules.
This module provides the *compiled* (SPMD) realization: training steps
jitted over a ``jax.sharding.Mesh`` via ``shard_map``, with XLA collectives
riding ICI.  The dynamic-runtime realization of the same patterns (halo/ring
PTG taskpools, redistribute) lives beside it in this package.

Two steps:

- :func:`make_train_step` — dp × tp MLP block (megatron-style column/row
  sharding, one ``psum`` per block);
- :func:`make_transformer_train_step` — the flagship dp × tp × sp step: a
  transformer block whose attention is **ring attention** over the ``sp``
  axis (:mod:`parsec_tpu.parallel.ring`), heads sharded over ``tp``, batch
  over ``dp``; gradients for replicated params reduce over dp × sp.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from .ring import ring_attention_local


def psum_r(x, axis_name: str):
    """Megatron's *g* operator: forward allreduce, backward identity.

    Placed AFTER a row-parallel matmul.  Inside ``shard_map(...,
    check_vma=False)`` the transpose of ``lax.psum`` is another ``psum`` —
    but the cotangent arriving here is replicated (the loss is computed
    identically on every shard of ``axis_name``), so the correct backward
    is the identity, not another allreduce.
    """
    @jax.custom_vjp
    def f(v):
        return lax.psum(v, axis_name)

    f.defvjp(lambda v: (lax.psum(v, axis_name), None),
             lambda _, g: (g,))
    return f(x)


def ident_f(x, axis_name: str):
    """Megatron's *f* operator: forward identity, backward allreduce.

    Placed BEFORE a column-parallel matmul on a replicated activation: each
    shard back-propagates only its own head-group/column contribution into
    the activation, so the true cotangent is the psum of the per-shard
    partials.  Omitting this leaves activation gradients tp-local and the
    upstream parameter gradients silently wrong.
    """
    @jax.custom_vjp
    def f(v):
        return v

    f.defvjp(lambda v: (v, None),
             lambda _, g: (lax.psum(g, axis_name),))
    return f(x)


def init_params(key: Any, d_model: int, d_ff: int) -> dict:
    k1, k2 = jax.random.split(key)
    return {
        "w1": jax.random.normal(k1, (d_model, d_ff), jnp.float32) * 0.02,
        "w2": jax.random.normal(k2, (d_ff, d_model), jnp.float32) * 0.02,
    }


def make_train_step(mesh: Mesh, lr: float = 0.1):
    """One SGD step of a TP-sharded MLP block over dp×tp."""
    param_specs = {"w1": P(None, "tp"), "w2": P("tp", None)}

    def local_loss(params: dict, x, y):
        h = jax.nn.relu(x @ params["w1"])        # [b, s, d_ff/tp]
        o = psum_r(h @ params["w2"], "tp")       # row-parallel matmul reduce
        return jnp.mean((o - y) ** 2)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(param_specs, P("dp"), P("dp")),
        out_specs=(param_specs, P()),
        check_vma=False,
    )
    def step(params: dict, x, y):
        loss, grads = jax.value_and_grad(local_loss)(params, x, y)
        # data-parallel gradient reduction over dp (tp shards stay sharded)
        grads = jax.tree.map(lambda g: lax.pmean(g, "dp"), grads)
        new_params = jax.tree.map(lambda p, g: p - lr * g, params, grads)
        return new_params, lax.pmean(loss, "dp")

    return jax.jit(step)


# ---------------------------------------------------------------------------
# flagship: transformer block over dp × tp × sp
# ---------------------------------------------------------------------------

def init_transformer_params(key: Any, d_model: int, n_heads: int,
                            d_head: int, d_ff: int) -> dict:
    ks = jax.random.split(key, 6)
    s = 0.02
    return {
        "wq": jax.random.normal(ks[0], (d_model, n_heads * d_head)) * s,
        "wk": jax.random.normal(ks[1], (d_model, n_heads * d_head)) * s,
        "wv": jax.random.normal(ks[2], (d_model, n_heads * d_head)) * s,
        "wo": jax.random.normal(ks[3], (n_heads * d_head, d_model)) * s,
        "w1": jax.random.normal(ks[4], (d_model, d_ff)) * s,
        "w2": jax.random.normal(ks[5], (d_ff, d_model)) * s,
    }


def transformer_param_specs() -> dict:
    """qkv projections column-sharded by head group (tp); wo row-sharded;
    MLP megatron-style.  Replicated across dp and sp."""
    return {
        "wq": P(None, "tp"), "wk": P(None, "tp"), "wv": P(None, "tp"),
        "wo": P("tp", None),
        "w1": P(None, "tp"), "w2": P("tp", None),
    }


def make_transformer_train_step(mesh: Mesh, n_heads: int, d_head: int,
                                lr: float = 0.1, causal: bool = True):
    """One SGD step of a transformer block: ring attention over ``sp``,
    head-group tensor parallelism over ``tp``, batch over ``dp``."""
    param_specs = transformer_param_specs()
    tp_size = mesh.shape["tp"]
    h_loc = n_heads // tp_size
    assert h_loc * tp_size == n_heads, (n_heads, tp_size)

    def block(params: dict, x):
        # x: [b_l, s_l, d]; projections are tp-local head groups
        b, s, d = x.shape

        def heads(t):   # [b_l, s_l, h_l*dh] -> [b_l, h_l, s_l, dh]
            return t.reshape(b, s, h_loc, d_head).transpose(0, 2, 1, 3)

        # Megatron f/g pairing: ident_f before the column-parallel
        # projections (backward psums the per-head-group activation
        # cotangents), psum_r after the row-parallel ones
        xf = ident_f(x, "tp")
        q = heads(xf @ params["wq"])
        k = heads(xf @ params["wk"])
        v = heads(xf @ params["wv"])
        a = ring_attention_local(q, k, v, axis_name="sp", causal=causal)
        a = a.transpose(0, 2, 1, 3).reshape(b, s, h_loc * d_head)
        x = x + psum_r(a @ params["wo"], "tp")
        h = jax.nn.relu(ident_f(x, "tp") @ params["w1"])
        x = x + psum_r(h @ params["w2"], "tp")
        return x

    def local_loss(params: dict, x, y):
        o = block(params, x)
        return jnp.mean((o - y) ** 2)

    data_spec = P("dp", "sp", None)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(param_specs, data_spec, data_spec),
        out_specs=(param_specs, P()),
        check_vma=False,
    )
    def step(params: dict, x, y):
        loss, grads = jax.value_and_grad(local_loss)(params, x, y)
        # params replicate across dp and sp: reduce their grads over both
        grads = jax.tree.map(
            lambda g: lax.pmean(lax.pmean(g, "dp"), "sp"), grads)
        new_params = jax.tree.map(lambda p, g: p - lr * g, params, grads)
        return new_params, lax.pmean(lax.pmean(loss, "dp"), "sp")

    return jax.jit(step)
