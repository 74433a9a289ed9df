"""Pipeline parallelism (PP), both incarnations.

SURVEY §2.12's missing recipe, in the two forms the framework supports:

1. **On the dataflow core** (:func:`pipeline_ptg`): the Ex03 chain shape
   (``/root/reference/examples/Ex03_ChainMPI.jdf`` — a task chain whose
   affinity walks the ranks) widened into a stage × microbatch grid.  Task
   ``P(s, m)`` runs stage ``s`` on microbatch ``m``, lives on the rank that
   owns stage ``s`` (a 1-D cyclic stage distribution), receives its
   activation from ``P(s-1, m)`` and feeds ``P(s+1, m)`` — so activations
   hop rank to rank through the remote-dep protocol exactly like the
   reference's chain hops nodes over MPI.  Microbatch priority gives the
   interleaved 1F1B-ish fill: early microbatches drain ahead so every stage
   keeps busy.

2. **On the mesh** (:func:`make_pipeline_step`): the TPU-native schedule —
   stages are a ``pp`` mesh axis, weights shard per-stage, and the GPipe
   rotation runs as a ``lax.scan`` over ``nmicro + nstages - 1`` ticks with
   a ``ppermute`` handing each stage's activation to its successor over
   ICI.  No per-tick host dispatch: the whole pipeline is one XLA program.
"""

from __future__ import annotations

from typing import Any, Callable, Sequence

import numpy as np

from .. import ptg
from ..data_dist.matrix import VectorTwoDimCyclic

__all__ = ["pipeline_ptg", "make_pipeline_step"]


# ---------------------------------------------------------------------------
# 1. the dataflow-core recipe
# ---------------------------------------------------------------------------

def pipeline_ptg(X: Any, stage_fns: Sequence[Callable], nranks: int,
                 name: str = "pipeline") -> "ptg.PTGTaskpool":
    """Stage-chain PTG: ``X(m)`` microbatch tiles flow through every stage.

    ``X`` is the microbatch collection (inputs read from it, final outputs
    written back to it, home rank 0); ``stage_fns[s]`` is a pure
    ``ndarray -> ndarray`` applied by stage ``s``, which runs on rank
    ``s % nranks`` (the cyclic stage distribution the reference's Ex03
    ``rank_of`` plays with).
    """
    S = len(stage_fns)
    stages = VectorTwoDimCyclic(f"{name}_stages", lm=S, mb=1, P=nranks)

    p = ptg.PTGBuilder(name, X=X, STAGES=stages, S=S, M=X.mt,
                       FNS=tuple(stage_fns))
    t = p.task("P",
               s=ptg.span(0, lambda g, l: g.S - 1),
               m=ptg.span(0, lambda g, l: g.M - 1))
    t.affinity("STAGES", lambda g, l: (l.s,))
    # drain early microbatches first so stages stay busy (1F1B-ish fill)
    t.priority(lambda g, l: g.M - l.m)
    f = t.flow("V", ptg.RW)
    f.input(data=("X", lambda g, l: (l.m, 0)), guard=lambda g, l: l.s == 0)
    f.input(pred=("P", "V", lambda g, l: {"s": l.s - 1, "m": l.m}),
            guard=lambda g, l: l.s > 0)
    f.output(succ=("P", "V", lambda g, l: {"s": l.s + 1, "m": l.m}),
             guard=lambda g, l: l.s < g.S - 1)
    f.output(data=("X", lambda g, l: (l.m, 0)),
             guard=lambda g, l: l.s == g.S - 1)

    def body(es, task, g, l):
        v = task.flow_data("V")
        v.value = np.asarray(g.FNS[l.s](np.asarray(v.value)))
        v.version += 1

    t.body(body)
    return p.build()


# ---------------------------------------------------------------------------
# 2. the mesh recipe (shard_map + ppermute GPipe rotation)
# ---------------------------------------------------------------------------

def make_pipeline_step(mesh: Any, stage_fn: Callable, nstages: int,
                       nmicro: int) -> Callable:
    """Compile a forward pipeline over the ``pp`` mesh axis.

    ``stage_fn(w, x) -> x`` is one stage's computation; weights ``w`` carry
    a leading per-stage axis sharded over ``pp``, microbatches ``xs`` have
    shape ``[nmicro, ...]`` (replicated).  Returns ``run(w, xs) -> ys`` —
    one jitted XLA program executing the GPipe schedule:
    ``nmicro + nstages - 1`` ticks, each a local stage apply plus a
    ``ppermute`` shifting activations one stage forward over ICI.
    """
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.lax import pcast
    from jax.sharding import PartitionSpec as P

    # no wraparound pair: the last stage's activation retires into ys, and
    # stage 0 always injects fresh microbatches (ppermute zero-fills the
    # unsourced device, which stage 0 never reads)
    right = [(i, i + 1) for i in range(nstages - 1)]
    if nstages != mesh.shape["pp"]:
        raise ValueError(f"nstages={nstages} != pp axis "
                         f"size {mesh.shape['pp']}")

    def spmd(w, xs):
        # w: [1, ...] this stage's weights; xs: [nmicro, ...] replicated
        if xs.shape[0] != nmicro:
            raise ValueError(f"xs carries {xs.shape[0]} microbatches, "
                             f"expected nmicro={nmicro}")
        s = jax.lax.axis_index("pp")
        wl = jax.tree_util.tree_map(lambda a: a[0], w)
        T = nmicro + nstages - 1
        # the carry varies per stage: mark it device-varying up front so the
        # scan carry type is stable (shard_map's vma typing)
        cur0 = pcast(jnp.zeros_like(xs[0]), "pp", to="varying")
        ys0 = pcast(jnp.zeros_like(xs), "pp", to="varying")

        def tick(carry, t):
            cur, ys = carry
            # stage 0 injects microbatch t (while they last); others take
            # the activation handed over by their predecessor last tick
            inject = jnp.where(t < nmicro, t, 0)
            inp = jnp.where(s == 0, xs[inject], cur)
            out = stage_fn(wl, inp)
            # the last stage retires microbatch t-(nstages-1) into ys
            done = t - (nstages - 1)
            keep = (s == nstages - 1) & (done >= 0)
            ys = jnp.where(
                keep,
                jax.lax.dynamic_update_index_in_dim(
                    ys, out, jnp.maximum(done, 0), 0),
                ys)
            nxt = jax.lax.ppermute(out, "pp", right)
            return (nxt, ys), None

        (cur, ys), _ = jax.lax.scan(tick, (cur0, ys0), jnp.arange(T))
        # ys lives on the last stage; share it along pp (psum of one-hot)
        ys = jax.lax.psum(jnp.where(s == nstages - 1, ys, 0.0), "pp")
        return ys

    run = shard_map(spmd, mesh=mesh, in_specs=(P("pp"), P()),
                    out_specs=P())
    return jax.jit(run)
