"""1-D radius-R stencil as a PTG taskpool — the halo-exchange app.

Rebuild of ``tests/apps/stencil/stencil_1D.jdf`` (SURVEY §4.6, §5.7): each
iteration, every sequence tile exchanges radius-R ghost regions with its
left/right neighbors and applies a (2R+1)-point weighted update — the
dataflow skeleton that SURVEY §5.7 identifies as structurally identical to
ring-attention block exchange (neighbor send / compute overlap on a ring).
Across ranks the ghost flows ride the remote-dep activation protocol.

The GFLOPS harness mirrors ``testing_stencil_1D.c:142-199``:
``flops = iterations * N * (2R+1) * 2`` (one multiply+add per weight).
"""

from __future__ import annotations

import time

import numpy as np

from .. import ptg
from ..data.data import data_create
from ..data_dist.matrix import VectorTwoDimCyclic


def stencil_1d_ptg(V: VectorTwoDimCyclic, weights: np.ndarray,
                   iterations: int) -> ptg.PTGTaskpool:
    """Build the ST(t, i) taskpool over sequence tiles of ``V``.

    Flows: C is the tile state chained over t; L/R read the neighbor tiles
    of the previous iteration for the ghost regions (halo exchange).
    Boundaries are zero-padded.
    """
    R = (len(weights) - 1) // 2
    assert 2 * R + 1 == len(weights), "weights must have odd length"
    assert R <= V.mb, "radius must fit in one tile"
    NT = V.mt

    # t == 0 reads come from a lazy snapshot of V (classic double-buffer):
    # otherwise the t == T-1 writeback to V(i) races the t == 0 ghost reads
    # of V(i) when T == 1 (same task generation, no transitive ordering).
    # Snapshots materialize during startup enumeration — before any task
    # body runs — via the eager data-input resolution.
    from ..data_dist.collection import DictCollection
    V0 = DictCollection(
        name=V.name + "_0",
        init_fn=lambda i: np.array(
            np.asarray(V.data_of(i).newest_copy().value)),
        nodes=V.nodes, myrank=V.myrank,
        rank_of_fn=lambda i: V.rank_of(i),
        keys=[(i,) for i in range(V.mt)])   # declared key space: mirrors
    # V's 1-D tiling, so the taskpool→XLA lowering can walk the snapshot

    p = ptg.PTGBuilder("stencil1d", V=V, V0=V0, NT=NT, T=iterations,
                       W=np.asarray(weights, dtype=np.float64), R=R)
    t = p.task("ST",
               t=ptg.span(0, lambda g, l: g.T - 1),
               i=ptg.span(0, lambda g, l: g.NT - 1))
    t.affinity("V", lambda g, l: (l.i,))
    t.priority(lambda g, l: g.T - l.t)

    fc = t.flow("C", ptg.RW)
    fc.input(data=("V0", lambda g, l: (l.i,)),
             guard=lambda g, l: l.t == 0)
    fc.input(pred=("ST", "C", lambda g, l: {"t": l.t - 1, "i": l.i}),
             guard=lambda g, l: l.t > 0)
    fc.output(succ=("ST", "C", lambda g, l: {"t": l.t + 1, "i": l.i}),
              guard=lambda g, l: l.t < g.T - 1)
    # halo flows to next iteration's neighbors
    fc.output(succ=("ST", "L", lambda g, l: {"t": l.t + 1, "i": l.i + 1}),
              guard=lambda g, l: l.t < g.T - 1 and l.i < g.NT - 1)
    fc.output(succ=("ST", "R", lambda g, l: {"t": l.t + 1, "i": l.i - 1}),
              guard=lambda g, l: l.t < g.T - 1 and l.i > 0)
    fc.output(data=("V", lambda g, l: (l.i,)),
              guard=lambda g, l: l.t == g.T - 1)

    fl = t.flow("L", ptg.READ)
    fl.input(data=("V0", lambda g, l: (l.i - 1,)),
             guard=lambda g, l: l.t == 0 and l.i > 0)
    fl.input(pred=("ST", "C", lambda g, l: {"t": l.t - 1, "i": l.i - 1}),
             guard=lambda g, l: l.t > 0 and l.i > 0)

    fr = t.flow("R", ptg.READ)
    fr.input(data=("V0", lambda g, l: (l.i + 1,)),
             guard=lambda g, l: l.t == 0 and l.i < g.NT - 1)
    fr.input(pred=("ST", "C", lambda g, l: {"t": l.t - 1, "i": l.i + 1}),
             guard=lambda g, l: l.t > 0 and l.i < g.NT - 1)

    def body(es, task, g, l):
        c = np.asarray(task.flow_data("C").value, dtype=np.float64)
        left = task.flow_data("L")
        right = task.flow_data("R")
        lg = (np.asarray(left.value, dtype=np.float64)[-g.R:]
              if left is not None else np.zeros(g.R))
        rg = (np.asarray(right.value, dtype=np.float64)[:g.R]
              if right is not None else np.zeros(g.R))
        padded = np.concatenate([lg, c, rg])
        new = np.convolve(padded, g.W[::-1], mode="valid")
        new = new.astype(task.flow_data("C").value.dtype)
        # ALWAYS detach into a fresh copy: the incoming C copy is still
        # read by the neighbors' L/R flows of this same iteration (WAR
        # hazard) — rebinding it in place would leak t's state into their
        # t-1 ghost reads.  (At t == 0 this also protects the home tile.)
        task.set_flow_data(
            "C", data_create(new, key=("st", l.t, l.i)).get_copy(0))

    # Traceable incarnation for the compiled (wavefront) lowering: weights
    # fold into the program as constants; boundary tasks arrive with their
    # L/R flow as None (no active arrow) and read zero ghosts, exactly like
    # the dynamic body.  Computes in the promoted tile dtype (f64 tiles stay
    # f64 when ``jax_enable_x64`` is on; TPU-native runs are f32).  Scoped to
    # THIS taskpool via ``local_traceables`` — weights differ per build, so
    # the process-global registry is not the right home.
    Wd = np.asarray(weights, np.float64)
    R_ = R

    def traceable(c, left, right):
        import jax.numpy as jnp

        from ..ops.stencil import stencil1d_xla
        dt = c.dtype
        ct = jnp.result_type(dt, jnp.float32)
        cw = c.astype(ct)
        lg = (jnp.zeros((R_,), ct) if left is None
              else left[-R_:].astype(ct))
        rg = (jnp.zeros((R_,), ct) if right is None
              else right[:R_].astype(ct))
        padded = jnp.concatenate([lg, cw, rg])
        # the tap loop FUSES into one pass (its bandwidth: not measured on
        # this machine); ops/stencil.py carries the Pallas variant for
        # shapes XLA fuses poorly, and the lowered program's cost lives
        # in the per-level store reshuffles instead
        return stencil1d_xla(padded, Wd).astype(dt)

    from ..ptg.lowering import Traceable
    t.body(body, dyld="stencil1d")
    tp = p.build()
    tp.local_traceables = {"stencil1d": Traceable(traceable)}
    return tp


def stencil_reference(x: np.ndarray, weights: np.ndarray,
                      iterations: int) -> np.ndarray:
    """Dense numpy oracle (zero-padded boundaries)."""
    R = (len(weights) - 1) // 2
    x = np.asarray(x, dtype=np.float64)
    for _ in range(iterations):
        padded = np.concatenate([np.zeros(R), x, np.zeros(R)])
        x = np.convolve(padded, weights[::-1], mode="valid")
    return x


def stencil_flops(n: int, radius: int, iterations: int) -> float:
    return 2.0 * (2 * radius + 1) * n * iterations


def run_stencil_bench(n: int = 1 << 20, mb: int = 1 << 16, radius: int = 4,
                      iterations: int = 10, nb_cores: int = 2) -> dict:
    """GFLOPS harness (``testing_stencil_1D.c`` analog)."""
    from ..runtime import Context
    rng = np.random.default_rng(0)
    base = rng.standard_normal(n).astype(np.float32)
    V = VectorTwoDimCyclic("V", lm=n, mb=mb, P=1,
                           init_fn=lambda m, size:
                           base[m * mb:m * mb + size])
    weights = np.full(2 * radius + 1, 1.0 / (2 * radius + 1))
    tp = stencil_1d_ptg(V, weights, iterations)
    ctx = Context(nb_cores=nb_cores)
    t0 = time.perf_counter()
    ctx.add_taskpool(tp)
    ctx.wait(timeout=600)
    dt = time.perf_counter() - t0
    ctx.fini()
    flops = stencil_flops(n, radius, iterations)
    return {"gflops": flops / dt / 1e9, "seconds": dt, "n": n,
            "radius": radius, "iterations": iterations}
