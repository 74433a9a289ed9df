"""Collective-tree communication: broadcast and reduction taskpools.

"Large Scale Distributed Linear Algebra With TPUs" (arxiv 2112.09017)
operates in the regime this module targets — dense collectives over
pod-scale meshes — and T3 (arxiv 2401.16677) argues collectives must ride
the taskpool (overlappable, fragment-granular) rather than block it.  Both
shapes here are therefore emitted as plain PTG taskpools: graphcheck-clean,
schedulable and fair-shareable like any other pool, with fragment progress
interleaved by busy workers (the ``_frag_active`` gate) and the 8-byte
trace id riding every frame via ``tp._trace``.

**Broadcast** (:func:`bcast_taskpool`): one task per tree position; the
root reads its tile, every other position receives the payload from its
:func:`tree_parent` and re-serves it to its :func:`tree_children` — the
per-hop payload move is the activation layer's staged re-serve
(``remote_dep._complete_incoming``): an interior rank re-registers the
landed buffer and its children pull from *it* over credit-windowed
fragmented GETs, so root egress is O(children(root)) payload transfers
(⌈log₂ n⌉ for binomial) instead of O(n).

**Reduction** (:func:`reduce_taskpool`): leaves ship their tile up the
same tree; interior positions combine their children's partials with a
registered op (:func:`register_reduce_op`) before forwarding, so each
edge carries exactly one tile and the root applies the final combine.

Tree shapes are the activation propagation shapes (``binomial | chain |
star``, validated — an unknown kind raises
:class:`~parsec_tpu.core.params.MCAParamValueError` instead of silently
degrading).  ``comm_bcast_tree=auto`` resolves per payload class through
:func:`~parsec_tpu.comm.remote_dep.resolve_tree_kind` — the same rule
``analysis/commcheck.recommend_tree`` derives statically (docs/COMM.md).  ``redistribute_taskpool`` routes multi-consumer fan-out
through the same staging (``data_dist/redistribute.py``).
"""

from __future__ import annotations

from typing import Any, Callable

import numpy as np

from ..core.params import params as _params
from ..data.data import data_create
from .remote_dep import (TREE_KINDS, resolve_tree_kind, tree_children,
                         tree_parent)

__all__ = ["bcast_taskpool", "reduce_taskpool", "register_reduce_op",
           "reduce_op", "tree_children", "tree_parent", "TREE_KINDS",
           "resolve_tree_kind"]


def _dtt_nbytes(V: Any) -> int | None:
    """Per-tile payload hint for ``resolve_tree_kind`` under ``auto``."""
    dtt = getattr(V, "default_dtt", None)
    try:
        return int(dtt.nbytes)
    except Exception:
        return None


# ---------------------------------------------------------------------------
# reduction op registry
# ---------------------------------------------------------------------------

# concurrency contract (analysis.runtimelint, docs/ANALYSIS.md): no
# shared mutable state beyond the reduce-op registry, which follows the
# register-at-import / read-at-build discipline (same as the codec and
# PINS registries) — registration after pools are running is unsupported,
# so the registry carries no lock.  The empty registry declares that:
# nothing here may grow cross-thread mutation without growing an entry.
_LOCK_PROTECTED = {}
_LOCK_ORDER = ()

_REDUCE_OPS: dict[str, Callable[[Any, Any], Any]] = {
    "sum": np.add,
    "prod": np.multiply,
    "max": np.maximum,
    "min": np.minimum,
}


def register_reduce_op(name: str, fn: Callable[[Any, Any], Any]) -> None:
    """Register a binary combine for :func:`reduce_taskpool` — must be
    associative and commutative: the tree applies it in position order,
    not submission order."""
    _REDUCE_OPS[name] = fn


def reduce_op(name: str) -> Callable[[Any, Any], Any]:
    fn = _REDUCE_OPS.get(name)
    if fn is None:
        raise KeyError(f"unknown reduce op {name!r}; registered: "
                       f"{sorted(_REDUCE_OPS)} (register_reduce_op)")
    return fn


# ---------------------------------------------------------------------------
# broadcast
# ---------------------------------------------------------------------------


def _positions(V: Any, n: int | None) -> int:
    if n is not None:
        return n
    n = getattr(V, "mt", None)
    if n is None:
        raise TypeError(f"cannot infer tree size from {type(V).__name__}; "
                        f"pass n= explicitly")
    return n


def _max_children(kind: str, n: int) -> int:
    return max((len(tree_children(kind, p, n)) for p in range(n)),
               default=0)


def bcast_taskpool(V: Any, *, root: int = 0, n: int | None = None,
                   kind: str | None = None,
                   name: str = "coll_bcast") -> Any:
    """Broadcast tile ``V(root)`` into every tile ``V(p)`` for the ``n``
    tree positions, staged along a ``kind`` tree (default: the
    ``comm_bcast_tree`` MCA param).

    Position ``p`` maps to tile ``(root + p) % n`` so the root is always
    position 0; each position runs on its tile's home rank (the task
    affinity), which is what turns the PTG edges into the staged
    activation tree on a distributed collection."""
    from .. import ptg

    n = _positions(V, n)
    kind = resolve_tree_kind(
        kind, nbytes=_dtt_nbytes(V), n=n)
    if not 0 <= root < n:
        raise ValueError(f"root {root} outside [0, {n})")
    kids = _max_children(kind, n)

    def key(p: int) -> int:
        return (root + p) % n

    p_ = ptg.PTGBuilder(name, V=V, N=n, ROOT=root)
    t = p_.task("B", p=ptg.span(0, lambda g, l: g.N - 1))
    t.affinity("V", lambda g, l: (key(l.p),))
    f = t.flow("A", ptg.RW)
    f.input(data=("V", lambda g, l: (g.ROOT,)),
            guard=lambda g, l: l.p == 0)
    f.input(pred=("B", "A",
                  lambda g, l: {"p": tree_parent(kind, l.p, g.N)}),
            guard=lambda g, l: l.p > 0)
    for s in range(kids):
        f.output(succ=("B", "A",
                       lambda g, l, s=s:
                       {"p": tree_children(kind, l.p, g.N)[s]}),
                 guard=lambda g, l, s=s:
                 s < len(tree_children(kind, l.p, g.N)))
    f.output(data=("V", lambda g, l: (key(l.p),)))

    @t.body
    def body(es, task, g, l):
        pass        # pure movement: the landed copy IS the result

    return p_.build()


# ---------------------------------------------------------------------------
# reduction
# ---------------------------------------------------------------------------


def reduce_taskpool(V: Any, OUT: Any, *, op: str = "sum", root: int = 0,
                    n: int | None = None, kind: str | None = None,
                    out_key: int = 0, name: str = "coll_reduce") -> Any:
    """Combine the ``n`` tiles of ``V`` up a ``kind`` tree with ``op``;
    the root writes the final combine into ``OUT(out_key)``.

    Each position reads its own tile (flow ``L``), receives at most one
    partial per child slot (flows ``C0..Ck``), combines, and ships the
    partial to its parent (flow ``P``) — one tile per tree edge, combines
    at interior nodes."""
    from .. import ptg

    n = _positions(V, n)
    kind = resolve_tree_kind(
        kind, nbytes=_dtt_nbytes(V), n=n)
    if not 0 <= root < n:
        raise ValueError(f"root {root} outside [0, {n})")
    fn = reduce_op(op)
    kids = _max_children(kind, n)

    def key(p: int) -> int:
        return (root + p) % n

    def slot(p: int, nn: int) -> int:
        """Which child slot of its parent position ``p`` occupies."""
        return tree_children(kind, tree_parent(kind, p, nn), nn).index(p)

    p_ = ptg.PTGBuilder(name, V=V, OUT=OUT, N=n, ROOT=root)
    t = p_.task("R", p=ptg.span(0, lambda g, l: g.N - 1))
    t.affinity("V", lambda g, l: (key(l.p),))
    fl = t.flow("L", ptg.READ)
    fl.input(data=("V", lambda g, l: (key(l.p),)))
    for s in range(kids):
        fc = t.flow(f"C{s}", ptg.READ)
        fc.input(pred=("R", "P",
                       lambda g, l, s=s:
                       {"p": tree_children(kind, l.p, g.N)[s]}),
                 guard=lambda g, l, s=s:
                 s < len(tree_children(kind, l.p, g.N)))
    fp = t.flow("P", ptg.WRITE)
    for s in range(kids):
        fp.output(succ=("R", f"C{s}",
                        lambda g, l: {"p": tree_parent(kind, l.p, g.N)}),
                  guard=lambda g, l, s=s:
                  l.p > 0 and slot(l.p, g.N) == s)
    fp.output(data=("OUT", lambda g, l: (out_key,)),
              guard=lambda g, l: l.p == 0)

    @t.body
    def body(es, task, g, l):
        acc = np.array(np.asarray(task.flow_data("L").value), copy=True)
        for s in range(len(tree_children(kind, l.p, n))):
            acc = fn(acc, np.asarray(task.flow_data(f"C{s}").value))
        task.set_flow_data(
            "P", data_create(acc, key=(name, "partial", l.p)).get_copy(0))

    return p_.build()


# ---------------------------------------------------------------------------
# multiproc body (the 8-rank acceptance test + commcheck's agreement gate)
# ---------------------------------------------------------------------------

# payload of the body's broadcast tile: far past comm_short_limit, so the
# tree carries rendezvous GETs and root egress is counted in payloads
MP_COLLECTIVE_BYTES = 4 << 20


def _mp_collective_body(ctx, rank, nranks):
    """One broadcast of a ``MP_COLLECTIVE_BYTES`` tile + one tree
    reduction, timed; returns per-rank latency, payload digests, and the
    socket fabric's per-peer traffic ledger so the parent can assert root
    egress stays O(children(root))."""
    import hashlib
    import time

    from ..data_dist.matrix import VectorTwoDimCyclic

    mb = MP_COLLECTIVE_BYTES // 4                  # float32 elements
    V = VectorTwoDimCyclic(
        "V", lm=mb * nranks, mb=mb, P=nranks, myrank=rank,
        init_fn=lambda m, size: (
            np.arange(size, dtype=np.float32) * 0.5 + 7.0 if m == 0
            else np.zeros(size, np.float32)))
    t0 = time.perf_counter()
    ctx.add_taskpool(bcast_taskpool(V, n=nranks))
    ctx.wait(timeout=120)
    ctx.comm_barrier()
    bcast_s = time.perf_counter() - t0

    mine = np.asarray(V.data_of(rank).newest_copy().value)
    digest = hashlib.sha256(np.ascontiguousarray(mine).tobytes()).hexdigest()

    # reduction: every rank contributes rank+1 over a small tile
    R = VectorTwoDimCyclic(
        "R", lm=64 * nranks, mb=64, P=nranks, myrank=rank,
        init_fn=lambda m, size: np.full(size, float(m + 1), np.float32))
    O = VectorTwoDimCyclic("O", lm=64, mb=64, P=1, myrank=rank,
                           init_fn=lambda m, size:
                           np.zeros(size, np.float32))
    t0 = time.perf_counter()
    ctx.add_taskpool(reduce_taskpool(R, O, op="sum", n=nranks))
    ctx.wait(timeout=120)
    ctx.comm_barrier()
    reduce_s = time.perf_counter() - t0
    red = float(np.asarray(O.data_of(0).newest_copy().value)[0]) \
        if rank == 0 else None

    fab = ctx.comm_engine.ce.fabric
    stats = fab.peer_stats() if hasattr(fab, "peer_stats") else {}
    return {"rank": rank, "digest": digest, "bcast_s": bcast_s,
            "reduce_s": reduce_s, "reduce0": red, "peer_stats": stats,
            "tree": _params.get("comm_bcast_tree")}
