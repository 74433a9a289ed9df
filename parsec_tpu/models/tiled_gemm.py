"""Flagship: tiled GEMM as a PTG taskpool (+ fused single-program executor).

The rebuild's analog of the reference's GEMM benchmarks
(``tests/dsl/dtd/dtd_test_simple_gemm.c``, ``tests/runtime/cuda/stress.jdf``)
and the BASELINE.md target config (PTG tiled-GEMM, N=16384, nb=512).

Two execution paths, by design (TPU-first):

1. :func:`tiled_gemm_ptg` — the dynamic-runtime path: a PTG taskpool
   GEMM(m,n,k) whose C-flow chains along k; tiles stage into HBM through the
   TPU device module; correctness/irregular-shape path.
   :func:`tiled_gemm_dtd` is the same graph through the other front end:
   the reference harness's insertion program.
2. :func:`tiled_gemm_fused` — the compiled path: the same dataflow lowered to
   one XLA program (single chip: one MXU-tiled matmul; multi-chip: shard_map
   over a mesh in :mod:`parsec_tpu.parallel`).  On TPU the compiler's
   schedule of the regular k-chain beats any host-dispatched task loop, so
   the runtime treats "fused" as just another incarnation of the taskpool.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from .. import ptg
from ..data_dist.matrix import TiledMatrix
from ..dtd import AFFINITY, INOUT, INPUT, PUSHOUT
from ..ops import gemm as gemm_ops


def tiled_gemm_ptg(A: TiledMatrix, B: TiledMatrix, C: TiledMatrix,
                   devices: str = "auto") -> ptg.PTGTaskpool:
    """Build the GEMM(m,n,k) PTG over tiled matrices: C += A·B.

    Flows (positionally fixed for the kernel bodies): 0=A READ, 1=B READ,
    2=C RW chained over k.
    """
    MT, NT, KT = C.mt, C.nt, A.nt
    assert A.mt == MT and B.nt == NT and B.mt == KT

    p = ptg.PTGBuilder("tiled_gemm", A=A, B=B, C=C, MT=MT, NT=NT, KT=KT)
    t = p.task("GEMM",
               m=ptg.span(0, lambda g, l: g.MT - 1),
               n=ptg.span(0, lambda g, l: g.NT - 1),
               k=ptg.span(0, lambda g, l: g.KT - 1))
    t.affinity("C", lambda g, l: (l.m, l.n))
    t.priority(lambda g, l: g.KT - l.k)   # deeper chains first
    fa = t.flow("A", ptg.READ)
    fa.input(data=("A", lambda g, l: (l.m, l.k)))
    fb = t.flow("B", ptg.READ)
    fb.input(data=("B", lambda g, l: (l.k, l.n)))
    fc = t.flow("C", ptg.RW)
    fc.input(data=("C", lambda g, l: (l.m, l.n)), guard=lambda g, l: l.k == 0)
    fc.input(pred=("GEMM", "C", lambda g, l: {"m": l.m, "n": l.n, "k": l.k - 1}),
             guard=lambda g, l: l.k > 0)
    fc.output(succ=("GEMM", "C", lambda g, l: {"m": l.m, "n": l.n, "k": l.k + 1}),
              guard=lambda g, l: l.k < g.KT - 1)
    fc.output(data=("C", lambda g, l: (l.m, l.n)),
              guard=lambda g, l: l.k == g.KT - 1)
    # flops-based time estimate feeds best-device selection
    flops = 2.0 * A.mb * C.nb * A.nb
    t.time_estimate(lambda task, dev: flops / (dev.gflops_fp32 * 1e9))
    if devices in ("auto", "tpu"):
        t.body(device="tpu", dyld="gemm")
    if devices in ("auto", "cpu"):
        t.body(_cpu_wrap, device="cpu")
    return p.build()


def _cpu_wrap(es: Any, task: Any, g: Any, l: Any) -> None:
    gemm_ops.gemm_cpu_body(es, task)


def _gemm_dtd_cpu(a: np.ndarray, b: np.ndarray, c: np.ndarray) -> None:
    """The host incarnation of a DTD GEMM task: its arguments arrive unpacked
    in insertion order, and C is updated in place."""
    c += a.astype(np.float32) @ b.astype(np.float32)


def tiled_gemm_dtd(tp: Any, A: TiledMatrix, B: TiledMatrix,
                   C: TiledMatrix) -> int:
    """C += A·B as PaRSEC's own DTD harness inserts it
    (``tests/dsl/dtd/dtd_test_simple_gemm.c``): for every C tile, in (m, n)
    order, one GEMM per k with A and B ``INPUT`` and C ``INOUT | AFFINITY``;
    the last k of a tile carries ``PUSHOUT``, so its result starts home when
    that task completes.  ``tp`` is a :class:`DTDTaskpool` already enqueued
    in a context; the DAG is what the insertion order and the access modes
    give.  Returns the number of tasks inserted.  The accelerator runs the
    ``"gemm"`` kernel :func:`tiled_gemm_ptg` names, the host
    :func:`_gemm_dtd_cpu`."""
    MT, NT, KT = C.mt, C.nt, A.nt
    assert A.mt == MT and B.nt == NT and B.mt == KT
    tile_of, insert = tp.tile_of, tp.insert_task
    for m in range(MT):
        for n in range(NT):
            for k in range(KT):
                last = PUSHOUT if k == KT - 1 else 0
                insert(_gemm_dtd_cpu,
                       (tile_of(A, m, k), INPUT),
                       (tile_of(B, k, n), INPUT),
                       (tile_of(C, m, n), INOUT | AFFINITY | last),
                       name="GEMM", tpu_kernel="gemm")
    return MT * NT * KT


def tiled_gemm_recursive_ptg(A: TiledMatrix, B: TiledMatrix, C: TiledMatrix,
                             sub_mb: int, sub_nb: int,
                             min_tile: int = 0) -> ptg.PTGTaskpool:
    """GEMM PTG whose bodies *recurse*: each GEMM(m,n,k) spawns a nested
    tiled-GEMM taskpool over (sub_mb, sub_nb) sub-tiles of its own flow
    tiles and detaches until it drains — the ``PARSEC_DEV_RECURSIVE``
    pattern (``parsec/recursive.h``, ``device.h:64``) on the flagship app.

    ``min_tile`` is the recursion cutoff (the role of the evaluate hook in
    reference recursive chores): tiles with both dims <= ``min_tile`` run
    the plain CPU GEMM body instead of recursing.
    """
    MT, NT, KT = C.mt, C.nt, A.nt
    assert A.mt == MT and B.nt == NT and B.mt == KT

    p = ptg.PTGBuilder("tiled_gemm_rec", A=A, B=B, C=C, MT=MT, NT=NT, KT=KT)
    t = p.task("GEMM",
               m=ptg.span(0, lambda g, l: g.MT - 1),
               n=ptg.span(0, lambda g, l: g.NT - 1),
               k=ptg.span(0, lambda g, l: g.KT - 1))
    t.affinity("C", lambda g, l: (l.m, l.n))
    t.priority(lambda g, l: g.KT - l.k)
    fa = t.flow("A", ptg.READ)
    fa.input(data=("A", lambda g, l: (l.m, l.k)))
    fb = t.flow("B", ptg.READ)
    fb.input(data=("B", lambda g, l: (l.k, l.n)))
    fc = t.flow("C", ptg.RW)
    fc.input(data=("C", lambda g, l: (l.m, l.n)), guard=lambda g, l: l.k == 0)
    fc.input(pred=("GEMM", "C", lambda g, l: {"m": l.m, "n": l.n, "k": l.k - 1}),
             guard=lambda g, l: l.k > 0)
    fc.output(succ=("GEMM", "C", lambda g, l: {"m": l.m, "n": l.n, "k": l.k + 1}),
              guard=lambda g, l: l.k < g.KT - 1)
    fc.output(data=("C", lambda g, l: (l.m, l.n)),
              guard=lambda g, l: l.k == g.KT - 1)

    def _too_small(es: Any, task: Any) -> int:
        from ..runtime.task import HOOK_RETURN_NEXT
        shape = np.asarray(task.data[2].value).shape
        if max(shape) <= min_tile:
            return HOOK_RETURN_NEXT     # fall through to the plain CPU chore
        return 0

    def _recurse(es: Any, task: Any, g: Any, l: Any) -> int:
        from ..data_dist.matrix import SubtileCollection
        from ..runtime.recursive import recursive_call
        a = SubtileCollection.of_copy(task.data[0], sub_mb, sub_nb,
                                      name=f"Asub{task.key}")
        b = SubtileCollection.of_copy(task.data[1], sub_mb, sub_nb,
                                      name=f"Bsub{task.key}")
        c = SubtileCollection.of_copy(task.data[2], sub_mb, sub_nb,
                                      name=f"Csub{task.key}")
        inner = tiled_gemm_ptg(a, b, c, devices="cpu")
        # sync_parent on C publishes the sub-writes into the outer flow copy
        # before the outer completion walks its out-deps
        return recursive_call(es, task, inner, collections=(c,))

    t.body(_recurse, device="recursive",
           evaluate=_too_small if min_tile else None)
    t.body(_cpu_wrap, device="cpu")
    return p.build()


@functools.partial(jax.jit, static_argnames=("precision",))
def _fused_gemm(a, b, c, precision=None):
    return c + jnp.dot(a, b, preferred_element_type=c.dtype,
                       precision=precision)


def tiled_gemm_fused(a: Any, b: Any, c: Any, precision: Any = None) -> Any:
    """One-program lowering of the GEMM taskpool for dense operands."""
    return _fused_gemm(a, b, c, precision=precision)


def gemm_flops(M: int, N: int, K: int) -> float:
    return 2.0 * M * N * K
