"""C = A.B over square f32 tiles through the DTD front end
(``models/tiled_gemm.py:tiled_gemm_dtd``, the insertion program of PaRSEC's
``dtd_test_simple_gemm.c``).

Everything but the front end is ``problems/gemm.py``'s, loaded by file name:
the seeded operands, the FLOPs and least bytes, the result tiles, the plain
reference, the gap and the control.  A DTD solve has no graph to hand over:
``pool()`` gives an empty ``DTDTaskpool`` and ``insert()`` runs the insertion
program on it once it is enqueued (``paths/dtd.py``)."""

from __future__ import annotations

from harness import load_module


def _insert_as_the_source(tp, A, B, C) -> None:
    """The same insertion program for a program that has no
    ``tiled_gemm_dtd`` yet (the parent of PR 34, so that it can be measured
    in this cell): its ``PUSHOUT`` is a flag nothing reads."""
    from parsec_tpu.dtd import AFFINITY, INOUT, INPUT, PUSHOUT

    def gemm(a, b, c):          # the host incarnation, not taken here
        c += a @ b

    for m in range(C.mt):
        for n in range(C.nt):
            for k in range(A.nt):
                last = PUSHOUT if k == A.nt - 1 else 0
                tp.insert_task(gemm, (tp.tile_of(A, m, k), INPUT),
                               (tp.tile_of(B, k, n), INPUT),
                               (tp.tile_of(C, m, n), INOUT | AFFINITY | last),
                               name="GEMM", tpu_kernel="gemm")


class Problem(load_module("problems", "gemm").Problem):
    def pool(self, colls: tuple = ()):
        from parsec_tpu.dtd import DTDTaskpool
        return DTDTaskpool()

    def insert(self, pool, colls: tuple) -> None:
        from parsec_tpu.models import tiled_gemm
        getattr(tiled_gemm, "tiled_gemm_dtd", _insert_as_the_source)(
            pool, *colls)
