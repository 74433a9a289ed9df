"""CLI: ``python -m parsec_tpu.analysis``.

Runs both prongs and exits nonzero on any error-severity finding — the
one-command CI gate (``scripts/check.sh`` wraps it together with ruff).

Usage::

    python -m parsec_tpu.analysis                  # self-lint + all models
    python -m parsec_tpu.analysis --self-lint [PATH ...]
    python -m parsec_tpu.analysis --graph cholesky --nt 6 --ranks 4
    python -m parsec_tpu.analysis --graph path/to/graph.jdf --bind NT=4
    python -m parsec_tpu.analysis --comm [--ranks 8]   # comm patterns
"""

from __future__ import annotations

import argparse
import sys

import numpy as np


def _model_graphs(nt: int, ranks: int = 1):
    """Small default instances of every shipped model builder — the same
    registry the pytest gate sweeps.  ``ranks > 1`` distributes the
    vector-backed pools round-robin so commcheck sees cross-rank edges;
    the dense-matrix and LLM pools stay single-home (classified
    ``none`` — legitimately rank-local)."""
    from ..data_dist.matrix import (SymTwoDimBlockCyclic, TiledMatrix,
                                    TwoDimBlockCyclic, VectorTwoDimCyclic)
    from ..models import (cholesky, irregular, lu, pingpong, qr, reduction,
                          stencil, stencil2d, tiled_gemm)
    from ..models.qrtree import QRTree
    nb = 8
    n = nt * nb

    def _vec(name):
        return VectorTwoDimCyclic(name, lm=n, mb=nb, P=ranks,
                                  init_fn=lambda m, s: np.zeros(s,
                                                                np.float32))

    yield "cholesky", cholesky.tiled_cholesky_ptg(
        SymTwoDimBlockCyclic("A", n, n, nb, nb), devices="cpu")
    yield "lu", lu.tiled_lu_ptg(
        TiledMatrix.from_dense("A", lu.make_dd(n), nb, nb), devices="cpu")
    yield "getrf", lu.tiled_getrf_ptg(
        TwoDimBlockCyclic("A", n, n, nb, nb), lu.ipiv_matrix(n, nb),
        devices="cpu")
    yield "qr", qr.tiled_qr_ptg(
        TwoDimBlockCyclic("A", n, n, nb, nb),
        TwoDimBlockCyclic("T", n, n, nb, nb), devices="cpu")
    # the hierarchical tree on a tall grid: domains of two rows, the last
    # of a step shorter where 3 nt - k is odd
    yield "hqr", qr.tiled_hqr_ptg(
        *(TwoDimBlockCyclic(name, 3 * n, n, nb, nb)
          for name in ("A", "TS", "TT")), QRTree(3 * nt, nt, 2),
        devices="cpu")
    yield "pingpong", pingpong.pingpong_ptg(_vec("V"), 2 * nt)
    yield "reduction", reduction.bt_reduction_ptg(_vec("R"))
    yield "stencil1d", stencil.stencil_1d_ptg(
        _vec("S"), np.array([0.25, 0.5, 0.25]), 3)
    yield "stencil2d", stencil2d.stencil_2d_ptg(
        TwoDimBlockCyclic.from_dense(
            "M", np.zeros((n, n), np.float32), nb, nb),
        (0.5, 0.15, 0.15, 0.1, 0.1), 3)
    A = TiledMatrix.from_dense("A", np.zeros((n, n), np.float32), nb, nb)
    B = TiledMatrix.from_dense("B", np.zeros((n, n), np.float32), nb, nb)
    yield "tiled_gemm", tiled_gemm.tiled_gemm_ptg(
        A, B, TiledMatrix("C", n, n, nb, nb), devices="cpu")
    yield "all2all", irregular.all2all_ptg(_vec("IA"), _vec("IB"), 2)

    # the LLM serving pools (docs/LLM.md): ragged page chains + the
    # paged-KV has_key bounds oracle, at mixed sequence lengths
    from ..data.datatype import TileType
    from ..data_dist.collection import DictCollection
    from ..data_dist.paged_kv import PagedKVCollection
    from ..llm import ToyLM, decode_step_ptg, prefill_chunks, prefill_ptg
    model = ToyLM()
    H, D = model.num_heads, model.head_dim
    kv = PagedKVCollection("KV", page_size=4, num_heads=H, head_dim=D)
    prompts = {"a": list(range(2 * nt)), "b": [1, 2]}
    chunks = {}
    for seq, toks in prompts.items():
        kv.alloc_seq(seq)
        chunks.update(prefill_chunks(model, kv, seq, toks[:-1]))
    T = DictCollection("T", dtt=kv.default_dtt,
                       init_fn=lambda *k: chunks[k], keys=list(chunks))
    yield "llm_prefill", prefill_ptg(kv, T, list(prompts))
    Q = DictCollection("Q", dtt=TileType((3, H, D), np.float32))
    O = DictCollection("O", dtt=TileType((H, D), np.float32))
    for seq in prompts:
        kv.ensure_tail_slot(seq)
    yield "llm_decode", decode_step_ptg(kv, Q, O, list(prompts))

    # the k-step decode superpool (ISSUE 9): in-graph SAMPLE chains,
    # cross-step tail-page dataflow, mixed per-seq step counts — the
    # ragged multi-step graph the batcher submits per tenant iteration
    from ..llm import decode_superpool_ptg, preallocate_decode_steps
    kv2 = PagedKVCollection("KVk", page_size=4, num_heads=H, head_dim=D)
    chunks2 = {}
    for seq, toks in prompts.items():
        kv2.alloc_seq(seq)
        chunks2.update(prefill_chunks(model, kv2, seq, toks[:-1]))
    Q2 = DictCollection("Qk", dtt=TileType((3, H, D), np.float32))
    O2 = DictCollection("Ok", dtt=TileType((H, D), np.float32))
    TOK = DictCollection("TOKk", dtt=TileType((3,), np.float32))
    EMB = DictCollection("EMBk", dtt=TileType(model.q3_table().shape,
                                              np.float32))
    steps = {"a": max(2, nt // 2), "b": 2}      # mixed step counts
    for seq in prompts:
        preallocate_decode_steps(kv2, seq, steps[seq])
        TOK.data_of(seq, -1)                    # the chain seed tile
    yield "llm_decode_k", decode_superpool_ptg(
        kv2, Q2, O2, TOK, EMB, list(prompts),
        [steps[s] for s in prompts])

    # the speculative superpools (ISSUE 12), both incarnations.
    # llm_decode_spec: one task per (position, page) with IN-GRAPH
    # speculative appends — the rollback-facing WAR/WAW ordering of the
    # speculative tail (position t's tail-page read AFTER position
    # t-1's append, re-reads of written pages) must prove statically
    # off the builder's last-writer/reader tables, like the PR-9 k-step
    # schedule it generalizes.  llm_decode_spec_batched: the serving
    # hot path's collapsed graph (one multi-query SATTN per page + one
    # SVERIFY per stream over host-staged speculative slots).
    from ..llm import (seed_spec_batched_pool, seed_spec_superpool,
                       spec_batched_ptg, spec_superpool_ptg)
    kv3 = PagedKVCollection("KVs", page_size=4, num_heads=H, head_dim=D)
    DRAFT = DictCollection("DRAFTs", dtt=TileType((3, H, D), np.float32))
    O3 = DictCollection("Os", dtt=TileType((H, D), np.float32))
    STOK = DictCollection("STOKs", dtt=TileType((4,), np.float32))
    DTOK = DictCollection("DTOKs", dtt=TileType((1,), np.float32))
    EMB3 = DictCollection("EMBs", dtt=TileType(model.q3_table().shape,
                                               np.float32))
    drafts = {"a": [1] * max(2, nt // 2), "b": [2, 3]}  # mixed lengths
    npos = seed_spec_superpool(model, kv3, DRAFT, DTOK, STOK, EMB3,
                               prompts, drafts)
    yield "llm_decode_spec", spec_superpool_ptg(
        kv3, DRAFT, O3, STOK, DTOK, EMB3, list(prompts),
        [npos[s] for s in prompts])

    kv4 = PagedKVCollection("KVb", page_size=4, num_heads=H, head_dim=D)
    pad = max(len(d) for d in drafts.values()) + 1
    QS = DictCollection("QSb", dtt=TileType((pad, 3, H, D), np.float32))
    LIM = DictCollection("LIMb", dtt=TileType((pad,), np.float32))
    DTOKS = DictCollection("DTOKSb", dtt=TileType((pad + 2,), np.float32))
    VOUT = DictCollection("VOUTb", dtt=TileType((pad + 2,), np.float32))
    npos_b, pad = seed_spec_batched_pool(model, kv4, QS, LIM, DTOKS,
                                         EMB3, prompts, drafts, pad=pad)
    yield "llm_decode_spec_batched", spec_batched_ptg(
        kv4, QS, LIM, DTOKS, VOUT, EMB3, list(prompts),
        [npos_b[s] for s in prompts], pad=pad)

    # the collective-tree pools (ISSUE 14, comm/collectives.py): the
    # staged broadcast's RW relay fan-out and the combining reduction's
    # per-slot guarded partial flows, at the default tree shape
    from ..comm.collectives import bcast_taskpool, reduce_taskpool
    yield "comm_bcast", bcast_taskpool(_vec("CB"), n=nt)
    yield "comm_reduce", reduce_taskpool(_vec("CR"), _vec("CO"), n=nt)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m parsec_tpu.analysis",
        description="static dataflow verification + runtime concurrency "
                    "lint (docs/ANALYSIS.md)")
    ap.add_argument("--graph", metavar="MODEL|JDF",
                    help="verify one graph: a model name (cholesky, lu, "
                         "pingpong, reduction, stencil1d, stencil2d, "
                         "tiled_gemm, all2all, llm_prefill, llm_decode, "
                         "llm_decode_k, llm_decode_spec, "
                         "llm_decode_spec_batched, comm_bcast, "
                         "comm_reduce) or a .jdf path")
    ap.add_argument("--bind", action="append", default=[],
                    metavar="NAME=INT", help="JDF global binding")
    ap.add_argument("--nt", type=int, default=5,
                    help="tile-grid size for model graphs (default 5)")
    ap.add_argument("--ranks", type=int, default=1,
                    help="verify for this many ranks (default 1)")
    ap.add_argument("--self-lint", action="store_true",
                    help="run runtimelint over parsec_tpu/ (or PATHs)")
    ap.add_argument("--comm", action="store_true",
                    help="derive every model pool's comm pattern "
                         "statically (commcheck; --ranks defaults to 4 "
                         "here so cross-rank edges exist)")
    ap.add_argument("paths", nargs="*", help="paths for --self-lint")
    ap.add_argument("-v", "--verbose", action="store_true",
                    help="also print warnings")
    args = ap.parse_args(argv)

    from . import check_jdf, check_ptg, lint_paths, lint_self
    failed = False
    run_all = not args.graph and not args.self_lint and not args.comm

    if args.comm:
        from . import check_comm
        ranks = args.ranks if args.ranks > 1 else 4
        for gname, tp in _model_graphs(args.nt, ranks=ranks):
            if args.graph and gname != args.graph:
                continue
            cr = check_comm(tp, nb_ranks=ranks)
            print(cr.summary())
            for f in cr.errors + (cr.warnings if args.verbose else []):
                print("  " + repr(f))
            failed |= not cr.ok
        return 1 if failed else 0

    if args.graph or run_all:
        if args.graph and args.graph.endswith(".jdf"):
            binds = dict((k, int(v)) for k, v in
                         (b.split("=", 1) for b in args.bind))
            reports = [check_jdf(args.graph, **binds)]
        elif args.graph:
            graphs = dict(_model_graphs(args.nt))
            if args.graph not in graphs:
                ap.error(f"unknown model {args.graph!r}; "
                         f"one of {sorted(graphs)}")
            reports = [check_ptg(graphs[args.graph], nb_ranks=args.ranks)]
        else:
            reports = [check_ptg(tp, nb_ranks=args.ranks)
                       for _name, tp in _model_graphs(args.nt)]
        for r in reports:
            print(r.summary())
            shown = r.errors + (r.warnings if args.verbose else [])
            for f in shown:
                print("  " + repr(f))
            failed |= not r.ok

    if args.self_lint or run_all:
        lr = lint_paths(args.paths) if args.paths else lint_self()
        print(lr.summary())
        for f in lr.errors + (lr.warnings if args.verbose else []):
            print("  " + repr(f))
        failed |= not lr.ok

    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
