#!/usr/bin/env python
"""chip_smoke.py — does the task runtime still start on the chip?

Drives the main path once through the entry points a user calls — the
lowering (``lower_taskpool``), the dynamic runtime (``Context`` over PTG and
DTD pools) and the resident server (``RuntimeServer``) — at the widths of the
repo's headline configuration (BASELINE.json: tiled GEMM at N=16384), on
seeded random tiles, and checks every result on the host in float64.  One
process; nothing here is a benchmark: the walls and compile seconds it prints
are observations of one run, labelled with the device.

    python chip_smoke.py [--seed N]

It refuses to start unless ``jax.devices()[0].platform == "tpu"``.  A stage
that fails raises; nothing is caught to continue.  On success the last line
of stdout is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

With four devices it adds the four-chip stages.  ``--rehearse-cpu`` (never
chosen automatically) runs every stage at toy sizes on CPU devices wrapped as
accelerators, with interpret-mode kernels, to debug the script itself; it
prints no result line.  ``--stages a,b`` runs a subset for debugging on the
chip and prints no result line either.

Tolerances: relative Frobenius residuals on probe vectors, each about one
order of magnitude above what the TPU v5 lite runs of PR 21 showed
(CHANGES.md has the readings), none looser than 5e-2:

- bf16 tiles, f32 accumulation (lowered GEMM): the host reference uses the
  same bf16 values, so only the f32 accumulation order differs — 2.2e-7
  observed;
- f32 tiles at default precision (dynamic, DTD and served GEMM): the MXU
  multiplies f32 operands in bf16 passes, about three decimal digits per
  product — 2.3e-3 to 2.4e-3 observed;
- Cholesky at default precision on ``make_spd_fast``'s diagonally dominant
  matrix: stayed finite, 2.3e-5 observed;
- tile QR at N=4096 with every product at the highest precision (f32):
  8.5e-7 and 5.8e-7 observed on its two gaps (PR 36);
- Pallas against its XLA twin: the same f32 taps in the same order — no
  difference observed on the chip, one ulp under the interpreter; ten ulp
  of O(1) values allowed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

TOL_BF16_IN_F32_ACC = 2e-6
TOL_F32_DEFAULT_PRECISION = 2.5e-2
TOL_CHOLESKY_DEFAULT_PRECISION = 2.5e-4
TOL_QR_F32 = 1e-5
TOL_PALLAS_VS_XLA_ABS = 1e-6
NPROBE = 4

# fixed integer per matrix: tile (m, n) of matrix i under --seed s is drawn
# from default_rng([s, i, m, n]) in every process and on every rank
MAT_A, MAT_B, MAT_PROBE, MAT_STENCIL = 1, 2, 3, 4


class SmokeFailure(RuntimeError):
    """A stage ran and its result or its accounting is wrong."""


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# seeded data and the float64 host reference
# ---------------------------------------------------------------------------

def tile_init(seed: int, mat: int, dtype, salt: int = 0):
    def init(m: int, n: int, shape):
        rng = np.random.default_rng([seed, mat, salt, m, n])
        return rng.standard_normal(shape, dtype=np.float32).astype(dtype)
    return init


def zeros_init(m: int, n: int, shape):
    return np.zeros(shape, np.float32)


def probes(seed: int, n: int) -> np.ndarray:
    return np.random.default_rng([seed, MAT_PROBE]).standard_normal(
        (n, NPROBE))


def host_tile(M, m: int, n: int) -> np.ndarray:
    """Tile (m, n) as float64, from the newest copy (flush the devices
    first: a dirty device copy would be pulled tile by tile here)."""
    return np.asarray(M.data_of(m, n).newest_copy().value).astype(np.float64)


def tiled_apply(M, X: np.ndarray, tiles=None) -> np.ndarray:
    """M @ X over the tiles of a tiled matrix (all, or the listed ones)."""
    Y = np.zeros((M.lm, X.shape[1]))
    if tiles is None:
        tiles = [(m, n) for m in range(M.mt) for n in range(M.nt)]
    for m, n in tiles:
        Y[m * M.mb:(m + 1) * M.mb] += host_tile(M, m, n) @ \
            X[n * M.nb:(n + 1) * M.nb]
    return Y


def rel_residual(got: np.ndarray, want: np.ndarray) -> float:
    require(bool(np.all(np.isfinite(got))), "non-finite values in the result")
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


# ---------------------------------------------------------------------------
# accounting: which device ran what, what was compiled
# ---------------------------------------------------------------------------

class DeviceLedger:
    """Executed-task counts per registered device since construction.
    Devices a stage's ``Context`` registers later count from zero."""

    def __init__(self) -> None:
        from parsec_tpu.device import registry
        self._registry = registry
        self._base = {id(d): d.executed_tasks for d in registry.devices}

    def deltas(self) -> tuple[dict[str, int], int]:
        tpu, cpu = {}, 0
        for d in self._registry.devices:
            n = d.executed_tasks - self._base.get(id(d), 0)
            if d.type == "cpu":
                cpu += n
            else:
                tpu[d.name] = n
        return tpu, cpu

    def check(self, pool_tasks: int, host_tasks: int = 0) -> dict[str, int]:
        """The accelerators ran exactly the pool's tasks, the host CPU
        device ran ``host_tasks`` (0 unless the stage says why not), and
        no accelerator was demoted on the way."""
        tpu, cpu = self.deltas()
        for d in self._registry.devices:
            require(d.enabled, f"device {d.name} was disabled (demoted)")
        require(bool(tpu), "no accelerator is registered")
        require(sum(tpu.values()) == pool_tasks,
                f"accelerators executed {tpu}, pool has {pool_tasks} tasks")
        require(cpu == host_tasks,
                f"host CPU device executed {cpu} tasks, expected "
                f"{host_tasks}")
        return tpu


class CompileMeter:
    """Counts XLA compile requests through ``jax.monitoring``: every
    request fires one backend-compile duration (a persistent-cache hit
    included); hits fire their own event, so fresh = requests - hits."""

    def __init__(self) -> None:
        import jax.monitoring as mon
        self.requests = self.hits = 0
        self.seconds = 0.0
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _duration(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.seconds += secs

    def snapshot(self) -> tuple[int, int, float]:
        return self.requests, self.hits, self.seconds


def accelerators():
    from parsec_tpu.device import registry
    return [d for d in registry.devices if d.type != "cpu"]


def sync_all() -> None:
    """Wait for every enqueued dispatch; a device-side failure raises here
    (and disables its device, which the ledger check then reports)."""
    for d in accelerators():
        d.sync()


def settle() -> None:
    """Write dirty tiles back and drop the residency, so one stage's tiles
    are not the next one's pressure (and the host check reads host
    copies)."""
    sync_all()
    for d in accelerators():
        d.flush_cache()


# ---------------------------------------------------------------------------
# stages
# ---------------------------------------------------------------------------

def gemm_operands(seed: int, n: int, nb: int, dtype, salt: int = 0,
                  cls=None, **kw):
    """Seeded A and B, zero C; ``salt`` tells apart the operand sets of one
    run.  Every set names its collections A, B and C, as every tenant of a
    server would."""
    from parsec_tpu.data_dist.matrix import TiledMatrix
    cls = cls or TiledMatrix
    A = cls("A", n, n, nb, nb, dtype=dtype,
            init_fn=tile_init(seed, MAT_A, dtype, salt), **kw)
    B = cls("B", n, n, nb, nb, dtype=dtype,
            init_fn=tile_init(seed, MAT_B, dtype, salt), **kw)
    C = cls("C", n, n, nb, nb, dtype=np.float32, init_fn=zeros_init, **kw)
    return A, B, C


def gemm_residual(seed: int, A, B, C) -> float:
    """‖C·x − A·(B·x)‖ / ‖A·(B·x)‖ on seeded probe vectors."""
    X = probes(seed, C.ln)
    return rel_residual(tiled_apply(C, X), tiled_apply(A, tiled_apply(B, X)))


def stage_lowered_gemm(cfg) -> str:
    """``lower_taskpool(tiled_gemm_ptg(A, B, C)).execute()``: the PTG
    collapses into one XLA contraction over the tile stores."""
    import jax.numpy as jnp

    from parsec_tpu.models.tiled_gemm import tiled_gemm_ptg
    from parsec_tpu.ptg.lowering import lower_taskpool

    n, nb = cfg.n_lowered, cfg.nb_lowered
    A, B, C = gemm_operands(cfg.seed, n, nb, np.dtype(jnp.bfloat16))
    low = lower_taskpool(tiled_gemm_ptg(A, B, C))
    require(low.mode == "chain-collapse", f"lowering mode is {low.mode}")
    out = low.execute()         # runs, then writes C's tiles back
    require(out["C"].shape == (n, n), f"C store is {out['C'].shape}")
    del out
    res = gemm_residual(cfg.seed, A, B, C)
    require(res < TOL_BF16_IN_F32_ACC, f"residual {res:.3e}")
    return (f"N={n} nb={nb} bf16->f32 tasks={(n // nb) ** 3} "
            f"mode={low.mode} residual={res:.3e}")


def stage_dynamic_gemm(cfg) -> str:
    """The README quick start: a bare ``Context()`` and the default
    ``devices="auto"`` bodies.  Nothing here registers a device."""
    from parsec_tpu.models.tiled_gemm import tiled_gemm_ptg
    from parsec_tpu.runtime import Context

    n, nb = cfg.n_dynamic, cfg.nb_dynamic
    A, B, C = gemm_operands(cfg.seed, n, nb, np.float32)
    ledger = DeviceLedger()
    calls0 = {d.name: d.xla_calls for d in accelerators()}

    ctx = Context()
    ctx.add_taskpool(tiled_gemm_ptg(A, B, C))
    ctx.wait(timeout=cfg.timeout)
    sync_all()
    ctx.fini()

    ntasks = (n // nb) ** 3
    per_dev = ledger.check(ntasks)
    calls = sum(d.xla_calls - calls0.get(d.name, 0) for d in accelerators())
    require(calls > 0, "no XLA call was counted")
    note = ""
    if len(per_dev) > 1:
        note = " " + check_spread(per_dev, ntasks, C, n // nb)
    settle()
    res = gemm_residual(cfg.seed, A, B, C)
    require(res < TOL_F32_DEFAULT_PRECISION, f"residual {res:.3e}")
    return (f"N={n} nb={nb} f32 tasks={ntasks} per_device={per_dev} "
            f"cpu_tasks=0 xla_calls={calls} residual={res:.3e}{note}")


def check_spread(per_dev: dict[str, int], ntasks: int, C, kt: int) -> str:
    """Several chips under one ``Context``: every chip worked, none did
    more than half, and no C tile's k-chain moved between chips (a tile
    that moved would have left a copy on each chip it visited).  Call
    before the caches are flushed."""
    require(all(v > 0 for v in per_dev.values()),
            f"an accelerator executed nothing: {per_dev}")
    require(max(per_dev.values()) <= ntasks // 2,
            f"one accelerator took more than half: {per_dev}")
    require(all(v % kt == 0 for v in per_dev.values()),
            f"a k-chain of {kt} tasks was split: {per_dev}")
    for m in range(C.mt):
        for n in range(C.nt):
            datum = C.data_of(m, n)
            on = [i for i in datum.device_copies if i != 0]
            require(len(on) == 1 and datum.owner_device == on[0],
                    f"C({m},{n}) has copies on devices {on}, owner "
                    f"{datum.owner_device}")
    return "k-chains stayed on one chip"


def cholesky_tasks(nt: int) -> int:
    """POTRF + TRSM + SYRK + GEMM over nt x nt lower tiles."""
    return nt + nt * (nt - 1) + nt * (nt - 1) * (nt - 2) // 6


def cholesky_residual(tiles: dict, a: np.ndarray, X: np.ndarray,
                      nb: int) -> float:
    """‖L(Lᵀx) − A·x‖ / ‖A·x‖ from the factored lower tiles (float64).
    The diagonal tiles keep A's strict upper part: only their tril is L."""
    want = np.concatenate([a[i:i + nb].astype(np.float64) @ X
                           for i in range(0, a.shape[0], nb)])
    L = {(m, k): np.tril(t) if m == k else np.asarray(t, np.float64)
         for (m, k), t in tiles.items()}
    Y = np.zeros_like(X)        # Lᵀ·X
    for (m, k), t in L.items():
        Y[k * nb:(k + 1) * nb] += t.T @ X[m * nb:(m + 1) * nb]
    got = np.zeros_like(X)      # L·(Lᵀ·X)
    for (m, k), t in L.items():
        got[m * nb:(m + 1) * nb] += t @ Y[k * nb:(k + 1) * nb]
    return rel_residual(got, want)


def stage_dynamic_cholesky(cfg) -> str:
    """``tiled_cholesky_ptg`` through ``Context``: four classes over a
    triangular space, at the default matmul precision."""
    from parsec_tpu.data_dist.matrix import SymTwoDimBlockCyclic
    from parsec_tpu.models.cholesky import make_spd_fast, tiled_cholesky_ptg
    from parsec_tpu.runtime import Context

    n, nb = cfg.n_dynamic, cfg.nb_dynamic
    nt = n // nb
    a = make_spd_fast(n, seed=cfg.seed)
    X = probes(cfg.seed, n)
    A = SymTwoDimBlockCyclic.from_dense("A", a, nb, nb)
    ledger = DeviceLedger()

    ctx = Context()
    ctx.add_taskpool(tiled_cholesky_ptg(A))
    ctx.wait(timeout=cfg.timeout)
    sync_all()
    ctx.fini()

    ntasks = cholesky_tasks(nt)
    per_dev = ledger.check(ntasks)
    settle()
    res = cholesky_residual(
        {(m, k): host_tile(A, m, k) for m in range(nt) for k in range(m + 1)},
        a, X, nb)
    require(res < TOL_CHOLESKY_DEFAULT_PRECISION, f"residual {res:.3e}")
    return (f"N={n} nb={nb} f32 precision=default tasks={ntasks} "
            f"per_device={per_dev} cpu_tasks=0 all finite "
            f"residual={res:.3e}")


def run_qr(cfg, n: int, nb: int) -> tuple[dict[str, int], list[float], int]:
    """``tiled_qr_ptg`` through one bare ``Context`` at (n, nb): which
    accelerator ran how many tasks, both of the benchmark's gaps
    (``benchmarks/reference_qr.py``) on seeded probes, the pool's tasks."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "benchmarks"))
    import reference_qr as refq
    from parsec_tpu.data_dist.matrix import TwoDimBlockCyclic
    from parsec_tpu.models.qr import tiled_qr_ptg
    from parsec_tpu.runtime import Context

    nt = n // nb
    tiles = refq.qr_tiles(cfg.seed, n, nb)
    X = probes(cfg.seed, n)
    A = TwoDimBlockCyclic("A", n, n, nb, nb, dtype=np.float32,
                          init_fn=lambda m, k, shape: tiles[m, k])
    T = TwoDimBlockCyclic("T", n, n, nb, nb, dtype=np.float32)
    ledger = DeviceLedger()

    ctx = Context()
    ctx.add_taskpool(tiled_qr_ptg(A, T))
    ctx.wait(timeout=cfg.timeout)
    sync_all()
    ctx.fini()

    ntasks = nt + nt * (nt - 1) + (nt - 1) * nt * (2 * nt - 1) // 6
    per_dev = ledger.check(ntasks)
    settle()
    got = refq.qr_got(
        {key: host_tile(A, *key) for key in tiles},
        {(m, k): host_tile(T, m, k)
         for m in range(nt) for k in range(m + 1)}, X, nb)
    ax = refq.apply(tiles, X, nb)
    res = [rel_residual(g, w)
           for g, w in zip(got, (ax, refq.apply_t(tiles, ax, nb)))]
    require(max(res) < TOL_QR_F32, f"residuals {res}")
    return per_dev, res, ntasks


def stage_dynamic_qr(cfg) -> str:
    """``tiled_qr_ptg`` through ``Context``: four classes that write two or
    three tiles a task into two collections, every product in f32."""
    n, nb = cfg.n_qr, cfg.nb_qr
    per_dev, res, ntasks = run_qr(cfg, n, nb)
    return (f"N={n} nb={nb} f32 precision=highest tasks={ntasks} "
            f"per_device={per_dev} cpu_tasks=0 all finite "
            f"|Q(Rx)-Ax|={res[0]:.3e} |RtRx-AtAx|={res[1]:.3e}")


def stage_ctx4_qr(cfg) -> str:
    """The benchmark's ``geqrf52k.ctx4`` in small: the tile QR at 16 x 16
    tiles under one ``Context`` over four chips.  ``best_device`` deals the
    tile columns, a flood hands another chip's tasks back to the scheduler,
    V and T tiles cross from chip to chip: every chip ran tasks, none more
    than 40% of them, and tiles did cross."""
    n, nb = cfg.n_ctx4, cfg.nb_ctx4
    base = {d.name: (d.bytes_d2d, d.flood_putbacks) for d in accelerators()}
    per_dev, res, ntasks = run_qr(cfg, n, nb)
    require(len(per_dev) == 4 and min(per_dev.values()) > 0,
            f"an accelerator executed nothing: {per_dev}")
    require(max(per_dev.values()) <= 0.4 * ntasks,
            f"one accelerator took more than 40%: {per_dev}")
    d2d = sum(d.bytes_d2d - base.get(d.name, (0, 0))[0]
              for d in accelerators())
    putbacks = sum(d.flood_putbacks - base.get(d.name, (0, 0))[1]
                   for d in accelerators())
    require(d2d > 0, "no tile crossed from chip to chip")
    return (f"N={n} nb={nb} f32 precision=highest tasks={ntasks} "
            f"per_device={per_dev} cpu_tasks=0 d2d_GB={d2d / 1e9:.3f} "
            f"putbacks={putbacks} |Q(Rx)-Ax|={res[0]:.3e} |RtRx-AtAx|={res[1]:.3e}")


def stage_dtd_gemm(cfg) -> str:
    """GEMM tasks inserted at run time by the library's insertion program
    (``tiled_gemm_dtd``), hazards discovered from the tile access chains,
    bodies resolved by kernel name, every result tile pushed out at its
    last k."""
    from parsec_tpu.dtd import DTDTaskpool
    from parsec_tpu.models.tiled_gemm import tiled_gemm_dtd
    from parsec_tpu.runtime import Context

    n, nb = cfg.n_dtd, cfg.nb_dynamic
    nt = n // nb
    A, B, C = gemm_operands(cfg.seed, n, nb, np.float32, salt=1)
    ledger = DeviceLedger()
    pushed0 = sum(d.pushouts for d in accelerators())
    ctx = Context()
    tp = DTDTaskpool()
    ctx.add_taskpool(tp)
    tiled_gemm_dtd(tp, A, B, C)
    tp.wait(timeout=cfg.timeout)
    sync_all()
    ctx.fini()
    per_dev = ledger.check(nt ** 3)
    pushed = sum(d.pushouts for d in accelerators()) - pushed0
    require(pushed == nt * nt, f"{pushed} push-outs for {nt * nt} tiles")
    settle()
    res = gemm_residual(cfg.seed, A, B, C)
    require(res < TOL_F32_DEFAULT_PRECISION, f"residual {res:.3e}")
    return (f"N={n} nb={nb} f32 tasks={nt ** 3} per_device={per_dev} "
            f"cpu_tasks=0 pushouts={pushed} residual={res:.3e}")


def stage_server(cfg) -> str:
    """A resident ``RuntimeServer``: two tenants, eight device-backed GEMM
    pools through ``submit`` and two through ``submit_lowered``."""
    from parsec_tpu.models.tiled_gemm import tiled_gemm_ptg
    from parsec_tpu.serve import RuntimeServer

    n, nb = cfg.n_served, cfg.nb_served
    ndyn, nlow = 8, 2
    ops = [gemm_operands(cfg.seed, n, nb, np.float32, salt=10 + i)
           for i in range(ndyn + nlow)]
    ledger = DeviceLedger()
    server = RuntimeServer(nb_cores=2)
    tickets = []
    for i, (A, B, C) in enumerate(ops):
        submit = server.submit if i < ndyn else server.submit_lowered
        tickets.append(submit(tiled_gemm_ptg(A, B, C),
                              tenant=f"tenant{i % 2}"))
    results = [t.result(timeout=cfg.timeout) for t in tickets]
    server.drain(timeout=cfg.timeout)
    sync_all()
    stats = server.stats()
    require(stats["completed"] == ndyn + nlow and stats["failed"] == 0
            and stats["rejected"] == 0, f"server stats {stats}")
    alive = [t.name for t in threading.enumerate()
             if t.name.startswith("parsec-es")]
    require(not alive, f"worker threads alive after drain: {alive}")
    # each lowered submission is driven by one host task of the server's
    # wrapper pool (serve/server.py:submit_lowered): those, and no GEMM
    # task, are what the host CPU device may count
    per_dev = ledger.check(ndyn * (n // nb) ** 3, host_tasks=nlow)
    settle()
    X = probes(cfg.seed, n)
    worst = 0.0
    for i, (A, B, C) in enumerate(ops):
        want = tiled_apply(A, tiled_apply(B, X))
        if i < ndyn:
            got = tiled_apply(C, X)
        else:
            dense = results[i]["C"]
            require(dense.shape == (n, n), f"lowered C is {dense.shape}")
            got = dense.astype(np.float64) @ X
        res = rel_residual(got, want)
        require(res < TOL_F32_DEFAULT_PRECISION,
                f"pool {i}: residual {res:.3e}")
        worst = max(worst, res)
    return (f"pools={ndyn}+{nlow} lowered, each N={n} nb={nb} "
            f"tasks={(n // nb) ** 3}; completed={stats['completed']} "
            f"failed=0 rejected=0 per_device={per_dev} "
            f"cpu_tasks={nlow} (the lowered drivers) "
            f"worst residual={worst:.3e}")


def stage_kernels(cfg) -> str:
    """Every Pallas kernel in the tree, compiled by Mosaic, against its
    XLA twin at the shape ``run_stencil_bench``'s default tiles have."""
    from parsec_tpu.ops.stencil import stencil1d_pallas, stencil1d_xla

    rows, mb, radius = cfg.stencil_rows, cfg.stencil_mb, 4
    w = np.full(2 * radius + 1, 1.0 / (2 * radius + 1))
    p = np.random.default_rng([cfg.seed, MAT_STENCIL]).standard_normal(
        (rows, mb + 2 * radius), dtype=np.float32)
    got = np.asarray(stencil1d_pallas(p, w, interpret=cfg.rehearse))
    want = np.asarray(stencil1d_xla(p, w))
    require(got.shape == (rows, mb), f"stencil output is {got.shape}")
    err = float(np.max(np.abs(got.astype(np.float64) - want)))
    require(np.isfinite(err) and err < TOL_PALLAS_VS_XLA_ABS,
            f"stencil1d_pallas vs stencil1d_xla: max abs diff {err:.3e}")
    mode = "interpret" if cfg.rehearse else "mosaic"
    return (f"stencil1d_pallas[{mode}] rows={rows}x{mb}+{2 * radius} f32 "
            f"max_abs_diff={err:.3e}")


def stage_four_ranks(cfg) -> str:
    """Four in-process ranks over the device fabric: a 2x2 block-cyclic
    Cholesky (BASELINE.json config 5) with the default device bodies,
    rank r held to chip r, panel tiles pulled chip to chip.  Not the
    GEMM: every rank of the repo's multi-rank GEMM materializes the
    operand tiles it reads from its own ``init_fn``, so no tile crosses
    ranks there (PR 21 finding)."""
    import jax

    from parsec_tpu.comm import run_multirank
    from parsec_tpu.data_dist.matrix import SymTwoDimBlockCyclic
    from parsec_tpu.models.cholesky import make_spd_fast, tiled_cholesky_ptg

    n, nb = cfg.n_dynamic, cfg.nb_dynamic
    nt = n // nb
    a = make_spd_fast(n, seed=cfg.seed)
    ledger = DeviceLedger()

    def body(ctx, rank, nranks):
        A = SymTwoDimBlockCyclic.from_dense("A", a, nb, nb, P=2, Q=2,
                                            myrank=rank)
        (dev,) = ctx.accelerators()
        before = dev.executed_tasks
        ctx.add_taskpool(tiled_cholesky_ptg(A))
        ctx.wait(timeout=cfg.timeout)
        dev.sync()
        ctx.comm_barrier()
        ran = dev.executed_tasks - before
        dev.flush_cache()
        mine = {(m, k): host_tile(A, m, k) for m in range(nt)
                for k in range(m + 1) if A.rank_of(m, k) == rank}
        return dev.jax_device.id, ran, ctx.comm_engine.ce.bytes_got, mine

    parts = run_multirank(4, body, transport="device", timeout=cfg.timeout)
    ntasks = cholesky_tasks(nt)
    per_dev = ledger.check(ntasks)
    tiles, moved = {}, []
    for rank, (dev_id, ran, bytes_got, mine) in enumerate(parts):
        require(dev_id == jax.devices()[rank].id,
                f"rank {rank} ran on device {dev_id}")
        require(ran > 0, f"rank {rank} executed nothing")
        require(bytes_got > 0, f"rank {rank} pulled no bytes chip to chip")
        moved.append(bytes_got)
        tiles.update(mine)
    require(len(tiles) == nt * (nt + 1) // 2, f"{len(tiles)} lower tiles")
    settle()
    res = cholesky_residual(tiles, a, probes(cfg.seed, n), nb)
    require(res < TOL_CHOLESKY_DEFAULT_PRECISION, f"residual {res:.3e}")
    return (f"N={n} nb={nb} f32 2x2 block-cyclic Cholesky tasks={ntasks} "
            f"rank r on chip r per_device={per_dev} cpu_tasks=0 "
            f"bytes_got={moved} residual={res:.3e}")


def stage_mesh_lowered(cfg) -> str:
    """``lower_taskpool(tp, mesh=...)``: the distributed GEMM as one SPMD
    program, tile shardings from the block-cyclic distribution."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from parsec_tpu.data_dist.matrix import TwoDimBlockCyclic
    from parsec_tpu.models.tiled_gemm import tiled_gemm_ptg
    from parsec_tpu.ptg.lowering import lower_taskpool

    n, nb = cfg.n_lowered, cfg.nb_lowered
    A, B, C = gemm_operands(cfg.seed, n, nb, np.dtype(jnp.bfloat16),
                            cls=TwoDimBlockCyclic, P=2, Q=2)
    mesh = Mesh(np.array(jax.devices()[:4]), ("ranks",))
    low = lower_taskpool(tiled_gemm_ptg(A, B, C), mesh=mesh)
    out = low.execute()
    on = sorted({s.device.id for s in out["C"].addressable_shards})
    require(len(on) == 4, f"C store has shards on devices {on}")
    del out
    res = gemm_residual(cfg.seed, A, B, C)
    require(res < TOL_BF16_IN_F32_ACC, f"residual {res:.3e}")
    return (f"N={n} nb={nb} bf16->f32 mode={low.mode} C shards on "
            f"devices {on} residual={res:.3e}")


STAGES = {
    "lowered_gemm": stage_lowered_gemm,
    "dynamic_gemm": stage_dynamic_gemm,
    "dynamic_cholesky": stage_dynamic_cholesky,
    "dynamic_qr": stage_dynamic_qr,
    "dtd_gemm": stage_dtd_gemm,
    "server": stage_server,
    "kernels": stage_kernels,
}
FOUR_CHIP_STAGES = {
    "four_ranks": stage_four_ranks,
    "mesh_lowered": stage_mesh_lowered,
    "ctx4_qr": stage_ctx4_qr,
}


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every tile, matrix and probe vector")
    ap.add_argument("--rehearse-cpu", dest="rehearse", action="store_true",
                    help="toy sizes on CPU devices, interpret-mode "
                    "kernels: debugs this script, proves nothing about "
                    "a chip, prints no result")
    ap.add_argument("--stages", default="",
                    help="comma list of stages to run (debugging on the "
                    "chip); a partial run prints no result")
    cfg = ap.parse_args(argv)
    cfg.timeout = 900.0
    if cfg.rehearse:
        cfg.n_lowered, cfg.nb_lowered = 512, 128
        cfg.n_dynamic, cfg.nb_dynamic = 512, 128
        cfg.n_qr, cfg.nb_qr = 512, 128
        cfg.n_ctx4, cfg.nb_ctx4 = 1024, 64
        cfg.n_dtd = 256
        cfg.n_served, cfg.nb_served = 256, 64
        cfg.stencil_rows, cfg.stencil_mb = 16, 1024
    else:
        cfg.n_lowered, cfg.nb_lowered = 16384, 512      # BASELINE headline
        cfg.n_dynamic, cfg.nb_dynamic = 16384, 1024
        cfg.n_qr, cfg.nb_qr = 4096, 512
        cfg.n_ctx4, cfg.nb_ctx4 = 8192, 512
        cfg.n_dtd = 8192
        cfg.n_served, cfg.nb_served = 4096, 512
        cfg.stencil_rows, cfg.stencil_mb = 16, 1 << 16  # run_stencil_bench

    import jax
    dev0 = jax.devices()[0]
    if cfg.rehearse:
        if dev0.platform != "cpu":
            print(f"--rehearse-cpu needs JAX_PLATFORMS=cpu, found "
                  f"{dev0.platform}", file=sys.stderr)
            return 2
        print("REHEARSAL on cpu — not a chip result", flush=True)
        # read when the device module registers its params
        os.environ["PARSEC_MCA_device_tpu_allow_cpu"] = "1"
    elif dev0.platform != "tpu":
        print(f"chip_smoke: no TPU: jax.devices()[0].platform is "
              f"{dev0.platform!r}; refusing to run a stage",
              file=sys.stderr)
        return 2

    import jaxlib
    from importlib import metadata

    from parsec_tpu import native
    from parsec_tpu.device.compile_cache import ensure_compile_cache
    cache_dir = ensure_compile_cache()
    meter = CompileMeter()
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    ndev = len(jax.devices())
    label = f"{dev0.device_kind} x{ndev}"
    print(f"[smoke] platform={dev0.platform} device_kind={dev0.device_kind} "
          f"devices={ndev} jax={jax.__version__} jaxlib={jaxlib.__version__} "
          f"libtpu={libtpu} compile_cache={cache_dir} seed={cfg.seed}",
          flush=True)
    # the native core, from source: a .so the tool copied along proves
    # nothing about this machine's toolchain
    require(native.ensure_built(force=True) is not None
            and native.available(), "the native core did not build")
    print("[smoke] native core: built from src/core.cpp and loaded",
          flush=True)

    stages = dict(STAGES)
    if ndev == 4:
        stages.update(FOUR_CHIP_STAGES)
    else:
        print(f"[smoke] four-chip stages: not run ({ndev} device"
              f"{'s' if ndev != 1 else ''})", flush=True)
    chosen = [s for s in cfg.stages.split(",") if s]
    unknown = [s for s in chosen if s not in stages]
    if unknown:
        print(f"unknown or unavailable stages {unknown}; have "
              f"{list(stages)}", file=sys.stderr)
        return 2

    t_all = time.perf_counter()
    for name, fn in stages.items():
        if chosen and name not in chosen:
            continue
        req0, hit0, sec0 = meter.snapshot()
        t0 = time.perf_counter()
        info = fn(cfg)
        wall = time.perf_counter() - t0
        req1, hit1, sec1 = meter.snapshot()
        print(f"[smoke] {name}: PASS {info} | observed on {label}: "
              f"wall={wall:.1f}s (data and host check included) "
              f"compile={sec1 - sec0:.1f}s programs={req1 - req0} "
              f"fresh={(req1 - req0) - (hit1 - hit0)} "
              f"cache_hits={hit1 - hit0}", flush=True)
    req, hit, sec = meter.snapshot()
    print(f"[smoke] all stages passed in {time.perf_counter() - t_all:.1f}s "
          f"on {label}; compile={sec:.1f}s programs={req} "
          f"fresh={req - hit} cache_hits={hit}", flush=True)
    for d in accelerators():
        require(d.enabled, f"device {d.name} ended disabled")

    if cfg.rehearse or chosen:
        print("[smoke] rehearsal or partial run: no result", flush=True)
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": dev0.platform, "kind": dev0.device_kind,
        "count": ndev}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
