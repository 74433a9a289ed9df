"""The phase plane (ISSUE 27, ``prof/spans.py``): every host second of a
dynamic solve gets an owner on the profiler's clock, and costs nothing while
nobody looks.  CPU devices wrapped as accelerators, N=1024 nb=128; only what
repeats exactly is asserted (names, counts, sums against the solve's own
walls), never a duration."""

import time

import numpy as np
import pytest

import jax

from parsec_tpu.data_dist.matrix import SymTwoDimBlockCyclic, TiledMatrix
from parsec_tpu.device import registry
from parsec_tpu.device.tpu import TPUDevice
from parsec_tpu.prof import spans
from parsec_tpu.runtime import Context

N, NB = 1024, 128
NT = N // NB
# docs/OBSERVABILITY.md, "The phase plane": the names are the contract
DOCUMENTED = {
    "ctx.init", "ctx.add_taskpool", "ctx.progress", "ctx.fini",
    "devmod.manage", "sched.flood", "devmod.prefetch", "devmod.stage_in",
    "devmod.dispatch", "devmod.inflight_wait", "devmod.sync",
    "devmod.complete", "sched.release", "devmod.pushout", "devmod.drain",
    "devmod.writeback", "devmod.pressure"}
WALLS = ("t_stage_in", "t_dispatch", "t_complete", "t_drain", "t_writeback")


def _gemm():
    from parsec_tpu.models.tiled_gemm import tiled_gemm_ptg
    rng = np.random.default_rng(27)
    a = rng.standard_normal((N, N)).astype(np.float32)
    A = TiledMatrix.from_dense("A", a, NB, NB)
    B = TiledMatrix.from_dense("B", a.T.copy(), NB, NB)
    C = TiledMatrix("C", N, N, NB, NB)
    return tiled_gemm_ptg(A, B, C), NT ** 3, {"gemm"}, NT * NT


def _cholesky():
    from parsec_tpu.models.cholesky import tiled_cholesky_ptg
    rng = np.random.default_rng(27)
    m = rng.standard_normal((N, N)).astype(np.float32)
    spd = m @ m.T / N + 2 * np.eye(N, dtype=np.float32)
    A = SymTwoDimBlockCyclic(
        "A", N, N, NB, NB, dtype=np.float32,
        init_fn=lambda i, k, shape: np.ascontiguousarray(
            spd[i * NB:(i + 1) * NB, k * NB:(k + 1) * NB]))
    tasks = NT + NT * (NT - 1) + NT * (NT - 1) * (NT - 2) // 6
    return (tiled_cholesky_ptg(A), tasks, {"trsm_rlt", "syrk_ln", "gemm_nt"},
            NT * (NT + 1) // 2)


PROBLEMS = {"gemm": _gemm, "cholesky": _cholesky}


@pytest.fixture
def one_accelerator(param, monkeypatch, device_registry):
    """One CPU device wrapped as the accelerator, and the plane left as it
    was found: off, its table empty."""
    param("device_tpu_allow_cpu", True)
    real = jax.devices
    monkeypatch.setattr(jax, "devices", lambda *a: real(*a)[:1])
    spans.phase_reset()
    yield
    spans.phase_refresh()
    spans.phase_reset()
    assert not spans.phase_on


def _solve(pool):
    """One solve as the benchmark's dynamic path makes it; its wall, the
    deltas of the device's walls and counters over it, and the device."""
    t0 = time.perf_counter()
    ctx = Context(nb_cores=0)
    (dev,) = [d for d in registry.devices if isinstance(d, TPUDevice)]
    before = {k: getattr(dev, k) for k in WALLS + ("executed_tasks",
                                                   "xla_calls")}
    ctx.add_taskpool(pool)
    ctx.wait(timeout=120)
    dev.sync()
    dev.flush_cache()
    ctx.fini()
    wall = time.perf_counter() - t0
    return wall, {k: getattr(dev, k) - v for k, v in before.items()}, dev


class _Counted:
    """Stands in for ``jax.profiler.TraceAnnotation``: counts what is
    built."""
    built = 0

    def __init__(self, name):
        type(self).built += 1

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        pass


@pytest.mark.parametrize("problem", PROBLEMS)
def test_off_a_solve_builds_nothing_and_asks_once_per_batch(
        problem, one_accelerator, monkeypatch):
    pool, tasks, _, _ = PROBLEMS[problem]()
    spans.phase_refresh()       # binds the profiler's probe
    asked = []
    probe = spans._session_active
    monkeypatch.setattr(spans, "_session_active",
                        lambda: asked.append(1) or probe())
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Counted)
    _Counted.built = 0
    _, delta, _ = _solve(pool)
    assert delta["executed_tasks"] == tasks
    assert spans.phase_totals() == {}
    assert _Counted.built == 0
    # Context init, add_taskpool, sync, flush_cache, fini: five a solve
    assert len(asked) <= delta["xla_calls"] + 5, (len(asked), delta)
    assert delta["xla_calls"] < tasks       # per batch is not per task


@pytest.mark.parametrize("how", ["profiler_session", "prof_spans"])
@pytest.mark.parametrize("problem", PROBLEMS)
def test_on_every_second_of_a_solve_has_a_documented_owner(
        problem, how, one_accelerator, param, tmp_path):
    pool, tasks, classes, result_tiles = PROBLEMS[problem]()
    if how == "prof_spans":
        param("prof_spans", True)
    else:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        wall, delta, dev = _solve(pool)
    finally:
        if how == "prof_spans":
            param("prof_spans", False)
            spans.uninstall()       # Context installed the request recorder
        else:
            jax.profiler.stop_trace()
    table = spans.phase_totals()
    assert set(table) <= DOCUMENTED
    # a solve that fits the budget waits for nothing and makes no room, and
    # ``devmod.prefetch`` is ``prefetch_data``'s (the KV tiers' call): every
    # other name shows
    assert set(table) >= DOCUMENTED - {"devmod.inflight_wait",
                                       "devmod.pressure",
                                       "devmod.prefetch"}, set(table)
    assert "devmod.pressure" not in table
    assert dev.pressure_confirms == 0 and dev.evicted_bytes == 0

    # a wall and its spans are fed from one pair of clock readings
    for names, attr in ((("devmod.stage_in",), "t_stage_in"),
                        (("devmod.dispatch",), "t_dispatch"),
                        (("devmod.complete",), "t_complete"),
                        (("devmod.drain",), "t_drain"),
                        (("devmod.writeback",), "t_writeback")):
        inclusive = sum(table[n][1] for n in names) / 1e9
        assert inclusive == pytest.approx(delta[attr], rel=0.02), attr
    owned = sum(row[0] for row in table.values()) / 1e9
    assert 0.9 * wall <= owned <= wall, (owned, wall)
    assert all(row[0] >= 0 for row in table.values()), table
    assert table["sched.release"][2] == tasks == delta["executed_tasks"]
    # a counter per memory edge, inside the release and off its self time
    assert table["devmod.pushout"][2] == result_tiles
    assert table["sched.release"][1] - table["sched.release"][0] \
        == table["devmod.pushout"][1]
    # one span a batch or a solve, none a task
    assert table["devmod.dispatch"][2] == delta["xla_calls"]
    assert table["ctx.init"][2] == table["devmod.writeback"][2] == 1
    fused = {fn.__name__ for fn in dev._vmap_cache.values()}
    assert fused >= {f"fused_{c}" for c in classes}, fused
    assert all(name.startswith("fused_") for name in fused)


def test_under_a_tight_budget_the_pressure_has_its_own_span(one_accelerator,
                                                            param):
    """``devmod.pressure`` opens only when the budget presses, under
    ``devmod.manage``; its self time is the confirming and the queueing of
    evictions, the wait stays ``devmod.inflight_wait``'s, and the solve's
    seconds are owned as before."""
    pool, tasks, _, _ = PROBLEMS["cholesky"]()
    param("prof_spans", True)
    Context(nb_cores=0).fini()         # registers the accelerator
    (dev,) = [d for d in registry.devices if isinstance(d, TPUDevice)]
    dev._mem_budget = 40 * NB * NB * 4     # the triangle is 36 tiles
    spans.phase_reset()
    try:
        wall, delta, dev = _solve(pool)
    finally:
        param("prof_spans", False)
        spans.uninstall()
    table = spans.phase_totals()
    assert set(table) <= DOCUMENTED and delta["executed_tasks"] == tasks
    self_ns, inclusive_ns, count = table["devmod.pressure"]
    assert count >= 1 and dev.pressure_confirms >= 1
    assert 0 <= self_ns <= inclusive_ns
    # the waits it caused are inside it and not its own
    assert table["devmod.inflight_wait"][2] >= dev.pressure_confirms
    owned = sum(row[0] for row in table.values()) / 1e9
    assert 0.9 * wall <= owned <= wall, (owned, wall)


# the DTD front end's two rows (PR 34), on top of the ones above
DTD_DOCUMENTED = {"dtd.insert", "dtd.window"}


def test_a_dtd_solve_owns_its_insertion_and_its_window_drives(
        one_accelerator, param):
    """``dtd.insert`` is a counter per inserted task, outside any span on the
    client's thread; ``dtd.window`` one inclusive span per
    execute-and-come-back with ``ctx.progress`` and the device module's spans
    nested in it, so its self time is next to nothing and the solve's seconds
    stay owned."""
    from parsec_tpu.dtd import DTDTaskpool
    from parsec_tpu.models.tiled_gemm import tiled_gemm_dtd
    rng = np.random.default_rng(34)
    a = rng.standard_normal((N, N)).astype(np.float32)
    colls = (TiledMatrix.from_dense("A", a, NB, NB),
             TiledMatrix.from_dense("B", a.T.copy(), NB, NB),
             TiledMatrix("C", N, N, NB, NB))
    param("dtd_window_size", 64)
    param("dtd_threshold_size", 32)
    param("prof_spans", True)
    try:
        t0 = time.perf_counter()
        ctx = Context(nb_cores=0)
        (dev,) = [d for d in registry.devices if isinstance(d, TPUDevice)]
        tp = DTDTaskpool()
        ctx.add_taskpool(tp)
        tiled_gemm_dtd(tp, *colls)
        tp.wait(timeout=120)
        dev.sync()
        dev.flush_cache()
        ctx.fini()
        wall = time.perf_counter() - t0
    finally:
        param("prof_spans", False)
        spans.uninstall()
    table = spans.phase_totals()
    assert DTD_DOCUMENTED <= set(table) <= DOCUMENTED | DTD_DOCUMENTED
    assert table["dtd.insert"][2] == tp.inserted == NT ** 3
    assert table["dtd.insert"][0] == table["dtd.insert"][1]     # no child
    assert table["dtd.window"][2] == tp.window_drives >= 1
    # each drive, and the wait, is one ctx.progress; the drives' lie inside
    # dtd.window, whose own time is what is left around them
    assert table["ctx.progress"][2] == tp.window_drives + 1
    self_ns, inclusive_ns, _ = table["dtd.window"]
    assert 0 <= self_ns <= 0.05 * inclusive_ns
    assert inclusive_ns < table["ctx.progress"][1]
    assert table["sched.release"][2] == NT ** 3
    assert table["devmod.pushout"][2] == NT * NT
    owned = sum(row[0] for row in table.values()) / 1e9
    assert 0.8 * wall <= owned <= wall, (owned, wall)
