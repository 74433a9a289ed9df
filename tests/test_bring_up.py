"""Start-up and device selection (ISSUE 21): what has to hold for the
normal entry points to reach an accelerator, rehearsed on the 8-device CPU
mesh with ``device_tpu_allow_cpu`` wrapping CPU devices as accelerators.
The chip itself is ``chip_smoke.py``'s business."""

import os
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

import jax

from parsec_tpu.data_dist.matrix import TiledMatrix
from parsec_tpu.device import registry
from parsec_tpu.device import tpu as tpu_mod
from parsec_tpu.device.tpu import TPUDevice, init_tpu_devices
from parsec_tpu.models.tiled_gemm import tiled_gemm_ptg
from parsec_tpu.runtime import Context

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def wrapped_cpus(param, monkeypatch, device_registry):
    """``device_tpu_allow_cpu`` with the first ``n`` CPU devices visible;
    whatever a ``Context`` registers is dropped again afterwards."""
    param("device_tpu_allow_cpu", True)
    real = jax.devices

    def show(n):
        monkeypatch.setattr(jax, "devices", lambda *a: real(*a)[:n])

    return show


def _gemm(n=64, nb=16, seed=0):
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((n, n)).astype(np.float32)
    b = rng.standard_normal((n, n)).astype(np.float32)
    A = TiledMatrix.from_dense("A", a, nb, nb)
    B = TiledMatrix.from_dense("B", b, nb, nb)
    C = TiledMatrix("C", n, n, nb, nb)
    return a, b, A, B, C


def _accels():
    return [d for d in registry.devices if isinstance(d, TPUDevice)]


# -- A: the normal entry points reach the accelerator -----------------------

def test_bare_context_registers_accelerators_and_auto_pool_uses_them(
        wrapped_cpus):
    """The README quick start: no explicit ``init_tpu_devices()``."""
    wrapped_cpus(1)
    from parsec_tpu.device.device import cpu_device
    a, b, A, B, C = _gemm()
    cpu0 = cpu_device.executed_tasks
    ctx = Context()
    ctx.add_taskpool(tiled_gemm_ptg(A, B, C))
    ctx.wait(timeout=60)
    (dev,) = _accels()
    dev.sync()
    ctx.fini()
    assert dev.executed_tasks == 4 ** 3 and dev.enabled
    assert cpu_device.executed_tasks == cpu0      # no numpy body ran
    np.testing.assert_allclose(C.to_dense(), a @ b, rtol=1e-4, atol=1e-4)


def test_tiles_of_equal_key_stage_in_as_different_data(accel_device):
    """Two tenants both call a matrix A: their tiles (A, 0, 0) are equal
    keys of different data.  One batched stage-in (a prefetch mixes the
    pending tasks of every pool) must land each task's own bytes, and the
    cache must hold both."""
    from parsec_tpu.data.data import ACCESS_READ, data_create
    flow = SimpleNamespace(is_ctl=False, flow_index=0, access=ACCESS_READ)
    tasks = []
    for fill in (1.0, 2.0):
        datum = data_create(np.full((4, 4), fill, np.float32),
                            key=("A", 0, 0))
        tasks.append(SimpleNamespace(
            task_class=SimpleNamespace(flows=[flow]),
            data=[datum.get_copy(0)]))
    accel_device.stage_in_many(tasks)
    assert [float(t.data[0].value[0, 0]) for t in tasks] == [1.0, 2.0]
    assert len(accel_device._mem_lru) == 2


def test_registration_is_once_per_process(wrapped_cpus):
    wrapped_cpus(2)
    first = init_tpu_devices()
    Context().fini()
    Context().fini()
    assert init_tpu_devices() == first and len(_accels()) == 2


def test_cpu_backend_registers_nothing_without_allow_cpu():
    before = list(registry.devices)
    Context().fini()
    assert registry.devices == before


def test_cpu_bodies_are_counted_on_the_cpu_device():
    """Both routes a host body can take: the dynamic scheduler's chore
    walk and the compiled-DAG executor."""
    from parsec_tpu.device.device import cpu_device
    for threads in (0, 2):
        a, b, A, B, C = _gemm()
        before = cpu_device.executed_tasks
        ctx = Context(nb_cores=threads)
        ctx.add_taskpool(tiled_gemm_ptg(A, B, C, devices="cpu"))
        ctx.wait(timeout=60)
        ctx.fini()
        assert cpu_device.executed_tasks - before == 4 ** 3


def test_failed_probe_propagates_and_does_not_latch(wrapped_cpus,
                                                    monkeypatch):
    wrapped_cpus(1)

    def dead(*a):
        raise RuntimeError("Unable to initialize backend 'tpu'")

    with monkeypatch.context() as m:
        m.setattr(jax, "devices", dead)
        with pytest.raises(RuntimeError, match="Unable to initialize"):
            Context()
    assert not _accels()
    Context().fini()                    # the next probe registers
    assert len(_accels()) == 1


def test_unknown_device_kind_raises():
    assert tpu_mod._flop_rating("tpu v5 lite") == (197_000.0, 98_500.0)
    assert tpu_mod._flop_rating("cpu") == tpu_mod._CPU_STANDIN_GFLOPS
    with pytest.raises(ValueError, match="unknown accelerator"):
        tpu_mod._flop_rating("tpu v9 hyper")


def test_accelerator_without_memory_stats_raises():
    fake = SimpleNamespace(id=0, platform="tpu", device_kind="TPU v5 lite",
                           memory_stats=lambda: None)
    with pytest.raises(RuntimeError, match="no memory limit"):
        TPUDevice(fake)


# -- C: the compile cache is placed from outside -----------------------------

def test_cache_dir_from_environment_is_left_to_jax(monkeypatch):
    from parsec_tpu.device import compile_cache
    updates = []
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/where")
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append(k))
    assert compile_cache.ensure_compile_cache() == "/some/where"
    assert "jax_compilation_cache_dir" not in updates


def test_cache_dir_default_is_in_the_checkout(monkeypatch):
    from parsec_tpu.device import compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prior = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        assert compile_cache.ensure_compile_cache() == \
            os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == \
            os.path.join(REPO, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", prior)


def test_context_places_the_cache_before_anything_jits(monkeypatch):
    from parsec_tpu.device import compile_cache
    calls = []
    monkeypatch.setattr(compile_cache, "ensure_compile_cache",
                        lambda: calls.append(1))
    Context().fini()
    assert calls


# -- E: several chips ---------------------------------------------------------

def test_four_devices_share_a_gemm_pool_and_chains_stay_put(wrapped_cpus):
    wrapped_cpus(4)
    a, b, A, B, C = _gemm(n=128, nb=16)          # 8x8 chains of 8 tasks
    ctx = Context()
    ctx.add_taskpool(tiled_gemm_ptg(A, B, C))
    ctx.wait(timeout=120)
    devs = _accels()
    for d in devs:
        d.sync()
    ctx.fini()
    counts = [d.executed_tasks for d in devs]
    assert len(devs) == 4 and sum(counts) == 8 ** 3
    assert all(0 < c <= 8 ** 3 // 2 for c in counts), counts
    assert all(c % 8 == 0 for c in counts), counts    # whole k-chains
    for m in range(8):
        for n in range(8):
            datum = C.data_of(m, n)
            on = [i for i in datum.device_copies if i != 0]
            assert on == [datum.owner_device], (m, n, on)
    assert all(abs(d.device_load) < 1e-9 for d in devs)   # all released
    np.testing.assert_allclose(C.to_dense(), a @ b, rtol=1e-4, atol=1e-4)


def test_graft_entry_multirank_gemm_runs_default_bodies_rank_r_on_device_r(
        wrapped_cpus):
    """The driver's dry-run stage 1, in process: four device-fabric ranks,
    ``tiled_gemm_ptg`` with its default bodies; the stage itself asserts
    that rank r's tasks ran on device r and none on a numpy body."""
    wrapped_cpus(4)
    sys.path.insert(0, REPO)
    try:
        import __graft_entry__ as graft
    finally:
        sys.path.remove(REPO)
    graft._dryrun_ptg_runtime(4)
    assert len(_accels()) == 4


# -- native build -------------------------------------------------------------

def test_native_build_is_atomic_and_reports_a_failure(monkeypatch):
    from parsec_tpu import native
    from parsec_tpu.core import output
    so = native.ensure_built(force=True)
    build = os.path.dirname(so)
    assert os.listdir(build) == [os.path.basename(so)]    # no temp left

    def broken(cmd, **kw):
        raise subprocess.CalledProcessError(1, cmd,
                                            stderr=b"core.cpp:1: error: no")

    said = []
    monkeypatch.setattr(subprocess, "run", broken)
    monkeypatch.setattr(output, "warning", said.append)
    assert native.ensure_built(force=True) is None
    assert len(said) == 1 and "core.cpp:1: error: no" in said[0]
    assert os.listdir(build) == [os.path.basename(so)]    # old .so kept


# -- chip_smoke.py ------------------------------------------------------------

def _smoke(*args, devices=8):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    return subprocess.run([sys.executable, "chip_smoke.py", *args], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=600)


def test_chip_smoke_refuses_to_run_without_a_chip():
    p = _smoke()
    assert p.returncode != 0
    assert "[smoke]" not in p.stdout and '"ok"' not in p.stdout
    assert "no TPU" in p.stderr


def test_chip_smoke_rehearsal_passes_every_stage_and_prints_no_result():
    p = _smoke("--rehearse-cpu", devices=4)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    assert p.stdout.startswith("REHEARSAL on cpu — not a chip result")
    for stage in ("lowered_gemm", "dynamic_gemm", "dynamic_cholesky",
                  "dtd_gemm", "server", "kernels", "four_ranks",
                  "mesh_lowered"):
        assert f"[smoke] {stage}: PASS" in p.stdout, stage
    assert '"ok"' not in p.stdout
