"""The fused batch program (``device/tpu.py:_fused_program``) compiled for a
described TPU v5e at the benchmark cells' widths, 4 MiB f32 tiles: what the
chip's compiler allocates beside the results.  ``_run_vmapped`` asks the HBM
budget for the results alone (``held``), and for nothing where the results
take donated buffers, so the executable's temporaries have to stay a small
part of them; and the donating program has to pair every written lane's
input with that lane's result.  Nothing runs and no chip is needed; the
topology is described inside a fixture, in the test's own process (one
process a machine may hold the TPU's library)."""

import os
import re

import pytest

import jax
import jax.numpy as jnp

NB = 1024
# dyld -> (flows, written flows, the largest padded batch a cell gives it)
PROGRAMS = {"gemm": (3, 1, 64), "gemm_nt": (3, 1, 64), "trsm_rlt": (2, 1, 16),
            "syrk_ln": (2, 1, 16), "qr_unmqr": (3, 1, 32),
            "qr_tsmqr": (4, 2, 32)}
# dyld -> the written flows' positions among a lane's arguments
WRITTEN = {"gemm": [2], "gemm_nt": [2], "trsm_rlt": [1], "syrk_ln": [1],
           "qr_unmqr": [2], "qr_tsmqr": [0, 1]}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable for a chip that is not attached cannot be read back
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cached)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("dyld", list(PROGRAMS))
def test_the_program_s_temporaries_are_a_small_part_of_its_results(one_chip,
                                                                   dyld):
    import parsec_tpu.models.cholesky  # noqa: F401  (registers traceables)
    import parsec_tpu.models.qr  # noqa: F401
    import parsec_tpu.ops.gemm  # noqa: F401
    from parsec_tpu.device.tpu import _fused_program
    from parsec_tpu.ptg.lowering import find_traceable
    flows, written, lanes = PROGRAMS[dyld]
    tile = jax.ShapeDtypeStruct((NB, NB), jnp.float32, sharding=one_chip)
    mem = _fused_program(find_traceable(dyld).apply, dyld, lanes).lower(
        *[tile] * (flows * lanes)).compile().memory_analysis()
    held = written * lanes * NB * NB * 4
    assert held <= mem.output_size_in_bytes < held + (1 << 20)
    assert mem.alias_size_in_bytes == 0          # nothing is donated
    assert mem.temp_size_in_bytes <= held // 4, mem


@pytest.mark.parametrize("dyld", list(PROGRAMS))
def test_the_donating_program_pairs_each_written_lane_with_its_result(
        one_chip, dyld):
    """What ``_run_vmapped`` runs where the module alone holds the written
    tiles: every written flow of these classes has a result of its input's
    shape and dtype, so all of them are donated; the compiled module's
    ``input_output_alias`` gives result ``j * lanes + i`` (flow j, lane i)
    the parameter of that flow's lane i, the aliased bytes are the results'
    and the temporaries stay under a quarter of them."""
    import parsec_tpu.models.cholesky  # noqa: F401  (registers traceables)
    import parsec_tpu.models.qr  # noqa: F401
    import parsec_tpu.ops.gemm  # noqa: F401
    from parsec_tpu.device.tpu import _donatable, _fused_program
    from parsec_tpu.ptg.lowering import find_traceable
    flows, written, lanes = PROGRAMS[dyld]
    apply = find_traceable(dyld).apply
    tile = jax.ShapeDtypeStruct((NB, NB), jnp.float32, sharding=one_chip)
    donates = _donatable(apply, [tile] * flows, WRITTEN[dyld])
    assert list(donates) == WRITTEN[dyld] and len(donates) == written
    fn = _fused_program(apply, dyld, lanes, donates)
    assert fn.donates == donates and fn.__name__ == f"fused_{dyld}"
    compiled = fn.lower(*[tile] * (flows * lanes)).compile()
    (aliases,) = re.findall(r"input_output_alias=\{(.*?\)) \}",
                            compiled.as_text())
    pairs = {int(out): int(param) for out, param in re.findall(
        r"\{(\d+)\}: \((\d+), \{\}, may-alias\)", aliases)}
    assert pairs == {j * lanes + i: f * lanes + i
                     for j, f in enumerate(donates) for i in range(lanes)}
    mem = compiled.memory_analysis()
    held = written * lanes * NB * NB * 4
    assert held <= mem.output_size_in_bytes < held + (1 << 20)
    assert mem.alias_size_in_bytes == held
    assert mem.temp_size_in_bytes <= held // 4, mem


def test_the_pivoting_panel_compiles_with_its_own_scoped_vmem(one_chip):
    """``jit_fused_getrf_panel`` on a column of 16 tiles (PR 42): XLA's TPU
    LU keeps a 128-column block of the whole stack in scoped VMEM, which the
    default 16 MiB cannot hold at that height (PERF.md, PR 42, step 0); with
    the traceable's ``tpu_compiler_options`` it compiles, and the donating
    program takes every row's and the pivot tile's buffer."""
    from parsec_tpu.device.tpu import _fused_program
    from parsec_tpu.models import lu
    tr = lu._panel_traceable
    rows = [jax.ShapeDtypeStruct((NB, NB), jnp.float32, sharding=one_chip)] \
        * 16
    piv = jax.ShapeDtypeStruct((4, NB), jnp.int32, sharding=one_chip)
    mem = _fused_program(tr, "getrf_panel", 1, tuple(range(17)),
                         tr.tpu_compiler_options).lower(
        piv, *rows).compile().memory_analysis()
    assert mem.alias_size_in_bytes == 16 * NB * NB * 4 + 4 * NB * 4


def test_a_batch_of_tree_kills_compiles_as_one_batched_qr(one_chip):
    """``jit_fused_qr_ttqrt`` at 16 lanes, a binary tree's first level at
    the hierarchical QR cell's size: the traceable's ``vmap_lanes``
    stacks each flow's lanes and runs one batched Householder QR, where a
    copy a lane compiled to 285 MiB of code at 16 lanes and 570 at 32 (the
    described v5e; PERF.md, section 6).  The donated R and B take their inputs'
    buffers; T, written whole, reads nothing of its input.  The stacks are
    temporaries larger than the results (523 MiB against 192), which is
    why ``_run_vmapped`` asks the budget for a stacked program's
    (``temps``)."""
    import parsec_tpu.models.qr  # noqa: F401  (registers traceables)
    from parsec_tpu.device.tpu import _donatable, _fused_program
    from parsec_tpu.ptg.lowering import find_traceable
    lanes = 16
    apply = find_traceable("qr_ttqrt").apply
    assert apply.vmap_lanes
    tile = jax.ShapeDtypeStruct((NB, NB), jnp.float32, sharding=one_chip)
    donates = _donatable(apply, [tile] * 3, [0, 1, 2])
    mem = _fused_program(apply, "qr_ttqrt", lanes, donates,
                         stacked=True).lower(
        *[tile] * (3 * lanes)).compile().memory_analysis()
    assert mem.generated_code_size_in_bytes < 64 << 20, mem
    assert mem.alias_size_in_bytes == 2 * lanes * NB * NB * 4
    assert mem.output_size_in_bytes < 3 * lanes * NB * NB * 4 + (1 << 20)
    assert mem.temp_size_in_bytes > 3 * lanes * NB * NB * 4
