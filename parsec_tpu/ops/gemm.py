"""GEMM kernels: the MXU workhorse.

Kernel incarnations for the tiled-GEMM task bodies (the cuBLAS analog of the
reference's GEMM tests, e.g. ``tests/dsl/dtd/dtd_test_simple_gemm.c``):
:func:`matmul_xla` is a jitted ``C + A@B`` with fp32 accumulation, which
XLA tiles onto the MXU.  The device and host bodies register in the kernel
registry under ``"gemm"`` so PTG/DTD bodies can resolve them by name
(``dyld=`` contract).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..device.kernels import register_kernel


@functools.partial(jax.jit, static_argnames=("precision",))
def _gemm_update(a, b, c, precision=None):
    """C += A@B with fp32 accumulation.

    ``precision``: None = platform default (bf16 MXU passes on TPU);
    ``jax.lax.Precision.HIGHEST`` = f32-strict (bf16x6 passes).
    No donation here: this is the body of a task submitted alone, one
    result a call.  The fused batch program donates the chained C tiles
    (``device/tpu.py:_run_vmapped``) under the ownership rule in
    ``data/data.py:DataCopy``.
    """
    acc = jnp.dot(a, b, preferred_element_type=jnp.float32,
                  precision=precision)
    return (c.astype(jnp.float32) + acc).astype(c.dtype)


def matmul_xla(a: Any, b: Any, c: Any) -> Any:
    return _gemm_update(a, b, c)


# ---------------------------------------------------------------------------
# task-body incarnations
# ---------------------------------------------------------------------------

from ..core.params import params as _params

_params.register("gemm_precision", "default",
                 "matmul precision for GEMM bodies: default|highest")


def _precision():
    return (jax.lax.Precision.HIGHEST
            if _params.get("gemm_precision") == "highest" else None)


def gemm_tpu_body(es: Any, task: Any, device: Any) -> Any:
    """TPU incarnation of GEMM(m,n,k): C_tile += A_tile @ B_tile.

    Flows by position: 0=A (READ), 1=B (READ), 2=C (RW).  Stage-in has
    already placed the tiles in this device's HBM.
    """
    a = task.data[0].value
    b = task.data[1].value
    c_copy = task.data[2]
    c_copy.value = _gemm_update(a, b, c_copy.value, precision=_precision())
    c_copy.version += 1
    return c_copy.value


def gemm_cpu_body(es: Any, task: Any) -> Any:
    a = np.asarray(task.data[0].value)
    b = np.asarray(task.data[1].value)
    c_copy = task.data[2]
    c_copy.value = np.asarray(c_copy.value) + a.astype(np.float32) @ b.astype(
        np.float32)
    c_copy.version += 1
    return None


register_kernel("gemm", "tpu", gemm_tpu_body)
register_kernel("gemm", "cpu", gemm_cpu_body)


# ---------------------------------------------------------------------------
# traceable incarnation: the same body as a pure jax function, consumed by
# the taskpool→XLA lowering (parsec_tpu.ptg.lowering); bilinear=True lets
# the chain-collapse pass turn the k-chain into one MXU-sized contraction
# ---------------------------------------------------------------------------

from ..ptg.lowering import register_traceable


def _gemm_traceable(a: Any, b: Any, c: Any) -> Any:
    acc = jnp.dot(a, b, preferred_element_type=jnp.float32,
                  precision=_precision())
    return (c.astype(jnp.float32) + acc).astype(c.dtype)


register_traceable("gemm", _gemm_traceable, bilinear=True)
