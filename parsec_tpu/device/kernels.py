"""Kernel incarnation registry.

The TPU analog of the reference's ``dyld=`` dynamic body resolution
(``find_incarnation``, ``device_gpu.c:201``: dlopen/dlsym per device): device
bodies are registered by name and device type; PTG/DTD chores resolve them at
dispatch.  TPU kernels are jitted XLA/Pallas callables; registration usually
happens at module import of :mod:`parsec_tpu.ops`.
"""

from __future__ import annotations

import threading
from typing import Any, Callable

_lock = threading.Lock()
_kernels: dict[tuple[str, str], Callable] = {}
_lazy: dict[tuple[str, str], Callable] = {}


def register_kernel(name: str, device_type: str, fn: Callable) -> Callable:
    with _lock:
        _kernels[(name, device_type)] = fn
    return fn


def register_lazy_kernel(name: str, device_type: str,
                         loader: Callable[[], Callable]) -> Callable:
    """Deferred incarnation registration — the Pallas seam.

    ``loader()`` is called at most once, on the first dispatch that
    resolves ``(name, device_type)``, and must return the body callable;
    the result is promoted into the eager registry.  Kernels whose
    construction is expensive or platform-conditional (a Pallas build
    that should only trace on a real TPU, an import that would drag the
    accelerator stack into CPU-only runs) register here instead of at
    module import — the exact role dlopen/dlsym lazy resolution plays
    for the reference's ``dyld=`` bodies (``device_gpu.c:201``)."""
    with _lock:
        _lazy[(name, device_type)] = loader
    return loader


def find_incarnation(name: str, device: Any) -> Callable | None:
    for dt in (device.type, "*"):
        with _lock:
            fn = _kernels.get((name, dt))
            loader = None if fn is not None else _lazy.get((name, dt))
        if loader is not None:
            # build OUTSIDE the lock (loaders may import jax/pallas and
            # take seconds); a racing duplicate build is harmless — the
            # registry keeps whichever lands, both are the same kernel
            fn = loader()
            with _lock:
                _kernels[(name, dt)] = fn
                _lazy.pop((name, dt), None)
        if fn is not None:
            return fn
    return None


def traceable_body(apply: Callable, jitted: Callable | None = None) -> Callable:
    """A per-task device body from a jax-traceable: ``apply`` takes the
    task's non-CTL flow values in flow order and returns the new value of its
    written flows, one value or a tuple in flow order (the contract of
    ``ptg.lowering.Traceable.apply`` and of the fused batch program); a null
    flow is left out of both.  Every written flow gets its value and a new
    version, as ``_run_vmapped`` does for a batch.  ``jitted``: where ``apply`` is one ``jax.jit`` function on
    those values, a callable that hands it out; the body carries it as
    ``body.jitted``, and the device module compiles it for every accelerator
    at once (``TPUDevice._meet_task_program``)."""
    def body(es: Any, task: Any, device: Any) -> Any:
        from ..data.data import ACCESS_WRITE
        # a flow the instance leaves null is not the kernel's
        flows = [f for f in task.task_class.flows
                 if not f.is_ctl and task.data[f.flow_index] is not None]
        out = apply(*(task.data[f.flow_index].value for f in flows))
        outs = out if isinstance(out, (tuple, list)) else (out,)
        written = [f for f in flows if f.access & ACCESS_WRITE]
        if len(outs) != len(written):
            raise ValueError(
                f"{task.task_class.name}: the kernel returned {len(outs)} "
                f"values for {len(written)} written flows")
        for f, value in zip(written, outs):
            c = task.data[f.flow_index]
            c.value = value
            c.version += 1
        return out
    body.jitted = jitted
    return body


def registered() -> list[tuple[str, str]]:
    with _lock:
        return sorted(set(_kernels) | set(_lazy))
