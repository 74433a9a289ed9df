"""Device registry, statistics, and best-device selection.

Rebuild of ``parsec/mca/device/device.{c,h}`` (SURVEY §2.5): devices register
with the process-global registry; each carries transfer/execution statistics
(``device.h:151-156``), per-precision gflops ratings and a load accumulator
(``device.h:161-166``); ``best_device`` implements
``parsec_get_best_device``: the device that already owns the datum of the
task's first written flow, else argmin over (device_load +
time_estimate(task)) with task classes contributing ``time_estimate``
functions
(``parsec_internal.h:441``); the chosen device carries the task's estimate
in its load from selection until the task completes.
"""

from __future__ import annotations

import threading
from typing import Any

from ..core.params import params as _params
from ..core.info import InfoObjectArray
from ..data.data import ACCESS_WRITE

# ---------------------------------------------------------------------------
# process-wide XLA dispatch ledger
#
# Every accelerator enqueue in the process — the dynamic device path's
# per-task (or fused-batch) dispatches (device/tpu.py) AND the lowered
# paths' whole-program / per-region invocations (ptg/lowering.py) — bumps
# ONE counter, so "XLA calls per DAG" is a single comparable axis across
# execution modes (tests/test_lowering_regions.py holds the count per
# emission and the region path's ≥5x drop against task-per-dispatch).  A plain int under a lock: this is per
# dispatch (≥ µs of enqueue work), not per task.
# ---------------------------------------------------------------------------

_xla_lock = threading.Lock()
_xla_calls = 0


def note_xla_calls(n: int = 1) -> None:
    global _xla_calls
    with _xla_lock:
        _xla_calls += n


def xla_calls_total() -> int:
    with _xla_lock:
        return _xla_calls


# load a task of a class without a ``time_estimate`` contributes: unrated
# classes still spread over devices by task count
_NOMINAL_TASK_S = 1e-6


def task_load(task: Any, dev: "Device") -> float:
    te = task.task_class.time_estimate
    return te(task, dev) if te is not None else _NOMINAL_TASK_S


class Device:
    """Base device module (cf. ``parsec_device_module_t``)."""

    def __init__(self, name: str, device_type: str) -> None:
        self.name = name
        self.type = device_type          # DEV_CPU / DEV_TPU / ...
        self.device_index = -1
        self.enabled = True
        # statistics (device.h:151-156)
        self.executed_tasks = 0
        self.bytes_in = 0
        self.bytes_out = 0
        self.bytes_d2d = 0
        # capacity model (device.h:161-166)
        self.gflops_fp16 = 1.0
        self.gflops_fp32 = 1.0
        self.gflops_fp64 = 1.0
        self.device_load = 0.0
        # guards device_load and executed_tasks, which worker threads of
        # several streams (and several in-process ranks) update
        self._lock = threading.Lock()
        self.infos = InfoObjectArray(self)

    # load accounting around task execution: best_device adds a task's
    # estimate when it selects this device, release_task takes it back
    def load_add(self, delta: float) -> None:
        with self._lock:
            self.device_load += delta

    def release_task(self, task: Any) -> None:
        """The task left this device (completed, or requeued by a
        demotion): its estimate no longer counts toward the load."""
        if task.selected_device is self:
            task.selected_device = None
            self.load_add(-task_load(task, self))

    def note_executed(self) -> None:
        with self._lock:
            self.executed_tasks += 1

    def taskpool_register(self, taskpool: Any) -> None:
        """Hook for per-taskpool device state (kernel resolution etc.)."""

    def memory_register(self, buffer: Any) -> Any:
        return buffer

    def memory_unregister(self, handle: Any) -> None:
        pass

    def flush_cache(self) -> None:
        pass

    def pushout(self, copy: Any) -> None:
        """``release_deps`` walked an active output dep to a collection on
        a flow whose copy lives on this device: the tile is final.  An
        accelerator starts its transfer to the host here; the host's own
        device has nothing to move."""

    def stats_reset(self) -> dict[str, float]:
        s = self.stats()
        self.executed_tasks = 0
        self.bytes_in = self.bytes_out = self.bytes_d2d = 0
        return s

    def stats(self) -> dict[str, float]:
        return {
            "executed_tasks": self.executed_tasks,
            "bytes_in": self.bytes_in,
            "bytes_out": self.bytes_out,
            "bytes_d2d": self.bytes_d2d,
            "device_load": self.device_load,
        }


class CPUDevice(Device):
    """Host device: chores run inline on the worker thread."""

    def __init__(self) -> None:
        super().__init__("cpu", "cpu")


class DeviceRegistry:
    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.devices: list[Device] = []

    def add(self, dev: Device) -> Device:
        with self._lock:
            dev.device_index = len(self.devices)
            self.devices.append(dev)
        return dev

    def by_type(self, device_type: str) -> list[Device]:
        return [d for d in self.devices if d.type == device_type and d.enabled]

    def get(self, index: int) -> Device:
        return self.devices[index]

    def best_device(self, task: Any, device_type: str | None = None,
                    allowed: Any = None) -> Device | None:
        """``parsec_get_best_device``.  A task keeps the device already
        selected for it.  Otherwise the datum of the task's first written
        flow decides: the device that owns it wins, so a chain of updates
        to one tile stays where the tile lives; failing that (a tile only
        the host holds yet), min (load + time_estimate).  With several
        accelerators the first writer of a tile is therefore chosen by load
        and every later one follows it: the tile QR and the Cholesky deal
        their tile columns over the accelerators at their first step and
        keep them there.  (``Data.preferred_device``, the reference's
        advice, is not read: PERF.md, PR 40, (e).)  The choice is
        committed: recorded in ``task.selected_device`` and added to the
        device's load until :meth:`Device.release_task`.  ``allowed``
        restricts the candidates to a set of device indices (a rank bound
        to its own chip); an owning device outside the candidates does not
        decide."""
        def usable(d: Device) -> bool:
            return (d.enabled
                    and (device_type is None or d.type == device_type)
                    and (allowed is None or d.device_index in allowed))

        prev = task.selected_device
        if prev is not None:
            if usable(prev):
                return prev
            prev.release_task(task)
        cands = [d for d in self.devices if usable(d)]
        if not cands:
            return None
        dev = None
        for f in task.task_class.flows:
            if f.is_ctl or not (f.access & ACCESS_WRITE):
                continue
            datum = getattr(task.data[f.flow_index], "original", None)
            if datum is not None:
                dev = next((d for d in cands
                            if d.device_index == datum.owner_device), None)
                break
        if dev is None:
            dev = min(cands, key=lambda d: d.device_load + task_load(task, d))
        task.selected_device = dev
        dev.load_add(task_load(task, dev))
        return dev

    def dump_statistics(self) -> dict[str, dict[str, float]]:
        return {d.name: d.stats() for d in self.devices}

    def reset(self) -> None:
        with self._lock:
            self.devices = []


registry = DeviceRegistry()
cpu_device = registry.add(CPUDevice())

_params.register("device_tpu_enabled", True,
                        "enable the TPU device module")
