"""The TPU device module — the heart of the rebuild.

Rebuild of the generic accelerator engine + backend vtable
(``parsec/mca/device/device_gpu.{c,h}`` + ``cuda/device_cuda_module.c``,
SURVEY §2.5, §3.5) redesigned around XLA's execution model:

- **Manager-thread model kept** (``parsec_device_kernel_scheduler``,
  ``device_gpu.c:2423-2652``): the first worker to raise the atomic counter
  becomes the device manager; others enqueue to ``pending`` and leave.
- **Streams become async dispatch**: CUDA needs explicit streams + events;
  XLA-on-TPU enqueues work on the device's execution stream and returns
  immediately — host-side ordering of enqueues *is* the dependency chain, so
  ``kernel_exec`` completes a task as soon as its outputs are enqueued
  (`HOOK_RETURN_ASYNC` discipline preserved; an in-flight window bounds
  queue depth the way ``DEP_NB_CONCURRENT`` bounds comm).
- **Stage-in** (``parsec_device_data_stage_in``, ``device_gpu.c:1269``):
  versioned H2D/D2D ``jax.device_put`` with coherency transitions; **LRU
  tile cache** (clean + owned lists, ``device_gpu.h:234-235``) with
  eviction-by-writeback when an HBM budget is exceeded — the zone-malloc
  reservation becomes a byte budget, since XLA owns physical HBM.
- **Batched execution** (TPU-first addition): consecutive pending tasks of
  the same task class with the same kernel are dispatched as ONE fused XLA
  call (:meth:`TPUDevice._run_vmapped`, consuming the same
  traceable-kernel registry as the compiled lowering), one kernel a lane
  on the tiles where they lie — tiny-task dispatch overhead amortizes onto
  one enqueue (no reference analog; this is the idiomatic TPU answer to its
  per-task CUDA-stream pipelining).  The call is donated the tiles it
  overwrites, so each result takes the buffer of the version it supersedes
  and the call allocates nothing (the in-place write of the reference's
  kernels, got back under XLA's immutable arrays).
"""

from __future__ import annotations

import functools
import threading
import time
from sys import getrefcount
from collections import OrderedDict, deque
from typing import Any, Callable

import numpy as np

from ..core.params import params as _params
from ..data.data import (ACCESS_WRITE, COHERENCY_EXCLUSIVE, COHERENCY_INVALID,
                         COHERENCY_OWNED, COHERENCY_SHARED, DataCopy)
from ..prof import pins, spans
from ..prof.pins import PinsEvent
from ..runtime.task import HOOK_RETURN_ASYNC
from .device import Device, note_xla_calls, registry

_params.register("device_tpu_memory_use", 90,
                 "percent of per-device HBM the device module may hold: the "
                 "tile cache's current copies, the scratch tiles its pad "
                 "lanes write to, and what only its unconfirmed dispatches "
                 "keep alive: the versions they superseded without taking "
                 "their buffers (a per-task body's, a fused call's that "
                 "could not donate); a result that took its predecessor's "
                 "buffer adds nothing")
_params.register("device_tpu_max_inflight", 32,
                 "bound, by count, on enqueued-but-unconfirmed dispatches of "
                 "one accelerator: past it an enqueue waits for the oldest, "
                 "unless another accelerator of the context has run all it "
                 "was given and nobody feeds it (the wait would starve it "
                 "longer): then only what the chip has run leaves the ring; "
                 "the ring is cut shorter than this, always, whenever the "
                 "bytes it holds (of results that did not take a donated "
                 "buffer) would take the module past device_tpu_memory_use")
_params.register("device_tpu_batch", True,
                 "run same-class pending tasks as one fused dispatch")
_params.register("device_tpu_batch_max", 64,
                 "largest task batch a single fused dispatch may service")
_params.register("device_tpu_allow_cpu", False,
                 "register host CPU jax devices as accelerators, so the "
                 "device path (stage-in, LRU, batched dispatch) runs "
                 "without a chip: tests and CPU smoke runs")


def _fused_program(apply: Callable, dyld: str, lanes: int,
                   donates: tuple[int, ...] = (),
                   compiler_options: dict | None = None,
                   stacked: bool = False) -> Callable:
    """The one jitted program of a same-class batch: ``apply`` once a lane on
    the lane's own tiles (the flat arguments are flow-major: lane i's are
    ``flat[i::lanes]``), the results per written flow, a tuple of the lanes'.
    Its name on the trace's "XLA Modules" line is ``jit_fused_<dyld>``:
    device time splits by task class.  ``donates``: the flows (positions
    among the lane's arguments) every lane of which is donated, so that the
    lane's result takes its buffer and the call allocates no output for it
    (:func:`_donatable` says which can be; the compiled module pairs lane
    i's input with lane i's result); kept on the program as ``.donates``.
    ``compiler_options``: XLA's, for this program alone (a traceable's
    ``tpu_compiler_options`` on the TPU).  ``stacked``: each flow's lanes
    are stacked and ``apply`` runs once under ``jax.vmap`` (a traceable's
    ``vmap_lanes``): for a kernel whose one lane is a large program, such as
    a Householder QR, that XLA batches as one, where a copy a lane would
    multiply the code."""
    import jax

    def fused(*flat):
        with jax.named_scope("body"):
            if stacked:
                import jax.numpy as jnp
                outs = jax.vmap(apply)(*(
                    jnp.stack(flat[f:f + lanes])
                    for f in range(0, len(flat), lanes)))
                if not isinstance(outs, (tuple, list)):
                    outs = (outs,)
                return tuple(tuple(o[i] for i in range(lanes)) for o in outs)
            outs = [apply(*flat[i::lanes]) for i in range(lanes)]
        if not isinstance(outs[0], (tuple, list)):
            return (tuple(outs),)
        return tuple(zip(*outs))

    fused.__name__ = f"fused_{dyld}"
    fn = jax.jit(fused, donate_argnums=tuple(
        f * lanes + i for f in donates for i in range(lanes)),
        compiler_options=compiler_options)
    fn.donates = tuple(donates)
    return fn


def _donatable(apply: Callable, lane: list, written: list[int]) -> tuple:
    """Of a lane's ``written`` flows (positions among its arguments, in the
    order of the traceable's results), those whose result has the shape and
    dtype of the input it supersedes: only such a result can take the
    input's buffer.  Decided from ``jax.eval_shape`` once a program, not by
    class: a traceable whose result is another shape donates nothing."""
    import jax
    outs = jax.eval_shape(apply, *lane)
    if not isinstance(outs, (tuple, list)):
        outs = (outs,)
    return tuple(w for w, o in zip(written, outs)
                 if o.shape == lane[w].shape and o.dtype == lane[w].dtype)


def _avals(args: list[tuple], device: Any) -> list:
    """``(shape, dtype)`` pairs as abstract arguments placed on ``device``:
    what a program is compiled for ahead of its first call."""
    import jax
    from jax.sharding import SingleDeviceSharding
    where = SingleDeviceSharding(device)
    return [jax.ShapeDtypeStruct(shape, dtype, sharding=where)
            for shape, dtype in args]


def _stacked_temps(fn: Callable, avals: list) -> int:
    """The bytes a stacked fused program allocates on the device beside
    its arguments and results (XLA's ``memory_analysis``), read once when
    the program is built, by compiling it for ``avals``: its first call
    then finds it in the persistent compile cache.  A batched QR stacks its
    lanes' tiles and their results: 913 MiB at 32 TSQRT lanes for the v5e,
    against 384 of results (PERF.md, section 7)."""
    mem = fn.lower(*avals).compile().memory_analysis()
    return int(getattr(mem, "temp_size_in_bytes", 0) or 0)


def _compile_quietly(fn: Callable, avals: list) -> None:
    """Lower and compile ``fn`` for ``avals`` ahead of its first call there;
    a failure is left to that call, which meets it again and reports it."""
    try:
        fn.lower(*avals).compile()
    except Exception:       # noqa: BLE001
        pass


def _refs_of_slot(vals: list, i: int) -> int:
    return getrefcount(vals[i])


def _own_refs() -> int:
    """What ``getrefcount(vals[i])`` reads for an array that its
    ``DataCopy`` and the gather's list alone hold (measured, not reckoned:
    the interpreter's own temporaries are in it)."""
    held_by_the_copy = object()
    return _refs_of_slot([held_by_the_copy], 0)


_OWN_REFS = _own_refs()


def _copy_nbytes(copy: DataCopy) -> int:
    return getattr(copy.value, "nbytes", 0) if copy.value is not None else 0


def _host_is_newer(host: DataCopy | None, copy: DataCopy) -> bool:
    """The host already holds, as a host array, a later version than the
    dirty ``copy``: another accelerator wrote the tile after this one and
    its flush came first.  A write-back never lowers the host's version,
    so the order of the flushes does not decide what the host keeps.  (A
    host copy that a memory edge handed the device array itself is ahead by
    count only and waits for this very write-back.)"""
    return host is not None and host.version > copy.version \
        and host.coherency != COHERENCY_INVALID \
        and isinstance(host.value, np.ndarray)


def _pushed_out(copy: DataCopy) -> bool:
    """A push-out started the transfer of the very array ``copy`` holds now
    (a later writer's new ``copy.value`` is another object)."""
    return copy.pushed is not None and copy.pushed() is copy.value


# --------------------------------------------------------------------------
# tier spill hooks (ISSUE 11): the KV tier map (data_dist/kv_tiers.py)
# subscribes to device evictions so HBM -> host write-backs of its pages
# feed the host-tier residency ledger.  Weakly held — a dropped tier map
# must not be pinned by the device module for the process lifetime.
# --------------------------------------------------------------------------
import weakref as _weakref

_spill_hooks: list = []       # weakrefs to objects with .note_spill(d, nb)


def register_spill_hook(obj: Any) -> None:
    """Subscribe ``obj.note_spill(data, nbytes)`` to every device-tier
    eviction write-back.  Held by weakref; dead subscribers prune on
    the next fire."""
    _spill_hooks.append(_weakref.ref(obj))


def _fire_spill(data: Any, nbytes: int) -> None:
    dead = False
    for ref in _spill_hooks:
        obj = ref()
        if obj is None:
            dead = True
            continue
        try:
            obj.note_spill(data, nbytes)
        except Exception:       # noqa: BLE001 — accounting never faults I/O
            pass
    if dead:
        _spill_hooks[:] = [r for r in _spill_hooks if r() is not None]


class _Wall:
    """One phase of the device module, instrumented once: its host wall
    (``TPUDevice.t_<wall>``, always on, read as deltas by the benchmark and
    ``debug_state``) and, while the phase plane is on, its span, both from
    one pair of clock readings.  ``lock``: held while the wall is added to,
    for the one wall that closes after its thread gave the managership up
    (``t_manager``: the next manager may be closing its own by then)."""

    __slots__ = ("dev", "attr", "span", "t0", "lock")

    def __init__(self, dev: "TPUDevice", attr: str, name: str,
                 lock: Any = None) -> None:
        self.dev = dev
        self.attr = attr
        self.lock = lock
        self.span = spans.phase(name) if spans.phase_on else None

    def __enter__(self) -> None:
        if self.span is None:
            self.t0 = time.perf_counter_ns()
        else:
            self.t0 = self.span.__enter__().t0

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter_ns() - self.t0
        if self.span is not None:
            self.span.close(dt, *exc)
        if self.lock is None:
            self._add(dt)
        else:
            with self.lock:
                self._add(dt)

    def _add(self, dt: int) -> None:
        setattr(self.dev, self.attr, getattr(self.dev, self.attr) + dt / 1e9)


# the fields of a row of ``TPUDevice.call_table`` (plain ints); ``tasks``
# beside ``calls`` x lanes is the row's share of pad lanes, and
# ``results_donated`` beside ``results`` the results that took the buffer of
# the version they supersede (pad lanes' on the scratch pool among them)
CALL_FIELDS = ("calls", "tasks", "args", "results", "results_donated",
               "call_ns", "depth_sum", "held_bytes_sum", "held_run_bytes_sum")


def _arrays(out: Any):
    """The ``jax.Array`` s among what a dispatch handed back (nested tuples
    and lists, in order)."""
    if isinstance(out, (tuple, list)):
        for part in out:
            yield from _arrays(part)
    elif hasattr(out, "is_ready"):
        yield out


def _live(out: Any) -> list:
    """Those of :func:`_arrays` that no later call was donated."""
    return [a for a in _arrays(out) if not a.is_deleted()]


_DONATED = object()


def _first_live(out: Any) -> Any:
    """The first array among what a dispatch handed back that no later call
    was donated; ``_DONATED`` where every one was (a later dispatch of the
    same ring consumed them), None where the body handed back no array."""
    leaf = None
    for leaf in _arrays(out):
        if not leaf.is_deleted():
            return leaf
    return None if leaf is None else _DONATED


class _Call:
    """The call of one dispatch while the phase plane is on: the chip's queue
    read just before (:meth:`TPUDevice._queue_depth`), the span
    ``devmod.call`` around the call alone, with the class, the lanes and the
    depth as the annotation's arguments, and the dispatch added to its row of
    ``call_table``; span and ``call_ns`` come from one pair of clock
    readings, as a :class:`_Wall`'s do."""

    __slots__ = ("row", "span")

    def __init__(self, dev: "TPUDevice", tc: Any, lanes: int, tasks: int,
                 donated: int) -> None:
        flows = [f for f in tc.flows if not f.is_ctl]
        depth, held_run = dev._queue_depth()
        row = dev.call_table.get((tc.name, lanes))
        if row is None:
            row = dev.call_table[(tc.name, lanes)] = dict.fromkeys(
                CALL_FIELDS, 0)
        row["calls"] += 1
        row["tasks"] += tasks
        row["args"] += lanes * len(flows)
        row["results"] += lanes * sum(1 for f in flows
                                      if f.access & ACCESS_WRITE)
        row["results_donated"] += donated
        row["depth_sum"] += depth
        row["held_bytes_sum"] += dev._held_bytes
        row["held_run_bytes_sum"] += held_run
        self.row = row
        self.span = spans.phase("devmod.call", task_class=tc.name,
                                lanes=lanes, depth=depth,
                                device=dev.device_index)

    def __enter__(self) -> None:
        self.span.__enter__()

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter_ns() - self.span.t0
        self.span.close(dt, *exc)
        self.row["call_ns"] += dt


class TPUDeviceTask:
    """Device task descriptor (cf. ``parsec_gpu_task_t``, device_gpu.h:79-121)."""

    __slots__ = ("task", "submit", "stage_in", "stage_out", "es",
                 "flow_sizes")

    def __init__(self, es: Any, task: Any, submit: Callable) -> None:
        self.es = es
        self.task = task
        self.submit = submit
        # user transfer overrides (the stage_custom.jdf contract,
        # device_gpu.h:61-77) — read HERE so every construction site
        # (enqueue and scheduler flooding alike) honors them
        self.stage_in = getattr(task.task_class, "stage_in_hook", None)
        self.stage_out = getattr(task.task_class, "stage_out_hook", None)
        self.flow_sizes = None


class TPUDevice(Device):
    """One accelerator chip driven through JAX (PJRT underneath)."""

    def __init__(self, jax_device: Any) -> None:
        super().__init__(f"tpu({jax_device.id})", "tpu")
        self.jax_device = jax_device
        # flop ratings (cf. the CUDA flop table device_cuda_module.c:45-145)
        kind = getattr(jax_device, "device_kind", "").lower()
        self.gflops_fp16, self.gflops_fp32 = _flop_rating(kind)
        self.gflops_fp64 = self.gflops_fp32 / 8
        # manager-thread protocol state
        self._managing = False
        self._mutex_lock = threading.Lock()
        self._pending: deque[TPUDeviceTask] = deque()
        # LRU tile cache: master Data -> its DataCopy on this device.
        # Keyed by the datum itself, not its key: two collections of one
        # name hold equal keys for different tiles
        self._lru_lock = threading.RLock()
        self._mem_lru: OrderedDict[Any, DataCopy] = OrderedDict()
        self._mem_bytes = 0
        self._mem_budget = self._hbm_budget()
        # bounded in-flight window (poor-man's event ring): per dispatch,
        # what to wait on (a fused call: its first result, since one
        # program's results are ready together) and the bytes that stay
        # allocated until it has run and nothing else accounts for: the
        # versions it superseded without taking their buffers (the program
        # still reads them) and its padding lanes' results; nothing for a
        # result that took a donated buffer.  Bounded, with the LRU, by
        # the one byte budget (_make_room), always; and by count
        # (_max_inflight), where the wait for the oldest entry costs no
        # other chip anything: while an accelerator of _peers (the others
        # of the context whose batch this one runs) starves, an enqueue
        # past the count drops what the chip has run and waits for nothing
        # (_note_inflight; how often: ring_excused against ring_bounded)
        self._inflight: deque[tuple] = deque()
        self._held_bytes = 0
        self._max_inflight = _params.get("device_tpu_max_inflight")
        self._peers: list[TPUDevice] = []
        self.ring_excused = 0
        self.ring_bounded = 0
        self.ring_peak = 0
        # what the pad lanes of a donating call write to: scratch tiles by
        # (shape, dtype), handed to the call and taken back as its pad
        # results, so that a pad lane allocates nothing either.  At most the
        # pad lanes of one call a written flow (under half the widest
        # batch); their bytes are charged to the budget
        self._scratch: dict[tuple, list] = {}
        # tiles of zeros a class with ``pad_rows`` appends to its family
        # of row flows (``_run_fused``); its kernel hands them back zero
        self._zeros: dict[tuple, list] = {}
        self._scratch_bytes = 0
        # results that took the buffer of the version they superseded
        self.donated_results = 0
        # deferred evictions (the w2r-task analog): victims leave the LRU
        # immediately but write back AFTER the batch's dispatches enqueue,
        # so D2H never blocks the manager mid-pipeline.  _evict_bytes
        # tracks their still-live buffers: residency may exceed the budget
        # by one batch's eviction volume until the drain (the budget is
        # advisory — XLA owns physical HBM).
        self._evict_q: deque[DataCopy] = deque()
        self._evict_bytes = 0
        self.deferred_evictions = 0
        self.evicted_bytes = 0      # the bytes behind deferred_evictions
        self.evict_stuck = 0        # over budget with nothing evictable
        # clean copies the LRU let go (the host or the chip that wrote the
        # tile still holds that version): no device-to-host copy, and not
        # in evicted_bytes
        self.replicas_dropped = 0
        self.replica_bytes_dropped = 0
        # several accelerators under one context: tiles staged in from
        # another chip's copy (their bytes are Device.bytes_d2d, and
        # bytes_in counts host tiles alone), and other chips' copies this
        # chip's writes made invalid
        self.d2d_tiles = 0
        self.invalidated_copies = 0
        # the byte budget at work: dispatches confirmed early because of
        # it, and the most the ring ever held
        self.pressure_confirms = 0
        self.inflight_held_bytes_peak = 0
        # fused-dispatch cache ((dyld, padded B, signature) -> jitted fn)
        self._vmap_cache: dict[Any, Callable] = {}
        # per-task bodies that name their jitted function (``body.jitted``),
        # once this device or a peer has met them: body -> that function
        self._task_programs: dict[Callable, Callable] = {}
        # fault-injection seam for the pressure harness: called with the
        # batch right before the fused XLA dispatch (the reference gates
        # its GPU fault tests on real hardware; here injected faults
        # drive the same salvage/demote protocol)
        self._dispatch_hook: Callable | None = None
        self.batched_dispatches = 0   # XLA calls that serviced >1 task
        # per task class, by name: tasks run and the XLA calls that ran them
        # (one increment a dispatch): how far the flood batches each class
        self.tasks_by_class: dict[str, int] = {}
        self.calls_by_class: dict[str, int] = {}
        # inside the call, filled only while the phase plane is on (a traced
        # window's rows are that window's): (task class name, lanes) -> a
        # row of CALL_FIELDS, lanes = 1 for a task submitted alone
        self.call_table: dict[tuple[str, int], dict[str, int]] = {}
        # attribution instrumentation: wall seconds per pipeline phase +
        # how many device calls paid an enqueue latency
        self.xla_calls = 0
        self.t_stage_in = 0.0
        self.t_dispatch = 0.0
        self.t_complete = 0.0
        self.t_drain = 0.0
        self.t_writeback = 0.0   # flush_cache, its drain included
        # how often the write-back's transfer was under way before it was
        # read: transfers started at a memory edge, dirty tiles _writeback
        # brought back, and of those the ones a push-out had started on
        # the very array read
        self.pushouts = 0
        self.writebacks = 0
        self.writebacks_early = 0
        # the flood (_flood_from_scheduler): tasks it took out of the
        # scheduler into a batch, and tasks it popped only to hand back
        self.flood_selected = 0
        self.flood_putbacks = 0
        self.t_manager = 0.0   # total wall inside the manager drain loop
        # stage-in tile-cache effectiveness, per (task, flow) reference —
        # the hit-rate gauge the metrics snapshotter samples
        self.cache_hits = 0
        self.cache_misses = 0
        # the LRU's side of the hits: distinct hit copies a batch moved to
        # its recent end (summed over batches), and those of them it did not
        # hold under their datum (evicted meanwhile, or another copy), which
        # took the charging insert
        self.lru_touches = 0
        self.lru_recharged = 0
        # data flows an instance left null (a flow family's rows above the
        # panel): nothing staged, nothing handed to the kernel
        self.null_flows_skipped = 0
        # gauges hold the device only WEAKLY: devices are never fini'd,
        # and a strong closure would keep a discarded device (test
        # fixtures, demoted devices) plus its LRU tile cache alive in
        # the process-global SDE registry forever
        import weakref
        from ..prof.counters import sde
        ref = weakref.ref(self)

        def gauge(fn):
            def get():
                d = ref()
                return fn(d) if d is not None else 0
            return get

        sde.register_gauge(
            f"device::{self.name}::stage_in_hit_rate",
            gauge(lambda d: d.cache_hits
                  / max(1, d.cache_hits + d.cache_misses)))
        sde.register_gauge(f"device::{self.name}::bytes_in",
                           gauge(lambda d: d.bytes_in))
        sde.register_gauge(f"device::{self.name}::bytes_out",
                           gauge(lambda d: d.bytes_out))
        sde.register_gauge(f"device::{self.name}::pending",
                           gauge(lambda d: len(d._pending)))

    # ------------------------------------------------------------- memory
    def _hbm_budget(self) -> int:
        pct = _params.get("device_tpu_memory_use") / 100.0
        stats = self.jax_device.memory_stats() or {}
        total = stats.get("bytes_limit") or stats.get(
            "bytes_reservable_limit") or 0
        if not total:
            if self.jax_device.platform != "cpu":
                raise RuntimeError(
                    f"device {self.name} ({self.jax_device.device_kind}) "
                    f"reports no memory limit: the tile cache cannot be "
                    f"budgeted (memory_stats() = {stats!r})")
            total = _CPU_STANDIN_BYTES
        return int(total * pct)

    def _cache_insert(self, copy: DataCopy, nbytes: int) -> None:
        key = copy.original
        with self._lru_lock:
            old = self._mem_lru.get(key)
            if old is not None:
                self._mem_bytes -= _copy_nbytes(old)
            self._mem_lru[key] = copy
            self._mem_lru.move_to_end(key)
            self._mem_bytes += nbytes
        self._make_room(0)

    def _touch(self, hits: list[DataCopy]) -> None:
        """The LRU's side of a batch's hits (a copy a reference, in the
        walk's order): each distinct copy once, in the order of its last
        reference, as a move to the end per reference would leave them.  A
        copy the LRU holds under its datum only moves: it was charged its
        bytes when it landed, and again where a result of another size
        replaced its value (:meth:`_recharge`).  Any other takes the
        charging insert: a victim of an eviction whose write-back is still
        queued (the w2r drain skips what is back in the LRU), or a datum the
        LRU holds another copy of.  The budget is asked once, after."""
        touched = dict.fromkeys(reversed(hits))
        lru = self._mem_lru
        recharged = 0
        with self._lru_lock:
            for c in reversed(touched):
                d = c.original
                old = lru.get(d)
                if old is not c:
                    if old is not None:
                        self._mem_bytes -= _copy_nbytes(old)
                    lru[d] = c
                    self._mem_bytes += _copy_nbytes(c)
                    recharged += 1
                lru.move_to_end(d)
        self.lru_touches += len(touched)
        self.lru_recharged += recharged
        self._make_room(0)

    def _recharge(self, copies: list[DataCopy], delta: int) -> None:
        """Written results of another size than the versions they replaced:
        the LRU charged each of ``copies`` it holds the old value's bytes,
        and ``delta`` more is what the new one holds."""
        with self._lru_lock:
            for c in copies:
                if self._mem_lru.get(c.original) is c:
                    self._mem_bytes += delta

    def _make_room(self, need: int) -> None:
        """The one place the budget is held.  It covers everything the
        module holds on the chip: the LRU's current copies, the scratch
        pool, what only the unconfirmed dispatches keep alive
        (``_held_bytes``) and ``need``, the bytes the caller is about to
        allocate (a stage-in's misses, the results of a dispatch that take
        no donated buffer, new scratch tiles; 0 after an insertion).  Past
        the budget the oldest dispatches are confirmed and dropped first:
        that frees the versions they superseded, for a wait that is near
        nothing while the chip idles behind a slower host.  Only when the
        ring is empty do tiles leave through the w2r queue."""
        room = self._mem_budget - need - self._scratch_bytes
        if self._mem_bytes + self._held_bytes <= room:
            return
        with spans.phase("devmod.pressure"):
            while self._inflight \
                    and self._mem_bytes + self._held_bytes > room:
                self._confirm_oldest()
                self.pressure_confirms += 1
            with self._lru_lock:
                while self._mem_bytes + self._held_bytes > room \
                        and self._evict_one_locked():
                    pass

    def _evict_one_locked(self) -> bool:
        """Evict the least-recently-used unpinned tile; False where there is
        none.  The victim only leaves the LRU here; its write-back is
        DEFERRED to the w2r queue (``parsec_gpu_create_w2r_task``) drained
        between batches — the manager never blocks on a D2H mid-pipeline."""
        for k in list(self._mem_lru):
            c = self._mem_lru[k]
            if c.readers > 0:
                continue
            del self._mem_lru[k]
            nb = _copy_nbytes(c)
            self._mem_bytes -= nb
            self._evict_bytes += nb
            self._evict_q.append(c)
            return True
        # nothing evictable: XLA's allocator has to cope, and it is counted
        self.evict_stuck += 1
        return False

    def _drain_evictions(self) -> None:
        """Write back queued eviction victims (the w2r stage).  A victim
        that was re-staged meanwhile is back in the LRU under its key —
        skip it, its residency continues (and is counted there again).

        Two phases so D2H overlaps the in-flight dispatches (the w2r-side
        double-buffering, ``device_gpu.c`` D2H stream): first every
        victim's transfer is *started* asynchronously, then the host
        copies materialize — by which point the first transfers have
        ridden under the batch still executing."""
        with _Wall(self, "t_drain", "devmod.drain"):
            victims = []
            while True:
                with self._lru_lock:
                    if not self._evict_q:
                        break
                    c = self._evict_q.popleft()
                    self._evict_bytes -= _copy_nbytes(c)
                    if self._mem_lru.get(c.original) is c:
                        continue    # resurrected by a later stage_in
                if c.coherency != COHERENCY_INVALID:
                    self._start_d2h(c)
                    victims.append(c)
                else:
                    # written back earlier, or made invalid by another
                    # chip's write: garbage, which the datum lets go here
                    c.original.detach_copy(self.device_index, c)
            i = 0
            if victims:
                pins.fire(PinsEvent.DEVICE_EVICT, None, len(victims))
            try:
                while i < len(victims):
                    c = victims[i]
                    # a clean copy only leaves: someone else holds this
                    # version (the host, or the chip that wrote the tile)
                    clean = c.coherency == COHERENCY_SHARED
                    self._writeback(c)
                    if clean:
                        self.replicas_dropped += 1
                        self.replica_bytes_dropped += _copy_nbytes(c)
                    else:
                        self.evicted_bytes += _copy_nbytes(c)
                        self.deferred_evictions += 1
                    i += 1
            except BaseException:
                # a failed writeback must leave the unwritten victims
                # reachable: failure recovery salvages from _evict_q, and a
                # dirty copy outside it would be silently dropped
                with self._lru_lock:
                    for c in victims[i:]:
                        self._evict_bytes += _copy_nbytes(c)
                        self._evict_q.append(c)
                raise

    def _start_d2h(self, copy: DataCopy) -> bool:
        """Start the device-to-host transfer of a dirty copy's value and
        return at once; False where a push-out already started it on this
        very array, or nothing could be started (a clean copy, a value
        without ``copy_to_host_async``, a start that raised: the
        synchronous read in :meth:`_writeback` transfers those).  The
        transfer is cached on the array object, so it can only ever serve
        a read of that array: a later writer's new ``copy.value`` has none."""
        if copy.coherency not in (COHERENCY_OWNED, COHERENCY_EXCLUSIVE) \
                or _pushed_out(copy):
            return False
        start = getattr(copy.value, "copy_to_host_async", None)
        if start is None:
            return False
        try:
            start()
        except Exception:
            return False
        return True

    def pushout(self, copy: DataCopy) -> None:
        """The graph says this written tile is final (an active output dep
        to a collection, walked by ``release_deps``): start its transfer
        now, so that it rides under the rest of the solve.  Nothing else
        changes: the copy stays dirty, in the LRU and readable by the
        task's successors; the host copy, the coherency transition and
        ``bytes_out`` wait for :meth:`_writeback` at the flush or a drain."""
        t0 = time.perf_counter_ns() if spans.phase_on else 0
        if self._start_d2h(copy):
            copy.pushed = _weakref.ref(copy.value)
            self.pushouts += 1
        if t0:
            spans.phase_add("devmod.pushout", time.perf_counter_ns() - t0)

    def _writeback(self, copy: DataCopy) -> None:
        """Push a dirty device copy back to the host copy, then drop it."""
        d = copy.original
        host = d.get_copy(0)
        if copy.coherency in (COHERENCY_OWNED, COHERENCY_EXCLUSIVE) \
                and not _host_is_newer(host, copy):
            value = np.asarray(copy.value)
            self.writebacks += 1
            self.writebacks_early += _pushed_out(copy)
            if host is None:
                host = DataCopy(d, 0, value=value, dtt=copy.dtt)
                d.attach_copy(host)
            else:
                host.value = value
            host.version = copy.version
            host.coherency = COHERENCY_SHARED
            if d.owner_device == self.device_index:
                d.owner_device = 0
            self.bytes_out += value.nbytes
        d.detach_copy(self.device_index, copy)
        copy.coherency = COHERENCY_INVALID
        copy.pushed = None
        if _spill_hooks:
            # the datum is host-resident-only now: tier maps account it
            _fire_spill(d, _copy_nbytes(copy))

    def flush_cache(self) -> None:
        """Synchronize every dirty tile back to its host copy (epilog for a
        taskpool; the data_flush analog for device residency).  Write-back
        happens OUTSIDE the LRU lock: spill hooks may copy page bytes and
        push AMs (kv_tiers peer spill), and concurrent stage-ins must not
        serialize behind that I/O.  Two passes, as the drain does it:
        every dirty tile whose transfer no push-out started gets it started,
        then the host copies materialize; on return every one of them is a
        numpy array at the tile's newest version."""
        spans.phase_refresh()
        with _Wall(self, "t_writeback", "devmod.writeback"):
            self._drain_evictions()   # pending w2r victims: not in the LRU
            with self._lru_lock:
                victims = [self._mem_lru.pop(k) for k in list(self._mem_lru)]
                self._mem_bytes = 0
            for c in victims:
                self._start_d2h(c)
            for c in victims:
                self._writeback(c)
            # the cache's last references go here, inside the wall: freeing
            # the device buffers is part of what the flush costs
            c = victims = None

    # ----------------------------------------------------------- stage-in
    def stage_in(self, task: Any) -> None:
        """Ensure every data flow of ``task`` has a current copy on this
        device (versioned H2D/D2D; cf. ``parsec_device_data_stage_in``)."""
        self.stage_in_many([task])

    def stage_in_many(self, tasks: list[Any]) -> None:
        """Batched stage-in: resolve every task's misses first, then move
        them in one :meth:`_transfer` (the copies are made one after the
        other; each returns once PJRT has the bytes and crosses behind the
        next).  Duplicate tiles across the batch stage once.  A hit is a
        read of the datum's copy here and a recency touch, made once a
        distinct copy after the walk (:meth:`_touch`), before the misses
        land; a hit the LRU no longer holds resurrects an evicted-but-not-
        yet-written-back victim (the pending w2r skips anything back in the
        LRU)."""
        assigns: list[tuple[Any, int, Any]] = []   # (task, flow_idx, datum)
        missing: dict[Any, DataCopy] = {}          # datum -> source copy
        hits: list[DataCopy] = []                  # a hit copy a reference
        hit = hits.append
        here = self.device_index
        misses = nulls = 0
        tc = indices = None
        for task in tasks:
            if task.task_class is not tc:
                tc = task.task_class
                indices = [f.flow_index for f in tc.flows if not f.is_ctl]
            data = task.data
            for fi in indices:
                copy = data[fi]
                if copy is None:
                    nulls += 1
                    continue
                d = copy.original
                # one read of the datum's dict, which whoever changes it does
                # under the datum's lock: the copy before or after the change
                dev_copy = d.device_copies.get(here)
                if dev_copy is not None \
                        and dev_copy.version >= copy.version \
                        and dev_copy.coherency != COHERENCY_INVALID:
                    data[fi] = dev_copy
                    hit(dev_copy)
                    continue
                misses += 1
                prev = missing.get(d)
                if prev is None:
                    missing[d] = copy
                elif copy.version != prev.version:
                    # two tasks in one batch reference DIFFERENT versions
                    # of the same datum: dedupe keeps the highest, and the
                    # flight recorder makes that observable (ADVICE r5 —
                    # a copy-renaming scheme added later must not be able
                    # to silently hand an old-version reader new data)
                    pins.fire(PinsEvent.DEVICE_STAGE_MIXED_VERSIONS, None,
                              (d.key, max(copy.version, prev.version),
                               min(copy.version, prev.version)))
                    if copy.version > prev.version:
                        missing[d] = copy
                assigns.append((task, fi, d))
        self.cache_hits += len(hits)
        self.cache_misses += misses
        self.null_flows_skipped += nulls
        if hits:
            self._touch(hits)
        if not missing:
            return
        keys = list(missing)
        self._make_room(sum(_copy_nbytes(c) for c in missing.values()))
        srcs = [missing[k] for k in keys]
        # a miss whose newest copy is another accelerator's array crosses
        # from chip to chip
        far = [i for i, c in enumerate(srcs)
               if c.device_index not in (0, self.device_index)]
        values = self._transfer([c.value for c in srcs], far)
        crossed = set(far)
        landed: dict[Any, DataCopy] = {}
        batch_nb = 0
        for i, (k, value) in enumerate(zip(keys, values)):
            src = srcs[i]
            d = src.original
            dev_copy = d.get_copy(self.device_index)
            if dev_copy is None:
                dev_copy = DataCopy(d, self.device_index, value=value,
                                    dtt=src.dtt)
                d.attach_copy(dev_copy)
            else:
                dev_copy.value = value
            dev_copy.version = src.version
            dev_copy.coherency = COHERENCY_SHARED
            nb = getattr(src.value, "nbytes", 0)
            if i in crossed:
                self.bytes_d2d += nb
                self.d2d_tiles += 1
            else:
                self.bytes_in += nb
            batch_nb += nb
            self._cache_insert(dev_copy, nb)
            landed[k] = dev_copy
        pins.fire(PinsEvent.DEVICE_STAGE_IN, None, int(batch_nb))
        for task, fi, k in assigns:
            # every assigned key was ensured in `missing` and every miss
            # lands above — a KeyError here is a real landing bug
            task.data[fi] = landed[k]

    def _transfer(self, values: list, far: list[int] = ()) -> list:
        """The copy to this device itself, for ``stage_in_many`` and
        ``prefetch_data``.  ``far``: the positions of the values that are
        another accelerator's arrays; they cross in one ``jax.device_put``
        under the span ``devmod.d2d`` (PJRT orders the copy behind the
        program that writes the source and any donation of the source
        behind the copy: PERF.md, PR 40, step 0 (d)).  A numpy tile goes
        straight to the call
        ``jax.device_put`` ends in (:func:`_host_put`).  What
        ``jax.device_put`` does around that call costs 76-86 us a tile of
        Python on the chip's host, against 165-190 us for the call itself
        (PERF.md, PR 35), and decides nothing here, where shape, dtype and
        placement are those of the tile before."""
        jd = self.jax_device
        put = _host_put()
        out = [put(v, jd) for v in values] if put is not None \
            else [None] * len(values)
        def put_together(which: list[int]) -> None:
            import jax
            for i, o in zip(which, jax.device_put([values[i] for i in which],
                                                  jd)):
                out[i] = o

        if far:
            with spans.phase("devmod.d2d"):
                put_together(far)
        rest = [i for i, o in enumerate(out) if o is None]
        if rest:
            put_together(rest)
        return out

    def prefetch_data(self, datas: list[Any]) -> int:
        """Data-grain prefetch (ISSUE 11): stage host-resident datums
        back into the device tier AHEAD of the tasks that will read
        them — the KV tier map calls this one decode superpool ahead of
        the wavefront, so a paged-out stream re-enters decode without a
        synchronous stage-in stall.  Advisory and idempotent: datums
        with a current device copy are skipped, everything else moves
        in one :meth:`_transfer` on the caller's thread, beside whatever
        the manager is dispatching; a racing stage-in of the same datum
        lands identical bytes at the same version.  It MAY evict: the caller
        asserts the datums are the next wavefront's inputs, so trading
        colder residents for them is the point of the call — but each
        call stages at most HALF the byte budget, leaving the in-flight
        batch room to keep its own tiles (an HBM budget below the
        working set then pays one overlapped transfer sweep per
        iteration instead of degenerating into prefetch-vs-dispatch
        thrash).  Returns the number of datums staged."""
        cap = self._mem_budget // 2
        todo: list[tuple[Any, DataCopy, int, Any]] = []
        for d in datas:
            host = d.get_copy(0)
            if host is None or host.value is None \
                    or host.coherency == COHERENCY_INVALID:
                continue
            dev = d.get_copy(self.device_index)
            if dev is not None and dev.version >= host.version \
                    and dev.coherency != COHERENCY_INVALID:
                continue
            nb = getattr(host.value, "nbytes", 0)
            if nb > cap:
                break                 # the half-budget sweep is full
            cap -= nb
            # version and value snapshot TOGETHER: the landed copy is
            # tagged with the version of the bytes that actually moved,
            # never the (possibly advanced-meanwhile) live host version
            todo.append((d, host, host.version, host.value))
        if not todo:
            return 0
        with _Wall(self, "t_stage_in", "devmod.prefetch"):
            values = self._transfer([v for _, _, _, v in todo])
            nb_total = 0
            staged = 0
            for (d, host, snap_ver, _sv), value in zip(todo, values):
                with d._lock:
                    dev = d.device_copies.get(self.device_index)
                    if dev is not None and (
                            dev.coherency in (COHERENCY_OWNED,
                                              COHERENCY_EXCLUSIVE)
                            or (dev.version >= snap_ver
                                and dev.coherency != COHERENCY_INVALID)):
                        # a dispatch staged or wrote it meanwhile: a dirty
                        # device copy runs AHEAD of host and must never be
                        # clobbered with the (older) snapshot bytes
                        continue
                    if dev is None:
                        dev = DataCopy(d, self.device_index, value=value,
                                       dtt=host.dtt)
                        d.device_copies[self.device_index] = dev
                    else:
                        dev.value = value
                    # a host write-back that landed AFTER the snapshot makes
                    # this copy stale at birth: tagging it with snap_ver (not
                    # the live host version) makes the next stage_in see the
                    # miss and re-stage current bytes
                    dev.version = snap_ver
                    dev.coherency = COHERENCY_SHARED
                nb = getattr(_sv, "nbytes", 0)
                self.bytes_in += nb
                nb_total += nb
                staged += 1
                self._cache_insert(dev, nb)
        if nb_total:
            pins.fire(PinsEvent.DEVICE_STAGE_IN, None, int(nb_total))
        return staged

    # ------------------------------------------------- the manager protocol
    def kernel_scheduler(self, es: Any, task: Any, submit: Callable) -> int:
        """``parsec_device_kernel_scheduler``: enqueue; first thread in
        becomes the manager and drains the device (device_gpu.c:2457-2473)."""
        dtask = TPUDeviceTask(es, task, submit)
        pins.fire(PinsEvent.DEVICE_ENQUEUE, es, task)
        with self._mutex_lock:
            self._pending.append(dtask)
            if self._managing:
                return HOOK_RETURN_ASYNC  # a manager is already in charge
            self._managing = True
        # we are the manager
        with _Wall(self, "t_manager", "devmod.manage", self._mutex_lock):
            return self._manage()

    def _manage(self) -> int:
        """One managership: drain ``_pending`` batch by batch."""
        try:
            while True:
                with self._mutex_lock:
                    if not self._pending:
                        self._managing = False
                        return HOOK_RETURN_ASYNC
                    batch = self._take_batch_locked()
                self._peers = [
                    d for d in batch[0].es.context.accelerators()
                    if d is not self and d.type == self.type]
                spans.phase_refresh()
                try:
                    if _params.get("device_tpu_batch"):
                        with spans.phase("sched.flood"):
                            self._flood_from_scheduler(batch)
                    self._run_batch(batch)
                    self._drain_evictions()   # w2r: D2H post-dispatch
                except Exception as e:
                    # device failure: demote (the PARSEC_HOOK_RETURN_DISABLE
                    # path) — salvage resident tiles, reschedule the
                    # un-completed tasks so remaining incarnations run them
                    self._recover_failed_batch(batch, e)
        except BaseException:
            # unrecoverable (salvage escalation, interrupts): release the
            # managership so the error path never strands queued tasks
            with self._mutex_lock:
                self._managing = False
            raise

    def _recover_failed_batch(self, batch: list[TPUDeviceTask],
                              exc: Exception) -> None:
        """Demote after a failed dispatch: disable this device, salvage
        device-resident tiles back to their host copies, and reschedule
        every un-completed task — with the device chore disabled,
        ``execute_task`` walks on to the remaining incarnations (the
        ``device_gpu.c:2647-2652`` demote-and-requeue protocol).

        Escalates (re-raises) when a tile newer than its host copy cannot
        be written back — re-execution would silently read stale inputs,
        and fail-stop beats wrong answers.  A call that failed after it was
        donated its written tiles has consumed them: such a tile reads as
        deleted, cannot be written back, and stops the run here unless its
        host copy is as new (a tile staged in and never written, which the
        retry reads from the host).
        """
        from ..core.output import warning
        from ..runtime.scheduling import schedule_tasks
        self.enabled = False
        warning(f"device {self.name}: dispatch failed ({exc!r}); demoting "
                f"to remaining incarnations")
        with self._mutex_lock:
            victims = [d for d in self._pending]
            self._pending.clear()
        victims = [d for d in batch if d.task.status != "done"] + victims
        with self._lru_lock:
            copies = list(self._mem_lru.values()) + list(self._evict_q)
            self._mem_lru.clear()
            self._evict_q.clear()
            self._mem_bytes = 0
            self._evict_bytes = 0
        self._scratch.clear()
        self._zeros.clear()
        self._scratch_bytes = 0
        # tiles the victims will recompute from scratch (WRITE-only flows)
        # may be dropped freely; an RW flow's prior value is an INPUT, so
        # it gets no exemption — and any other tile newer than its host
        # copy must salvage or we stop
        from ..data.data import ACCESS_READ
        recomputed: set[int] = set()
        for d in victims:
            for f in d.task.task_class.flows:
                if f.is_ctl or not (f.access & ACCESS_WRITE) \
                        or (f.access & ACCESS_READ):
                    continue
                cp = d.task.data[f.flow_index]
                if cp is not None:
                    recomputed.add(id(cp.original))
        for c in copies:
            try:
                self._writeback(c)
            except Exception:
                home = c.original.get_copy(0)
                newer = home is None or c.version > home.version
                c.coherency = COHERENCY_INVALID
                c.original.detach_copy(self.device_index)
                if newer and id(c.original) not in recomputed:
                    raise RuntimeError(
                        f"device {self.name}: tile {c.original.key} newer "
                        f"than its host copy could not be salvaged — "
                        f"failing stop rather than recomputing on stale "
                        f"inputs") from exc
        for d in victims:
            # rebind flow slots off this device: the retry must read the
            # SALVAGED host copies, not dead-device arrays
            t = d.task
            for f in t.task_class.flows:
                cp = None if f.is_ctl else t.data[f.flow_index]
                if cp is not None and cp.device_index == self.device_index:
                    t.data[f.flow_index] = cp.original.get_copy(0)
            self.release_task(t)
            t.status = "ready"
            schedule_tasks(d.es, [t], 0)

    def _flood_from_scheduler(self, batch: list[TPUDeviceTask]) -> None:
        """Pull additional ready same-class tasks straight from the
        scheduler into this dispatch batch.

        The reference's manager accumulates batches passively because many
        workers enqueue concurrently (``device_gpu.c:2457-2473``); under the
        TPU module a single driving thread hands tasks over one at a time,
        so the manager *actively* asks the scheduler for the ready tasks of
        the batch's class (``SchedulerModule.select_class``: lfq pops that
        class's bucket alone; a module without such a store selects and puts
        back what is not the class).  Only classes with a
        traceable incarnation are worth flooding — everything else would
        fall back to the per-task path anyway.
        """
        from ..ptg.lowering import find_traceable
        from ..runtime.scheduling import prepare_input

        first = batch[0]
        es = first.es
        tc = first.task.task_class
        if (getattr(tc, "stage_in_hook", None) is not None
                or getattr(tc, "stage_out_hook", None) is not None):
            return   # custom staging forces per-task dispatch: no point
        dyld = next((c.dyld for c in tc.chores
                     if c.device_type == self.type and c.dyld), None)
        if dyld is None or find_traceable(dyld) is None:
            return
        sched = es.context.scheduler
        most = _params.get("device_tpu_batch_max")
        if getattr(tc, "batch_max", None) is not None:
            most = min(most, tc.batch_max)
        taken, put_back = sched.select_class(es, tc, most - len(batch))
        for t, distance in taken:
            if es.context.best_device(t, self.type) is self:
                prepare_input(es, t)
                batch.append(TPUDeviceTask(es, t, first.submit))
                self.flood_selected += 1
            else:       # another accelerator's: back where it came from
                sched.schedule(es, [t], distance)
                put_back += 1
        self.flood_putbacks += put_back

    def _take_batch_locked(self) -> list[TPUDeviceTask]:
        batch = [self._pending.popleft()]
        if _params.get("device_tpu_batch"):
            first = batch[0]
            most = getattr(first.task.task_class, "batch_max", None)
            while self._pending and \
                    self._pending[0].task.task_class is first.task.task_class \
                    and self._pending[0].submit is first.submit \
                    and (most is None or len(batch) < most):
                batch.append(self._pending.popleft())
        return batch

    # ------------------------------------------------------------ pipeline
    def _run_batch(self, batch: list[TPUDeviceTask]) -> None:
        from ..runtime import scheduling
        pins.fire(PinsEvent.DEVICE_BATCH_BEGIN, None, len(batch))
        with _Wall(self, "t_stage_in", "devmod.stage_in"):
            # stage-in phase (stream 0 analog): user-hooked tasks stage
            # individually, everything else moves in one batched device_put
            hooked = [d for d in batch if d.stage_in is not None]
            for dtask in hooked:
                dtask.stage_in(self, dtask.task)
            self.stage_in_many([d.task for d in batch
                                if d.stage_in is None])
        with _Wall(self, "t_dispatch", "devmod.dispatch"):
            if (len(batch) > 1 or batch[0].task.task_class.pad_rows) \
                    and self._run_vmapped(batch):
                pass              # one XLA call serviced the whole batch
            else:
                for dtask in batch:   # exec phase (exec streams analog)
                    # the body replaces each written flow's value: what it
                    # supersedes stays allocated until the program has run
                    written = [(c, _copy_nbytes(c)) for c in
                               self._written_copies(dtask.task)]
                    held = sum(nb for _, nb in written)
                    self._make_room(held)
                    warming = () if dtask.submit in self._task_programs \
                        else self._meet_task_program(dtask)
                    with self._call(dtask.task.task_class, 1, 1):
                        out = dtask.submit(dtask.es, dtask.task, self)
                    for thread in warming:  # a body's first call alone
                        thread.join()
                    with spans.phase("devmod.land"):
                        self.xla_calls += 1
                        note_xla_calls(1)
                        self._note_inflight(out, held)
                        self.executed_tasks += 1
                        self._count_dispatch(dtask.task.task_class, 1)
                        self._mark_written(dtask.task)
                        for c, nb in written:
                            delta = _copy_nbytes(c) - nb
                            if delta:
                                self._recharge([c], delta)
        with _Wall(self, "t_complete", "devmod.complete"):
            # per task, the plane adds to a counter (sched.release) and
            # opens no span
            complete = scheduling.complete_execution_timed \
                if spans.phase_on else scheduling.complete_execution
            for dtask in batch:   # completion (epilog analog)
                if dtask.stage_out is not None:
                    dtask.stage_out(self, dtask.task)
                self.release_task(dtask.task)
                complete(dtask.es, dtask.task)
        pins.fire(PinsEvent.DEVICE_BATCH_END, None, len(batch))

    def _meet_task_program(self, dtask: TPUDeviceTask) -> list:
        """The first task a per-task body runs on this device: where the
        body names its jitted function (``kernels.traceable_body``'s
        ``jitted``), the peers compile it beside this call."""
        body = dtask.submit
        jitted = getattr(body, "jitted", None)
        fn = self._task_programs[body] = jitted() if jitted else None
        if fn is None:
            return []
        task = dtask.task
        values = [task.data[f.flow_index].value
                  for f in task.task_class.flows
                  if not f.is_ctl and task.data[f.flow_index] is not None]
        return self._compile_for_peers(
            "_task_programs", body, fn, [(v.shape, v.dtype) for v in values])

    def _count_dispatch(self, tc: Any, ntasks: int) -> None:
        by_tasks, by_calls = self.tasks_by_class, self.calls_by_class
        by_tasks[tc.name] = by_tasks.get(tc.name, 0) + ntasks
        by_calls[tc.name] = by_calls.get(tc.name, 0) + 1

    def _written_copies(self, task: Any):
        """The copies on this device that ``task``'s written flows hold."""
        for f in task.task_class.flows:
            if f.is_ctl or not (f.access & ACCESS_WRITE):
                continue
            c = task.data[f.flow_index]
            if c is not None and c.device_index == self.device_index:
                yield c

    def _mark_written(self, task: Any) -> None:
        # written flows become dirty device copies (coherency epilog,
        # cf. kernel_epilog versions->owner, device_gpu.c:2251)
        for c in self._written_copies(task):
            c.coherency = COHERENCY_OWNED
            d = c.original
            d.owner_device = self.device_index
            copies = d.device_copies
            if len(copies) > 1 + (0 in copies):
                self._invalidate_elsewhere(d)

    def _invalidate_elsewhere(self, d: Any) -> None:
        """This device wrote ``d``: the copies other accelerators hold are
        of an older version now (write-invalidate, ``Data.start_write``'s
        rule on the device path).  They leave their chips' LRUs as garbage,
        never as write-backs; the host's copy keeps its version and is what
        :func:`_host_is_newer` compares."""
        with d._lock:
            for idx, other in d.device_copies.items():
                if idx != 0 and idx != self.device_index \
                        and other.coherency != COHERENCY_INVALID:
                    other.coherency = COHERENCY_INVALID
                    self.invalidated_copies += 1

    # --------------------------------------------------- fused batch dispatch
    def _run_vmapped(self, batch: list[TPUDeviceTask]) -> bool:
        """Dispatch a same-class batch as ONE fused XLA call (the
        TPU-first answer to per-task CUDA-stream pipelining: tiny-task
        dispatch overhead amortizes onto the MXU).

        The fused program takes the B x F per-task tiles FLAT, runs the
        class's traceable once a lane on the parameter buffers as they lie
        (no stack, no ``vmap``, no slices) and returns, per written flow,
        the tuple of every lane's result — so the whole batch costs ONE
        enqueue.  B is padded to the next power of two with copies of lane
        0 (outputs of pad lanes are dropped; kernels are pure XLA) to bound
        jit specializations to log2(batch_max) per (dyld, signature).

        The program is donated every lane of each written flow whose result
        has its input's shape and dtype, so that the result takes the buffer
        of the version it supersedes and the call allocates no output for it
        (libtpu allocates every other output one by one before the launch,
        50-60 us each: PERF.md, PR 38 and PR 39); a pad lane of such a flow
        writes to a scratch tile of the pool and hands it back.  That is
        safe while the module alone holds the tiles it consumes
        (:meth:`_sole_holder`; the rule is in ``DataCopy``'s docstring); a
        call in which anyone else holds one runs the same program without
        donation and allocates its results, as every call did before.

        A flow an instance leaves ``null`` is not handed to the kernel: the
        batch's instances are grouped by the flows they hold, a call a
        group.  A class with ``pad_rows`` (a family of row flows, some null)
        gets tiles of zeros after its present rows, up to the bucket, so
        that every height of a bucket shares one program; its instances go
        one a call, with no pad lane (a pad lane of such a class would take
        a scratch tile for every row).

        Eligibility: the class's device chore has a jax-traceable
        incarnation registered under its ``dyld`` name
        (:func:`parsec_tpu.ptg.lowering.register_traceable` — the same
        contract the compiled lowering consumes), every task's flow tiles
        agree on shape/dtype, and no task overrides its stage hooks.
        Returns False to fall back to per-task submission.
        """
        from ..ptg.lowering import find_traceable

        tc = batch[0].task.task_class
        if any(d.stage_in is not None or d.stage_out is not None
               for d in batch):
            return False   # custom stage hooks own data placement
        dyld = next((c.dyld for c in tc.chores
                     if c.device_type == self.type and c.dyld), None)
        if dyld is None:
            return False
        tr = find_traceable(dyld)
        if tr is None:
            return False
        data_flows = [f for f in tc.flows if not f.is_ctl]
        groups: dict[tuple, list] = {}
        for d in batch:
            data = d.task.data
            groups.setdefault(tuple(
                f for f in data_flows if data[f.flow_index] is not None),
                []).append(d)
        calls = []
        for flows, group in groups.items():
            # a wide class's instances, one call each: a lane of such a
            # class is tens of tiles and bound by the chip, and one lane a
            # program keeps its programs to one a bucket (PERF.md, PR 42)
            parts = [group] if tc.pad_rows is None else [[d] for d in group]
            for part in parts:
                copies, cols = [], []
                for f in flows:
                    cs = [t.task.data[f.flow_index] for t in part]
                    vals = [c.value for c in cs]
                    # no name is bound to a tile here: the donation below
                    # counts who refers to it
                    shape, dtype = vals[0].shape, vals[0].dtype
                    if any(v.shape != shape or v.dtype != dtype
                           for v in vals):
                        return False   # ragged tiles: per-task path
                    copies.append(cs)
                    cols.append(vals)
                calls.append((part, flows, copies, cols))
        for call in calls:
            self._run_fused(tc, dyld, tr, *call)
        return True

    def _run_fused(self, tc: Any, dyld: str, tr: Any,
                   batch: list[TPUDeviceTask], flows: tuple,
                   copies: list, cols: list) -> None:
        """One fused call of :meth:`_run_vmapped`: ``batch``'s lanes hold
        ``flows``, whose copies and values are ``copies`` and ``cols``."""
        B = len(batch)
        Bp = 1
        while Bp < B:
            Bp <<= 1
        written = [i for i, f in enumerate(flows)
                   if f.access & ACCESS_WRITE]
        # a flow family's zero tiles, after its present rows: written as
        # the family is, and handed back to the pool of zeros
        rows = 0 if tc.pad_rows is None else \
            -(len(flows) - tc.pad_rows[0]) % tc.pad_rows[1]
        for _ in range(rows):
            if flows[-1].access & ACCESS_WRITE:
                written.append(len(cols))
            cols.append(self._take_scratch(cols[-1][0], B, zeros=True))
            copies.append(None)
        sig = tuple((vs[0].shape, str(vs[0].dtype)) for vs in cols)
        key = (dyld, Bp, sig)
        fn = self._vmap_cache.get(key)
        warming: Any = ()
        # options of the TPU's compiler a kernel needs (the panel LU's
        # scoped VMEM: models/lu.py); no other backend knows them
        opts = getattr(tr.apply, "tpu_compiler_options", None) \
            if self.jax_device.platform == "tpu" else None
        stacked = getattr(tr.apply, "vmap_lanes", False)
        if fn is None:
            args = [a for a in sig for _ in range(Bp)]
            fn = self._vmap_cache[key] = _fused_program(
                tr.apply, dyld, Bp,
                _donatable(tr.apply, [vs[0] for vs in cols], written), opts,
                stacked)
            warming = self._compile_for_peers("_vmap_cache", key, fn, args)
            if stacked:
                fn.temps = _stacked_temps(
                    fn, _avals(args, self.jax_device))
        if fn.donates and not all(self._sole_holder(copies[w], cols[w])
                                  for w in fn.donates
                                  if copies[w] is not None):
            # someone else holds a tile this call would consume: the same
            # program without donation, compiled when first needed
            key += ("plain",)
            fn = self._vmap_cache.get(key)
            if fn is None:
                args = [a for a in sig for _ in range(Bp)]
                fn = self._vmap_cache[key] = _fused_program(
                    tr.apply, dyld, Bp, compiler_options=opts,
                    stacked=stacked)
                warming = self._compile_for_peers("_vmap_cache", key, fn,
                                                  args)
                if stacked:
                    fn.temps = _stacked_temps(
                        fn, _avals(args, self.jax_device))
        # what the call allocates: Bp results for each written flow that is
        # not donated, which supersede B current versions and pad Bp - B
        # lanes and stay until the call has run (a flow's tiles are of one
        # shape: one nbytes a flow, not a tile); a donated flow's results
        # take its inputs' buffers.  A stacked program's temporaries are
        # asked for too (``temps``).  A per-lane program's are not: at
        # 4 MiB tiles the v5e compiler's memory_analysis gives the program 0
        # bytes of them for gemm / gemm_nt (64 lanes), syrk_ln (16),
        # qr_tsmqr / qr_unmqr (32), 11 MiB for trsm_rlt (16: 64 MiB of
        # results); tests/test_fused_tpu_compile.py holds the per-lane
        # classes the cells batch to temporaries under a quarter of their
        # results, donating or not
        held = Bp * sum(cols[w][0].nbytes for w in written
                        if w not in fn.donates) + getattr(fn, "temps", 0)
        self._make_room(held)
        # pad lanes: copies of lane 0, whose results are dropped; in a
        # donated flow a scratch tile each, which the pad result takes and
        # which goes back to the pool at the landing
        npad = Bp - B
        flat = []
        for i, vs in enumerate(cols):
            flat += vs
            if npad:
                flat += self._take_scratch(vs[0], npad) if i in fn.donates \
                    else [vs[0]] * npad
        if self._dispatch_hook is not None:
            self._dispatch_hook(batch)
        donated = Bp * len(fn.donates)
        with self._call(tc, Bp, B, donated):
            outs = fn(*flat)
        for thread in warming:      # a program's first call alone
            thread.join()
        with spans.phase("devmod.land"):
            self.xla_calls += 1              # the whole batch, one enqueue
            note_xla_calls(1)
            assert len(outs) == len(written), (dyld, len(outs), len(written))
            self.donated_results += donated
            # one program's results are ready together: the first stands
            # for the call in the ring
            self._note_inflight(outs[0][0] if outs else None, held)
            for w, parts in zip(written, outs):
                if copies[w] is None:       # zeros, as they came
                    self._zeros[sig[w]].extend(parts[:B])
                    continue
                fi = flows[w].flow_index
                for i, dtask in enumerate(batch):
                    c = dtask.task.data[fi]
                    c.value = parts[i]
                    c.version += 1
                if npad and w in fn.donates:
                    self._scratch[sig[w]].extend(parts[B:])
                # a donated flow's results have its inputs' shape and dtype
                new, old = parts[0], cols[w][0]
                if w not in fn.donates and (new.shape != old.shape
                                            or new.dtype != old.dtype):
                    self._recharge(copies[w], new.nbytes - old.nbytes)
            for dtask in batch:
                self.executed_tasks += 1
                self._mark_written(dtask.task)
            self.batched_dispatches += 1
            self._count_dispatch(tc, B)

    def _compile_for_peers(self, cache: str, key: Any, fn: Callable,
                           args: list[tuple]) -> list:
        """A program this device meets for the first time (a fused batch
        program it has just built, ``cache`` = ``_vmap_cache``; a per-task
        body's jitted function, ``_task_programs``) is compiled for the
        other accelerators of its kind at the same time, each on a thread of
        its own beside this device's first call (XLA compiles outside the
        interpreter lock): on the TPU an executable is bound to its chip and
        so is its entry in the persistent cache, and four chips that meet
        the same batches would otherwise compile every program four times,
        one after the other, inside the solve that meets it first (PERF.md,
        PR 40).  The peers share the jitted object (their ``cache`` gets
        ``fn`` under ``key``), so what is compiled here for a peer is what
        the peer's first call finds.  ``args``: (shape, dtype) of each
        argument (no array: a reference more to a tile would keep a call
        from donating it).  Returns the threads to join; nothing with one
        accelerator."""
        kind = self.jax_device.device_kind
        threads = []
        for peer in registry.devices:
            if peer is self or not isinstance(peer, TPUDevice) \
                    or not peer.enabled or key in getattr(peer, cache) \
                    or peer.jax_device.device_kind != kind:
                continue
            getattr(peer, cache)[key] = fn
            thread = threading.Thread(
                target=_compile_quietly,
                args=(fn, _avals(args, peer.jax_device)), daemon=True)
            thread.start()
            threads.append(thread)
        return threads

    def _take_scratch(self, like: Any, n: int, zeros: bool = False) -> list:
        """``n`` scratch tiles of ``like``'s shape and dtype off the pool,
        made (of zeros, and asked of the budget) where the pool lacks them.
        Two written flows of one shape share a pool and take one after the
        other; what a call took comes back as its pad lanes' results.
        ``zeros``: the pool of ``pad_rows``' tiles of zeros, which their
        kernels hand back zero, kept apart from the pad lanes' garbage."""
        pools = self._zeros if zeros else self._scratch
        pool = pools.setdefault((like.shape, str(like.dtype)), [])
        lacking = n - len(pool)
        if lacking > 0:
            import jax
            host = np.zeros(like.shape, like.dtype)
            self._make_room(lacking * host.nbytes)
            pool.extend(jax.device_put([host] * lacking, self.jax_device))
            self._scratch_bytes += lacking * host.nbytes
        taken = pool[-n:]
        del pool[-n:]
        return taken

    def _sole_holder(self, copies: list, vals: list) -> bool:
        """Whether the module alone holds every array of ``vals``, the
        current values of ``copies`` in one written flow of a batch, so that
        a call may consume them.  One reading of the reference count a lane:
        the ``DataCopy`` and the gather's list hold the array, and the ring
        may (a dispatch's first result); anyone else who kept the array and
        not the copy (the datum's host copy after a memory edge, a send
        registered with the comm engine, a second lane of this call, a
        caller's variable) shows as one more and keeps the call from
        donating.  A push-out holds the array weakly while its transfer
        flies, so it is asked by itself."""
        for i in range(len(vals)):
            extra = _refs_of_slot(vals, i) - _OWN_REFS
            if extra and (extra != 1 or not any(
                    vals[i] is a for out, _ in self._inflight
                    for a in _arrays(out))):
                return False
            pushed = copies[i].pushed
            if pushed is not None and pushed() is vals[i]:
                return False
        return True

    def _call(self, tc: Any, lanes: int, tasks: int, donated: int = 0) -> Any:
        """What wraps the call of one dispatch (the fused program, or the
        body of a task submitted alone, whose few lines of Python around its
        jitted call are in it): nothing while the phase plane is off."""
        return _Call(self, tc, lanes, tasks, donated) if spans.phase_on \
            else spans.phase("devmod.call")

    def _queue_depth(self) -> tuple[int, int]:
        """How many of the unconfirmed dispatches the chip has not run yet,
        and the ``held`` bytes of those it has (which the budget still
        counts): ``is_ready()`` on the first live ``jax.Array`` among a
        dispatch's results, which does not block.  One chip runs its
        programs in the order they were enqueued, so the ring is ready up to
        some entry and not from there on, and that entry is found by
        bisection: at most 6 probes for a ring of 32.  A dispatch whose body
        handed back no array says nothing of the chip: it is counted with
        the dispatch enqueued before it.  One whose arrays were all donated
        was consumed by a later dispatch of this ring: it is as ready as the
        first later entry with a live array.  What cannot be seen: a body
        whose first array is one of its inputs passed through reads as
        run."""
        ring = self._inflight
        lo, hi = 0, len(ring)
        while lo < hi:
            mid = probe = (lo + hi) // 2
            leaf = _first_live(ring[probe][0])
            while leaf is None and probe > lo:
                probe -= 1
                leaf = _first_live(ring[probe][0])
            later = probe
            while leaf is _DONATED and later + 1 < hi:
                later += 1
                leaf = _first_live(ring[later][0])
                if leaf is None:            # no array: says nothing
                    leaf = _DONATED
            if leaf is _DONATED:
                # nothing live before hi: what is at hi is owed, and past
                # the ring's end nothing is
                ran = hi == len(ring)
            else:
                ran = leaf is None or leaf.is_ready()
            if ran:
                lo = mid + 1
            else:
                hi = probe
        return len(ring) - lo, sum(ring[i][1] for i in range(lo))

    def _starving(self) -> bool:
        """Asked by a peer's manager, which takes no lock and waits for
        nothing here: this chip has run all it was given and nobody is
        about to give it more (no manager, nothing pending, the newest ring
        entry with a live array ``is_ready()``: one probe).  Whoever takes
        the chip up meanwhile changes the ring under the walk, or donates
        the array asked: both raise, and the chip is then not starving."""
        if self._managing or self._pending or not self.enabled:
            return False
        try:
            for out, _ in reversed(self._inflight):
                leaf = _first_live(out)
                if leaf is not None and leaf is not _DONATED:
                    return leaf.is_ready()
        except RuntimeError:
            return False
        return True

    def _note_inflight(self, out: Any, held: int = 0) -> None:
        """Bound the enqueue depth: block on the oldest dispatch once more
        than ``max_inflight`` are unconfirmed (event-ring analog), unless a
        peer starves: the thread that would wait here is the one that could
        feed it.  Then the entries the chip has run are confirmed (no wait
        in it), the others stay past the count, and the next enqueue asks
        again; ``_make_room`` bounds the ring by bytes either way.  With no
        peer the count holds at every enqueue.  ``held``: the bytes that
        stay allocated until this dispatch has run."""
        if out is None:
            return
        ring = self._inflight
        ring.append((out, held))
        self._held_bytes += held
        if self._held_bytes > self.inflight_held_bytes_peak:
            self.inflight_held_bytes_peak = self._held_bytes
        if len(ring) > self._max_inflight:
            waited = False
            while len(ring) > self._max_inflight:
                if any(peer._starving() for peer in self._peers):
                    owed = self._queue_depth()[0]
                    while len(ring) > max(owed, self._max_inflight):
                        self._confirm_oldest()
                    break
                self._confirm_oldest()
                waited = True
            if waited:
                self.ring_bounded += 1
            else:
                self.ring_excused += 1
        if len(ring) > self.ring_peak:
            self.ring_peak = len(ring)

    def _confirm_oldest(self) -> None:
        out, held = self._inflight.popleft()
        self._held_bytes -= held
        self._confirm(out)

    def _confirm(self, out: Any) -> None:
        """Wait for an enqueued dispatch; a device-side failure disables
        this device so later tasks demote to their remaining incarnations
        (the ``PARSEC_HOOK_RETURN_DISABLE`` path, ``device_gpu.c:2647-2652``)
        and is re-raised — a failed kernel must not pass silently.  Arrays a
        later call was donated cannot be waited on: a dispatch all of whose
        arrays went that way was consumed by a later entry of the ring, and
        one chip runs in order, so it is confirmed by the first later entry
        with a live array (which stays in the ring)."""
        import jax
        live = _live(out)
        if not live and _first_live(out) is _DONATED:
            live = next(filter(None, (_live(later)
                                      for later, _ in self._inflight)), [])
        try:
            with spans.phase("devmod.inflight_wait"):
                jax.block_until_ready(live)
        except Exception:
            from ..core.output import warning
            self.enabled = False
            warning(f"device {self.name}: dispatch failed; "
                    "disabling the device for subsequent tasks")
            raise

    def sync(self) -> None:
        spans.phase_refresh()
        # self time: dropping the confirmed dispatches, which hold the last
        # references to the intermediate versions of every RW tile
        with spans.phase("devmod.sync"):
            while self._inflight:
                self._confirm_oldest()

    # -------------------------------------------------------- diagnostics
    def debug_state(self) -> dict:
        """Stage-in / pipeline state for the flight-recorder stall dump.
        Lock acquisition is bounded: a dump racing a wedged manager must
        report what it can reach, never block."""
        state = {"name": self.name, "enabled": self.enabled,
                 "executed_tasks": self.executed_tasks,
                 "xla_calls": self.xla_calls,
                 "batched_dispatches": self.batched_dispatches,
                 "tasks_by_class": dict(self.tasks_by_class),
                 "calls_by_class": dict(self.calls_by_class),
                 "call_table": [
                     dict(row, task_class=name, lanes=lanes)
                     for (name, lanes), row in list(self.call_table.items())],
                 "inflight_dispatches": len(self._inflight),
                 "inflight_held_bytes": self._held_bytes,
                 "inflight_held_bytes_peak": self.inflight_held_bytes_peak,
                 "ring_peak": self.ring_peak,
                 "ring_excused": self.ring_excused,
                 "ring_bounded": self.ring_bounded,
                 "donated_results": self.donated_results,
                 "scratch_tiles": sum(map(len, self._scratch.values())),
                 "zero_tiles": sum(map(len, self._zeros.values())),
                 "null_flows_skipped": self.null_flows_skipped,
                 "scratch_bytes": self._scratch_bytes,
                 "pressure_confirms": self.pressure_confirms,
                 "evicted_bytes": self.evicted_bytes,
                 "evict_stuck": self.evict_stuck,
                 "replicas_dropped": self.replicas_dropped,
                 "replica_bytes_dropped": self.replica_bytes_dropped,
                 "invalidated_copies": self.invalidated_copies,
                 "cache_hits": self.cache_hits,
                 "cache_misses": self.cache_misses,
                 "lru_touches": self.lru_touches,
                 "lru_recharged": self.lru_recharged,
                 "bytes_in": self.bytes_in, "bytes_out": self.bytes_out,
                 "bytes_d2d": self.bytes_d2d, "d2d_tiles": self.d2d_tiles,
                 "pushouts": self.pushouts, "writebacks": self.writebacks,
                 "writebacks_early": self.writebacks_early,
                 "flood_selected": self.flood_selected,
                 "flood_putbacks": self.flood_putbacks,
                 "stage_in_s": round(self.t_stage_in, 3),
                 "dispatch_s": round(self.t_dispatch, 3),
                 "complete_s": round(self.t_complete, 3),
                 "drain_s": round(self.t_drain, 3),
                 "writeback_s": round(self.t_writeback, 3)}
        if self._mutex_lock.acquire(timeout=0.2):
            try:
                state["pending_tasks"] = len(self._pending)
                state["managing"] = self._managing
            finally:
                self._mutex_lock.release()
        else:
            state["pending_tasks"] = "<manager lock held>"
        if self._lru_lock.acquire(timeout=0.2):
            try:
                state["lru_tiles"] = len(self._mem_lru)
                state["lru_bytes"] = self._mem_bytes
                state["evict_queue"] = len(self._evict_q)
            finally:
                self._lru_lock.release()
        else:
            state["lru_tiles"] = "<lru lock held>"
        return state


@functools.cache
def _host_put() -> Callable | None:
    """``put(x, jax_device) -> jax.Array | None``: a C-contiguous numpy array
    of a dtype JAX keeps as it is whatever ``jax_enable_x64`` says (at most
    32 bits an element), placed on one device by jaxlib's
    ``batched_device_put``, the call ``jax.device_put`` ends in for such an
    array, with the abstract value and the sharding kept from one tile to
    the next.  The result is what ``jax.device_put(x, device)`` returns
    (committed, ``SingleDeviceSharding``, not weakly typed), so a program
    compiled for the one is the program for the other.  ``put`` returns None
    for anything else, which the caller hands to ``jax.device_put``; this
    function returns None where the installed jaxlib has no such call."""
    try:
        import jax
        from jax.sharding import SingleDeviceSharding
        from jaxlib._jax import batched_device_put
    except ImportError:
        return None
    avals: dict[Any, Any] = {}
    shardings: dict[Any, Any] = {}

    def put(x: Any, jd: Any) -> Any:
        if type(x) is not np.ndarray or not x.flags.c_contiguous:
            return None
        key = (x.shape, x.dtype)
        aval = avals.get(key)
        if aval is None:
            if x.dtype.itemsize > 4 \
                    or jax.dtypes.canonicalize_dtype(x.dtype) != x.dtype:
                return None
            aval = avals[key] = jax.core.ShapedArray(x.shape, x.dtype)
        sharding = shardings.get(jd)
        if sharding is None:
            sharding = shardings[jd] = SingleDeviceSharding(jd)
        return batched_device_put(aval, sharding, [x], [jd])

    return put


# the host-CPU stand-in (device_tpu_allow_cpu) has no HBM to report and no
# MXU to rate: nominal figures, so the budget and the time estimates of a
# CPU rehearsal are defined.  Nothing measured rests on them.
_CPU_STANDIN_BYTES = 16 << 30
_CPU_STANDIN_GFLOPS = (100_000.0, 50_000.0)


def _flop_rating(kind: str) -> tuple[float, float]:
    """Per-chip peak GFLOPS (bf16, fp32) by device kind — the scheduling
    input analog of the CUDA flop-rate table.  An accelerator that is not
    in the table is an error: a made-up rating would steer best-device
    selection and every share-of-peak figure derived from it."""
    table = {
        "tpu v2": (45_000.0, 22_500.0),
        "tpu v3": (123_000.0, 61_500.0),
        "tpu v4": (275_000.0, 137_500.0),
        "tpu v5 lite": (197_000.0, 98_500.0),
        "tpu v5e": (197_000.0, 98_500.0),
        "tpu v5": (459_000.0, 229_500.0),
        "tpu v5p": (459_000.0, 229_500.0),
        "tpu v6 lite": (918_000.0, 459_000.0),
        "tpu v6e": (918_000.0, 459_000.0),
        "cpu": _CPU_STANDIN_GFLOPS,
    }
    for k, v in table.items():
        if kind.startswith(k):
            return v
    raise ValueError(
        f"unknown accelerator device_kind {kind!r}: add its peak to "
        f"device/tpu.py:_flop_rating")


_init_lock = threading.Lock()


def init_tpu_devices() -> list[TPUDevice]:
    """Register every visible accelerator with the device registry
    (cf. per-component ``module_init`` during ``parsec_init``), once per
    process: a JAX device some registered :class:`TPUDevice` already wraps
    is not wrapped again.  ``Context.__init__`` calls this.  On a CPU-only
    backend nothing registers unless ``device_tpu_allow_cpu`` is set.  A
    failing ``jax.devices()`` propagates — a chip that cannot be reached
    is not a host without chips."""
    if not _params.get("device_tpu_enabled"):
        return []
    import jax
    allow_cpu = _params.get("device_tpu_allow_cpu")
    out = []
    with _init_lock:    # in-process ranks build their contexts concurrently
        have = {d.jax_device: d for d in registry.devices
                if isinstance(d, TPUDevice)}
        for jd in jax.devices():
            if jd.platform == "cpu" and not allow_cpu:
                continue
            out.append(have.get(jd) or registry.add(TPUDevice(jd)))
    return out
