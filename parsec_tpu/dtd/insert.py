"""DTD engine: runtime task insertion with discovered dependencies.

Rebuild of ``parsec/interfaces/dtd/insert_function.c`` (SURVEY §2.8, §3.6):

- ``insert_task(body, (tile, INOUT), (x, VALUE), ...)`` — the analog of
  ``parsec_dtd_insert_task`` (``insert_function.h:53-70``): flags describe
  each argument's role; data arguments thread through per-tile
  ``last_writer`` / ``last_user`` accessor records
  (``SET_LAST_ACCESSOR``, ``insert_function_internal.h:55-68``) to discover
  RAW / WAR / WAW edges at insert time.
- ``tile_of(dc, key)`` — per-collection tile table
  (``parsec_dtd_tile_of``, ``insert_function.c:1260``).
- sliding window — when more than ``dtd_window_size`` tasks are in flight the
  inserting thread joins execution until below ``dtd_threshold_size``
  (``parsec_execute_and_come_back``, ``insert_function.c:570``).
- ``data_flush`` — inserts a flush task pushing the final tile version back
  to its home copy/rank (``parsec_dtd_data_flush.c``).
- ``PUSHOUT`` — a written flow inserted with the flag is final when its task
  completes: ``release_task`` has the accelerator that holds the copy start
  its transfer home at once (``scheduling.start_home``, the call a PTG's
  memory edge makes), so the flush only collects it.  An untagged tile pays
  the synchronous read at the flush.

TPU-first notes: a task body may carry a TPU incarnation (a kernel-registry
name) next to the Python host body, exactly like the reference's per-chore
CUDA bodies; in-place mutation works on host numpy tiles, while device/jax
bodies return replacement arrays (functional update — the XLA-native
convention) which the engine writes back to the tile copy.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable

import numpy as np

from ..core.params import params as _params
from ..data.data import (ACCESS_READ, ACCESS_RW, ACCESS_WRITE, DataCopy,
                         data_create)
from ..prof import pins, spans
from ..prof.pins import PinsEvent
from ..runtime.scheduling import schedule_tasks, start_home
from ..runtime.task import (DEV_CPU, DEV_TPU, HOOK_RETURN_DONE, Chore, Flow,
                            Task, TaskClass)
from ..runtime.taskpool import Taskpool

# ---------------------------------------------------------------------------
# argument flags (cf. insert_function.h:53-70; region index in low bits there,
# here region/layout rides on the tile itself)
# ---------------------------------------------------------------------------
INPUT = ACCESS_READ
OUTPUT = ACCESS_WRITE
INOUT = ACCESS_RW
_MODE_MASK = 0x3

VALUE = 0x10        # pass by value (copied at insert time)
SCRATCH = 0x20      # per-task scratch allocation
REF = 0x40          # pass the object reference untracked

AFFINITY = 0x100    # this argument's tile decides the executing rank
DONT_TRACK = 0x200  # do not thread dependencies through this argument
PUSHOUT = 0x400     # this written version is final: start it home at release
PULLIN = 0x800      # accepted and read by nothing: stage-in pulls every tile

_params.register("dtd_window_size", 2048,
                 "max in-flight inserted tasks before the inserter "
                 "joins execution (parsec_dtd_window_size)")
_params.register("dtd_threshold_size", 1024,
                 "in-flight level at which the inserter resumes "
                 "(parsec_dtd_threshold_size)")

_MAX_TASK_CLASSES = 25  # PARSEC_DTD_NB_TASK_CLASSES (insert_function_internal.h:31)

# PINS fast path, as in runtime/scheduling.py: the identity-stable dispatch
# table, so a site nothing listens to is an index load and a branch
_hooks = pins.hooks
_RELEASE_DEPS_BEGIN = int(PinsEvent.RELEASE_DEPS_BEGIN)
_RELEASE_DEPS_END = int(PinsEvent.RELEASE_DEPS_END)

_now = time.perf_counter_ns

# always on, like the device module's counters, per pool (the attributes of
# the same names, less the prefix) and here as the process's totals over the
# pools that have terminated (a benchmark's solves are a pool each):
# tasks inserted, times the window made an inserter execute and come back,
# tasks that had completed when wait() closed the insertion, and written
# flows inserted with PUSHOUT
dtd_totals = {"dtd_inserted": 0, "dtd_window_drives": 0,
              "dtd_tasks_in_window": 0, "dtd_pushouts_flagged": 0}
_totals_lock = threading.Lock()

# concurrency contracts, enforced by analysis.runtimelint (docs/ANALYSIS.md):
# accessor chains mutate under the tile's _lock, per-task dep state under
# the task's _dlock, the tile tables under _tlock, the arrival table under
# _alock, and the in-flight window counter under _icond; the insertion
# sequence is serialized by _insert_lock (helpers annotate `holds`).
# The declared order is outermost-first: the inserter may take chain/task
# locks while holding _insert_lock, never the reverse.
_LOCK_PROTECTED = {
    "DTDTile.last_writer": "_lock",
    "DTDTile.last_users": "_lock",
    "DTDTaskpool._tiles": "_tlock",
    "DTDTaskpool._tiles_by_wire": "_tlock",
    "DTDTaskpool._pending_flush": "_tlock",
    "DTDTaskpool._arrivals": "_alock",
    "DTDTaskpool._insert_seq": "_insert_lock",
    "DTDTaskpool._inflight": "_icond",
    "DTDTaskpool.window_drives": "_icond",
    "DTDTaskpool.inserted": "_insert_lock",
    "DTDTaskpool.pushouts_flagged": "_insert_lock",
    "DTDTask.successors": "_dlock",
    "DTDTask.push_records": "_dlock",
    "DTDTask.deps_pending": "_dlock",
    "DTDTask.completed": "_dlock",
}
_LOCK_ORDER = ("_insert_lock", "_tlock", "_lock", "_dlock", "_alock",
               "_icond")


class Scratch:
    """Scratch-argument descriptor: ``(Scratch(shape, dtype), SCRATCH)``."""

    __slots__ = ("shape", "dtype")

    def __init__(self, shape, dtype=np.float32) -> None:
        self.shape = tuple(shape) if not isinstance(shape, int) else (shape,)
        self.dtype = np.dtype(dtype)


class DTDTile:
    """One trackable datum with its accessor chain (cf. ``parsec_dtd_tile_t``).

    ``last_writer`` / ``last_users`` implement the reference's
    ``SET_LAST_ACCESSOR`` discipline: a new reader depends on the last writer
    and joins ``last_users``; a new writer depends on the last writer (WAW)
    *and* every reader since (WAR), then resets the chain.

    Across ranks the chain contains **shell tasks** for remotely-routed
    insertions (the reference's remote-shell discipline,
    ``insert_function.c:821,866``): shells are inert position markers whose
    data effects are realized by snapshot *pushes* — see
    :meth:`DTDTaskpool._link_tile`.
    """

    __slots__ = ("data", "dc", "key", "last_writer", "last_users", "_lock",
                 "flushed", "wire_key", "_pristine_sent")

    def __init__(self, data: Any, dc: Any = None, key: tuple = ()) -> None:
        self.data = data              # the master Data record
        self.dc = dc                  # owning collection, if any
        self.key = key
        self.last_writer: tuple[DTDTask, int] | None = None
        self.last_users: list[tuple[DTDTask, int]] = []
        self._lock = threading.Lock()
        self.flushed = False
        # rank-stable identity for the wire (collections carry names; bare
        # arrays are process-local and single-rank only)
        self.wire_key: tuple = ((dc.name,) + key if dc is not None
                                else ("arr",) + key)
        self._pristine_sent: set[int] = set()   # dedup of pristine pushes

    @property
    def rank(self) -> int:
        return self.dc.rank_of(*self.key) if self.dc is not None else 0

    def __repr__(self) -> str:
        return f"<DTDTile {self.key or self.data.key}>"


class _ArgSpec:
    __slots__ = ("obj", "flags", "mode", "flow_index")

    def __init__(self, obj: Any, flags: int) -> None:
        self.obj = obj
        self.flags = flags
        self.mode = flags & _MODE_MASK
        self.flow_index = -1   # set for data args


class DTDTask(Task):
    """A dynamically-inserted task with per-instance discovered deps.

    ``dtd_seq`` is the per-taskpool insertion sequence number — identical on
    every rank under SPMD insertion, so it names this task on the wire (raw
    ``uid`` counters are process-global and diverge between in-process rank
    threads).  ``is_shell`` marks a remotely-routed insertion: an inert
    marker in the accessor chains, never scheduled locally.
    """

    __slots__ = ("body", "args", "deps_pending", "successors", "completed",
                 "_dlock", "tiles", "dtd_seq", "is_shell", "rank",
                 "push_records")

    def __init__(self, taskpool: Any, task_class: TaskClass, body: Callable,
                 args: list[_ArgSpec], priority: int = 0) -> None:
        super().__init__(taskpool, task_class, {"uid": 0}, priority=priority)
        self.locals = {"uid": self.uid}
        self.body = body
        self.args = args
        # +1 insertion guard: dropped when all deps are linked (SURVEY §3.6)
        self.deps_pending = 1
        # (successor_task, successor_flow_index) release records
        self.successors: list[tuple[DTDTask, int]] = []
        self.completed = False
        self._dlock = threading.Lock()
        self.tiles: list[DTDTile | None] = [None] * len(task_class.flows)
        self.dtd_seq = -1
        self.is_shell = False
        self.rank = 0
        # (flow_index, dst_rank): snapshot-push the written tile on completion
        self.push_records: set[tuple[int, int]] = set()

    def unpack_args(self) -> list[Any]:
        """``parsec_dtd_unpack_args``: resolved argument values in insert
        order — data/scratch args as arrays, VALUE/REF args as-is."""
        out = []
        for spec in self.args:
            if spec.flags & (VALUE | REF):
                out.append(spec.obj)
            elif spec.flags & SCRATCH:
                out.append(self.data[spec.flow_index])
            else:
                copy = self.data[spec.flow_index]
                out.append(copy.value if copy is not None else None)
        return out


def unpack_args(task: DTDTask) -> list[Any]:
    return task.unpack_args()


class _DTDTaskClass(TaskClass):
    """Dynamic task class (cf. ``parsec_dtd_create_task_class``): flows are
    positional slots; successor iteration walks per-instance records, so the
    class-level guarded-dep machinery is bypassed."""

    def make_key(self, locals_: dict) -> tuple:
        return (locals_["uid"],)

    def iterate_successors(self, task: Task, visitor: Callable) -> None:
        # DTD releases through instance records (complete_hook_of_dtd,
        # insert_function.c:1797); nothing for the generic walker to do.
        return


def _dtd_cpu_hook(es: Any, task: DTDTask) -> int:
    values = task.unpack_args()
    result = task.body(*values)
    _apply_result(task, result)
    return HOOK_RETURN_DONE


def _dtd_prepare_input(es: Any, task: DTDTask) -> None:
    """DTD data lookup: tracked flows carry their copies from insert time;
    SCRATCH flows allocate per-execution temporaries here."""
    for spec in task.args:
        if spec.flags & SCRATCH and task.data[spec.flow_index] is None:
            task.data[spec.flow_index] = np.zeros(spec.obj.shape,
                                                  dtype=spec.obj.dtype)


def _apply_result(task: DTDTask, result: Any) -> None:
    """Functional-update write-back: a body returning a tuple/array replaces
    the values of its written flows in order (jax-style); ``None`` means the
    body mutated host arrays in place."""
    if result is None:
        return
    written = [s for s in task.args
               if s.flow_index >= 0 and not (s.flags & SCRATCH)
               and (s.mode & ACCESS_WRITE)]
    results = result if isinstance(result, (tuple, list)) else (result,)
    if len(results) != len(written):
        raise ValueError(
            f"{task}: body returned {len(results)} values for "
            f"{len(written)} written flows")
    for spec, value in zip(written, results):
        copy = task.data[spec.flow_index]
        copy.value = value


def _dtd_flush_body(arr, tile: "DTDTile") -> None:
    home = tile.data.get_copy(0)
    newest = tile.data.newest_copy()
    if newest is not None and home is not None and newest is not home:
        home.value = np.asarray(newest.value)
        home.version = newest.version
    tile.flushed = True


def _snapshot(value: Any) -> Any:
    """A stable payload for the wire: host arrays are copied (later local
    writers may mutate them in place), device arrays are immutable."""
    from ..comm.device_fabric import is_device_array
    if is_device_array(value):
        return value
    return np.asarray(value).copy()


class _Arrival:
    """One expected cross-rank tile payload, keyed by (tile wire key,
    producing task's insertion seq; -1 = the pristine pre-writer value).

    Local consumer tasks register as waiters; the landing push installs the
    payload as a fresh host copy on the tile's data record (so later chain
    accessors and the flush see it) and releases the waiters.  Landing and
    waiting may happen in either order (a push can outrun the consumer's
    insertion, and a tile may not even exist locally yet when its payload
    lands)."""

    __slots__ = ("value", "version", "copy", "landed", "waiters")

    def __init__(self) -> None:
        self.value = None
        self.version = 0
        self.copy = None          # installed DataCopy (made once, lazily)
        self.landed = False
        self.waiters: list[tuple[DTDTask, int]] = []


class DTDTaskpool(Taskpool):
    """``parsec_dtd_taskpool_new``: a taskpool whose DAG is discovered from
    the insertion order of tasks touching shared tiles."""

    def __init__(self, name: str = "dtd") -> None:
        super().__init__(name=name)
        self._classes: dict[Any, _DTDTaskClass] = {}
        self._tiles: dict[tuple, DTDTile] = {}
        self._tlock = threading.Lock()
        # serializes insert_task: bodies may insert tasks from worker
        # threads (recursive discovery — haar_tree/merge_sort shape), and
        # the seq numbering + accessor-chain splices assume one inserter
        # at a time.  RLock: a body executed from inside the window
        # backpressure drive may itself insert.
        self._insert_lock = threading.RLock()
        self._inflight = 0
        self._icond = threading.Condition()
        self._armed = False
        self._closed = False
        self.window_size = _params.get("dtd_window_size")
        self.threshold_size = _params.get("dtd_threshold_size")
        # what dtd_totals sums (local tasks; shells are not counted)
        self.inserted = 0
        self.window_drives = 0
        self.tasks_in_window: int | None = None    # set by close()
        self.pushouts_flagged = 0
        # -- cross-rank state (shells + push/arrival protocol) --------------
        self._insert_seq = 0
        self._arrivals: dict[tuple, _Arrival] = {}
        self._alock = threading.Lock()
        self._tiles_by_wire: dict[tuple, DTDTile] = {}
        self._pending_flush: dict[tuple, tuple] = {}   # wire -> (value, ver)

    # ------------------------------------------------------------- lifecycle
    def startup(self, context: Any) -> list[Task]:
        # Hold one pending action until wait()/close(): task counts are
        # unknown until the app stops inserting (the DTD termdet discipline,
        # §3.6).  A taskpool fully populated at enqueue (on_enqueue +
        # close()) must not re-arm.
        if not self._closed:
            self.tdm.taskpool_addto_nb_pa(+1)
            self._armed = True
        return []

    def nb_local_tasks(self) -> int:
        return -1

    def close(self) -> None:
        """Declare insertion finished: drops the armed pending action so the
        termination detector may conclude (needed when nobody calls
        :meth:`wait` on this member — e.g. inside ``compose()``)."""
        if not self._closed and _params.get("analysis_check", False):
            # the enqueue-time hook cannot see a DTD graph (it is empty
            # then); end-of-insertion is the first structurally-complete
            # moment (tasks may already have run — checks are read-only)
            self.validate()
        if not self._closed:
            # what of the execution ran under discovery and not after it
            with self._icond:
                self.tasks_in_window = self.inserted - self._inflight
        self._closed = True
        if self._armed:
            self._armed = False
            self.tdm.taskpool_addto_nb_pa(-1)

    def validate(self, nb_ranks: int | None = None,
                 raise_on_error: bool = True) -> Any:
        """Statically verify the discovered structure so far (tile/rank
        bounds, accessor-chain consistency — analysis.graphcheck's DTD
        prong); see :meth:`PTGTaskpool.validate
        <parsec_tpu.ptg.dsl.PTGTaskpool.validate>`."""
        from ..analysis import check_dtd
        report = check_dtd(self, nb_ranks=nb_ranks)
        if raise_on_error:
            report.raise_if_failed()
        return report

    def wait(self, timeout: float | None = None) -> None:
        """``parsec_dtd_taskpool_wait``: no more insertions; drain."""
        self.close()
        super().wait(timeout)

    def terminated(self) -> None:
        super().terminated()
        # every task has run: the accessor chains are the last references to
        # them (each A and B tile's last_users lists every GEMM that read it),
        # and through task.taskpool they close a cycle with this pool, which
        # would keep a solve's 4,096 tasks for the cyclic collector to find
        # (65,695 tracked objects against the PTG twin's 6,121)
        with self._tlock:
            tiles = list(self._tiles.values())
        for tile in tiles:
            with tile._lock:
                tile.last_writer = None
                tile.last_users = []
        with _totals_lock:
            dtd_totals["dtd_inserted"] += self.inserted
            dtd_totals["dtd_window_drives"] += self.window_drives
            dtd_totals["dtd_tasks_in_window"] += self.tasks_in_window or 0
            dtd_totals["dtd_pushouts_flagged"] += self.pushouts_flagged

    # ----------------------------------------------------------------- tiles
    def tile_of(self, dc: Any, *key) -> DTDTile:
        """``parsec_dtd_tile_of``: the unique tile record for ``dc(key)``."""
        k = (id(dc),) + key
        with self._tlock:
            t = self._tiles.get(k)
            if t is None:
                t = DTDTile(dc.data_of(*key), dc=dc, key=key)
                self._tiles[k] = t
                self._tiles_by_wire[t.wire_key] = t
                flush = self._pending_flush.pop(t.wire_key, None)
            else:
                flush = None
        if flush is not None:
            self._apply_flush(t, *flush)
        return t

    def tile_of_array(self, array: Any, key: Any = None) -> DTDTile:
        """Tile over a bare array (tests/small apps; no collection)."""
        k = ("arr", id(array) if key is None else key)
        with self._tlock:
            t = self._tiles.get(k)
            if t is None:
                t = DTDTile(data_create(array, key=k))
                self._tiles[k] = t
            return t

    # -------------------------------------------------------------- classes
    def _class_for(self, body: Callable, specs: list[_ArgSpec],
                   name: str | None, tpu_kernel: str | None) -> _DTDTaskClass:
        # access modes are part of the class identity: the same body inserted
        # with different INPUT/OUTPUT roles must not reuse baked-in flows
        modes = tuple(s.flags & (_MODE_MASK | SCRATCH) for s in specs
                      if not (s.flags & (VALUE | REF)))
        ck = (body, modes, tpu_kernel)
        tc = self._classes.get(ck)
        if tc is not None:
            return tc
        if len(self._classes) >= _MAX_TASK_CLASSES:
            raise RuntimeError(
                f"too many DTD task classes (max {_MAX_TASK_CLASSES})")
        flows = []
        fi = 0
        for s in specs:
            if s.flags & (VALUE | REF):
                continue
            access = ACCESS_RW if s.flags & SCRATCH else s.mode
            flows.append(Flow(f"f{fi}", access))
            fi += 1
        chores = []
        if tpu_kernel is not None:
            from ..device.hooks import make_device_hook
            chores.append(Chore(
                DEV_TPU, hook=make_device_hook(DEV_TPU, None, tpu_kernel),
                dyld=tpu_kernel))
        chores.append(Chore(DEV_CPU, hook=_dtd_cpu_hook))
        tc = _DTDTaskClass(name or getattr(body, "__name__", "dtd_task"),
                           params=["uid"], flows=flows, chores=chores)
        tc.prepare_input = _dtd_prepare_input
        tc.complete_execution = lambda es, t: t.taskpool.release_task(es, t)
        self.add_task_class(tc)
        self._classes[ck] = tc
        return tc

    # --------------------------------------------------------------- insert
    def insert_task(self, body: Callable, *args: Any,
                    name: str | None = None, priority: int = 0,
                    tpu_kernel: str | None = None,
                    _rank: int | None = None) -> DTDTask:
        """``parsec_dtd_insert_task``.  Each argument is either a bare value
        (treated as VALUE) or a tuple ``(obj, flags)``; data arguments are
        :class:`DTDTile` (or arrays, auto-wrapped via :meth:`tile_of_array`).

        Across ranks every rank runs the same insertion program (SPMD, the
        reference discipline): the AFFINITY argument's tile decides the
        executing rank (``insert_function.h:61``; default rank 0), tasks
        routed elsewhere become inert *shells* in the accessor chains, and
        cross-rank dataflow is realized by snapshot pushes keyed by the
        producer's insertion sequence number (see :meth:`_link_tile`).
        """
        if self.context is None:
            raise RuntimeError("taskpool not enqueued in a context")
        # the plane's dtd.insert: a counter per task, no span; the window
        # drive below is dtd.window's
        t0 = _now() if spans.phase_on else 0
        with self._insert_lock:
            task = self._insert_task_locked(body, args, name, priority,
                                            tpu_kernel, _rank)
        if t0:
            spans.phase_add("dtd.insert", _now() - t0)
        # backpressure OUTSIDE the insert lock: a blocked inserter must not
        # stop worker bodies (which may themselves insert) from completing
        # tasks — that would hold _inflight above the threshold forever
        if not task.is_shell:
            self._window_backpressure()
        return task

    def _insert_task_locked(self, body: Callable, args: tuple, name,
                            priority, tpu_kernel,
                            _rank) -> DTDTask:  # lint: holds(_insert_lock)
        multirank = self.context.nb_ranks > 1
        specs: list[_ArgSpec] = []
        for a in args:
            if isinstance(a, tuple) and len(a) == 2 and isinstance(a[1], int):
                obj, flags = a
            else:
                obj, flags = a, VALUE
            if not (flags & (VALUE | SCRATCH | REF)):
                if isinstance(obj, np.ndarray):
                    obj = self.tile_of_array(obj)
                elif not isinstance(obj, DTDTile):
                    raise TypeError(
                        f"data argument must be a DTDTile or ndarray, "
                        f"got {type(obj).__name__}")
                if multirank and obj.dc is None:
                    raise ValueError(
                        "cross-rank DTD needs collection-backed tiles "
                        "(bare arrays have no rank-stable identity)")
            specs.append(_ArgSpec(obj, flags))
        tc = self._class_for(body, specs, name, tpu_kernel)
        task = DTDTask(self, tc, body, specs, priority=priority)
        task.dtd_seq = self._insert_seq = self._insert_seq + 1
        if multirank:
            task.rank = _rank if _rank is not None else next(
                (s.obj.rank for s in specs
                 if s.flags & AFFINITY and isinstance(s.obj, DTDTile)), 0)
            task.is_shell = task.rank != self.context.my_rank
        if not task.is_shell:
            self.tdm.taskpool_addto_nb_tasks(+1)
            self.inserted += 1
            with self._icond:
                self._inflight += 1

        # thread dependencies through each tracked data argument
        fi = 0
        for spec in specs:
            if spec.flags & (VALUE | REF):
                continue
            spec.flow_index = fi
            fi += 1
            if spec.flags & SCRATCH:
                continue
            tile: DTDTile = spec.obj
            task.tiles[spec.flow_index] = tile
            if spec.flags & PUSHOUT and spec.mode & ACCESS_WRITE \
                    and not task.is_shell:
                self.pushouts_flagged += 1
            if spec.flags & DONT_TRACK:
                if not task.is_shell:
                    self._attach_tile_copy(task, spec, tile)
                continue
            self._link_tile(task, spec, tile)

        if task.is_shell:
            return task
        ready = False
        with task._dlock:
            task.deps_pending -= 1  # drop the insertion guard
            ready = task.deps_pending == 0
        if ready:
            task.status = "ready"
            schedule_tasks(self.context._submit_es, [task], 0)
        return task

    def _attach_tile_copy(self, task: DTDTask, spec: _ArgSpec,
                          tile: DTDTile) -> None:
        copy = tile.data.newest_copy()
        if copy is None:
            raise RuntimeError(f"{tile}: no valid copy")
        task.data[spec.flow_index] = copy

    def _link_tile(self, task: DTDTask, spec: _ArgSpec, tile: DTDTile) -> None:
        """The SET_LAST_ACCESSOR walk: register RAW/WAR/WAW edges from the
        tile's previous accessors to ``task``.

        Cross-rank edges (chain positions held by shells) become **snapshot
        pushes** instead of local deps:

        - *local consumer, shell writer*: wait for the writer rank's push,
          keyed by the writer's insertion seq (an :class:`_Arrival`);
        - *local consumer, no writer, remote home*: wait for the owner's
          pristine push (key ``-1``);
        - *shell consumer, local writer*: record a push on the writer — its
          completion snapshots the flow value and ships it (WAR-safe: the
          snapshot is taken before any successor writer is released);
        - *shell consumer, no writer, local home*: push the pristine value
          now (insert-time snapshot — any earlier writer would be in the
          chain, so the home copy is stable; dedup per destination rank).

        Shells in ``last_users`` are skipped by later local writers (no WAR
        edge needed — their data was snapshotted), matching the reference's
        remote-shell handling (``insert_function.c:821,866``).
        """
        me = self.context.my_rank
        needs_data = bool(spec.mode & ACCESS_READ)
        deps: list[DTDTask] = []
        arrival_key: tuple | None = None
        push_on: DTDTask | None = None
        pristine_to: int | None = None
        with tile._lock:
            lw = tile.last_writer
            if not task.is_shell:
                if needs_data:
                    if lw is not None and lw[0].is_shell:
                        arrival_key = (tile.wire_key, lw[0].dtd_seq)
                    elif lw is None and tile.dc is not None \
                            and tile.rank != me:
                        arrival_key = (tile.wire_key, -1)
                if lw is not None and not lw[0].is_shell:
                    deps.append(lw[0])          # RAW / WAW
            else:
                if needs_data:
                    if lw is not None and not lw[0].is_shell:
                        push_on = lw[0]          # push after writer completes
                    elif lw is None and tile.rank == me:
                        pristine_to = task.rank  # push the home value now
            if spec.mode == INPUT:
                tile.last_users.append((task, spec.flow_index))
            else:  # OUTPUT and INOUT both serialize against the chain
                if not task.is_shell:
                    for (u, _) in tile.last_users:   # WAR (local users only)
                        if u is not task and not u.is_shell:
                            deps.append(u)
                tile.last_users = []
                tile.last_writer = (task, spec.flow_index)
            if push_on is not None:
                task_rank = task.rank
                with push_on._dlock:
                    if not push_on.completed:
                        push_on.push_records.add(
                            (lw[1], task_rank))
                        push_on = None   # completion will ship it
        if task.is_shell:
            if push_on is not None:
                # writer already completed: snapshot and ship immediately
                self._send_push(tile, push_on, lw[1], task.rank)
            if pristine_to is not None and pristine_to != me:
                self._send_pristine(tile, pristine_to)
            return
        if arrival_key is not None:
            self._add_waiter(arrival_key, task, spec.flow_index)
        else:
            self._attach_tile_copy(task, spec, tile)
        for pred in deps:
            self._link_dep(pred, task)

    def _link_dep(self, pred: DTDTask, succ: DTDTask) -> None:
        if pred is succ:
            return
        with pred._dlock:
            if not pred.completed:
                with succ._dlock:
                    succ.deps_pending += 1
                pred.successors.append((succ, -1))

    # --------------------------------------------- cross-rank push protocol
    def _send_push(self, tile: DTDTile, writer: DTDTask, flow_index: int,
                   dst: int) -> None:
        """Ship the writer's output for ``tile`` to ``dst`` (keyed by the
        writer's insertion seq — identical on every rank)."""
        copy = writer.data[flow_index]
        self.context.comm_engine.dtd_send(self, dst, {
            "kind": "push", "tile": tile.wire_key, "writer": writer.dtd_seq,
            "value": _snapshot(copy.value), "version": copy.version})

    def _send_pristine(self, tile: DTDTile, dst: int) -> None:
        """Push the pre-writer home value of a tile this rank owns."""
        if dst in tile._pristine_sent:
            return
        tile._pristine_sent.add(dst)
        home = tile.data.newest_copy()
        self.context.comm_engine.dtd_send(self, dst, {
            "kind": "push", "tile": tile.wire_key, "writer": -1,
            "value": _snapshot(home.value), "version": home.version})

    def _install_arrival_locked(self, tile: DTDTile, arr: _Arrival) -> DataCopy:
        """Materialize a landed payload as a *new* host copy on the tile's
        data record (replacing the stale mirror if the version advanced —
        earlier local readers keep their old copy object untouched, so a
        late-landing push cannot leak a future value into them)."""
        if arr.copy is not None:
            return arr.copy
        d = tile.data
        copy = DataCopy(d, 0, value=arr.value, dtt=d.get_copy(0).dtt
                        if d.get_copy(0) is not None else None)
        copy.version = arr.version
        cur = d.get_copy(0)
        if cur is None or cur.version < copy.version:
            d.attach_copy(copy)
        arr.copy = copy
        arr.value = None
        return copy

    def _add_waiter(self, key: tuple, task: DTDTask, flow_index: int) -> None:
        """Block ``task``'s flow on a cross-rank arrival (or attach it
        immediately if the push already landed).

        The pending-dep is raised *before* the waiter becomes visible: a
        push landing between publication and the raise would otherwise
        decrement first and schedule the half-linked task (the insertion
        guard alone does not order against the comm thread)."""
        with task._dlock:
            task.deps_pending += 1
        with self._alock:
            arr = self._arrivals.get(key)
            if arr is None:
                arr = self._arrivals[key] = _Arrival()
            if arr.landed:
                task.data[flow_index] = self._install_arrival_locked(
                    task.tiles[flow_index], arr)
            else:
                arr.waiters.append((task, flow_index))
                return
        # already landed: retract the provisional dep (the insertion guard
        # is still held, so this cannot reach zero / schedule)
        with task._dlock:
            task.deps_pending -= 1

    def _land_arrival(self, key: tuple, value: Any, version: int) -> None:
        with self._tlock:
            tile = self._tiles_by_wire.get(key[0])
        with self._alock:
            arr = self._arrivals.get(key)
            if arr is None:
                arr = self._arrivals[key] = _Arrival()
            if arr.landed:
                return   # duplicate delivery
            arr.value, arr.version, arr.landed = value, version, True
            if tile is None and arr.waiters:
                # waiters imply the tile exists locally (linked via tile_of)
                t0, fi0 = arr.waiters[0]
                tile = t0.tiles[fi0]
            copy = (self._install_arrival_locked(tile, arr)
                    if tile is not None else None)
            waiters, arr.waiters = arr.waiters, []
        ready = []
        for (t, fi) in waiters:
            t.data[fi] = copy
            with t._dlock:
                t.deps_pending -= 1
                if t.deps_pending == 0:
                    t.status = "ready"
                    ready.append(t)
        if ready:
            schedule_tasks(self.context._submit_es, ready, 0)

    def _apply_flush(self, tile: DTDTile, value: Any, version: int) -> None:
        home = tile.data.get_copy(0)
        home.value = value
        home.version = max(home.version, version)
        tile.flushed = True

    def _on_dtd_message(self, rde: Any, src: int, msg: dict) -> None:
        """Receive a cross-rank DTD message (dispatched by
        :meth:`~parsec_tpu.comm.remote_dep.RemoteDepEngine._on_dtd`)."""
        wire = tuple(msg["tile"])
        if msg["kind"] == "push":
            self._land_arrival((wire, msg["writer"]), msg["value"],
                               msg["version"])
            return
        if msg["kind"] == "flush":
            with self._tlock:
                tile = self._tiles_by_wire.get(wire)
                if tile is None:
                    # tile not materialized here yet: apply at tile_of time
                    self._pending_flush[wire] = (msg["value"], msg["version"])
                    return
            self._apply_flush(tile, msg["value"], msg["version"])
            return
        raise ValueError(f"unknown DTD message kind {msg['kind']!r}")

    # ------------------------------------------------------------ completion
    def release_task(self, es: Any, task: DTDTask) -> None:
        """``complete_hook_of_dtd`` → ``dtd_release_dep_fct``: bump written
        tile versions, start a ``PUSHOUT`` flow's transfer home where its
        copy lies on an accelerator, ship cross-rank pushes, release instance
        successors, notify the window.  Pushes snapshot *before* successors
        are released — a successor writer mutating the host tile in place
        cannot corrupt an in-flight payload (the WAR discipline of the shell
        protocol)."""
        h = _hooks[_RELEASE_DEPS_BEGIN]
        if h is not None:
            h(es, task)
        for spec in task.args:
            if spec.flow_index < 0 or spec.flags & SCRATCH:
                continue
            if spec.mode & ACCESS_WRITE:
                copy = task.data[spec.flow_index]
                if copy is not None:
                    copy.version += 1
                    if spec.flags & PUSHOUT:
                        start_home(self.context, copy)
        with task._dlock:
            task.completed = True
            succs = list(task.successors)
            task.successors.clear()
            pushes = sorted(task.push_records)
            task.push_records.clear()
        for (fi, dst) in pushes:
            self._send_push(task.tiles[fi], task, fi, dst)
        ready = []
        for (succ, _) in succs:
            with succ._dlock:
                succ.deps_pending -= 1
                if succ.deps_pending == 0:
                    succ.status = "ready"
                    ready.append(succ)
        h = _hooks[_RELEASE_DEPS_END]
        if h is not None:
            h(es, task)
        if ready:
            schedule_tasks(es, ready, 0)
        with self._icond:
            self._inflight -= 1
            self._icond.notify_all()

    # --------------------------------------------------------------- window
    def _window_backpressure(self) -> None:
        """``parsec_execute_and_come_back``: above ``window_size`` in-flight
        tasks the inserter pitches in (no workers), blocks (external
        thread with workers), or — when the inserter IS a worker running a
        task body (recursive discovery) — executes-and-comes-back on its
        own stream: parking it would strand its unfinished task, and with
        every worker inserting at once nothing could ever drain.  Each
        engagement is one ``dtd.window`` span of the phase plane and one
        ``window_drives``."""
        if self._inflight <= self.window_size:
            return
        with self._icond:
            self.window_drives += 1
        with spans.phase("dtd.window"):
            self._execute_and_come_back()

    def _execute_and_come_back(self) -> None:
        ctx = self.context
        if not ctx.started:
            # insertion demands progress: release parked workers (the
            # execute-and-come-back contract cannot hold otherwise)
            ctx.start()
        if ctx._threads:
            ident = threading.get_ident()
            es = next((s for s in ctx.streams if s.owner_ident == ident),
                      None)
            if es is not None:
                # worker-thread inserter: drive tasks instead of parking
                from ..runtime.scheduling import (select_task,
                                                  task_progress)
                while self._inflight > self.threshold_size:
                    t, distance = select_task(es)
                    if t is None:
                        return   # nothing runnable here; don't spin
                    task_progress(es, t, distance)
                return
            with self._icond:
                self._icond.wait_for(
                    lambda: self._inflight <= self.threshold_size)
        else:
            ctx._drive_until(
                lambda: self._inflight <= self.threshold_size)

    # ---------------------------------------------------------------- flush
    def data_flush(self, tile: DTDTile) -> None:
        """``parsec_dtd_data_flush``: insert a task after every current
        accessor that writes the final version back to the tile's home.

        One shared task class serves every flush (the tile rides as an
        untracked REF arg) — flushes must not consume class slots.

        Across ranks the flush runs on the rank of the tile's last writer
        (data-local) and ships the final version to the home rank when they
        differ (``parsec_dtd_data_flush.c``'s push-to-owner)."""
        if self.context is None or self.context.nb_ranks <= 1 \
                or tile.dc is None:
            self.insert_task(_dtd_flush_body, (tile, INPUT), (tile, REF),
                             name="dtd_flush")
            return
        with tile._lock:
            lw = tile.last_writer
        flush_rank = lw[0].rank if lw is not None else tile.rank
        self.insert_task(self._flush_remote_body, (tile, INPUT), (tile, REF),
                         name="dtd_flush", _rank=flush_rank)

    def _flush_remote_body(self, arr: Any, tile: DTDTile) -> None:
        if tile.rank == self.context.my_rank:
            _dtd_flush_body(arr, tile)
            return
        newest = tile.data.newest_copy()
        self.context.comm_engine.dtd_send(self, tile.rank, {
            "kind": "flush", "tile": tile.wire_key,
            "value": _snapshot(newest.value), "version": newest.version})
        tile.flushed = True

    def data_flush_all(self) -> None:
        """``parsec_dtd_data_flush_all`` over every tile seen so far."""
        with self._tlock:
            tiles = list(self._tiles.values())
        for t in tiles:
            self.data_flush(t)
